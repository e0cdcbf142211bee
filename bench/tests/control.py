#!/usr/bin/env python3
"""Readings for the limits of ``correct``: on each seed, one short run of a
cell at its own load, and at the same served prompts and tokens the widest
logit gap of the program and of the control (the reference computed with
every matmul in fp8, the precision below the configuration's bf16).

    python3 bench/tests/control.py --workload sat2b-det-bulk \\
        --seeds 1,2,3 --seconds 8

One process holds the chip for every seed; the program is built anew from
each seed.  ``--kv-dtype fp8`` runs the program with its own fp8 KV pages,
to read how far that path lies from the reference; ``--fault token``
plants a fault from ``faults.py``.  Prints one JSON line per seed:
``{"seed", "program": {...}, "fp8": {...}, "correct"}`` with each reading
of ``harness/check.py``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))


def readings(cell, seeds, seconds, overrides=None, fault=None):
    from faults import FAULTS
    from harness.cell import run_cell
    for seed in seeds:
        out = run_cell(cell, seed, seconds, False, time.perf_counter(),
                       overrides=overrides, controls=("fp8",),
                       mutate=FAULTS[fault] if fault else None)
        yield {"seed": seed, **out["readings"],
               "correct": out["line"]["correct"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--kv-dtype", default=None)
    ap.add_argument("--fault", default=None, choices=("token",))
    args = ap.parse_args(argv)
    import jax
    from harness.spec import load_cell
    jax.config.update("jax_compilation_cache_dir",
                      str(BENCH.parent / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = load_cell(args.workload, BENCH.parent)
    ov = {"kv_dtype": args.kv_dtype} if args.kv_dtype else None
    for r in readings(cell, [int(s) for s in args.seeds.split(",")],
                      args.seconds, ov, args.fault):
        r["kv_dtype"], r["fault"] = args.kv_dtype, args.fault
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
