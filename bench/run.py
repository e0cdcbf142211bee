#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, limits and per-layer readers are files under
``bench/`` found by name (``bench/README.md`` shows how to add each).
Everything runs in this one process, the only one that touches JAX.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Either way the run
checks what the timed path served against the plain reference and prints
each compared number beside its limit, on the last lines of standard error
and under ``check`` in the result.  The last line of standard output is the
result.  With no accelerator, or fewer chips than the cell asks for, the
run exits 2 and prints no result.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``, so
only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.spec import load_cell
    cell = load_cell(args.workload, ROOT)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform == "cpu":
        print(f"bench: needs an accelerator; JAX found only "
              f"{devs[0].device_kind!r}", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2

    from harness.cell import run_cell
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    line = out["line"]
    for name, c in line["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
