#!/usr/bin/env python3
"""Split one run's window by the program's spans.

    python3 bench/tools/span_split.py --workload <name> --seed <n> \\
        --seconds 20 --trace <0|1> [--cost-spans 200000]

Runs the cell once, as ``bench/run.py`` does, then reads the ``engine.*``
spans the program recorded in the window (``harness/program_spans.py``)
and prints one JSON line:

- ``line``: the run's own result line;
- ``host_ms_per_step``: each span name's summed duration over the
  window's steps, and ``self_ms_per_step`` the top spans' own time (what
  none of their children covers);
- traced: ``idle_s``, the device idle in the window by leaf span, with
  ``engine`` for the idle under an engine span's own time and ``outside
  the engine`` for the rest, beside the harness's ``bench.*`` attribution
  (``bench_idle_s``) and the skew of the two clock anchors;
- ``--cost-spans n``: n empty spans timed with the profiler off, less the
  bare loop: the recorder's cost per span, and per step at the window's
  spans per step.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def span_cost_us(n: int) -> float:
    from repro.serving import obs
    tr = obs.Tracer()
    t = time.perf_counter()
    for _ in range(n):
        with tr.span("cost"):
            pass
    spent = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(n):
        pass
    return 1e6 * (spent - (time.perf_counter() - t)) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cost-spans", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from harness import program_spans as PS
    from harness.cell import run_cell
    from harness.spec import Metric, load_cell
    from repro.serving import obs
    jax.config.update("jax_compilation_cache_dir",
                      str(BENCH.parent / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform == "cpu":
        print("span_split: needs an accelerator", file=sys.stderr)
        return 2
    cell = load_cell(args.workload, BENCH.parent)
    seen = {}

    def keep(run):              # a traced run's reduced trace and record
        seen.update(run)
    cell.per_layer.append(Metric("span_split", "-", "lower", "-", keep))
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    run = seen or {"rec": out["rec"], "trace": None}

    spans = PS.window_spans(run) or []
    steps = len(PS.top_steps(spans)) or 1
    host, own = {}, {}
    for s in spans:
        host[s.name] = host.get(s.name, 0.0) + s.seconds
        if s.parent is None:
            own[s.name] = (own.get(s.name, 0.0)
                           + obs.TRACER.self_time(s, among=spans))
    res = {"workload": args.workload, "seed": args.seed,
           "trace": args.trace, "line": out["line"],
           "window_s": run["rec"].window_s, "steps": steps,
           "spans": len(spans),
           "host_ms_per_step": {k: 1e3 * v / steps for k, v in host.items()},
           "self_ms_per_step": {k: 1e3 * v / steps for k, v in own.items()},
           "step_host_ms": PS.step_host_ms(run)}
    if args.trace:
        red, rec = run["trace"], run["rec"]
        res["anchor_skew_ms"] = 1e3 * ((red["lo"] - rec.t0)
                                       - (red["hi"] - rec.t_end))
        res["idle_s"] = PS.idle_split(run)
        res["idle_host_ms"] = PS.idle_host_ms(run)
        res["bench_idle_s"] = dict(red["idle_gaps"])
        res["window_idle_s"] = red["window_s"] - red["busy_s"]
    if args.cost_spans:
        us = span_cost_us(args.cost_spans)
        res["span_cost_us"] = us
        res["span_cost_us_per_step"] = us * len(spans) / steps
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
