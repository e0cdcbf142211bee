#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest scene rate at which the
backlog of requests that are due but not yet admitted does not grow over a
run.  One process, one engine, one rate after another.

    python3 bench/tools/knee_sweep.py --workload sat2b-vqa-fanout \\
        --seed 1 --seconds 51 --rates 1,2,4,8 [--bisect 2]

For each rate the cell's traffic mix runs with ``scene_period_s = 1/rate``
on fresh scenes (its history served first, as in a run), for ``--seconds``;
the backlog is sampled at every loop iteration.  A rate holds when the
backlog does not grow: its mean over the run's last third is at most
1.25 times its mean over the first third, plus 2 requests.  The sweep
then bisects between the highest rate that held and the lowest that did
not.  Prints one JSON line per rate and a
last line with the knee.  This tool sets the cell's rate once; the
benchmark's runs never search for one.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def one_rate(core, ac, cell, seed, rate, seconds, first_scene, a):
    from harness import drive, system, traffic
    mix = dict(cell.traffic, scene_period_s=1.0 / rate)
    sched = traffic.open_schedule(mix, seed, seconds, a)
    reqs, next_scene = [], first_scene
    for q in sched:
        q.scene += first_scene
        next_scene = max(next_scene, q.scene + 1)
        reqs.append((q.t, system.request(
            q, traffic.scene_image(seed, q.scene, a), ac)))
    drive.serve_until_idle(core, [r for t, r in reqs if t < 0],
                           lambda r, tk: None)
    sched = [(t, r) for t, r in reqs if t >= 0]
    due = collections.deque()
    samples, ttft, arr = [], [], {}
    i, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        now = time.perf_counter() - t0
        while i < len(sched) and sched[i][0] <= now:
            arr[sched[i][1].request_id] = t0 + sched[i][0]
            due.append(sched[i][1])
            i += 1
        samples.append((now, len(due)))
        k = min(len(due), len(core.free_slots()))
        if k:
            core.admit_many([due.popleft() for _ in range(k)])
        if core.active_count():
            for r, _ in core.step():
                ttft.append(time.perf_counter() - arr[r.request_id])
        elif i < len(sched):
            time.sleep(max(t0 + sched[i][0] - time.perf_counter(), 0.0))
    while core.active_count():                 # leave the engine idle
        core.step()
    third = seconds / 3
    first = [n for t, n in samples if t < third]
    last = [n for t, n in samples if t >= 2 * third]
    mean = lambda xs: sum(xs) / max(len(xs), 1)           # noqa: E731
    holds = mean(last) <= 1.25 * mean(first) + 2
    ttft.sort()
    return {"rate_scenes_per_s": rate, "holds": holds,
            "backlog_first_third": mean(first),
            "backlog_last_third": mean(last), "backlog_end": len(due),
            "answered": len(ttft),
            "ttft_p50_ms": 1e3 * ttft[len(ttft) // 2] if ttft else None,
            "ttft_p95_ms": (1e3 * ttft[int(0.95 * (len(ttft) - 1))]
                            if ttft else None)}, next_scene


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--bisect", type=int, default=2)
    args = ap.parse_args(argv)

    import jax
    from harness import system
    from harness import weights as W
    from harness.spec import load_cell
    jax.config.update("jax_compilation_cache_dir",
                      str(BENCH.parent / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = load_cell(args.workload, BENCH.parent)
    if cell.traffic["loop"] != "open":
        raise SystemExit("the knee is defined for open-loop mixes only")
    a = W.arch(cell.config)
    core, ac = system.build(cell.config, args.seed)
    core.warmup()
    results, scene = [], 0

    def run(rate):
        nonlocal scene
        res, scene = one_rate(core, ac, cell, args.seed, rate, args.seconds,
                              scene, a)
        print(json.dumps(res), flush=True)
        results.append(res)
        return res["holds"]

    for r in [float(x) for x in args.rates.split(",")]:
        if not run(r):
            break
    for _ in range(args.bisect):
        held = [x["rate_scenes_per_s"] for x in results if x["holds"]]
        failed = [x["rate_scenes_per_s"] for x in results if not x["holds"]]
        if not held or not failed:
            break
        run((max(held) + min(failed)) / 2)
    held = [x["rate_scenes_per_s"] for x in results if x["holds"]]
    print(json.dumps({"knee_scenes_per_s": max(held) if held else None,
                      "seconds": args.seconds, "seed": args.seed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
