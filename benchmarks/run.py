"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Scale via
``REPRO_BENCH_SCALE`` ∈ {"ci" (default), "full"}.  The roofline summary reads
``results/dryrun.jsonl`` if the multi-pod dry-run has been executed.

Each figure runs in a child process (XLA:CPU's JIT code cache exhausts
after many compilations in one process).  The parent never imports JAX, so
on an accelerator only one process at a time holds the device; the first
child trains and caches the shared system, later children reload it.  A
figure that fails fails the run (non-zero exit) after the others finish.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

MODULES = ("fig2_onboard", "fig3_redundancy", "fig4_connectivity",
           "fig9_overall", "fig10_offload", "fig11_progressive",
           "fig12_multiscale")


def csv_row(name: str, seconds: float, derived: str) -> str:
    return f"{name},{seconds*1e6:.0f},{derived}"


def run_one(name: str) -> None:
    """Run a single figure module inline, in this child process."""
    import importlib
    from benchmarks.common import get_bundle
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bundle = get_bundle()
    mod = importlib.import_module(f"benchmarks.{name}")
    for row in mod.run(bundle):
        print(csv_row(*row), flush=True)


def main() -> int:
    t_all = time.time()
    if len(sys.argv) > 2 and sys.argv[1] == "--module":
        run_one(sys.argv[2])
        return 0
    print("name,us_per_call,derived")

    env = dict(os.environ)
    env["PYTHONPATH"] = "src:."
    failed = []
    for name in MODULES:
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--module", name],
            capture_output=True, text=True, env=env)
        out = proc.stdout.strip()
        if out:
            print(out, flush=True)
        if proc.returncode != 0:
            failed.append(name)
            print(csv_row(f"{name}_SUBPROC_ERROR", time.time() - t0,
                          proc.stderr.strip()[-200:].replace("\n", " ")),
                  flush=True)

    # roofline summary (from the dry-run artifact, if present)
    try:
        from benchmarks import roofline
        rows = roofline.load_rows("results/dryrun.jsonl", "16x16")
        for r in rows:
            print(csv_row(
                f"roofline_{r['arch']}_{r['shape']}", 0.0,
                f"compute={r['compute_s']*1e3:.2f}ms;"
                f"memory={r['memory_s']*1e3:.2f}ms;"
                f"collective={r['collective_s']*1e3:.2f}ms;"
                f"bottleneck={r['dominant']};"
                f"frac={r['roofline_fraction']*100:.1f}%"), flush=True)
    except FileNotFoundError:
        print(csv_row("roofline_SKIPPED", 0.0,
                      "run repro.launch.dryrun first"), flush=True)

    print(csv_row("total_wall", time.time() - t_all,
                  f"failed: {','.join(failed)}" if failed
                  else "benchmarks complete"), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
