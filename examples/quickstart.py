"""Quickstart: train a tiny two-tier system and run the SpaceVerse cascade.

    PYTHONPATH=src python examples/quickstart.py

Trains laptop-scale satellite/GS proxy LVLMs on synthetic Earth-observation
tasks (~1 min on CPU), fits the progressive confidence network on a 5 %
split, then answers a batch of classification queries through Algorithm 1 —
printing, per sample, where it exited, what was transmitted, and the
latency ledger at the paper's deployment scale (Qwen2-VL-2B/7B, Starlink
link).
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import pipeline as P
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    print("== training tiers + confidence network (tiny scale) ==")
    bundle = P.build_system(scale="small", n_train=192, n_test=64,
                            proxy_steps=150, conf_steps=150, seed=0,
                            tasks=("vqa", "cls"))
    sv = bundle.spaceverse()

    task = "cls"
    data = bundle.datasets[task]
    out = sv.run_batch(task, data["images"][:16], data["prompts"][:16])

    print(f"\n== cascade decisions ({task}) ==")
    off = np.asarray(out["offload"])
    stage = np.asarray(out["exit_stage"])
    for i in range(16):
        route = (f"offload@stage{stage[i]+1}" if off[i] else "onboard")
        print(f"sample {i:2d}: conf={np.asarray(out['conf_scores'])[i]} "
              f"→ {route:16s} tx={out['tx_bytes'][i]/1e6:6.2f}MB "
              f"latency={out['latency_s'][i]:.3f}s")

    res = sv.evaluate(task, data)
    print(f"\n{task}: performance={res['performance']:.3f} "
          f"mean latency={res['latency_s']:.3f}s "
          f"offload rate={res['offload_rate']:.2f}")


if __name__ == "__main__":
    main()
