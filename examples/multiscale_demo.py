"""Eq. 2 / Eq. 3 walkthrough: score regions, filter, count bytes.

    PYTHONPATH=src python examples/multiscale_demo.py

Prints an ASCII region-score map for a detection sample, the per-region
decision (discard / downsample level / preserve), and the transmission
ledger — the paper's Fig. 7/12c in text form.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from repro.core import eo_adapter as EO
from repro.core import pipeline as P
from repro.core import preprocess as PP
from repro.core import region_attention as RA
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    bundle = P.build_system(scale="small", n_train=160, n_test=32,
                            proxy_steps=120, conf_steps=80, seed=0,
                            tasks=("det",))
    ac = bundle.adapter_cfg
    data = bundle.datasets["det"]
    images = jnp.asarray(data["images"][:4])
    prompts = jnp.asarray(data["prompts"][:4])

    rf = EO.encode_regions(bundle.sat.params, ac, images)
    tf = EO.encode_text(bundle.sat.params, bundle.sat.cfg,
                        ac.prompt_token("det", prompts))
    raw, norm = RA.score_regions(rf[:, :, None, :], tf)
    regions = synthetic.regions_of(images, ac.grid)
    filt, txb, meta = PP.multiscale_filter(regions, norm)

    for s in range(2):
        print(f"\n== sample {s} (target class {int(prompts[s])}) ==")
        score = np.asarray(norm[s]).reshape(ac.grid, ac.grid)
        rel = np.asarray(data["region_rel"][s]).reshape(ac.grid, ac.grid)
        lvl = np.asarray(meta["levels"][s]).reshape(ac.grid, ac.grid)
        drop = np.asarray(meta["discarded"][s]).reshape(ac.grid, ac.grid)
        print("Eq.2 scores (× = ground-truth relevant region):")
        for r in range(ac.grid):
            print("  " + " ".join(
                f"{score[r, c]:.2f}{'×' if rel[r, c] else ' '}"
                for c in range(ac.grid)))
        print("Eq.3 decisions (D=discard, digit=downsample level, K=keep):")
        for r in range(ac.grid):
            row = []
            for c in range(ac.grid):
                if drop[r, c]:
                    row.append("D")
                elif lvl[r, c] == 1:
                    row.append("K")
                else:
                    row.append(str(int(lvl[r, c])))
            print("  " + " ".join(row))
        print(f"bytes: {float(txb[s]):.0f} / "
              f"{float(meta['full_bytes'][s]):.0f} "
              f"(compression {float(meta['compression_ratio'][s]):.1f}:1)")


if __name__ == "__main__":
    main()
