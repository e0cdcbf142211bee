"""Shared benchmark substrate: one trained two-tier system, disk-cached.

``REPRO_BENCH_SCALE`` ∈ {"ci" (default), "full"} controls training budget.
The trained tiers + confidence net are cached under results/system_<scale>/
so the Fig. 9–12 benchmarks reuse them.
"""
from __future__ import annotations

import os
import time
from typing import Dict

import numpy as np

from repro.core import pipeline as P
from repro.train import checkpoint as CK

SCALES = {
    "ci": dict(n_train=512, n_test=160, proxy_steps=420, conf_steps=300),
    "full": dict(n_train=1536, n_test=384, proxy_steps=1200, conf_steps=500),
}


def bench_scale() -> str:
    return os.environ.get("REPRO_BENCH_SCALE", "ci")


def get_bundle(force: bool = False) -> P.SystemBundle:
    scale = bench_scale()
    kw = SCALES[scale]
    cache = f"results/system_{scale}"
    bundle = None
    if not force and CK.latest_step(cache) is not None:
        bundle = _load_cached(cache, kw)
    if bundle is None:
        t0 = time.time()
        bundle = P.build_system(scale="small", seed=0, **kw)
        print(f"# trained system in {time.time()-t0:.0f}s "
              f"(scale={scale})", flush=True)
        state = {"sat": bundle.sat.params, "gs": bundle.gs.params,
                 "conf": bundle.conf_params}
        CK.save(cache, 1, state)
    return bundle


def _load_cached(cache: str, kw: Dict) -> P.SystemBundle | None:
    """Rebuild the bundle around cached weights (datasets are seeded)."""
    try:
        import jax
        from repro.configs.spaceverse_pair import proxy_pair
        from repro.core import eo_adapter as EO
        from repro.core.cascade import CascadeConfig, TierModel
        from repro.core.confidence import init_confidence
        from repro.core.latency import LatencyModel
        from repro.data import synthetic

        sat_cfg, gs_cfg = proxy_pair("small")
        ac = EO.EOAdapterConfig()
        like = {
            "sat": EO.init_adapter(jax.random.PRNGKey(0), sat_cfg, ac),
            "gs": EO.init_adapter(jax.random.PRNGKey(1), gs_cfg, ac),
            "conf": init_confidence(jax.random.PRNGKey(2),
                                    sat_cfg.d_model, sat_cfg.d_model,
                                    hidden=64, num_stages=2),
        }
        state, _ = CK.restore(cache, like)
        eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                        num_classes=ac.num_classes)
        tasks = P.TASKS
        test = {t: synthetic.make_dataset(t, kw["n_test"], seed=100 + i,
                                          cfg=eo_cfg)
                for i, t in enumerate(tasks)}
        train = {t: synthetic.make_dataset(t, kw["n_train"], seed=0 + i,
                                           cfg=eo_cfg)
                 for i, t in enumerate(tasks)}
        cc = CascadeConfig(answer_vocab=max(ac.num_classes + 1, 2))
        return P.SystemBundle(
            sat=TierModel(state["sat"], sat_cfg),
            gs=TierModel(state["gs"], gs_cfg),
            adapter_cfg=ac, conf_params=state["conf"], cascade_cfg=cc,
            latency=LatencyModel(), datasets=test, train_datasets=train,
            history={})
    except Exception as e:
        print(f"# cache load failed ({e}); retraining", flush=True)
        return None
