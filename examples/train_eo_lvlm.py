"""Training driver: pre-train both LVLM tiers on synthetic EO tasks with the
full training runtime (AdamW, grad accumulation, gradient compression,
async checkpointing + resume), then fit the confidence network.

    PYTHONPATH=src python examples/train_eo_lvlm.py --scale small --steps 200
    PYTHONPATH=src python examples/train_eo_lvlm.py --scale example   # ~110M GS tier

``--scale example`` trains the ~110M-parameter GS proxy — a few hundred steps
is hours on this CPU container but the intended few-hundred-step run on real
hardware; ``small`` (default) completes in minutes and exercises every code
path including checkpoint-restart.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import jax
import numpy as np

from repro.configs.spaceverse_pair import proxy_pair
from repro.core import eo_adapter as EO
from repro.core import pipeline as P
from repro.core.cascade import TierModel
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.train import checkpoint as CK
from repro.train import compression as GC
from repro.train import optimizer as O


def train_tier(name, cfg, adapter_cfg, train_data, steps, lr, ckpt_dir,
               batch_size=16, compress=False):
    """Multi-task training with checkpoint/resume + the full opt stack."""
    key = jax.random.PRNGKey(hash(name) % 2 ** 31)
    params = EO.init_adapter(key, cfg, adapter_cfg)
    opt_cfg = O.OptConfig(lr=lr, warmup_steps=max(steps // 20, 5),
                          total_steps=steps, weight_decay=0.0)
    opt_state = O.init_opt_state(params)
    err = GC.init_error_state(params) if compress else None
    comp_cfg = GC.CompressionConfig(scheme="int8") if compress else None

    start = 0
    if CK.latest_step(ckpt_dir) is not None:
        state, start = CK.restore(ckpt_dir, {"p": params, "o": opt_state})
        params, opt_state = state["p"], state["o"]
        print(f"[{name}] resumed from step {start}")

    from repro.models import transformer as T

    def loss_fn(params, batch):
        mb = {k: v for k, v in batch.items() if k != "raw_regions"}
        mb["patch_embeds"] = batch["raw_regions"] @ params["patch_proj"]
        return T.loss_fn(params["backbone"], cfg, mb, remat=False)

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, grads

    ck = CK.AsyncCheckpointer(ckpt_dir, keep=2)
    tasks = list(train_data)
    dtype = params["patch_proj"].dtype
    t0 = time.time()
    for s in range(start, steps):
        task = tasks[s % len(tasks)]
        key, sub = jax.random.split(key)
        n = train_data[task]["images"].shape[0]
        idx = np.asarray(jax.random.permutation(sub, n)[:batch_size])
        batch = P._task_batch(adapter_cfg, task, train_data[task], idx, dtype)
        loss, grads = step_fn(params, opt_state, batch)
        if compress:
            grads, err = GC.compress_grads(grads, err, comp_cfg)
        params, opt_state, stats = O.apply_updates(params, grads, opt_state,
                                                   opt_cfg)
        if (s + 1) % 50 == 0 or s + 1 == steps:
            ck.save_async(s + 1, {"p": params, "o": opt_state})
            print(f"[{name}] step {s+1}/{steps} loss={float(loss):.4f} "
                  f"lr={float(stats['lr']):.2e} "
                  f"({(time.time()-t0)/(s-start+1):.2f}s/step)", flush=True)
    ck.wait()
    return params


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["small", "example"], default="small")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--n-train", type=int, default=384)
    ap.add_argument("--ckpt", default="results/eo_lvlm_ckpt")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    sat_cfg, gs_cfg = proxy_pair(args.scale)
    print(f"tiers: W^s={sat_cfg.param_count()/1e6:.1f}M params, "
          f"W^g={gs_cfg.param_count()/1e6:.1f}M params")
    ac = EO.EOAdapterConfig()
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    train = {t: synthetic.make_dataset(t, args.n_train, seed=i, cfg=eo_cfg)
             for i, t in enumerate(P.TASKS)}
    test = {t: synthetic.make_dataset(t, 96, seed=100 + i, cfg=eo_cfg)
            for i, t in enumerate(P.TASKS)}

    sat_p = train_tier("sat", sat_cfg, ac, train, args.steps, 3e-3,
                       args.ckpt + "/sat", compress=args.compress_grads)
    gs_p = train_tier("gs", gs_cfg, ac, train, int(args.steps * 1.5), 2e-3,
                      args.ckpt + "/gs", compress=args.compress_grads)
    sat, gs = TierModel(sat_p, sat_cfg), TierModel(gs_p, gs_cfg)

    print("== fitting progressive confidence network (5% split) ==")
    conf, losses = P.train_confidence_net(sat, gs, ac, train, 9,
                                          steps=300)
    print(f"conf loss {losses[0]:.4f} → {losses[-1]:.4f}")

    from repro.core.cascade import CascadeConfig, SpaceVerse
    sv = SpaceVerse(sat, gs, ac, conf, CascadeConfig(answer_vocab=9))
    for task in P.TASKS:
        r = sv.evaluate(task, test)
        print(f"{task}: perf={r['performance']:.3f} "
              f"lat={r['latency_s']:.3f}s offload={r['offload_rate']:.2f}")


if __name__ == "__main__":
    main()
