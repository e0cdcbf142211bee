#!/usr/bin/env python3
"""Chip smoke test: serve the paper's model pair at published width on a TPU.

Drives the serving path once through the entry points a user calls —
``InferenceEngine`` → ``EngineCore`` → ``serve`` → ``step``, and
``CascadeServer.handle`` — with random weights made from ``--seed``, checks
every answer, and proves that the Pallas kernels did the work on the chip.
Everything runs in this one process (a chip belongs to one process).

    python chip_smoke.py            # one chip: Qwen2-VL-2B + the cascade
    python chip_smoke.py --chips 4  # four chips: Qwen2-VL-7B, tensor-parallel

One chip, in order: (1) device gate, (2) the satellite model at published
width (``SAT_CONFIG``, 1024 region tokens of 28x28x3 pixels), (3) engine
serving with warm-up, (4) the other kernel paths: chunked prefill,
speculative decode with a 2-layer drafter, int8 and fp8 KV pages, (5) the
two-tier cascade server with the ground model (``GS_CONFIG`` cut in depth to
share the chip), (6) proof the chip ran the kernels: Pallas dispatch,
``tpu_custom_call`` in the compiled decode step, and logit parity against
the jnp reference kernels on the same chip.

Four chips run only the sharded path and what it is compared with:
``GS_CONFIG`` at full depth on a (data 1, model 4) mesh, then the same
config cut to 2 layers on tp4 and dp2xtp2, slot path and batch path,
against one chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no TPU, or when any phase fails, the script exits non-zero and prints
no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Logit parity bound, pallas vs the jnp reference kernels on the same chip
#: and weights: max |Δlogit| over all rows and the full vocabulary, relative
#: to max |logit|.  Both paths run the same bf16 matmuls; only attention
#: differs (tiled online softmax vs one masked softmax), so the gap is
#: bf16 rounding carried through the stack — a wrong mask, block or page is
#: O(1) and far past it.
LOGIT_REL_TOL = 5e-2
#: ground-model depth that shares one 16 GB chip with the 2B satellite
GS_LAYERS_ONE_CHIP = 10
#: depth of the 7B config the four-chip comparison runs on every mesh
GS_LAYERS_COMPARE = 2

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def device_gate(chips: int):
    """Phase 1: a TPU with at least ``chips`` devices, or None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r} "
              f"({devs[0].device_kind}) — not running on the CPU",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"chip_smoke: needs {chips} TPU chips, JAX found {len(devs)}",
              file=sys.stderr)
        return None
    from importlib import metadata

    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"device: {devs[0].device_kind} x{len(devs)} ({devs[0].platform}); "
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory(tag: str) -> None:
    import jax
    for d in jax.local_devices():
        m = d.memory_stats() or {}
        log(f"memory[{tag}] {d}: in use "
            f"{m.get('bytes_in_use', 0) / 1e9:.3f} GB, peak "
            f"{m.get('peak_bytes_in_use', 0) / 1e9:.3f} GB of "
            f"{m.get('bytes_limit', 0) / 1e9:.3f} GB")


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def make_requests(rng, scenes, tasks, ac):
    """One request per task, cycling over ``scenes`` (scene i's queries
    share its region-prefix pages)."""
    from repro.serving.request import Request
    return [Request(task=t, image=scenes[i % len(scenes)],
                    prompt=int(rng.integers(ac.num_classes)),
                    scene_id=f"scene-{i % len(scenes)}")
            for i, t in enumerate(tasks)]


def same_requests(reqs):
    """Fresh requests with the same content (ids are per-request)."""
    from repro.serving.request import Request
    return [Request(task=r.task, image=r.image, prompt=r.prompt,
                    scene_id=r.scene_id) for r in reqs]


def check_answers(name, resps, reqs, ac, answer_vocab) -> list:
    """Every request answered once, at its task's length, in the answer
    vocabulary.  Returns the token lists in request order."""
    by_id = {r.request_id: r for r in resps}
    if len(resps) != len(reqs) or set(by_id) != {r.request_id
                                                 for r in reqs}:
        raise RuntimeError(f"{name}: {len(resps)} answers for {len(reqs)} "
                           "requests")
    out = []
    for r in reqs:
        toks = [int(t) for t in by_id[r.request_id].tokens]
        if len(toks) != ac.answer_len(r.task):
            raise RuntimeError(f"{name}: {r.task} answer has {len(toks)} "
                               f"tokens, expected {ac.answer_len(r.task)}")
        if not all(0 <= t < answer_vocab for t in toks):
            raise RuntimeError(f"{name}: token out of [0, {answer_vocab})")
        out.append(toks)
    log(f"{name}: {len(reqs)}/{len(reqs)} requests answered "
        f"({sum(len(t) for t in out)} tokens)")
    return out


def agreement(name, expected, got) -> None:
    """Greedy-token agreement between two engines on the same requests —
    reported, not gated (bf16 near-ties flip with random weights)."""
    from repro.kernels import kv_quant
    ag = kv_quant.compare_outputs(dict(enumerate(expected)),
                                  dict(enumerate(got)))
    log(f"{name}: {ag['n_tokens_diverged']}/{ag['n_tokens']} tokens "
        f"diverged across {ag['n_requests_diverged']}/{ag['n_requests']} "
        f"requests (first at {ag['first_divergences'] or '-'})")


def logit_parity(name, ref, got) -> None:
    """Max-abs error and top-1 agreement of two (rows, vocab) logit arrays;
    raises past ``LOGIT_REL_TOL``."""
    import numpy as np
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    err = float(np.abs(ref - got).max())
    scale = float(np.abs(ref).max())
    top1 = float((ref.argmax(-1) == got.argmax(-1)).mean())
    ok = np.isfinite(got).all() and err <= LOGIT_REL_TOL * scale
    log(f"{name}: max|dlogit| {err:.6g} (max|logit| {scale:.6g}, rel "
        f"{err / max(scale, 1e-30):.3g}, bound {LOGIT_REL_TOL}), top-1 "
        f"agreement {top1:.3f} over {ref.shape[0]} rows")
    if not ok:
        raise RuntimeError(f"{name}: logits outside the parity bound")


def fresh_jit(fn, **jit_kw):
    """``jax.jit`` of a NEW function object wrapping ``fn``: jit keys its
    trace cache on the function object, not on the kernel choice of
    ``ops.set_default_impl``, so re-jitting ``fn`` itself after switching
    the impl would silently replay the old trace."""
    import jax
    return jax.jit(lambda *a, **kw: fn(*a, **kw), **jit_kw)


def admit_logits(core, reqs):
    """Admit ``reqs`` into an idle engine and return each one's logits
    after its prompt token (the distribution its first answer token is
    drawn from), as (len(reqs), vocab) host floats."""
    import numpy as np
    slots = core.admit_many(reqs)
    lg = core._slot_logits
    if isinstance(lg, tuple):       # data-parallel shards, global order
        lg = np.concatenate([np.asarray(x) for x in lg])
    return np.asarray(lg)[np.asarray(slots)]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs.spaceverse_pair import GS_CONFIG, SAT_CONFIG
    from repro.core import confidence as C
    from repro.core import eo_adapter as EO
    from repro.core.cascade import CascadeConfig, TierModel
    from repro.kernels import autotune, ops
    from repro.network.orbit import ContactPlan
    from repro.serving.cascade_server import CascadeServer
    from repro.serving.engine import EngineConfig, InferenceEngine

    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    # -- phase 2: the satellite model at published width --------------------
    cfg = SAT_CONFIG
    ac = EO.EOAdapterConfig(grid=32, image_size=896)
    assert ac.n_regions == cfg.num_patches == 1024, ac
    t = time.perf_counter()
    params = jax.block_until_ready(EO.init_adapter(keys[0], cfg, ac))
    log(f"phase 2: {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads} x "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}; {ac.n_regions} region tokens of "
        f"{ac.image_size // ac.grid}x{ac.image_size // ac.grid}x"
        f"{ac.channels} px; {tree_bytes(params) / 1e9:.3f} GB of weights "
        f"({time.perf_counter() - t:.1f}s)")
    memory("weights")
    # no kernels/tuned/tpu.json is committed, so these are the defaults
    log("tile knobs in use: " + "; ".join(
        f"{k}[{d}]={autotune.lookup(k, d)}"
        for k in ("decode_dense", "paged_decode", "paged_verify",
                  "paged_prefill")
        for d in {autotune.dtype_key(x) for x in (cfg.dtype, "int8",
                                                  "float8_e4m3fn")}))

    scenes = rng.random((4, ac.image_size, ac.image_size, ac.channels),
                        dtype=np.float32)

    # -- phase 3: engine serving ------------------------------------------
    tasks = ["vqa", "cls", "vqa", "det", "cls", "vqa", "cls", "vqa",
             "vqa", "det", "cls", "vqa", "cls", "vqa", "vqa", "cls"]
    reqs = make_requests(rng, scenes, tasks, ac)
    ec = EngineConfig(slots=8)
    eng = InferenceEngine(params, cfg, ac, ec)
    t = time.perf_counter()
    eng.warmup()
    log(f"phase 3: warm-up (compile) {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    resps = eng.serve(reqs)
    dt = time.perf_counter() - t
    base = check_answers("phase 3 serve", resps, reqs, ac, ec.answer_vocab)
    st = eng.core.scheduler_stats()
    log(f"phase 3: serving {dt:.1f}s, {st['steps']} engine steps, "
        f"{st.get('steady_recompiles', 0)} steady-state recompiles")
    memory("served")
    served = {i: toks for i, toks in enumerate(base)}

    # -- phase 4: the other kernel paths ----------------------------------
    sub = [0, 1, 2, 3]                  # vqa, cls, vqa, det over 4 scenes
    short = [0, 1, 2, 4]                # 1-token answers only
    draft_cfg = dataclasses.replace(cfg, num_layers=2,
                                    name=f"{cfg.name}-2L-draft")
    draft = TierModel(EO.init_adapter(keys[1], draft_cfg, ac), draft_cfg)
    for name, kw, idx, d in (
            ("chunked prefill C=64", dict(prefill_chunk=64), short, None),
            ("spec γ=3", dict(spec_gamma=3), sub, draft),
            ("int8 KV", dict(kv_dtype="int8"), short, None),
            ("fp8 KV", dict(kv_dtype="fp8"), short, None)):
        e4 = InferenceEngine(params, cfg, ac,
                             EngineConfig(slots=4, **kw), draft=d)
        r4 = same_requests([reqs[i] for i in idx])
        t = time.perf_counter()
        got = check_answers(f"phase 4 {name}", e4.serve(r4), r4, ac,
                            ec.answer_vocab)
        log(f"phase 4 {name}: {time.perf_counter() - t:.1f}s with compiles")
        if "spec_gamma" in kw:
            sp = e4.core.spec_stats()
            log(f"phase 4 {name}: accept rate {sp.get('accept_rate')}, "
                f"{sp.get('tokens_per_slot_step')} tokens/slot-step")
        agreement(f"phase 4 {name} vs phase 3", [served[i] for i in idx],
                  got)
        del e4
        gc.collect()
    del draft
    gc.collect()

    # -- phase 5: the cascade server --------------------------------------
    gs_cfg = dataclasses.replace(
        GS_CONFIG, num_layers=GS_LAYERS_ONE_CHIP,
        name=f"{GS_CONFIG.name}-{GS_LAYERS_ONE_CHIP}L")
    t = time.perf_counter()
    gs_params = jax.block_until_ready(EO.init_adapter(keys[2], gs_cfg, ac))
    log(f"phase 5: ground tier {gs_cfg.name}: d_model {gs_cfg.d_model}, "
        f"heads {gs_cfg.num_heads}/{gs_cfg.num_kv_heads}, d_ff "
        f"{gs_cfg.d_ff}, vocab {gs_cfg.vocab_size}, cut to "
        f"{gs_cfg.num_layers}/{GS_CONFIG.num_layers} layers; "
        f"{tree_bytes(gs_params) / 1e9:.3f} GB "
        f"({time.perf_counter() - t:.1f}s)")
    memory("both tiers")
    conf = C.init_confidence(keys[3], cfg.d_model, cfg.d_model, hidden=64,
                             num_stages=2)
    server = CascadeServer(TierModel(params, cfg),
                           TierModel(gs_params, gs_cfg), ac, conf,
                           CascadeConfig(),
                           plan=ContactPlan(contact_fraction_override=1.0))
    # τ below every score answers onboard; τ above every score offloads
    # after the onboard decode — both branches run whatever the random
    # confidence net says
    onboard, offload = (0.0, -1.0), (0.0, 1.1)
    plan5 = [("vqa", offload), ("cls", offload), ("vqa", onboard),
             ("cls", offload)]
    r5 = make_requests(rng, scenes, [t_ for t_, _ in plan5], ac)
    t = time.perf_counter()
    for i, (req, (_, taus)) in enumerate(zip(r5, plan5)):
        server.cc = dataclasses.replace(server.cc, taus=taus)
        resp = server.handle(req, now=float(i))
        want = "ground" if taus is offload else "satellite"
        check_answers(f"phase 5 request {i} ({req.task}, {resp.tier})",
                      [resp], [req], ac, server.cc.answer_vocab)
        if resp.tier != want:
            raise RuntimeError(f"phase 5: expected tier {want}, got "
                               f"{resp.tier}")
        if want == "ground" and not resp.tx_bytes > 0:
            raise RuntimeError("phase 5: an offload transmitted no bytes")
    log(f"phase 5: {len(r5)} cascade requests in "
        f"{time.perf_counter() - t:.1f}s with compiles")
    del server, gs_params
    gc.collect()

    # -- phase 6: proof the chip did the work ------------------------------
    if ops.default_impl() != "pallas":
        raise RuntimeError(f"kernel dispatch is {ops.default_impl()!r}, "
                           "not 'pallas'")
    log("phase 6: kernel dispatch = pallas")
    core = eng.core
    core.admit_many(same_requests(reqs[:ec.slots]))
    args = (core._bb, core._slot_logits, core._slot_cache,
            core._slot_index,
            jax.numpy.asarray([s.active for s in core._slots]),
            core._block_table_dev())
    step = core._slot_step_j
    hlo = step.lower(*args, answer_vocab=ec.answer_vocab).compile().as_text()
    n_kern = hlo.count("tpu_custom_call")
    if not n_kern:
        raise RuntimeError("compiled decode step has no tpu_custom_call")
    log(f"phase 6: compiled decode step holds {n_kern} tpu_custom_call "
        "site(s)")
    got = step(*args, answer_vocab=ec.answer_vocab)[1]

    def prefill_logits(p, images, ptok):
        return EO.prefill_tokens(p, cfg, ac, images, ptok,
                                 ac.n_regions + 2)[0]
    images = jax.numpy.asarray(scenes[:1])
    ptok = jax.numpy.zeros((1,), jax.numpy.int32)
    got_prefill = fresh_jit(prefill_logits)(params, images, ptok)
    prev = ops.set_default_impl("ref")
    try:
        want = fresh_jit(step.__wrapped__, static_argnames=("answer_vocab",))(
            *args, answer_vocab=ec.answer_vocab)[1]
        want_prefill = fresh_jit(prefill_logits)(params, images, ptok)
    finally:
        ops.set_default_impl(prev)
    logit_parity("phase 6 decode step (paged kernel) vs ref", want, got)
    logit_parity(f"phase 6 dense prefill ({ac.n_regions + 1} tokens, "
                 "flash kernel) vs ref", want_prefill, got_prefill)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs.spaceverse_pair import GS_CONFIG
    from repro.core import eo_adapter as EO
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_host_mesh
    from repro.serving.engine import EngineConfig, InferenceEngine

    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    ac = EO.EOAdapterConfig(grid=32, image_size=896)
    scenes = rng.random((2, ac.image_size, ac.image_size, ac.channels),
                        dtype=np.float32)
    reqs = make_requests(rng, scenes, ["vqa", "cls", "vqa", "cls"], ac)

    # -- Qwen2-VL-7B at full depth, tensor-parallel over four chips --------
    cfg = GS_CONFIG
    mesh = make_host_mesh(model=4, data=1)
    t = time.perf_counter()
    params = jax.block_until_ready(SH.init_sharded(
        lambda key: EO.init_adapter(key, cfg, ac), cfg, mesh, keys[0]))
    wq = params["backbone"]["blocks"][0]["mixer"]["wq"]
    log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads} x {cfg.resolved_head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; {tree_bytes(params) / 1e9:.3f} "
        f"GB of weights made on a {dict(mesh.shape)} mesh "
        f"({time.perf_counter() - t:.1f}s); wq {wq.shape} is held as "
        f"{wq.addressable_shards[0].data.shape} per device")
    memory("7B weights, tp=4")
    ec = EngineConfig(slots=4, mesh=mesh)
    eng = InferenceEngine(params, cfg, ac, ec)
    t = time.perf_counter()
    check_answers("7B tp=4 serve", eng.serve(reqs), reqs, ac,
                  ec.answer_vocab)
    kv = eng.core.kv_stats()
    log(f"7B tp=4: {time.perf_counter() - t:.1f}s with compiles; KV "
        f"{kv.get('kv_bytes_per_slot_device')} B/slot/device")
    memory("7B served")
    del eng, params
    gc.collect()

    # -- the same config cut in depth: tp4 and dp2xtp2 against one chip ----
    # slot path: logits after each request's prompt token; batch path (the
    # cascade executor's ``prefill`` + ``decode_chunk``): logits after the
    # prompt and after one more greedy token
    cut = dataclasses.replace(cfg, num_layers=GS_LAYERS_COMPARE,
                              name=f"{cfg.name}-{GS_LAYERS_COMPARE}L")
    p_cut = EO.init_adapter(keys[1], cut, ac)
    images = jax.numpy.asarray(np.stack([r.image for r in reqs]))
    prompts = jax.numpy.asarray([r.prompt for r in reqs], jax.numpy.int32)
    logits = {}
    for label, mesh_ in (("one chip", None),
                         ("tp4", make_host_mesh(model=4, data=1)),
                         ("dp2xtp2", make_host_mesh(model=2, data=2))):
        core = InferenceEngine(p_cut, cut, ac,
                               EngineConfig(slots=4, mesh=mesh_)).core
        slot = admit_logits(core, same_requests(reqs))
        lg, cache, idx = core.prefill("vqa", images, prompts, 2)
        lg2 = core.decode_chunk(cache, lg, idx, 1, ec.answer_vocab)[3]
        logits[label] = {"slot path": slot,
                         "batch prefill": np.asarray(lg),
                         "batch decode": np.asarray(lg2)}
        del core, cache
        gc.collect()
    for label in ("tp4", "dp2xtp2"):
        for path, want in logits["one chip"].items():
            logit_parity(f"{cut.name} {label} {path} vs one chip", want,
                         logits[label][path])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded 7B "
                         "path and its comparison")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    device = device_gate(args.chips)
    if device is None:
        return 2
    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    log("all phases passed")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
