"""Serving-throughput benchmark for the EngineCore slot path.

Drives one satellite-tier ``EngineCore`` at **full slot occupancy** — every
finished slot is refilled from a synthetic request stream before the next
decode step — and measures the continuous-batching hot loop for each step
implementation:

- ``batched``: one ``T.decode_step`` over the whole slot table per step with
  a (slots,) ragged index vector, refilled through one bucketed
  ``admit_many`` prefill per step (PR 2),
- ``vmap``:    the pre-PR-2 engine — ``jax.vmap`` of a batch-1 step over the
  stacked table (kept in ``EngineCore`` as the baseline oracle) **and** one
  batch-1 prefill + scatter per admitted request.

A second workload benchmarks the paged KV cache at **scene fan-out** —
several queries per captured scene, the paper's dominant traffic shape —
for ``cache_impl`` paged vs dense: end-to-end tokens/s, prefilled tokens
(paged prefills the N_r region tokens once per scene + a 1-token prompt
suffix per request; dense re-prefills the full prefix per request), prefix
hit rate and amortised KV bytes per slot, with the output token streams
checked equal.

A third workload benchmarks **cascade-speculative decoding** on the ground
tier: the compact satellite model drafts γ tokens per slot (and its
already-computed answers piggyback on the request as free drafts — bytes
the downlink carries anyway), the regular model verifies them in ONE
multi-token paged scoring step.  Both tiers are briefly proxy-trained so
they agree the way the paper's deployed pair does (accept rate is a
property of model agreement, not of the harness); the speculative outputs
are asserted token-for-token equal to the non-speculative greedy engine on
the same request stream, and the record reports accept rate, drafts/step
and decode tokens/s for both engines.

A fourth workload benchmarks **chunked prefill** under continuous arrival
on production-shaped scenes (grid² = 256 region tokens — real EO tiles
carry hundreds of visual tokens, and the toy 16-token adapter makes scene
prefill as cheap as one decode step, leaving nothing to stall on): every
downlink burst delivers fresh scenes (det queries — long answers that
keep decode busy) together with urgent vqa queries fanning out over the
PREVIOUS burst's already-resident scenes.  The chunked engine
(Sarathi-style token-budget steps — admission streams the region prefill
into the paged cache alongside decode) is measured against the stall
engine (synchronous scene prefill at admission, the PR 3/4 path) at an
arrival interval calibrated from the slower engine's service time, so
TTFT measures the admission freeze rather than an unbounded queue.
Outputs are asserted token-for-token equal in-bench; the record carries
per-task TTFT percentiles from ARRIVAL (the urgent-vqa class is the
time-to-first-result headline — those queries need no prefill at all,
yet the stall engine makes them wait behind the whole burst's synchronous
scene prefill), decode-gap percentiles (the freeze as seen by in-flight
rows), and an interleaved-median steady-state decode comparison (the
chunked engine falls back to the identical compiled step — parity
required).

A fifth workload benchmarks **overload control** under sustained
over-capacity arrivals (offered load ≈ 2× the measured service rate,
~80% bulk det / 20% urgent vqa): the overload-controlled engine (bounded
priority admission queue + page-aware check-then-commit admission +
drop-and-recompute preemption) against the pre-overload baseline — an
unbounded host FIFO in front of ``admit_many``.  The record carries
per-class TTFT from arrival, queue peaks, preemption/rejection counts and
the urgent-p99 speedup; every completed answer (preempted-then-resumed
included) is asserted token-for-token equal to the uncontended dense
oracle and the controlled engine's pool must drain leak-free.

Every workload now reports **TTFT and per-request p50/p99 latency** next
to aggregate tokens/s, derived from the engine's own request log
(admit / first-token / done wall-clock milestones per request).

Metrics land in ``BENCH_serving.json`` so CI can smoke the harness and
future PRs can diff the numbers.  The file carries schema metadata at the
top level and a backend-keyed, bounded ``history`` of full run records —
every run's config rides inside its own entry (schema 2; the old layout
left the latest run's config at the top level, clobbered by whichever leg
ran last).  ``--trend`` prints the per-workload tokens/s trajectory from
that history; ``--regress-guard`` fails the run if a headline metric drops
>20% against the last comparable same-backend entry.  Model weights are
randomly initialised — throughput does not depend on training, so the
bench needs no proxy-training warmup.

Usage:
    PYTHONPATH=src python benchmarks/serving_bench.py            # full run
    PYTHONPATH=src python benchmarks/serving_bench.py --smoke    # CI smoke
    PYTHONPATH=src python benchmarks/serving_bench.py --trend    # history
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.spaceverse_pair import proxy_pair
from repro.core import eo_adapter as EO
from repro.core.cascade import TierModel
from repro.data import synthetic
from repro.serving.engine_core import EngineCore, EngineCoreConfig
from repro.serving.request import Request
from repro.serving.sharded import make_engine_core


def _request_stream(ac: EO.EOAdapterConfig, n: int, det_frac: float,
                    seed: int) -> List[Request]:
    """Mixed-length traffic: ``det`` answers take N_r tokens, vqa/cls take 1
    — the ragged-length regime the slot table exists for."""
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", max(n, 2), seed=seed, cfg=eo_cfg)
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        task = "det" if rng.rand() < det_frac else "vqa"
        reqs.append(Request(task=task, image=data["images"][i % len(
            data["images"])], prompt=int(data["prompts"][i % len(
                data["prompts"])]) % 2))
    return reqs


def _latency_stats(core: EngineCore, arrivals: Dict[int, float] = None
                   ) -> Dict[str, float]:
    """TTFT + per-request latency percentiles from the engine's request
    log.  ``arrivals``: request_id → absolute arrival wall-clock; when
    given, TTFT/latency are measured from arrival (queue wait included),
    else from admission."""
    guard = {"steady_recompiles":
             core.scheduler_stats()["steady_recompiles"]}
    log = core.stats["request_log"]
    if not log:
        return {"requests": 0, **guard}
    t0 = lambda r: (arrivals[r["request_id"]] if arrivals is not None
                    else r["t_admit"])
    ttft = np.asarray([r["t_first"] - t0(r) for r in log])
    lat = np.asarray([r["t_done"] - t0(r) for r in log])
    ms = lambda x: round(float(x) * 1e3, 3)
    return {
        "requests": len(log),
        "ttft_p50_ms": ms(np.percentile(ttft, 50)),
        "ttft_p99_ms": ms(np.percentile(ttft, 99)),
        "latency_p50_ms": ms(np.percentile(lat, 50)),
        "latency_p99_ms": ms(np.percentile(lat, 99)),
        **guard,
    }


def _legacy_admit(core: EngineCore, request: Request) -> int:
    """The pre-PR ``EngineCore.admit``, verbatim: one batch-1 prefill + one
    per-leaf ``dynamic_update_index_in_dim`` scatter + one ``prompt_token``
    device roundtrip per admitted request.  Kept here (not in the engine) so
    the benchmark baseline stays the pre-PR engine even as the real
    admission path improves."""
    import jax.numpy as jnp
    from repro.serving.engine_core import _Slot

    free = core.free_slots()
    if not free:
        raise RuntimeError("no free slot")
    core._ensure_slot_tables()
    scatter = getattr(core, "_legacy_scatter_j", None)
    if scatter is None:
        def _slot_scatter(slot_cache, slot_logits, slot_index,
                          cache, logits, s, idx):
            sc = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new[:, 0], s, 1),
                slot_cache, cache)
            sl = jax.lax.dynamic_update_index_in_dim(slot_logits, logits[0],
                                                     s, 0)
            si = jax.lax.dynamic_update_index_in_dim(
                slot_index, idx.astype(slot_index.dtype), s, 0)
            return sc, sl, si
        scatter = core._legacy_scatter_j = jax.jit(_slot_scatter)
    s = free[0]
    images = jnp.asarray(np.asarray(request.image)[None])
    prompts = jnp.asarray(np.array([request.prompt], np.int32))
    ptok = core.ac.prompt_token(request.task, prompts)
    logits, cache, idx = core._prefill_j(core._p, images, ptok,
                                         max_len=core._slot_max_len)
    core._slot_cache, core._slot_logits, core._slot_index = scatter(
        core._slot_cache, core._slot_logits, core._slot_index, cache, logits,
        jnp.asarray(s, jnp.int32), idx)
    core._slots[s] = _Slot(request=request,
                           l_ans=core.ac.answer_len(request.task),
                           tokens=[], active=True,
                           t_admit=time.perf_counter())
    core._active_dev = None
    core.stats["admitted"] += 1
    if core._step_no > 0 and core.active_count() > 1:
        core.stats["mid_stream_refills"] += 1
    return s


def bench_impl(impl: str, *, slots: int, steps: int, warmup: int,
               det_frac: float, seed: int,
               kv_dtype: str = None) -> Dict[str, float]:
    sat_cfg, _ = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    params = EO.init_adapter(jax.random.PRNGKey(seed), sat_cfg, ac)
    core = EngineCore(TierModel(params, sat_cfg), ac,
                      EngineCoreConfig(slots=slots, answer_vocab=9,
                                       step_impl=impl,
                                       kv_dtype=(kv_dtype if impl != "vmap"
                                                 else None)))
    # enough pending work that the table never starves (det pins slots for
    # N_r steps; 1-token requests churn through the rest)
    stream = _request_stream(ac, n=slots * (steps + warmup + 4) + 8,
                             det_frac=det_frac, seed=seed)
    queue = list(reversed(stream))

    per_request_admission = impl == "vmap"   # the pre-PR refill path

    def refill():
        free = core.free_slots()
        n = min(len(free), len(queue))
        if per_request_admission:
            for _ in range(n):
                _legacy_admit(core, queue.pop())
        elif n:
            core.admit_many([queue.pop() for _ in range(n)])
        return n

    def step():
        if per_request_admission:
            # pre-PR step() rebuilt + re-uploaded the active mask
            # host→device every call; reproduce that cost for the baseline
            core._active_dev = None
        return core.step()

    # -- warmup: compile every admission bucket + the decode step -----------
    core.warmup()
    refill()
    for _ in range(warmup):
        step()
        refill()

    # -- timed: full occupancy, refilled every step -------------------------
    tokens = 0
    admissions = 0
    n_admit_calls = 0
    core.stats["request_log"].clear()       # percentiles over the timed run
    t0 = time.perf_counter()
    for _ in range(steps):
        step()
        tokens += core.cfg.slots          # full occupancy: slots tokens/step
        n = refill()
        admissions += n
        n_admit_calls += 1 if n else 0
    jax.block_until_ready(core._slot_logits)
    dt = time.perf_counter() - t0

    return {
        "impl": impl,
        "slots": slots,
        "steps": steps,
        "wall_s": round(dt, 4),
        "decode_tokens_per_s": round(tokens / dt, 2),
        "steps_per_s": round(steps / dt, 2),
        "admissions_per_s": round(admissions / dt, 2),
        "admissions": admissions,
        "admit_calls": n_admit_calls,
        "mid_stream_refills": core.stats["mid_stream_refills"],
        **_latency_stats(core),
    }


def _fanout_stream(ac: EO.EOAdapterConfig, scenes: int, fanout: int,
                   seed: int) -> List[Request]:
    """Scene fan-out: ``fanout`` mixed-task queries over each of ``scenes``
    captured scenes (1 det + 1 cls + vqa rest), scene-grouped as a capture's
    query burst arrives."""
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", max(scenes, 2), seed=seed,
                                  cfg=eo_cfg)
    reqs = []
    for s in range(scenes):
        img = data["images"][s % len(data["images"])]
        reqs.append(Request(task="det", image=img, prompt=0, scene_id=s))
        reqs.append(Request(task="cls", image=img, prompt=0, scene_id=s))
        reqs += [Request(task="vqa", image=img, prompt=q % 2, scene_id=s)
                 for q in range(max(fanout - 2, 0))]
    return reqs


def bench_fanout(cache_impl: str, *, slots: int, scenes: int, fanout: int,
                 seed: int, kv_dtype: str = None,
                 tier: TierModel = None, mesh=None) -> Dict[str, object]:
    ac = EO.EOAdapterConfig()
    if tier is None:
        sat_cfg, _ = proxy_pair("small")
        params = EO.init_adapter(jax.random.PRNGKey(seed), sat_cfg, ac)
        tier = TierModel(params, sat_cfg)
    core = make_engine_core(
        tier, ac,
        EngineCoreConfig(slots=slots, answer_vocab=9,
                         cache_impl=cache_impl, mesh=mesh,
                         kv_dtype=(kv_dtype
                                   if cache_impl == "paged"
                                   else None)))
    queue = list(reversed(_fanout_stream(ac, scenes, fanout, seed)))
    n_req = len(queue)
    core.warmup()

    tokens = 0
    outputs = {}
    kv_sample = None
    t0 = time.perf_counter()
    while queue or core.active_count() > 0:
        n = min(len(queue), len(core.free_slots()))
        if n:
            core.admit_many([queue.pop() for _ in range(n)])
        if kv_sample is None and core.active_count() == slots:
            kv_sample = core.kv_stats()          # footprint at full occupancy
        for req, toks in core.step():
            tokens += len(toks)
            outputs[req.request_id] = toks.tolist()
    jax.block_until_ready(core._slot_logits)
    dt = time.perf_counter() - t0
    kv = kv_sample or core.kv_stats()

    return {
        "cache_impl": cache_impl,
        "slots": slots,
        "scenes": scenes,
        "fanout": fanout,
        "requests": n_req,
        "wall_s": round(dt, 4),
        "answer_tokens_per_s": round(tokens / dt, 2),
        "prefill_tokens": core.stats["prefill_tokens"],
        "prefix_hits": core.stats["prefix_hits"],
        "prefix_misses": core.stats["prefix_misses"],
        "prefix_hit_rate": round(
            core.stats["prefix_hits"]
            / max(core.stats["prefix_hits"]
                  + core.stats["prefix_misses"], 1), 4),
        "kv_bytes_per_slot": kv["kv_bytes_per_slot"],
        # mesh engines additionally report the per-DEVICE footprint (the
        # TP shard's cut, from the full-occupancy sample) and the DP
        # router's end-of-run per-shard breakdown (final routed totals)
        **{k: kv[k] for k in ("kv_bytes_per_slot_device", "mesh")
           if k in kv},
        **({"per_shard": core.kv_stats()["per_shard"]}
           if mesh is not None and hasattr(core, "shards") else {}),
        **_latency_stats(core),
        # token streams in request-creation order (ids are monotonic per
        # run): compared across impls, then dropped from the JSON record
        "outputs": [outputs[i] for i in sorted(outputs)],
    }


# ---------------------------------------------------------------------------
# speculative decoding: compact model drafts, regular model verifies
# ---------------------------------------------------------------------------

def _spec_pair(seed: int, train_steps: int):
    """(satellite drafter, ground verifier, adapter cfg) — proxy-trained on
    the same synthetic EO tasks when ``train_steps > 0`` (speculation's win
    is model agreement; untrained random pairs only agree by chance)."""
    sat_cfg, gs_cfg = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    if train_steps > 0:
        from repro.core import pipeline as P
        eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size,
                                        grid=ac.grid,
                                        num_classes=ac.num_classes)
        train = {t: synthetic.make_dataset(t, 96, seed=seed, cfg=eo_cfg)
                 for t in ("vqa", "cls", "det")}
        sat_p, _ = P.train_proxy(sat_cfg, ac, train, steps=train_steps,
                                 seed=seed)
        gs_p, _ = P.train_proxy(gs_cfg, ac, train,
                                steps=int(train_steps * 1.5), seed=seed + 1)
    else:
        sat_p = EO.init_adapter(jax.random.PRNGKey(seed), sat_cfg, ac)
        gs_p = EO.init_adapter(jax.random.PRNGKey(seed + 1), gs_cfg, ac)
    return TierModel(sat_p, sat_cfg), TierModel(gs_p, gs_cfg), ac


def _attach_sat_drafts(sat: TierModel, ac, reqs) -> None:
    """Precompute the satellite's compact-model answers (batched, per task)
    and piggyback them as draft seeds — in deployment these tokens already
    exist (the satellite decoded them before offloading) and ride the same
    downlink as the image payload, so they are not charged to the timed
    ground-side loop."""
    import jax.numpy as jnp
    from repro.serving.engine_core import shared_core
    core = shared_core(sat, ac)      # memoised per tier: no duplicate jits
    by_task = {}
    for r in reqs:
        by_task.setdefault(r.task, []).append(r)
    for task, rs in by_task.items():
        images = jnp.asarray(np.stack([np.asarray(r.image) for r in rs]))
        prompts = jnp.asarray(np.array([r.prompt for r in rs], np.int32))
        toks, _ = core.generate(task, images, prompts, 9)
        for r, t in zip(rs, np.asarray(toks)):
            r.draft_tokens = t.astype(np.int32)


def _drive(core: EngineCore, reqs) -> Dict[str, object]:
    """Admit/step a queue to drain at full occupancy.

    Decode and admission are timed separately: speculation attacks the
    sequential decode steps, so ``decode_tokens_per_s`` is emitted tokens
    over time spent in ``step()`` (each step's host sync included).
    Admission is NOT identical across engines — the speculative engine's
    ``admit_many`` additionally prefills the drafter — which is why the
    record also carries ``wall_s``/``total_tokens_per_s`` over the whole
    serve (and the spec section reports both speedups)."""
    queue = list(reversed(reqs))
    outputs, tokens = {}, 0
    step_s = 0.0
    core.stats["request_log"].clear()
    t0 = time.perf_counter()
    while queue or core.active_count() > 0:
        n = min(len(queue), len(core.free_slots()))
        if n:
            core.admit_many([queue.pop() for _ in range(n)])
        t1 = time.perf_counter()
        done = core.step()
        step_s += time.perf_counter() - t1
        for req, toks in done:
            tokens += len(toks)
            outputs[req.request_id] = toks.tolist()
    jax.block_until_ready(core._slot_logits)
    dt = time.perf_counter() - t0
    return {"outputs": outputs, "tokens": tokens, "wall_s": round(dt, 4),
            "decode_s": round(step_s, 4),
            "decode_tokens_per_s": round(tokens / max(step_s, 1e-9), 2),
            "total_tokens_per_s": round(tokens / dt, 2),
            **_latency_stats(core)}


def bench_spec(*, slots: int, n_req: int, det_frac: float, gamma: int,
               train_steps: int, seed: int, reps: int = 3,
               kv_dtype: str = None) -> Dict[str, object]:
    """Speculative vs greedy ground-tier decode on one request stream.

    The stream mixes 1-token vqa answers with N_r-token det answers
    (det-heavy: multi-token answers are where drafting pays); every request
    carries the satellite's piggybacked answer.  Outputs are asserted
    token-for-token equal in-bench.  Each engine serves the stream ``reps``
    times (alternating) and the median-``decode_s`` run is recorded — the
    streams are short enough that scheduler noise otherwise dominates."""
    sat, gs, ac = _spec_pair(seed, train_steps)
    stream = _request_stream(ac, n=n_req, det_frac=det_frac, seed=seed)
    _attach_sat_drafts(sat, ac, stream)

    def clone():
        out = []
        for r in stream:
            c = Request(task=r.task, image=r.image, prompt=r.prompt,
                        draft_tokens=r.draft_tokens)
            c.request_id = r.request_id
            out.append(c)
        return out

    base = EngineCore(gs, ac, EngineCoreConfig(slots=slots, answer_vocab=9,
                                               kv_dtype=kv_dtype))
    base.warmup()
    spec = EngineCore(gs, ac,
                      EngineCoreConfig(slots=slots, answer_vocab=9,
                                       spec_gamma=gamma, kv_dtype=kv_dtype),
                      draft=sat)
    spec.warmup()
    runs_base, runs_spec = [], []
    for _ in range(max(reps, 1)):
        runs_base.append(_drive(base, clone()))
        runs_spec.append(_drive(spec, clone()))

    def median_run(runs):
        return sorted(runs, key=lambda r: r["decode_s"])[len(runs) // 2]

    # strip token streams from EVERY run first (they must never land in the
    # JSON record), then compare every rep — no short-circuit
    outs_base = [r.pop("outputs") for r in runs_base]
    outs_spec = [r.pop("outputs") for r in runs_spec]
    match = all(ob == os_ for ob, os_ in zip(outs_base, outs_spec))
    r_base, r_spec = median_run(runs_base), median_run(runs_spec)
    # the guard counter is cumulative per engine: overwrite the median
    # rep's snapshot with the end-of-bench total so nothing hides in an
    # unpicked rep
    r_base["steady_recompiles"] = \
        base.scheduler_stats()["steady_recompiles"]
    r_spec["steady_recompiles"] = \
        spec.scheduler_stats()["steady_recompiles"]
    sp = spec.spec_stats()
    return {
        "slots": slots, "requests": n_req, "det_frac": det_frac,
        "gamma": gamma, "train_steps": train_steps,
        "greedy": r_base, "spec": r_spec,
        "outputs_match": match,
        "speedup_tokens_per_s": round(
            r_spec["decode_tokens_per_s"]
            / max(r_base["decode_tokens_per_s"], 1e-9), 3),
        "speedup_total_tokens_per_s": round(
            r_spec["total_tokens_per_s"]
            / max(r_base["total_tokens_per_s"], 1e-9), 3),
        "accept_rate": round(sp["accept_rate"], 4),
        "drafts_per_step": round(sp["drafts_per_step"], 2),
        "tokens_per_slot_step": round(sp["tokens_per_slot_step"], 3),
        "piggyback_frac": round(sp["piggyback_frac"], 4),
        "verify_only_steps": sp["verify_only_steps"],
        "spec_steps": sp["steps"],
    }


# ---------------------------------------------------------------------------
# chunked prefill: token-budget fused steps vs synchronous admission stalls
# ---------------------------------------------------------------------------

def _monitor_tier(grid: int, seed: int):
    """A production-shaped serving tier for the chunked workload: the
    4-layer GS proxy with a ``grid``x``grid`` region adapter.  The default
    toy adapter (16 region tokens) makes scene prefill as cheap as a
    single decode step, so admission has nothing to stall on; real EO
    tiles carry hundreds of visual tokens (EarthSight-style high-res
    scenes), which is the regime chunked prefill exists for."""
    import dataclasses
    _, gs_cfg = proxy_pair("small")
    cfg = dataclasses.replace(gs_cfg, num_patches=grid * grid)
    ac = EO.EOAdapterConfig(grid=grid, image_size=8 * grid)  # 8-px patches
    params = EO.init_adapter(jax.random.PRNGKey(seed), cfg, ac)
    return TierModel(params, cfg), ac


def _monitor_bursts(ac: EO.EOAdapterConfig, bursts: int, new_scenes: int,
                    fanout: int, seed: int, tag: str) -> List[List[Request]]:
    """Continuous-arrival monitoring traffic: every burst is a downlink
    pass delivering ``new_scenes`` freshly captured scenes (one det query
    each — the long multi-token answers that keep decode busy) PLUS
    ``fanout`` urgent vqa queries fanning out over the PREVIOUS burst's
    scenes (already page-resident — analysts keep querying earlier
    captures).  The vqa queries are the time-to-first-result story: they
    need no prefill at all, yet in the stall engine they queue behind the
    whole burst's synchronous scene prefill."""
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", max(bursts * new_scenes, 2),
                                  seed=seed, cfg=eo_cfg)
    out = []
    for b in range(bursts):
        burst = []
        for s in range(new_scenes):
            i = b * new_scenes + s
            burst.append(Request(task="det",
                                 image=data["images"][i % len(data["images"])],
                                 prompt=0, scene_id=f"{tag}-{b}-{s}"))
        if b > 0:
            for q in range(fanout):
                burst.append(Request(
                    task="vqa",
                    image=data["images"][((b - 1) * new_scenes + q
                                          % new_scenes)
                                         % len(data["images"])],
                    prompt=q % 2,
                    scene_id=f"{tag}-{b - 1}-{q % new_scenes}"))
        out.append(burst)
    return out


def _clone_bursts(bursts: List[List[Request]], tag: str
                  ) -> List[List[Request]]:
    """Clone a burst stream with request ids preserved (output equality is
    compared id-by-id) and scene ids re-tagged (so no engine or phase can
    hit a prefix another drive warmed)."""
    out = []
    for b in bursts:
        nb = []
        for r in b:
            c = Request(task=r.task, image=r.image, prompt=r.prompt,
                        scene_id=f"{tag}-{r.scene_id}")
            c.request_id = r.request_id
            nb.append(c)
        out.append(nb)
    return out


def _drive_arrivals(core: EngineCore, bursts: List[List[Request]],
                    interval: float) -> Dict[str, object]:
    """Serve scene bursts that ARRIVE over time (one burst every
    ``interval`` seconds; 0 = everything due immediately).  A request only
    becomes admittable at its arrival instant, so TTFT measured from
    arrival includes the queue wait behind whatever the engine is doing —
    for the stall engine, synchronous scene prefills.  Also records the
    per-iteration wall gaps seen by in-flight decode rows (``decode_gap``):
    the stall engine's admission freeze lands right here."""
    pending = [(i * interval, r) for i, b in enumerate(bursts) for r in b]
    arrivals: Dict[int, float] = {}
    due: List[Request] = []
    outputs, tokens = {}, 0
    gaps: List[float] = []
    core.stats["request_log"].clear()
    t0 = time.perf_counter()
    while pending or due or core.active_count() > 0:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            rel, r = pending.pop(0)
            arrivals[r.request_id] = t0 + rel
            due.append(r)
        it0 = time.perf_counter()
        decoding = any(s.active and s.phase == "decode"
                       and len(s.tokens) < s.l_ans for s in core._slots)
        n = min(len(due), len(core.free_slots()))
        if n:
            core.admit_many(due[:n])
            del due[:n]
        if core.active_count() > 0:
            for req, toks in core.step():
                tokens += len(toks)
                outputs[req.request_id] = toks.tolist()
            if decoding:
                gaps.append(time.perf_counter() - it0)
        elif pending:
            time.sleep(max(min(pending[0][0] - now, 1e-3), 0.0))
    jax.block_until_ready(core._slot_logits)
    dt = time.perf_counter() - t0
    arr = arrivals if interval > 0 else None
    ms = lambda x: round(float(x) * 1e3, 3)
    rec = {"outputs": outputs, "tokens": tokens, "wall_s": round(dt, 4),
           "tokens_per_s": round(tokens / dt, 2),
           **_latency_stats(core, arr)}
    # per-task TTFT: vqa is the urgent-fan-out class the workload measures
    log = core.stats["request_log"]
    for task in ("vqa", "det"):
        t_of = [r["t_first"] - (arr[r["request_id"]] if arr
                                else r["t_admit"])
                for r in log if r["task"] == task]
        if t_of:
            rec[f"{task}_ttft_p50_ms"] = ms(np.percentile(t_of, 50))
            rec[f"{task}_ttft_p99_ms"] = ms(np.percentile(t_of, 99))
    if gaps:
        rec["decode_gap_p50_ms"] = ms(np.percentile(gaps, 50))
        rec["decode_gap_p99_ms"] = ms(np.percentile(gaps, 99))
        rec["decode_gap_max_ms"] = ms(np.max(gaps))
    return rec


def _steady_state_decode(stall: EngineCore, chunked: EngineCore, ac,
                         seed: int, steps: int, reps: int
                         ) -> Dict[str, float]:
    """Decode tokens/s with every slot mid-answer and nothing prefilling —
    the regime where the chunked engine must cost nothing extra (it falls
    back to the identical plain step).  Interleaved repetitions, median
    taken: the two engines run the same compiled function, so anything but
    noise here is a regression.

    Two fairness details: the scenes are served to completion ONCE first,
    so the timed admission is prefix-resident for BOTH engines and the
    chunked engine reaches full decode occupancy within a step or two of
    the stall engine (a cold chunked admission would stream N_r tokens per
    scene first, long enough for det answers to start finishing and the
    window to open at partial occupancy); and throughput divides tokens
    ACTUALLY committed (Σ active slots per step), not a nominal
    slots·steps that would credit freed slots."""
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", max(stall.cfg.slots, 2), seed=seed,
                                  cfg=eo_cfg)
    for tag, core in (("w0", stall), ("w1", chunked)):
        mk_reqs = lambda: [Request(task="det",
                                   image=data["images"][i
                                                        % len(data["images"]
                                                              )],
                                   prompt=0, scene_id=f"{tag}-{i}")
                           for i in range(core.cfg.slots)]
        core.admit_many(mk_reqs())            # warm pass: make resident
        while core.active_count() > 0:
            core.step()
        core.admit_many(mk_reqs())            # timed table: prompt-only
        while any(s.active and s.phase != "decode" for s in core._slots):
            core.step()
        core.step()
        assert core.active_count() == core.cfg.slots
    times = {"stall": [], "chunked": []}
    tokens = {"stall": 0, "chunked": 0}
    for _ in range(reps):
        for name, core in (("stall", stall), ("chunked", chunked)):
            jax.block_until_ready(core._slot_logits)
            t0 = time.perf_counter()
            for _ in range(steps):
                tokens[name] += core.active_count()
                core.step()
            jax.block_until_ready(core._slot_logits)
            times[name].append(time.perf_counter() - t0)
    for core in (stall, chunked):
        while core.active_count() > 0:
            core.step()
    med = lambda ts: sorted(ts)[len(ts) // 2]
    return {name: round((tokens[name] / reps) / med(ts), 2)
            for name, ts in times.items()}


def bench_chunked(*, slots: int, grid: int, bursts: int, new_scenes: int,
                  fanout: int, chunk: int, seed: int, smoke: bool,
                  kv_dtype: str = None) -> Dict[str, object]:
    """Chunked prefill vs the synchronous-admission stall engine on
    production-shaped monitoring traffic (grid² region tokens per scene).

    Measurements on identical burst streams, outputs asserted
    token-for-token equal each time:

    1. **steady-state decode** — full slots, no admissions, interleaved
       medians: the chunked engine must be within noise of the stall
       engine (it runs the same compiled step);
    2. **saturation** — the whole stream due at once: aggregate tokens/s
       with admissions interleaved;
    3. **continuous arrival** — bursts arrive at an interval calibrated
       from the slower engine's measured service time: TTFT / latency
       percentiles from ARRIVAL (queue wait included), per task class —
       the urgent resident-scene vqa queries are the time-to-first-result
       headline — plus the decode-gap percentiles that expose the
       admission freeze directly."""
    tier, ac = _monitor_tier(grid, seed)
    mk = lambda c: EngineCore(tier, ac, EngineCoreConfig(
        slots=slots, answer_vocab=9, prefill_chunk=c, kv_dtype=kv_dtype))
    stall, chunked = mk(0), mk(chunk)
    stall.warmup()
    chunked.warmup()

    steady = _steady_state_decode(stall, chunked, ac, seed,
                                  steps=4 if smoke else 12,
                                  reps=2 if smoke else 9)

    sat_bursts = _monitor_bursts(ac, bursts, new_scenes, fanout, seed,
                                 tag="sat")
    r_sat_stall = _drive_arrivals(stall, _clone_bursts(sat_bursts, "s0"),
                                  interval=0.0)
    r_sat_chunk = _drive_arrivals(chunked, _clone_bursts(sat_bursts, "s1"),
                                  interval=0.0)
    sat_match = r_sat_stall.pop("outputs") == r_sat_chunk.pop("outputs")
    assert sat_match, "chunked outputs diverged from the stall engine"

    # burst interval: 1.25x the slower engine's saturated per-burst service
    # time, so BOTH engines keep up and TTFT measures the admission freeze,
    # not an unbounded queue.  The arrival phase repeats (alternating
    # engines, fresh scene tags so nothing stays resident across reps) and
    # the median-by-vqa-TTFT rep is recorded — same discipline as the spec
    # workload: single short serves are scheduler-noise-dominated on this
    # machine.  Outputs are compared on EVERY rep.
    interval = 1.25 * max(r_sat_stall["wall_s"],
                          r_sat_chunk["wall_s"]) / bursts
    arr_reps = 1 if smoke else 3
    arr_match = True
    runs_stall, runs_chunk = [], []
    for rep in range(arr_reps):
        arr_bursts = _monitor_bursts(ac, bursts, new_scenes, fanout, seed,
                                     tag=f"arr{rep}")
        a = _drive_arrivals(stall, _clone_bursts(arr_bursts, f"a{rep}s"),
                            interval=interval)
        b = _drive_arrivals(chunked, _clone_bursts(arr_bursts, f"a{rep}c"),
                            interval=interval)
        arr_match &= a.pop("outputs") == b.pop("outputs")
        runs_stall.append(a)
        runs_chunk.append(b)
    assert arr_match, "chunked outputs diverged under continuous arrival"
    med = lambda runs: sorted(
        runs, key=lambda r: r.get("vqa_ttft_p50_ms", 0.0))[len(runs) // 2]
    r_arr_stall, r_arr_chunk = med(runs_stall), med(runs_chunk)
    # cumulative guard counters: report end-of-bench totals, not whichever
    # rep the median picked
    r_arr_stall["steady_recompiles"] = \
        stall.scheduler_stats()["steady_recompiles"]
    r_arr_chunk["steady_recompiles"] = \
        chunked.scheduler_stats()["steady_recompiles"]

    sched = chunked.scheduler_stats()
    ratio = lambda a, b: round(a / max(b, 1e-9), 3)
    return {
        "slots": slots, "grid": grid, "region_tokens": ac.n_regions,
        "bursts": bursts, "new_scenes_per_burst": new_scenes,
        "fanout": fanout, "chunk": chunked._chunk,
        "token_budget": chunked._token_budget,
        "steady_decode_tokens_per_s": steady,
        "steady_decode_ratio": ratio(steady["chunked"], steady["stall"]),
        "saturation": {"stall": r_sat_stall, "chunked": r_sat_chunk},
        "arrival_interval_s": round(interval, 4),
        "continuous_arrival": {"stall": r_arr_stall,
                               "chunked": r_arr_chunk},
        "vqa_ttft_p50_speedup": ratio(
            r_arr_stall.get("vqa_ttft_p50_ms", 0.0),
            r_arr_chunk.get("vqa_ttft_p50_ms", 1e9)),
        "vqa_ttft_p99_speedup": ratio(
            r_arr_stall.get("vqa_ttft_p99_ms", 0.0),
            r_arr_chunk.get("vqa_ttft_p99_ms", 1e9)),
        "decode_gap_p99_speedup": ratio(
            r_arr_stall.get("decode_gap_p99_ms", 0.0),
            r_arr_chunk.get("decode_gap_p99_ms", 1e9)),
        "decode_gap_max_speedup": ratio(
            r_arr_stall.get("decode_gap_max_ms", 0.0),
            r_arr_chunk.get("decode_gap_max_ms", 1e9)),
        "outputs_match": sat_match and arr_match,
        "scheduler": {k: sched[k] for k in
                      ("fused_steps", "stall_steps", "budget",
                       "budget_utilization", "tokens_per_step")},
    }


# ---------------------------------------------------------------------------
# overload control: sustained over-capacity arrivals, mixed priorities
# ---------------------------------------------------------------------------

def _overload_stream(ac: EO.EOAdapterConfig, n: int, urgent_frac: float,
                     seed: int) -> List[Request]:
    """Saturation traffic, the paper's disaster-monitoring mix: mostly bulk
    det mapping work (long N_r-token answers, ``PRIORITY_BULK``) with
    urgent vqa queries interspersed (1-token answers,
    ``PRIORITY_URGENT``) — the class whose TTFT must hold at saturation.
    One fresh scene per request: every admission carries its full
    worst-case page demand."""
    from repro.serving.request import PRIORITY_BULK, PRIORITY_URGENT
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", max(n, 2), seed=seed, cfg=eo_cfg)
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        img = data["images"][i % len(data["images"])]
        if rng.rand() < urgent_frac:
            reqs.append(Request(task="vqa", image=img, prompt=i % 2,
                                scene_id=f"ov-{i}",
                                priority=PRIORITY_URGENT))
        else:
            reqs.append(Request(task="det", image=img, prompt=0,
                                scene_id=f"ov-{i}", priority=PRIORITY_BULK))
    return reqs


def _clone_overload(stream: List[Request], tag: str) -> List[Request]:
    out = []
    for r in stream:
        c = Request(task=r.task, image=r.image, prompt=r.prompt,
                    scene_id=f"{tag}-{r.scene_id}", priority=r.priority)
        c.request_id = r.request_id
        out.append(c)
    return out


def _drive_overload(core: EngineCore, stream: List[Request],
                    interval: float, controlled: bool) -> Dict[str, object]:
    """Serve requests arriving every ``interval`` seconds.

    ``controlled`` engines take arrivals through ``submit_many`` (bounded
    priority queue, explicit rejections polled via ``take_rejected``); the
    baseline models the pre-overload deployment — an UNBOUNDED host-side
    FIFO in front of ``admit_many``, which is exactly the failure mode the
    layer replaces.  TTFT is measured from ARRIVAL, so queue wait — either
    queue — is charged."""
    from repro.serving.request import PRIORITY_BULK, PRIORITY_URGENT
    pending = [(i * interval, r) for i, r in enumerate(stream)]
    arrivals: Dict[int, float] = {}
    due: List[Request] = []
    outputs: Dict[int, list] = {}
    rejected = []
    fifo_peak = 0
    core.stats["request_log"].clear()
    t0 = time.perf_counter()
    while (pending or due or core.active_count() > 0
           or core.queue_depth() > 0):
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            rel, r = pending.pop(0)
            arrivals[r.request_id] = t0 + rel
            due.append(r)
        if due and controlled:
            core.submit_many(due)
            due = []
        elif due:
            n = min(len(due), len(core.free_slots()))
            if n:
                core.admit_many(due[:n])
                del due[:n]
            fifo_peak = max(fifo_peak, len(due))
        if core.active_count() > 0 or core.queue_depth() > 0:
            for req, toks in core.step():
                outputs[req.request_id] = toks.tolist()
            if controlled:
                rejected += core.take_rejected()
        elif pending:
            time.sleep(max(min(pending[0][0] - now, 1e-3), 0.0))
    jax.block_until_ready(core._slot_logits)
    dt = time.perf_counter() - t0

    ms = lambda x: round(float(x) * 1e3, 3)
    log = core.stats["request_log"]
    rec: Dict[str, object] = {
        "completed": len(outputs),
        "rejected": len(rejected),
        "wall_s": round(dt, 4),
        "completed_per_s": round(len(outputs) / dt, 2),
        "queue_peak": (core.scheduler_stats()["overload"]["queue_peak"]
                       if controlled else fifo_peak),
        "steady_recompiles":
            core.scheduler_stats()["steady_recompiles"],
    }
    for name, prio in (("urgent", PRIORITY_URGENT), ("bulk", PRIORITY_BULK)):
        ttft = [r["t_first"] - arrivals[r["request_id"]] for r in log
                if r.get("priority", 0) == prio
                and r["request_id"] in arrivals]
        if ttft:
            rec[f"{name}_completed"] = len(ttft)
            rec[f"{name}_ttft_p50_ms"] = ms(np.percentile(ttft, 50))
            rec[f"{name}_ttft_p99_ms"] = ms(np.percentile(ttft, 99))
    rec["outputs"] = outputs
    rec["rejected_ids"] = sorted(r.request_id for r, _ in rejected)
    return rec


def bench_overload(*, slots: int, n_req: int, urgent_frac: float,
                   queue_cap: int, seed: int, smoke: bool,
                   kv_dtype: str = None) -> Dict[str, object]:
    """Sustained over-capacity serving (offered load ≈ 2× measured service
    rate), overload control ON vs OFF.

    The controlled engine must degrade gracefully — bounded queue, explicit
    rejections, urgent p99 TTFT held by priority admission + preemption —
    while the baseline's unbounded FIFO makes every class's tail grow with
    the backlog.  Every completed answer (preempted-then-resumed included)
    is asserted token-for-token equal to the uncontended dense oracle, and
    the controlled engine's pool must drain to the cache-only state."""
    import jax.numpy as jnp
    from repro.serving.admission import OverloadConfig
    from repro.serving.kv_pool import TRASH_PAGE
    sat_cfg, _ = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    params = EO.init_adapter(jax.random.PRNGKey(seed), sat_cfg, ac)
    tier = TierModel(params, sat_cfg)
    base = EngineCore(tier, ac,
                      EngineCoreConfig(slots=slots, answer_vocab=9,
                                       kv_dtype=kv_dtype))
    ctrl = EngineCore(tier, ac,
                      EngineCoreConfig(slots=slots, answer_vocab=9,
                                       kv_dtype=kv_dtype,
                                       overload=OverloadConfig(
                                           queue_cap=queue_cap)))
    base.warmup()
    ctrl.warmup()
    stream = _overload_stream(ac, n_req, urgent_frac, seed)

    # uncontended oracle per request.  Exact engines check against a dense
    # engine (the strongest cross-impl oracle); under ``kv_dtype`` the
    # oracle must share the engines' numerics — dense stays fp-exact by
    # design — so the flat-out service-rate probe below doubles as the
    # uncontended paged oracle.  Either way the invariant gated here is the
    # same: contention, preemption and recompute never change a request's
    # tokens.
    oracle: Dict[int, list] = {}
    if kv_dtype is None:
        dense = EngineCore(tier, ac,
                           EngineCoreConfig(slots=2, answer_vocab=9,
                                            cache_impl="dense"))
        by_task: Dict[str, List[Request]] = {}
        for r in stream:
            by_task.setdefault(r.task, []).append(r)
        for task, rs in by_task.items():
            images = jnp.asarray(np.stack([np.asarray(r.image)
                                           for r in rs]))
            prompts = jnp.asarray(np.array([r.prompt for r in rs],
                                           np.int32))
            toks, _ = dense.generate(task, images, prompts, 9)
            for r, t in zip(rs, np.asarray(toks)):
                oracle[r.request_id] = t.tolist()

    # service-rate probe: the baseline serves the stream flat-out, which
    # calibrates the arrival interval to 2× the measured capacity
    probe = _drive_overload(base, _clone_overload(stream, "p"),
                            interval=0.0, controlled=False)
    probe_outputs = probe.pop("outputs")
    if kv_dtype is not None:
        oracle = probe_outputs
    interval = 0.5 * probe["wall_s"] / max(n_req, 1)

    r_base = _drive_overload(base, _clone_overload(stream, "b"),
                             interval, controlled=False)
    r_ctrl = _drive_overload(ctrl, _clone_overload(stream, "c"),
                             interval, controlled=True)

    outs_base = r_base.pop("outputs")
    outs_ctrl = r_ctrl.pop("outputs")
    r_base.pop("rejected_ids")
    rejected_ids = set(r_ctrl.pop("rejected_ids"))
    match = (all(outs_base[rid] == oracle[rid] for rid in outs_base)
             and all(outs_ctrl[rid] == oracle[rid] for rid in outs_ctrl))
    assert match, "overload outputs diverged from the uncontended oracle"
    # explicit accounting: every submitted request either completed or was
    # explicitly rejected — nothing silently vanished
    assert set(outs_ctrl) | rejected_ids == {r.request_id for r in stream}
    # bounded queue + pool drained to the cache-only state
    assert r_ctrl["queue_peak"] <= queue_cap
    st = ctrl._prefix.stats()
    assert st["entries_in_use"] == 0
    assert ctrl._pool.pages_in_use == st["shared_pages"]
    assert (ctrl._bt_np == TRASH_PAGE).all()

    ol = ctrl.scheduler_stats()["overload"]
    ratio = lambda a, b: round(a / max(b, 1e-9), 3)
    rec = {
        "slots": slots, "requests": n_req, "urgent_frac": urgent_frac,
        "queue_cap": queue_cap,
        "arrival_interval_s": round(interval, 5),
        "service_probe_wall_s": probe["wall_s"],
        "baseline": r_base,
        "controlled": r_ctrl,
        "urgent_ttft_p50_speedup": ratio(
            r_base.get("urgent_ttft_p50_ms", 0.0),
            r_ctrl.get("urgent_ttft_p50_ms", 1e9)),
        "urgent_ttft_p99_speedup": ratio(
            r_base.get("urgent_ttft_p99_ms", 0.0),
            r_ctrl.get("urgent_ttft_p99_ms", 1e9)),
        "preemptions": ol["preemptions"],
        "admissions_deferred": ol["admissions_deferred"],
        "rejections": ol["rejections"],
        "readmit_wait_ms": ol["readmit_wait_ms"],
        "outputs_match": match,
    }
    if not smoke:
        # the acceptance bar: priority admission + preemption must hold the
        # urgent tail at least 2× better than FIFO under 2× offered load
        # (skipped in CI smoke, where single-request timings are noise)
        assert rec["urgent_ttft_p99_speedup"] >= 2.0, rec
    return rec


# ---------------------------------------------------------------------------
# quantized paged KV: int8 pools + in-kernel dequant vs the exact-fp engine
# ---------------------------------------------------------------------------

def bench_quantized(*, slots: int, scenes: int, fanout: int, seed: int,
                    smoke: bool, kv_dtype: str = "int8"
                    ) -> Dict[str, object]:
    """The quantized-vs-fp record: same scene-fan-out stream served by the
    exact paged engine and the ``kv_dtype`` engine (int8 or fp8 e4m3), plus
    an admission-capacity probe under ONE shared pool byte budget.

    Three claims, measured:

    1. **footprint** — ``kv_bytes_per_slot`` with scales included must be
       ≤ 0.55× the fp engine's (the honest ratio: f32 scale buffers ride
       the same pools they describe; fp8 pages cost exactly int8 bytes);
    2. **agreement** — greedy outputs are compared token-by-token via
       ``kv_quant.compare_outputs``; divergence (possible in principle —
       quantized KV noise can flip a near-tie argmax) is reported per
       request with first-divergence positions, never hidden;
    3. **capacity** — two overload-controlled engines sized from the SAME
       ``pool_bytes`` budget (picked so the fp engine is page-bound below
       its slot count) serve a burst of distinct-scene requests; the
       quantized engine's cheaper pages must admit measurably more
       concurrent work.
    """
    from repro.core import pipeline as P
    from repro.kernels import kv_quant

    # Agreement is measured on a briefly proxy-trained tier: a random-init
    # model's logits are near-uniform, so ANY perturbation — including the
    # ~0.4% (int8) / ~3.6% (fp8) relative error of quantized KV — flips
    # near-tie argmaxes; a trained model's greedy margins dominate the
    # quantization noise the way a deployed checkpoint's do.  The
    # comparison itself stays exact and per-token either way.
    sat_cfg, _ = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    train = {t: synthetic.make_dataset(t, 96, seed=seed, cfg=eo_cfg)
             for t in ("vqa", "cls", "det")}
    # training differentiates through attention — pin the ref impl for the
    # duration (the serving kernels define no autodiff rules, so a process-
    # wide "pallas_interpret" override would break value_and_grad)
    from repro.kernels import ops
    prev_impl = ops.set_default_impl("ref")
    try:
        params, _ = P.train_proxy(sat_cfg, ac, train,
                                  steps=8 if smoke else 40, seed=seed)
    finally:
        ops.set_default_impl(prev_impl)
    tier = TierModel(params, sat_cfg)

    per = {}
    for name, dt in (("fp", None), (kv_dtype, kv_dtype)):
        per[name] = bench_fanout("paged", slots=slots, scenes=scenes,
                                 fanout=fanout, seed=seed, kv_dtype=dt,
                                 tier=tier)
    outs = {name: r.pop("outputs") for name, r in per.items()}
    # fan-out outputs are creation-ordered lists: key by position
    agreement = kv_quant.compare_outputs(dict(enumerate(outs["fp"])),
                                         dict(enumerate(outs[kv_dtype])))
    ratio = (per[kv_dtype]["kv_bytes_per_slot"]
             / max(per["fp"]["kv_bytes_per_slot"], 1))

    # -- capacity under one byte budget ------------------------------------
    from repro.serving.admission import OverloadConfig
    cap_slots = 4 if smoke else 12
    probe = EngineCore(tier, ac, EngineCoreConfig(slots=cap_slots,
                                                  answer_vocab=9))
    # budget: the fp engine fits the floor + ~cap_slots/3 distinct-scene
    # admissions, so pages (not slots) bind admission for fp but not int8
    demand = probe.page_demand(Request(task="det", image=np.zeros(
        (ac.image_size, ac.image_size, ac.channels), np.float32), prompt=0))
    budget = probe._page_nbytes_stack() * (
        1 + probe._pages_per_slot + demand * max(cap_slots // 3, 1))
    capacity = {}
    for name, dt in (("fp", None), (kv_dtype, kv_dtype)):
        core = EngineCore(tier, ac, EngineCoreConfig(
            slots=cap_slots, answer_vocab=9, pool_bytes=budget, kv_dtype=dt,
            overload=OverloadConfig(queue_cap=2 * cap_slots)))
        core.warmup()
        burst = [Request(task="det",
                         image=np.zeros((ac.image_size, ac.image_size,
                                         ac.channels), np.float32),
                         prompt=0, scene_id=f"cap-{name}-{i}")
                 for i in range(2 * cap_slots)]
        core.submit_many(burst)
        peak, done = 0, 0
        while core.active_count() or core.queue_depth():
            peak = max(peak, core.active_count())
            done += len(core.step())
        capacity[name] = {"n_pages": core._n_pages,
                          "peak_concurrent": peak, "completed": done}

    rec = {
        "slots": slots, "scenes": scenes, "fanout": fanout,
        "kv_dtype": kv_dtype,
        "fp": per["fp"], kv_dtype: per[kv_dtype],
        "kv_bytes_per_slot_ratio": round(ratio, 4),
        "bytes_ratio_ok": ratio <= 0.55,
        "agreement": agreement,
        "outputs_match": agreement["match"],
        "tokens_per_s_ratio": round(
            per[kv_dtype]["answer_tokens_per_s"]
            / max(per["fp"]["answer_tokens_per_s"], 1e-9), 3),
        "capacity": {"pool_bytes_budget": budget, **capacity,
                     "page_ratio": round(capacity[kv_dtype]["n_pages"]
                                         / capacity["fp"]["n_pages"], 3)},
        "capacity_up": (capacity[kv_dtype]["peak_concurrent"]
                        > capacity["fp"]["peak_concurrent"]),
    }
    return rec


def bench_sharded(*, dp: int, tp: int, slots: int, scenes: int,
                  fanout: int, seed: int,
                  kv_dtype: str = None) -> Dict[str, object]:
    """The tentpole record: the SAME scene-fan-out stream served by the
    single-device paged engine and by the mesh engine at dp×tp — outputs
    must be token-for-token equal, per-device KV bytes per slot must shrink
    by the attention-sharding degree, and neither engine may recompile
    after warmup (``--check-compiles`` gates on the guard verdict).

    Host-mesh caveat: dp×tp "devices" here are XLA host-platform slices of
    one CPU, so tokens/s is a *correctness-under-sharding* probe (collective
    overhead at toy scale), not a speedup claim — the per-device footprint
    and the routing/occupancy numbers are the transferable results."""
    from repro.launch.mesh import make_host_mesh

    n_dev = len(jax.devices())
    if n_dev < dp * tp:
        raise SystemExit(
            f"--mesh dp{dp},tp{tp} needs {dp * tp} devices, have {n_dev} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before process start for a host-mesh run)")
    sat_cfg, _ = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    params = EO.init_adapter(jax.random.PRNGKey(seed), sat_cfg, ac)
    tier = TierModel(params, sat_cfg)

    single = bench_fanout("paged", slots=slots, scenes=scenes,
                          fanout=fanout, seed=seed, kv_dtype=kv_dtype,
                          tier=tier)
    mesh = make_host_mesh(model=tp, data=dp)
    sharded = bench_fanout("paged", slots=slots, scenes=scenes,
                           fanout=fanout, seed=seed, kv_dtype=kv_dtype,
                           tier=tier, mesh=mesh)
    outputs_match = single.pop("outputs") == sharded.pop("outputs")

    return {
        "mesh": {"data": dp, "model": tp},
        "slots": slots, "scenes": scenes, "fanout": fanout,
        "kv_dtype": kv_dtype,
        "single": single, "sharded": sharded,
        "outputs_match": outputs_match,
        "tokens_per_s_ratio": round(
            sharded["answer_tokens_per_s"]
            / max(single["answer_tokens_per_s"], 1e-9), 3),
        "kv_bytes_per_slot_single": single["kv_bytes_per_slot"],
        "kv_bytes_per_slot_device": sharded.get("kv_bytes_per_slot_device",
                                                sharded["kv_bytes_per_slot"]),
    }


def _collect_recompiles(obj, path=""):
    """Every ``steady_recompiles`` counter anywhere in the record tree —
    one per engine each workload drove — as (path, count) pairs."""
    found = []
    if isinstance(obj, dict):
        for k, v in obj.items():
            p = f"{path}.{k}" if path else str(k)
            if k == "steady_recompiles" and isinstance(v, (int, float)):
                found.append((path or "run", int(v)))
            else:
                found.extend(_collect_recompiles(v, p))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            found.extend(_collect_recompiles(v, f"{path}[{i}]"))
    return found


HISTORY_CAP = 12
#: the file's only top-level keys besides ``history`` — schema metadata.
#: Every RUN record (config included) lives inside ``history[backend]``;
#: schema 2 fixed the v1 layout where the latest run's record (and its
#: ``config``) sat at the top level, clobbered by whichever leg ran last
#: and masquerading as a description of the whole file.
SCHEMA = {"benchmark": "serving_bench", "schema": 2}


def _load_history(out_path: str) -> Dict[str, List[Dict]]:
    """The backend-keyed run history from either file layout.  Legacy
    (schema-1) files carried the latest run at the top level — it migrates
    into its backend's list; pre-matrix files carried a flat history list
    with no backend discipline — every record in them came from this
    container's CPU runs, so the flat list migrates under ``"cpu"``."""
    if not os.path.exists(out_path):
        return {}
    try:
        with open(out_path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        return {}
    h = prev.pop("history", {})
    history = {"cpu": h} if isinstance(h, list) else h
    if any(k not in SCHEMA for k in prev):
        # schema-1: the remaining top level IS the last run's record
        pb = prev.get("config", {}).get("backend", "cpu")
        if pb not in BACKENDS:
            pb = "cpu"                      # old records stored raw
        history.setdefault(pb, []).append(prev)
    return history


def _fold_history(out_path: str, run: Dict, backend: str) -> Dict:
    """Append this run to ``history[backend]`` (bounded) and return the
    full file record: schema metadata on top, every run — THIS one
    included, its config inside its own entry — in the history."""
    history = _load_history(out_path)
    history.setdefault(backend, []).append(run)
    return {**SCHEMA,
            "history": {b: h[-HISTORY_CAP:] for b, h in history.items()}}


#: headline tokens/s per workload — the metrics ``--trend`` charts and the
#: regression guard compares run-over-run
def _headline_metrics(entry: Dict) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for impl, r in entry.get("results", {}).items():
        out[f"impl.{impl}"] = r["decode_tokens_per_s"]
    for ci, r in entry.get("fanout", {}).items():
        out[f"fanout.{ci}"] = r["answer_tokens_per_s"]
    if "spec" in entry:
        out["spec.greedy"] = entry["spec"]["greedy"]["decode_tokens_per_s"]
        out["spec.spec"] = entry["spec"]["spec"]["decode_tokens_per_s"]
    if "chunked" in entry:
        for name, v in entry["chunked"]["steady_decode_tokens_per_s"].items():
            out[f"chunked.steady.{name}"] = v
    if "overload" in entry:
        out["overload.controlled"] = \
            entry["overload"]["controlled"]["completed_per_s"]
    if "quantized" in entry:
        q = entry["quantized"]
        dt = q.get("kv_dtype", "int8")
        for name in ("fp", dt):
            if name in q:
                out[f"quantized.{name}"] = q[name]["answer_tokens_per_s"]
    if "sharded" in entry:
        out["sharded.single"] = \
            entry["sharded"]["single"]["answer_tokens_per_s"]
        out["sharded.sharded"] = \
            entry["sharded"]["sharded"]["answer_tokens_per_s"]
    return out


def _print_trend(out_path: str) -> int:
    """``--trend``: the per-backend, per-workload tokens/s trajectory
    across the recorded history — oldest run first, one line per metric,
    smoke runs flagged (their absolute numbers are not comparable to full
    runs, so each line groups a single (smoke, kv_dtype, mesh) regime)."""
    history = _load_history(out_path)
    if not history:
        print(f"no history in {out_path}")
        return 1
    for backend in sorted(history):
        runs = history[backend]
        print(f"== {backend} ({len(runs)} runs) ==")
        by_regime: Dict[tuple, List[Dict]] = {}
        for e in runs:
            c = e.get("config", {})
            key = (bool(c.get("smoke")), c.get("kv_dtype"), c.get("mesh"))
            by_regime.setdefault(key, []).append(e)
        for (smoke, dt, mesh), entries in sorted(by_regime.items(),
                                                 key=str):
            tags = [t for t in ("smoke" if smoke else "full",
                                dt and f"kv={dt}", mesh and f"mesh={mesh}")
                    if t]
            print(f"  [{' '.join(tags)}]")
            series: Dict[str, List[str]] = {}
            for e in entries:
                m = _headline_metrics(e)
                for k in sorted(m):
                    series.setdefault(k, []).append(f"{m[k]:.1f}")
            for k, vals in sorted(series.items()):
                print(f"    {k:24s} {'  '.join(vals)}  tok/s")
    return 0


def _regression_failures(history: Dict[str, List[Dict]], run: Dict,
                         backend: str, max_drop: float = 0.20
                         ) -> List[str]:
    """``--regress-guard``: headline tokens/s of this run vs the LAST
    comparable same-backend history entry (same smoke/kv_dtype/mesh
    regime — absolute numbers across regimes mean nothing).  Returns the
    metrics that dropped more than ``max_drop``; empty = pass (including
    the no-prior-run case)."""
    cfg = run.get("config", {})
    key = lambda c: (bool(c.get("smoke")), c.get("kv_dtype"),
                     c.get("mesh"))
    prior = [e for e in history.get(backend, [])
             if key(e.get("config", {})) == key(cfg)]
    if not prior:
        return []
    prev = _headline_metrics(prior[-1])
    cur = _headline_metrics(run)
    fails = []
    for k in sorted(set(prev) & set(cur)):
        if prev[k] > 0 and cur[k] < (1.0 - max_drop) * prev[k]:
            fails.append(f"{k}: {prev[k]:.1f} -> {cur[k]:.1f} tok/s "
                         f"({cur[k] / prev[k]:.2f}x)")
    return fails


def _autotune_record(backend: str) -> Dict[str, object]:
    """The checked-in autotune result for this backend key, summarized for
    the bench record: winning configs + measured speedup over the
    hand-picked defaults (``kernels/autotune.py`` wrote the file)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "src", "repro", "kernels", "tuned",
                        f"{backend}.json")
    try:
        with open(path) as f:
            tuned = json.load(f)
    except (OSError, ValueError):
        return {}
    return {"configs": tuned.get("configs", {}),
            "speedup_vs_default": {
                k: {d: t["speedup_vs_default"] for d, t in per.items()}
                for k, per in tuned.get("timings_ms", {}).items()}}


# ---------------------------------------------------------------------------
# backends and workloads
# ---------------------------------------------------------------------------

#: cpu-interpret = the CPU backend with every kernel dispatch pinned to
#: ``pallas_interpret``: the Pallas TPU kernel BODIES (int8 dequant
#: included) execute in the serving loop instead of the jnp oracles.  Each
#: leg is its own process (a chip belongs to one process): pick the
#: platform with JAX_PLATFORMS and the label with --backend.
BACKENDS = ("cpu", "cpu-interpret", "gpu", "tpu")
#: "sharded" is NOT in the default "all" set: it needs dp×tp devices
#: (XLA_FLAGS host-platform slices on CPU) — run it via --mesh or an
#: explicit --workloads sharded
WORKLOADS = ("impl", "fanout", "spec", "chunked", "overload", "quantized",
             "sharded")
DEFAULT_WORKLOADS = tuple(w for w in WORKLOADS if w != "sharded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument("--det-frac", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", choices=["batched", "vmap", "both"],
                    default="both")
    ap.add_argument("--scenes", type=int, default=12,
                    help="fan-out workload: distinct captured scenes")
    ap.add_argument("--fanout", type=int, default=8,
                    help="queries per scene in the fan-out workload")
    ap.add_argument("--fanout-slots", type=int, default=16)
    ap.add_argument("--spec-gamma", type=int, default=4,
                    help="draft tokens verified per speculative step")
    ap.add_argument("--spec-requests", type=int, default=192)
    ap.add_argument("--spec-slots", type=int, default=16)
    ap.add_argument("--spec-det-frac", type=float, default=0.5,
                    help="det share of the spec stream (multi-token answers"
                         " are where drafting pays)")
    ap.add_argument("--spec-train-steps", type=int, default=120,
                    help="proxy-training steps for the drafter/verifier "
                         "pair (0 = untrained: equality still holds, "
                         "agreement — and thus speedup — does not)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk (region tokens per fused step) for "
                         "the chunked-prefill workload")
    ap.add_argument("--chunk-slots", type=int, default=24)
    ap.add_argument("--chunk-grid", type=int, default=16,
                    help="region grid of the chunked workload's scenes "
                         "(grid² region tokens — production-shaped tiles)")
    ap.add_argument("--chunk-bursts", type=int, default=10,
                    help="downlink bursts in the continuous-arrival "
                         "workload")
    ap.add_argument("--chunk-new-scenes", type=int, default=3,
                    help="freshly captured scenes per burst (det query "
                         "each)")
    ap.add_argument("--chunk-fanout", type=int, default=8,
                    help="urgent vqa queries per burst over the previous "
                         "burst's (resident) scenes")
    ap.add_argument("--overload-slots", type=int, default=8)
    ap.add_argument("--overload-requests", type=int, default=96)
    ap.add_argument("--overload-urgent-frac", type=float, default=0.2,
                    help="share of PRIORITY_URGENT vqa in the saturation "
                         "mix (the rest is PRIORITY_BULK det)")
    ap.add_argument("--overload-queue-cap", type=int, default=16,
                    help="bounded admission-queue capacity of the "
                         "controlled engine")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: prove the harness executes end-to-end")
    ap.add_argument("--check-compiles", action="store_true",
                    help="fail (exit 1) if any engine recompiled a jitted "
                         "step function after warmup — the CompileGuard "
                         "steady-state verdict across the plain, spec and "
                         "chunked workloads")
    ap.add_argument("--kv-dtype", choices=["int8", "fp8"], default=None,
                    help="run every paged engine quantized (int8 or fp8 "
                         "e4m3 pages, in-kernel dequant — fp8 also takes "
                         "the native-fp8 dot path); each workload's "
                         "existing output assertions then check the "
                         "quantized engines against their fp/dense "
                         "oracles")
    ap.add_argument("--backend", choices=["auto"] + list(BACKENDS),
                    default="auto",
                    help="backend label for this leg; cpu-interpret pins "
                         "kernel dispatch to pallas_interpret (kernel "
                         "bodies execute on CPU); gpu/tpu fail unless JAX "
                         "runs on that platform.  The JAX platform itself "
                         "is chosen via JAX_PLATFORMS before process start")
    ap.add_argument("--mesh", default=None, metavar="dp2,tp2",
                    help="device-mesh shape for the sharded workload "
                         "(launch.mesh.parse_mesh_shape syntax); implies "
                         "--workloads sharded unless workloads are given "
                         "explicitly")
    ap.add_argument("--workloads", default="all",
                    help="comma list of workloads to run "
                         f"({','.join(WORKLOADS)}; default all minus "
                         "sharded, which needs a multi-device process — "
                         "see --mesh)")
    ap.add_argument("--trend", action="store_true",
                    help="print the per-backend, per-workload tokens/s "
                         "trajectory from --out's recorded history and "
                         "exit (no benching)")
    ap.add_argument("--regress-guard", action="store_true",
                    help="fail (exit 1) if any headline tokens/s metric "
                         "drops >20%% against the last comparable "
                         "same-backend history entry (same smoke/kv-dtype/"
                         "mesh regime)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)

    if args.trend:
        return _print_trend(args.out)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    backend = (jax.default_backend() if args.backend == "auto"
               else args.backend)
    platform = backend.split("-")[0]
    if jax.default_backend() != platform:
        # never measure one platform under another's name
        raise SystemExit(f"--backend {backend} needs JAX on {platform}, "
                         f"found {jax.default_backend()}")
    if backend == "cpu-interpret":
        from repro.kernels import ops
        ops.set_default_impl("pallas_interpret")

    if args.workloads == "all":
        wl = ({"sharded"} if args.mesh is not None
              else set(DEFAULT_WORKLOADS))
    else:
        wl = {w.strip() for w in args.workloads.split(",") if w.strip()}
    unknown = wl - set(WORKLOADS)
    if unknown:
        raise SystemExit(f"unknown workloads: {sorted(unknown)}")

    if args.smoke:
        args.slots, args.steps, args.warmup = 4, 8, 2
        args.scenes, args.fanout, args.fanout_slots = 2, 3, 4
        args.spec_requests, args.spec_slots = 6, 2
        args.spec_gamma, args.spec_train_steps = 2, 0
        args.chunk_slots, args.chunk_grid = 3, 8
        args.chunk_bursts, args.chunk_new_scenes, args.chunk_fanout = 3, 1, 2
        args.overload_slots, args.overload_requests = 3, 20
        args.overload_queue_cap = 4

    matches: List[bool] = []
    rec: Dict[str, object] = {
        "config": {"slots": args.slots, "steps": args.steps,
                   "warmup": args.warmup, "det_frac": args.det_frac,
                   "scenes": args.scenes, "fanout": args.fanout,
                   "fanout_slots": args.fanout_slots,
                   "backend": backend, "jax_backend": jax.default_backend(),
                   "kv_dtype": args.kv_dtype, "mesh": args.mesh,
                   "workloads": sorted(wl), "smoke": args.smoke},
    }

    if "impl" in wl:
        impls = ["batched", "vmap"] if args.impl == "both" else [args.impl]
        results = {}
        for impl in impls:
            r = bench_impl(impl, slots=args.slots, steps=args.steps,
                           warmup=args.warmup, det_frac=args.det_frac,
                           seed=args.seed, kv_dtype=args.kv_dtype)
            results[impl] = r
            print(f"[{impl:7s}] {r['decode_tokens_per_s']:9.1f} tok/s  "
                  f"{r['steps_per_s']:7.2f} steps/s  "
                  f"{r['admissions_per_s']:6.2f} admits/s  "
                  f"({r['wall_s']}s wall)", flush=True)
        rec["results"] = results
        if "batched" in results and "vmap" in results:
            rec["speedup_tokens_per_s"] = round(
                results["batched"]["decode_tokens_per_s"]
                / results["vmap"]["decode_tokens_per_s"], 3)
            print(f"speedup (batched/vmap): {rec['speedup_tokens_per_s']}×")

    if "fanout" in wl:
        # -- scene fan-out: paged prefix sharing vs dense ------------------
        fanout = {}
        for cache_impl in ("paged", "dense"):
            r = bench_fanout(cache_impl, slots=args.fanout_slots,
                             scenes=args.scenes, fanout=args.fanout,
                             seed=args.seed, kv_dtype=args.kv_dtype)
            fanout[cache_impl] = r
            print(f"[fanout {cache_impl:5s}] "
                  f"{r['answer_tokens_per_s']:9.1f} "
                  f"tok/s  prefill {r['prefill_tokens']:6d} tok  "
                  f"hit-rate {r['prefix_hit_rate']:.2f}  "
                  f"kv/slot {r['kv_bytes_per_slot']} B  "
                  f"({r['wall_s']}s wall)", flush=True)
        paged_outs = fanout["paged"].pop("outputs")
        dense_outs = fanout["dense"].pop("outputs")
        outputs_match = (paged_outs == dense_outs)
        if args.kv_dtype is None:
            print(f"fan-out outputs paged == dense: {outputs_match}")
            matches.append(outputs_match)
        else:
            # the dense engine is fp-exact by design, so this comparison
            # crosses dtypes: report token-level divergence instead of
            # gating on it — the GATED cross-dtype agreement check is the
            # quantized workload (same fan-out stream, trained tier).
            from repro.kernels import kv_quant
            ag = kv_quant.compare_outputs(dict(enumerate(dense_outs)),
                                          dict(enumerate(paged_outs)))
            rec["fanout_agreement"] = ag
            print(f"fan-out paged-{args.kv_dtype} vs dense-fp "
                  f"(cross-dtype, reported not gated): "
                  f"{ag['n_tokens_diverged']}/{ag['n_tokens']} tokens "
                  f"diverged across {ag['n_requests_diverged']}/"
                  f"{ag['n_requests']} requests")
        rec["fanout"] = fanout
        rec["fanout_outputs_match"] = outputs_match
        rec["fanout_prefill_token_ratio"] = round(
            fanout["dense"]["prefill_tokens"]
            / max(fanout["paged"]["prefill_tokens"], 1), 3)
        print(f"fan-out prefill-token ratio (dense/paged): "
              f"{rec['fanout_prefill_token_ratio']}×")

    if "spec" in wl:
        # -- cascade-speculative decoding: compact drafts, regular verifies
        spec = bench_spec(slots=args.spec_slots, n_req=args.spec_requests,
                          det_frac=args.spec_det_frac, gamma=args.spec_gamma,
                          train_steps=args.spec_train_steps, seed=args.seed,
                          kv_dtype=args.kv_dtype)
        print(f"[spec γ={spec['gamma']}] "
              f"{spec['spec']['decode_tokens_per_s']:9.1f} tok/s vs "
              f"{spec['greedy']['decode_tokens_per_s']:9.1f} greedy "
              f"({spec['speedup_tokens_per_s']}×)  "
              f"accept {spec['accept_rate']:.2f}  "
              f"{spec['tokens_per_slot_step']:.2f} tok/slot-step  "
              f"piggyback {spec['piggyback_frac']:.2f}")
        print(f"spec outputs == greedy: {spec['outputs_match']}")
        matches.append(spec["outputs_match"])
        rec["spec"] = spec

    if "chunked" in wl:
        # -- chunked prefill: token-budget fused steps vs admission stalls
        chunked = bench_chunked(slots=args.chunk_slots, grid=args.chunk_grid,
                                bursts=args.chunk_bursts,
                                new_scenes=args.chunk_new_scenes,
                                fanout=args.chunk_fanout, chunk=args.chunk,
                                seed=args.seed, smoke=args.smoke,
                                kv_dtype=args.kv_dtype)
        ca = chunked["continuous_arrival"]
        print(f"[chunked C={chunked['chunk']} grid={chunked['grid']}] "
              f"continuous arrival "
              f"(interval {chunked['arrival_interval_s']}s): "
              f"urgent-vqa TTFT p50 "
              f"{ca['chunked'].get('vqa_ttft_p50_ms', 0):.1f}ms vs "
              f"{ca['stall'].get('vqa_ttft_p50_ms', 0):.1f}ms stall "
              f"({chunked['vqa_ttft_p50_speedup']}×; p99 "
              f"{chunked['vqa_ttft_p99_speedup']}×)")
        print(f"          decode-gap p99 "
              f"{ca['chunked'].get('decode_gap_p99_ms', 0):.1f}ms vs "
              f"{ca['stall'].get('decode_gap_p99_ms', 0):.1f}ms "
              f"({chunked['decode_gap_p99_speedup']}×; max "
              f"{chunked['decode_gap_max_speedup']}×)  steady-decode ratio "
              f"{chunked['steady_decode_ratio']}")
        print(f"chunked outputs == stall: {chunked['outputs_match']}")
        matches.append(chunked["outputs_match"])
        rec["chunked"] = chunked

    if "overload" in wl:
        # -- overload control: sustained over-capacity, mixed priorities ---
        overload = bench_overload(slots=args.overload_slots,
                                  n_req=args.overload_requests,
                                  urgent_frac=args.overload_urgent_frac,
                                  queue_cap=args.overload_queue_cap,
                                  seed=args.seed, smoke=args.smoke,
                                  kv_dtype=args.kv_dtype)
        ob, oc = overload["baseline"], overload["controlled"]
        print(f"[overload q={overload['queue_cap']}] 2x saturation: "
              f"urgent TTFT "
              f"p99 {oc.get('urgent_ttft_p99_ms', 0):.1f}ms vs "
              f"{ob.get('urgent_ttft_p99_ms', 0):.1f}ms FIFO "
              f"({overload['urgent_ttft_p99_speedup']}×; p50 "
              f"{overload['urgent_ttft_p50_speedup']}×)  "
              f"queue peak {oc['queue_peak']}/{overload['queue_cap']} vs "
              f"{ob['queue_peak']} unbounded  "
              f"preempt {overload['preemptions']}  "
              f"rejected {oc['rejected']}/{overload['requests']}")
        print(f"overload outputs == oracle: {overload['outputs_match']}")
        matches.append(overload["outputs_match"])
        rec["overload"] = overload

    if "quantized" in wl:
        # -- quantized paged KV: int8/fp8 vs the exact-fp engine -----------
        qdt = args.kv_dtype or "int8"
        quant = bench_quantized(slots=args.fanout_slots, scenes=args.scenes,
                                fanout=args.fanout, seed=args.seed,
                                smoke=args.smoke, kv_dtype=qdt)
        cap = quant["capacity"]
        print(f"[quantized {qdt}] kv/slot ratio "
              f"{quant['kv_bytes_per_slot_ratio']} (≤0.55: "
              f"{quant['bytes_ratio_ok']})  tok/s ratio "
              f"{quant['tokens_per_s_ratio']}  capacity "
              f"{cap[qdt]['peak_concurrent']} vs "
              f"{cap['fp']['peak_concurrent']} concurrent "
              f"({cap[qdt]['n_pages']} vs {cap['fp']['n_pages']} pages "
              f"under {cap['pool_bytes_budget']} B)")
        ag = quant["agreement"]
        print(f"{qdt} outputs == fp: {quant['outputs_match']}  "
              f"({ag['n_requests_diverged']}/{ag['n_requests']} requests "
              f"diverged, first at {ag['first_divergences'] or '-'})")
        matches.append(quant["outputs_match"] and quant["bytes_ratio_ok"]
                       and quant["capacity_up"])
        rec["quantized"] = quant

    if "sharded" in wl:
        # -- sharded serving: TP attention + DP slot split on a mesh -------
        from repro.launch.mesh import parse_mesh_shape
        dp, tp = parse_mesh_shape(args.mesh or "dp2,tp2")
        sharded = bench_sharded(dp=dp, tp=tp, slots=args.fanout_slots,
                                scenes=args.scenes, fanout=args.fanout,
                                seed=args.seed, kv_dtype=args.kv_dtype)
        sh = sharded["sharded"]
        print(f"[sharded dp{dp}×tp{tp}] "
              f"{sh['answer_tokens_per_s']:9.1f} tok/s vs "
              f"{sharded['single']['answer_tokens_per_s']:9.1f} "
              f"single-device ({sharded['tokens_per_s_ratio']}× on a "
              f"host mesh)  kv/slot/device "
              f"{sharded['kv_bytes_per_slot_device']} B vs "
              f"{sharded['kv_bytes_per_slot_single']} B single")
        if "per_shard" in sh:
            for row in sh["per_shard"]:
                print(f"          shard {row['shard']}: "
                      f"slots {row['slots']} (@{row['slot_offset']})  "
                      f"routed {row['routed']}  "
                      f"pages used {row.get('pages_used', 0)}")
        print(f"sharded outputs == single-device: "
              f"{sharded['outputs_match']}")
        matches.append(sharded["outputs_match"])
        rec["sharded"] = sharded

    recompiles = _collect_recompiles(rec)
    total_recompiles = sum(v for _, v in recompiles)
    rec["steady_recompiles_total"] = total_recompiles
    offenders = [f"{p}={v}" for p, v in recompiles if v]
    print(f"steady-state recompiles after warmup: {total_recompiles}"
          + (f"  ({', '.join(offenders)})" if offenders else ""))

    at = _autotune_record(backend)
    if at:
        rec["autotune"] = at

    regress = []
    if args.regress_guard:
        regress = _regression_failures(_load_history(args.out), rec,
                                       backend)
        for line in regress:
            print(f"REGRESSION: {line}")
        if not regress:
            print("regression guard: no headline metric dropped >20% vs "
                  "the last comparable run")

    out = _fold_history(args.out, rec, backend)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    n_hist = sum(len(h) for h in out["history"].values())
    print(f"wrote {args.out} (history: {n_hist} runs across "
          f"{sorted(out['history'])})")
    compiles_ok = not (args.check_compiles and total_recompiles)
    return 0 if (all(matches) and compiles_ok and not regress) else 1


if __name__ == "__main__":
    sys.exit(main())
