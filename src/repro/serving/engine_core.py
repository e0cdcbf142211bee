"""EngineCore — the single jitted execution substrate for Algorithm 1.

One ``EngineCore`` wraps one tier (``TierModel``) of the satellite-ground
cascade and owns every compiled entry point the serving layer needs:

- **batch path** (``encode`` / ``prefill`` / ``decode_chunk`` / ``generate``
  / ``token_features``): shape-stable ``jax.jit`` functions used by the
  ``CascadeExecutor`` for both the vectorised counterfactual evaluator and
  the per-request server.  Compilation is keyed only by (batch, chunk
  length), so repeated traffic at the same shapes never recompiles.
  ``encode_cached`` additionally memoises per-scene encodes for the serve
  path's scene fan-out traffic.

- **slot path** (``admit`` / ``admit_many`` / ``step``): a fixed-capacity
  slot table for true continuous batching.  Every slot holds one in-flight
  request's next-token logits and decode position; ``step`` advances *all*
  slots one token through **one** batched ``T.decode_step`` call over the
  whole table with a ``(B,)`` per-slot index vector — per-row RoPE
  positions, per-row KV scatter and per-row ragged attention masks all the
  way down to the flash-decoding kernel.  Finished slots free immediately
  and are refilled from the pending queue mid-stream.

The KV cache behind the slot table comes in two implementations
(``EngineCoreConfig.cache_impl``):

- ``"paged"`` (default): KV lives in a pool of fixed-size pages
  (``serving/kv_pool.py``) addressed through a per-slot block table that
  the decode step resolves page-indirectly (``kernels/decode_attention.py``
  scalar-prefetches the ``(B, pages)`` table next to the ``(B,)`` length
  vector).  ``admit_many`` keys the image-region prefill on a **scene
  hash**: the R region tokens are the prompt-independent prefix of every
  query over one captured scene, so their KV pages are prefilled once per
  scene, cached (LRU, ref-counted), and mapped **read-only** into each new
  request's block table — admission then only runs the 1-token prompt
  suffix through the decode step.  K queries over one scene prefill the
  ``N_r`` vision tokens once instead of K times, and a slot's KV footprint
  is its private pages plus an amortised share of the prefix.

- ``"dense"``: the pre-paging layout — one worst-case
  ``(slots, N_r + 1 + max_answer_len)`` cache slice per slot, whole-row
  prefill + scatter admission.  Kept as the token-for-token equivalence
  oracle (``tests/test_kv_pool.py``) exactly like the ``step_impl="vmap"``
  oracle of the batched-decode PR (which implies ``dense``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis.compile_guard import CompileGuard
from repro.configs.base import ATTN, HYBRID
from repro.core import eo_adapter as EO
from repro.distributed import collectives as CO
from repro.distributed import sharding as SH
from repro.kernels import kv_quant
from repro.models import transformer as T
from repro.serving.admission import (ADMITTED, QUEUED, REJECTED,
                                     REASON_EXPIRED, REASON_INFEASIBLE,
                                     REASON_QUEUE_FULL,
                                     AdmissionQueue, OverloadConfig,
                                     QueueEntry)
from repro.serving.kv_pool import (KVPagePool, PrefixCache, TRASH_PAGE,
                                   page_nbytes)
from repro.serving.request import Request, scene_key

Params = Dict[str, Any]


@dataclasses.dataclass
class EngineCoreConfig:
    slots: int = 8
    answer_vocab: int = 64
    max_answer_len: Optional[int] = None   # default: N_r (longest task = det)
    step_impl: str = "batched"             # "batched" | "vmap" (legacy oracle)
    cache_impl: str = "paged"              # "paged" | "dense" (oracle)
    page_size: int = 8                     # tokens per KV page (paged only)
    #: scenes the prefix cache keeps resident beyond the active slots'
    #: (None → slots); bounds the pool at
    #: slots·pages_per_slot + scenes·shared_pages_per_scene
    prefix_cache_scenes: Optional[int] = None
    #: speculative decoding: γ draft tokens per slot verified by ONE
    #: multi-token scoring step of this (regular) tier; the compact draft
    #: tier is passed to the ``EngineCore`` constructor.  0 = off — the
    #: non-speculative engine stays the token-for-token oracle, exactly as
    #: ``step_impl="vmap"`` and ``cache_impl="dense"`` are oracles.
    #: Requires the batched paged engine and attention-only stacks (paged
    #: rollback is only free for attention KV).
    spec_gamma: int = 0
    #: Sarathi-style chunked prefill: admission stops running the N_r-token
    #: scene prefill as one synchronous call (the engine's worst
    #: head-of-line-blocking latency event) and instead streams it into the
    #: paged KV cache ``prefill_chunk`` region tokens at a time, co-scheduled
    #: with the in-flight decode rows inside ONE fused token-budget step —
    #: decode never stops for admission.  0 = off (synchronous admission
    #: stays the token-for-token oracle, exactly as ``step_impl="vmap"`` /
    #: ``cache_impl="dense"`` / ``spec_gamma=0`` are oracles).  Values above
    #: ``n_regions`` clamp.  Requires the batched paged engine and
    #: attention-only stacks (KV appends are bit-stable across chunk
    #: boundaries; recurrent scans are not).
    prefill_chunk: int = 0
    #: Token budget per fused step (chunked prefill only): each engine
    #: iteration schedules at most this many tokens — every active decode
    #: row first (1 each), then pending prompt suffixes, then region chunks
    #: of streaming scenes (FIFO).  ``None`` → ``slots + prefill_chunk``.
    #: Must exceed ``slots`` so prefill streams can never starve.
    token_budget: Optional[int] = None
    #: Explicit KV pool size in pages (paged only).  ``None`` → the
    #: worst-case bound (every slot a distinct scene + the resident-scene
    #: allowance), under which admission can never run out of pages.
    #: Smaller values model real capacity pressure: admission becomes
    #: genuinely page-bound, which is what overload control arbitrates.
    #: Must cover at least one slot's pages + the trash page.
    pool_pages: Optional[int] = None
    #: Explicit KV pool size as a device **byte budget** (paged only,
    #: mutually exclusive with ``pool_pages``).  The pool gets
    #: ``pool_bytes // bytes_per_page`` pages, where bytes-per-page is the
    #: whole stack's cost for one page — K+V pools *and* int8 scale
    #: buffers (``kv_pool.page_nbytes`` per attention layer).  This is how
    #: quantization buys capacity rather than just smaller numbers: under
    #: the same budget ``kv_dtype="int8"`` yields ~``4·hd/(hd+4)``× the
    #: pages, which admission control can spend on more concurrent
    #: requests.  Must cover at least one slot's pages + the trash page.
    pool_bytes: Optional[int] = None
    #: KV pool element type (paged only).  ``None`` → the model dtype
    #: (exact — the oracle).  ``"int8"`` / ``"fp8"`` (e4m3) → pages
    #: quantize per (token slot, head) symmetric with f32 scale leaves
    #: alongside; the paged Pallas kernels dequantize in-register (fp8 can
    #: instead feed the stored bytes straight into the dot and apply the
    #: scales post-hoc — the native-fp8 path).  Both cost the same bytes
    #: per page; fp8 trades int8's uniform grid for relative precision
    #: below each row's amax.  Greedy outputs are expected (and
    #: bench-asserted) to agree with the exact engine on the serving
    #: workloads, but equality is empirical, not a kernel guarantee —
    #: divergence is *reported*, never hidden.
    kv_dtype: Optional[str] = None
    #: Device mesh with ``("data", "model")`` axes (``launch.mesh``) or
    #: None = today's single-device engine, byte-for-byte.  An EngineCore
    #: handles the TENSOR-parallel "model" axis only: its jitted step
    #: families run under ``shard_map`` with q/k/v/o projections and the
    #: paged KV pools head-sharded per ``distributed.sharding``'s serving
    #: plan, so each device's page pool holds only its KV-head shard
    #: (``kv_bytes_per_slot`` per device shrinks by the TP degree,
    #: composing with int8 pages).  Requires the batched paged engine and a
    #: size-1 "data" axis — data-parallel slot splits are
    #: ``serving.sharded.ShardedEngineCore``'s job.
    mesh: Optional[Any] = None
    #: Overload control (None = off, the legacy contract: ``admit_many``
    #: admits unconditionally and callers queue in front of the engine).
    #: When set, ``submit_many``/``step`` run page-pool-aware admission
    #: with a bounded priority queue, deadline expiry and (optionally)
    #: lowest-priority preemption — see ``serving/admission.py`` and
    #: DESIGN.md §serving "Overload control".
    overload: Optional[OverloadConfig] = None


@dataclasses.dataclass
class _Slot:
    request: Optional[Request] = None
    l_ans: int = 0
    tokens: Optional[List[int]] = None
    active: bool = False
    scene: Optional[Any] = None         # paged: resident prefix this slot maps
    private_pages: Optional[List[int]] = None
    #: remaining piggybacked draft tokens (the satellite's answer riding the
    #: offload payload), aligned with answer positions; dropped on the first
    #: committed token that diverges from it
    pending_drafts: Optional[List[int]] = None
    #: speculative engines only: per-emitted-token answer-vocab probability
    #: rows (the distribution each committed token was argmaxed from), so
    #: ``generate_spec`` can honour ``generate``'s (tokens, probs) contract
    probs: Optional[List[np.ndarray]] = None
    #: chunked-prefill state machine (``prefill_chunk > 0``): "prefill" —
    #: this slot streams its scene's region chunks; "wait" — its scene is
    #: streaming in another slot (shared pages mapped at publication);
    #: "prompt" — prefix resident, the 1-token prompt suffix is pending;
    #: "decode" — normal answer decoding (the only phase other engines use)
    phase: str = "decode"
    #: wall-clock request milestones (time-to-first-token accounting)
    t_admit: float = 0.0
    t_first: Optional[float] = None


def _sel_scatter(slots: jax.Array, n_slots: int):
    """The engine's one gather+select slot-scatter idiom.

    ``slots``: (K,) target slot id per source row (out-of-range ids — the
    padding convention — never match).  Returns ``(hit, put)`` where
    ``hit`` is the (n_slots,) matched mask and ``put(full, new, axis)``
    writes source rows of ``new`` into the matched rows of ``full`` along
    ``axis``.  Formulated as gather + select rather than scatter because
    XLA:CPU lowers true scatters an order of magnitude slower than the
    equivalent gather: each destination row looks up which source row
    targets it, if any."""
    sel = slots[None, :] == jnp.arange(n_slots)[:, None]      # (S, K)
    hit = sel.any(axis=1)
    src = jnp.argmax(sel, axis=1)

    def put(full, new, axis):
        gathered = jnp.take(new, src, axis=axis)
        m = hit.reshape((1,) * axis + (-1,)
                        + (1,) * (full.ndim - axis - 1))
        return jnp.where(m, gathered, full)

    return hit, put


def shared_core(tier, adapter_cfg: EO.EOAdapterConfig) -> "EngineCore":
    """Per-tier ``EngineCore`` cache keyed by adapter-config **value**.

    Adapters (SpaceVerse, CascadeServer, baselines) are constructed freely —
    often many per test session over the same trained tiers — and each
    ``EngineCore`` owns jit caches.  Sharing cores means the jitted step
    functions compile once per tier, not once per adapter instance.  The
    cache lives ON the ``TierModel`` instance, so cores (and their compiled
    executables) are garbage-collected together with the tier they serve.

    The key is the frozen ``EOAdapterConfig`` itself (hashable, compared by
    value) — keying on ``id(adapter_cfg)`` was unsound: after an
    unreferenced config is garbage-collected its id can be reused by a
    *different* config object, silently serving it a core built for the old
    one."""
    cache = getattr(tier, "_engine_cores", None)
    if cache is None:
        cache = {}
        tier._engine_cores = cache
    core = cache.get(adapter_cfg)
    if core is None:
        core = EngineCore(tier, adapter_cfg)
        cache[adapter_cfg] = core
    return core


class EngineCore:
    """Jitted fixed-shape executor + slot table over one tier model."""

    def __init__(self, tier, adapter_cfg: EO.EOAdapterConfig,
                 core_cfg: Optional[EngineCoreConfig] = None,
                 draft=None):
        self.tier = tier
        self.ac = adapter_cfg
        self.cfg = core_cfg or EngineCoreConfig()
        self.max_answer_len = (self.cfg.max_answer_len
                               or adapter_cfg.n_regions)
        # fixed slot-cache capacity: [regions | prompt | longest answer]
        self._slot_max_len = adapter_cfg.n_regions + 1 + self.max_answer_len

        if self.cfg.step_impl not in ("batched", "vmap"):
            raise ValueError(f"unknown step_impl {self.cfg.step_impl!r}")
        if self.cfg.cache_impl not in ("paged", "dense"):
            raise ValueError(f"unknown cache_impl {self.cfg.cache_impl!r}")
        # the vmap oracle predates paging and steps the dense layout
        self.cache_impl = ("dense" if self.cfg.step_impl == "vmap"
                           else self.cfg.cache_impl)

        if self.cfg.kv_dtype is not None:
            if self.cfg.kv_dtype not in ("int8", "fp8"):
                raise ValueError(f"unknown kv_dtype {self.cfg.kv_dtype!r} "
                                 "(None, 'int8' or 'fp8')")
            if self.cache_impl != "paged":
                raise ValueError(
                    "kv_dtype requires the paged cache: quantization lives "
                    "in the page pools + paged kernels (dense/vmap engines "
                    "stay the exact oracle)")

        self.draft = draft
        if self.cfg.spec_gamma:
            if self.cfg.spec_gamma < 1:
                raise ValueError("spec_gamma must be >= 1 when set")
            if draft is None:
                raise ValueError("spec_gamma > 0 requires a compact draft "
                                 "tier (the cascade's satellite model)")
            if self.cfg.step_impl != "batched" or self.cache_impl != "paged":
                raise ValueError("speculative decoding requires the batched "
                                 "paged engine (spec=off is the oracle)")
            for c in (tier.cfg, draft.cfg):
                if any(s.kind != ATTN for s in c.block_pattern):
                    raise ValueError(
                        "speculative decoding requires attention-only "
                        "stacks: recurrent state folds the whole chunk into "
                        "one snapshot, so only attention KV rolls back for "
                        "free (a per-row length decrement)")
        # a verify chunk writes γ positions past the committed index, so
        # spec engines reserve γ extra KV slots per row (rejected drafts
        # land there and are overwritten by the next chunk)
        self._spec_margin = self.cfg.spec_gamma

        if self.cfg.prefill_chunk:
            if self.cfg.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1 when set")
            if self.cfg.step_impl != "batched" or self.cache_impl != "paged":
                raise ValueError("chunked prefill requires the batched "
                                 "paged engine (chunking off is the oracle)")
            if any(s.kind != ATTN for s in tier.cfg.block_pattern):
                raise ValueError(
                    "chunked prefill requires attention-only stacks: KV "
                    "appends are bit-stable across chunk boundaries, "
                    "recurrent scans reassociate their state accumulation "
                    "and break the chunked == unchunked token guarantee")
            self._chunk = min(self.cfg.prefill_chunk, adapter_cfg.n_regions)
            self._token_budget = (self.cfg.token_budget
                                  if self.cfg.token_budget is not None
                                  else self.cfg.slots + self._chunk)
            if self._token_budget <= self.cfg.slots:
                raise ValueError(
                    f"token_budget {self._token_budget} must exceed the "
                    f"slot count {self.cfg.slots}: every active decode row "
                    "takes one token per step, so a smaller budget would "
                    "starve prefill streams forever")
        else:
            self._chunk = 0
            self._token_budget = 0

        params, cfg, ac = tier.params, tier.cfg, adapter_cfg

        # -- device mesh / tensor-parallel plan (None = single device) ------
        self.mesh = self.cfg.mesh
        plan = None
        if self.mesh is not None:
            if self.cfg.step_impl != "batched" or self.cache_impl != "paged":
                raise ValueError(
                    "mesh requires the batched paged engine (the vmap/dense "
                    "oracles stay single-device by design)")
            if SH.mesh_axis_size(self.mesh, "data") != 1:
                raise ValueError(
                    "EngineCore shards tensor-parallel only (the mesh's "
                    "'data' axis must be 1); data-parallel slot splits are "
                    "serving.sharded.ShardedEngineCore's job — it runs one "
                    "EngineCore per data shard on a 1-row sub-mesh")
            if any(s.kind != ATTN for s in cfg.block_pattern):
                raise ValueError(
                    "mesh serving requires attention-only stacks: recurrent "
                    "prefix-state rows would mix mesh-committed and "
                    "uncommitted placements across the admit path (and "
                    "head-sharding has nothing to shard in an SSM state)")
            plan = SH.tp_serving_plan(cfg, self.mesh)
            # weights go to the plan's shardings once (a no-op when they
            # were initialised there, see ``SH.init_sharded``)
            self._p_specs = SH.adapter_param_specs(
                plan, jax.eval_shape(lambda: params))
            params = jax.device_put(params, SH.named(self.mesh,
                                                     self._p_specs))
        self._tp_plan = plan
        mesh = self.mesh
        # Every jitted family takes the weights as an OPERAND, never as a
        # closure: jit embeds closed-over arrays into the program as
        # constants, which at published widths means gigabytes of HLO and
        # a second copy of the weights on the device.
        #: the adapter param tree (backbone + patch projection) and the
        #: backbone alone — the leading operand of every jitted call
        self._p = params
        self._bb = params["backbone"]
        self._dp = None
        # config the step bodies run the model with: per-device head counts
        # under shard_map (head_dim pinned so RoPE is unchanged), identical
        # to ``cfg`` on a single device
        mcfg = plan.cfg_local if plan is not None else cfg

        if mesh is not None:
            # -- sharded jit family -----------------------------------------
            # Every jit that runs a Pallas kernel runs under ONE shard_map
            # over the ("data"=1, "model"=tp) mesh (the TPU lowering refuses
            # a Mosaic kernel under jit's automatic partitioning): q/k/v/o
            # projections and every KV cache are head-sharded per the
            # serving plan, all other operands replicated.  The tp_context
            # arms the all-reduce hooks in models/layers.py at trace time.
            rep = P()
            self._rep_sharding = SH.named(mesh, rep)
            self._bb_specs = self._p_specs["backbone"]
            # a dense KV cache (batch path, regions-only prefill),
            # head-sharded the same way as the paged pools
            dense_specs = T.map_cache_kinds(
                cfg, [jax.eval_shape(lambda: T.init_cache(cfg, 1, 1))],
                kv=lambda t: jax.tree.map(
                    lambda x: SH.paged_kv_leaf_spec(len(x.shape),
                                                    plan.attn), t),
                state=lambda t: jax.tree.map(lambda x: P(), t))

            def shard_jit(fn, in_specs, out_specs, static=()):
                """``jit(shard_map(fn))`` keeping the ``static`` kwargs
                static (the shard_map is staged per static value inside
                jit's trace cache, exactly one compile per value)."""
                @functools.partial(jax.jit, static_argnames=static)
                def call(*args, **kw):
                    def body(*ops):
                        with CO.tp_context("model", attn=plan.attn,
                                           mlp=plan.mlp):
                            return fn(*ops, **kw)
                    return jax.shard_map(
                        body, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)(*args)
                return call

        def _encode(p, images, ptok):
            rf = EO.encode_regions(p, ac, images)
            tf = EO.encode_text(p, cfg, ptok)
            vis = rf.astype(jnp.float32).mean(axis=1)
            return rf, tf, vis

        def _prefill(p, images, ptok, *, max_len):
            return EO.prefill_tokens(p, mcfg, ac, images, ptok, max_len)

        def _decode_chunk(p, cache, logits, idx, *, n_tokens, answer_vocab):
            return EO.decode_chunk(p, mcfg, cache, logits, idx,
                                   n_tokens, answer_vocab)

        self._encode_j = jax.jit(_encode)
        if mesh is None:
            self._prefill_j = jax.jit(_prefill, static_argnames=("max_len",))
            self._decode_chunk_j = jax.jit(
                _decode_chunk, static_argnames=("n_tokens", "answer_vocab"))
        else:
            self._prefill_j = shard_jit(
                _prefill, (self._p_specs, rep, rep), (rep, dense_specs, rep),
                ("max_len",))
            self._decode_chunk_j = shard_jit(
                _decode_chunk, (self._p_specs, dense_specs, rep, rep),
                (rep, rep, dense_specs, rep, rep),
                ("n_tokens", "answer_vocab"))
        self._token_feats_j = jax.jit(
            lambda p, toks: EO.token_features(p, toks))
        # scene-keyed encode memo for the serve path (bounded LRU)
        self._encode_cache: "OrderedDict[Any, Tuple]" = OrderedDict()
        self._encode_cache_cap = 32

        # -- slot-path compiled functions (shapes fixed at construction) ----
        def _slot_step(bb, slot_logits, slot_cache, slot_index, active,
                       *, answer_vocab):
            """All-slot decode step: ONE batched ``T.decode_step`` over the
            whole slot table with a (slots,) ragged index vector.  Per-row
            RoPE / KV scatter / attention masks happen inside the model;
            inactive slots compute garbage that the next admission's full
            cache-row overwrite discards (their index never advances)."""
            a_logits = slot_logits[:, :answer_vocab]
            toks = jnp.argmax(a_logits, axis=-1).astype(jnp.int32)
            new_logits, new_cache = T.decode_step(
                bb, cfg, slot_cache, {"tokens": toks[:, None]}, slot_index)
            new_index = jnp.where(active, slot_index + 1, slot_index)
            return toks, new_logits, new_cache, new_index

        def _slot_step_paged(bb, slot_logits, slot_cache, slot_index, active,
                             block_table, *, answer_vocab):
            """Paged all-slot step: identical to ``_slot_step`` except the
            KV write/read resolve through the block table.  Inactive slots'
            block-table rows point at the trash page, so their garbage write
            can never land in a page another sequence owns.  ``bb`` is the
            backbone param tree (the sharded engine feeds per-device weight
            shards through ``shard_map``)."""
            a_logits = slot_logits[:, :answer_vocab]
            toks = jnp.argmax(a_logits, axis=-1).astype(jnp.int32)
            new_logits, new_cache = T.decode_step(
                bb, mcfg, slot_cache, {"tokens": toks[:, None]},
                slot_index, block_table=block_table)
            new_index = jnp.where(active, slot_index + 1, slot_index)
            return toks, new_logits, new_cache, new_index

        def _slot_step_vmap(bb, slot_logits, slot_cache, slot_index, active,
                            *, answer_vocab):
            """Pre-batching per-slot step: vmap of a batch-1 decode over the
            stacked table.  Kept as the token-for-token equivalence oracle
            for tests and the before/after benchmark baseline."""
            def _one_step(tok, cache_s, idx):
                """Advance ONE slot by one token; ``cache_s`` is this slot's
                cache slice (batch axis stripped)."""
                c1 = jax.tree.map(lambda x: x[:, None], cache_s)
                logits, new_c = T.decode_step(bb, cfg, c1,
                                              {"tokens": tok[None, None]},
                                              idx)
                return logits[0], jax.tree.map(lambda x: x[:, 0], new_c)

            a_logits = slot_logits[:, :answer_vocab]
            toks = jnp.argmax(a_logits, axis=-1).astype(jnp.int32)
            new_logits, new_cache = jax.vmap(
                _one_step, in_axes=(0, 1, 0), out_axes=(0, 1))(
                    toks, slot_cache, slot_index)
            new_index = jnp.where(active, slot_index + 1, slot_index)
            return toks, new_logits, new_cache, new_index

        n_slots = self.cfg.slots

        def _slot_scatter_many(slot_cache, slot_logits, slot_index,
                               cache, logits, slots, idx):
            """Write K freshly-prefilled requests into slots ``slots`` in
            one jitted update (the shared ``_sel_scatter`` idiom; padding
            rows carry an out-of-range slot id and simply never match)."""
            hit, put = _sel_scatter(slots, n_slots)
            sc = jax.tree.map(lambda f, n: put(f, n, 1), slot_cache, cache)
            sl = put(slot_logits, logits, 0)
            si = jnp.where(hit, idx.astype(slot_index.dtype), slot_index)
            return sc, sl, si

        if self.cfg.step_impl == "vmap":
            self._slot_step_j = jax.jit(_slot_step_vmap,
                                        static_argnames=("answer_vocab",))
        elif self.cache_impl == "paged":
            if mesh is None:
                self._slot_step_j = jax.jit(
                    _slot_step_paged, static_argnames=("answer_vocab",))
            # mesh: jitted under shard_map in the paged section below, once
            # the pool shape (and hence the cache partition specs) exists
        else:
            self._slot_step_j = jax.jit(_slot_step,
                                        static_argnames=("answer_vocab",))
        self._slot_scatter_many_j = jax.jit(_slot_scatter_many)

        # -- paged-cache machinery ------------------------------------------
        if self.cache_impl == "paged":
            import math
            ps = self.cfg.page_size
            n_regions = ac.n_regions
            if ps < 1:
                raise ValueError(f"page_size must be positive, got {ps}")
            if n_regions % ps != 0:
                # the shared scene prefix must occupy whole pages; clamp to
                # the largest divisor ≤ the requested size (shared_core
                # builds default configs over arbitrary adapters)
                ps = math.gcd(ps, n_regions)
            self._page_size = ps
            self._n_shared_pages = n_regions // ps
            self._pages_per_slot = -(-(self._slot_max_len
                                       + self._spec_margin) // ps)
            self._private_per_slot = (self._pages_per_slot
                                      - self._n_shared_pages)
            scenes = (self.cfg.prefix_cache_scenes
                      if self.cfg.prefix_cache_scenes is not None
                      else n_slots)
            # worst case: every slot holds a distinct scene (its prefix pages
            # refcounted by slot + cache) + `scenes` cache-only prefixes
            self._n_pages = (1 + n_slots * self._pages_per_slot
                             + scenes * self._n_shared_pages)
            floor = 1 + self._pages_per_slot
            if self.cfg.pool_pages is not None:
                if self.cfg.pool_bytes is not None:
                    raise ValueError("pool_pages and pool_bytes are "
                                     "mutually exclusive pool-size knobs")
                if self.cfg.pool_pages < floor:
                    raise ValueError(
                        f"pool_pages {self.cfg.pool_pages} below the "
                        f"single-slot floor {floor} (trash page + one "
                        "slot's worst-case pages)")
                self._n_pages = self.cfg.pool_pages
            elif self.cfg.pool_bytes is not None:
                # one page's device cost across the whole stack (every
                # attention layer's K+V pools, scale buffers included) —
                # the single accounting rule shared with kv_stats()
                per_page = self._page_nbytes_stack()
                n = self.cfg.pool_bytes // per_page
                if n < floor:
                    raise ValueError(
                        f"pool_bytes {self.cfg.pool_bytes} buys only {n} "
                        f"pages at {per_page} B/page, below the "
                        f"single-slot floor {floor} (trash page + one "
                        "slot's worst-case pages)")
                self._n_pages = int(n)
            self._pool = KVPagePool(self._n_pages, ps)
            self._prefix = PrefixCache(self._pool,
                                       capacity=n_slots + scenes)
            self._bt_np = np.full((n_slots, self._pages_per_slot),
                                  TRASH_PAGE, np.int32)
            self._bt_dev = None

            def _prefill_prefix(p, images):
                """Regions-only prefill: the shared prefix of every query
                over one scene (KV capacity exactly N_r → reshapes straight
                into whole pages; final recurrent state = the snapshot a
                prompt-suffix admission resumes from).  On a mesh it runs
                tensor-parallel like the steps, so each device computes
                exactly the KV-head block its pool shard keeps."""
                _, cache, _ = EO.prefill_regions(p, mcfg, ac, images,
                                                 n_regions)
                return cache

            n_shared = self._n_shared_pages

            def _prefix_scatter(slot_cache, prefix_cache, pages):
                """Write K scenes' region KV into their shared pages.
                ``pages``: (K·n_shared,) flat physical page ids (padding
                rows target the trash page)."""
                def kv(pool, pref):
                    def leaf(pool_leaf, pref_leaf):
                        ns, kb = pref_leaf.shape[:2]
                        resh = pref_leaf.reshape(
                            (ns, kb * n_shared, ps) + pref_leaf.shape[3:])
                        return pool_leaf.at[:, pages].set(resh)
                    if "k_scale" in pool:
                        # quantized pool, exact dense prefix cache: quantize
                        # at scatter time so the shared pages carry the same
                        # (values, scales) layout every other write path
                        # maintains.  Scale leaves drop the trailing hd axis,
                        # which `leaf` handles via shape[3:].
                        kq, ks = kv_quant.quantize_kv_as(
                            pref["k"], pool["k"].dtype)
                        vq, vs = kv_quant.quantize_kv_as(
                            pref["v"], pool["v"].dtype)
                        pref = {"k": kq, "v": vq,
                                "k_scale": ks, "v_scale": vs}
                    return jax.tree.map(leaf, pool, pref)
                return T.map_cache_kinds(cfg, [slot_cache, prefix_cache],
                                         kv=kv, state=lambda sl, pr: sl)

            def _paged_admit(bb, slot_logits, slot_cache, slot_index,
                             block_table, admit_slots, ptoks, prefix_state):
                """Admit K requests whose prefixes are already page-resident:
                scatter each scene's recurrent-state snapshot into its slot
                row, then run ONE decode step over the whole table that
                processes only the 1-token prompt suffix of the admitted
                rows (everyone else is steered to the trash page and merged
                back unchanged).  This *is* the paged prefill: the region
                tokens were never re-computed."""
                sel = admit_slots[None, :] == jnp.arange(n_slots)[:, None]
                hit = sel.any(axis=1)                             # (S,)
                src = jnp.argmax(sel, axis=1)                     # (S,)

                def put_state(full, new):
                    def leaf(f, n):
                        g = jnp.take(n, src, axis=1)
                        m = hit.reshape((1, -1) + (1,) * (f.ndim - 2))
                        return jnp.where(m, g, f)
                    return jax.tree.map(leaf, full, new)

                cache1 = T.map_cache_kinds(
                    cfg, [slot_cache, prefix_state],
                    kv=lambda full, _new: full, state=put_state)

                # non-admitted rows write to the trash page and keep their
                # state; admitted rows decode the prompt at position N_r
                bt_call = jnp.where(hit[:, None], block_table, TRASH_PAGE)
                idx_in = jnp.where(hit, jnp.int32(n_regions), 0)
                ptok_row = jnp.where(hit, jnp.take(ptoks, src), 0)
                logits, cache2 = T.decode_step(
                    bb, mcfg, cache1,
                    {"tokens": ptok_row[:, None]}, idx_in,
                    block_table=bt_call)

                def sel_state(old, new):
                    def leaf(o, n):
                        m = hit.reshape((1, -1) + (1,) * (o.ndim - 2))
                        return jnp.where(m, n, o)
                    return jax.tree.map(leaf, old, new)

                cache3 = T.map_cache_kinds(
                    cfg, [cache1, cache2],
                    kv=lambda _old, new: new, state=sel_state)
                sl = jnp.where(hit[:, None], logits, slot_logits)
                si = jnp.where(hit, jnp.int32(n_regions + 1),
                               slot_index).astype(slot_index.dtype)
                return sl, cache3, si

            if mesh is None:
                self._prefill_prefix_j = jax.jit(_prefill_prefix)
                self._prefix_scatter_j = jax.jit(_prefix_scatter)
                self._paged_admit_j = jax.jit(_paged_admit)
            else:
                cache_shape = jax.eval_shape(
                    lambda: T.init_paged_cache(cfg, n_slots, self._n_pages,
                                               ps,
                                               kv_dtype=self.cfg.kv_dtype))
                cache_specs = T.map_cache_kinds(
                    cfg, [cache_shape],
                    kv=lambda t: jax.tree.map(
                        lambda x: SH.paged_kv_leaf_spec(len(x.shape),
                                                        plan.attn), t),
                    state=lambda t: jax.tree.map(lambda x: P(), t))
                self._cache_specs = cache_specs
                self._slot_step_j = shard_jit(
                    _slot_step_paged,
                    (self._bb_specs, rep, cache_specs, rep, rep, rep),
                    (rep, rep, cache_specs, rep), ("answer_vocab",))
                self._prefill_prefix_j = shard_jit(
                    _prefill_prefix, (self._p_specs, rep), dense_specs)
                self._prefix_scatter_j = shard_jit(
                    _prefix_scatter, (cache_specs, dense_specs, rep),
                    cache_specs)
                self._paged_admit_j = shard_jit(
                    _paged_admit,
                    (self._bb_specs, rep, cache_specs, rep, rep, rep, rep,
                     rep),
                    (rep, cache_specs, rep))

        # -- chunked-prefill machinery (prefill_chunk > 0) ------------------
        if self.cfg.prefill_chunk:
            C = self._chunk

            def _region_embed(p, images):
                """V(x) only — the learned patch projection, a single small
                matmul.  This is ALL the model work chunked admission does
                synchronously; the N_r-token transformer prefill itself
                streams through later fused steps."""
                return EO.encode_regions(p, ac, images)

            def _staging_scatter(staging, embs, slots):
                """Write K freshly-projected region-embed rows into the
                (slots, N_r, d) staging buffer (the shared ``_sel_scatter``
                idiom; padding rows never match)."""
                _, put = _sel_scatter(slots, n_slots)
                return put(staging, embs, 0)

            budget = self._token_budget

            def _fused_step(bb, slot_logits, slot_cache, block_table, staging,
                            srow, tokens, pos, patch_mask, use_argmax,
                            *, answer_vocab):
                """ONE token-budget step over a FLAT token batch — the
                fixed shape IS the budget.  Row ``j`` of the
                (token_budget,) batch is one scheduled token of slot
                ``srow[j]`` at cache position ``pos[j]``: decode rows feed
                their own argmax (1 flat row each), prompt rows the
                host-supplied prompt id, region rows the staged scene
                embedding at ``pos`` (a scene's chunk occupies up to
                ``prefill_chunk`` consecutive flat rows, whose KV writes
                land before the reads — so chunk token t attends to its
                same-step siblings < t through the cache, exactly as a
                (B, C) chunk would).  Flat packing is what keeps decode
                rows from paying chunk width: a fused step costs exactly
                ``token_budget`` token-positions, never slots·C.  Padding
                rows (srow == slots) write nothing (steered out of bounds
                and dropped) and read garbage nobody consumes.  Logits
                scatter back per slot for the ≤ 1 decode/prompt row each
                slot contributes; the per-slot index vector is rebuilt by
                the host (it owns the phase machine)."""
                valid = srow < n_slots
                sclamp = jnp.minimum(srow, n_slots - 1)
                av_logits = slot_logits[:, :answer_vocab]
                y1 = jnp.argmax(av_logits, axis=-1).astype(jnp.int32)
                probs0 = jax.nn.softmax(av_logits, axis=-1)
                tok = jnp.where(use_argmax, jnp.take(y1, sclamp), tokens)
                feed = staging[sclamp, jnp.clip(pos, 0, n_regions - 1)]
                bt_flat = jnp.take(block_table, sclamp, axis=0)
                logits_f, new_cache = T.prefill_chunk_step(
                    bb, mcfg, slot_cache,
                    {"tokens": tok[:, None], "patch_embeds": feed[:, None],
                     "patch_mask": patch_mask},
                    pos, block_table=bt_flat,
                    chunk_lens=valid.astype(jnp.int32))
                wants = valid & ~patch_mask          # decode + prompt rows
                _, put = _sel_scatter(jnp.where(wants, srow, n_slots),
                                      n_slots)
                sl = put(slot_logits, logits_f, 0)
                return tok, probs0, sl, new_cache

            self._region_embed_j = jax.jit(_region_embed)
            self._staging_scatter_j = jax.jit(_staging_scatter)
            if mesh is None:
                self._fused_step_j = jax.jit(
                    _fused_step, static_argnames=("answer_vocab",))
            else:
                self._fused_step_j = shard_jit(
                    _fused_step,
                    (self._bb_specs, rep, cache_specs, rep, rep, rep, rep,
                     rep, rep, rep),
                    (rep, rep, rep, cache_specs), ("answer_vocab",))
            #: scene → dict(slot, pages, progress, order): region streams
            #: currently being chunk-prefilled (FIFO by ``order``)
            self._streaming: Dict[Any, Dict[str, Any]] = {}
            self._stream_seq = 0
            self._staging = None

        # -- speculative-decoding machinery (spec_gamma > 0) ----------------
        if self.cfg.spec_gamma:
            gam = self.cfg.spec_gamma
            dcfg = draft.cfg
            self._dp = draft.params
            self._draft_max_len = self._slot_max_len + gam

            def _draft_prefill(dp, images, ptok, *, max_len):
                """Drafter-side [regions | prompt] prefill: the compact
                model mirrors the slot table on its own small dense cache
                (no page pool — its KV is cheap and never shared)."""
                return EO.prefill_tokens(dp, dcfg, ac, images, ptok, max_len)

            def _draft_scatter(draft_cache, cache, slots):
                """Gather+select scatter of K freshly-prefilled drafter rows
                (the shared ``_sel_scatter`` idiom)."""
                _, put = _sel_scatter(slots, n_slots)
                return jax.tree.map(lambda f, n: put(f, n, 1),
                                    draft_cache, cache)

            def _verify_accept(bb, chunk, slot_logits, slot_cache, slot_index,
                               active, block_table, answer_vocab):
                """ONE γ+1-token scoring step of the regular model + the
                longest-accepted-prefix per row, entirely on device.
                ``chunk``: (slots, γ+1) = [y₁ | d₁..d_γ] where y₁ is this
                tier's own next token (free — argmax of the held logits)
                and d_i are the drafts.  Greedy acceptance: d_i commits iff
                it equals the verifier's argmax at its position, so the
                committed stream is exactly the greedy stream.  Rollback is
                the index update (idx += 1 + accepted): rejected positions
                stay in row-private pages, are never attended (ragged masks
                read < idx), and the next chunk overwrites them — no page
                copies."""
                logits_all, new_cache = T.verify_step(
                    bb, mcfg, slot_cache, {"tokens": chunk},
                    slot_index, block_table=block_table)
                gtok = jnp.argmax(logits_all[..., :answer_vocab],
                                  axis=-1).astype(jnp.int32)   # (S, γ+1)
                eq = (gtok[:, :gam] == chunk[:, 1:]).astype(jnp.int32)
                acc = jnp.cumprod(eq, axis=1).sum(axis=1)      # (S,) prefix
                n_commit = 1 + acc
                new_logits = jnp.take_along_axis(
                    logits_all, acc[:, None, None], axis=1)[:, 0]
                new_index = jnp.where(active, slot_index + n_commit,
                                      slot_index)
                # distribution each chunk token was argmaxed from (the
                # greedy ``decode_chunk`` contract): y₁ ← the held logits,
                # chunk token j ← the verifier's logits after chunk[..j-1]
                tok_probs = jax.nn.softmax(jnp.concatenate(
                    [slot_logits[:, None, :answer_vocab],
                     logits_all[:, :-1, :answer_vocab]], axis=1), axis=-1)
                return n_commit, new_logits, new_cache, new_index, tok_probs

            def _spec_step(bb, dp, slot_logits, slot_cache, slot_index,
                           active, block_table, draft_cache, pending,
                           pending_len, *, answer_vocab):
                """Full speculative step: γ+1 compact-model draft feeds
                (piggybacked ``pending`` drafts override the drafter's
                argmax where provided and are fed THROUGH it, so its cache
                tracks the committed stream), then verify-accept.  The
                extra γ+1-th feed writes the last draft's KV so an
                all-accepted step leaves the drafter's cache complete."""
                y1 = jnp.argmax(slot_logits[:, :answer_vocab],
                                axis=-1).astype(jnp.int32)

                def body(carry, j):
                    tok, dcache, i = carry
                    dlogits, dcache = T.decode_step(
                        dp["backbone"], dcfg, dcache,
                        {"tokens": tok[:, None]}, i)
                    nxt = jnp.argmax(dlogits[:, :answer_vocab],
                                     axis=-1).astype(jnp.int32)
                    pig = jax.lax.dynamic_index_in_dim(
                        pending, jnp.minimum(j, gam - 1), axis=1,
                        keepdims=False)
                    nxt = jnp.where(j < pending_len, pig, nxt)
                    return (nxt, dcache, i + 1), nxt

                (_, draft_cache, _), drafts = jax.lax.scan(
                    body, (y1, draft_cache, slot_index), jnp.arange(gam + 1),
                    unroll=gam + 1)
                chunk = jnp.concatenate([y1[:, None], drafts[:gam].T], 1)
                out = _verify_accept(bb, chunk, slot_logits, slot_cache,
                                     slot_index, active, block_table,
                                     answer_vocab)
                return (chunk,) + out + (draft_cache,)

            def _spec_verify(bb, slot_logits, slot_cache, slot_index, active,
                             block_table, drafts, *, answer_vocab):
                """Verify-only fast path: every active row's useful drafts
                arrived piggybacked (the satellite's answer riding the
                offload payload), so the drafter is skipped entirely.  Its
                cache goes stale for these rows — that can only hurt LATER
                local draft quality, never correctness: the verifier is the
                sole authority on committed tokens."""
                y1 = jnp.argmax(slot_logits[:, :answer_vocab],
                                axis=-1).astype(jnp.int32)
                chunk = jnp.concatenate([y1[:, None], drafts], 1)
                return (chunk,) + _verify_accept(bb, chunk, slot_logits,
                                                 slot_cache, slot_index,
                                                 active, block_table,
                                                 answer_vocab)

            def _draft_feed(dp, draft_cache, toks, idx):
                """Mirror tokens committed OUTSIDE a spec step (the chunked
                engine's fused steps advance decode rows through the plain
                1-token path) into the drafter's cache at per-row ``idx``.
                Without this the drafter would resume over zero-KV gaps
                after a prefill burst and draft garbage — accept rate
                would silently collapse; with it the drafter's cache holds
                exactly the committed stream, as the spec-step scan
                guarantees in the unchunked engine.  Rows with nothing
                committed write a garbage token at position 0 of drafter
                rows that are re-prefilled wholesale before their next
                draft (transition prefill / admission), so nothing ever
                reads it."""
                _, dcache = T.decode_step(dp["backbone"], dcfg, draft_cache,
                                          {"tokens": toks[:, None]}, idx)
                return dcache

            if mesh is None:
                self._draft_prefill_j = jax.jit(_draft_prefill,
                                                static_argnames=("max_len",))
                self._draft_scatter_j = jax.jit(_draft_scatter)
                self._draft_feed_j = jax.jit(_draft_feed)
                self._spec_step_j = jax.jit(
                    _spec_step, static_argnames=("answer_vocab",))
                self._spec_verify_j = jax.jit(
                    _spec_verify, static_argnames=("answer_vocab",))
            else:
                # drafter params are replicated, and the draft jits run
                # under the SAME shard_map (all-replicated specs): the draft
                # cache cycles through the sharded spec step, so keeping
                # every producer on the mesh stops it bouncing between
                # committed placements
                self._dp = self._commit_rep(self._dp)
                self._draft_prefill_j = shard_jit(_draft_prefill, rep, rep,
                                                  ("max_len",))
                self._draft_scatter_j = shard_jit(_draft_scatter, rep, rep)
                self._draft_feed_j = shard_jit(_draft_feed, rep, rep)
                self._spec_step_j = shard_jit(
                    _spec_step,
                    (self._bb_specs, rep, rep, cache_specs, rep, rep, rep,
                     rep, rep, rep),
                    (rep, rep, rep, cache_specs, rep, rep, rep),
                    ("answer_vocab",))
                self._spec_verify_j = shard_jit(
                    _spec_verify,
                    (self._bb_specs, rep, cache_specs, rep, rep, rep, rep),
                    (rep, rep, rep, cache_specs, rep, rep),
                    ("answer_vocab",))

        # runtime half of spacelint (repro.analysis): warmup() compiles
        # every slot-path executable, then arms the guard — any cache
        # growth after that is a mid-serve compile stall (raised under
        # pytest, counted in scheduler_stats()['steady_recompiles'] in
        # production).  _prefill_j is deliberately NOT tracked: it is
        # shared with the batch path, whose max_len legitimately varies
        # per request (encode/prefill/decode_chunk are batch-path too).
        self._compile_guard = CompileGuard()
        for name in ("_slot_step_j", "_slot_scatter_many_j",
                     "_prefill_prefix_j", "_prefix_scatter_j",
                     "_paged_admit_j", "_region_embed_j",
                     "_staging_scatter_j", "_fused_step_j",
                     "_draft_prefill_j", "_draft_scatter_j",
                     "_draft_feed_j", "_spec_step_j", "_spec_verify_j"):
            fn = getattr(self, name, None)
            if fn is not None:
                self._compile_guard.register(name, fn)

        self._slots: List[_Slot] = [_Slot() for _ in range(self.cfg.slots)]
        self._draft_cache = None
        self._spec_probs: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self._slot_cache = None
        self._slot_logits = None
        self._slot_index = None
        # active mask lives on device, derived from _slots (the single
        # source of truth) and only re-uploaded when admission or release
        # actually changes it — not rebuilt host→device every step
        self._active_dev = None
        self._step_no = 0
        self.stats: Dict[str, Any] = {
            "admitted": 0, "finished": 0, "mid_stream_refills": 0,
            "prefix_hits": 0, "prefix_misses": 0,
            "prefill_tokens": 0,        # tokens actually run through prefill
            #: per-kind breakdown of the same counter, maintained by the ONE
            #: accounting hook (``_note_prefill``) every prefill path calls:
            #: "dense" (full [regions|prompt] dense admission), "prefix"
            #: (unchunked regions-only scene prefill), "prompt" (1-token
            #: prompt suffixes), "chunk" (region tokens streamed by the
            #: chunked engine), "draft" (drafter-side prefills, spec only)
            "prefill_by_kind": {},
            "encode_reuse": 0,          # serve-path scene-encode cache hits
            "occupancy_log": [],        # (step, active_slots_after_admit)
            #: finished-request milestones (bounded):
            #: {request_id, task, t_admit, t_first, t_done} wall-clock —
            #: the serving bench derives TTFT / latency percentiles from it
            "request_log": [],
            #: per-step scheduling ledger (all step flavours): token counts
            #: by kind, fused-step budget accounting, stall steps (a fused
            #: step where a pending prefill stream got zero budget)
            "sched": {"steps": 0, "fused_steps": 0, "decode_tokens": 0,
                      "prompt_tokens": 0, "chunk_tokens": 0,
                      "scheduled_tokens": 0, "stall_steps": 0,
                      "budget": self._token_budget, "step_log": []},
        }
        if self.cfg.pool_pages is not None and self.cache_impl != "paged":
            raise ValueError("pool_pages only applies to the paged cache")
        if self.cfg.pool_bytes is not None and self.cache_impl != "paged":
            raise ValueError("pool_bytes only applies to the paged cache")
        # -- overload control (None = legacy admit-unconditionally) ---------
        self._admq: Optional[AdmissionQueue] = None
        if self.cfg.overload is not None:
            self._admq = AdmissionQueue(self.cfg.overload.queue_cap)
            self._submit_seq = 0
            #: request_id → {t_submit, seq, deferred, preempts}: alive from
            #: submit to finish/reject (bounded by queue_cap + slots)
            self._submit_meta: Dict[int, Dict[str, Any]] = {}
            #: (request, reason) drained by ``take_rejected`` — late
            #: rejections (expiry, overflow by a later push) happen inside
            #: ``step``, after ``submit_many`` already returned
            self._rejected: List[Tuple[Request, str]] = []
            self.stats["overload"] = {
                "submitted": 0, "admissions_deferred": 0,
                "preemptions": 0,
                "rejections": {REASON_QUEUE_FULL: 0, REASON_EXPIRED: 0},
                #: seconds between a preemption and the re-admission of the
                #: same request (bounded log; scheduler_stats summarises)
                "readmit_wait_s": [],
            }
        if self.cfg.spec_gamma:
            self.stats["spec"] = {
                "steps": 0,             # speculative engine steps
                "verify_only_steps": 0,  # steps that skipped the drafter
                "slot_steps": 0,        # active-slot · step pairs
                "drafted": 0,           # γ per active slot per step
                "accepted": 0,          # drafts the verifier accepted
                "committed": 0,         # tokens committed (1 + accepted)
                "emitted": 0,           # committed tokens kept (≤ l_ans)
                "piggybacked": 0,       # drafts supplied by the satellite
            }
        self._occupancy_cap = 4096      # keep the log bounded on long runs

    # ------------------------------------------------------------------
    # batch path (shared by CascadeExecutor)
    # ------------------------------------------------------------------
    def encode(self, task: str, images: jax.Array, prompts: jax.Array):
        """V(x), E(T) and pooled visual features: (B,R,d), (B,1,d), (B,d)."""
        return self._encode_j(self._p, images,
                              self.ac.prompt_token(task, prompts))

    def encode_cached(self, task: str, images: jax.Array, prompts: jax.Array,
                      scene: Optional[Any] = None,
                      prompt_id: Optional[int] = None):
        """``encode`` with a scene-keyed memo for the batch-of-one serve
        path: queries fanning out over one captured scene reuse V(x)/E(T)
        instead of re-encoding per request.  Falls back to ``encode`` when
        no scene key is given or the batch isn't a single request.

        ``prompt_id`` is the host-side prompt scalar (``Request.prompt``);
        callers that have it pass it so the memo key never touches the
        device copy."""
        if scene is None or images.shape[0] != 1:
            return self.encode(task, images, prompts)
        if prompt_id is None:
            # legacy callers hand us only the device prompt row — one fetch
            # per MISS-path lookup, amortised by the memo itself
            prompt_id = int(np.asarray(prompts)[0])  # spacelint: disable=SL001 (cache-key fetch for callers without host prompt metadata)
        key = (scene, task, prompt_id)
        hit = self._encode_cache.get(key)
        if hit is not None:
            self._encode_cache.move_to_end(key)
            self.stats["encode_reuse"] += 1
            return hit
        out = self.encode(task, images, prompts)
        self._encode_cache[key] = out
        while len(self._encode_cache) > self._encode_cache_cap:
            self._encode_cache.popitem(last=False)
        return out

    def prefill(self, task: str, images: jax.Array, prompts: jax.Array,
                extra_len: int):
        max_len = self.ac.n_regions + 1 + extra_len
        return self._prefill_j(self._p, images,
                               self.ac.prompt_token(task, prompts),
                               max_len=max_len)

    def decode_chunk(self, cache, logits, idx, n_tokens: int,
                     answer_vocab: int):
        return self._decode_chunk_j(self._p, cache, logits, idx,
                                    n_tokens=n_tokens,
                                    answer_vocab=answer_vocab)

    def token_features(self, tokens: jax.Array) -> jax.Array:
        return self._token_feats_j(self._p, tokens)

    def generate(self, task: str, images: jax.Array, prompts: jax.Array,
                 answer_vocab: int) -> Tuple[jax.Array, jax.Array]:
        """Full greedy answer (prefill + one chunk), as ``EO.generate``."""
        l_ans = self.ac.answer_len(task)
        logits, cache, idx = self.prefill(task, images, prompts, l_ans)
        toks, probs, *_ = self.decode_chunk(cache, logits, idx, l_ans,
                                            answer_vocab)
        return toks, probs

    # ------------------------------------------------------------------
    # slot path (continuous batching)
    # ------------------------------------------------------------------
    def _ensure_slot_tables(self):
        if self._slot_cache is None:
            cfg = self.tier.cfg
            if self.cache_impl == "paged":
                self._slot_cache = T.init_paged_cache(
                    cfg, self.cfg.slots, self._n_pages, self._page_size,
                    kv_dtype=self.cfg.kv_dtype)
                if self.mesh is not None:
                    # commit the pool to its head-sharded layout up front;
                    # every sharded step keeps it there (logits/index stay
                    # uncommitted and auto-replicate)
                    self._slot_cache = jax.device_put(
                        self._slot_cache,
                        SH.named(self.mesh, self._cache_specs))
            else:
                self._slot_cache = T.init_cache(cfg, self.cfg.slots,
                                                self._slot_max_len)
            self._slot_logits = self._commit_rep(
                jnp.zeros((self.cfg.slots, cfg.vocab_size), jnp.float32))
            self._slot_index = self._commit_rep(
                jnp.zeros((self.cfg.slots,), jnp.int32))
        if self.cfg.spec_gamma and self._draft_cache is None:
            self._draft_cache = self._commit_rep(
                T.init_cache(self.draft.cfg, self.cfg.slots,
                             self._draft_max_len))
        if self.cfg.prefill_chunk and self._staging is None:
            self._staging = self._commit_rep(jnp.zeros(
                (self.cfg.slots, self.ac.n_regions, self.tier.cfg.d_model),
                jnp.dtype(self.tier.cfg.dtype)))

    def _commit_rep(self, x):
        """Replicate a host-built value onto the mesh (identity when
        single-device).  Every input of the sharded step families must keep
        a STABLE placement across the engine's lifetime — warmup compiles
        one signature per family, and a later uncommitted-vs-committed flip
        on any operand is a fresh jit cache entry, i.e. a steady-state
        recompile the CompileGuard flags."""
        if self.mesh is None:
            return x
        return jax.device_put(x, self._rep_sharding)

    def _block_table_dev(self) -> jax.Array:
        if self._bt_dev is None:
            self._bt_dev = jnp.asarray(self._bt_np)
        return self._bt_dev

    def _page_nbytes_stack(self) -> int:
        """Device bytes ONE pool page costs across the whole stack: the
        per-layer ``kv_pool.page_nbytes`` (K+V pools + int8 scale buffers)
        times the number of attention-KV-carrying layers (ATTN and the
        attention half of HYBRID supers).  ``pool_bytes`` sizing divides by
        this; ``kv_stats`` asserts the live cache agrees with it."""
        cfg = self.tier.cfg
        n_kv = (cfg.n_super
                * sum(1 for s in cfg.block_pattern
                      if s.kind in (ATTN, HYBRID)))
        return n_kv * page_nbytes(
            self._page_size, cfg.num_kv_heads, cfg.resolved_head_dim,
            kv_dtype=self.cfg.kv_dtype,
            fp_bytes=jnp.dtype(cfg.dtype).itemsize)

    def _note_prefill(self, kind: str, tokens: int) -> None:
        """The ONE prefill-token accounting hook: every path that runs
        tokens through a prefill — dense whole-prefix admission, unchunked
        scene-prefix prefill, 1-token prompt suffixes, streamed region
        chunks, drafter-side prefills — reports here, so the total and the
        per-kind breakdown can never drift apart across paths again."""
        self.stats["prefill_tokens"] += tokens
        by_kind = self.stats["prefill_by_kind"]
        by_kind[kind] = by_kind.get(kind, 0) + tokens

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if not s.active]

    def active_count(self) -> int:
        return sum(s.active for s in self._slots)

    def warmup(self) -> None:
        """Pre-compile every slot-path executable: the decode step plus, per
        power-of-two admission bucket, the dense prefill + scatter pair or
        the paged admit trio (prefix prefill, page scatter, prompt-suffix
        admit).  Speculative engines additionally compile the drafter's
        prefill + scatter per bucket and BOTH jitted spec step variants
        (draft-loop + verify, and the piggyback verify-only path), so the
        first admission/verify of a serving loop never pays compile time.

        Traffic decides when each bucket size first occurs, so without this
        a compile can land mid-serve — exactly the stall the fixed-shape
        slot design exists to avoid (a satellite pays it inside a contact
        window).  Idempotent; slot state is untouched (dense warmup scatters
        target out-of-range slot ids; paged warmup admissions match no slot
        and write only the trash page, and the functional outputs are
        discarded)."""
        self._ensure_slot_tables()
        shape = (self.ac.image_size, self.ac.image_size, self.ac.channels)
        sizes, b = set(), 1
        while b <= self.cfg.slots:
            sizes.add(b)
            b *= 2
        sizes.add(self.cfg.slots)
        if self.cfg.prefill_chunk:
            # chunked engines never run the synchronous admit trio: compile
            # the region-embed + staging buckets, the fused token-budget
            # step (an all-idle call — every row unscheduled, writes
            # dropped, outputs discarded) and the plain/spec decode step
            # the engine falls back to at steady state
            for k in sorted(sizes):
                images = jnp.zeros((k,) + shape, jnp.float32)
                embs = self._region_embed_j(self._p, images)
                drop = jnp.full((k,), self.cfg.slots, jnp.int32)
                self._staging_scatter_j(self._staging, embs, drop)
                if self.cfg.spec_gamma:
                    _, dcache, _ = self._draft_prefill_j(
                        self._dp, images, jnp.zeros((k,), jnp.int32),
                        max_len=self._draft_max_len)
                    self._draft_scatter_j(self._draft_cache, dcache, drop)
            if self.cfg.spec_gamma:
                zs = jnp.zeros((self.cfg.slots,), jnp.int32)
                self._draft_feed_j(self._dp, self._draft_cache, zs, zs)
            tb = self._token_budget
            self._fused_step_j(self._bb,
                               self._slot_logits, self._slot_cache,
                               self._block_table_dev(), self._staging,
                               jnp.full((tb,), self.cfg.slots, jnp.int32),
                               jnp.zeros((tb,), jnp.int32),
                               jnp.zeros((tb,), jnp.int32),
                               jnp.zeros((tb,), bool),
                               jnp.zeros((tb,), bool),
                               answer_vocab=self.cfg.answer_vocab)
            self._step_once_compiled()
            return
        for k in sorted(sizes):
            images = jnp.zeros((k,) + shape, jnp.float32)
            if self.cache_impl == "paged":
                cache = self._prefill_prefix_j(self._p, images)
                trash = jnp.zeros((k * self._n_shared_pages,), jnp.int32)
                self._prefix_scatter_j(self._slot_cache, cache, trash)
                state = T.map_cache_kinds(
                    self.tier.cfg, [cache],
                    kv=lambda _t: None, state=lambda t: t)
                self._paged_admit_j(
                    self._bb,
                    self._slot_logits, self._slot_cache, self._slot_index,
                    self._block_table_dev(),
                    jnp.full((k,), self.cfg.slots, jnp.int32),
                    jnp.zeros((k,), jnp.int32), state)
                if self.cfg.spec_gamma:
                    _, dcache, _ = self._draft_prefill_j(
                        self._dp, images, jnp.zeros((k,), jnp.int32),
                        max_len=self._draft_max_len)
                    self._draft_scatter_j(self._draft_cache, dcache,
                                          jnp.full((k,), self.cfg.slots,
                                                   jnp.int32))
            else:
                ptok = jnp.zeros((k,), jnp.int32)
                logits, cache, idx = self._prefill_j(
                    self._p, images, ptok, max_len=self._slot_max_len)
                drop = jnp.full((k,), self.cfg.slots, jnp.int32)
                self._slot_scatter_many_j(self._slot_cache, self._slot_logits,
                                          self._slot_index, cache, logits,
                                          drop, idx)
        self._step_once_compiled()

    def _step_args(self) -> Tuple:
        """Positional tail of a ``_slot_step_j`` call: the paged step takes
        the block table after the active mask; dense/vmap take nothing."""
        if self.cache_impl == "paged":
            return (self._block_table_dev(),)
        return ()

    def _step_once_compiled(self):
        inactive = jnp.zeros((self.cfg.slots,), bool)
        if self.cfg.spec_gamma:
            # compile both speculative step variants (no slot matches, all
            # block-table rows point at the trash page, outputs discarded)
            pend = jnp.zeros((self.cfg.slots, self.cfg.spec_gamma),
                             jnp.int32)
            self._spec_step_j(self._bb, self._dp,
                              self._slot_logits, self._slot_cache,
                              self._slot_index, inactive,
                              self._block_table_dev(), self._draft_cache,
                              pend, jnp.zeros((self.cfg.slots,), jnp.int32),
                              answer_vocab=self.cfg.answer_vocab)
            self._spec_verify_j(self._bb,
                                self._slot_logits, self._slot_cache,
                                self._slot_index, inactive,
                                self._block_table_dev(), pend,
                                answer_vocab=self.cfg.answer_vocab)
        else:
            self._slot_step_j(self._bb,
                              self._slot_logits, self._slot_cache,
                              self._slot_index, inactive,
                              *self._step_args(),
                              answer_vocab=self.cfg.answer_vocab)
        # both warmup() exits end here: everything the slot path will ever
        # run is now compiled — recompiles past this point are findings
        self._compile_guard.arm()

    def admit(self, request: Request) -> int:
        """Prefill ``request`` into a free slot; returns the slot id."""
        return self.admit_many([request])[0]

    @staticmethod
    def _admit_pad(k: int, cap: int) -> int:
        """Fixed-shape admission buckets: next power of two, capped at the
        slot count — at most log2(slots)+1 prefill shapes ever compile."""
        p = 1
        while p < k:
            p *= 2
        return min(p, cap)

    def admit_many(self, requests: List[Request]) -> List[int]:
        """Prefill up to ``slots`` pending requests in ONE batched call and
        scatter them into free slots in one jitted update.

        Dense cache: the full [regions | prompt] prefix prefills per
        request (padded to a power-of-two bucket ≤ slot count, so refilling
        K slots costs one fixed-shape launch).  Paged cache: the
        region prefix prefills once per **unique scene not already
        page-resident**, then every request maps the shared prefix pages
        read-only and runs only its 1-token prompt suffix (see
        ``_admit_many_paged``).  Returns the slot id per request."""
        if not requests:
            return []
        t_admit = time.perf_counter()      # arrival at the engine: TTFT
        free = self.free_slots()           # clocks start BEFORE any prefill
        if len(requests) > len(free):
            raise RuntimeError("no free slot")
        self._ensure_slot_tables()
        if self.cache_impl == "paged":
            if self.cfg.prefill_chunk:
                out = self._admit_many_chunked(requests, free, t_admit)
            else:
                out = self._admit_many_paged(requests, free, t_admit)
            self._compile_guard.check("admit_many")
            return out
        k = len(requests)
        kpad = self._admit_pad(k, self.cfg.slots)
        assert kpad >= k, "more requests than slots"
        target = free[:k] + [self.cfg.slots] * (kpad - k)   # pad ids: dropped
        pad = [requests[-1]] * (kpad - k)
        images = jnp.asarray(np.stack(
            [np.asarray(r.image) for r in requests] +
            [np.asarray(r.image) for r in pad]))
        # prompt ids computed host-side (scalar mirror of prompt_token):
        # no device roundtrip per distinct task on the admission hot path
        ptok = np.empty((kpad,), np.int32)
        for i, r in enumerate(requests):
            ptok[i] = self.ac.prompt_id(r.task, r.prompt)
        ptok[k:] = ptok[k - 1]
        # fixed max_len: every request uses the same cache capacity, so the
        # prefill and decode step never see a new sequence length
        logits, cache, idx = self._prefill_j(self._p, images,
                                             jnp.asarray(ptok),
                                             max_len=self._slot_max_len)
        self._slot_cache, self._slot_logits, self._slot_index = \
            self._slot_scatter_many_j(self._slot_cache, self._slot_logits,
                                      self._slot_index, cache, logits,
                                      jnp.asarray(target, jnp.int32), idx)
        self._note_prefill("dense", k * (self.ac.n_regions + 1))
        self._record_admissions(target[:k], requests, t_admit=t_admit)
        self._compile_guard.check("admit_many")
        return target[:k]

    def _record_admissions(self, slot_ids: List[int],
                           requests: List[Request], scenes=None,
                           private=None, phases=None,
                           t_admit: Optional[float] = None) -> None:
        log = self.stats["occupancy_log"]
        # t_admit is captured at admit_many ENTRY: stamping here would run
        # AFTER the synchronous scene prefill and hide the very admission
        # stall the TTFT instrumentation exists to expose
        now = t_admit if t_admit is not None else time.perf_counter()
        for j, (s, request) in enumerate(zip(slot_ids, requests)):
            others_active = self.active_count()
            pending = None
            if self.cfg.spec_gamma and request.draft_tokens is not None:
                # Request.__post_init__ normalised drafts to flat host
                # int32 — no device fetch happens here
                pending = [int(t) for t in request.draft_tokens]
            # per-token probs are only materialised for requests that will
            # read them (generate_spec) — plain slot-path serving never
            # pays the host transfer / per-token appends
            wants_probs = (self.cfg.spec_gamma
                           and getattr(request, "_wants_probs", False))
            self._slots[s] = _Slot(
                request=request, l_ans=self.ac.answer_len(request.task),
                tokens=[], active=True,
                scene=scenes[j] if scenes else None,
                private_pages=private[j] if private else None,
                pending_drafts=pending,
                probs=[] if wants_probs else None,
                phase=phases[j] if phases else "decode",
                t_admit=now)
            self.stats["admitted"] += 1
            if self._step_no > 0 and others_active > 0:
                self.stats["mid_stream_refills"] += 1
            log.append((self._step_no, self.active_count()))
        self._active_dev = None
        if len(log) > self._occupancy_cap:
            del log[:self._occupancy_cap // 2]

    # -- paged admission ------------------------------------------------
    def _prefill_prefixes(self, miss: List[Tuple[Any, Request]]) -> None:
        """Region-prefill the scenes in ``miss`` (one batched bucketed call),
        scatter their KV into freshly allocated shared pages, and make them
        resident in the prefix cache with their recurrent-state snapshots.
        The caller has already budgeted the pages and entries (the one
        up-front ``evict_for`` of ``_admit_many_paged``), so nothing here
        can fail — this is the commit phase of check-then-commit."""
        km = len(miss)
        n_shared = self._n_shared_pages
        kpad = self._admit_pad(km, self.cfg.slots)
        images = jnp.asarray(np.stack(
            [np.asarray(r.image) for _, r in miss]
            + [np.asarray(miss[-1][1].image)] * (kpad - km)))
        cache = self._prefill_prefix_j(self._p, images)
        pages = np.full((kpad, n_shared), TRASH_PAGE, np.int32)
        allocs = []
        for i in range(km):
            pg = self._pool.alloc(n_shared)
            allocs.append(pg)
            pages[i] = pg
        self._slot_cache = self._prefix_scatter_j(
            self._slot_cache, cache, jnp.asarray(pages.reshape(-1)))
        state_tree = T.map_cache_kinds(self.tier.cfg, [cache],
                                       kv=lambda _t: None, state=lambda t: t)
        for i, (scene, _r) in enumerate(miss):
            row = jax.tree.map(lambda x: x[:, i:i + 1], state_tree)
            self._prefix.put(scene, allocs[i], row)
        self.stats["prefix_misses"] += km
        self._note_prefill("prefix", km * self.ac.n_regions)

    def _admit_many_paged(self, requests: List[Request], free: List[int],
                          t_admit: Optional[float] = None) -> List[int]:
        """Scene-shared admission: prefix pages are mapped read-only into
        each new request's block table (refcount++), and only the 1-token
        prompt suffix runs through the model — K queries over one scene
        prefill the ``N_r`` region tokens once."""
        k = len(requests)
        scenes = [scene_key(r) for r in requests]
        batch_scenes = set(scenes)
        miss, seen = [], set()
        for s_, r in zip(scenes, requests):
            if s_ not in self._prefix and s_ not in seen:
                miss.append((s_, r))
                seen.add(s_)
        # check-then-commit (admission atomicity): ONE eviction call budgets
        # the whole batch — shared pages + cache entries for the missing
        # scenes AND every request's private pages — before anything is
        # allocated, scattered or made resident.  A MemoryError here leaves
        # the engine byte-identical to before the call; past this line no
        # allocation can fail, so a batch can never leak refcounts or leave
        # partially mapped prefix pages behind.
        self._prefix.evict_for(
            k * self._private_per_slot
            + len(miss) * self._n_shared_pages,
            need_entries=len(miss), protect=batch_scenes)
        if miss:
            self._prefill_prefixes(miss)
        self.stats["prefix_hits"] += k - len(miss)
        target = free[:k]
        ptoks = np.empty((k,), np.int32)
        states, private = [], []
        for i, (r, s_) in enumerate(zip(requests, scenes)):
            entry = self._prefix.acquire(s_)
            priv = self._pool.alloc(self._private_per_slot)
            self._bt_np[target[i]] = list(entry.pages) + priv
            ptoks[i] = self.ac.prompt_id(r.task, r.prompt)
            states.append(entry.state)
            private.append(priv)
        self._bt_dev = None

        kpad = self._admit_pad(k, self.cfg.slots)
        admit_slots = np.asarray(target + [self.cfg.slots] * (kpad - k),
                                 np.int32)
        ptoks_pad = np.concatenate([ptoks,
                                    np.repeat(ptoks[-1:], kpad - k)])
        states_pad = states + [states[-1]] * (kpad - k)
        prefix_state = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1), *states_pad)

        self._slot_logits, self._slot_cache, self._slot_index = \
            self._paged_admit_j(self._bb,
                                self._slot_logits, self._slot_cache,
                                self._slot_index, self._block_table_dev(),
                                jnp.asarray(admit_slots),
                                jnp.asarray(ptoks_pad, jnp.int32),
                                prefix_state)
        self._note_prefill("prompt", k)        # one prompt token per request
        if self.cfg.spec_gamma:
            # the drafter mirrors the slot table on its own dense cache: one
            # bucketed [regions | prompt] prefill for the admitted batch
            # (the compact model has no page pool — its KV is cheap)
            imgs = jnp.asarray(np.stack(
                [np.asarray(r.image) for r in requests]
                + [np.asarray(requests[-1].image)] * (kpad - k)))
            _, dcache, _ = self._draft_prefill_j(
                self._dp, imgs, jnp.asarray(ptoks_pad, jnp.int32),
                max_len=self._draft_max_len)
            self._draft_cache = self._draft_scatter_j(
                self._draft_cache, dcache, jnp.asarray(admit_slots))
            self._note_prefill("draft", k * (self.ac.n_regions + 1))
        self._record_admissions(target, requests, scenes=scenes,
                                private=private, t_admit=t_admit)
        return target

    # -- chunked admission ----------------------------------------------
    def _admit_many_chunked(self, requests: List[Request], free: List[int],
                            t_admit: Optional[float] = None) -> List[int]:
        """Stall-free admission: NO model forward runs here.  Each request
        gets a slot, private pages, and a phase:

        - scene resident in the prefix cache → ``"prompt"`` (shared pages
          mapped read-only; its 1-token prompt suffix rides the next fused
          step);
        - scene currently streaming in another slot → ``"wait"`` (shared
          pages mapped at publication);
        - scene unseen → ``"prefill"``: this slot becomes the scene's
          streamer — fresh shared pages are allocated and the region
          embeddings (one small projection, the only jitted call here) are
          staged; the N_r region tokens then stream into the pages
          ``prefill_chunk`` at a time inside the fused token-budget steps,
          co-scheduled with everyone else's decode tokens.

        Scene-prefix sharing is preserved exactly: only the first query of
        a scene streams the region chunks; fan-out queries map the pages
        read-only (resident) or wait for the stream (in flight)."""
        k = len(requests)
        scenes = [scene_key(r) for r in requests]
        batch_scenes = set(scenes)
        new_streams, seen = [], set()
        for s_, r in zip(scenes, requests):
            if (s_ not in self._prefix and s_ not in self._streaming
                    and s_ not in seen):
                new_streams.append(s_)
                seen.add(s_)
        # whole-batch page budget up front; in-flight streams are protected
        # alongside this batch's scenes (their pages are not yet resident,
        # but their scenes must not be evicted-then-restreamed underneath)
        # and their FUTURE publications need entry capacity reserved NOW —
        # put() never checks capacity, so without the reservation two
        # overlapping admissions could push the cache past its bound
        self._prefix.evict_for(
            k * self._private_per_slot
            + len(new_streams) * self._n_shared_pages,
            need_entries=len(new_streams) + len(self._streaming),
            protect=batch_scenes | set(self._streaming))
        target = free[:k]
        stream_imgs, stream_slots = [], []
        phases, private = [], []
        for i, (r, s_) in enumerate(zip(requests, scenes)):
            slot = target[i]
            priv = self._pool.alloc(self._private_per_slot)
            private.append(priv)
            if s_ in self._prefix:
                entry = self._prefix.acquire(s_)
                self._bt_np[slot] = list(entry.pages) + priv
                phases.append("prompt")
            elif s_ in self._streaming:
                # shared slots stay trash-parked until publication; a
                # higher-priority waiter raises the stream's priority (its
                # TTFT now depends on this stream finishing)
                st = self._streaming[s_]
                st["priority"] = max(st["priority"], r.priority)
                self._bt_np[slot] = ([TRASH_PAGE] * self._n_shared_pages
                                     + priv)
                phases.append("wait")
            else:
                shared = self._pool.alloc(self._n_shared_pages)
                self._streaming[s_] = {"slot": slot, "pages": shared,
                                       "progress": 0,
                                       "order": self._stream_seq,
                                       "priority": r.priority}
                self._stream_seq += 1
                self._bt_np[slot] = shared + priv
                phases.append("prefill")
                stream_imgs.append(np.asarray(r.image))
                stream_slots.append(slot)
        self._bt_dev = None
        self.stats["prefix_hits"] += k - len(new_streams)
        self.stats["prefix_misses"] += len(new_streams)
        if stream_slots:
            km = len(stream_slots)
            kpad = self._admit_pad(km, self.cfg.slots)
            imgs = jnp.asarray(np.stack(
                stream_imgs + [stream_imgs[-1]] * (kpad - km)))
            embs = self._region_embed_j(self._p, imgs)
            slots_pad = np.asarray(stream_slots
                                   + [self.cfg.slots] * (kpad - km), np.int32)
            self._staging = self._staging_scatter_j(self._staging, embs,
                                                    jnp.asarray(slots_pad))
        self._record_admissions(target, requests, scenes=scenes,
                                private=private, phases=phases,
                                t_admit=t_admit)
        return target

    def _release_slot(self, i: int) -> None:
        slot = self._slots[i]
        self._slots[i] = _Slot()
        self._active_dev = None
        if self.cache_impl == "paged" and slot.private_pages is not None:
            self._pool.free(slot.private_pages)
            self._prefix.release(slot.scene)
            self._bt_np[i] = TRASH_PAGE
            self._bt_dev = None

    def _finish_slot(self, i: int,
                     finished: List[Tuple[Request, np.ndarray]]) -> None:
        """Shared finish path: emit the answer, log the request's
        wall-clock milestones (admit / first token / done — the bench's
        TTFT and latency-percentile source), stash spec probs if the
        request asked for them, and free the slot."""
        slot = self._slots[i]
        finished.append((slot.request, np.asarray(slot.tokens, np.int32)))
        log = self.stats["request_log"]
        # overload engines log queue wait too: t_submit is when the request
        # entered submit_many (≤ t_admit); per-priority TTFT is measured
        # from it, so time parked under saturation is charged, not hidden
        meta = (self._submit_meta.pop(slot.request.request_id, None)
                if self._admq is not None else None)
        log.append({"request_id": slot.request.request_id,
                    "task": slot.request.task, "t_admit": slot.t_admit,
                    "t_first": slot.t_first,
                    "t_done": time.perf_counter(),
                    "priority": slot.request.priority,
                    "t_submit": (meta["t_submit"] if meta is not None
                                 else slot.t_admit),
                    "preempts": (meta["preempts"] if meta is not None
                                 else 0)})
        if len(log) > self._occupancy_cap:
            del log[:self._occupancy_cap // 2]
        if slot.probs:
            self._stash_spec_probs(slot)
        self._release_slot(i)
        self.stats["finished"] += 1

    # ------------------------------------------------------------------
    # overload control (cfg.overload set): page-pool-aware admission with
    # a bounded priority queue, deadline expiry and priority preemption
    # ------------------------------------------------------------------
    def page_demand(self, request: Request) -> int:
        """Worst-case page demand of admitting ``request`` right now: its
        private pages (prompt + max answer + spec γ slack — the fixed
        per-slot reservation) plus the shared scene prefix if the scene is
        neither resident nor currently streaming.  Dense caches reserve
        worst-case slices per slot at construction, so their demand is 0
        (admission is slot-gated only)."""
        if self.cache_impl != "paged":
            return 0
        s_ = scene_key(request)
        streams = self._streaming if self.cfg.prefill_chunk else {}
        shared = (0 if s_ in self._prefix or s_ in streams
                  else self._n_shared_pages)
        return self._private_per_slot + shared

    def _fits(self, entries: List[QueueEntry]) -> bool:
        """Pure page/entry feasibility check for admitting ``entries`` as
        one batch: would the up-front ``evict_for`` of the admit path
        succeed?  Headroom = free pages + zero-user unprotected prefix
        pages; nothing is evicted or allocated here — requests that do not
        fit stay parked instead of tearing down cache state they may never
        use (check-then-commit, the admission-atomicity contract)."""
        if self.cache_impl != "paged":
            return True
        k = len(entries)
        scenes = [scene_key(e.request) for e in entries]
        streams = self._streaming if self.cfg.prefill_chunk else {}
        new = {s_ for s_ in scenes
               if s_ not in self._prefix and s_ not in streams}
        protect = set(scenes) | set(streams)
        need_pages = (k * self._private_per_slot
                      + len(new) * self._n_shared_pages)
        # mirror the admit paths' eviction budget exactly: in-flight
        # streams reserve entry capacity for their future publications
        need_entries = len(new) + len(streams)
        if (self._pool.free_pages + self._prefix.evictable_pages(protect)
                < need_pages):
            return False
        resident = len(self._prefix) - self._prefix.evictable_entries(protect)
        return resident + need_entries <= self._prefix.capacity

    def queue_depth(self) -> int:
        return len(self._admq) if self._admq is not None else 0

    def take_rejected(self) -> List[Tuple[Request, str]]:
        """Drain (request, reason) pairs rejected since the last call.
        Rejections can happen after ``submit_many`` returned ``QUEUED`` —
        deadline expiry at pump time, or eviction by a later higher-priority
        push — so drivers poll this next to ``step``'s finished list to
        learn which requests will never complete."""
        if self._admq is None:
            return []
        out, self._rejected = self._rejected, []
        return out

    def submit_many(self, requests: List[Request],
                    now: Optional[float] = None) -> Dict[int, str]:
        """Overload-controlled admission entry: returns an outcome per
        request id — ``"admitted"`` (in a slot now), ``"queued"`` (parked
        in the bounded priority queue; admitted, preempted-for or rejected
        later) or ``"rejected"`` (queue overflow / already expired).
        Requires ``EngineCoreConfig.overload``; ``admit_many`` remains the
        legacy unconditional path and is what the queue pump commits
        through."""
        if self._admq is None:
            raise ValueError("submit_many requires EngineCoreConfig."
                             "overload (admit_many is the legacy path)")
        now = time.perf_counter() if now is None else now
        ol = self.stats["overload"]
        out: Dict[int, str] = {}
        for r in requests:
            ol["submitted"] += 1
            meta = {"t_submit": now, "seq": self._submit_seq,
                    "deferred": False, "preempts": 0, "t_preempt": None}
            self._submit_meta[r.request_id] = meta
            self._submit_seq += 1
            entry = QueueEntry(request=r, seq=meta["seq"], t_submit=now)
            dropped = self._admq.push(entry)
            if dropped is entry:
                # queue full of equal-or-better work — drain whatever fits
                # into free slots first, then retry once before giving up,
                # so a burst submitted to an idle engine isn't rejected by
                # the queue bound that exists for *saturation*
                self._pump_queue(now)
                dropped = self._admq.push(entry)
            if dropped is not None:
                self._reject(dropped, REASON_QUEUE_FULL)
                if dropped is entry:
                    out[r.request_id] = REJECTED
                    continue
            out[r.request_id] = QUEUED
        self._pump_queue(now)
        active = {s.request.request_id for s in self._slots if s.active}
        queued = {e.request.request_id for e in self._admq}
        for r in requests:
            rid = r.request_id
            if out[rid] == REJECTED:
                continue
            if rid in active:
                out[rid] = ADMITTED
            elif rid in queued:
                meta = self._submit_meta[rid]
                if not meta["deferred"]:
                    meta["deferred"] = True
                    ol["admissions_deferred"] += 1
            else:
                out[rid] = REJECTED     # expired/evicted inside the pump
        return out

    def _reject(self, entry: QueueEntry, reason: str) -> None:
        ol = self.stats["overload"]
        ol["rejections"][reason] = ol["rejections"].get(reason, 0) + 1
        self._submit_meta.pop(entry.request.request_id, None)
        self._rejected.append((entry.request, reason))
        if len(self._rejected) > self._occupancy_cap:
            del self._rejected[:self._occupancy_cap // 2]

    def _pump_queue(self, now: Optional[float] = None) -> None:
        """Admit the longest strictly-priority-ordered queue prefix that
        fits (slots AND pages); when the head cannot fit and outranks an
        in-flight request, preempt the lowest-priority slot and retry.
        Strict head-of-line by priority: lower-priority entries never jump
        a parked urgent request, so backfill can't starve it of the very
        pages it is waiting for."""
        if self._admq is None or len(self._admq) == 0:
            return
        now = time.perf_counter() if now is None else now
        for e in self._admq.expire(now):
            self._reject(e, REASON_EXPIRED)
        ov = self.cfg.overload
        while len(self._admq):
            free = len(self.free_slots())
            batch: List[QueueEntry] = []
            for e in self._admq:
                if len(batch) >= free:
                    break
                if not self._fits(batch + [e]):
                    break
                batch.append(e)
            if batch:
                for _ in batch:
                    self._admq.pop()
                self._admit_submitted(batch, now)
                continue
            head = self._admq.peek()
            if (ov.preempt and head is not None
                    and self._preempt_one(head.request.priority, now)):
                continue
            if head is not None and self.active_count() == 0 \
                    and not self._fits([head]):
                # idle engine, everything evictable counted, still no fit:
                # this request can NEVER be admitted — parking it would
                # wedge the strict-priority head forever
                self._admq.pop()
                self._reject(head, REASON_INFEASIBLE)
                continue
            break

    def _admit_submitted(self, entries: List[QueueEntry], now: float
                         ) -> None:
        """Commit phase of the pump: ``_fits`` proved the batch feasible,
        so the legacy admit path (whose one up-front ``evict_for`` can now
        be satisfied by construction) runs unchanged — same buckets, same
        compiled shapes, zero new executables for overload traffic."""
        self.admit_many([e.request for e in entries])
        ol = self.stats["overload"]
        for e in entries:
            meta = self._submit_meta.get(e.request.request_id)
            if meta is not None and meta["t_preempt"] is not None:
                wait = ol["readmit_wait_s"]
                wait.append(now - meta["t_preempt"])
                meta["t_preempt"] = None
                if len(wait) > self._occupancy_cap:
                    del wait[:self._occupancy_cap // 2]

    def _preempt_one(self, above_priority: int, now: float) -> bool:
        """Preempt ONE in-flight slot whose priority is strictly below
        ``above_priority``: drop-and-recompute — free its private pages,
        release its prefix mapping, and re-enqueue the request at the front
        of its priority class (its original submit seq preserves aging).
        Greedy decoding is deterministic and the scene prefix stays (or is
        re-prefilled) in the cache, so the re-admitted request's token
        stream is identical to the uncontended one.  Victims: the
        lowest-priority slot, ties broken by least decode progress (least
        recompute lost).  Only slots that own their prefix mapping
        (decode/prompt phases) are eligible — a chunked streamer's pages
        are what its waiters wait on, and "wait"/"prefill" slots have not
        acquired the prefix the release path would unmap."""
        victims = [(s.request.priority, len(s.tokens or ()), i)
                   for i, s in enumerate(self._slots)
                   if s.active and s.phase in ("decode", "prompt")
                   and s.request.priority < above_priority]
        if not victims:
            return False
        victims.sort()
        i = victims[0][2]
        req = self._slots[i].request
        t_admit = self._slots[i].t_admit
        ol = self.stats["overload"]
        ol["preemptions"] += 1
        meta = self._submit_meta.get(req.request_id)
        if meta is None:
            # admitted through the legacy path (admit_many callers can mix
            # with submit traffic); synthesise meta so aging still works
            meta = {"t_submit": t_admit, "seq": self._submit_seq,
                    "deferred": False, "preempts": 0, "t_preempt": None}
            self._submit_meta[req.request_id] = meta
            self._submit_seq += 1
        meta["preempts"] += 1
        meta["t_preempt"] = now
        self._release_slot(i)
        dropped = self._admq.push(QueueEntry(
            request=req, seq=meta["seq"], t_submit=meta["t_submit"],
            preempts=meta["preempts"]))
        if dropped is not None:
            # queue full of work at least this valuable: the victim (or the
            # displaced entry) is the least valuable in the system — drop it
            self._reject(dropped, REASON_QUEUE_FULL)
        return True

    def step(self) -> List[Tuple[Request, np.ndarray]]:
        """Advance every active slot; return finished requests.

        Non-speculative engines commit one token per slot; speculative
        engines (``spec_gamma > 0``) commit the longest verified draft
        prefix + 1 — up to γ+1 tokens per slot per step, token-for-token
        identical to the greedy stream.  Chunked-prefill engines
        (``prefill_chunk > 0``) take a fused token-budget step whenever any
        slot is still prefilling — decode rows, prompt suffixes and region
        chunks advance together in ONE call — and fall back to the plain
        (or speculative) all-decode step otherwise, so steady-state decode
        pays nothing for the chunked machinery.  Finished slots free
        immediately — callers refill them from their pending queue before
        the next ``step`` (continuous batching).  Overload-controlled
        engines additionally pump their own admission queue first, so
        slots freed by the previous step refill before advancing."""
        if self._admq is not None:
            self._pump_queue()
        if self.cfg.prefill_chunk and any(
                s.active and s.phase != "decode" for s in self._slots):
            return self._step_chunked()
        if self.cfg.spec_gamma:
            return self._step_spec()
        if self.active_count() == 0:
            return []
        if self._active_dev is None:
            self._active_dev = jnp.asarray([s.active for s in self._slots])
        toks, self._slot_logits, self._slot_cache, self._slot_index = \
            self._slot_step_j(self._bb,
                              self._slot_logits, self._slot_cache,
                              self._slot_index, self._active_dev,
                              *self._step_args(),
                              answer_vocab=self.cfg.answer_vocab)
        # spacelint: disable=SL001 (the single deliberate per-step fetch: committed tokens must reach the host-side scheduler)
        toks_np = np.asarray(toks)
        self._step_no += 1
        now = time.perf_counter()
        sched = self.stats["sched"]
        sched["steps"] += 1
        finished: List[Tuple[Request, np.ndarray]] = []
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            slot.tokens.append(int(toks_np[i]))
            sched["decode_tokens"] += 1
            if slot.t_first is None:
                slot.t_first = now
            if len(slot.tokens) >= slot.l_ans:
                self._finish_slot(i, finished)
        self._compile_guard.check("step")
        return finished

    def _slot_pos(self, i: int) -> int:
        """A slot's current logical cache index, from the phase machine
        (the host is the source of truth in chunked mode)."""
        slot = self._slots[i]
        if not slot.active:
            return 0
        if slot.phase == "decode":
            return self.ac.n_regions + 1 + len(slot.tokens)
        if slot.phase == "prompt":
            return self.ac.n_regions
        if slot.phase == "prefill":
            return self._streaming[slot.scene]["progress"]
        return 0                                   # wait: nothing written

    def _step_chunked(self) -> List[Tuple[Request, np.ndarray]]:
        """ONE fused token-budget step (Sarathi-style chunked prefill).

        The scheduler packs a FLAT (token_budget,) token batch: every
        active decode row first (1 token each — in-flight answers are
        never delayed by admission, the fairness guarantee), then pending
        1-token prompt suffixes (they unlock decoding, i.e. TTFT), then
        region chunks of streaming scenes in FIFO order, each up to
        ``prefill_chunk`` consecutive flat tokens (budget / chunk
        permitting).  All scheduled tokens advance in ONE ``_fused_step_j``
        call whose cost is the budget, not slots·chunk; a scene whose
        stream completes is published to the prefix cache and its
        streamer + waiters move to the prompt phase (speculative engines
        drafter-prefill rows the moment they reach the decode phase —
        drafting starts when a slot finishes prefill)."""
        self._ensure_slot_tables()
        n_slots, C = self.cfg.slots, self._chunk
        n_regions = self.ac.n_regions
        tb = self._token_budget
        srow = np.full((tb,), n_slots, np.int32)
        tokens = np.zeros((tb,), np.int32)
        pos = np.zeros((tb,), np.int32)
        patch_mask = np.zeros((tb,), bool)
        use_argmax = np.zeros((tb,), bool)
        decode_rows, prompt_rows = [], []
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            if slot.phase == "decode":
                decode_rows.append(i)
            elif slot.phase == "prompt":
                prompt_rows.append(i)
        # SLO-aware budget split: decode rows always come first (every
        # admitted answer keeps advancing — the fairness invariant), but
        # WITHIN the prompt and chunk classes the budget is granted by
        # priority, so at saturation an urgent request's TTFT-critical
        # tokens (its prompt suffix, its scene's region chunks) are never
        # queued behind bulk work.  Ties keep slot/FIFO order, so engines
        # whose traffic is all one priority schedule byte-identically to
        # the pre-overload scheduler.
        prompt_rows.sort(
            key=lambda i: (-self._slots[i].request.priority, i))
        j = 0
        decode_flat = {}
        for i in decode_rows:
            srow[j] = i
            pos[j] = n_regions + 1 + len(self._slots[i].tokens)
            use_argmax[j] = True
            decode_flat[i] = j
            j += 1
        scheduled_prompt = []
        for i in prompt_rows:
            if j >= tb:
                break
            slot = self._slots[i]
            srow[j] = i
            pos[j] = n_regions
            tokens[j] = self.ac.prompt_id(slot.request.task,
                                          slot.request.prompt)
            scheduled_prompt.append(i)
            j += 1
        streams = sorted(self._streaming.items(),
                         key=lambda kv: (-kv[1]["priority"], kv[1]["order"]))
        stream_sched = []                          # (scene, tokens granted)
        for s_, st in streams:
            c = min(C, n_regions - st["progress"], tb - j)
            if c <= 0:
                continue
            for t in range(c):
                srow[j] = st["slot"]
                pos[j] = st["progress"] + t
                patch_mask[j] = True
                j += 1
            stream_sched.append((s_, c))

        tok, probs0, self._slot_logits, self._slot_cache = \
            self._fused_step_j(
                self._bb,
                self._slot_logits, self._slot_cache,
                self._block_table_dev(), self._staging,
                jnp.asarray(srow), jnp.asarray(tokens), jnp.asarray(pos),
                jnp.asarray(patch_mask), jnp.asarray(use_argmax),
                answer_vocab=self.cfg.answer_vocab)
        # spacelint: disable=SL001 (the single deliberate per-step fetch: committed tokens must reach the host-side phase machine)
        toks_np = np.asarray(tok)
        probs_np = None
        if any(self._slots[i].probs is not None for i in decode_rows):
            # spacelint: disable=SL001 (probs ride the same step fetch, and only for slots that asked for them)
            probs_np = np.asarray(probs0)
        self._step_no += 1
        now = time.perf_counter()

        n_prompt = len(scheduled_prompt)
        n_chunk = int(sum(c for _, c in stream_sched))
        sched = self.stats["sched"]
        sched["steps"] += 1
        sched["fused_steps"] += 1
        sched["decode_tokens"] += len(decode_rows)
        sched["prompt_tokens"] += n_prompt
        sched["chunk_tokens"] += n_chunk
        sched["scheduled_tokens"] += len(decode_rows) + n_prompt + n_chunk
        if self._streaming and n_chunk == 0:
            sched["stall_steps"] += 1
        slog = sched["step_log"]
        slog.append((len(decode_rows), n_prompt, n_chunk))
        if len(slog) > self._occupancy_cap:
            del slog[:self._occupancy_cap // 2]
        self._note_prefill("prompt", n_prompt)
        self._note_prefill("chunk", n_chunk)

        if self.cfg.spec_gamma and decode_rows:
            # keep the drafter's mirrored cache tracking the committed
            # stream: fused steps commit tokens through the plain path the
            # drafter never sees, and a later spec step would otherwise
            # draft over zero-KV gaps
            dtoks = np.zeros((n_slots,), np.int32)
            didx = np.zeros((n_slots,), np.int32)
            for i in decode_rows:
                jf = decode_flat[i]
                dtoks[i] = toks_np[jf]
                didx[i] = pos[jf]
            self._draft_cache = self._draft_feed_j(
                self._dp, self._draft_cache, jnp.asarray(dtoks),
                jnp.asarray(didx))

        finished: List[Tuple[Request, np.ndarray]] = []
        for i in decode_rows:
            slot = self._slots[i]
            slot.tokens.append(int(toks_np[decode_flat[i]]))
            if slot.t_first is None:
                slot.t_first = now
            if slot.probs is not None:
                slot.probs.append(probs_np[i])
            if len(slot.tokens) >= slot.l_ans:
                self._finish_slot(i, finished)
        newly_decoding = []
        for i in scheduled_prompt:
            self._slots[i].phase = "decode"
            newly_decoding.append(i)
        for s_, c in stream_sched:
            st = self._streaming[s_]
            st["progress"] += c
            if st["progress"] < n_regions:
                continue
            # stream complete: publish the prefix (the alloc-time page
            # reference becomes the cache's own, as in _prefill_prefixes)
            # and move the streamer + every waiter to the prompt phase
            del self._streaming[s_]
            state_row = T.map_cache_kinds(
                self.tier.cfg, [self._slot_cache], kv=lambda t: None,
                state=lambda t, jj=st["slot"]: jax.tree.map(
                    lambda x: x[:, jj:jj + 1], t))
            self._prefix.put(s_, st["pages"], state_row)
            for jj, slot in enumerate(self._slots):
                if (slot.active and slot.scene == s_
                        and slot.phase in ("prefill", "wait")):
                    self._prefix.acquire(s_)
                    if slot.phase == "wait":
                        self._bt_np[jj, :self._n_shared_pages] = st["pages"]
                        self._bt_dev = None
                    slot.phase = "prompt"
        # the host owns the phase machine: rebuild the per-slot index
        # vector for the plain/spec steps that take over once prefill
        # drains (fused steps themselves take positions per flat token)
        self._slot_index = self._commit_rep(jnp.asarray(
            [self._slot_pos(i) for i in range(n_slots)], jnp.int32))
        if self.cfg.spec_gamma and newly_decoding:
            self._draft_prefill_rows(newly_decoding)
        self._compile_guard.check("_step_chunked")
        return finished

    def _draft_prefill_rows(self, rows: List[int]) -> None:
        """Drafter-side [regions | prompt] prefill for rows that just
        finished their chunked prefill — speculative drafting composes on
        top of chunked admission by starting the moment a slot reaches the
        decode phase (the compact model's prefill is cheap and was NOT run
        at admission, which is what keeps chunked admission stall-free)."""
        km = len(rows)
        kpad = self._admit_pad(km, self.cfg.slots)
        imgs = jnp.asarray(np.stack(
            [np.asarray(self._slots[i].request.image) for i in rows]
            + [np.asarray(self._slots[rows[-1]].request.image)]
            * (kpad - km)))
        ptoks = np.empty((kpad,), np.int32)
        for j, i in enumerate(rows):
            slot = self._slots[i]
            ptoks[j] = self.ac.prompt_id(slot.request.task,
                                         slot.request.prompt)
        ptoks[km:] = ptoks[km - 1]
        _, dcache, _ = self._draft_prefill_j(self._dp, imgs,
                                             jnp.asarray(ptoks),
                                             max_len=self._draft_max_len)
        slots_pad = np.asarray(rows + [self.cfg.slots] * (kpad - km),
                               np.int32)
        self._draft_cache = self._draft_scatter_j(self._draft_cache, dcache,
                                                  jnp.asarray(slots_pad))
        self._note_prefill("draft", km * (self.ac.n_regions + 1))

    def _step_spec(self) -> List[Tuple[Request, np.ndarray]]:
        """Speculative all-slot step: draft γ tokens per row (piggybacked
        satellite answers supply them for free where available), verify all
        of them in ONE multi-token scoring step of the regular model, and
        commit each row's longest accepted prefix + 1.

        Greedy acceptance makes the committed stream exactly the greedy
        stream; rejected drafts cost nothing beyond the verify FLOPs —
        paged rollback is a per-row index decrement (drafts only ever write
        pages the slot owns)."""
        if self.active_count() == 0:
            return []
        if self._active_dev is None:
            self._active_dev = jnp.asarray([s.active for s in self._slots])
        g = self.cfg.spec_gamma
        n_slots = self.cfg.slots
        pend = np.zeros((n_slots, g), np.int32)
        plen = np.zeros((n_slots,), np.int32)
        n_active = covered = 0
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            n_active += 1
            p = slot.pending_drafts
            if p:
                # y₁ covers answer position len(tokens); draft j predicts
                # position len(tokens) + j
                off = len(slot.tokens) + 1
                avail = p[off:off + g]
                pend[i, :len(avail)] = avail
                plen[i] = len(avail)
            # drafts past the answer end are useless — a row is "covered"
            # when piggybacked drafts span every position it still needs
            useful = min(g, max(slot.l_ans - len(slot.tokens) - 1, 0))
            if plen[i] >= useful:
                covered += 1
        sp = self.stats["spec"]
        args = (self._slot_logits, self._slot_cache, self._slot_index,
                self._active_dev, self._block_table_dev())
        verify_only = covered == n_active
        if verify_only:
            chunk, n_commit, self._slot_logits, self._slot_cache, \
                self._slot_index, tok_probs = self._spec_verify_j(
                    self._bb, *args, jnp.asarray(pend),
                    answer_vocab=self.cfg.answer_vocab)
            sp["verify_only_steps"] += 1
        else:
            chunk, n_commit, self._slot_logits, self._slot_cache, \
                self._slot_index, tok_probs, self._draft_cache = \
                self._spec_step_j(
                    self._bb, self._dp, *args, self._draft_cache,
                    jnp.asarray(pend),
                    jnp.asarray(plen), answer_vocab=self.cfg.answer_vocab)
        # spacelint: disable=SL001 (the single deliberate per-step fetch: the verified chunk must reach the host-side scheduler)
        chunk_np = np.asarray(chunk)
        n_np = np.asarray(n_commit)  # spacelint: disable=SL001 (accept counts ride the same per-step fetch)
        probs_np = None
        if any(s.active and s.probs is not None for s in self._slots):
            # spacelint: disable=SL001 (probs ride the same step fetch, and only for slots that asked for them)
            probs_np = np.asarray(tok_probs)
        self._step_no += 1
        now = time.perf_counter()
        sp["steps"] += 1
        sp["slot_steps"] += n_active
        sp["piggybacked"] += int(plen.sum())
        sched = self.stats["sched"]
        sched["steps"] += 1
        finished: List[Tuple[Request, np.ndarray]] = []
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            n = int(n_np[i])
            # accept-rate accounting counts REAL drafts only: the drafter
            # proposes γ per row, a verify-only step exactly the
            # piggybacked plen[i] — the zero-padded tail of ``pend`` is not
            # a draft, and an acceptance among padding (the verifier's
            # argmax happening to be 0) must not read as agreement
            real = int(plen[i]) if verify_only else g
            sp["drafted"] += real
            sp["accepted"] += min(n - 1, real)
            sp["committed"] += n
            for j in range(n):
                pos = len(slot.tokens)
                if pos >= slot.l_ans:
                    break                       # over-commit past the answer
                t = int(chunk_np[i, j])
                p = slot.pending_drafts
                if p is not None and pos < len(p) and p[pos] != t:
                    slot.pending_drafts = None  # satellite stream diverged
                slot.tokens.append(t)
                if slot.t_first is None:
                    slot.t_first = now
                if slot.probs is not None:
                    slot.probs.append(probs_np[i, j])
                sp["emitted"] += 1
                sched["decode_tokens"] += 1
            if len(slot.tokens) >= slot.l_ans:
                self._finish_slot(i, finished)
        self._compile_guard.check("_step_spec")
        return finished

    def _stash_spec_probs(self, slot: _Slot) -> None:
        """Keep a finished slot's per-token probability rows so
        ``generate_spec`` can return them (bounded: the serve path consumes
        an entry immediately after its request finishes)."""
        if not slot.probs:
            return
        self._spec_probs[slot.request.request_id] = np.stack(slot.probs)
        while len(self._spec_probs) > 64:
            self._spec_probs.popitem(last=False)

    def scheduler_stats(self) -> Dict[str, Any]:
        """Token-budget scheduler counters + derived rates.

        Works for every engine flavour (the plain and speculative steps
        report their decode tokens through the same ledger); the
        fused-step fields — budget utilisation, per-kind token mix, stall
        steps — are only non-trivial for chunked engines."""
        sched = self.stats["sched"]
        out = {k: v for k, v in sched.items() if k != "step_log"}
        steps = max(sched["steps"], 1)
        out["tokens_per_step"] = {
            "decode": sched["decode_tokens"] / steps,
            "prompt": sched["prompt_tokens"] / steps,
            "chunk": sched["chunk_tokens"] / steps,
        }
        fused = sched["fused_steps"]
        out["budget_utilization"] = (
            sched["scheduled_tokens"] / (fused * sched["budget"])
            if fused and sched["budget"] else 0.0)
        out["prefill_by_kind"] = dict(self.stats["prefill_by_kind"])
        if self._admq is not None:
            ol = self.stats["overload"]
            # per-priority TTFT measured from SUBMIT time (queue wait is
            # charged): the graceful-degradation claim is exactly that the
            # urgent class's tail holds while bulk's degrades
            by_prio: Dict[int, List[float]] = {}
            for e in self.stats["request_log"]:
                if e.get("t_first") is None:
                    continue
                t0 = e.get("t_submit", e["t_admit"])
                by_prio.setdefault(e.get("priority", 0), []).append(
                    e["t_first"] - t0)
            ttft = {
                p: {"n": len(v),
                    "p50_ms": float(np.percentile(v, 50)) * 1e3,
                    "p99_ms": float(np.percentile(v, 99)) * 1e3}
                for p, v in sorted(by_prio.items())}
            wait = ol["readmit_wait_s"]
            out["overload"] = {
                "queue_depth": len(self._admq),
                "queue_peak": self._admq.depth_peak,
                "submitted": ol["submitted"],
                "admissions_deferred": ol["admissions_deferred"],
                "preemptions": ol["preemptions"],
                "rejections": dict(ol["rejections"]),
                "rejected_total": sum(ol["rejections"].values()),
                "readmit_wait_ms": {
                    "n": len(wait),
                    "mean": float(np.mean(wait)) * 1e3 if wait else 0.0,
                    "p50": (float(np.percentile(wait, 50)) * 1e3
                            if wait else 0.0)},
                "ttft_by_priority": ttft,
            }
        # compile-guard verdict: jit compilations observed after warmup()
        # armed the guard (0 at healthy steady state; see repro.analysis)
        out["steady_recompiles"] = self._compile_guard.steady_recompiles
        return out

    def spec_stats(self) -> Dict[str, Any]:
        """Speculative-decoding counters + derived rates (empty when off)."""
        sp = dict(self.stats.get("spec") or {})
        if not sp:
            return sp
        sp["accept_rate"] = sp["accepted"] / max(sp["drafted"], 1)
        sp["drafts_per_step"] = sp["drafted"] / max(sp["steps"], 1)
        sp["tokens_per_slot_step"] = (sp["committed"]
                                      / max(sp["slot_steps"], 1))
        sp["piggyback_frac"] = sp["piggybacked"] / max(sp["drafted"], 1)
        return sp

    def generate_spec(self, task: str, images: jax.Array,
                      prompts: jax.Array, answer_vocab: int,
                      draft_tokens=None, priority: int = 0,
                      deadline_s: Optional[float] = None
                      ) -> Tuple[jax.Array, jax.Array]:
        """Batch-of-one greedy answer through the SPECULATIVE slot path —
        the GS-side entry the executor uses for offloaded requests, so the
        satellite's piggybacked answer tokens can seed the verify chunks
        (the ground station's first verify step then starts with free
        drafts).  Honours ``generate``'s contract: tokens are
        token-for-token identical and probs are the answer-vocab
        distributions each token was argmaxed from.  Intended for a
        dedicated serve core (it drains only its own request)."""
        if not self.cfg.spec_gamma:
            raise ValueError("generate_spec requires spec_gamma > 0")
        if answer_vocab != self.cfg.answer_vocab:
            raise ValueError(
                f"answer_vocab {answer_vocab} != engine answer_vocab "
                f"{self.cfg.answer_vocab} (baked into the compiled spec "
                "step)")
        req = Request(task=task, image=np.asarray(images)[0],
                      prompt=int(np.asarray(prompts)[0]),
                      draft_tokens=draft_tokens, priority=priority,
                      deadline_s=deadline_s)
        req._wants_probs = True
        self.admit_many([req])
        while True:
            for r, toks in self.step():
                if r is req:
                    probs = self._spec_probs.pop(req.request_id)
                    return jnp.asarray(toks[None]), jnp.asarray(probs[None])

    # ------------------------------------------------------------------
    def kv_stats(self) -> Dict[str, Any]:
        """KV-cache footprint of the slot table.

        ``kv_bytes_per_slot``: dense — the reserved worst-case slice every
        slot holds; paged — each active slot's private pages plus its
        *amortised* share of the prefix pages it maps (idle engines report
        the reserved-page equivalent).  ``prefix_hit_rate`` is over all
        slot-path admissions so far."""
        self._ensure_slot_tables()
        kv_bytes, scale_bytes = [], []

        def _kv(t):
            kv_bytes.append(sum(
                x.size * x.dtype.itemsize for x in jax.tree.leaves(t)))
            scale_bytes.append(sum(
                v.size * v.dtype.itemsize for k_, v in t.items()
                if k_.endswith("_scale")))

        T.map_cache_kinds(self.tier.cfg, [self._slot_cache],
                          kv=_kv, state=lambda t: None)
        total = sum(kv_bytes)
        out: Dict[str, Any] = {"cache_impl": self.cache_impl,
                               "kv_bytes_total": int(total),
                               "kv_dtype": self.cfg.kv_dtype,
                               #: f32 scale buffers riding the int8 pools —
                               #: already included in kv_bytes_total; broken
                               #: out so the ≤ 0.55× fp claim is auditable
                               "kv_scale_bytes": int(sum(scale_bytes))}
        adm = self.stats["prefix_hits"] + self.stats["prefix_misses"]
        out["prefix_hit_rate"] = (self.stats["prefix_hits"] / adm
                                  if adm else 0.0)
        out["prefill_tokens"] = self.stats["prefill_tokens"]
        if self.cache_impl == "dense":
            out["kv_bytes_per_slot"] = int(total // self.cfg.slots)
            return out
        page_bytes = total // self._n_pages
        out.update(page_size=self._page_size, n_pages=self._n_pages,
                   page_bytes=int(page_bytes),
                   pages_in_use=self._pool.pages_in_use,
                   **{f"prefix_{k}": v for k, v in
                      self._prefix.stats().items()})
        active = [s for s in self._slots if s.active]
        if active:
            pages = 0.0
            for s in active:
                entry = self._prefix.get(s.scene)
                if entry is None:
                    # chunked engines: the scene is still streaming (or this
                    # slot is waiting on it) — charge the streamer the whole
                    # shared group, waiters nothing yet
                    share = (self._n_shared_pages
                             if s.phase == "prefill" else 0)
                else:
                    share = self._n_shared_pages / max(entry.users, 1)
                pages += self._private_per_slot + share
            out["kv_bytes_per_slot"] = int(page_bytes * pages / len(active))
        else:
            out["kv_bytes_per_slot"] = int(page_bytes * self._pages_per_slot)
        if self.mesh is not None:
            # sharded pools: leaf sizes above are GLOBAL (the full logical
            # pool); each device physically holds 1/tp of the KV heads, so
            # the per-device footprint — the capacity the tentpole buys —
            # is the global number over the attention-sharding degree
            tp_kv = self._tp_plan.tp if self._tp_plan.attn else 1
            out["mesh"] = {a: int(self.mesh.shape[a])
                           for a in self.mesh.axis_names}
            out["tp_kv_shards"] = tp_kv
            out["kv_bytes_total_device"] = int(total // tp_kv)
            out["kv_bytes_per_slot_device"] = int(
                out["kv_bytes_per_slot"] // tp_kv)
        return out
