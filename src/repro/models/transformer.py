"""Decoder stack: scan-over-super-blocks with stacked parameters.

The layer pattern (``cfg.block_pattern``) repeats ``n_super`` times; the stack
executes as one ``lax.scan`` over super-blocks with each pattern position's
parameters stacked along the scan axis.  HLO size is O(period), not O(depth)
— essential for the 46-layer dry-runs on this single-core container and for
TPU compile times at fleet scale.  KV caches / recurrent states ride the scan
as per-position xs/ys pytrees with an ``n_super`` leading dim.

Public entry points (all pure functions of (params, cfg, ...)):
- ``init_params`` / ``init_cache``
- ``forward_train``  full-sequence logits (+ MoE aux loss), remat'd scan
- ``loss_fn``        masked next-token cross-entropy
- ``prefill``        full-sequence forward that fills a KV cache
- ``decode_step``    one-token step against the cache
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ArchConfig, BlockSpec, ATTN, MAMBA, MLSTM,
                                SLSTM, HYBRID)
from repro.kernels.decode_attention import (largest_divisor_leq as
                                            _largest_divisor_leq)
from repro.models import frontends
from repro.models import layers as L

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ArchConfig, spec: BlockSpec) -> Params:
    dt = jnp.dtype(cfg.dtype)
    k1, k2 = jax.random.split(key)
    p: Params = {"norm1": jnp.zeros((cfg.d_model,), dt)}
    if spec.kind == ATTN:
        p["mixer"] = L.init_attention(k1, cfg)
    elif spec.kind == MAMBA:
        p["mixer"] = L.init_mamba(k1, cfg)
    elif spec.kind == MLSTM:
        p["mixer"] = L.init_mlstm(k1, cfg)
    elif spec.kind == SLSTM:
        p["mixer"] = L.init_slstm(k1, cfg)
    elif spec.kind == HYBRID:
        p["mixer"] = L.init_hybrid(k1, cfg)
    if _has_ffn(cfg, spec):
        p["norm2"] = jnp.zeros((cfg.d_model,), dt)
        p["ffn"] = L.init_moe(k2, cfg) if spec.moe else L.init_mlp(k2, cfg)
    return p


def _has_ffn(cfg: ArchConfig, spec: BlockSpec) -> bool:
    if spec.kind in (MLSTM, SLSTM):
        return False
    return spec.moe or cfg.d_ff > 0


def init_params(cfg: ArchConfig, key: jax.Array) -> Params:
    ke, kb = jax.random.split(key)
    blocks = []
    for pos, spec in enumerate(cfg.block_pattern):
        pos_keys = jax.random.split(jax.random.fold_in(kb, pos), cfg.n_super)
        # one vmapped init per pattern position (the same values as a loop
        # over super-blocks, but O(1) program size in the depth when jitted)
        blocks.append(jax.vmap(lambda k, spec=spec: _init_block(k, cfg, spec))(
            pos_keys))
    return {
        "embed": frontends.init_embed(ke, cfg),
        "blocks": tuple(blocks),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.dtype)),
    }


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Tuple:
    """Per-pattern-position caches, each leaf stacked to (n_super, ...)."""
    dt = jnp.dtype(cfg.dtype)

    def single(spec: BlockSpec):
        if spec.kind == ATTN:
            return L.init_attn_cache(cfg, batch, max_len, dt)
        if spec.kind == MAMBA:
            return L.init_mamba_cache(cfg, batch)
        if spec.kind == MLSTM:
            return L.init_mlstm_cache(cfg, batch)
        if spec.kind == SLSTM:
            return L.init_slstm_cache(cfg, batch)
        if spec.kind == HYBRID:
            return L.init_hybrid_cache(cfg, batch, max_len, dt)
        raise ValueError(spec.kind)

    out = []
    for spec in cfg.block_pattern:
        one = single(spec)
        out.append(jax.tree.map(
            lambda x: jnp.zeros((cfg.n_super,) + x.shape, x.dtype), one))
    return tuple(out)


def init_paged_cache(cfg: ArchConfig, batch: int, n_pages: int,
                     page_size: int, kv_dtype: Optional[str] = None
                     ) -> Tuple:
    """Paged variant of ``init_cache``: attention KV leaves become page
    pools ``(n_super, n_pages, page, KH, hd)`` shared by every sequence and
    addressed through the ``block_table`` argument of ``decode_step``;
    recurrent-state leaves (O(1) per token — nothing to page) stay per-slot
    ``(n_super, batch, ...)`` exactly as in the dense cache.

    ``kv_dtype="int8"``/``"fp8"``: the pools quantize (int8 or e4m3) with
    per-(token slot, head) scale leaves ``k_scale``/``v_scale`` stacked
    alongside (``(n_super, n_pages, page, KH)`` f32) — the attention write
    paths maintain them and the paged kernels dequant in-register."""
    dt = jnp.dtype(cfg.dtype)

    def single(spec: BlockSpec):
        if spec.kind == ATTN:
            return L.init_paged_attn_cache(cfg, n_pages, page_size, dt,
                                           kv_dtype)
        if spec.kind == MAMBA:
            return L.init_mamba_cache(cfg, batch)
        if spec.kind == MLSTM:
            return L.init_mlstm_cache(cfg, batch)
        if spec.kind == SLSTM:
            return L.init_slstm_cache(cfg, batch)
        if spec.kind == HYBRID:
            return {"attn": L.init_paged_attn_cache(cfg, n_pages, page_size,
                                                    dt, kv_dtype),
                    "mamba": L.init_mamba_cache(cfg, batch)}
        raise ValueError(spec.kind)

    out = []
    for spec in cfg.block_pattern:
        one = single(spec)
        out.append(jax.tree.map(
            lambda x: jnp.zeros((cfg.n_super,) + x.shape, x.dtype), one))
    return tuple(out)


def map_cache_kinds(cfg: ArchConfig, caches, *, kv, state) -> Tuple:
    """Apply ``kv`` to every attention-KV subtree and ``state`` to every
    recurrent-state subtree of one or more structurally-identical caches.

    ``caches`` is a sequence of cache tuples (as returned by ``init_cache``
    / ``init_paged_cache``); ``kv`` / ``state`` receive the corresponding
    subtrees from each cache positionally and return the new subtree.  This
    is the one place that knows which cache leaves are pageable KV versus
    per-slot recurrent state — engine-side paging logic (prefix-state
    scatter, pool merges) goes through it instead of guessing from shapes.
    """
    def one(spec: BlockSpec, parts):
        if spec.kind == ATTN:
            return kv(*parts)
        if spec.kind == HYBRID:
            return {"attn": kv(*[p["attn"] for p in parts]),
                    "mamba": state(*[p["mamba"] for p in parts])}
        return state(*parts)

    return tuple(one(spec, [c[i] for c in caches])
                 for i, spec in enumerate(cfg.block_pattern))


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _apply_block(p: Params, x: jax.Array, *, cfg: ArchConfig,
                 spec: BlockSpec, cos, sin, cache, cache_index, mode: str,
                 block_table=None, chunk_lens=None
                 ) -> Tuple[jax.Array, Any, jax.Array]:
    if mode == "verify" and spec.kind != ATTN:
        # Recurrent mixers fold the whole chunk into one state — rejecting a
        # draft suffix would need per-position state snapshots, so rollback
        # is only free for attention KV (a pure length decrement).  The
        # engine gates speculative decoding on all-ATTN stacks; this is the
        # model-level backstop.
        raise NotImplementedError(
            f"verify mode needs rollback-free attention blocks, got "
            f"{spec.kind!r}")
    if mode == "prefill_append" and spec.kind != ATTN:
        # Chunked prefill demands bit-stable chunk boundaries: attention KV
        # appends commute with chunking (each position's KV is computed
        # independently), but a recurrent scan split at a chunk boundary
        # reassociates its state accumulation and drifts numerically —
        # which breaks the chunked == unchunked token-for-token guarantee
        # the engine advertises.  The engine gates chunked prefill on
        # all-ATTN stacks; this is the model-level backstop.
        raise NotImplementedError(
            f"prefill_append mode needs attention blocks (bit-stable chunk "
            f"boundaries), got {spec.kind!r}")
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if spec.kind == ATTN:
        h, new_cache = L.attention(p["mixer"], h, cfg=cfg, window=spec.window,
                                   cos=cos, sin=sin, cache=cache,
                                   cache_index=cache_index,
                                   block_table=block_table,
                                   chunk_lens=chunk_lens, mode=mode)
    elif spec.kind == MAMBA:
        h, new_cache = L.mamba(p["mixer"], h, cfg=cfg, cache=cache, mode=mode)
    elif spec.kind == MLSTM:
        h, new_cache = L.mlstm(p["mixer"], h, cfg=cfg, cache=cache, mode=mode)
    elif spec.kind == SLSTM:
        h, new_cache = L.slstm(p["mixer"], h, cfg=cfg, cache=cache, mode=mode)
    elif spec.kind == HYBRID:
        h, new_cache = L.hybrid(p["mixer"], h, cfg=cfg, window=spec.window,
                                cos=cos, sin=sin, cache=cache,
                                cache_index=cache_index,
                                block_table=block_table, mode=mode)
    else:
        raise ValueError(spec.kind)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if _has_ffn(cfg, spec):
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.moe:
            h2, aux = L.moe(p["ffn"], h2, cfg)
        else:
            h2 = L.mlp(p["ffn"], h2)
        x = x + h2
    return x, new_cache, aux


REMAT_POLICIES = {
    "nothing": lambda: jax.checkpoint_policies.nothing_saveable,
    "dots": lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "dots_saveable": lambda: jax.checkpoint_policies.dots_saveable,
}


def _run_stack(params: Params, cfg: ArchConfig, x: jax.Array,
               positions: jax.Array, *, mode: str, cache=None,
               cache_index=None, block_table=None, chunk_lens=None,
               remat: bool = False, remat_policy: str = "nothing"):
    hd = cfg.resolved_head_dim
    cos, sin = L.rope_angles(
        positions, hd, cfg.rope_theta,
        cfg.mrope_sections if cfg.use_mrope and positions.ndim == 3 else None)

    has_cache = cache is not None

    def block_fn(spec):
        def fn(p, x, c):
            return _apply_block(p, x, cfg=cfg, spec=spec, cos=cos, sin=sin,
                                cache=c, cache_index=cache_index, mode=mode,
                                block_table=block_table,
                                chunk_lens=chunk_lens)
        if remat:
            # checkpoint at BLOCK granularity: backward recomputes one layer
            # at a time, so the live recompute working set is O(1 layer), not
            # O(pattern period) layers.
            fn = jax.checkpoint(fn, policy=REMAT_POLICIES[remat_policy]())
        return fn

    block_fns = [block_fn(spec) for spec in cfg.block_pattern]

    def body(carry, xs):
        x, aux = carry
        if has_cache:
            blocks_slice, cache_slice = xs
        else:
            blocks_slice, cache_slice = xs, (None,) * len(cfg.block_pattern)
        new_caches = []
        for pos in range(len(cfg.block_pattern)):
            x, nc, a = block_fns[pos](blocks_slice[pos], x, cache_slice[pos])
            aux = aux + a
            new_caches.append(nc)
        ys = tuple(new_caches) if has_cache and mode != "train" else None
        return (x, aux), ys

    xs = (params["blocks"], cache) if has_cache else params["blocks"]
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward_train(params: Params, cfg: ArchConfig,
                  inputs: Dict[str, jax.Array], *, remat: bool = True,
                  remat_policy: str = "nothing"
                  ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence logits. Returns (logits (B, S, V) f32, moe_aux)."""
    x, positions = frontends.embed_inputs(params["embed"], cfg, inputs)
    x, aux, _ = _run_stack(params, cfg, x, positions, mode="train",
                           remat=remat, remat_policy=remat_policy)
    return frontends.logits_from_hidden(params["embed"], cfg, x), aux




def loss_fn(params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array],
            *, remat: bool = True, ce_chunks: int = 8,
            remat_policy: str = "nothing"
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Masked next-token cross entropy + MoE aux. batch: inputs ∪
    {targets (B,S) int32, loss_mask (B,S)}.

    The unembedding + CE is computed in remat'd SEQUENCE CHUNKS with the
    target logit taken via one-hot contraction + logsumexp — both reduce over
    the (model-sharded) vocab axis.  This avoids (a) a (B,S,V) f32 logits
    buffer ever being live, and (b) the logits all-gather a
    ``take_along_axis`` on a sharded dim would force.
    """
    x, positions = frontends.embed_inputs(params["embed"], cfg, batch)
    x, aux, _ = _run_stack(params, cfg, x, positions, mode="train",
                           remat=remat, remat_policy=remat_policy)
    targets = batch["targets"]
    mask = batch["loss_mask"].astype(jnp.float32)
    b, s, d = x.shape
    n = _largest_divisor_leq(s, ce_chunks)
    c = s // n
    xc = x.reshape(b, n, c, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, c).transpose(1, 0, 2)
    mc = mask.reshape(b, n, c).transpose(1, 0, 2)

    def chunk_body(carry, xs):
        xx, tt, mm = xs
        logits = frontends.logits_from_hidden(params["embed"], cfg, xx)
        onehot = jax.nn.one_hot(tt, logits.shape[-1], dtype=logits.dtype)
        target_logit = jnp.einsum("bsv,bsv->bs", logits, onehot)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        nll = lse - target_logit
        ce_sum = (nll * mm).sum()
        acc_sum = ((logits.argmax(-1) == tt) * mm).sum()
        return (carry[0] + ce_sum, carry[1] + acc_sum), None

    if remat:
        chunk_body = jax.checkpoint(
            chunk_body, policy=jax.checkpoint_policies.nothing_saveable)
    (ce_sum, acc_sum), _ = jax.lax.scan(
        chunk_body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc, mc))
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = ce_sum / denom
    loss = ce + aux
    acc = acc_sum / denom
    return loss, {"ce": ce, "aux": aux, "acc": acc}


def prefill(params: Params, cfg: ArchConfig, inputs: Dict[str, jax.Array],
            max_len: int) -> Tuple[jax.Array, Tuple, jax.Array]:
    """Run the full prompt, fill a cache of capacity ``max_len``.

    Returns (logits_last (B, V), cache, next_index ())."""
    x, positions = frontends.embed_inputs(params["embed"], cfg, inputs)
    b, s = x.shape[:2]
    cache = init_cache(cfg, b, max_len)
    x, _, cache = _run_stack(params, cfg, x, positions, mode="prefill",
                             cache=cache)
    logits = frontends.logits_from_hidden(params["embed"], cfg, x[:, -1])
    return logits, cache, jnp.asarray(s, jnp.int32)


def decode_step(params: Params, cfg: ArchConfig, cache: Tuple,
                inputs: Dict[str, jax.Array], index: jax.Array,
                block_table: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Tuple]:
    """One decode step at cache slot ``index`` — () int32 for batch-uniform
    decode, or (B,) int32 for ragged slot-table decode where every batch row
    sits at its own cache position (per-row RoPE, KV scatter and attention
    mask; the whole slot table advances in ONE call).

    With ``block_table`` (B, P) int32, ``cache`` must be a paged cache
    (``init_paged_cache``): attention layers resolve position ``index``
    through the table to (page, offset) for the KV write and read the whole
    row page-indirectly — sequences can then share read-only prefix pages.

    Returns (logits (B, V), new_cache)."""
    x, positions = frontends.embed_decode(params["embed"], cfg, inputs, index)
    x, _, new_cache = _run_stack(params, cfg, x, positions, mode="decode",
                                 cache=cache, cache_index=index,
                                 block_table=block_table)
    logits = frontends.logits_from_hidden(params["embed"], cfg, x[:, -1])
    return logits, new_cache


def verify_step(params: Params, cfg: ArchConfig, cache: Tuple,
                inputs: Dict[str, jax.Array], index: jax.Array,
                block_table: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Tuple]:
    """Score a T = γ+1-token draft chunk in ONE step — the speculative
    verifier.  ``inputs`` holds (B, T) chunk tokens whose first token sits
    at cache slot ``index`` (() or (B,) int32; per-row ragged positions);
    KV for all T tokens is written at (page, offset) through
    ``block_table`` when given (or scattered densely), and attention is
    causal within the chunk via the multi-token scoring kernel.

    Returns (logits (B, T, V), new_cache): logits[:, t] conditions on the
    chunk prefix ..t, so the engine can compute the longest accepted draft
    prefix from one call.  Rolling back a rejected suffix is a pure per-row
    index decrement — drafts only ever write positions the row owns, and
    the ragged masks never read past the committed length, so the next
    chunk simply overwrites them (no page copies).  Only defined for
    attention-only stacks (recurrent state has no free rollback)."""
    x, positions = frontends.embed_decode(params["embed"], cfg, inputs,
                                          index)
    x, _, new_cache = _run_stack(params, cfg, x, positions, mode="verify",
                                 cache=cache, cache_index=index,
                                 block_table=block_table)
    return frontends.logits_from_hidden(params["embed"], cfg, x), new_cache


def prefill_chunk_step(params: Params, cfg: ArchConfig, cache: Tuple,
                       inputs: Dict[str, jax.Array], index: jax.Array,
                       block_table: Optional[jax.Array] = None,
                       chunk_lens: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, Tuple]:
    """Advance each row's cache by up to C tokens in ONE fused step — the
    chunked-prefill engine's workhorse.

    ``inputs`` holds a fixed-shape (B, C) chunk per row, mixed-modality via
    ``frontends.embed_chunk`` (region rows feed precomputed patch
    embeddings selected by ``patch_mask``; token rows feed prompt/answer
    ids); ``index``: (B,) int32 absolute cache slot of each row's first
    chunk token; ``chunk_lens``: (B,) int32 valid-token counts — rows are
    RAGGED, mixing C-token region chunks, 1-token prompt/decode rows,
    partial tail chunks and idle rows (0).  KV for the valid tokens is
    written at per-row (page, offset) through ``block_table`` (or scattered
    densely); padding tokens' writes are steered out of bounds and dropped.

    Returns (logits (B, V), new_cache): logits are materialised at ONE
    position per row — the LAST VALID chunk token — via a (B, d) hidden
    gather before the unembedding, so a C-token region chunk never pays a
    C·vocab unembed it would throw away (only the final chunk of a prefill
    stream, and decode/prompt rows, consume them).  Only defined for
    attention-only stacks: chunk boundaries are bit-stable for KV appends,
    so the chunked stream is token-for-token the unchunked stream."""
    x, positions = frontends.embed_chunk(params["embed"], cfg, inputs, index)
    x, _, new_cache = _run_stack(params, cfg, x, positions,
                                 mode="prefill_append", cache=cache,
                                 cache_index=index, block_table=block_table,
                                 chunk_lens=chunk_lens)
    if chunk_lens is None:
        xh = x[:, -1]
    else:
        last = jnp.clip(chunk_lens - 1, 0, x.shape[1] - 1)
        xh = jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return frontends.logits_from_hidden(params["embed"], cfg, xh), new_cache


def hidden_features(params: Params, cfg: ArchConfig,
                    inputs: Dict[str, jax.Array]) -> jax.Array:
    """Final-layer hidden states (B, S, d) — the paper's V(x)/E(T) feature
    space for Eq. (2) scoring and the confidence network input."""
    x, positions = frontends.embed_inputs(params["embed"], cfg, inputs)
    x, _, _ = _run_stack(params, cfg, x, positions, mode="train", remat=False)
    return x
