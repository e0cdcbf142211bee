"""Pallas TPU decode attention (flash-decoding style split-K).

One query token per sequence against a long KV cache.  The KV sequence axis
is the innermost grid dimension; partial (max, denom, accumulator) statistics
persist in VMEM scratch and are combined online — the split-K structure is
what lets the sequence axis also be sharded across devices for ``long_500k``
(each shard computes partial stats; the combine is a cheap psum done by the
wrapper in ``ops.py`` when run under shard_map).

``cache_len`` (#valid slots) arrives via scalar prefetch so block masks can
be computed without touching HBM.  It is a **per-sequence** ``(B,)`` vector:
continuous-batching slot tables hold sequences admitted at different times,
so each batch row sits at its own cache position and the kernel skips KV
blocks row-by-row (rows with short caches read O(cache_len) blocks, not
O(S)).  A scalar length broadcasts — batch-uniform decode is the special
case.  Rows with ``cache_len == 0`` attend to nothing and output zeros.

``paged_decode_attention_pallas`` is the page-indirect variant for the paged
KV cache (``serving/kv_pool.py``): K/V live in a pool of fixed-size pages
``(n_pages, KH, page, hd)`` and each row's logical KV blocks are resolved
through a per-row ``(B, pages)`` **block table**, scalar-prefetched next to
the length vector so the page indirection happens in the BlockSpec index map
(the DMA engine fetches the right physical page; the kernel body is the
dense kernel unchanged — logical column indices, masks and block skipping
are identical).  Shared prefix pages can therefore appear in many rows'
tables at zero extra cost.

Both kernels generalise to **multi-token query chunks** (``q_len > 1``): the
speculative-decoding verifier scores a γ+1-token draft chunk per sequence in
ONE pass, so the row axis of the query block becomes ``q_len · group`` rows
(token-major) and the mask is causal *within the chunk* — chunk token ``t``
(rows ``t·group .. (t+1)·group``) sees logical columns
``< cache_len - (q_len - 1 - t)``, where ``cache_len`` counts valid slots
INCLUDING all ``q_len`` chunk tokens.  ``q_len == 1`` reduces exactly to the
single-token decode above; shared read-only prefix pages are untouched (the
kernel never writes KV).

``paged_prefill_attention_pallas`` extends the multi-token form to the
**chunked-prefill** regime (Sarathi-style prefill chunks, C ≫ γ+1): the
query-chunk axis joins the grid in ``q_blk``-token sub-blocks, each with its
own online-softmax scratch and its own causal KV-block skip bounds, so large
prefix-append chunks stream through bounded VMEM and early chunk tokens
never fetch KV blocks only later tokens can see.

All three paged kernels additionally accept **quantized pools** (serving
``kv_dtype="int8"`` / ``"fp8"``): pass ``k_scale``/``v_scale`` pools of
per-(token-slot, head) symmetric scales, laid out ``(n_pages, KH, page, 1)``
so each scale block rides the SAME scalar-prefetched block-table indirection
as its K/V page and lands in VMEM next to it.  Dequantization is fused
in-register — the stored block is upcast and multiplied by its scale column
at the point the fp kernel already upcasts K/V — so quantized decode costs
one extra (page, 1) fetch and one multiply per page, never a separate
dequant pass over the pool.  For **fp8 (e4m3) pools** the kernels take a
``native_dot`` fast path where the backend supports widening fp8 matmuls
(TPU MXU; interpret mode for parity): the stored fp8 block feeds the dot
directly and the per-slot scale commutes *out* of the contraction — applied
to the score columns after the QK dot and folded into the probability rows
before the PV dot — skipping the explicit vector dequant entirely.  Scale
granularity is per token slot, not per page, so incremental writes never
requantize committed neighbours (see ``kernels/kv_quant.py`` for the
write-side numerics and the rationale).

Tunable tile knobs (``kv_blk`` for the dense kernel, page-block fan-in
``fan`` for the paged kernels — how many physical pages each grid step
fetches and reduces, shrinking the KV grid axis ``fan``× — and ``q_blk``
for the prefill kernel) are swept per (backend, kernel, dtype) by
``kernels/autotune.py``; ``ops.py`` consults the checked-in winners at
dispatch time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
FP8_DTYPE = jnp.float8_e4m3fn


def largest_divisor_leq(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``cap`` (block/chunk sizing:
    grids need the tile count to divide the axis exactly)."""
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def _kv_block_update(q, k_ref, v_ref, ks_ref, vs_ref, ikv, cache_len, t0,
                     acc_ref, m_ref, l_ref, *, scale, window, softcap,
                     kv_blk, q_len, group, native_dot, kv_total=None):
    """One KV block's online-softmax update (shared by the decode and
    prefill kernel bodies, and by every ``fan`` sub-block within a grid
    step).  ``t0`` is the first chunk-token index covered by this query
    block (0 for the un-tiled decode/verify kernels).

    Quantized pools (``ks_ref``/``vs_ref`` present) take one of two
    numerically-equivalent routes: explicit in-register dequant (upcast ×
    per-slot scale column — works for int8 and fp8 alike), or, with
    ``native_dot``, the widening-dot path for fp8 pools where the stored
    block feeds ``dot_general`` directly and the scale commutes out of the
    contraction: ``dot(q, k·s)[r, c] = dot(q, k)[r, c] · s[c]`` for QK, and
    ``dot(p, v·s) = dot(p·sᵀ, v)`` for PV.

    ``kv_total`` (dense kernel only) is the KV axis length when the last
    block runs past it: rows beyond it hold garbage (NaN in interpret
    mode), so they are zeroed before the PV dot — a masked score alone
    gives p == 0, and 0 · NaN is still NaN."""
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    if kv_total is not None:
        vrow = ikv * kv_blk + jax.lax.broadcasted_iota(jnp.int32,
                                                       (v.shape[0], 1), 0)
        v = jnp.where(vrow < kv_total, v, jnp.zeros_like(v))
    if ks_ref is not None and native_dot:
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * ks_ref[0, 0].reshape(1, -1) * scale
    else:
        k = k.astype(jnp.float32)
        if ks_ref is not None:
            # in-register dequant: quantized page × per-slot scale column
            k = k * ks_ref[0, 0]                      # (kv_blk, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    cols = ikv * kv_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # causal within the chunk: score row r belongs to chunk token
    # t = t0 + r // group whose effective valid length is
    # cache_len - (q_len - 1 - t); q_len == 1 reduces to t = 0,
    # eff_len = cache_len (plain decode)
    t = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    eff_len = cache_len - (q_len - 1) + t
    mask = cols < eff_len
    if window > 0:
        mask &= cols >= eff_len - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # explicit zero for masked columns: a chunk row that is FULLY masked
    # inside a needed block (0 < cache_len < q_len — an earlier chunk token
    # of a nearly-empty row) has m_new == NEG_INF, where exp(s - m_new)
    # alone would turn every masked score into 1 and emit mean(V) instead
    # of the documented zeros
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1)
    if vs_ref is not None and native_dot:
        pv = jax.lax.dot_general(p * vs_ref[0, 0].reshape(1, -1), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    else:
        v = v.astype(jnp.float32)
        if vs_ref is not None:
            v = v * vs_ref[0, 0]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[...] = m_new


def _parse_kv_refs(rest, fan):
    """Positional-ref layout shared by the kernel bodies: ``fan`` K blocks,
    ``fan`` V blocks, optionally ``fan`` + ``fan`` scale blocks (quantized
    pools only), then the output and the three online-softmax scratches.
    ``fan`` is static, so the presence of scales is unambiguous from the
    count alone."""
    quant = len(rest) == 4 * fan + 4
    k_refs = rest[:fan]
    v_refs = rest[fan:2 * fan]
    ks_refs = rest[2 * fan:3 * fan] if quant else (None,) * fan
    vs_refs = rest[3 * fan:4 * fan] if quant else (None,) * fan
    return k_refs, v_refs, ks_refs, vs_refs, rest[-4:]


def _decode_kernel(len_ref, q_ref, *rest, scale: float, window: int,
                   softcap: Optional[float], kv_blk: int, n_kv: int,
                   q_len: int = 1, group: int = 0, fan: int = 1,
                   native_dot: bool = False, kv_total=None):
    """Decode / multi-token verify body for one (batch row, KV head) and one
    KV grid step.  ``n_kv`` counts GRID steps along the KV axis; each step
    reduces ``fan`` consecutive logical blocks (sub-block ``f`` covers
    logical block ``ig·fan + f``), each skippable on its own bounds."""
    k_refs, v_refs, ks_refs, vs_refs, tail = _parse_kv_refs(rest, fan)
    o_ref, acc_ref, m_ref, l_ref = tail
    ib = pl.program_id(0)
    ig = pl.program_id(2)
    cache_len = len_ref[ib]

    @pl.when(ig == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (q_len·group, hd)
    # Skip blocks entirely outside [lo, cache_len).  For a multi-token chunk
    # the earliest row (chunk token 0) ends at cache_len - (q_len - 1), so
    # the windowed lower bound widens by the chunk length; the upper bound
    # is the last row's cache_len either way.
    lo = (jnp.maximum(cache_len - window - (q_len - 1), 0)
          if window > 0 else 0)
    for f in range(fan):
        ikv = ig * fan + f
        needed = (ikv * kv_blk < cache_len) & ((ikv + 1) * kv_blk > lo)

        @pl.when(needed)
        def _compute(k_ref=k_refs[f], v_ref=v_refs[f], ks_ref=ks_refs[f],
                     vs_ref=vs_refs[f], ikv=ikv):
            _kv_block_update(q, k_ref, v_ref, ks_ref, vs_ref, ikv,
                             cache_len, 0, acc_ref, m_ref, l_ref,
                             scale=scale, window=window, softcap=softcap,
                             kv_blk=kv_blk, q_len=q_len, group=group,
                             native_dot=native_dot, kv_total=kv_total)

    @pl.when(ig == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            cache_len: jax.Array, *, window: int = 0,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            kv_blk: int = 256, q_len: int = 1,
                            interpret: bool = False) -> jax.Array:
    """q: (B, KH, q_len·group, hd) token-major rows; k, v: (B, KH, S, hd);
    cache_len: () or (B,) int32 (per-sequence valid-slot counts INCLUDING
    the q_len chunk tokens) → (B, KH, q_len·group, hd).  ``q_len > 1``
    scores a multi-token chunk causally within the chunk (speculative
    verify); ``q_len == 1`` is plain decode.  ``kv_blk`` is the tunable KV
    tile (``kernels/autotune.py`` sweeps it per backend).

    A KV tile is the whole axis or a multiple of 8 rows (the TPU sublane
    tile).  When ``S`` is not a multiple of the tile the grid is
    ``cdiv(S, kv_blk)`` and the tail block's rows past ``S`` are masked —
    capacities like N_r + 1 + answer + γ are rarely round."""
    b, kh, rows, hd = q.shape
    s = k.shape[2]
    assert rows % q_len == 0
    group = rows // q_len
    scale = scale if scale is not None else hd ** -0.5
    kv_blk = s if s <= kv_blk else max(kv_blk - kv_blk % 8, 8)
    n_kv = pl.cdiv(s, kv_blk)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        kv_blk=kv_blk, n_kv=n_kv, q_len=q_len, group=group,
        kv_total=s if s % kv_blk else None)

    # list-built (not inline) so the spec count stays dynamic: the kernel
    # body takes the KV refs as a vararg tail the static arity check
    # cannot see (dense pools never pass scales; the paged wrappers may)
    in_specs = [
        pl.BlockSpec((1, 1, rows, hd), lambda b_, h_, ik, *_: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, kv_blk, hd), lambda b_, h_, ik, *_: (b_, h_, ik, 0)),
        pl.BlockSpec((1, 1, kv_blk, hd), lambda b_, h_, ik, *_: (b_, h_, ik, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda b_, h_, ik, *_: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
        ],
    )

    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rows, hd), q.dtype),
        interpret=interpret,
    )(cache_len, q, k, v)


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, *rest, **kw):
    """The dense kernel body, page-indirected: the block table only steers
    the BlockSpec index maps (which physical page each logical block DMAs
    from); the in-kernel math sees logical columns exactly as dense.  With
    quantized pools ``rest`` additionally carries the scale blocks, whose
    index maps follow the same table."""
    del tbl_ref
    _decode_kernel(len_ref, q_ref, *rest, **kw)


def _resolve_fan(fan: int, n_blocks: int) -> int:
    return largest_divisor_leq(n_blocks, max(int(fan), 1))


def _resolve_native_dot(native_dot: Optional[bool], pool_dtype) -> bool:
    """The fp8 widening-dot path: on by default for fp8 pools (TPU MXU and
    interpret mode both take widening fp8 operands), never for int8 (an
    integer operand cannot feed the fp contraction — int8 always takes the
    explicit dequant route).  Pass ``native_dot=False`` to force the
    dequant fallback on a backend whose compiler rejects mixed-precision
    dots."""
    if pool_dtype != jnp.dtype(FP8_DTYPE):
        return False
    return True if native_dot is None else bool(native_dot)


def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, block_table: jax.Array,
                                  cache_len: jax.Array, *, window: int = 0,
                                  softcap: Optional[float] = None,
                                  scale: Optional[float] = None,
                                  q_len: int = 1, fan: int = 1,
                                  k_scale: Optional[jax.Array] = None,
                                  v_scale: Optional[jax.Array] = None,
                                  native_dot: Optional[bool] = None,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, KH, q_len·group, hd) token-major rows; k_pool, v_pool:
    (n_pages, KH, page, hd); block_table: (B, P) int32 physical page per
    logical block; cache_len: () or (B,) int32 (INCLUDING the q_len chunk
    tokens) → (B, KH, q_len·group, hd).

    Logical KV position ``s`` of row ``b`` lives at
    ``pool[block_table[b, s // page], :, s % page]``; masks/skipping use the
    logical position, so the result equals dense decode over the gathered
    cache.  ``q_len > 1`` is the multi-token speculative scoring chunk,
    causal within the chunk; the kernel only ever reads the pools, so shared
    read-only prefix pages are untouched.

    ``fan`` (page-block fan-in, autotuned per backend) makes each grid step
    fetch and reduce ``fan`` consecutive logical blocks — ``fan`` repeated
    pool operands whose index maps read table entries ``ig·fan + f`` —
    shrinking the KV grid axis ``fan``× at the cost of a wider per-step
    VMEM working set.  Clamped to a divisor of the table width.

    ``k_scale``/``v_scale`` (both or neither): quantized pools with
    per-slot symmetric scales ``(n_pages, KH, page, 1)`` f32 — each scale
    block's index map follows the same block-table entry as its page, and
    the kernel dequants in-register before the QK/PV dots (or, for fp8
    pools with ``native_dot``, feeds the fp8 block to the widening dot and
    applies the scale past the contraction)."""
    b, kh, rows, hd = q.shape
    page = k_pool.shape[2]
    n_blocks = block_table.shape[1]
    assert rows % q_len == 0
    group = rows // q_len
    scale = scale if scale is not None else hd ** -0.5
    fan = _resolve_fan(fan, n_blocks)
    n_grid = n_blocks // fan

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window, softcap=softcap,
        kv_blk=page, n_kv=n_grid, q_len=q_len, group=group, fan=fan,
        native_dot=_resolve_native_dot(native_dot, k_pool.dtype))

    def page_map(f):
        def m(b_, h_, ig, tbl, lens):
            return (tbl[b_, ig * fan + f], h_, 0, 0)
        return m

    in_specs = [pl.BlockSpec((1, 1, rows, hd),
                             lambda b_, h_, ig, tbl, lens: (b_, h_, 0, 0))]
    in_specs += [pl.BlockSpec((1, 1, page, hd), page_map(f))
                 for f in range(fan)]
    in_specs += [pl.BlockSpec((1, 1, page, hd), page_map(f))
                 for f in range(fan)]
    operands = (q,) + (k_pool,) * fan + (v_pool,) * fan
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if k_scale is not None:
        in_specs += [pl.BlockSpec((1, 1, page, 1), page_map(f))
                     for f in range(fan)]
        in_specs += [pl.BlockSpec((1, 1, page, 1), page_map(f))
                     for f in range(fan)]
        operands += (k_scale,) * fan + (v_scale,) * fan

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, n_grid),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda b_, h_, ig, tbl, lens: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
        ],
    )

    block_table = jnp.asarray(block_table, jnp.int32)
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rows, hd), q.dtype),
        interpret=interpret,
    )(block_table, cache_len, *operands)


def _prefill_append_kernel(tbl_ref, len_ref, q_ref, *rest, scale: float,
                           window: int, softcap: Optional[float],
                           kv_blk: int, n_kv: int, q_len: int, q_blk: int,
                           group: int, fan: int = 1,
                           native_dot: bool = False):
    """Prefix-append attention for one (batch row, KV head, query sub-block,
    KV grid step) cell.  The query-chunk axis is tiled: sub-block ``iq``
    covers chunk tokens ``iq·q_blk .. iq·q_blk + q_blk - 1``, so only its
    own causal prefix of KV blocks is fetched — early chunk tokens of a
    long prefill chunk skip the blocks that only later tokens can see, and
    the per-sub-block VMEM footprint stays q_blk·group rows no matter how
    large the chunk is (the γ+1 verify kernel holds the whole chunk in one
    block, which is fine for small γ but not for C-token prefill chunks).
    Each KV grid step reduces ``fan`` consecutive logical blocks, each
    skippable on its own causal bounds."""
    k_refs, v_refs, ks_refs, vs_refs, tail = _parse_kv_refs(rest, fan)
    o_ref, acc_ref, m_ref, l_ref = tail
    ib = pl.program_id(0)
    iq = pl.program_id(2)
    ig = pl.program_id(3)
    cache_len = len_ref[ib]
    t0 = iq * q_blk

    @pl.when(ig == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (q_blk·group, hd)
    # chunk token t has effective length cache_len - (q_len - 1) + t; this
    # sub-block's tokens span [t0, t0 + q_blk), so its last row bounds the
    # columns it can ever read and its first row bounds the window floor
    hi = cache_len - (q_len - 1) + t0 + q_blk - 1   # last row's eff length
    lo = (jnp.maximum(cache_len - (q_len - 1) + t0 - window, 0)
          if window > 0 else 0)
    for f in range(fan):
        ikv = ig * fan + f
        needed = (ikv * kv_blk < hi) & ((ikv + 1) * kv_blk > lo)

        @pl.when(needed)
        def _compute(k_ref=k_refs[f], v_ref=v_refs[f], ks_ref=ks_refs[f],
                     vs_ref=vs_refs[f], ikv=ikv):
            _kv_block_update(q, k_ref, v_ref, ks_ref, vs_ref, ikv,
                             cache_len, t0, acc_ref, m_ref, l_ref,
                             scale=scale, window=window, softcap=softcap,
                             kv_blk=kv_blk, q_len=q_len, group=group,
                             native_dot=native_dot)

    @pl.when(ig == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def paged_prefill_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                   v_pool: jax.Array, block_table: jax.Array,
                                   cache_len: jax.Array, *, window: int = 0,
                                   softcap: Optional[float] = None,
                                   scale: Optional[float] = None,
                                   q_len: int = 1, q_blk: int = 8,
                                   fan: int = 1,
                                   k_scale: Optional[jax.Array] = None,
                                   v_scale: Optional[jax.Array] = None,
                                   native_dot: Optional[bool] = None,
                                   interpret: bool = False) -> jax.Array:
    """Chunked-prefill **prefix-append** attention, page-indirect.

    q: (B, KH, q_len·group, hd) token-major rows of a q_len-token prefill
    chunk whose KV the caller just wrote at per-row (page, offset);
    k_pool, v_pool: (n_pages, KH, page, hd); block_table: (B, P) int32;
    cache_len: () or (B,) int32 valid-slot counts INCLUDING the chunk
    → (B, KH, q_len·group, hd).

    Semantics are exactly ``paged_decode_attention_pallas`` at the same
    ``q_len`` (chunk token ``t`` sees logical columns
    ``< cache_len - (q_len - 1 - t)``); the difference is structural: the
    query-chunk axis joins the grid in ``q_blk``-token sub-blocks with
    per-sub-block online-softmax scratch and per-sub-block KV-block
    skipping, so a C-token chunk costs O(Σ_t prefix_t) block fetches and
    bounded VMEM instead of one C·group-row mega-block — the shape a
    Sarathi-style chunked prefill feeds (C ≫ γ+1).  ``q_blk`` and the
    page-block fan-in ``fan`` are the autotuned tile knobs.

    ``k_scale``/``v_scale`` (both or neither): quantized pools with
    per-slot symmetric scales ``(n_pages, KH, page, 1)`` f32, dequanted
    in-register (or scale-commuted around the native fp8 dot) exactly as in
    ``paged_decode_attention_pallas``."""
    b, kh, rows, hd = q.shape
    page = k_pool.shape[2]
    n_blocks = block_table.shape[1]
    assert rows % q_len == 0
    group = rows // q_len
    scale = scale if scale is not None else hd ** -0.5
    if q_len % q_blk != 0:
        q_blk = largest_divisor_leq(q_len, q_blk)
    if (q_blk * group) % 8 and q_blk != q_len:
        # a query sub-block must span a multiple of 8 rows (the TPU sublane
        # tile) unless it is the whole chunk
        q_blk = q_len
    n_q = q_len // q_blk
    sub_rows = q_blk * group
    fan = _resolve_fan(fan, n_blocks)
    n_grid = n_blocks // fan

    kernel = functools.partial(
        _prefill_append_kernel, scale=scale, window=window, softcap=softcap,
        kv_blk=page, n_kv=n_grid, q_len=q_len, q_blk=q_blk, group=group,
        fan=fan, native_dot=_resolve_native_dot(native_dot, k_pool.dtype))

    def page_map(f):
        def m(b_, h_, iq, ig, tbl, lens):
            return (tbl[b_, ig * fan + f], h_, 0, 0)
        return m

    in_specs = [pl.BlockSpec((1, 1, sub_rows, hd),
                             lambda b_, h_, iq, ig, tbl, lens:
                             (b_, h_, iq, 0))]
    in_specs += [pl.BlockSpec((1, 1, page, hd), page_map(f))
                 for f in range(fan)]
    in_specs += [pl.BlockSpec((1, 1, page, hd), page_map(f))
                 for f in range(fan)]
    operands = (q,) + (k_pool,) * fan + (v_pool,) * fan
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if k_scale is not None:
        in_specs += [pl.BlockSpec((1, 1, page, 1), page_map(f))
                     for f in range(fan)]
        in_specs += [pl.BlockSpec((1, 1, page, 1), page_map(f))
                     for f in range(fan)]
        operands += (k_scale,) * fan + (v_scale,) * fan

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, n_q, n_grid),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, sub_rows, hd),
                               lambda b_, h_, iq, ig, tbl, lens:
                               (b_, h_, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((sub_rows, hd), jnp.float32),
            pltpu.VMEM((sub_rows,), jnp.float32),
            pltpu.VMEM((sub_rows,), jnp.float32),
        ],
    )

    block_table = jnp.asarray(block_table, jnp.int32)
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rows, hd), q.dtype),
        interpret=interpret,
    )(block_table, cache_len, *operands)
