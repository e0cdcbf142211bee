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
the length vector.  Its grid has one step per row, whose cost follows the
row's live pages, not the table width: an in-kernel loop walks the row's
needed blocks in chunks of ``fan`` pages, copying them from HBM into a
double-buffered VMEM chunk by async copies whose page ids it reads from the
table — one copy per page (both KV heads at once: one page of the pool is
contiguous), or one for the whole chunk where its pages are consecutive in
the pool — so chunk ``i + 1``'s copies run while chunk ``i`` is reduced.
Logical column indices and masks are the dense kernel's, so shared prefix
pages can appear in many rows' tables at zero extra cost.

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

Tunable tile knobs (``kv_blk`` for the dense kernel; ``fan`` for the paged
kernels — pages per in-kernel chunk for decode / verify, picked from the
shapes when unset, and pages per grid step for prefill; ``q_blk`` for the
prefill kernel) are swept per (backend, kernel, dtype) by
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


def _kv_block_update(q, k, v, ks, vs, col0, cache_len, t0,
                     acc_ref, m_ref, l_ref, *, scale, window, softcap,
                     q_len, group, native_dot, kv_total=None):
    """One KV block's online-softmax update, shared by every kernel body:
    the dense and prefill kernels' pipelined blocks and the paged decode
    kernel's copied chunks.  ``k``/``v`` are the block's ``(rows, hd)``
    values as stored, ``ks``/``vs`` their ``(rows, 1)`` f32 scale columns
    (quantized pools) or None; ``col0`` is the logical column of the
    block's first row and ``t0`` the first chunk-token index covered by
    this query block (0 for the un-tiled decode/verify kernels).

    Quantized pools take one of two numerically-equivalent routes:
    explicit in-register dequant (upcast × per-slot scale column — works
    for int8 and fp8 alike), or, with ``native_dot``, the widening-dot path
    for fp8 pools where the stored block feeds ``dot_general`` directly and
    the scale commutes out of the contraction: ``dot(q, k·s)[r, c] =
    dot(q, k)[r, c] · s[c]`` for QK, and ``dot(p, v·s) = dot(p·sᵀ, v)`` for
    PV.

    ``kv_total`` (dense kernel only) is the KV axis length when the last
    block runs past it: rows beyond it hold garbage (NaN in interpret
    mode), so they are zeroed before the PV dot — a masked score alone
    gives p == 0, and 0 · NaN is still NaN."""
    if kv_total is not None:
        vrow = col0 + jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
        v = jnp.where(vrow < kv_total, v, jnp.zeros_like(v))
    if ks is not None and native_dot:
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * ks.reshape(1, -1) * scale
    else:
        k = k.astype(jnp.float32)
        if ks is not None:
            # in-register dequant: quantized page × per-slot scale column
            k = k * ks                                # (rows, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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
    if vs is not None and native_dot:
        pv = jax.lax.dot_general(p * vs.reshape(1, -1), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    else:
        v = v.astype(jnp.float32)
        if vs is not None:
            v = v * vs
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[...] = m_new


def _init_stats(acc_ref, m_ref, l_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _normalized(acc_ref, l_ref, dtype):
    denom = jnp.maximum(l_ref[...], 1e-30)
    return (acc_ref[...] / denom[:, None]).astype(dtype)


def _parse_kv_refs(rest, fan):
    """Positional-ref layout of the prefill kernel body: ``fan`` K blocks,
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, window: int,
                   softcap: Optional[float], kv_blk: int, n_kv: int,
                   q_len: int = 1, group: int = 0, kv_total=None):
    """Dense decode / multi-token verify body for one (batch row, KV head)
    and one KV block, skipped when it lies outside the row's bounds."""
    ib = pl.program_id(0)
    ikv = pl.program_id(2)
    cache_len = len_ref[ib]

    @pl.when(ikv == 0)
    def _init():
        _init_stats(acc_ref, m_ref, l_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (q_len·group, hd)
    # Skip blocks entirely outside [lo, cache_len).  For a multi-token chunk
    # the earliest row (chunk token 0) ends at cache_len - (q_len - 1), so
    # the windowed lower bound widens by the chunk length; the upper bound
    # is the last row's cache_len either way.
    lo = (jnp.maximum(cache_len - window - (q_len - 1), 0)
          if window > 0 else 0)
    needed = (ikv * kv_blk < cache_len) & ((ikv + 1) * kv_blk > lo)

    @pl.when(needed)
    def _compute():
        _kv_block_update(q, k_ref[0, 0], v_ref[0, 0], None, None,
                         ikv * kv_blk, cache_len, 0, acc_ref, m_ref, l_ref,
                         scale=scale, window=window, softcap=softcap,
                         q_len=q_len, group=group, native_dot=False,
                         kv_total=kv_total)

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = _normalized(acc_ref, l_ref, o_ref.dtype)


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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda b_, h_, ik, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b_, h_, ik, *_: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b_, h_, ik, *_: (b_, h_, ik, 0)),
        ],
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


#: what one paged decode chunk aims to cover: enough tokens that a chunk's
#: copies and its two dots outweigh the loop's fixed cost, few enough that
#: a short row does not copy much past its end (on a v5e at the
#: Qwen2-VL-2B decode shape, 512-token chunks beat 256 and 128 at
#: contexts of 1k-2k tokens and lose to them under 64)
CHUNK_TOKENS = 512
#: VMEM the double-buffered chunk (K, V and scale pages) may take
CHUNK_VMEM_BYTES = 4 << 20
#: pages per iteration of the kernel's page loops: a loop iteration per
#: page costs the scalar core about as much as the copy it issues, while a
#: fully unrolled chunk makes every program that holds the kernel slower
#: to lower at start-up
COPY_UNROLL = 8


def _page_vmem_bytes(kh: int, page: int, hd: int, dtype, quant: bool) -> int:
    """VMEM of one page of K plus V (and their scales) for every KV head,
    padded to the TPU's (sublane · packing, 128) tiles."""
    item = jnp.dtype(dtype).itemsize
    rows = -(-page // (8 * (4 // item))) * (8 * (4 // item))
    lanes = -(-hd // 128) * 128
    nbytes = 2 * kh * rows * lanes * item
    if quant:
        nbytes += 2 * kh * (-(-page // 8) * 8) * 128 * 4
    return nbytes


def chunk_pages(fan: Optional[int], n_blocks: int, kh: int, page: int,
                hd: int, dtype, quant: bool) -> int:
    """Pages per paged decode chunk: ``fan`` when given, else
    ``CHUNK_TOKENS`` worth of pages, within ``CHUNK_VMEM_BYTES`` for the
    two buffers; never more than the table holds.  The last chunk of a row
    may be partial: it copies the row's last live page again in the spare
    places, whose columns the length mask hides."""
    if fan is None:
        fan = max(CHUNK_TOKENS // page, 1)
        per_page = 2 * _page_vmem_bytes(kh, page, hd, dtype, quant)
        fan = min(fan, max(CHUNK_VMEM_BYTES // per_page, 1))
    return max(min(int(fan), n_blocks), 1)


def _page_loop(n: int, body, carry):
    """``carry = body(j, carry)`` for pages ``j`` in ``[0, n)``, in loop
    iterations of ``COPY_UNROLL`` pages (Mosaic unrolls a loop wholly or
    not at all, so the grouping is spelled out)."""
    u = min(COPY_UNROLL, n)

    def group(g, c):
        for k in range(u):
            c = body(g * u + k, c)
        return c

    carry = jax.lax.fori_loop(0, n // u, group, carry)
    for j in range(n - n % u, n):
        carry = body(j, carry)
    return carry


def _chunk_rows(buf, slot, h, width):
    """KV head ``h`` of a copied chunk as ``(fan·page, width)`` f32 rows.
    The widening comes first: a page is 8 rows, under the packed tile of
    16-bit and 8-bit types, so only f32 pages fold into rows in place (the
    widening is exact, and the dots take f32 operands as before)."""
    x = buf[slot, :, h].astype(jnp.float32)          # (fan, page, lanes)
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])[:, :width]


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, *refs, scale: float,
                         window: int, softcap: Optional[float], page: int,
                         fan: int, n_blocks: int, q_len: int, group: int,
                         native_dot: bool, n_pools: int):
    """Paged decode / multi-token verify body for one batch row, every KV
    head.  ``refs``: the ``n_pools`` pools left in HBM (K, V and, for
    quantized pools, their scales), the output block, a ``(2, fan, KH,
    page, ·)`` VMEM chunk buffer per pool, the two chunk slots' DMA
    semaphores, then each KV head's accumulator, max and denominator.

    The row's needed blocks are ``[first, n_live)``: ``n_live`` covers its
    ``cache_len`` and ``first`` its window's floor (the earliest chunk
    token's, as the dense kernel's skip test has it).  They are walked in
    chunks of ``fan`` blocks; chunk ``i + 1``'s copies are started before
    chunk ``i`` is waited on and reduced.  A row with no needed block
    copies nothing and writes zeros."""
    pools = refs[:n_pools]
    o_ref = refs[n_pools]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sem = refs[2 * n_pools + 1]
    stats = refs[2 * n_pools + 2:]
    heads = [stats[3 * h:3 * h + 3] for h in range(len(stats) // 3)]
    hd = q_ref.shape[-1]
    b = pl.program_id(0)
    cache_len = len_ref[b]
    n_live = jnp.minimum(pl.cdiv(cache_len, page), n_blocks)
    if window > 0:
        lo = jnp.maximum(cache_len - window - (q_len - 1), 0)
        first = jnp.minimum(lo // page, n_live)
    else:
        first = 0
    n_chunks = pl.cdiv(n_live - first, fan)

    def chunk_copies(chunk, slot, op):
        """Start (or wait for) chunk ``chunk``'s copies into ``slot``: one
        copy per pool where the chunk's pages are consecutive in the pool
        (the allocator hands out runs), else one per page; the spare places
        of a partial last chunk take the row's last live page again."""
        blk0 = first + chunk * fan

        def pid(j):
            return tbl_ref[b, jnp.minimum(blk0 + j, n_live - 1)]

        pid0 = pid(0)
        run = _page_loop(fan, lambda j, r: r & (pid(j) == pid0 + j),
                         blk0 + fan <= n_live)

        @pl.when(run)
        def _one_copy():
            for pool, buf in zip(pools, bufs):
                getattr(pltpu.make_async_copy(pool.at[pl.ds(pid0, fan)],
                                              buf.at[slot], sem.at[slot]),
                        op)()

        @pl.when(jnp.logical_not(run))
        def _page_copies():
            def page(j, carry):
                p = pid(j)
                for pool, buf in zip(pools, bufs):
                    getattr(pltpu.make_async_copy(pool.at[p],
                                                  buf.at[slot, j],
                                                  sem.at[slot]), op)()
                return carry
            _page_loop(fan, page, 0)

    for acc_m_l in heads:
        _init_stats(*acc_m_l)

    @pl.when(n_chunks > 0)
    def _prefetch_first():
        chunk_copies(0, 0, "start")

    def chunk_body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_chunks)
        def _prefetch_next():
            chunk_copies(i + 1, 1 - slot, "start")

        chunk_copies(i, slot, "wait")
        col0 = (first + i * fan) * page
        for h, acc_m_l in enumerate(heads):
            kv = [_chunk_rows(buf, slot, h, w)
                  for buf, w in zip(bufs, (hd, hd, 1, 1))]
            ks, vs = kv[2:] if n_pools == 4 else (None, None)
            _kv_block_update(q_ref[0, h].astype(jnp.float32), kv[0], kv[1],
                             ks, vs, col0, cache_len, 0, *acc_m_l,
                             scale=scale, window=window, softcap=softcap,
                             q_len=q_len, group=group, native_dot=native_dot)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    for h, (acc_ref, _, l_ref) in enumerate(heads):
        o_ref[0, h] = _normalized(acc_ref, l_ref, o_ref.dtype)


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
                                  q_len: int = 1, fan: Optional[int] = None,
                                  k_scale: Optional[jax.Array] = None,
                                  v_scale: Optional[jax.Array] = None,
                                  native_dot: Optional[bool] = None,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, KH, q_len·group, hd) token-major rows; k_pool, v_pool:
    (n_pages, KH, page, hd); block_table: (B, P) int32 physical page per
    logical block; cache_len: () or (B,) int32 (INCLUDING the q_len chunk
    tokens) → (B, KH, q_len·group, hd).

    Logical KV position ``s`` of row ``b`` lives at
    ``pool[block_table[b, s // page], :, s % page]``; masks use the
    logical position, so the result equals dense decode over the gathered
    cache.  ``q_len > 1`` is the multi-token speculative scoring chunk,
    causal within the chunk; the kernel only ever reads the pools, so shared
    read-only prefix pages are untouched.

    One grid step per row.  The pools stay in HBM; the step copies the
    row's needed pages, ``fan`` per chunk (``chunk_pages``: about
    ``CHUNK_TOKENS`` tokens when ``fan`` is None), into a double-buffered
    VMEM chunk — one async copy per page covering every KV head, or one
    per pool where the chunk's pages are consecutive — and reduces each
    chunk with the online-softmax update while the next chunk's copies
    run.  So a row costs its live pages, not the table width, and a row
    with ``cache_len == 0`` copies nothing.  The width need not be a
    multiple of ``fan``: a partial last chunk is masked by the logical
    column test.

    ``k_scale``/``v_scale`` (both or neither): quantized pools with
    per-slot symmetric scales ``(n_pages, KH, page, 1)`` f32 — each scale
    page rides the same copy schedule as its K/V page, and the kernel
    dequants in-register before the QK/PV dots (or, for fp8 pools with
    ``native_dot``, applies the scale past the contraction)."""
    b, kh, rows, hd = q.shape
    page = k_pool.shape[2]
    n_blocks = block_table.shape[1]
    assert rows % q_len == 0
    group = rows // q_len
    scale = scale if scale is not None else hd ** -0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    pools = (k_pool, v_pool)
    if k_scale is not None:
        # a copied slice must span whole (8, 128) tiles, and HBM already
        # pads a scale page's single lane to 128: make the padding explicit
        lanes = ((0, 0),) * 3 + ((0, 127),)
        pools += (jnp.pad(k_scale, lanes), jnp.pad(v_scale, lanes))
    fan = chunk_pages(fan, n_blocks, kh, page, hd, k_pool.dtype,
                      k_scale is not None)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window, softcap=softcap,
        page=page, fan=fan, n_blocks=n_blocks, q_len=q_len, group=group,
        native_dot=_resolve_native_dot(native_dot, k_pool.dtype),
        n_pools=len(pools))

    row_block = pl.BlockSpec((1, kh, rows, hd),
                             lambda b_, tbl, lens: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_block] + [pl.BlockSpec(memory_space=pl.ANY)]
        * len(pools),
        out_specs=row_block,
        scratch_shapes=[pltpu.VMEM((2, fan) + p.shape[1:], p.dtype)
                        for p in pools]
        + [pltpu.SemaphoreType.DMA((2,))]
        + [pltpu.VMEM(shape, jnp.float32) for _ in range(kh)
           for shape in ((rows, hd), (rows,), (rows,))],
    )

    block_table = jnp.asarray(block_table, jnp.int32)
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rows, hd), q.dtype),
        interpret=interpret,
    )(block_table, cache_len, q, *pools)


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
        _init_stats(acc_ref, m_ref, l_ref)

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
            _kv_block_update(q, k_ref[0, 0], v_ref[0, 0],
                             None if ks_ref is None else ks_ref[0, 0],
                             None if vs_ref is None else vs_ref[0, 0],
                             ikv * kv_blk, cache_len, t0, acc_ref, m_ref,
                             l_ref, scale=scale, window=window,
                             softcap=softcap, q_len=q_len, group=group,
                             native_dot=native_dot)

    @pl.when(ig == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = _normalized(acc_ref, l_ref, o_ref.dtype)


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
