"""Jit-ready wrappers around the Pallas kernels with backend dispatch.

On TPU the Pallas implementations run natively; on this CPU-only container
the pure-jnp oracles in ``ref.py`` execute instead (Pallas TPU kernels cannot
lower for the CPU backend).  Tests pin ``impl="pallas_interpret"`` to execute
the kernel bodies in Python and compare against the oracle.

The wrappers also own layout adaptation (the models use (B, S, H, d); the
kernels want (B, H, S, d)) and head-dim padding to MXU-friendly multiples.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas,
                                            paged_prefill_attention_pallas)
from repro.kernels.ssm_scan import ssm_scan_pallas
from repro.kernels.region_score import region_score_pallas

Impl = Optional[str]
# None (auto) | "ref" | "flash_structured" | "pallas" | "pallas_interpret"

_DEFAULT_OVERRIDE: Optional[str] = None


def set_default_impl(impl: Optional[str]) -> Optional[str]:
    """Process-wide override (the dry-run sets "flash_structured" so the
    lowered HLO matches the TPU kernel's work profile).  Returns the
    previous override so callers can scope it (e.g. pin "ref" across a
    training phase — the serving kernels are inference-only and define no
    autodiff rules).  The override is read at trace time, and jit caches
    traces per function object: after switching, jit a new function (a
    fresh closure), or the old trace and its kernels are replayed."""
    global _DEFAULT_OVERRIDE
    prev = _DEFAULT_OVERRIDE
    _DEFAULT_OVERRIDE = impl
    return prev


def default_impl() -> str:
    if _DEFAULT_OVERRIDE:
        return _DEFAULT_OVERRIDE
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: Impl) -> Tuple[str, bool]:
    impl = impl or default_impl()
    if impl in ("ref", "flash_structured"):
        return impl, False
    if impl == "pallas":
        return "pallas", False
    if impl == "pallas_interpret":
        return "pallas", True
    raise ValueError(f"unknown impl {impl!r}")


def _tile_cfg(kernel: str, pool_dtype, interp: bool):
    """The autotuned tile knobs for (backend, kernel, pool dtype) — a
    pure-Python trace-time read of ``kernels/tuned/{backend}.json``
    (defaults when absent or ``REPRO_KERNEL_TUNED=off``), so tuned dispatch
    is exactly as compile-stable as a hard-coded constant.  Explicit caller
    kwargs override per call."""
    return autotune.lookup(kernel, autotune.dtype_key(pool_dtype),
                           interpret=interp)


# ---------------------------------------------------------------------------
# region_score
# ---------------------------------------------------------------------------

def region_score(v: jax.Array, e: jax.Array, *, impl: Impl = None) -> jax.Array:
    """Eq. (2): v (B, R, Nv, D), e (B, Ne, D) → (B, R) float32."""
    kind, interp = _resolve(impl)
    if kind in ("ref", "flash_structured"):
        return ref.region_score(v, e)
    return region_score_pallas(v, e, interpret=interp)


# ---------------------------------------------------------------------------
# flash attention (B, S, H, hd) model layout
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    impl: Impl = None) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) → (B, Sq, H, hd)."""
    kind, interp = _resolve(impl)
    if kind == "ref":
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale)
    if kind == "flash_structured":
        # named scope → HLO metadata tag; the roofline analyser re-attributes
        # this region's HBM traffic to the Pallas kernel's analytic bytes
        with jax.named_scope("KERNELREGION_flash"):
            return ref.flash_structured(q, k, v, causal, window, softcap,
                                        scale)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_pallas(qt, kt, vt, causal=causal, window=window,
                               softcap=softcap, scale=scale, interpret=interp)
    return o.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# decode attention (single query token per sequence)
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cache_len: jax.Array, *, window: int = 0,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None,
                     impl: Impl = None) -> jax.Array:
    """q: (B, H, hd); k, v: (B, S, K, hd); cache_len: () or (B,) int32
    (per-sequence valid-slot counts — ragged slot-table decode) → (B, H, hd)."""
    kind, interp = _resolve(impl)
    s = k.shape[1]
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if window > 0 and s > window:
        # static-size band slice around the current position: windowed decode
        # touches O(window) cache instead of O(S) — same trick the Pallas
        # kernel plays with block skipping, here at the HLO level.
        start = jnp.clip(cache_len - window, 0, s - window)
        if start.ndim == 0:
            k = jax.lax.dynamic_slice_in_dim(k, start, window, axis=1)
            v = jax.lax.dynamic_slice_in_dim(v, start, window, axis=1)
        else:
            # ragged lengths: each row slices its own band — a static-shape
            # (B, window) gather instead of B dynamic slices.
            rows = start[:, None] + jnp.arange(window)[None, :]
            k = jnp.take_along_axis(k, rows[:, :, None, None], axis=1)
            v = jnp.take_along_axis(v, rows[:, :, None, None], axis=1)
        cache_len = cache_len - start
    if kind in ("ref", "flash_structured"):
        with jax.named_scope("KERNELREGION_decode"):
            return ref.decode_attention(q, k, v, cache_len, window=window,
                                        softcap=softcap, scale=scale)
    b, h, hd = q.shape
    kh = k.shape[2]
    group = h // kh
    qg = q.reshape(b, kh, group, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    cfg = _tile_cfg("decode_dense", k.dtype, interp)
    o = decode_attention_pallas(qg, kt, vt, cache_len, window=window,
                                softcap=softcap, scale=scale,
                                kv_blk=cfg["kv_blk"], interpret=interp)
    return o.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# paged decode attention (page-pool layout; per-row block tables)
# ---------------------------------------------------------------------------

def _scale_to_kernel(scale: Optional[jax.Array]) -> Optional[jax.Array]:
    """Model-side per-slot scales (n_pages, page, KH) → the kernel layout
    (n_pages, KH, page, 1): the trailing length-1 lane keeps the in-kernel
    scale block 2D so it broadcasts straight against the (page, hd) K/V
    block."""
    if scale is None:
        return None
    return scale.transpose(0, 2, 1)[..., None]


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           cache_len: jax.Array, *, window: int = 0,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           fan: Optional[int] = None,
                           native_dot: Optional[bool] = None,
                           impl: Impl = None) -> jax.Array:
    """q: (B, H, hd); k_pool, v_pool: (n_pages, page, K, hd); block_table:
    (B, P) int32 (physical page per logical block); cache_len: () or (B,)
    int32 → (B, H, hd).

    The paged analogue of ``decode_attention``: each row reads its KV
    through its block table, so shared prefix pages are fetched once per
    page, not once per sequence.  ``k_scale``/``v_scale`` (n_pages, page, K)
    f32: the pools are int8/fp8 with per-slot symmetric scales, dequanted
    inside the kernel (see ``kernels/kv_quant.py``).  ``fan`` (page-block
    fan-in) and ``native_dot`` (fp8 widening-dot path) default to the
    backend's autotuned config (``kernels/autotune.py``)."""
    kind, interp = _resolve(impl)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if kind in ("ref", "flash_structured"):
        with jax.named_scope("KERNELREGION_decode"):
            return ref.paged_decode_attention(q, k_pool, v_pool, block_table,
                                              cache_len, window=window,
                                              softcap=softcap, scale=scale,
                                              k_scale=k_scale,
                                              v_scale=v_scale)
    b, h, hd = q.shape
    kh = k_pool.shape[2]
    group = h // kh
    qg = q.reshape(b, kh, group, hd)
    kp = k_pool.transpose(0, 2, 1, 3)     # (n_pages, KH, page, hd)
    vp = v_pool.transpose(0, 2, 1, 3)
    cfg = _tile_cfg("paged_decode", k_pool.dtype, interp)
    o = paged_decode_attention_pallas(qg, kp, vp, block_table, cache_len,
                                      window=window, softcap=softcap,
                                      scale=scale,
                                      fan=cfg["fan"] if fan is None else fan,
                                      k_scale=_scale_to_kernel(k_scale),
                                      v_scale=_scale_to_kernel(v_scale),
                                      native_dot=native_dot,
                                      interpret=interp)
    return o.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# multi-token scoring attention (speculative verify; q_len = γ+1 per row)
# ---------------------------------------------------------------------------

def _chunk_to_rows(q: jax.Array, kh: int):
    """(B, T, H, hd) → (B, KH, T·group, hd) token-major rows for the
    multi-token kernels (row r ↦ chunk token r // group)."""
    b, t, h, hd = q.shape
    group = h // kh
    qg = q.reshape(b, t, kh, group, hd).transpose(0, 2, 1, 3, 4)
    return qg.reshape(b, kh, t * group, hd)


def _rows_to_chunk(o: jax.Array, t: int, h: int):
    b, kh, rows, hd = o.shape
    group = rows // t
    return o.reshape(b, kh, t, group, hd).transpose(0, 2, 1, 3, 4) \
            .reshape(b, t, h, hd)


def multi_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           cache_len: jax.Array, *, window: int = 0,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           impl: Impl = None) -> jax.Array:
    """q: (B, T, H, hd) — T-token chunk at logical positions
    ``cache_len - T .. cache_len - 1``, causal within the chunk; k, v:
    (B, S, K, hd); cache_len: () or (B,) int32 INCLUDING the chunk
    → (B, T, H, hd).  The speculative verifier's dense scoring op."""
    kind, interp = _resolve(impl)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if kind in ("ref", "flash_structured"):
        with jax.named_scope("KERNELREGION_decode"):
            return ref.multi_decode_attention(q, k, v, cache_len,
                                              window=window, softcap=softcap,
                                              scale=scale)
    b, t, h, hd = q.shape
    kh = k.shape[2]
    cfg = _tile_cfg("decode_dense", k.dtype, interp)
    o = decode_attention_pallas(_chunk_to_rows(q, kh),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), cache_len,
                                window=window, softcap=softcap, scale=scale,
                                q_len=t, kv_blk=cfg["kv_blk"],
                                interpret=interp)
    return _rows_to_chunk(o, t, h)


def paged_multi_decode_attention(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, block_table: jax.Array,
                                 cache_len: jax.Array, *, window: int = 0,
                                 softcap: Optional[float] = None,
                                 scale: Optional[float] = None,
                                 k_scale: Optional[jax.Array] = None,
                                 v_scale: Optional[jax.Array] = None,
                                 fan: Optional[int] = None,
                                 native_dot: Optional[bool] = None,
                                 impl: Impl = None) -> jax.Array:
    """q: (B, T, H, hd); k_pool, v_pool: (n_pages, page, K, hd);
    block_table: (B, P) int32; cache_len: () or (B,) int32 INCLUDING the
    chunk → (B, T, H, hd).

    The speculative verifier's scoring op: ONE call emits attention for all
    T = γ+1 draft positions of every row through its block table (shared
    read-only prefix pages fetched once per page, never written).
    ``k_scale``/``v_scale`` (n_pages, page, K): int8/fp8 pools, in-kernel
    dequant (fp8 may take the native widening-dot path); ``fan`` defaults
    to the backend's autotuned ``paged_verify`` config."""
    kind, interp = _resolve(impl)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if kind in ("ref", "flash_structured"):
        with jax.named_scope("KERNELREGION_decode"):
            return ref.paged_multi_decode_attention(
                q, k_pool, v_pool, block_table, cache_len, window=window,
                softcap=softcap, scale=scale, k_scale=k_scale,
                v_scale=v_scale)
    b, t, h, hd = q.shape
    kh = k_pool.shape[2]
    cfg = _tile_cfg("paged_verify", k_pool.dtype, interp)
    o = paged_decode_attention_pallas(
        _chunk_to_rows(q, kh), k_pool.transpose(0, 2, 1, 3),
        v_pool.transpose(0, 2, 1, 3), block_table, cache_len, window=window,
        softcap=softcap, scale=scale, q_len=t,
        fan=cfg["fan"] if fan is None else fan,
        k_scale=_scale_to_kernel(k_scale),
        v_scale=_scale_to_kernel(v_scale), native_dot=native_dot,
        interpret=interp)
    return _rows_to_chunk(o, t, h)


# ---------------------------------------------------------------------------
# paged prefill-append attention (chunked prefill; q_len = C per row)
# ---------------------------------------------------------------------------

def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_table: jax.Array,
                            cache_len: jax.Array, *, window: int = 0,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            q_blk: Optional[int] = None,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None,
                            fan: Optional[int] = None,
                            native_dot: Optional[bool] = None,
                            impl: Impl = None) -> jax.Array:
    """q: (B, C, H, hd) — a C-token **prefill chunk** whose KV the caller
    just scattered at per-row (page, offset); k_pool, v_pool: (n_pages,
    page, K, hd); block_table: (B, P) int32; cache_len: () or (B,) int32
    INCLUDING the chunk → (B, C, H, hd).

    The chunked-prefill scoring op: chunk token ``t`` attends causally to
    its own chunk prefix plus all previously-written paged KV (columns
    ``< cache_len - (C - 1 - t)``).  Ragged engine rows (decode rows with
    C_eff = 1, partial tail chunks, idle rows) ride as rows whose
    ``cache_len`` reflects their own valid-token count; their padding
    positions produce garbage the engine discards and their padding KV
    writes were steered out of bounds by the model layer.  The Pallas path
    tiles the query-chunk axis in ``q_blk``-token sub-blocks (per-sub-block
    scratch + KV-block skipping) — the structural difference from the γ+1
    verify op, which holds the whole chunk in one block.  ``q_blk`` and
    ``fan`` default to the backend's autotuned ``paged_prefill`` config."""
    kind, interp = _resolve(impl)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if kind in ("ref", "flash_structured"):
        with jax.named_scope("KERNELREGION_decode"):
            return ref.paged_prefill_attention(
                q, k_pool, v_pool, block_table, cache_len, window=window,
                softcap=softcap, scale=scale, k_scale=k_scale,
                v_scale=v_scale)
    b, t, h, hd = q.shape
    kh = k_pool.shape[2]
    cfg = _tile_cfg("paged_prefill", k_pool.dtype, interp)
    o = paged_prefill_attention_pallas(
        _chunk_to_rows(q, kh), k_pool.transpose(0, 2, 1, 3),
        v_pool.transpose(0, 2, 1, 3), block_table, cache_len, window=window,
        softcap=softcap, scale=scale, q_len=t,
        q_blk=cfg["q_blk"] if q_blk is None else q_blk,
        fan=cfg["fan"] if fan is None else fan,
        k_scale=_scale_to_kernel(k_scale),
        v_scale=_scale_to_kernel(v_scale), native_dot=native_dot,
        interpret=interp)
    return _rows_to_chunk(o, t, h)


# ---------------------------------------------------------------------------
# chunked gated linear attention (model layout (B, S, H, d))
# ---------------------------------------------------------------------------

def ssm_scan(q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array,
             state: Optional[jax.Array] = None, *, chunk: int = 64,
             impl: Impl = None) -> Tuple[jax.Array, jax.Array]:
    """q, k: (B, S, H, dk); v: (B, S, H, dv); log_g: (B, S, H);
    state (B, H, dk, dv) → (o (B, S, H, dv), final_state)."""
    kind, interp = _resolve(impl)
    if kind in ("ref", "flash_structured"):
        with jax.named_scope("KERNELREGION_ssm"):
            return ref.ssm_scan(q, k, v, log_g, state, chunk=chunk)
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)
    o, sf = ssm_scan_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), log_g.transpose(0, 2, 1),
        state.astype(jnp.float32), chunk=chunk, interpret=interp)
    return o.transpose(0, 2, 1, 3), sf


ssm_decode_step = ref.ssm_decode_step  # O(1) per-token update; no kernel needed


# ---------------------------------------------------------------------------
# sLSTM recurrence
# ---------------------------------------------------------------------------

def slstm_scan(gates_x: jax.Array, r: jax.Array, state=None, *,
               impl: Impl = None):
    """gates_x: (B,S,4d) [z|i|f|o]; r: (H,P,4P) → (h (B,S,d), final state)."""
    kind, interp = _resolve(impl)
    if kind in ("ref", "flash_structured"):
        with jax.named_scope("KERNELREGION_slstm"):
            return ref.slstm_scan(gates_x, r, state)
    from repro.kernels.slstm_scan import slstm_scan_pallas
    assert state is None, "pallas slstm kernel starts from zero state"
    return slstm_scan_pallas(gates_x, r, interpret=interp)
