"""Collective helpers: ZeRO-friendly gradient sync + comm/compute overlap.

- ``reduce_scatter_grads`` / ``all_gather_params``: the reduce-scatter →
  local-update → all-gather decomposition of the data-parallel step (half
  the link bytes of a plain all-reduce when combined with ZeRO-1 sharded
  optimizer state).
- ``chunked_psum``: splits one large gradient psum into per-leaf chunks
  issued eagerly, letting XLA's latency-hiding scheduler overlap each
  chunk's all-reduce with the backward compute that produces the next —
  the standard bucketed-overlap pattern expressed jax-natively.
- ``tp_context`` + ``tp_attn_all_reduce`` / ``tp_mlp_all_reduce``: the
  serving engine's tensor-parallel hooks.  ``models/layers.py`` calls the
  all-reduce helpers unconditionally after its attention / MLP output
  projections; outside a ``tp_context`` they are identity (the
  single-device engine stays byte-for-byte untouched), and inside one they
  psum partial outputs over the model axis — but only for the sublayer
  kinds the context marks as actually head-/ffn-sharded, so a replicated
  sublayer is never multiplied by the TP degree.
"""
from __future__ import annotations

import contextlib
from typing import Any, List, Optional

import jax
import jax.numpy as jnp


Params = Any


# ---------------------------------------------------------------------------
# Serving tensor-parallel context
# ---------------------------------------------------------------------------

_TP_AXIS: Optional[str] = None
_TP_ATTN: bool = False
_TP_MLP: bool = False


@contextlib.contextmanager
def tp_context(axis: str, *, attn: bool = False, mlp: bool = False):
    """Arm the TP all-reduce hooks while a sharded step function traces.

    Trace-time state, not run-time: enter this around the model call inside
    a ``shard_map`` body so the psums are staged into the jaxpr.  ``attn`` /
    ``mlp`` flag which sublayers hold sharded parameters (partial-sum
    outputs); the hooks stay identity for the rest.
    """
    global _TP_AXIS, _TP_ATTN, _TP_MLP
    prev = (_TP_AXIS, _TP_ATTN, _TP_MLP)
    _TP_AXIS, _TP_ATTN, _TP_MLP = axis, attn, mlp
    try:
        yield
    finally:
        _TP_AXIS, _TP_ATTN, _TP_MLP = prev


def tp_attn_all_reduce(x: jax.Array) -> jax.Array:
    """Sum attention-output partials over the model axis (identity when no
    ``tp_context`` is active or attention is not head-sharded)."""
    if _TP_AXIS is not None and _TP_ATTN:
        return jax.lax.psum(x, _TP_AXIS)
    return x


def tp_mlp_all_reduce(x: jax.Array) -> jax.Array:
    """Sum MLP-output partials over the model axis (identity when no
    ``tp_context`` is active or the FFN is not sharded)."""
    if _TP_AXIS is not None and _TP_MLP:
        return jax.lax.psum(x, _TP_AXIS)
    return x


def reduce_scatter_grads(grads: Params, axis: str) -> Params:
    """psum_scatter each leaf over ``axis`` (leading dim must divide)."""
    size = jax.lax.axis_size(axis)

    def one(g):
        if g.ndim == 0 or g.shape[0] % size != 0:
            return jax.lax.psum(g, axis) / size
        return jax.lax.psum_scatter(g, axis, scatter_dimension=0,
                                    tiled=True) / size
    return jax.tree.map(one, grads)


def all_gather_params(shards: Params, full_like: Params, axis: str) -> Params:
    def one(s, f):
        if s.shape == f.shape:
            return s
        return jax.lax.all_gather(s, axis, axis=0, tiled=True)
    return jax.tree.map(one, shards, full_like)


def chunked_psum(grads: Params, axis: str, n_buckets: int = 4) -> Params:
    """Bucketed gradient all-reduce: leaves are grouped into ``n_buckets``
    by size and psum'd per bucket, giving the scheduler independent
    collectives to overlap with compute."""
    leaves, treedef = jax.tree.flatten(grads)
    order = sorted(range(len(leaves)), key=lambda i: -leaves[i].size)
    buckets: List[List[int]] = [[] for _ in range(max(n_buckets, 1))]
    sizes = [0] * max(n_buckets, 1)
    for i in order:                      # greedy balance by bytes
        b = sizes.index(min(sizes))
        buckets[b].append(i)
        sizes[b] += leaves[i].size
    out = list(leaves)
    for bucket in buckets:
        if not bucket:
            continue
        reduced = jax.lax.psum(tuple(leaves[i] for i in bucket), axis)
        for i, r in zip(bucket, reduced):
            out[i] = r
    return jax.tree.unflatten(treedef, out)
