"""Pallas TPU kernel for Eq. (2) — text-image region attention scoring.

K(x^r) = Σ_i Σ_j cos(V_i(x^r), E_j(T)): the paper's per-offload hot loop
(N^r = 100 regions × N_V visual tokens × N_E text tokens, all pairs).  A
naive port does R·N_V·N_E cosine evaluations; here rows are L2-normalised
in VMEM and the all-pairs sum factors into pooled sums on each side, so each
(region-tile × text-tile) step is one (r_blk, d)·(d, 1) dot.  The
text-tile axis is innermost so the (r_blk, 1) accumulator stays in the
output block across steps.  The output is laid out (B, R, 1) so that its
block is a legal TPU tile for any batch: ``r_blk`` rows (a multiple of 8, or
all R) by the whole unit lane axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _region_kernel(v_ref, e_ref, o_ref, *, eps: float):
    ie = pl.program_id(2)

    @pl.when(ie == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    v = v_ref[0].astype(jnp.float32)              # (r_blk, nv, d)
    e = e_ref[0].astype(jnp.float32)              # (e_blk, d)
    vn = v * jax.lax.rsqrt((v * v).sum(-1, keepdims=True) + eps)
    en = e * jax.lax.rsqrt((e * e).sum(-1, keepdims=True) + eps)
    # Σ_i Σ_j vn_i·en_j = (Σ_i vn_i)·(Σ_j en_j): pool each side, one dot
    vsum = vn.sum(axis=1)                         # (r_blk, d)
    esum = en.sum(axis=0, keepdims=True)          # (1, d)
    o_ref[0] += jax.lax.dot_general(vsum, esum, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)


def region_score_pallas(v: jax.Array, e: jax.Array, *, r_blk: int = 8,
                        e_blk: int = 128, eps: float = 1e-12,
                        interpret: bool = False) -> jax.Array:
    """v: (B, R, Nv, D); e: (B, Ne, D) → (B, R) float32."""
    b, r, nv, d = v.shape
    ne = e.shape[1]
    # largest block sizes that divide the (possibly odd) region/text counts —
    # the paper's N_r = 100 is not a power of two.  A region block is a
    # multiple of 8 rows or all R; a text block is a multiple of 8 or all Ne
    r_blk = next((x for x in range(min(r_blk, r), 0, -1)
                  if r % x == 0 and x % 8 == 0), r)
    e_blk = next((x for x in range(min(e_blk, ne), 0, -1)
                  if ne % x == 0 and x % 8 == 0), ne)
    kernel = functools.partial(_region_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(b, r // r_blk, ne // e_blk),
        in_specs=[
            pl.BlockSpec((1, r_blk, nv, d), lambda b_, ir, ie: (b_, ir, 0, 0)),
            pl.BlockSpec((1, e_blk, d), lambda b_, ir, ie: (b_, ie, 0)),
        ],
        out_specs=pl.BlockSpec((1, r_blk, 1), lambda b_, ir, ie: (b_, ir, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, 1), jnp.float32),
        interpret=interpret,
    )(v, e)
    return out[..., 0]
