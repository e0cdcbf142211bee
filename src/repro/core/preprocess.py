"""Attention-guided multi-scale preprocessing — Eq. (3) (§3.2.3).

              ⎧ 0                          K(x^r) < α        (discard)
  f(x^r)  =   ⎨ D(x^r, (β−α)/(K−α))        α ≤ K(x^r) < β    (downsample)
              ⎩ x^r                        β ≤ K(x^r)        (preserve)

The paper's scaling factor c = (β−α)/(K−α) ≥ 1 shrinks each spatial side by
c.  JAX needs static shapes, so c is quantised to a pyramid of power-of-two
pooling levels; the "transmitted" tensor keeps full layout with each region
replaced by its pooled-then-nearest-upsampled reconstruction (zero if
discarded) — information loss and byte accounting are exact per level.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp


def _avg_pool(regions: jax.Array, f: int) -> jax.Array:
    """(B, R, h, w, C) average-pool by factor f then nearest-upsample back.
    A side that f does not divide (28-pixel Qwen2-VL regions at f = 8) pools
    its last, partial cells over the pixels they actually hold."""
    if f == 1:
        return regions
    b, r, h, w, c = regions.shape
    hp, wp = -(-h // f) * f, -(-w // f) * f
    pad = ((0, hp - h), (0, wp - w))
    x = jnp.pad(regions, ((0, 0), (0, 0)) + pad + ((0, 0),))
    n = jnp.pad(jnp.ones((h, w), regions.dtype), pad)
    x = (x.reshape(b, r, hp // f, f, wp // f, f, c).sum(axis=(3, 5))
         / n.reshape(hp // f, f, wp // f, f).sum(axis=(1, 3))[..., None])
    x = jnp.repeat(jnp.repeat(x, f, axis=2), f, axis=3)
    return x[:, :, :h, :w]


def scale_factor(scores: jax.Array, alpha: float, beta: float) -> jax.Array:
    """Paper's c = (β−α)/(K−α) on the downsample band, ∞ below α, 1 above β."""
    c = (beta - alpha) / jnp.maximum(scores - alpha, 1e-9)
    return jnp.where(scores >= beta, 1.0,
                     jnp.where(scores < alpha, jnp.inf, jnp.maximum(c, 1.0)))


def multiscale_filter(regions: jax.Array, scores: jax.Array, *,
                      alpha: float = 0.35, beta: float = 0.55,
                      levels: Sequence[int] = (1, 2, 4, 8),
                      bytes_per_px: float = 3.0
                      ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """regions: (B, R, h, w, C); scores: (B, R) normalised K(x^r).

    Returns (filtered regions, tx_bytes (B,), meta).  ``tx_bytes`` counts
    the pooled cells sent per kept region, ⌈h/c⌉·⌈w/c⌉·C (c = selected
    pooling level; partial edge cells are sent too), zero if dropped.
    """
    b, r, h, w, ch = regions.shape
    c = scale_factor(scores, alpha, beta)                     # (B, R)
    # quantise c to the pyramid: pick the smallest level ≥ c (most faithful
    # resolution that still meets the paper's compression target)
    lv = jnp.asarray(levels, jnp.float32)
    # level index: number of levels strictly below c, clipped
    li = jnp.clip(jnp.sum(lv[None, None, :] < c[..., None], axis=-1),
                  0, len(levels) - 1)                         # (B, R) int
    discard = scores < alpha

    pyramid = jnp.stack([_avg_pool(regions, f) for f in levels], axis=0)
    sel = jnp.take_along_axis(
        pyramid, li[None, ..., None, None, None].astype(jnp.int32),
        axis=0)[0]
    out = jnp.where(discard[..., None, None, None], 0.0, sel)

    level_vals = jnp.take(lv, li)
    cells = jnp.asarray([-(-h // f) * -(-w // f) * ch for f in levels],
                        jnp.float32)
    px = jnp.take(cells, li)
    tx_bytes = jnp.where(discard, 0.0, px * bytes_per_px).sum(-1)  # (B,)
    full_bytes = float(r * h * w * ch * bytes_per_px)
    meta = {
        "levels": level_vals,
        "discarded": discard,
        "compression_ratio": full_bytes / jnp.maximum(tx_bytes, 1.0),
        "full_bytes": jnp.full((b,), full_bytes),
    }
    return out, tx_bytes, meta


def random_mask_filter(regions: jax.Array, keep_frac: float, key: jax.Array,
                       *, bytes_per_px: float = 3.0):
    """GS-only baseline redundancy reduction (Fig. 3/12): random region drop."""
    b, r = regions.shape[:2]
    keep = jax.random.uniform(key, (b, r)) < keep_frac
    out = jnp.where(keep[..., None, None, None], regions, 0.0)
    px = regions.shape[2] * regions.shape[3] * regions.shape[4]
    tx_bytes = keep.sum(-1).astype(jnp.float32) * px * bytes_per_px
    return out, tx_bytes, {"kept": keep}
