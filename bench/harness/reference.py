"""The plain reference: the served model's forward pass in float32 jax.numpy.

It follows the architecture the configuration file states, Qwen2-VL's
decoder with the EO adapter in front (see ``bench/configs/*.json``):

    sequence  = [R region tokens | prompt token | answer tokens]
    region r  = (28x28x3 pixels of tile r, row-major) @ patch_proj
    position  = M-RoPE (t, h, w): region r at (0, r // grid, r % grid),
                text token m after the regions at (grid + m) on all three
    block     = x + attn(rmsnorm(x)); x + swiglu(rmsnorm(x))
    rmsnorm   = x / sqrt(mean(x^2) + eps) * (1 + w)
    attention = causal GQA softmax(q k^T / sqrt(hd)) v, head h reads KV
                head h // (heads / kv_heads); RoPE rotates the two halves
    logits    = rmsnorm(x) @ tok^T (tied) or @ head, answer vocabulary only

It imports nothing of the program and takes nothing it made: weights come
from ``weights.py`` and the seed, images from ``traffic.scene_image``.  It
runs layer by layer over blocks of scenes, so it fits beside nothing else
on the chip once the program's state is freed.

Each request's sequence is computed as its own causal sequence; requests on
one scene share the region rows (they cannot see later tokens), which is
why the region stream is computed once per scene.

``precision="fp8"`` is the control: every matmul takes its two operands
rounded to float8_e4m3fn, each scaled by its amax along the contracted
axis, with float32 accumulation; the rest stays float32.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fq(x, axis, precision):
    """Operand as the matmul sees it: itself in float32, or rounded to fp8
    with one scale per slice along the contracted ``axis``."""
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _ein(spec, a, b, axes, precision):
    return jnp.einsum(spec, _fq(a, axes[0], precision),
                      _fq(b, axes[1], precision), precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos3, a):
    """x (..., n, heads, hd); pos3 (3, n) int → rotated x."""
    hd = x.shape[-1]
    half = hd // 2
    inv = a["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    sec = np.repeat(np.arange(3), a["mrope"])               # (half,)
    ang = pos3[sec, :].T.astype(jnp.float32) * inv          # (n, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _positions(a, m: int):
    g = a["grid"]
    r = np.arange(a["regions"])
    reg = np.stack([np.zeros_like(r), r // g, r % g])
    t = g + np.arange(m)
    return jnp.asarray(reg), jnp.asarray(np.stack([t, t, t]))


@functools.partial(jax.jit, static_argnames=("a_items", "precision"))
def _layer(key, layer, x_r, x_s, valid, *, a_items, precision):
    """One decoder block over the region stream x_r (b, R, d) and the
    suffix stream x_s (b, Q, M, d) of each scene's requests."""
    a = dict(a_items)
    w = {k: v.astype(jnp.float32)
         for k, v in W.layer_weights(key, a, layer).items()}
    b, q_n, m_n, d = x_s.shape
    kh, hd = a["kv_heads"], a["hd"]
    g = a["heads"] // kh
    pr, ps = _positions(a, m_n)
    p = precision

    def proj(h, name):
        return _ein("...d,df->...f", h, w[name], (-1, 0), p)

    def qkv(h, pos):
        lead = h.shape[:-1]
        q = _rope(proj(h, "wq").reshape(lead + (kh * g, hd)), pos, a)
        k = _rope(proj(h, "wk").reshape(lead + (kh, hd)), pos, a)
        v = proj(h, "wv").reshape(lead + (kh, hd))
        return q.reshape(lead + (kh, g, hd)), k, v

    scale = hd ** -0.5
    h_r = _rms(x_r, w["norm1"], a["eps"])
    h_s = _rms(x_s, w["norm1"], a["eps"])
    q_r, k_r, v_r = qkv(h_r, pr)
    q_s, k_s, v_s = qkv(h_s, ps)

    # regions: causal among themselves
    n_r = x_r.shape[1]
    s = _ein("bqkgd,bskd->bkgqs", q_r, k_r, (-1, -1), p) * scale
    s = jnp.where(jnp.tril(jnp.ones((n_r, n_r), bool)), s, -jnp.inf)
    pw = jax.nn.softmax(s, axis=-1)
    o_r = _ein("bkgqs,bskd->bqkgd", pw, v_r, (-1, 1), p)
    # suffix tokens: every region, then their own request's earlier tokens
    s_reg = _ein("bqmkgd,brkd->bqkgmr", q_s, k_r, (-1, -1), p) * scale
    s_own = _ein("bqmkgd,bqnkd->bqkgmn", q_s, k_s, (-1, -1), p) * scale
    own_ok = (jnp.tril(jnp.ones((m_n, m_n), bool))[None, None]
              & valid[:, :, None, :])                       # (b, Q, M, M)
    s_own = jnp.where(own_ok[:, :, None, None], s_own, -jnp.inf)
    pw = jax.nn.softmax(jnp.concatenate([s_reg, s_own], axis=-1), axis=-1)
    o_s = (_ein("bqkgmr,brkd->bqmkgd", pw[..., :n_r], v_r, (-1, 1), p)
           + _ein("bqkgmn,bqnkd->bqmkgd", pw[..., n_r:], v_s, (-1, 2), p))

    def out(x, o):
        x = x + proj(o.reshape(o.shape[:-3] + (kh * g * hd,)), "wo")
        h = _rms(x, w["norm2"], a["eps"])
        act = jax.nn.silu(proj(h, "wg")) * proj(h, "wu")
        return x + proj(act, "wd")

    return out(x_r, o_r), out(x_s, o_s)


@functools.partial(jax.jit, static_argnames=("a_items", "precision"))
def _embed(key, pixels, toks, *, a_items, precision):
    a = dict(a_items)
    g = W.global_weights(key, a)
    b, hgt, wid, c = pixels.shape
    n, side = a["grid"], hgt // a["grid"]
    reg = pixels.reshape(b, n, side, n, side, c).transpose(0, 1, 3, 2, 4, 5)
    reg = reg.reshape(b, n * n, side * side * c)
    x_r = _ein("brp,pd->brd", reg, g["patch_proj"], (-1, 0), precision)
    x_s = jnp.take(g["tok"], toks, axis=0).astype(jnp.float32)
    return x_r, x_s


@functools.partial(jax.jit, static_argnames=("a_items", "precision"))
def _logits(key, x_s, *, a_items, precision):
    a = dict(a_items)
    g = W.global_weights(key, a)
    h = _rms(x_s, g["final_norm"], a["eps"])
    av = a["answer_vocab"]
    table = (g["tok"][:av].T if a["tied"] else g["head"][:, :av])
    return _ein("...d,dv->...v", h, table, (-1, 0), precision)


def answer_logits(seed: int, a: Dict, pixels: np.ndarray,
                  suffixes: Sequence[Sequence[np.ndarray]],
                  precision: str = "f32", block: int = 4
                  ) -> List[List[np.ndarray]]:
    """Answer-vocabulary logits at every suffix position.

    ``pixels[i]`` is scene i; ``suffixes[i]`` lists its requests' input
    tokens ``[prompt id, answer_0 .. answer_{n-2}]``.  Returns, per scene and
    request, an (n, answer_vocab) float32 array: row j is the distribution
    answer token j was chosen from."""
    key = W.seed_key(seed)
    a_items = tuple(sorted((k, v) for k, v in a.items()))
    out: List[List[np.ndarray]] = []
    q_n = max(len(s) for s in suffixes)
    m_n = max(len(t) for s in suffixes for t in s)
    for lo in range(0, len(suffixes), block):
        hi = min(lo + block, len(suffixes))
        toks = np.zeros((block, q_n, m_n), np.int32)
        valid = np.zeros((block, q_n, m_n), bool)
        pix = np.zeros((block,) + pixels[0].shape, np.float32)
        for i in range(lo, hi):
            pix[i - lo] = pixels[i]
            for j, t in enumerate(suffixes[i]):
                toks[i - lo, j, :len(t)] = t
                valid[i - lo, j, :len(t)] = True
        x_r, x_s = _embed(key, pix, toks, a_items=a_items,
                          precision=precision)
        valid_d = jnp.asarray(valid)
        for layer in range(a["layers"]):
            x_r, x_s = _layer(key, layer, x_r, x_s, valid_d,
                              a_items=a_items, precision=precision)
        lg = np.asarray(_logits(key, x_s, a_items=a_items,
                                precision=precision))
        for i in range(lo, hi):
            out.append([lg[i - lo, j, :len(t)]
                        for j, t in enumerate(suffixes[i])])
    return out


def gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far below the reference's best each chosen token's reference
    logit lies: ``max_v ref[i, v] - ref[i, chosen[i]]`` (0 where they
    agree)."""
    ref = np.asarray(ref, np.float64)
    return ref.max(axis=-1) - ref[np.arange(len(chosen)), chosen]
