"""Random weights from the seed, one leaf at a time or the whole tree at once.

Every leaf has a key of its own, ``fold_in(fold_in(PRNGKey(seed), leaf id),
layer)``, so the reference can make layer ``l`` again without the rest of
the model, and gets the very values the system under test was given.  The
values are N(0, 1) · 1/sqrt(fan-in) for projections (as the program's own
init), and 0.1 · N(0, 1) for the RMSNorm offsets ``w`` in ``(1 + w)``, so
that a norm's weight is exercised rather than left at 1.

This module imports JAX alone, never the program: the reference uses it.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

LEAF_IDS = {"tok": 0, "head": 1, "final_norm": 2, "patch_proj": 3,
            "norm1": 10, "wq": 11, "wk": 12, "wv": 13, "wo": 14,
            "norm2": 15, "wg": 16, "wu": 17, "wd": 18}
NORM_SCALE = 0.1


def arch(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the weights and the reference need, from a config file."""
    eo = conf["eo_adapter"]
    side = eo["image_size"] // eo["grid"]
    return {
        "d": conf["hidden_size"], "ff": conf["intermediate_size"],
        "layers": conf["num_hidden_layers"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "vocab": conf["vocab_size"], "theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
        "tied": bool(conf["tie_word_embeddings"]),
        "mrope": tuple(conf["rope_scaling"]["mrope_section"]),
        "dtype": jnp.dtype(conf["torch_dtype"]),
        "grid": eo["grid"], "image_size": eo["image_size"],
        "channels": eo["channels"], "num_classes": eo["num_classes"],
        "patch_dim": side * side * eo["channels"],
        "regions": eo["grid"] ** 2,
        "answer_vocab": conf["engine"]["answer_vocab"],
    }


def _layer_shapes(a) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    d, nq, nkv, ff = a["d"], a["heads"] * a["hd"], a["kv_heads"] * a["hd"], \
        a["ff"]
    return {"norm1": ((d,), NORM_SCALE), "wq": ((d, nq), d ** -0.5),
            "wk": ((d, nkv), d ** -0.5), "wv": ((d, nkv), d ** -0.5),
            "wo": ((nq, d), nq ** -0.5), "norm2": ((d,), NORM_SCALE),
            "wg": ((d, ff), d ** -0.5), "wu": ((d, ff), d ** -0.5),
            "wd": ((ff, d), ff ** -0.5)}


def _global_shapes(a) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    d = a["d"]
    out = {"tok": ((a["vocab"], d), d ** -0.5),
           "final_norm": ((d,), NORM_SCALE),
           "patch_proj": ((a["patch_dim"], d), a["patch_dim"] ** -0.5)}
    if not a["tied"]:
        out["head"] = ((d, a["vocab"]), d ** -0.5)
    return out


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


def leaf(key, name: str, shape, scale: float, dtype, layer=None):
    k = jax.random.fold_in(key, LEAF_IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def layer_weights(key, a, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s leaves in the served dtype."""
    return {n: leaf(key, n, s, sc, a["dtype"], layer)
            for n, (s, sc) in _layer_shapes(a).items()}


def global_weights(key, a) -> Dict[str, jax.Array]:
    return {n: leaf(key, n, s, sc, a["dtype"])
            for n, (s, sc) in _global_shapes(a).items()}


def program_tree(key, a) -> Dict[str, Any]:
    """The whole tree in the layout the program's EO adapter takes:
    ``{"backbone": {"embed", "blocks", "final_norm"}, "patch_proj"}`` with
    layer leaves stacked on a leading axis.  Jit it (with out_shardings on
    a mesh) to make the weights on the device in one call."""
    g = global_weights(key, a)
    stacked = jax.vmap(lambda l: layer_weights(key, a, l))(
        jnp.arange(a["layers"]))
    block = {"norm1": stacked["norm1"],
             "mixer": {n: stacked[n] for n in ("wq", "wk", "wv", "wo")},
             "norm2": stacked["norm2"],
             "ffn": {n: stacked[n] for n in ("wg", "wu", "wd")}}
    embed = {"tok": g["tok"]}
    if "head" in g:
        embed["head"] = g["head"]
    return {"backbone": {"embed": embed, "blocks": (block,),
                         "final_norm": g["final_norm"]},
            "patch_proj": g["patch_proj"]}
