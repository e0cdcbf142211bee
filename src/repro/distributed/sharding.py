"""PartitionSpec rules for params, optimizer state, caches and batches.

Policy (single-pod mesh ("data","model"); multi-pod adds a leading "pod"
axis that folds into data parallelism):

- **TP** over "model": attention q/o projections shard by heads, k/v by KV
  heads, MLP/MoE hidden dims, and the vocabulary dim of embed/head —
  each only when the dim is divisible by the model-axis size (otherwise the
  leaf stays replicated; small-model attention replication is deliberate and
  shows up in the roofline as a hillclimb lever).
- **EP**: expert dim of MoE weights when num_experts divides; otherwise TP
  inside each expert's FFN.
- **DP** over ("pod","data"): the batch dim of every activation/batch leaf.
- **SP for long-context decode** (batch=1): the KV sequence dim shards over
  ("data","model") [or "data" + KV-heads over "model" when those divide] —
  flash-decoding split-K across devices.
- **ZeRO-1**: optimizer moments take the parameter spec plus the largest
  still-replicated dim sharded over "data".
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ATTN, ArchConfig
from repro.configs.shapes import ShapeSpec

Params = Any


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_size(mesh: Mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def param_specs(cfg: ArchConfig, mesh: Mesh, params_shape: Params) -> Params:
    """Spec tree matching ``params_shape`` (a ShapeDtypeStruct pytree)."""
    m = mesh_axis_size(mesh, "model")
    hd = cfg.resolved_head_dim
    q_ok = cfg.num_heads % m == 0
    kv_ok = cfg.num_kv_heads % m == 0
    ff_ok = cfg.d_ff % m == 0 and cfg.d_ff > 0
    moe_ff = cfg.moe_d_ff or cfg.d_ff
    ep_ok = cfg.moe_num_experts % m == 0 and cfg.moe_num_experts > 0
    moe_tp_ok = moe_ff % m == 0
    vocab_ok = cfg.vocab_size % m == 0
    d_in = cfg.ssm_expand * cfg.d_model
    ssm_ok = cfg.resolved_ssm_heads % m == 0 and (d_in // max(
        cfg.resolved_ssm_heads, 1)) % 1 == 0
    shared_ff_ok = (cfg.moe_num_shared * moe_ff) % m == 0 \
        if cfg.moe_num_shared else False

    def rule(path, leaf) -> P:
        name = _path_str(path)
        nd = len(leaf.shape)
        last = name.rsplit("/", 1)[-1]
        blocks = name.startswith("blocks")  # leading n_super dim

        def spec(*tail):
            """Prepend None for the stacked n_super dim of block leaves."""
            assert len(tail) + (1 if blocks else 0) == nd, (name, leaf.shape)
            return P(*(((None,) if blocks else ()) + tail))

        # --- embedding / head ---
        if name == "embed/tok":
            if cfg.num_codebooks:
                return P(None, "model" if vocab_ok else None, None)
            return P("model" if vocab_ok else None, None)
        if name == "embed/head":
            return P(None, "model" if vocab_ok else None)

        # --- norms and other vectors/scalars ---
        if nd <= (2 if blocks else 1):
            return spec(*([None] * (nd - (1 if blocks else 0))))

        # --- attention ---
        if last in ("wq",):
            return spec(None, "model" if q_ok else None)
        if last in ("wk", "wv"):
            return spec(None, "model" if kv_ok else None)
        if last == "wo":
            return spec("model" if q_ok else None, None)

        # --- MoE ---
        if "ffn" in name and last in ("wg", "wu") and nd == (4 if blocks else 3):
            if ep_ok:
                return spec("model", None, None)
            return spec(None, None, "model" if moe_tp_ok else None)
        if "ffn" in name and last == "wd" and nd == (4 if blocks else 3):
            if ep_ok:
                return spec("model", None, None)
            return spec(None, "model" if moe_tp_ok else None, None)
        if last == "router":
            return spec(None, None)
        if "shared" in name and last in ("wg", "wu"):
            return spec(None, "model" if shared_ff_ok else None)
        if "shared" in name and last == "wd":
            return spec("model" if shared_ff_ok else None, None)

        # --- dense MLP ---
        if last in ("wg", "wu"):
            return spec(None, "model" if ff_ok else None)
        if last == "wd":
            return spec("model" if ff_ok else None, None)

        # --- mamba / mlstm / slstm projections ---
        if last in ("w_in", "w_bc", "w_up", "w_dt", "w_i", "w_f"):
            return spec(None, "model" if ssm_ok else None)
        if last in ("w_out", "w_down"):
            return spec("model" if ssm_ok else None, None)
        if last in ("wq_m", "wk_m", "wv_m"):
            return spec(None, "model" if ssm_ok else None)
        if last == "r_gates":
            return spec(None, None, None)
        if last == "w_gates":
            return spec(None, "model" if ssm_ok else None)

        # default: replicate
        return spec(*([None] * (nd - (1 if blocks else 0))))

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def zero1_specs(cfg: ArchConfig, mesh: Mesh, params_shape: Params,
                p_specs: Params) -> Params:
    """ZeRO-1 moment specs: param spec + largest replicated dim → "data"."""
    dsize = mesh_axis_size(mesh, "data")

    def widen(leaf, spec):
        parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
        best, best_size = -1, 0
        for i, (dim, pt) in enumerate(zip(leaf.shape, parts)):
            if pt is None and dim % dsize == 0 and dim > best_size:
                best, best_size = i, dim
        if best >= 0:
            parts[best] = "data"
        return P(*parts)

    return jax.tree.map(widen, params_shape, p_specs)


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec,
                batch_shape: Dict[str, Any]) -> Dict[str, Any]:
    """Input-batch specs: batch dim over (pod, data) when divisible."""
    da = data_axes(mesh)
    bsz = shape.global_batch
    b_axes = da if (bsz % data_size(mesh) == 0 and da) else ()

    def rule(path, leaf):
        nd = len(leaf.shape)
        tail = (None,) * (nd - 1)
        return P(b_axes if b_axes else None, *tail)

    return jax.tree_util.tree_map_with_path(rule, batch_shape)


def cache_specs(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec,
                cache_shape: Tuple) -> Tuple:
    """KV-cache / recurrent-state specs for decode shapes.

    Layout: attn k/v (n_super, B, S, n_kv, hd); states (n_super, B, ...).
    """
    m = mesh_axis_size(mesh, "model")
    da = data_axes(mesh)
    bsz = shape.global_batch
    kv_ok = cfg.num_kv_heads % m == 0
    batch_sharded = bsz % data_size(mesh) == 0 and bool(da)

    def rule(path, leaf):
        nd = len(leaf.shape)
        name = _path_str(path)
        # attention KV leaves are named .../k or .../v; recurrent states
        # ("state", "h", "c", "n", "m") shard batch-only regardless of rank
        last = name.rsplit("/", 1)[-1]
        is_attn_kv = nd == 5 and last in ("k", "v")
        if is_attn_kv:
            if batch_sharded:
                # batch over data(+pod); kv-heads over model if divisible,
                # else split-K: sequence over model
                if kv_ok:
                    return P(None, da, None, "model", None)
                return P(None, da, "model", None, None)
            # long-context batch=1: sequence over every axis we can
            if kv_ok:
                return P(None, None, "data", "model", None)
            return P(None, None, ("data", "model"), None, None)
        # recurrent states: batch over data when divisible, else replicate
        if batch_sharded and nd >= 2:
            return P(None, da, *([None] * (nd - 2)))
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


# ---------------------------------------------------------------------------
# Serving tensor-parallel plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPServingPlan:
    """What the serving engine shards over the mesh's "model" axis.

    ``attn``: q/k/v/o projections and the paged KV pools split by
    (KV-)heads — requires BOTH head counts divisible by ``tp`` (dividing
    only q-heads would break the contiguous-block GQA head→group mapping)
    and a pure-attention block pattern (mLSTM reuses the ``wq``/``wk``/
    ``wv`` leaf names at the same rank, so kind-gating, not name-matching,
    decides).  ``mlp``: dense-FFN hidden dim split — requires ``d_ff``
    divisible and no MoE blocks (MoE has its own EP/TP rules and does not
    route through ``layers.mlp``'s all-reduce hook).  ``cfg_local`` is the
    per-device config every ``shard_map`` body runs the model with: head
    counts (and ``d_ff``) divided by ``tp``, with ``head_dim`` pinned to
    the global value — otherwise ``resolved_head_dim`` (= d_model/heads
    when unset) would grow by ``tp`` after the division.
    """
    tp: int
    attn: bool
    mlp: bool
    cfg_local: ArchConfig


def tp_serving_plan(cfg: ArchConfig, mesh: Mesh) -> TPServingPlan:
    tp = mesh_axis_size(mesh, "model")
    all_attn = all(s.kind == ATTN for s in cfg.block_pattern)
    no_moe = not any(s.moe for s in cfg.block_pattern)
    attn = (tp > 1 and all_attn and cfg.num_heads % tp == 0
            and cfg.num_kv_heads % tp == 0)
    mlp = tp > 1 and all_attn and no_moe and cfg.d_ff > 0 \
        and cfg.d_ff % tp == 0
    over: Dict[str, Any] = {}
    if attn:
        over.update(head_dim=cfg.resolved_head_dim,
                    num_heads=cfg.num_heads // tp,
                    num_kv_heads=cfg.num_kv_heads // tp)
    if mlp:
        over.update(d_ff=cfg.d_ff // tp)
    cfg_local = dataclasses.replace(cfg, **over) if over else cfg
    return TPServingPlan(tp=tp, attn=attn, mlp=mlp, cfg_local=cfg_local)


def serving_param_specs(plan: TPServingPlan, params_shape: Params) -> Params:
    """Spec tree for the serving backbone params under ``plan``.

    Narrower than :func:`param_specs` on purpose: only the sublayers whose
    partial outputs the engine all-reduces (``tp_attn_all_reduce`` /
    ``tp_mlp_all_reduce`` hooks in ``models/layers.py``) may shard —
    anything else sharded here would produce silently-wrong sums.
    """
    def rule(path, leaf) -> P:
        name = _path_str(path)
        nd = len(leaf.shape)
        last = name.rsplit("/", 1)[-1]
        blocks = name.startswith("blocks")

        def spec(*tail):
            assert len(tail) + (1 if blocks else 0) == nd, (name, leaf.shape)
            return P(*(((None,) if blocks else ()) + tail))

        if blocks and "mixer" in name and nd == 3:
            if plan.attn and last in ("wq", "wk", "wv"):
                return spec(None, "model")
            if plan.attn and last == "wo":
                return spec("model", None)
        if blocks and "ffn" in name and nd == 3:
            if plan.mlp and last in ("wg", "wu"):
                return spec(None, "model")
            if plan.mlp and last == "wd":
                return spec("model", None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def adapter_param_specs(plan: TPServingPlan, params_shape: Params) -> Params:
    """Spec tree for a whole EO-adapter param tree (``EO.init_adapter``):
    the backbone per :func:`serving_param_specs`, everything else (the
    patch projection) replicated."""
    return {k: (serving_param_specs(plan, v) if k == "backbone"
                else jax.tree.map(lambda x: P(), v))
            for k, v in params_shape.items()}


def init_sharded(init_fn: Callable[..., Params], cfg: ArchConfig,
                 mesh: Mesh, *args) -> Params:
    """``init_fn(*args)`` — an EO-adapter param tree for backbone ``cfg``,
    e.g. ``EO.init_adapter`` — generated straight into the serving plan's
    shardings on ``mesh``: each device makes only its own shards, so a model
    that no single device holds is never materialised on one."""
    specs = adapter_param_specs(tp_serving_plan(cfg, mesh),
                                jax.eval_shape(init_fn, *args))
    return jax.jit(init_fn, out_shardings=named(mesh, specs))(*args)


def paged_kv_leaf_spec(nd: int, sharded: bool) -> P:
    """Spec for one paged attention-KV pool leaf.

    Pools are ``(n_super, n_pages, page, KH, hd)``; int8 scale leaves ride
    alongside as ``(n_super, n_pages, page, KH)``.  Head-sharding puts the
    KH axis over "model" in both — each device's pool holds only its own
    KV-head shard, which is where the per-device ``kv_bytes_per_slot`` ÷ tp
    comes from.

    Returned specs never carry trailing ``None`` entries (unmentioned dims
    are replicated anyway): jit normalizes output shardings to the short
    form, and the engine's steady-state zero-recompile guarantee needs the
    ``device_put`` placement of the initial pool to compare EQUAL to the
    sharding the first sharded step hands back.
    """
    if sharded and nd >= 4:
        return P(None, None, None, "model")
    return P()


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def named(mesh: Mesh, spec_tree: Params) -> Params:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
