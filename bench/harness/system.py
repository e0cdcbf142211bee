"""The system under test, built from a configuration file: the program's
``EngineCore`` (through ``make_engine_core``, on a mesh where the file asks
for one) over weights made on the device from the seed in one jitted call.

This is the only harness module that imports the program.
"""
from __future__ import annotations

import pathlib
import sys
from typing import Any, Dict, Optional, Tuple

import jax

from harness import weights as W

SRC = pathlib.Path(__file__).resolve().parents[2] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


def arch_config(conf: Dict[str, Any]):
    from repro.configs.base import ATTN, ArchConfig, BlockSpec
    a = W.arch(conf)
    return ArchConfig(
        name=conf["name"], family="vlm", num_layers=a["layers"],
        d_model=a["d"], num_heads=a["heads"], num_kv_heads=a["kv_heads"],
        d_ff=a["ff"], vocab_size=a["vocab"], head_dim=a["hd"],
        use_mrope=True, mrope_sections=a["mrope"], rope_theta=a["theta"],
        frontend="vision", num_patches=a["regions"],
        block_pattern=(BlockSpec(kind=ATTN),),
        norm_eps=a["eps"], tie_embeddings=a["tied"],
        dtype=str(a["dtype"]))


def mesh_of(conf: Dict[str, Any]):
    m = conf.get("mesh", {"data": 1, "model": 1})
    if m["data"] * m["model"] == 1:
        return None
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(model=m["model"], data=m["data"])


def make_weights(conf: Dict[str, Any], seed: int, mesh=None):
    """The adapter's weights for ``seed``, made on the device in one jitted
    call, straight into the serving plan's shardings on a mesh."""
    a = W.arch(conf)
    fn = lambda key: W.program_tree(key, a)                 # noqa: E731
    key = W.seed_key(seed)
    if mesh is None:
        return jax.jit(fn)(key)
    from repro.distributed import sharding as SH
    specs = SH.adapter_param_specs(
        SH.tp_serving_plan(arch_config(conf), mesh),
        jax.eval_shape(fn, key))
    return jax.jit(fn, out_shardings=SH.named(mesh, specs))(key)


def build(conf: Dict[str, Any], seed: int,
          overrides: Optional[Dict[str, Any]] = None) -> Tuple[Any, Any]:
    """(engine core, adapter config) for ``conf``; ``overrides`` replace
    engine settings (the control script switches a path on this way)."""
    from repro.core import eo_adapter as EO
    from repro.core.cascade import TierModel
    from repro.serving.engine_core import EngineCoreConfig
    from repro.serving.sharded import make_engine_core
    eo = conf["eo_adapter"]
    ac = EO.EOAdapterConfig(grid=eo["grid"], image_size=eo["image_size"],
                            channels=eo["channels"],
                            num_classes=eo["num_classes"])
    mesh = mesh_of(conf)
    params = make_weights(conf, seed, mesh)
    eng = dict(conf["engine"], **(overrides or {}))
    core_cfg = EngineCoreConfig(
        slots=eng["slots"], answer_vocab=eng["answer_vocab"],
        page_size=eng["page_size"],
        prefix_cache_scenes=eng["prefix_cache_scenes"],
        kv_dtype=eng.get("kv_dtype"), mesh=mesh)
    core = make_engine_core(TierModel(params, arch_config(conf)), ac,
                            core_cfg)
    return core, ac


def request(query, image, ac):
    from repro.serving.request import Request
    return Request(task=query.task, image=image, prompt=query.prompt,
                   scene_id=f"scene-{query.scene}")


def kernel_impl() -> str:
    from repro.kernels import ops
    return ops.default_impl()
