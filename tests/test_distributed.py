"""Distribution layer: sharding specs, HLO parser, memory model, small-mesh
lowering (8 host devices stand in for the pod; the 512-device production mesh
is exercised by repro.launch.dryrun)."""
import os
import sys

# must be set before jax initialises — pytest may import jax earlier via
# another test module, so only assert the count if we got there first.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.shapes import ShapeSpec
from repro.distributed import hlo_parser
from repro.distributed import sharding as SH
from repro.launch import specs as SP

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 host devices (XLA_FLAGS set "
    "after jax initialised by an earlier import)")


def _mesh():
    return jax.make_mesh((4, 2), ("data", "model"))


def test_param_specs_cover_tree_and_divide():
    mesh = _mesh()
    for arch in ("gemma3-1b", "phi3.5-moe-42b-a6.6b", "xlstm-125m"):
        cfg = configs.get_config(arch, reduced=True)
        p_shape = SP.params_shape(cfg)
        specs = SH.param_specs(cfg, mesh, p_shape)
        flat_s = jax.tree.leaves(p_shape)
        flat_p = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, P))
        assert len(flat_s) == len(flat_p)
        for s, p in zip(flat_s, flat_p):
            parts = tuple(p)
            assert len(parts) <= len(s.shape)
            for dim, part in zip(s.shape, parts):
                if part is None:
                    continue
                axes = part if isinstance(part, tuple) else (part,)
                n = 1
                for a in axes:
                    n *= mesh.shape[a]
                assert dim % n == 0, (arch, s.shape, parts)


def test_zero1_adds_data_axis():
    mesh = _mesh()
    cfg = configs.get_config("glm4-9b", reduced=True)
    p_shape = SP.params_shape(cfg)
    specs = SH.param_specs(cfg, mesh, p_shape)
    z = SH.zero1_specs(cfg, mesh, p_shape, specs)
    n_data = sum("data" in tuple(p) for p in jax.tree.leaves(
        z, is_leaf=lambda x: isinstance(x, P)))
    assert n_data > 0


def test_small_mesh_train_lowering_compiles():
    mesh = _mesh()
    cfg = configs.get_config("glm4-9b", reduced=True)
    shape = ShapeSpec("t", 32, 8, "train")
    from repro.launch.dryrun import build_lowerable
    with jax.set_mesh(mesh):
        fn, arg_specs = build_lowerable(cfg, shape, mesh)
        compiled = fn.lower(*arg_specs).compile()
    assert compiled.cost_analysis().get("flops", 0) > 0


def test_small_mesh_decode_lowering_compiles():
    mesh = _mesh()
    cfg = configs.get_config("gemma2-27b", reduced=True)
    shape = ShapeSpec("d", 64, 8, "decode")
    from repro.launch.dryrun import build_lowerable
    with jax.set_mesh(mesh):
        fn, arg_specs = build_lowerable(cfg, shape, mesh)
        compiled = fn.lower(*arg_specs).compile()
    analysis = hlo_parser.analyze(compiled.as_text())
    assert analysis["flops_per_device"] > 0


def test_hlo_parser_trip_counts_and_flops():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def g(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    c = jax.jit(g).lower(a).compile()
    s = hlo_parser.analyze(c.as_text())
    assert s["flops_per_device"] == pytest.approx(5 * 2 * 256 ** 3, rel=0.01)
    assert 5 in s["while_trips"].values()


def test_hlo_parser_collectives_detected():
    mesh = jax.make_mesh((8,), ("m",))
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    with jax.set_mesh(mesh):
        f = jax.jit(lambda x, w: (x @ w).sum(),
                    in_shardings=(P(None, "m"), P("m", None)))
        c = f.lower(a, a).compile()
    s = hlo_parser.analyze(c.as_text())
    assert s["collectives"]["total"]["link_bytes"] > 0


def test_memory_model_scales_with_sharding():
    from repro.distributed.memory_model import analytic_memory
    from repro.launch.mesh import make_production_mesh
    cfg = configs.get_config("gemma2-27b")
    mesh = make_production_mesh() if len(jax.devices()) >= 256 else _mesh()
    shape = ShapeSpec("train_4k", 4096, 256, "train")
    m = analytic_memory(cfg, shape, mesh)
    assert m["params"] > 0 and m["total"] > m["params"]
    shape_d = ShapeSpec("decode_32k", 32768, 128, "decode")
    md = analytic_memory(cfg, shape_d, mesh)
    assert md["kv_cache"] > 0
