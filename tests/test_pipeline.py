"""Pipeline parallelism + collective helpers (8 host devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 host devices")


def _mesh(shape, names):
    return jax.make_mesh(shape, names)


def test_gpipe_matches_sequential():
    """Microbatches through a 4-stage pipe == plain layer-by-layer apply."""
    from repro.distributed.pipeline import pipeline_apply, split_stages
    key = jax.random.PRNGKey(0)
    n_layers, d = 8, 16
    w = jax.random.normal(key, (n_layers, d, d)) * (d ** -0.5)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n_layers, d)) * 0.1
    params = {"w": w, "b": b}
    n_micro, mb = 6, 4
    x = jax.random.normal(jax.random.fold_in(key, 2), (n_micro, mb, d))

    def layers_fn(p, x):
        def body(x, wl):
            return jnp.tanh(x @ wl[0] + wl[1]), None
        y, _ = jax.lax.scan(body, x, (p["w"], p["b"]))
        return y

    # sequential reference
    ref = jax.vmap(lambda xm: layers_fn(params, xm))(x)

    mesh = _mesh((2, 4), ("data", "model"))
    staged = split_stages(params, 4)
    with jax.set_mesh(mesh):
        out = pipeline_apply(layers_fn, staged, x, mesh, axis="model")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_reduce_scatter_gather_roundtrip():
    from repro.distributed import collectives as C
    mesh = _mesh((8,), ("data",))
    g = {"a": jnp.arange(32.0).reshape(8, 4), "b": jnp.ones((3,))}

    def f(grads):
        shards = C.reduce_scatter_grads(grads, "data")
        return C.all_gather_params(shards, grads, "data")

    with jax.set_mesh(mesh):
        out = jax.shard_map(f, mesh=mesh, in_specs=(P(),),
                            out_specs=P(), check_vma=False)(g)
    # mean over an identical-replica axis is identity
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(g["a"]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]), np.asarray(g["b"]),
                               rtol=1e-6)


def test_chunked_psum_equals_psum():
    from repro.distributed import collectives as C
    mesh = _mesh((8,), ("data",))
    g = {"a": jnp.ones((16, 4)), "b": jnp.full((5,), 2.0),
         "c": jnp.ones((2, 2, 2))}

    def f(grads):
        return C.chunked_psum(grads, "data", n_buckets=2)

    with jax.set_mesh(mesh):
        out = jax.shard_map(f, mesh=mesh, in_specs=(P(),),
                            out_specs=P(), check_vma=False)(g)
    for k in g:
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.asarray(g[k]) * 8, rtol=1e-6)
