"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Opt-in (DESIGN.md §5): repurposes a mesh axis as the pipeline axis. Each
device along the axis holds one STAGE's parameters; microbatches stream
through the pipe with a `lax.ppermute` shift per tick; the classic GPipe
schedule runs `n_micro + n_stages − 1` ticks with bubbles at the ends.

``pipeline_apply`` is generic over the per-stage function, so it composes
with the transformer stack: split ``cfg.num_layers`` into ``n_stages``
groups, stack each group's params along the stage axis, and pass
``stage_fn = lambda p, x: run_layers(p, x)``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(stage_fn: Callable, stage_params, x_micro: jax.Array,
                   mesh: Mesh, axis: str = "model") -> jax.Array:
    """Run ``n_micro`` microbatches through ``n_stages`` pipeline stages.

    stage_params: pytree with a leading stage axis of size mesh.shape[axis]
                  on every leaf (stage i's slice lives on device i).
    x_micro:      (n_micro, mb, ...) microbatched activations.
    stage_fn:     (params_slice, x (mb, ...)) → (mb, ...).

    Returns (n_micro, mb, ...) outputs (as produced by the LAST stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def per_stage(params, xs):
        # params: stage-local slice (leading dim 1) ; xs: full microbatch set
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])          # activation currently held
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any remain); others use buf
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0, xs[inject], buf)
            active = (t >= stage) & (t - stage < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # last stage banks its finished microbatch
            done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            bank = active & (stage == n_stages - 1)
            outs = jax.lax.cond(
                bank, lambda o: o.at[done_idx].set(y), lambda o: o, outs)
            # shift activations forward one stage
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # every stage holds a copy of `outs`; only the last stage's is real —
        # broadcast it so the result is replicated along the pipe
        last = jax.lax.ppermute(
            outs, axis,
            [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)]) \
            if n_stages > 1 else outs
        return last

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=(spec_p, P()),
                       out_specs=P(), check_vma=False)
    return fn(stage_params, x_micro)


def split_stages(params, n_stages: int):
    """Stack a per-layer params pytree (leading dim = n_layers) into
    (n_stages, layers_per_stage, ...) for ``pipeline_apply``."""
    def reshape(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])
    return jax.tree.map(reshape, params)
