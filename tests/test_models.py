"""Per-architecture smoke tests (reduced configs) + decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def _inputs(cfg, b, s):
    if cfg.frontend == "vision":
        return {"tokens": jnp.ones((b, s - cfg.num_patches), jnp.int32),
                "patch_embeds": jnp.ones((b, cfg.num_patches, cfg.d_model),
                                         jnp.float32)}
    if cfg.frontend == "audio":
        return {"codes": jnp.ones((b, s, cfg.num_codebooks), jnp.int32)}
    return {"tokens": jnp.ones((b, s), jnp.int32)}


def _step_inputs(cfg, seq, t):
    if cfg.frontend == "audio":
        return {"codes": seq[:, t:t + 1]}
    return {"tokens": seq[:, t:t + 1]}


@pytest.mark.parametrize("arch", configs.ASSIGNED)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = configs.get_config(arch, reduced=True)
    params = T.init_params(cfg, KEY)
    b, s = 2, 32
    batch = dict(_inputs(cfg, b, s))
    batch["targets"] = jnp.zeros((b, s), jnp.int32)
    batch["loss_mask"] = jnp.ones((b, s))
    logits, aux = T.forward_train(params, cfg, batch, remat=False)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits)).any()
    loss, metrics = T.loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: T.loss_fn(p, cfg, batch)[0])(params)
    gn = jax.tree.reduce(
        lambda a, g: a + float(jnp.sum(jnp.asarray(g, jnp.float32) ** 2)),
        grads, 0.0)
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", configs.ASSIGNED)
def test_init_params_matches_per_block_loop(arch):
    """``init_params`` vmaps each pattern position's block init over the
    super-blocks; the weights are bitwise those of one init per block."""
    cfg = configs.get_config(arch, reduced=True)
    params = T.init_params(cfg, KEY)
    _, kb = jax.random.split(KEY)
    for pos, spec in enumerate(cfg.block_pattern):
        keys = jax.random.split(jax.random.fold_in(kb, pos), cfg.n_super)
        loop = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[T._init_block(k, cfg, spec) for k in keys])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params["blocks"][pos], loop)


@pytest.mark.parametrize("arch", configs.ASSIGNED)
def test_arch_smoke_prefill_decode(arch):
    cfg = configs.get_config(arch, reduced=True)
    params = T.init_params(cfg, KEY)
    b, s = 2, 32
    logits, cache, idx = T.prefill(params, cfg, _inputs(cfg, b, s),
                                   max_len=s + 4)
    assert logits.shape == (b, cfg.vocab_size)
    tok = ({"codes": jnp.ones((b, 1, cfg.num_codebooks), jnp.int32)}
           if cfg.frontend == "audio"
           else {"tokens": jnp.ones((b, 1), jnp.int32)})
    logits2, cache2 = T.decode_step(params, cfg, cache, tok, idx)
    assert logits2.shape == (b, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits2)).any()


@pytest.mark.parametrize("arch", [
    "gemma3-1b", "gemma2-27b", "xlstm-125m", "hymba-1.5b",
    "qwen2-moe-a2.7b", "musicgen-medium",
])
def test_decode_consistency_vs_full_forward(arch):
    """prefill + step-by-step decode must reproduce full-seq logits."""
    cfg = configs.get_config(arch, reduced=True)
    if cfg.moe_num_experts:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # dropless
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    b, s, s0 = 2, 24, 16
    if cfg.frontend == "audio":
        seq = jax.random.randint(KEY, (b, s, cfg.num_codebooks), 0,
                                 cfg.vocab_size)
        full = {"codes": seq}
        pre = {"codes": seq[:, :s0]}
    else:
        seq = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
        full = {"tokens": seq}
        pre = {"tokens": seq[:, :s0]}
    logits_full, _ = T.forward_train(params, cfg, full, remat=False)
    lg, cache, idx = T.prefill(params, cfg, pre, max_len=s)
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(logits_full[:, s0 - 1]),
                               rtol=3e-4, atol=3e-4)
    for t in range(s0, s):
        lg, cache = T.decode_step(params, cfg, cache, _step_inputs(cfg, seq, t),
                                  jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits_full[:, t]),
                                   rtol=1e-3, atol=1e-3)


def test_sliding_window_restricts_attention():
    """With window w, tokens farther than w in the past must not matter."""
    cfg = configs.get_config("gemma3-1b", reduced=True)
    # all-local tiny variant with window 8
    from repro.configs.base import BlockSpec, ATTN
    cfg = dataclasses.replace(
        cfg, num_layers=1, block_pattern=(BlockSpec(kind=ATTN, window=8),))
    params = T.init_params(cfg, KEY)
    b, s = 1, 32
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    out1, _ = T.forward_train(params, cfg, {"tokens": toks}, remat=False)
    # perturb a token far outside the window of the last position
    toks2 = toks.at[0, 2].set((toks[0, 2] + 1) % cfg.vocab_size)
    out2, _ = T.forward_train(params, cfg, {"tokens": toks2}, remat=False)
    np.testing.assert_allclose(np.asarray(out1[0, -1]),
                               np.asarray(out2[0, -1]), rtol=2e-4, atol=2e-4)
    # ...but it must matter within the window
    assert not np.allclose(np.asarray(out1[0, 3]), np.asarray(out2[0, 3]))


def test_moe_capacity_drops_are_the_only_decode_divergence():
    cfg = configs.get_config("phi3.5-moe-42b-a6.6b", reduced=True)
    cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    b, s, s0 = 2, 20, 12
    seq = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    logits_full, _ = T.forward_train(params, cfg, {"tokens": seq},
                                     remat=False)
    lg, cache, idx = T.prefill(params, cfg, {"tokens": seq[:, :s0]},
                               max_len=s)
    errs = []
    for t in range(s0, s):
        lg, cache = T.decode_step(params, cfg, cache,
                                  {"tokens": seq[:, t:t + 1]},
                                  jnp.asarray(t, jnp.int32))
        errs.append(float(jnp.abs(lg - logits_full[:, t]).max()))
    assert max(errs) < 1e-3


def test_param_count_close_to_analytic():
    for arch in ("gemma3-1b", "codeqwen1.5-7b", "phi3.5-moe-42b-a6.6b"):
        cfg = configs.get_config(arch)
        reduced = configs.get_config(arch, reduced=True)
        params = T.init_params(reduced, KEY)
        real = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        est = reduced.param_count()
        assert abs(est - real) / real < 0.35, (arch, est, real)


def test_remat_matches_no_remat():
    cfg = configs.get_config("glm4-9b", reduced=True)
    params = T.init_params(cfg, KEY)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32),
             "loss_mask": jnp.ones((2, 16))}
    l1, _ = T.loss_fn(params, cfg, batch, remat=True)
    l2, _ = T.loss_fn(params, cfg, batch, remat=False)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    g1 = jax.grad(lambda p: T.loss_fn(p, cfg, batch, remat=True)[0])(params)
    g2 = jax.grad(lambda p: T.loss_fn(p, cfg, batch, remat=False)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-3, atol=1e-5)
