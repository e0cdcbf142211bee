"""Unit tests for the paper-core modules (confidence, Eq. 2, Eq. 3, Simi)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import confidence as C
from repro.core import preprocess as PP
from repro.core import region_attention as RA
from repro.core import similarity as SIM

KEY = jax.random.PRNGKey(3)


# ---------------------------------------------------------------------------
# progressive confidence network (§3.1)
# ---------------------------------------------------------------------------

def test_confidence_shapes_and_range():
    p = C.init_confidence(KEY, d_visual=32, d_state=16, hidden=24,
                          num_stages=3)
    assert C.num_stages(p) == 3
    vis = jax.random.normal(KEY, (5, 32))
    st = jax.random.normal(KEY, (5, 16))
    s0 = C.apply_stage(p, 0, vis)
    s1 = C.apply_stage(p, 1, vis, st)
    s2 = C.apply_stage(p, 2, vis, st)
    for s in (s0, s1, s2):
        assert s.shape == (5,)
        assert np.all((np.asarray(s) >= 0) & (np.asarray(s) <= 1))


def test_confidence_stage1_needs_no_state_stage2_does():
    p = C.init_confidence(KEY, 8, 4, num_stages=2)
    vis = jnp.ones((3, 8))
    C.apply_stage(p, 0, vis)  # ok
    with pytest.raises(AssertionError):
        C.apply_stage(p, 1, vis)  # missing generated-token features


def test_confidence_training_reduces_eq1_loss():
    k1, k2, k3 = jax.random.split(KEY, 3)
    n = 256
    vis = jax.random.normal(k1, (n, 16))
    st = jax.random.normal(k2, (n, 8))
    # synthetic similarity target correlated with features
    w = jax.random.normal(k3, (16,))
    target = jax.nn.sigmoid(vis @ w)
    p = C.init_confidence(KEY, 16, 8, hidden=32, num_stages=2)
    l0 = float(C.loss_fn(p, vis, [st], target))
    p2, losses = C.train_confidence(p, vis, [st], target, steps=200)
    l1 = float(C.loss_fn(p2, vis, [st], target))
    assert l1 < 0.5 * l0, (l0, l1)


# ---------------------------------------------------------------------------
# Eq. 2 region attention
# ---------------------------------------------------------------------------

def test_score_regions_normalisation_bounds():
    v = jax.random.normal(KEY, (2, 9, 3, 16))
    e = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 4, 16))
    raw, norm = RA.score_regions(v, e)
    assert raw.shape == norm.shape == (2, 9)
    n = np.asarray(norm)
    assert np.all((n >= 0) & (n <= 1))
    # identical region/text directions → max normalised score
    e1 = jnp.ones((1, 2, 8))
    v1 = jnp.ones((1, 1, 3, 8))
    _, n1 = RA.score_regions(v1, e1)
    assert float(n1[0, 0]) > 0.99


# ---------------------------------------------------------------------------
# Eq. 3 multi-scale preprocessing
# ---------------------------------------------------------------------------

def test_multiscale_piecewise_rules():
    b, r, hw = 1, 4, 8
    regions = jnp.broadcast_to(
        jnp.arange(r, dtype=jnp.float32)[None, :, None, None, None] + 1.0,
        (b, r, hw, hw, 3)) * jnp.abs(jax.random.normal(KEY, (b, r, hw, hw, 3)))
    scores = jnp.asarray([[0.1, 0.45, 0.56, 0.99]])  # below α / band / above β
    out, tx, meta = PP.multiscale_filter(regions, scores, alpha=0.35,
                                         beta=0.55)
    o = np.asarray(out)
    # K < α → discarded (zero)
    assert np.all(o[0, 0] == 0)
    # K ≥ β → preserved exactly
    np.testing.assert_allclose(o[0, 2], np.asarray(regions)[0, 2], rtol=1e-6)
    np.testing.assert_allclose(o[0, 3], np.asarray(regions)[0, 3], rtol=1e-6)
    # α ≤ K < β → downsampled (changed, nonzero)
    assert not np.allclose(o[0, 1], np.asarray(regions)[0, 1])
    assert np.abs(o[0, 1]).sum() > 0
    # byte accounting: discarded contributes 0; preserved full
    full_px = hw * hw * 3 * 3.0
    assert float(tx[0]) < 4 * full_px
    assert float(meta["compression_ratio"][0]) > 1.0


def test_multiscale_partial_cells_at_level_8():
    """A 28-px Qwen2-VL region at level 8 pools into ⌈28/8⌉² = 16 cells per
    channel: the edge cells average only the pixels they hold, and all 16
    are counted as sent."""
    hw, ch = 28, 3
    regions = jax.random.uniform(KEY, (1, 2, hw, hw, ch))
    # c = 0.2 / (0.36 - 0.35) = 20 → level 8; second region dropped
    scores = jnp.asarray([[0.36, 0.1]])
    out, tx, meta = PP.multiscale_filter(regions, scores, alpha=0.35,
                                         beta=0.55)
    assert float(meta["levels"][0, 0]) == 8.0
    x, o = np.asarray(regions)[0, 0], np.asarray(out)[0, 0]
    for i0 in range(0, hw, 8):
        for j0 in range(0, hw, 8):
            cell = x[i0:i0 + 8, j0:j0 + 8]      # 4 px wide at the edge
            np.testing.assert_allclose(
                o[i0:i0 + 8, j0:j0 + 8],
                np.broadcast_to(cell.mean(axis=(0, 1)), cell.shape),
                rtol=1e-5, atol=1e-6)
    assert float(tx[0]) == 16 * ch * 3.0


def test_multiscale_scale_factor_formula():
    scores = jnp.asarray([0.35, 0.45, 0.549, 0.55, 0.9])
    c = np.asarray(PP.scale_factor(scores, 0.35, 0.55))
    assert c[-1] == 1.0 and c[-2] == 1.0          # ≥ β → 1
    assert c[1] == pytest.approx((0.55 - 0.35) / (0.45 - 0.35))
    assert np.isinf(c[0]) or c[0] >= 1e6          # at α → unbounded


def test_random_mask_filter_bytes():
    regions = jnp.ones((2, 16, 4, 4, 3))
    out, tx, meta = PP.random_mask_filter(regions, 0.5, KEY)
    kept = np.asarray(meta["kept"]).sum(-1)
    np.testing.assert_allclose(np.asarray(tx), kept * 4 * 4 * 3 * 3.0)


# ---------------------------------------------------------------------------
# Simi metrics
# ---------------------------------------------------------------------------

def test_similarity_metrics():
    assert float(SIM.simi_exact(jnp.asarray([1, 2]),
                                jnp.asarray([1, 3])).mean()) == 0.5
    iou = SIM.simi_region_iou(jnp.asarray([[1, 1, 0, 0]]),
                              jnp.asarray([[1, 0, 1, 0]]))
    assert float(iou[0]) == pytest.approx(1 / 3)
    d1 = jnp.asarray([[[0.9, 0.1]]])
    d2 = jnp.asarray([[[0.9, 0.1]]])
    assert float(SIM.output_similarity(d1, d2)[0]) == pytest.approx(1.0)
    d3 = jnp.asarray([[[0.1, 0.9]]])
    assert float(SIM.output_similarity(d1, d3)[0]) < 0.5
