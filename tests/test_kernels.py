"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# region_score (Eq. 2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,r,nv,ne,d", [
    (1, 8, 4, 16, 32), (2, 16, 1, 8, 64), (3, 25, 2, 12, 128),
    (2, 100, 1, 7, 48),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.kernel_parity
def test_region_score_sweep(b, r, nv, ne, d, dtype):
    k1, k2 = jax.random.split(KEY)
    v = _rand(k1, (b, r, nv, d), dtype)
    e = _rand(k2, (b, ne, d), dtype)
    got = ops.region_score(v, e, impl="pallas_interpret")
    want = ops.region_score(v, e, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL[dtype])


def test_region_score_matches_manual_cosine():
    v = _rand(KEY, (1, 3, 2, 16), jnp.float32)
    e = _rand(jax.random.fold_in(KEY, 1), (1, 5, 16), jnp.float32)
    manual = np.zeros((1, 3))
    vn = np.asarray(v)
    en = np.asarray(e)
    for r in range(3):
        for i in range(2):
            for j in range(5):
                a, b_ = vn[0, r, i], en[0, j]
                manual[0, r] += (a @ b_) / (np.linalg.norm(a)
                                            * np.linalg.norm(b_))
    got = ops.region_score(v, e, impl="ref")
    np.testing.assert_allclose(np.asarray(got), manual, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,h,kh,hd", [
    (128, 4, 4, 32), (256, 8, 2, 32), (128, 4, 1, 64), (256, 2, 2, 16),
    (200, 4, 2, 32),    # ragged tail block: N_r + 1 style lengths
])
@pytest.mark.parametrize("window,softcap", [(0, None), (64, None),
                                            (0, 50.0), (96, 30.0)])
@pytest.mark.kernel_parity
def test_flash_attention_sweep(sq, h, kh, hd, window, softcap):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (2, sq, h, hd), jnp.float32)
    k = _rand(k2, (2, sq, kh, hd), jnp.float32)
    v = _rand(k3, (2, sq, kh, hd), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              softcap=softcap, impl="pallas_interpret")
    want = ops.flash_attention(q, k, v, causal=True, window=window,
                               softcap=softcap, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (1, 128, 4, 32), dtype)
    k = _rand(k2, (1, 128, 2, 32), dtype)
    v = _rand(k3, (1, 128, 2, 32), dtype)
    got = ops.flash_attention(q, k, v, impl="pallas_interpret")
    want = ops.flash_attention(q, k, v, impl="ref")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_structured_matches_ref_and_grads():
    k1, k2, k3 = jax.random.split(KEY, 3)
    for window, cap in [(0, None), (64, None), (48, 50.0)]:
        q = _rand(k1, (2, 256, 4, 32), jnp.float32)
        k = _rand(k2, (2, 256, 2, 32), jnp.float32)
        v = _rand(k3, (2, 256, 2, 32), jnp.float32)
        f1 = lambda q, k, v: (ref.flash_attention(
            q, k, v, causal=True, window=window, softcap=cap) ** 2).sum()
        f2 = lambda q, k, v: (ref.flash_structured(
            q, k, v, True, window, cap) ** 2).sum()
        np.testing.assert_allclose(f1(q, k, v), f2(q, k, v), rtol=1e-4)
        g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,h,kh,hd,clen,window", [
    (256, 8, 2, 32, 256, 0), (256, 8, 2, 32, 100, 0),
    (512, 4, 1, 64, 300, 128), (256, 4, 4, 16, 37, 0),
])
def test_decode_attention_sweep(s, h, kh, hd, clen, window):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (2, h, hd), jnp.float32)
    k = _rand(k2, (2, s, kh, hd), jnp.float32)
    v = _rand(k3, (2, s, kh, hd), jnp.float32)
    got = ops.decode_attention(q, k, v, jnp.int32(clen), window=window,
                               impl="pallas_interpret")
    want = ref.decode_attention(q, k, v, jnp.int32(clen), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.kernel_parity
@pytest.mark.parametrize("s,clen,q_len", [
    (300, 300, 1), (300, 290, 1), (300, 137, 1), (300, 300, 3),
    (2052, 2052, 4),
])
def test_decode_attention_ragged_tail_block(s, clen, q_len):
    """A KV axis that is not a multiple of the tile runs on a cdiv grid;
    the tail block's rows past S must not leak into the result."""
    h, kh, hd = 6, 2, 128
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (2, q_len, h, hd), jnp.float32)
    k = _rand(k2, (2, s, kh, hd), jnp.float32)
    v = _rand(k3, (2, s, kh, hd), jnp.float32)
    lens = jnp.asarray([clen, max(clen - 5, q_len)], jnp.int32)
    got = ops._rows_to_chunk(decode_attention_pallas(
        ops._chunk_to_rows(q, kh), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lens, q_len=q_len, kv_blk=128,
        interpret=True), q_len, h)
    want = ref.multi_decode_attention(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _block_tables(rng, b, n_logical, n_pages, n_shared):
    """Per-row tables whose first ``n_shared`` entries alias the same pages
    (the shared-prefix regime) and whose tail pages are row-private."""
    bt = np.zeros((b, n_logical), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    bt[:, :n_shared] = perm[:n_shared]
    nxt = n_shared
    for r in range(b):
        for c in range(n_shared, n_logical):
            bt[r, c] = perm[nxt]
            nxt += 1
    return bt


@pytest.mark.kernel_parity
@pytest.mark.parametrize("s,h,kh,hd,page,window", [
    (64, 8, 2, 32, 8, 0),        # plain paged ragged decode
    (64, 4, 1, 64, 16, 24),      # paged + sliding window
    (64, 4, 4, 16, 8, 0),        # MHA (group = 1)
    (32, 4, 2, 32, 8, 40),       # window wider than some rows' caches
])
def test_paged_decode_attention_block_table_parity(s, h, kh, hd, page,
                                                   window):
    """Page-indirect decode (interpret=True) vs the gather-then-dense
    oracle: per-row (B, P) block tables with aliased shared-prefix pages,
    ragged lengths including the empty / singleton / full extremes."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    clen = jnp.asarray([0, 1, s // 2 + 1, s], jnp.int32)
    b = clen.shape[0]
    n_logical = s // page
    n_pages = 1 + 2 + b * n_logical
    kp = _rand(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = _rand(k2, (n_pages, page, kh, hd), jnp.float32)
    q = _rand(k3, (b, h, hd), jnp.float32)
    bt = jnp.asarray(_block_tables(np.random.RandomState(0), b, n_logical,
                                   n_pages, n_shared=2))
    got = ops.paged_decode_attention(q, kp, vp, bt, clen, window=window,
                                     impl="pallas_interpret")
    want = ops.paged_decode_attention(q, kp, vp, bt, clen, window=window,
                                      impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert np.all(np.asarray(got)[0] == 0)      # empty row → exact zeros


@pytest.mark.kernel_parity
def test_paged_decode_matches_dense_decode_on_gathered_cache():
    """Page indirection is pure layout: gathering each row's pages into a
    dense cache and running the dense ragged kernel gives the same output
    (both in interpret mode)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    s, h, kh, hd, page = 64, 4, 2, 32, 8
    clen = jnp.asarray([5, 17, 33, 64], jnp.int32)
    b = clen.shape[0]
    n_logical = s // page
    n_pages = 1 + 2 + b * n_logical
    kp = _rand(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = _rand(k2, (n_pages, page, kh, hd), jnp.float32)
    q = _rand(k3, (b, h, hd), jnp.float32)
    bt = jnp.asarray(_block_tables(np.random.RandomState(1), b, n_logical,
                                   n_pages, n_shared=2))
    paged = ops.paged_decode_attention(q, kp, vp, bt, clen,
                                       impl="pallas_interpret")
    kd = ref.gather_pages(kp, bt)
    vd = ref.gather_pages(vp, bt)
    dense = ops.decode_attention(q, kd, vd, clen, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.kernel_parity
@pytest.mark.parametrize("q_len", [1, 2, 8])
@pytest.mark.parametrize("s,h,kh,hd,page,window", [
    (64, 8, 2, 32, 8, 0),        # plain multi-token paged scoring
    (64, 4, 1, 64, 16, 24),      # + sliding window
    (64, 4, 4, 16, 8, 0),        # MHA (group = 1)
])
def test_paged_multi_token_scoring_parity(s, h, kh, hd, page, window, q_len):
    """The speculative verifier's kernel: a q_len = γ+1 token chunk per row
    scored in ONE page-indirect pass (interpret mode) vs the
    gather-then-dense chunk-causal oracle.  Ragged lengths include the
    empty row (an inactive slot parked on the trash page), a row SHORTER
    than the chunk (its early chunk tokens are fully masked inside a
    needed block — the m == NEG_INF corner), the chunk-only row and the
    full row; shared-prefix pages alias across rows and are verified
    bit-identical after the call (the scoring kernel never writes KV)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    clen = jnp.asarray([0, max(q_len - 1, 1), q_len, s], jnp.int32)
    b = clen.shape[0]
    n_logical = s // page
    n_pages = 1 + 2 + b * n_logical
    kp = _rand(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = _rand(k2, (n_pages, page, kh, hd), jnp.float32)
    q = _rand(k3, (b, q_len, h, hd), jnp.float32)
    bt = jnp.asarray(_block_tables(np.random.RandomState(0), b, n_logical,
                                   n_pages, n_shared=2))
    kp_before, vp_before = np.asarray(kp).copy(), np.asarray(vp).copy()
    got = ops.paged_multi_decode_attention(q, kp, vp, bt, clen,
                                           window=window,
                                           impl="pallas_interpret")
    want = ops.paged_multi_decode_attention(q, kp, vp, bt, clen,
                                            window=window, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert np.all(np.asarray(got)[0] == 0)      # empty row → exact zeros
    # the pools (shared prefix pages included) are untouched
    np.testing.assert_array_equal(np.asarray(kp), kp_before)
    np.testing.assert_array_equal(np.asarray(vp), vp_before)


@pytest.mark.kernel_parity
@pytest.mark.parametrize("q_len,q_blk", [(1, 8), (4, 2), (8, 8), (16, 4),
                                         (6, 4)])
@pytest.mark.parametrize("s,h,kh,hd,page,window", [
    (64, 8, 2, 32, 8, 0),        # plain chunked prefill-append
    (64, 4, 1, 64, 16, 24),      # + sliding window
    (64, 4, 4, 16, 8, 0),        # MHA (group = 1)
])
def test_paged_prefill_attention_parity(s, h, kh, hd, page, window, q_len,
                                        q_blk):
    """The chunked-prefill kernel: a C-token prefix-append chunk per row,
    scored with a TILED query-chunk grid (q_blk-token sub-blocks, incl. a
    q_blk that does not divide C and falls back to a smaller divisor) vs
    the gather-then-dense chunk-causal oracle.  Ragged lengths cover the
    len-0 idle row, a row SHORTER than the chunk (early chunk tokens fully
    masked — the m == NEG_INF corner), the chunk-only row (a fresh stream:
    nothing before the chunk), a ragged mid-prefill tail and the full row;
    shared-prefix pages alias across rows and the pools stay bit-identical
    (the kernel never writes KV)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    clen = jnp.asarray([0, max(q_len - 1, 1), q_len, q_len + s // 2, s],
                       jnp.int32)
    b = clen.shape[0]
    n_logical = s // page
    n_pages = 1 + 2 + b * n_logical
    kp = _rand(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = _rand(k2, (n_pages, page, kh, hd), jnp.float32)
    q = _rand(k3, (b, q_len, h, hd), jnp.float32)
    bt = jnp.asarray(_block_tables(np.random.RandomState(0), b, n_logical,
                                   n_pages, n_shared=2))
    kp_before, vp_before = np.asarray(kp).copy(), np.asarray(vp).copy()
    got = ops.paged_prefill_attention(q, kp, vp, bt, clen, window=window,
                                      q_blk=q_blk, impl="pallas_interpret")
    want = ops.paged_prefill_attention(q, kp, vp, bt, clen, window=window,
                                       impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert np.all(np.asarray(got)[0] == 0)      # idle row → exact zeros
    np.testing.assert_array_equal(np.asarray(kp), kp_before)
    np.testing.assert_array_equal(np.asarray(vp), vp_before)


@pytest.mark.kernel_parity
def test_paged_prefill_matches_multi_decode_kernel():
    """At the same q_len the tiled prefill-append kernel and the γ+1
    verify kernel compute the same function — the tiling is pure structure
    (per-sub-block scratch + skip bounds), not new semantics."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    s, h, kh, hd, page, t = 64, 4, 2, 32, 8, 8
    clen = jnp.asarray([t, 21, 40, s], jnp.int32)
    b = clen.shape[0]
    n_logical = s // page
    n_pages = 1 + 2 + b * n_logical
    kp = _rand(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = _rand(k2, (n_pages, page, kh, hd), jnp.float32)
    q = _rand(k3, (b, t, h, hd), jnp.float32)
    bt = jnp.asarray(_block_tables(np.random.RandomState(2), b, n_logical,
                                   n_pages, n_shared=2))
    prefill = ops.paged_prefill_attention(q, kp, vp, bt, clen, q_blk=4,
                                          impl="pallas_interpret")
    verify = ops.paged_multi_decode_attention(q, kp, vp, bt, clen,
                                              impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(prefill), np.asarray(verify),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.kernel_parity
def test_multi_token_chunk_matches_sequential_single_token():
    """Chunk-causal semantics pinned against the single-token kernel: token
    t of a T-chunk must equal a 1-token call at cache_len - (T-1-t)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    s, h, kh, hd, t = 64, 4, 2, 32, 4
    clen = jnp.asarray([t, 17, s], jnp.int32)
    b = clen.shape[0]
    q = _rand(k3, (b, t, h, hd), jnp.float32)
    k = _rand(k1, (b, s, kh, hd), jnp.float32)
    v = _rand(k2, (b, s, kh, hd), jnp.float32)
    chunk = ops.multi_decode_attention(q, k, v, clen,
                                       impl="pallas_interpret")
    for ti in range(t):
        one = ops.decode_attention(q[:, ti], k, v, clen - (t - 1 - ti),
                                   impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(chunk[:, ti]),
                                   np.asarray(one), rtol=2e-4, atol=2e-4)


@pytest.mark.kernel_parity
@pytest.mark.parametrize("s,h,kh,hd,window", [
    (256, 8, 2, 32, 0),          # plain ragged decode
    (512, 4, 1, 64, 128),        # ragged + sliding window (band slice path)
    (256, 4, 4, 16, 0),          # MHA (group = 1)
    (128, 4, 2, 32, 96),         # window wider than some rows' caches
])
def test_decode_attention_ragged_lengths(s, h, kh, hd, window):
    """Per-sequence (B,) cache lengths — the continuous-batching slot-table
    regime: every row sits at its own position, including the empty (0),
    singleton (1) and completely-full (S) extremes."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    clen = jnp.asarray([0, 1, 37, s // 2, s], jnp.int32)
    b = clen.shape[0]
    q = _rand(k1, (b, h, hd), jnp.float32)
    k = _rand(k2, (b, s, kh, hd), jnp.float32)
    v = _rand(k3, (b, s, kh, hd), jnp.float32)
    got = ops.decode_attention(q, k, v, clen, window=window,
                               impl="pallas_interpret")
    want = ops.decode_attention(q, k, v, clen, window=window, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # row-0 (empty cache) attends to nothing → exact zeros in both impls
    assert np.all(np.asarray(got)[0] == 0)
    assert np.all(np.asarray(want)[0] == 0)


def test_decode_attention_ragged_matches_per_row_scalar():
    """Each row of a ragged batch must equal a batch-1 scalar-length call —
    the (B,) path is exactly B independent decodes."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    s, h, kh, hd = 256, 4, 2, 32
    clen = jnp.asarray([3, 100, 256, 57], jnp.int32)
    q = _rand(k1, (4, h, hd), jnp.float32)
    k = _rand(k2, (4, s, kh, hd), jnp.float32)
    v = _rand(k3, (4, s, kh, hd), jnp.float32)
    for window in (0, 64):
        batched = ops.decode_attention(q, k, v, clen, window=window,
                                       impl="pallas_interpret")
        for i in range(4):
            one = ops.decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                       clen[i], window=window,
                                       impl="pallas_interpret")
            np.testing.assert_allclose(np.asarray(batched[i]),
                                       np.asarray(one[0]),
                                       rtol=2e-4, atol=2e-4)


def test_decode_attention_scalar_broadcasts_to_ragged():
    """A scalar cache_len is the batch-uniform special case of (B,)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (3, 4, 32), jnp.float32)
    k = _rand(k2, (3, 128, 2, 32), jnp.float32)
    v = _rand(k3, (3, 128, 2, 32), jnp.float32)
    for impl in ("ref", "pallas_interpret"):
        a = ops.decode_attention(q, k, v, jnp.int32(77), impl=impl)
        bvec = ops.decode_attention(q, k, v, jnp.full((3,), 77, jnp.int32),
                                    impl=impl)
        np.testing.assert_allclose(np.asarray(a), np.asarray(bvec),
                                   rtol=1e-6, atol=1e-6)


def test_decode_matches_flash_last_row():
    """Decode at position S-1 must equal the last row of full attention."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    s = 128
    q = _rand(k1, (2, s, 4, 32), jnp.float32)
    k = _rand(k2, (2, s, 2, 32), jnp.float32)
    v = _rand(k3, (2, s, 2, 32), jnp.float32)
    full = ops.flash_attention(q, k, v, causal=True, impl="ref")
    dec = ops.decode_attention(q[:, -1], k, v, jnp.int32(s), impl="ref")
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssm_scan (chunked GLA)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,h,dk,dv,chunk", [
    (128, 4, 16, 16, 32), (256, 2, 8, 24, 64), (64, 1, 32, 8, 16),
])
@pytest.mark.kernel_parity
def test_ssm_scan_sweep(s, h, dk, dv, chunk):
    ks = jax.random.split(KEY, 4)
    q = _rand(ks[0], (2, s, h, dk), jnp.float32)
    k = _rand(ks[1], (2, s, h, dk), jnp.float32) * 0.3
    v = _rand(ks[2], (2, s, h, dv), jnp.float32)
    g = -jax.nn.softplus(_rand(ks[3], (2, s, h), jnp.float32))
    o1, f1 = ops.ssm_scan(q, k, v, g, impl="pallas_interpret", chunk=chunk)
    o2, f2 = ops.ssm_scan(q, k, v, g, impl="ref", chunk=chunk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               rtol=3e-4, atol=3e-4)


def test_ssm_chunked_equals_sequential():
    ks = jax.random.split(KEY, 4)
    s = 96
    q = _rand(ks[0], (1, s, 2, 8), jnp.float32)
    k = _rand(ks[1], (1, s, 2, 8), jnp.float32) * 0.3
    v = _rand(ks[2], (1, s, 2, 12), jnp.float32)
    g = -jax.nn.softplus(_rand(ks[3], (1, s, 2), jnp.float32))
    o_chunk, f_chunk = ops.ssm_scan(q, k, v, g, impl="ref", chunk=32)
    st = jnp.zeros((1, 2, 8, 12))
    outs = []
    for t in range(s):
        o_t, st = ref.ssm_decode_step(q[:, t], k[:, t], v[:, t], g[:, t], st)
        outs.append(o_t)
    o_seq = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(o_chunk), np.asarray(o_seq),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(f_chunk), np.asarray(st),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# slstm_scan (sLSTM recurrence)
# ---------------------------------------------------------------------------

@pytest.mark.kernel_parity
@pytest.mark.parametrize("b,s,heads,p", [(2, 16, 2, 8), (1, 33, 4, 4),
                                         (3, 8, 1, 16)])
def test_slstm_scan_parity(b, s, heads, p):
    d = heads * p
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 11))
    gates_x = _rand(k1, (b, s, 4 * d), jnp.float32)
    r = _rand(k2, (heads, p, 4 * p), jnp.float32) * 0.2
    h1, st1 = ops.slstm_scan(gates_x, r, impl="pallas_interpret")
    h2, st2 = ops.slstm_scan(gates_x, r, impl="ref")
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-4, atol=2e-4)
    assert len(st1) == len(st2) == 4
    for got, want in zip(st1, st2):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_ssm_state_continuation():
    """Splitting a sequence across two scans must match one scan."""
    ks = jax.random.split(KEY, 4)
    s = 128
    q = _rand(ks[0], (1, s, 2, 8), jnp.float32)
    k = _rand(ks[1], (1, s, 2, 8), jnp.float32) * 0.3
    v = _rand(ks[2], (1, s, 2, 8), jnp.float32)
    g = -jax.nn.softplus(_rand(ks[3], (1, s, 2), jnp.float32))
    o_full, f_full = ops.ssm_scan(q, k, v, g, impl="ref", chunk=32)
    o1, f1 = ops.ssm_scan(q[:, :64], k[:, :64], v[:, :64], g[:, :64],
                          impl="ref", chunk=32)
    o2, f2 = ops.ssm_scan(q[:, 64:], k[:, 64:], v[:, 64:], g[:, 64:],
                          state=f1, impl="ref", chunk=32)
    np.testing.assert_allclose(np.asarray(o_full[:, 64:]), np.asarray(o2),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(f_full), np.asarray(f2),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# quantized paged KV: int8 pools + in-kernel dequant, via the strategy factory
# ---------------------------------------------------------------------------

from repro.kernels import kv_quant  # noqa: E402


def _quant_operands(s, kh, hd, page, q_len, seed=0):
    """Shared paged-cache fixture for the quantized parity sweep: fp pools,
    aliased shared-prefix block tables, and ragged lengths hitting the
    empty row, a row shorter than the chunk, the chunk-only row and the
    full row."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    clen = jnp.asarray([0, max(q_len - 1, 1), q_len, s], jnp.int32)
    b = clen.shape[0]
    n_logical = s // page
    n_pages = 1 + 2 + b * n_logical
    kp = _rand(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = _rand(k2, (n_pages, page, kh, hd), jnp.float32)
    bt = jnp.asarray(_block_tables(np.random.RandomState(seed), b,
                                   n_logical, n_pages, n_shared=2))
    return kp, vp, bt, clen, b, k3


def test_kv_quant_roundtrip():
    """quantize→dequantize stays within the per-element noise bound
    (amax/254 per row) and all-zero vectors round-trip exactly."""
    x = _rand(KEY, (5, 4, 2, 32), jnp.float32)
    q, scale = kv_quant.quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    back = kv_quant.dequantize_kv(q, scale)
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert np.all(np.abs(np.asarray(back - x))
                  < amax / (2 * kv_quant.Q_MAX) + 1e-7)
    zq, zs = kv_quant.quantize_kv(jnp.zeros((3, 8)))
    assert np.all(np.asarray(zq) == 0) and np.all(np.asarray(zs) == 0)
    np.testing.assert_array_equal(np.asarray(kv_quant.dequantize_kv(zq, zs)),
                                  0.0)


def test_kv_quant_fp8_roundtrip():
    """fp8 (e4m3) quantize→dequantize: 3 mantissa bits give a relative
    step of 2⁻³ between adjacent values, so the per-element error after
    scaling onto ±448 is ≤ amax/16 — ~9× int8's bound, but checked the
    same way; all-zero rows round-trip to exact zeros (0.0 is exactly
    representable in e4m3)."""
    x = _rand(KEY, (5, 4, 2, 32), jnp.float32)
    q, scale = kv_quant.quantize_kv_fp8(x)
    assert q.dtype == kv_quant.FP8_DTYPE and scale.dtype == jnp.float32
    back = kv_quant.dequantize_kv(q, scale)
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert np.all(np.abs(np.asarray(back - x)) <= amax / 16 + 1e-7)
    zq, zs = kv_quant.quantize_kv_fp8(jnp.zeros((3, 8)))
    assert np.all(np.asarray(zq).astype(np.float32) == 0)
    assert np.all(np.asarray(zs) == 0)
    np.testing.assert_array_equal(
        np.asarray(kv_quant.dequantize_kv(zq, zs)), 0.0)


def test_kv_quant_fp8_saturating_cast():
    """The clamp the quantizer exists for: jnp's raw e4m3 cast OVERFLOWS TO
    NaN past ±448, so the row amax (which scales exactly onto ±FP8_MAX) and
    anything float-rounding pushes past it must saturate finite.  Every
    stored byte round-trips finite, and the amax element round-trips to
    amax exactly (448 is representable)."""
    x = jnp.asarray([[1e4, -1e4, 3.0, -2.5, 0.5, 1e-3, 7.0, -448.0]],
                    jnp.float32)
    q, scale = kv_quant.quantize_kv_fp8(x)
    qf = np.asarray(q).astype(np.float32)
    assert np.isfinite(qf).all()
    assert np.abs(qf).max() == kv_quant.FP8_MAX
    back = np.asarray(kv_quant.dequantize_kv(q, scale))
    np.testing.assert_allclose(back[0, 0], 1e4, rtol=1e-6)
    # sanity: the raw cast really is non-saturating — the clamp is load-
    # bearing, not defensive
    raw = jnp.asarray([600.0], jnp.float32).astype(kv_quant.FP8_DTYPE)
    assert np.isnan(np.asarray(raw).astype(np.float32)).all()


def test_kv_quant_fp8_subnormal_inputs():
    """Tiny-magnitude rows, two regimes, no garbage in either:

    - amax above the quantizer's 1e-30 guard floor (but far below e4m3's
      normal range): the per-row scale maps amax onto 448 BEFORE the cast,
      so the stored elements live in e4m3's well-conditioned range and the
      round-trip keeps the usual amax/16 bound;
    - true f32-subnormal rows (amax below the floor): the guard denominator
      takes over and the row flushes to EXACT zeros — finite, deterministic,
      and identical to int8's behavior on the same row."""
    base = np.asarray([[1.0, -0.5, 0.25, 0.125, -1.0, 0.75, 0.3, -0.06]],
                      np.float32)
    x = jnp.asarray(base * 1e-20, jnp.float32)
    q, scale = kv_quant.quantize_kv_fp8(x)
    qf = np.asarray(q).astype(np.float32)
    assert np.isfinite(qf).all() and np.abs(qf).max() == kv_quant.FP8_MAX
    back = np.asarray(kv_quant.dequantize_kv(q, scale))
    assert np.all(np.abs(back - np.asarray(x)) <= 1e-20 / 16 + 1e-30)
    # the relative shape of the row survives: largest element stays largest
    assert np.argmax(np.abs(back[0])) in (0, 4)
    sub = jnp.asarray(base * 1e-40, jnp.float32)      # f32 subnormals
    for quant in (kv_quant.quantize_kv_fp8, kv_quant.quantize_kv):
        qs, ss = quant(sub)
        np.testing.assert_array_equal(
            np.asarray(qs).astype(np.float32), 0.0)
        np.testing.assert_array_equal(
            np.asarray(kv_quant.dequantize_kv(qs, ss)), 0.0)


def test_kv_quant_fp8_chunked_equals_unchunked():
    """Write-local bit-stability at the quantizer level: quantizing a
    sequence row-by-row (how decode/verify/prefill chunks land in pages)
    produces BIT-IDENTICAL stored bytes and scales to quantizing the whole
    tensor at once — the property that makes chunked == unchunked prefill
    and free spec rollback hold under fp8."""
    x = _rand(jax.random.PRNGKey(7), (6, 2, 16), jnp.float32)
    q_all, s_all = kv_quant.quantize_kv_fp8(x)
    for i in range(x.shape[0]):
        q_i, s_i = kv_quant.quantize_kv_fp8(x[i:i + 1])
        np.testing.assert_array_equal(
            np.asarray(q_i).view(np.uint8),
            np.asarray(q_all[i:i + 1]).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(s_i),
                                      np.asarray(s_all[i:i + 1]))


def test_kv_quantize_as_dispatch():
    """``quantize_kv_as`` keys the quantizer off the pool leaf's dtype —
    the one dispatch all three write paths share."""
    x = _rand(KEY, (4, 2, 16), jnp.float32)
    qi, si = kv_quant.quantize_kv_as(x, jnp.int8)
    qi2, si2 = kv_quant.quantize_kv(x)
    np.testing.assert_array_equal(np.asarray(qi), np.asarray(qi2))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(si2))
    qf, sf = kv_quant.quantize_kv_as(x, kv_quant.FP8_DTYPE)
    qf2, sf2 = kv_quant.quantize_kv_fp8(x)
    np.testing.assert_array_equal(np.asarray(qf).view(np.uint8),
                                  np.asarray(qf2).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(sf), np.asarray(sf2))
    with pytest.raises(ValueError):
        kv_quant.quantize_kv_as(x, jnp.float16)


def test_kv_strategy_factory():
    with pytest.raises(ValueError):
        kv_quant.get_strategy("int4")
    with pytest.raises(ValueError):
        kv_quant.for_kv_dtype("int4")
    assert kv_quant.for_kv_dtype(None).name == "exact"
    assert kv_quant.for_kv_dtype("int8").name == "int8"
    assert kv_quant.for_kv_dtype("fp8").name == "fp8"
    exact = kv_quant.get_strategy("exact")
    pools = exact.make_pools(jnp.ones((2, 4, 1, 8)), jnp.ones((2, 4, 1, 8)))
    assert set(pools) == {"k", "v"} and exact.scale_kwargs(pools) == {}
    fp8 = kv_quant.get_strategy("fp8")
    pools8 = fp8.make_pools(jnp.ones((2, 4, 1, 8)), jnp.ones((2, 4, 1, 8)))
    assert pools8["k"].dtype == kv_quant.FP8_DTYPE
    assert set(fp8.scale_kwargs(pools8)) == {"k_scale", "v_scale"}


@pytest.mark.kernel_parity
@pytest.mark.parametrize("strategy", ["exact", "int8", "fp8"])
@pytest.mark.parametrize("which,q_len,window", [
    ("decode", 1, 0),            # single-token decode
    ("decode", 1, 24),           # + sliding window
    ("multi", 3, 0),             # speculative verify chunk (γ+1 = 3)
    ("multi", 1, 0),             # γ = 0 degenerate chunk
    ("prefill", 8, 0),           # full prefill chunk (q_blk 4)
    ("prefill", 6, 24),          # ragged chunk + sliding window
])
def test_paged_kernel_strategy_parity(strategy, which, q_len, window):
    """Every paged kernel × every KV strategy, two bounds per case:

    - kernel vs the strategy's OWN oracle (tight ``tol_self`` — the Pallas
      body computes the same dequantized math in-register);
    - strategy oracle vs the exact-fp oracle (``tol_exact`` — the
      strategy's quantization-noise budget; 0 for the exact strategy).

    fp8 additionally exercises the native-fp8 dot path: ``native_dot``
    resolves True for e4m3 pools, so the kernel contracts over the STORED
    bytes and applies the scales post-dot — still held to ``tol_self``
    against the dequantize-first oracle.
    """
    st = kv_quant.get_strategy(strategy)
    s, h, kh, hd, page = 64, 4, 2, 32, 8
    kp, vp, bt, clen, b, kq = _quant_operands(s, kh, hd, page, q_len)
    pools = st.make_pools(kp, vp)
    if which == "decode":
        q = _rand(kq, (b, h, hd), jnp.float32)
        fn = ops.paged_decode_attention
    elif which == "multi":
        q = _rand(kq, (b, q_len, h, hd), jnp.float32)
        fn = ops.paged_multi_decode_attention
    else:
        q = _rand(kq, (b, q_len, h, hd), jnp.float32)
        fn = lambda *a, **kw: ops.paged_prefill_attention(*a, q_blk=4, **kw)
    kw = dict(window=window, **st.scale_kwargs(pools))
    got = fn(q, pools["k"], pools["v"], bt, clen,
             impl="pallas_interpret", **kw)
    own = st.oracle(which, q, pools, bt, clen, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(own),
                               rtol=st.tol_self, atol=st.tol_self)
    exact = kv_quant.get_strategy("exact")
    want = exact.oracle(which, q, exact.make_pools(kp, vp), bt, clen,
                        window=window)
    np.testing.assert_allclose(np.asarray(own), np.asarray(want),
                               rtol=st.tol_exact + 1e-6,
                               atol=st.tol_exact + 1e-6)
    assert np.all(np.asarray(got)[0] == 0)      # empty row → exact zeros


@pytest.mark.kernel_parity
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_paged_decode_quantized_zero_scale_rows(kv_dtype):
    """Pages quantized from all-zero KV carry scale 0: the kernel's
    dequantized contribution is exactly 0·score, so outputs are finite and
    the all-zero-cache row attends to nothing but still normalizes (fp8
    additionally pins that 0.0 is exactly representable in e4m3, so the
    native-dot path contracts true zeros)."""
    s, kh, hd, page = 32, 2, 16, 8
    kp, vp, bt, clen, b, kq = _quant_operands(s, kh, hd, page, 1, seed=3)
    pools = kv_quant.quantize_pool(jnp.zeros_like(kp), jnp.zeros_like(vp),
                                   kv_dtype=kv_dtype)
    q = _rand(kq, (b, 4, hd), jnp.float32)
    got = ops.paged_decode_attention(q, pools["k"], pools["v"], bt, clen,
                                     k_scale=pools["k_scale"],
                                     v_scale=pools["v_scale"],
                                     impl="pallas_interpret")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), 0.0)


@pytest.mark.kernel_parity
@pytest.mark.parametrize("pool,fan,h,kh", [
    pytest.param("bf16", 32, 4, 2, id="bf16-32"),
    pytest.param("bf16", 8, 4, 2, id="bf16-8"),
    pytest.param("int8", 8, 4, 2, id="int8-8"),
    pytest.param("fp8", 8, 4, 2, id="fp8-8"),
    # one device's share of Qwen2-VL-7B on four: 1 KV head, group 7
    pytest.param("bf16", 32, 7, 1, id="bf16-32-kv1-group7")])
@pytest.mark.parametrize("q_len,window", [(1, 0), (1, 20), (3, 0), (3, 20)])
def test_paged_decode_chunk_walk_parity(pool, fan, h, kh, q_len, window):
    """The chunked page walk (interpret mode) on a prime table width, 37
    blocks, that no chunk size divides: chunks of 32 pages (256 tokens) and
    of 8 leave partial last chunks.  One batch holds the lengths 0, 1,
    page - 1, page, either side of and on both chunk boundaries (64 and 256
    tokens), and the full width; q_len 3 puts a row shorter than the chunk
    (fully masked early tokens) beside them.  Even rows map scattered
    pages (a copy per page); odd rows map two runs of consecutive pages
    that break at block 20, so a chunk is copied whole, or page by page
    where it spans the break or runs past the row's end."""
    page, n_logical, hd = 8, 37, 32
    clen = jnp.asarray([0, 1, page - 1, page, 63, 64, 65, 255, 256, 257,
                        n_logical * page], jnp.int32)
    b = clen.shape[0]
    n_pages = 1 + 2 + b * n_logical
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    kp = _rand(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = _rand(k2, (n_pages, page, kh, hd), jnp.float32)
    bt = _block_tables(np.random.RandomState(5), b, n_logical, n_pages,
                       n_shared=2)
    runs = np.concatenate([np.arange(20) + n_pages - 20,
                           np.arange(n_logical - 20) + 3])
    bt[1::2] = runs
    bt = jnp.asarray(bt)
    if pool == "bf16":
        st = kv_quant.get_strategy("exact")
        pools = {"k": kp.astype(jnp.bfloat16), "v": vp.astype(jnp.bfloat16)}
    else:
        st = kv_quant.get_strategy(pool)
        pools = st.make_pools(kp, vp)
    q = _rand(k3, (b, q_len, h, hd), jnp.float32)
    which = "multi" if q_len > 1 else "decode"
    fn = (ops.paged_multi_decode_attention if q_len > 1
          else ops.paged_decode_attention)
    qq = q if q_len > 1 else q[:, 0]
    got = fn(qq, pools["k"], pools["v"], bt, clen, window=window, fan=fan,
             impl="pallas_interpret", **st.scale_kwargs(pools))
    want = st.oracle(which, qq, pools, bt, clen, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=st.tol_self, atol=st.tol_self)
    assert np.all(np.asarray(got)[0] == 0)      # empty row → exact zeros


def test_paged_decode_chunk_pages_follow_the_shapes():
    """``fan=None`` picks CHUNK_TOKENS worth of pages from the page size,
    shrinks to the VMEM budget where pages are wide, and never exceeds
    the table; an explicit ``fan`` only meets the table bound."""
    from repro.kernels import decode_attention as DA
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert DA.chunk_pages(None, 257, 2, 8, 128, bf16, False) == 64
    assert DA.chunk_pages(None, 257, 2, 16, 128, bf16, False) == 32
    quant = DA.chunk_pages(None, 257, 4, 8, 128, jnp.int8, True)
    assert quant == 32 and 2 * quant * DA._page_vmem_bytes(
        4, 8, 128, jnp.int8, True) == DA.CHUNK_VMEM_BYTES
    assert DA.chunk_pages(None, 10, 2, 8, 128, bf16, False) == 10
    wide = DA.chunk_pages(None, 257, 8, 8, 512, f32, False)
    assert wide < 64
    assert 2 * wide * DA._page_vmem_bytes(8, 8, 512, f32, False) \
        <= DA.CHUNK_VMEM_BYTES
    assert DA.chunk_pages(5, 257, 2, 8, 128, bf16, False) == 5
    assert DA.chunk_pages(64, 37, 2, 8, 128, bf16, False) == 37
