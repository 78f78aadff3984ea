"""Pallas kernel sweeps: shapes x dtypes x k against the pure-jnp oracles
(interpret=True executes the kernel body on CPU), plus operator-property checks
of the blockwise compressor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.qsgd import qsgd_blocks
from repro.kernels.sign_topk import (
    BLOCK,
    BLOCK_ROWS,
    sign_topk_blocks,
    sign_topk_q,
    slab_rows,
)


@pytest.mark.parametrize("nb", [1, 2, 8, 16, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k_b", [1, 16, 128, 512])
def test_sign_topk_kernel_matches_oracle(nb, dtype, k_b):
    key = jax.random.PRNGKey(nb * 1000 + k_b)
    xh = jax.random.normal(key, (nb * BLOCK,), dtype)
    xe = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                 (nb * BLOCK,), dtype)
    for trig in (0.0, 1.0):
        q_k, xn_k, sc_k = sign_topk_blocks(
            xh.reshape(nb, BLOCK), xe.reshape(nb, BLOCK),
            jnp.float32(trig), k_b)
        q_r, xn_r, vals_r, idx_r = ref.sign_topk_ref(xh, xe,
                                                     jnp.float32(trig), k_b)
        np.testing.assert_allclose(
            np.array(q_k.reshape(-1), np.float32),
            np.array(q_r, np.float32), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(
            np.array(xn_k.reshape(-1), np.float32),
            np.array(xn_r, np.float32), rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), k_b=st.integers(1, BLOCK // 2))
def test_blockwise_signtopk_is_contraction(seed, k_b):
    """The TPU-adapted blockwise SignTopK still satisfies Definition 1 with
    omega >= 1/BLOCK per block (DESIGN.md §3)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (4 * BLOCK,))
    q, _, _, _ = ref.sign_topk_ref(x, jnp.zeros_like(x), jnp.float32(1.0), k_b)
    num = float(jnp.sum((x - q) ** 2))
    den = float(jnp.sum(x ** 2))
    assert num / den <= 1.0 - 1.0 / BLOCK + 1e-6


@pytest.mark.parametrize("nb", [1, 4, 16])
@pytest.mark.parametrize("s", [4, 16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qsgd_kernel_matches_oracle(nb, s, dtype):
    key = jax.random.PRNGKey(nb + s)
    x = jax.random.normal(key, (nb * BLOCK,), dtype)
    u = jax.random.uniform(jax.random.fold_in(key, 7), (nb * BLOCK,))
    out_k = qsgd_blocks(x.reshape(nb, BLOCK), u.reshape(nb, BLOCK), s=s)
    out_r = ref.qsgd_ref(x, u, s)
    np.testing.assert_allclose(np.array(out_k.reshape(-1), np.float32),
                               np.array(out_r, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_qsgd_kernel_unbiased():
    # s=64 keeps beta = min(d/s^2, sqrt(d)/s) = 0.25 so 256 draws average out
    x = jax.random.normal(jax.random.PRNGKey(0), (BLOCK,))
    outs = []
    for i in range(256):
        outs.append(ops.qsgd(x, jax.random.PRNGKey(i), s=64))
    mean = jnp.mean(jnp.stack(outs), 0)
    assert float(jnp.max(jnp.abs(mean - x))) < 0.15


def test_fused_trigger_semantics():
    x = jax.random.normal(jax.random.PRNGKey(1), (3 * BLOCK + 17,))
    xe = 0.5 * x
    sq = float(jnp.sum((x - xe) ** 2))
    q, xn, trig = ops.trigger_compress_update(x, xe, jnp.float32(sq * 2), 32)
    assert float(trig) == 0.0 and bool(jnp.all(q == 0))
    np.testing.assert_allclose(np.array(xn), np.array(xe), atol=1e-7)
    q, xn, trig = ops.trigger_compress_update(x, xe, jnp.float32(sq / 2), 32)
    assert float(trig) == 1.0 and int(jnp.sum(q != 0)) >= 32
    np.testing.assert_allclose(np.array(xn), np.array(xe + q), atol=1e-6)


def test_ops_sign_topk_ragged_length():
    """Flat wrapper pads to BLOCK multiples and un-pads the outputs."""
    d = 2500
    x = jax.random.normal(jax.random.PRNGKey(2), (d,))
    q, vals, idx = ops.sign_topk(x, 250)
    assert q.shape == (d,)
    assert int(jnp.sum(q != 0)) >= 250 - 3  # ties may add, padding never selects
    assert int(idx.max()) < 3 * BLOCK
    # support of q is among the largest |x| per block (threshold semantics)
    nz = np.nonzero(np.array(q))[0]
    assert len(nz) > 0


def test_sign_topk_fixed_seed_smoke():
    """Hypothesis-free smoke: fixed-seed contraction + support-size check for
    the blockwise kernel (regression for the suite silently skipping when
    hypothesis is absent)."""
    key = jax.random.PRNGKey(42)
    xh = jax.random.normal(key, (2, BLOCK))
    xe = jnp.zeros_like(xh)
    k_b = 32
    q, xn, _ = sign_topk_blocks(xh, xe, jnp.float32(1.0), k_b)
    q = q.reshape(-1)
    # Definition 1 contraction with the blockwise omega >= 1/BLOCK
    num = float(jnp.sum((xh.reshape(-1) - q) ** 2))
    den = float(jnp.sum(xh.reshape(-1) ** 2))
    assert num / den <= 1.0 - 1.0 / BLOCK + 1e-6
    # exactly k_b survivors per block (fixed normal draw: no |x| ties)
    assert int(jnp.sum(q != 0)) == 2 * k_b
    np.testing.assert_allclose(np.array(xn.reshape(-1)), np.array(q),
                               atol=1e-6)  # x_hat += q from x_hat = 0


def test_qsgd_fixed_seed_smoke():
    """Hypothesis-free smoke: qsgd_blocks quantizes onto the s-level grid and
    matches the jnp oracle on one fixed draw."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (1, BLOCK))
    u = jax.random.uniform(jax.random.fold_in(key, 1), (1, BLOCK))
    s = 16
    out = qsgd_blocks(x, u, s=s)
    ref_out = ref.qsgd_ref(x.reshape(-1), u.reshape(-1), s)
    np.testing.assert_allclose(np.array(out.reshape(-1), np.float32),
                               np.array(ref_out, np.float32),
                               rtol=1e-5, atol=1e-5)
    # levels are multiples of ||x||/s
    norm = float(jnp.linalg.norm(x))
    levels = np.array(jnp.abs(out.reshape(-1))) / (norm / s)
    np.testing.assert_allclose(levels, np.round(levels), atol=1e-4)


def test_xhat_update_closes_the_loop():
    """Iterating q = C(x - x_hat); x_hat += q drives x_hat -> x (error feedback
    contraction of the estimate — the property the consensus proof leans on)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2 * BLOCK,))
    xe = jnp.zeros_like(x)
    errs = []
    for _ in range(30):
        q, xe, _ = ops.trigger_compress_update(x, xe, jnp.float32(0.0), 64)
        errs.append(float(jnp.linalg.norm(x - xe) / jnp.linalg.norm(x)))
    assert errs[-1] < 0.05
    # strict=False is deliberate: consecutive-pairs idiom — errs[1:] is one
    # shorter than errs by construction, the zip stops at the short side.
    assert all(b <= a + 1e-6 for a, b in zip(errs, errs[1:], strict=False))


# --------------------------------------------------- compiled-lowering legs

LEGS = ("interpret", "xla")


def _mixed_tiles(key, n, dtype):
    """(n, BLOCK) x_half and x_hat whose differences hold, besides random
    tiles, tiles of one |diff| (every lane tied), all-zero tiles and tiles
    with fewer than 102 nonzero lanes."""
    xh = jax.random.normal(key, (n, BLOCK), dtype)
    xe = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (n, BLOCK),
                                 dtype)
    row = jnp.arange(n)[:, None]
    lane = jnp.arange(BLOCK)[None, :]
    tied = jnp.where(lane % 3 == 0, 1.5, -1.5).astype(dtype)
    xh = jnp.where(row % 5 == 1, tied, xh)
    xe = jnp.where((row % 5 == 1) | (row % 5 == 2), 0, xe)
    xh = jnp.where(row % 5 == 2, jnp.where(lane % 16 == 0, xh, 0), xh)
    xe = jnp.where(row % 5 == 3, xh, xe)
    return xh, xe


@pytest.mark.parametrize("n_tiles", [8, 13, 2 * BLOCK_ROWS,
                                     2 * BLOCK_ROWS + 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sign_topk_legs_bit_equal_to_oracle(dtype, n_tiles):
    """The compiled XLA leg and the Pallas interpreter run the IDENTICAL
    per-row f32 block math, so all three (interpret, xla, ref.py) must be
    BIT-equal — not close — for q, x_hat_new and the scales, f32 and bf16,
    in one slab (8, 13 tiles), in whole slabs of BLOCK_ROWS, and in shorter
    slabs where BLOCK_ROWS does not divide the tile count; with tied,
    all-zero and sparse tiles among the random ones."""
    xh, xe = _mixed_tiles(jax.random.PRNGKey(11), n_tiles, dtype)
    q_r, xn_r, _, _ = ref.sign_topk_ref(xh.reshape(-1), xe.reshape(-1),
                                        jnp.float32(1.0), 102)
    q_r = np.asarray(q_r.astype(dtype)).reshape(n_tiles, BLOCK)
    # trig = 1: every selected lane of q is +-scale, so a row's scale is its
    # largest |q| (0 where nothing is selected)
    sc_r = np.abs(q_r.astype(np.float32)).max(axis=1)
    for leg in LEGS:
        q, xn, sc = sign_topk_blocks(xh, xe, jnp.float32(1.0), 102,
                                     lowering=leg)
        np.testing.assert_array_equal(np.asarray(q), q_r)
        np.testing.assert_array_equal(np.asarray(xn.reshape(-1)),
                                      np.asarray(xn_r.astype(dtype)))
        assert sc.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(sc.astype(dtype)).astype(np.float32), sc_r)
    # the zero, tied and sparse tiles took their paths: nothing selected,
    # exactly 102 lowest-index ties, all 64 nonzero lanes
    nnz = (q_r != 0).sum(axis=1)
    assert (nnz[3::5] == 0).all()
    assert (nnz[1::5] == 102).all()
    assert (np.flatnonzero(q_r[1]) == np.arange(102)).all()
    assert (nnz[2::5] == BLOCK // 16).all()


@pytest.mark.parametrize("n_tiles", [8, 13, 2 * BLOCK_ROWS,
                                     2 * BLOCK_ROWS + 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sign_topk_q_bit_equal_to_blocks(dtype, n_tiles):
    """The q-only entry (no x_hat input, no x_hat + q output) gives
    sign_topk_blocks's q and scales bit for bit on every leg, from the
    difference alone: x_half - 0 with trig 1, on the same tied, zero and
    sparse tiles."""
    x, _ = _mixed_tiles(jax.random.PRNGKey(11), n_tiles, dtype)
    for leg in LEGS:
        q_b, _, sc_b = sign_topk_blocks(x, jnp.zeros_like(x),
                                        jnp.float32(1.0), 102, lowering=leg)
        q, sc = sign_topk_q(x, 102, lowering=leg)
        assert q.dtype == x.dtype and sc.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_b))
        np.testing.assert_array_equal(np.asarray(sc), np.asarray(sc_b))


@pytest.mark.parametrize("n_tiles", [1, 13, BLOCK_ROWS, 2 * BLOCK_ROWS,
                                     2 * BLOCK_ROWS + 8, 4727 * 128])
def test_slab_rows_divides_the_tile_count(n_tiles):
    """One slab holds all tiles up to BLOCK_ROWS; above, the slab is the
    tallest multiple of 8 up to BLOCK_ROWS that divides the count."""
    rows = slab_rows(n_tiles)
    assert n_tiles % rows == 0
    if n_tiles <= BLOCK_ROWS:
        assert rows == n_tiles
    else:
        assert rows % 8 == 0 and rows <= BLOCK_ROWS
        assert not any(n_tiles % r == 0
                       for r in range(rows + 8, BLOCK_ROWS + 1, 8))


def test_qsgd_legs_bit_equal_to_oracle():
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (4, BLOCK))
    u = jax.random.uniform(jax.random.fold_in(key, 1), (4, BLOCK))
    want = np.asarray(ref.qsgd_ref(x.reshape(-1), u.reshape(-1), 16))
    for leg in LEGS:
        got = qsgd_blocks(x, u, s=16, lowering=leg)
        np.testing.assert_array_equal(np.asarray(got.reshape(-1)), want)


def test_payload_reconstructs_exactly_under_ties():
    """Regression (tie-truncated payload): with constant |diff| every lane
    ties at the threshold, the exact-k rule keeps the k lowest-index lanes
    per tile, and scatter(vals, idx) must rebuild q EXACTLY — the old
    globally-sorted payload dropped tied entries and reconstruction lost
    mass silently."""
    d, k = 2048, 256
    signs = jnp.where(jnp.arange(d) % 3 == 0, 1.0, -1.0)
    flat = 7.0 * signs          # every |entry| identical: maximal tie stress
    for leg in LEGS:
        q, vals, idx = ops.sign_topk(flat, k, lowering=leg)
        assert vals.shape == idx.shape == (2 * (k // 2),)
        rebuilt = jnp.zeros((2 * BLOCK,), q.dtype).at[idx].set(vals)[:d]
        np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(q))
        assert int(jnp.sum(q != 0)) == k   # exact-k, ties broken by index


def test_payload_reconstructs_on_random_irregular_lengths():
    for seed, (d, k) in enumerate([(1, 1), (1023, 100), (1025, 64),
                                   (2500, 250), (3089, 123)]):
        flat = jax.random.normal(jax.random.PRNGKey(seed), (d,))
        q, vals, idx = ops.sign_topk(flat, k)
        nb = max(1, -(-d // BLOCK))
        rebuilt = jnp.zeros((nb * BLOCK,), q.dtype).at[idx].set(vals)[:d]
        np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(q))


def test_padded_tail_tile_emits_zero():
    """Regression (padded tail): at non-multiple-of-1024 lengths the last
    tile is mostly zero padding; the old kernel's thr=0 path selected the
    ENTIRE tile (padding included) and emitted +scale on every padded lane.
    Pin: the kernel equals the unpadded oracle and the padding region of the
    padded buffer stays identically zero, on both legs."""
    for d in (1, 1023, 1025, 2500, 3089):
        flat = jax.random.normal(jax.random.PRNGKey(d), (d,))
        nb = max(1, -(-d // BLOCK))
        k_b = 50 if d > 64 else 1
        xb = jnp.pad(flat, (0, nb * BLOCK - d)).reshape(nb, BLOCK)
        for leg in LEGS:
            q, _, _ = sign_topk_blocks(xb, jnp.zeros_like(xb),
                                       jnp.float32(1.0), k_b, lowering=leg)
            q = q.reshape(-1)
            assert not np.any(np.asarray(q[d:])), \
                f"padding emitted nonzeros at d={d} leg={leg}"
            # tail-tile support comes only from real entries
            tail = q[(nb - 1) * BLOCK:]
            real = min(d - (nb - 1) * BLOCK, BLOCK)
            assert int(jnp.sum(tail != 0)) <= min(k_b, real)


def test_trigger_zero_is_exact_identity():
    """trig = 0 must make q EXACTLY zero and x_hat_new EXACTLY x_hat (not
    approximately — the event-trigger contract is a bit-level no-op)."""
    for d in (BLOCK, 2500):
        x = jax.random.normal(jax.random.PRNGKey(d), (d,))
        xe = 0.5 * x
        for leg in LEGS:
            q, xn, trig = ops.trigger_compress_update(
                x, xe, jnp.float32(1e12), 64, lowering=leg)
            assert float(trig) == 0.0
            assert not np.any(np.asarray(q))
            np.testing.assert_array_equal(np.asarray(xn), np.asarray(xe))


def test_all_zero_input_is_silent():
    """|diff| == 0 everywhere: the zero-lane rule keeps the support empty
    (no division blowup, no spurious +scale messages)."""
    xb = jnp.zeros((2, BLOCK))
    for leg in LEGS:
        q, xn, sc = sign_topk_blocks(xb, xb, jnp.float32(1.0), 128,
                                     lowering=leg)
        assert not np.any(np.asarray(q))
        assert not np.any(np.asarray(sc))
        np.testing.assert_array_equal(np.asarray(xn), np.asarray(xb))


def test_exact_k_support_matches_top_k():
    """The selected index set per block equals jax.lax.top_k's (restricted
    to nonzero lanes): exactly k_b survivors on tie-free draws, and the
    support is contained in top_k's under ties."""
    k_b = 37
    x = jax.random.normal(jax.random.PRNGKey(5), (4, BLOCK))
    q, _, _ = sign_topk_blocks(x, jnp.zeros_like(x), jnp.float32(1.0), k_b)
    _, want_idx = jax.lax.top_k(jnp.abs(x), k_b)
    for r in range(4):
        got = set(np.flatnonzero(np.asarray(q[r])).tolist())
        assert got == set(np.asarray(want_idx[r]).tolist())


@pytest.mark.parametrize("n, d", [(4, 2 * BLOCK + 300),
                                  (2, (BLOCK_ROWS // 2) * BLOCK + 300)])
def test_ensemble_matches_per_row_wrapper(n, d):
    """sign_topk_ensemble (ONE dispatch over all nodes' tiles) must be
    bit-equal to running trigger_compress_update row by row: in one slab,
    and where the stacked tiles pass one slab and each node's row is
    padded until they fill whole slabs."""
    diff = jax.random.normal(jax.random.PRNGKey(9), (n, d))
    for leg in LEGS:
        q_ens = ops.sign_topk_ensemble(diff, 13, lowering=leg)
        assert q_ens.shape == (n, d)
        for r in range(n):
            q_row, _, _ = ops.trigger_compress_update(
                diff[r], jnp.zeros((d,)), jnp.float32(0.0), 13, lowering=leg)
            np.testing.assert_array_equal(np.asarray(q_ens[r]),
                                          np.asarray(q_row))


def test_legs_bit_equal_bf16_ragged():
    """bf16 + irregular length + both legs: the f32-internal contract keeps
    interpret and xla bit-identical even when storage is bf16."""
    d = 3089
    x = jax.random.normal(jax.random.PRNGKey(21), (d,), jnp.bfloat16)
    outs = [ops.sign_topk(x, 200, lowering=leg) for leg in LEGS]
    for a, b in zip(outs[0], outs[1], strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("sizes", [[5, 0, 130, 7], [0, 0, 0, 300]])
def test_gmm_legs_agree_and_follow_the_tiles(sizes):
    """The grouped product's Pallas kernel (in the interpreter) and its
    XLA leg give the same products and gradients in the rows and the
    matrices, up to bfloat16's rounding of float32 sums taken in another
    order; each active tile is its group's product, a group with no rows
    still owns a tile, and the tiles past n_active are zero."""
    from repro.kernels import gmm as G
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = 8 * G.TILE_M
    first, tile_group, n_active = G.layout(sizes, rows)
    live = np.zeros(rows, bool)
    for o, s in zip(np.asarray(first), np.asarray(sizes)):
        live[o:o + s] = True
    lhs = jnp.where(jnp.asarray(live)[:, None], jax.random.normal(
        jax.random.PRNGKey(0), (rows, 64)), 0.0).astype(jnp.bfloat16)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 96)
                            ).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(2), (rows, 96))

    def run(leg):
        def f(a, b):
            out = G.gmm(a, b, tile_group, n_active, lowering=leg)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1),
                                             has_aux=True)(lhs, rhs)
        return out, grads

    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=2 ** -8 * np.abs(b).max())

    out_x, g_x = run("xla")
    out_i, g_i = run("interpret")
    close(out_x, out_i)
    for a, b in zip(g_x, g_i):
        close(a, b)
    na = int(n_active[0])
    assert na == int(np.maximum(1, -(-np.asarray(sizes) // G.TILE_M)).sum())
    want = np.zeros((rows, 96), np.float32)
    L, R = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    for i in range(na):
        sl = slice(i * G.TILE_M, (i + 1) * G.TILE_M)
        want[sl] = L[sl] @ R[int(tile_group[i])]
    np.testing.assert_allclose(np.asarray(out_i, np.float32), want,
                               atol=0.02 * np.abs(want).max())
    assert not np.asarray(out_i, np.float32)[na * G.TILE_M:].any()
