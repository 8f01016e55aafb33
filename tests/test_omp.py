"""OMP solver correctness + the paper's theoretical invariants (Thm 2/3),
plus incremental-vs-dense parity (the dense solver is the reference the
production incremental path must reproduce, see DESIGN.md §2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.omp import (_nnls_active, matching_error, omp_select,
                            omp_select_dense, omp_select_per_class,
                            split_budget)
from repro.kernels import ops
from repro.kernels.ref import PRECISION


def _k(i):
    return jax.random.PRNGKey(i)


def test_recovers_planted_support():
    """Target = positive combo of 5 rows of an incoherent G -> OMP finds
    exactly those rows."""
    g = jax.random.normal(_k(0), (200, 128))
    g = g / jnp.linalg.norm(g, axis=1, keepdims=True)
    support = jnp.array([3, 50, 77, 120, 199])
    w_true = jnp.array([1.0, 2.0, 0.5, 1.5, 3.0])
    target = w_true @ g[support]
    idx, w, mask, err = omp_select(g, target, k=5, lam=1e-6)
    assert set(np.asarray(idx).tolist()) == set(np.asarray(support).tolist())
    assert float(err) < 1e-3


def test_error_monotone_in_k():
    """E_lambda(X_k) is non-increasing as the budget k grows (greedy
    chain property of Alg. 2)."""
    g = jax.random.normal(_k(1), (100, 64))
    target = jnp.sum(g[:30], axis=0)
    errs = [float(omp_select(g, target, k=k, lam=0.1)[3])
            for k in (1, 2, 4, 8, 16, 32)]
    for a, b in zip(errs, errs[1:]):
        assert b <= a + 1e-5, errs


def test_weights_nonnegative_and_masked():
    g = jax.random.normal(_k(2), (64, 32))
    target = jnp.sum(g, axis=0)
    idx, w, mask, _ = omp_select(g, target, k=10, lam=0.5)
    assert bool(jnp.all(w >= 0))
    assert bool(jnp.all(jnp.where(~mask, w == 0, True)))
    assert bool(jnp.all(jnp.where(~mask, idx == -1, idx >= 0)))


def test_eps_stopping_short_circuits():
    """If 2 rows reconstruct the target exactly, slots 3.. stay unused."""
    g = jax.random.normal(_k(3), (50, 40))
    target = g[7] * 2.0 + g[31] * 1.0
    idx, w, mask, err = omp_select(g, target, k=10, lam=1e-8, eps=1e-6)
    assert int(jnp.sum(mask)) <= 4  # 2 needed; tiny slack for regularizer
    assert float(err) < 1e-4


def test_no_duplicate_selections():
    g = jax.random.normal(_k(4), (30, 16))
    target = jnp.sum(g, axis=0)
    idx, w, mask, _ = omp_select(g, target, k=20, lam=0.5)
    sel = np.asarray(idx)[np.asarray(mask)]
    assert len(sel) == len(set(sel.tolist()))


@pytest.mark.parametrize("method", ["incremental", "dense"])
def test_no_duplicate_when_last_candidate_selected(method):
    """Regression: candidate n-1 selected early must stay masked out of
    later rounds (the taken-mask scatter once used n-1 as its sentinel,
    racing duplicate writes).  Few NNLS iters keep the residual correlated
    with the taken row, which is what exposed the race."""
    g = jax.random.normal(_k(44), (12, 8))
    target = g[11] * 5.0 + jnp.sum(g, axis=0) * 0.1
    idx, w, mask, _ = omp_select(g, target, k=6, nnls_iters=2,
                                 method=method)
    sel = np.asarray(idx)[np.asarray(mask)]
    assert len(sel) == len(set(sel.tolist())), sel


def test_valid_mask_respected():
    g = jax.random.normal(_k(5), (60, 32))
    valid = jnp.arange(60) < 20
    target = jnp.sum(g[:20], axis=0)
    idx, w, mask, _ = omp_select(g, target, k=10, valid=valid)
    sel = np.asarray(idx)[np.asarray(mask)]
    assert (sel < 20).all()


def test_matching_error_decreases_vs_random():
    """OMP's Err is far below a random subset of the same size (paper
    Table 9 ordering)."""
    g = jax.random.normal(_k(6), (256, 64))
    target = jnp.sum(g, axis=0)
    idx, w, mask, _ = omp_select(g, target, k=32, lam=0.1)
    e_omp = float(matching_error(g, target, idx, w, mask))
    ridx = jax.random.permutation(_k(7), 256)[:32].astype(jnp.int32)
    rmask = jnp.ones((32,), bool)
    rw = jnp.full((32,), float(256 / 32), jnp.float32)  # unbiased scaling
    e_rand = float(matching_error(g, target, ridx, rw, rmask))
    assert e_omp < e_rand


def test_per_class_selects_within_class():
    g = jax.random.normal(_k(8), (120, 32))
    labels = jnp.arange(120) % 3
    onehot = jax.nn.one_hot(labels, 3, dtype=g.dtype)
    targets = onehot.T @ g
    idx, w, mask = omp_select_per_class(g, labels, targets, 3, 5)
    idx_np, mask_np = np.asarray(idx), np.asarray(mask)
    lab_np = np.asarray(labels)
    for c in range(3):
        block = idx_np[c * 5:(c + 1) * 5]
        bm = mask_np[c * 5:(c + 1) * 5]
        assert (lab_np[block[bm]] == c).all()


def _class_pool(seed, sizes, outside=0, d=24):
    """A shuffled pool with ``sizes[c]`` rows of class c and ``outside``
    rows labelled -1 or C (in no class); targets by the one-hot
    contraction ``gradmatch_per_class`` uses."""
    rng = np.random.default_rng(seed)
    labels = np.concatenate([np.repeat(np.arange(len(sizes)), sizes),
                             rng.choice([-1, len(sizes)], outside)])
    labels = jnp.asarray(rng.permutation(labels))
    g = jax.random.normal(_k(seed), (labels.shape[0], d))
    onehot = jax.nn.one_hot(labels, len(sizes), dtype=g.dtype)
    return g, labels, jnp.dot(onehot.T, g, precision=PRECISION)


def _per_class_whole_pool(g, labels, targets, num_classes, k, quotas,
                          lam=0.5, eps=1e-10, nnls_iters=50):
    """The whole-pool formulation: each class problem scores all n rows
    with the other classes masked invalid, then the same quota truncation
    and exact reweight."""
    k_run = k if quotas is None else int(max(quotas))
    slot = jnp.arange(k_run, dtype=jnp.int32)

    def one_class(c, target, quota):
        valid = labels == c
        idx, w, mask, _ = omp_select(g, target, k=k_run, lam=lam, eps=eps,
                                     valid=valid)
        # Rounds past the class's last row pick a row outside it: dropped.
        mask = mask & (slot < jnp.sum(valid))
        if quotas is not None:
            mask = mask & (slot < quota)
            sel = jnp.where(mask, idx, 0)
            g_s = g[sel] * mask[:, None].astype(g.dtype)
            gram = jnp.dot(g_s, g_s.T, precision=PRECISION)
            corr = jnp.dot(g_s, target, precision=PRECISION)
            w = _nnls_active(gram, corr, mask, lam, nnls_iters)
        return jnp.where(mask, idx, -1), jnp.where(mask, w, 0.0), mask

    q = jnp.asarray(np.zeros(num_classes) if quotas is None else quotas,
                    jnp.int32)
    idx, w, mask = jax.vmap(one_class)(jnp.arange(num_classes), targets, q)
    return idx.reshape(-1), w.reshape(-1), mask.reshape(-1)


# (class sizes, rows in no class, budget, ops backend, proxy width d); the
# budget is a global k split by split_budget, an explicit quota list, or
# ("k_per_class", k) for the quotas=None branch.
PER_CLASS_PARITY = {
    "shuffled": ([40, 40, 40], 0, 30, "ref", 24),
    "unequal_sizes": ([4, 30, 50, 16], 0, 40, "ref", 24),
    "empty_class": ([30, 0, 45], 0, 24, "ref", 24),
    "labels_outside": ([30, 25, 35], 20, 27, "ref", 24),
    "quota_over_class": ([3, 20, 20], 0, [6, 5, 5], "ref", 24),
    "no_quotas": ([40, 40, 40], 0, ("k_per_class", 8), "ref", 24),
    "no_quotas_empty_outside": ([30, 0, 25, 35], 10, ("k_per_class", 6),
                                "ref", 24),
    "pallas_interpret": ([12, 20, 9], 5, 15, "interpret", 40),
}


@pytest.mark.parametrize("case", sorted(PER_CLASS_PARITY))
def test_per_class_matches_whole_pool_formulation(case):
    """Solving each class on its own rows picks and weighs exactly what
    solving it on the whole pool with the other classes masked does."""
    sizes, outside, budget, backend, d = PER_CLASS_PARITY[case]
    g, labels, targets = _class_pool(sum(sizes) + outside, sizes, outside,
                                     d)
    C = len(sizes)
    k, quotas = 0, None
    if isinstance(budget, tuple):
        k = budget[1]
    elif isinstance(budget, list):
        quotas = budget
    else:
        quotas = split_budget(budget, sizes).tolist()
    ops.set_backend(backend)
    try:
        got = omp_select_per_class(g, labels, targets, C, k, quotas=quotas)
        want = _per_class_whole_pool(g, labels, targets, C, k, quotas)
    finally:
        ops.set_backend(None)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    if quotas is not None:
        # The reweight solves from the picked rows alone: bit for bit.
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
    else:
        # The solver's own weights come from its cached correlations,
        # which a per-class matrix-vector product rounds in another order
        # than one pool-wide matrix product: f32 rounding apart.
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-5, atol=1e-6)
    idx, mask = np.asarray(got[0]), np.asarray(got[2])
    assert mask.any()
    lab = np.asarray(labels)
    per_class = idx.reshape(C, -1)
    for c in range(C):
        picked = per_class[c][per_class[c] >= 0]
        assert (lab[picked] == c).all()


# ---------------------------------------------------------------------------
# incremental vs dense reference parity (DESIGN.md §2)
# ---------------------------------------------------------------------------

PARITY_SHAPES = [
    (200, 32, 24),    # narrow regime (d < k): residual scoring
    (100, 256, 16),   # wide regime (k < d): column-cache scoring
    (64, 16, 40),     # k > n/2, heavy masking
    (300, 128, 150),  # crosses the wide->narrow regime boundary
]


@pytest.mark.parametrize("n,d,k", PARITY_SHAPES)
@pytest.mark.parametrize("lam", [1e-6, 0.3])
def test_incremental_matches_dense(n, d, k, lam):
    """The cached-correlation solver must reproduce the dense reference's
    selections exactly and its weights/err to f32 tolerance."""
    g = jax.random.normal(_k(n + d + k), (n, d))
    target = jnp.sum(g, axis=0)
    i1, w1, m1, e1 = omp_select(g, target, k=k, lam=lam)
    i2, w2, m2, e2 = omp_select_dense(g, target, k=k, lam=lam)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(e1), float(e2), rtol=1e-4, atol=1e-5)


def test_incremental_matches_dense_valid_mask():
    g = jax.random.normal(_k(77), (120, 48))
    valid = jax.random.bernoulli(_k(78), 0.4, (120,))
    target = jnp.sum(jnp.where(valid[:, None], g, 0.0), axis=0)
    i1, w1, m1, e1 = omp_select(g, target, k=16, lam=0.2, valid=valid)
    i2, w2, m2, e2 = omp_select_dense(g, target, k=16, lam=0.2, valid=valid)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-5)


def test_incremental_matches_dense_negative_scores():
    """positive=False (|scores| selection) parity."""
    g = jax.random.normal(_k(79), (150, 32))
    target = -jnp.sum(g[:40], axis=0)   # anti-aligned target
    i1, w1, m1, e1 = omp_select(g, target, k=12, lam=0.1, positive=False)
    i2, w2, m2, e2 = omp_select_dense(g, target, k=12, lam=0.1,
                                      positive=False)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-5)


def test_incremental_block_size_invariant():
    """The blocked prefix growth is an implementation detail: any block
    size must yield the same selection."""
    g = jax.random.normal(_k(80), (128, 24))
    target = jnp.sum(g, axis=0)
    ref = omp_select(g, target, k=33, lam=0.2, block=128)
    for block in (1, 7, 33, 64):
        got = omp_select(g, target, k=33, lam=0.2, block=block)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(ref[0]))
        np.testing.assert_allclose(got[1], ref[1], rtol=1e-4, atol=1e-6)


def test_per_class_incremental_matches_dense():
    """The vmapped per-class decomposition agrees between solvers."""
    g = jax.random.normal(_k(81), (120, 32))
    labels = jnp.arange(120) % 3
    onehot = jax.nn.one_hot(labels, 3, dtype=g.dtype)
    targets = onehot.T @ g
    i1, w1, m1 = omp_select_per_class(g, labels, targets, 3, 8)
    i2, w2, m2 = omp_select_per_class(g, labels, targets, 3, 8,
                                      method="dense")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-5)


def test_incremental_eps_stop_matches_dense():
    """Exact 2-row target: both solvers stop at the same round."""
    g = jax.random.normal(_k(82), (50, 40))
    target = g[7] * 2.0 + g[31] * 1.0
    i1, w1, m1, e1 = omp_select(g, target, k=10, lam=1e-8, eps=1e-6)
    i2, w2, m2, e2 = omp_select_dense(g, target, k=10, lam=1e-8, eps=1e-6)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_matching_error_consistent_with_solver_err():
    """matching_error is the squared paper objective — it must equal the
    err the solver tracks internally (both formulations)."""
    g = jax.random.normal(_k(83), (90, 40))
    target = jnp.sum(g, axis=0)
    for method in ("incremental", "dense"):
        idx, w, mask, err = omp_select(g, target, k=12, lam=0.3,
                                       method=method)
        ext = matching_error(g, target, idx, w, mask, lam=0.3)
        np.testing.assert_allclose(float(ext), float(err), rtol=1e-4,
                                   atol=1e-5)


def test_lambda_regularizes_weights():
    """Larger lambda -> smaller ||w||^2 (Fig. 4g mechanism)."""
    g = jax.random.normal(_k(9), (80, 48))
    target = jnp.sum(g, axis=0)
    norms = []
    for lam in (1e-4, 0.5, 50.0):
        _, w, _, _ = omp_select(g, target, k=16, lam=lam)
        norms.append(float(jnp.sum(w ** 2)))
    assert norms[0] >= norms[1] >= norms[2]
