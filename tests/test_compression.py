"""EF-TopK gradient compression: losslessness of the feedback loop."""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.optim import apply_updates, sgd
from repro.train.compression import (compress_with_feedback, init_state,
                                     topk_sparsify)


def test_topk_keeps_largest():
    x = jnp.array([[0.1, -5.0], [3.0, 0.01]])
    dense, vals, idx = topk_sparsify(x, 0.5)
    kept = np.asarray(dense).ravel()
    assert kept[1] == -5.0 and kept[2] == 3.0
    assert kept[0] == 0.0 and kept[3] == 0.0


@given(seed=st.integers(0, 50), frac=st.sampled_from([0.1, 0.25, 0.5]))
@settings(max_examples=10, deadline=None)
def test_feedback_conserves_mass(seed, frac):
    """compressed + residual == grad + old residual (nothing is lost)."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(seed), (32,))}
    st0 = init_state(g)
    comp, st1 = compress_with_feedback(g, st0, frac)
    np.testing.assert_allclose(
        np.asarray(comp["w"] + st1.residual["w"]),
        np.asarray(g["w"] + st0.residual["w"]), rtol=1e-6, atol=1e-7)


def test_compressed_sgd_still_converges():
    """EF-TopK at 10% density converges on a quadratic (delayed, not
    destroyed, gradient information).  Plain SGD: naive momentum on top of
    error feedback amplifies the delayed bursts (the reason DGC uses
    momentum *correction*) — documented in train/compression.py."""
    opt = sgd(0.05)
    params = {"x": jnp.zeros((64,))}
    target = jax.random.normal(jax.random.PRNGKey(1), (64,))
    state = opt.init(params)
    cstate = init_state(params)

    @jax.jit
    def step(params, state, cstate):
        g = jax.grad(lambda p: jnp.sum((p["x"] - target) ** 2))(params)
        g, cstate = compress_with_feedback(g, cstate, 0.1)
        u, state = opt.update(g, state, params)
        return apply_updates(params, u), state, cstate

    for _ in range(500):
        params, state, cstate = step(params, state, cstate)
    err = float(jnp.max(jnp.abs(params["x"] - target)))
    assert err < 0.05, err


def test_density_bound():
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (1000,))}
    comp, _ = compress_with_feedback(g, init_state(g), 0.01)
    nnz = int(jnp.sum(comp["w"] != 0))
    assert nnz <= 10
