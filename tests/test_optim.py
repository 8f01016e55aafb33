"""Optimizers + schedules (from-scratch implementations)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adamw, apply_updates, cosine_annealing,
                         cosine_with_warmup, constant, global_norm, sgd)


def _quadratic(a=3.0):
    def loss(p):
        return jnp.sum((p["x"] - a) ** 2) + jnp.sum((p["y"] + 1.0) ** 2)
    return loss


def _run(opt, steps=200, dtype=jnp.float32):
    loss = _quadratic()
    params = {"x": jnp.zeros((4,), dtype), "y": jnp.ones((2,), dtype)}
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        g = jax.grad(loss)(params)
        u, state = opt.update(g, state, params)
        return apply_updates(params, u), state

    for _ in range(steps):
        params, state = step(params, state)
    return params, float(loss(params))


def test_sgd_momentum_converges():
    _, l = _run(sgd(0.05, momentum=0.9))
    assert l < 1e-4


def test_sgd_plain_converges():
    _, l = _run(sgd(0.1))
    assert l < 1e-3


def test_nesterov_converges():
    _, l = _run(sgd(0.05, momentum=0.9, nesterov=True))
    assert l < 1e-4


def test_adamw_converges():
    _, l = _run(adamw(0.05, weight_decay=0.0))
    assert l < 1e-3


def test_bf16_params_f32_master():
    """bf16 params train with f32 momentum (mixed-precision master)."""
    opt = sgd(0.05, momentum=0.9)
    params, l = _run(opt, dtype=jnp.bfloat16)
    assert params["x"].dtype == jnp.bfloat16
    assert l < 0.05  # bf16 resolution-limited
    state = opt.init({"x": jnp.zeros((4,), jnp.bfloat16),
                      "y": jnp.zeros((2,), jnp.bfloat16)})
    assert state.slots["x"].dtype == jnp.float32


def test_weight_decay_shrinks():
    opt = sgd(0.1, weight_decay=0.5)
    p = {"w": jnp.ones((4,))}
    s = opt.init(p)
    g = {"w": jnp.zeros((4,))}
    u, s = opt.update(g, s, p)
    p2 = apply_updates(p, u)
    assert float(p2["w"][0]) < 1.0


def test_clip_norm():
    opt = sgd(1.0, clip_norm=1.0)
    p = {"w": jnp.zeros((4,))}
    s = opt.init(p)
    g = {"w": jnp.full((4,), 100.0)}
    u, _ = opt.update(g, s, p)
    np.testing.assert_allclose(global_norm(u), 1.0, rtol=1e-5)


def test_cosine_annealing_endpoints():
    f = cosine_annealing(0.01, 100)
    assert abs(float(f(jnp.int32(0))) - 0.01) < 1e-8
    assert float(f(jnp.int32(100))) < 1e-8
    assert 0 < float(f(jnp.int32(50))) < 0.01


def test_cosine_with_warmup():
    f = cosine_with_warmup(0.01, 10, 110, final_scale=0.1)
    assert float(f(jnp.int32(0))) == 0.0
    np.testing.assert_allclose(float(f(jnp.int32(10))), 0.01, rtol=1e-5)
    assert float(f(jnp.int32(110))) >= 0.00099


def test_step_counter_advances():
    opt = sgd(constant(0.1))
    p = {"w": jnp.zeros((2,))}
    s = opt.init(p)
    for i in range(3):
        _, s = opt.update({"w": jnp.ones((2,))}, s, p)
    assert int(s.step) == 3


@pytest.mark.parametrize("make", [lambda: sgd(0.1, momentum=0.9),
                                  lambda: adamw(0.1)])
def test_state_slots_take_the_parameter_sharding(make):
    """f32 slots are created where their parameter lives: a sharded
    parameter never has its whole slot on one device."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, PartitionSpec("data"))
    p = {"w": jax.device_put(jnp.ones((8,), jnp.bfloat16), sh)}
    for slot in jax.tree_util.tree_leaves(make().init(p).slots):
        assert slot.sharding == sh
        assert slot.dtype == jnp.float32
