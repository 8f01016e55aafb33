"""Sharding rules: divisibility fallbacks + spec coverage (no devices
needed — specs are pure functions of shapes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_smoke_config
from repro.distributed.sharding import fit_spec, param_specs
from repro.models import lm as lm_lib


class FakeMesh:
    """Duck-typed mesh: fit_spec only reads .axis_names and .shape."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


MESH = FakeMesh(pod=2, data=16, model=16)


@given(dim0=st.integers(1, 64), dim1=st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_fit_spec_always_divides(dim0, dim1):
    spec = fit_spec((dim0, dim1), P(("pod", "data"), "model"), MESH)
    for d, entry in zip((dim0, dim1), tuple(spec)):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([MESH.shape[n] for n in names]))
        assert d % size == 0


def test_fit_spec_truncates_composite_left_to_right():
    # 16 divides 'pod'*? no: pod*data=32 > 16 -> truncate to ('pod',)? 16%2==0
    spec = fit_spec((16,), P(("pod", "data")), MESH)
    assert tuple(spec) == ("pod",)
    # 32 takes the full composite
    spec = fit_spec((32,), P(("pod", "data")), MESH)
    assert tuple(spec) == (("pod", "data"),)


def test_fit_spec_drops_unknown_axes():
    mesh = FakeMesh(data=4)
    spec = fit_spec((8, 8), P("model", "data"), mesh)
    assert tuple(spec) == (None, "data")


def test_fit_spec_single_kv_head_drops_model():
    # MQA: 1 kv head can't shard over 16-way model axis
    spec = fit_spec((32, 1), P(None, "model"), MESH)
    assert tuple(spec) == (None, None)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_cover_all_leaves(arch):
    """Every parameter leaf gets a spec whose entries divide its dims
    (after fit) — the single mechanism that makes all archs lower."""
    cfg = get_smoke_config(arch)
    params = jax.eval_shape(
        lambda: lm_lib.init_lm(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, params, fsdp=True)
    n = len(jax.tree_util.tree_leaves(params))
    m = len(jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P)))
    assert n == m


def test_stacked_block_params_shift_right():
    cfg = get_smoke_config("gemma-2b")
    params = jax.eval_shape(
        lambda: lm_lib.init_lm(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, params, fsdp=False)
    # stacked (L, d, q_dim) attention wq: leading superblock dim unsharded
    wq_spec = specs["blocks"]["sub0"]["attn"]["wq"]
    assert tuple(wq_spec)[0] is None
    assert "model" in tuple(wq_spec)


def test_moe_experts_over_model_axis():
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    params = jax.eval_shape(
        lambda: lm_lib.init_lm(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, params, fsdp=False)
    up = specs["blocks"]["sub0"]["mlp"]["w_up"]
    # stacked (L, E, d, ff): experts (dim 1 after shift) over 'model' (EP)
    assert tuple(up)[1] == "model"
