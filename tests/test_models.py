"""Per-arch smoke tests (reduced configs) + decode/prefill consistency.

Every assigned architecture instantiates its REDUCED config and runs one
forward/train step on CPU asserting output shapes + no NaNs (the full
configs are exercised via the dry-run only).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, applicable_shapes, get_config, \
    get_smoke_config
from repro.models import lm as lm_lib
from repro.optim import global_norm, sgd
from repro.train.steps import lm_train_step_fn

B, S = 2, 16


def _batch(cfg, b=B, s=S):
    batch = {}
    if cfg.family == "audio":
        batch["embeds"] = jax.random.normal(
            jax.random.PRNGKey(9), (b, s, cfg.d_model), jnp.bfloat16)
    else:
        batch["tokens"] = jax.random.randint(
            jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    if cfg.family == "vlm":
        batch["vision"] = jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.vision.n_tokens,
                                    cfg.vision.d_embed), jnp.bfloat16)
    batch["targets"] = jax.random.randint(
        jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    loss, metrics = lm_lib.lm_loss(cfg, params, _batch(cfg))
    assert np.isfinite(float(loss)), arch
    # uniform-ish CE at init: ln(V) +- 2
    assert abs(float(metrics["ce"]) - np.log(cfg.vocab_size)) < 2.5, arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    # recurrent archs (sLSTM especially) are step-size sensitive
    lr = 1e-3 if arch in ("xlstm-1.3b", "zamba2-7b") else 0.05
    opt = sgd(lr, momentum=0.9)
    step = jax.jit(lm_train_step_fn(cfg, opt))
    opt_state = opt.init(params)
    batch = _batch(cfg)
    losses = []
    for _ in range(6):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), (arch, losses)
    assert min(losses[1:]) < losses[0], f"{arch}: loss never decreased " \
        f"{losses}"


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if not get_config(a).encoder_only])
def test_decode_matches_prefill(arch):
    """Greedy continuation: decode(prefill(x[:t])) logits == the full
    forward's logits at position t (teacher forcing) — the KV-cache /
    recurrent-state decode path is exact, not approximate."""
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        # GShard capacity dropping makes train-mode forward lossy at tiny
        # batch; open the capacity so the comparison is exact routing.
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    s_tot = 12
    batch = _batch(cfg, b=2, s=s_tot)
    toks = batch["tokens"]

    # full forward logits (teacher forcing)
    h, _, _ = lm_lib.forward(cfg, params, toks,
                             vision=batch.get("vision"), mode="train")
    full_logits = lm_lib._head_out(cfg, params, h)
    full_logits = lm_lib.mask_padded_logits(cfg, full_logits)

    # prefill on the first s0 tokens, decode the rest one-by-one
    s0 = 6
    lg, pstate = lm_lib.prefill_step(cfg, params, toks[:, :s0],
                                     vision=batch.get("vision"))
    from repro.launch.serve import _seat
    state = _seat(lm_lib.init_decode_state(cfg, 2, s_tot), pstate)
    np.testing.assert_allclose(
        np.asarray(lg, np.float32),
        np.asarray(full_logits[:, s0 - 1], np.float32),
        rtol=2e-2, atol=2e-2)

    for t in range(s0, s_tot):
        lg, state = lm_lib.decode_step(cfg, params, state, toks[:, t:t + 1],
                                       jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(lg, np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=5e-2, atol=5e-2, err_msg=f"{arch} pos {t}")


def test_weighted_loss_is_weighted_sum():
    """lm_loss with weights w == sum_i w_i * per-seq CE — the exact
    objective of paper Alg. 1 line 9."""
    cfg = get_smoke_config("starcoder2-3b")
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, b=4)
    losses = []
    for i in range(4):
        one = {k: v[i:i + 1] for k, v in batch.items()}
        _, m = lm_lib.lm_loss(cfg, params, one)
        losses.append(float(m["ce"]))
    w = jnp.array([0.4, 0.3, 0.2, 0.1])
    loss, _ = lm_lib.lm_loss(cfg, params, {**batch, "weights": w})
    np.testing.assert_allclose(float(loss),
                               float(jnp.sum(w * jnp.array(losses))),
                               rtol=2e-3)


@pytest.mark.parametrize("step", ["train", "decode"])
def test_xlstm_f32_contractions_are_highest(step):
    """Every f32 x f32 dot_general in the xLSTM cells asks for HIGHEST: at
    a TPU's default precision their operands would be rounded to bf16.
    The CPU computes f32 exactly either way, so this reads the lowered
    StableHLO instead of the numbers."""
    import re
    cfg = get_smoke_config("xlstm-1.3b")
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    if step == "train":
        fn = jax.grad(lambda p: lm_lib.lm_loss(cfg, p, batch)[0])
        lowered = jax.jit(fn).lower(params)
    else:
        state = lm_lib.init_decode_state(cfg, B, S)
        lowered = jax.jit(functools.partial(lm_lib.decode_step, cfg)).lower(
            params, state, batch["tokens"][:, :1], jnp.int32(0))
    dots = re.findall(r"stablehlo\.dot_general[^\n]*", lowered.as_text())
    f32 = [d for d in dots
           if re.search(r": \(tensor<[^>]*xf32>, tensor<[^>]*xf32>\)", d)]
    assert f32, "no f32 contraction lowered: nothing was checked"
    loose = [d[-160:] for d in f32 if "[HIGHEST, HIGHEST]" not in d]
    assert not loose, "\n".join(loose)


@pytest.mark.parametrize("seed", [0, 1])
def test_xlstm_gradient_does_not_grow_with_depth(seed):
    """At init, xlstm-1.3b's 48 blocks (at smoke width) have a step-0
    gradient norm within a few times that of one 8-block super-block (1.7x
    and 2.3x).  With the mLSTM input-gate bias at 0 it read 160x and 213x
    here, and at full width unclipped SGD diverged within six steps."""
    def grad_norm(superblocks):
        cfg = get_smoke_config("xlstm-1.3b").replace(
            n_superblocks=superblocks, n_layers=8 * superblocks)
        params = lm_lib.init_lm(cfg, jax.random.PRNGKey(seed))
        batch = _batch(cfg, s=64)
        grads = jax.jit(jax.grad(
            lambda p: lm_lib.lm_loss(cfg, p, batch)[0]))(params)
        return float(global_norm(grads))

    shallow, deep = grad_norm(1), grad_norm(6)
    assert np.isfinite(deep) and deep < 4 * shallow, (shallow, deep)


def test_all_cells_enumeration():
    """40 assigned cells; skips per DESIGN.md §5 (encoder decode, 500k on
    pure full-attention archs)."""
    from repro.configs import all_cells
    cells = all_cells()
    # 10 archs x 4 shapes = 40 raw; hubert loses decode_32k+long_500k,
    # 6 full-attn archs lose long_500k; gemma2 (local+global) keeps it.
    assert ("hubert-xlarge", "train_4k") in cells
    assert ("hubert-xlarge", "decode_32k") not in cells
    assert ("xlstm-1.3b", "long_500k") in cells
    assert ("zamba2-7b", "long_500k") in cells
    assert ("gemma2-9b", "long_500k") in cells
    assert ("gemma-2b", "long_500k") not in cells
    assert len(cells) == 32


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_param_count_plausible(arch):
    """Full configs carry the published parameter scale (sanity vs name)."""
    cfg = get_config(arch)
    n = cfg.param_count()
    expected = {
        "hubert-xlarge": (0.9e9, 1.3e9),
        "xlstm-1.3b": (1.0e9, 1.8e9),
        "gemma-2b": (2.0e9, 3.3e9),
        "gemma2-9b": (8e9, 11e9),
        "starcoder2-3b": (2.7e9, 3.8e9),
        "codeqwen1.5-7b": (6.5e9, 8.5e9),   # padded 92416-vocab embeddings
        # the assignment's dims (48L x 64e x d_ff 1408) arithmetically give
        # ~29B total / ~3B active; the published -16B name corresponds to a
        # shallower variant the assignment overrides.
        "moonshot-v1-16b-a3b": (25e9, 33e9),
        "qwen3-moe-30b-a3b": (26e9, 33e9),
        "zamba2-7b": (5e9, 8.5e9),
        "llama-3.2-vision-90b": (78e9, 95e9),
    }[arch]
    assert expected[0] < n < expected[1], (arch, n)
