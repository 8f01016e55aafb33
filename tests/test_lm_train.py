"""LM training-step invariants: microbatch accumulation, compression,
weighted objective, smoke-train convergence, xLSTM equivalence."""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import lm as lm_lib
from repro.optim import OptState, sgd
from repro.train.compression import init_state
from repro.train.steps import lm_train_step_fn, make_lm_train_step


def _setup(arch="starcoder2-3b", b=8, s=16):
    cfg = get_smoke_config(arch)
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                     cfg.vocab_size),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                      cfg.vocab_size),
        "weights": jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(3), (b,))),
    }
    return cfg, params, batch


def test_microbatch_accumulation_exact():
    """grad(sum_mb) == grad(full batch): microbatching is a pure memory
    lever, not an approximation (weights are global slices)."""
    cfg, params, batch = _setup()
    opt = sgd(0.1)
    s1 = lm_train_step_fn(cfg, opt, microbatches=1)
    s4 = lm_train_step_fn(cfg, opt, microbatches=4)
    p1, _, m1 = jax.jit(s1)(params, opt.init(params), batch)
    p4, _, m4 = jax.jit(s4)(params, opt.init(params), batch)
    l1 = jax.tree_util.tree_leaves(p1)
    l4 = jax.tree_util.tree_leaves(p4)
    for a, b_ in zip(l1, l4):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=2e-2, atol=2e-3)


def test_compressed_step_runs():
    cfg, params, batch = _setup()
    opt = sgd(0.05, momentum=0.9)
    step = make_lm_train_step(cfg, opt, compress_frac=0.05)
    cstate = init_state(params)
    p, o, cstate, m = step(params, opt.init(params), cstate, batch)
    assert np.isfinite(float(m["loss"]))


def test_smoke_lm_training_reduces_loss():
    """~50 steps on the structured token stream: loss must drop — the
    synthetic pipeline carries learnable signal."""
    from repro.data.tokens import TokenStream
    cfg = get_smoke_config("gemma-2b")
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    opt = sgd(0.3, momentum=0.9)
    step = jax.jit(lm_train_step_fn(cfg, opt))
    opt_state = opt.init(params)
    stream = TokenStream(seed=0, batch_per_shard=8, seq_len=32,
                         vocab=cfg.vocab_size)
    losses = []
    for i in range(50):
        params, opt_state, m = step(params, opt_state, stream.batch(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.2, (
        losses[:5], losses[-5:])


def test_selection_proxy_matches_autodiff():
    """lm.selection_proxy (closed-form head-input gradient) == autodiff
    d(mean-CE)/d(hidden) — the paper's last-layer trick is exact."""
    cfg = get_smoke_config("starcoder2-3b")
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    b, s = 3, 8
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                     cfg.vocab_size),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                      cfg.vocab_size),
    }
    proxy = lm_lib.selection_proxy(cfg, params, batch)
    assert proxy.shape == (b, cfg.d_model)

    from repro.models import common
    h, _, _ = lm_lib.forward(cfg, params, batch["tokens"], mode="train")
    # the paper's "last-layer gradient" = dL/d(head input), i.e. the
    # POST-norm hidden feeding the unembedding matmul
    hn = common.norm_apply(cfg, params["final_norm"], h).astype(jnp.float32)

    w_head = (params["embed"].T if cfg.tie_embeddings
              else params["lm_head"])

    def sum_ce(hh):
        logits = hh.astype(h.dtype) @ w_head
        logits = common.softcap(logits, cfg.logit_softcap)
        ce = lm_lib.token_ce(cfg, logits, batch["targets"])
        return jnp.sum(ce)

    g = jax.grad(sum_ce)(hn)                 # (b, s, d)
    want = jnp.mean(g, axis=1)               # mean over tokens
    np.testing.assert_allclose(np.asarray(proxy), np.asarray(want),
                               rtol=5e-2, atol=5e-3)


@given(t=st.sampled_from([32, 64]), chunk=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 20))
@settings(max_examples=8, deadline=None)
def test_mlstm_parallel_equals_serial(t, chunk, seed):
    from repro.models.xlstm import (_mlstm_chunk_scan,
                                    _mlstm_chunkwise_parallel)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    b, h, dk, dv = 2, 2, 8, 16
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    ig = jax.random.normal(ks[3], (b, t, h)) * 2
    fg = jax.random.normal(ks[4], (b, t, h)) * 2 + 1
    c0 = jax.random.normal(ks[5], (b, h, dk, dv)) * 0.1
    n0 = jnp.abs(jax.random.normal(ks[5], (b, h, dk))) * 0.1
    m0 = jnp.zeros((b, h))
    h1, s1 = _mlstm_chunk_scan(q, k, v, ig, fg, chunk, (c0, n0, m0))
    h2, s2 = _mlstm_chunkwise_parallel(q, k, v, ig, fg, chunk,
                                       (c0, n0, m0))
    np.testing.assert_allclose(h1, h2, rtol=5e-4, atol=5e-5)
    for a, b_ in zip(s1, s2):
        np.testing.assert_allclose(a, b_, rtol=5e-4, atol=5e-5)


def test_blockwise_attention_matches_dense():
    from repro.models import attention, common
    cfg = get_smoke_config("gemma2-9b").replace(
        flash_threshold=1, flash_block_q=16, flash_block_kv=16,
        n_heads=4, n_kv_heads=2, head_dim=16)
    b, s = 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, 4, 16))
    k = jax.random.normal(ks[1], (b, s, 2, 16))
    v = jax.random.normal(ks[2], (b, s, 2, 16))
    for causal, window in [(True, None), (False, None), (True, 24)]:
        blk = attention._attend_blockwise(cfg, q, k, v, causal=causal,
                                          window=window)
        if not causal:
            mask = None
        elif window is not None:
            mask = common.window_mask(s, s, 0, window)
        else:
            mask = common.causal_mask(s, s, 0)
        dense = attention._attend(cfg, q, k, v, mask)
        np.testing.assert_allclose(np.asarray(blk, np.float32),
                                   np.asarray(dense, np.float32),
                                   rtol=2e-3, atol=2e-3)
