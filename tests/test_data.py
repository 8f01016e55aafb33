"""Data substrate: determinism, restartability, imbalance protocol."""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.data.loader import SubsetLoader
from repro.data.synthetic import make_classification, make_imbalanced, split
from repro.data.tokens import TokenStream, token_batch


# ---------------------------------------------------------------------------
# Token stream
# ---------------------------------------------------------------------------

def test_token_batch_deterministic():
    a = token_batch(0, step=7, shard=2, batch=4, seq_len=32, vocab=100)
    b = token_batch(0, step=7, shard=2, batch=4, seq_len=32, vocab=100)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["targets"], b["targets"])


def test_token_batch_distinct_across_steps_and_shards():
    base = token_batch(0, 0, 0, 4, 32, 100)["tokens"]
    for step, shard in [(1, 0), (0, 1), (5, 3)]:
        other = token_batch(0, step, shard, 4, 32, 100)["tokens"]
        assert not np.array_equal(base, other), (step, shard)


def test_token_targets_are_shifted_tokens():
    b = token_batch(0, 0, 0, 2, 16, 50)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])


def test_token_stream_restart_is_bit_exact():
    """Checkpoint = one integer: resuming at step k replays batch k."""
    s = TokenStream(seed=3, batch_per_shard=2, seq_len=16, vocab=64,
                    n_shards=4)
    ref = [s.batch(i, 1)["tokens"] for i in range(10)]
    state = s.state(6)
    resume_at = TokenStream.resume(state)
    for i in range(resume_at, 10):
        np.testing.assert_array_equal(s.batch(i, 1)["tokens"], ref[i])


@given(vocab=st.integers(20, 200), seed=st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_token_range(vocab, seed):
    b = token_batch(seed, 0, 0, 4, 32, vocab)
    assert int(b["tokens"].min()) >= 0
    assert int(b["tokens"].max()) < vocab


# ---------------------------------------------------------------------------
# Synthetic classification
# ---------------------------------------------------------------------------

def test_classification_is_learnable_structure():
    """Class means are separated: a nearest-mean rule beats chance by a
    lot (the gradient-space class structure GRAD-MATCH exploits)."""
    ds = make_classification(jax.random.PRNGKey(0), n=2000, dim=32,
                             num_classes=5, sep=6.0)
    means = jnp.stack([ds.x[ds.y == c].mean(0) for c in range(5)])
    d = jnp.linalg.norm(ds.x[:, None] - means[None], axis=-1)
    acc = float(jnp.mean((jnp.argmin(d, 1) == ds.y)))
    assert acc > 0.6, acc


def test_imbalance_protocol():
    train, val = make_imbalanced(jax.random.PRNGKey(1), n=4000, dim=16,
                                 num_classes=10, imbalanced_frac=0.3,
                                 keep_frac=0.1)
    counts = np.bincount(np.asarray(train.y), minlength=10)
    imb, bal = counts[:3], counts[3:]
    # imbalanced classes should be ~10x rarer
    assert imb.mean() < 0.3 * bal.mean(), counts
    vcounts = np.bincount(np.asarray(val.y), minlength=10)
    assert vcounts.min() > 0  # validation stays clean/balanced-ish


def test_split_disjoint_and_complete():
    ds = make_classification(jax.random.PRNGKey(2), n=500, dim=8)
    tr, va = split(ds, jax.random.PRNGKey(3), val_frac=0.2)
    assert tr.n + va.n == 500
    assert va.n == 100


# ---------------------------------------------------------------------------
# Subset loader
# ---------------------------------------------------------------------------

def _loader(n=64, bs=8):
    x = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))
    y = jnp.arange(n, dtype=jnp.int32) % 4
    return SubsetLoader(x, y, bs, seed=5)


def test_loader_serves_selection_only():
    ld = _loader()
    idx = np.array([1, 5, 9, 13, 17, 21, 25, 29])
    ld.set_selection(idx, np.full(8, 1 / 8, np.float32), np.ones(8, bool))
    for _ in range(5):
        b = ld.next_batch()
        rows = np.asarray(b["x"][:, 0]).astype(int)
        assert set(rows).issubset(set(idx.tolist()))
        np.testing.assert_allclose(float(b["weights"].sum()), 1.0,
                                   rtol=1e-5)


def test_loader_checkpoint_resume_bit_exact():
    ld = _loader()
    ld.set_selection(np.arange(32), np.full(32, 1 / 32, np.float32),
                     np.ones(32, bool))
    for _ in range(3):
        ld.next_batch()
    snap = ld.checkpoint_state()
    ref = [np.asarray(ld.next_batch()["x"]) for _ in range(6)]
    ld2 = _loader()
    ld2.restore_state(snap)
    got = [np.asarray(ld2.next_batch()["x"]) for _ in range(6)]
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


def test_loader_epoch_covers_subset():
    ld = _loader(n=32, bs=8)
    ld.set_selection(np.arange(16), np.full(16, 1 / 16, np.float32),
                     np.ones(16, bool))
    seen = set()
    for b in ld.epoch_batches():
        seen.update(np.asarray(b["x"][:, 0]).astype(int).tolist())
    assert seen == set(range(16))


def test_loader_padded_selection_filtered():
    ld = _loader()
    idx = np.array([3, 7, -1, -1])
    mask = np.array([True, True, False, False])
    w = np.array([0.6, 0.4, 0.0, 0.0], np.float32)
    ld.set_selection(idx, w, mask)
    assert ld.subset_size == 2
