"""Elastic scaling: checkpoint from one topology restores onto another."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import get_smoke_config
from repro.launch.elastic import rendezvous, reshard_like
from repro.models import lm as lm_lib


def test_rendezvous_roundtrip(tmp_path):
    """Save under topology A, restore under topology B, forward output
    identical — the reshard is value-preserving."""
    cfg = get_smoke_config("starcoder2-3b")
    params = lm_lib.init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    h0, _, _ = lm_lib.forward(cfg, params, toks, mode="train")

    save_checkpoint(str(tmp_path), 1, {"params": params})
    snap = load_checkpoint(str(tmp_path))

    # "new cluster": 1-device mesh (the only topology on this container;
    # the 512-way version is exercised by the dry-run artifacts)
    mesh, params2 = rendezvous(cfg, snap["params"], data=1, model=1,
                               fsdp=True)
    h1, _, _ = lm_lib.forward(cfg, params2, toks, mode="train")
    np.testing.assert_allclose(np.asarray(h0, np.float32),
                               np.asarray(h1, np.float32), rtol=1e-5)


def test_reshard_like_moves_leaves():
    dev = jax.devices()[0]
    tree = {"a": np.ones((4, 4), np.float32)}
    sh = {"a": jax.sharding.SingleDeviceSharding(dev)}
    out = reshard_like(tree, sh)
    assert isinstance(out["a"], jax.Array)
    np.testing.assert_array_equal(np.asarray(out["a"]), tree["a"])
