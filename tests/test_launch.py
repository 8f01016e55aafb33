"""Entry-point plumbing: the compile-cache location, and the errors that
keep a multi-device run from quietly shrinking to what is present."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import train
from repro.launch.cache import REPO_CACHE_DIR, enable_compile_cache
from repro.launch.mesh import make_host_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory the helper may set."""
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR


def test_compile_cache_leaves_env_var_to_jax(monkeypatch, tmp_path,
                                             cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_dir_config


def test_repo_cache_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("data,model", [(2, 1), (1, 2), (4, 1), (0, 1)])
def test_host_mesh_refuses_more_devices_than_present(data, model):
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data, model)


def test_train_refuses_mesh_larger_than_host(cache_dir_config):
    with pytest.raises(ValueError, match="mesh needs 4 devices"):
        train.main(["--arch", "gemma-2b", "--smoke", "--steps", "1",
                    "--mesh-data", "4", "--window", "16"])


def test_train_refuses_window_that_does_not_split_over_data():
    with pytest.raises(ValueError, match="multiple of --mesh-data"):
        train.main(["--arch", "gemma-2b", "--smoke", "--steps", "1",
                    "--mesh-data", "4", "--window", "15"])


_INIT_CHECK = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    import jax
    from repro.configs import get_smoke_config
    from repro.distributed.sharding import param_shardings
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm as lm_lib
    from repro.optim import sgd

    cfg = get_smoke_config(sys.argv[1])
    key = jax.random.PRNGKey(3)
    mesh = make_host_mesh(jax.device_count(), 1)
    params, p_sh = train.init_params(cfg, key, mesh, fsdp=True)
    eager = lm_lib.init_lm(cfg, key)
    assert p_sh == param_shardings(cfg, eager, mesh, fsdp=True)
    slots = sgd(0.1, momentum=0.9).init(params).slots
    split = 0
    for got, want, sh, m in zip(jax.tree_util.tree_leaves(params),
                                jax.tree_util.tree_leaves(eager),
                                jax.tree_util.tree_leaves(p_sh),
                                jax.tree_util.tree_leaves(slots)):
        assert got.sharding == sh and m.sharding == sh
        assert got.dtype == want.dtype
        assert bool(jax.numpy.array_equal(got, want))
        split += got.addressable_shards[0].data.shape != got.shape
    assert (split > 0) == (jax.device_count() > 1), split
    print("OK")
""")


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("arch", ["gemma-2b", "xlstm-1.3b"])
def test_init_params_matches_eager_init_in_its_shardings(arch, devices):
    """Eager on one device, one sharded jit on several: the same values,
    each leaf and its optimizer slot in the leaf's sharding."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _INIT_CHECK % devices, arch],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
