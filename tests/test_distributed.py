"""Distributed selection: sharded OMP == dense OMP.

The multi-device path runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (conftest keeps the
main test process on 1 real device, per the dry-run contract)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import sharded_gradmatch_pb, sharded_omp_select
from repro.core.omp import omp_select
from repro.launch.mesh import make_host_mesh


def test_sharded_omp_single_device_matches_dense():
    """data=1 mesh: the shard_map path must agree exactly with the dense
    solver (same math, one shard)."""
    mesh = make_host_mesh(data=1, model=1)
    g = jax.random.normal(jax.random.PRNGKey(0), (96, 32))
    t = jnp.sum(g[:9], axis=0)
    i1, w1, m1, e1 = omp_select(g, t, k=9, lam=0.3)
    sel = sharded_omp_select(mesh, g, t, k=9, lam=0.3)
    np.testing.assert_array_equal(np.sort(np.asarray(i1)),
                                  np.sort(np.asarray(sel.indices)))
    np.testing.assert_allclose(float(e1), float(sel.err), rtol=1e-5)


def test_sharded_gradmatch_pb_single_device():
    mesh = make_host_mesh(data=1, model=1)
    g = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    sel = sharded_gradmatch_pb(mesh, g, batch_size=4, k_batches=4)
    assert int(jnp.sum(sel.mask)) == 4
    assert abs(float(jnp.sum(sel.weights)) - 1.0) < 1e-4


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.core.distributed import sharded_omp_select, shard_rows
    from repro.core.omp import omp_select
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    g = jax.random.normal(jax.random.PRNGKey(0), (128, 48))
    t = jnp.sum(g[:12], axis=0)
    i1, w1, m1, e1 = omp_select(g, t, k=12, lam=0.3)
    sel = sharded_omp_select(mesh, shard_rows(mesh, g), t, k=12, lam=0.3)
    assert sorted(np.asarray(i1).tolist()) == sorted(
        np.asarray(sel.indices).tolist()), (i1, sel.indices)
    np.testing.assert_allclose(float(e1), float(sel.err), rtol=1e-4)
    np.testing.assert_allclose(np.sort(np.asarray(w1)),
                               np.sort(np.asarray(sel.weights
                                                  * jnp.sum(w1))),
                               rtol=1e-3, atol=1e-5)
    print("OK8")
""")


def test_sharded_omp_8way_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK8" in r.stdout
