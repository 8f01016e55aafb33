"""Every selection contraction asks for f32-exact (HIGHEST) precision.

The CPU computes f32 products exactly whatever a dot's precision says, so
no numeric test on it can see a dropped ``precision=``; left to its
default, XLA on a TPU rounds f32 operands to bf16 (DESIGN §1).  Each test
here runs one selection path at a tiny shape with StableHLO dumping on,
then checks every ``dot_general`` the path lowered.  The Pallas kernels
are checked the same way in interpret mode, where their bodies lower to
plain HLO, and in ref mode, where ``ops`` calls their jnp references.
"""

import contextlib
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decremental as dec_lib
from repro.core import distributed as dist_lib
from repro.core import gradmatch as gm_lib
from repro.core import omp as omp_lib
from repro.core import selection as sel_lib
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh

N, D, K = 96, 16, 8
_DOT = re.compile(r"stablehlo\.dot_general[^\n]*")
_HIGHEST = "precision = [HIGHEST, HIGHEST]"


def _pool(seed=0, n=N, d=D):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (n, d), dtype=np.float32))


@contextlib.contextmanager
def _dumped_stablehlo(path: pathlib.Path):
    jax.clear_caches()   # a cached executable would not be lowered again
    old = (jax.config.values["jax_dump_ir_to"],
           jax.config.values["jax_dump_ir_modes"])
    jax.config.update("jax_dump_ir_to", str(path))
    jax.config.update("jax_dump_ir_modes", "stablehlo")
    try:
        yield
    finally:
        jax.config.update("jax_dump_ir_to", old[0])
        jax.config.update("jax_dump_ir_modes", old[1])
        jax.clear_caches()


def _dots(tmp_path, run) -> list:
    """(module, op text) of every dot_general ``run`` lowers."""
    with _dumped_stablehlo(tmp_path):
        jax.block_until_ready(run())
    found = []
    for f in sorted(tmp_path.glob("*.mlir")):
        found += [(f.name, m.group(0)) for m in _DOT.finditer(f.read_text())]
    return found


def _assert_highest(tmp_path, run):
    dots = _dots(tmp_path, run)
    assert dots, "the path lowered no dot_general: nothing was checked"
    loose = [f"{mod}: {op[:160]}" for mod, op in dots if _HIGHEST not in op]
    assert not loose, "\n".join(loose)


def _select(strategy, **kw):
    return lambda: sel_lib.select(strategy, jax.random.PRNGKey(0), _pool(),
                                  K, **kw).weights


def _per_class():
    labels = jnp.asarray(np.arange(N) % 3)
    return sel_lib.select("gradmatch", jax.random.PRNGKey(0), _pool(), K,
                          labels=labels, num_classes=3).weights


def _batched():
    g = _pool()
    return omp_lib.omp_select_batched(g, _pool(1, n=3), K)


def _session_edits():
    g = _pool()
    sess = omp_lib.omp_session_start(g, jnp.sum(g, axis=0), K)
    cut = dec_lib.session_truncate(sess, K // 2)
    fresh = dec_lib.session_truncate(sess, 0)
    _, trace = dec_lib.session_extend_traced(g, fresh, K)
    down = dec_lib.omp_downdate(g, sess, int(sess.indices[0]))
    return cut, trace.resid, down


def _matching_error():
    g = _pool()
    idx, w, mask, _ = omp_lib.omp_select(g, jnp.sum(g, axis=0), K)
    return omp_lib.matching_error(g, jnp.sum(g, axis=0), idx, w, mask,
                                  lam=0.5)


def _sharded():
    g = _pool()
    return dist_lib.sharded_omp_select(make_host_mesh(1, 1), g,
                                       jnp.sum(g, axis=0), K).weights


def _pmap_partitions():
    g = _pool()[None]
    return dist_lib.pmap_partition_omp(g, jnp.sum(g, axis=1),
                                       jnp.ones((1, N), bool), K)


def _per_class_targets():
    return gm_lib.gradmatch_per_class(_pool(), jnp.asarray(np.arange(N) % 4),
                                      4, K, method="dense").weights


SELECTION_PATHS = {
    "gradmatch": _select("gradmatch"),
    "gradmatch-dense": _select("gradmatch", omp_method="dense"),
    "gradmatch-stream": _select("gradmatch-stream", chunk_size=32,
                                stream_buffer=16),
    "gradmatch-partitioned": _select("gradmatch-partitioned", partitions=2),
    "gradmatch-pb": _select("gradmatch-pb", batch_size=4),
    "gradmatch-continual": _select("gradmatch-continual", buffer_cap=48,
                                   continual_batch=16),
    "craig": _select("craig"),
    "craig-lazy": _select("craig-lazy"),
    "craig-lazy-otf": _select("craig-lazy-otf"),
    "craig-stochastic": _select("craig-stochastic"),
    "glister": _select("glister"),
    "per-class": _per_class,
    "per-class-dense": _per_class_targets,
    "batched": _batched,
    "session-edits": _session_edits,
    "matching-error": _matching_error,
    "sharded": _sharded,
    "pmap-partitions": _pmap_partitions,
}


@pytest.mark.parametrize("path", sorted(SELECTION_PATHS))
def test_selection_contractions_are_highest(tmp_path, path):
    _assert_highest(tmp_path, SELECTION_PATHS[path])


def _kernel_inputs():
    g = _pool(n=256, d=128)
    r = _pool(2, n=1, d=128)[0]
    mask = jnp.ones((256,), bool)
    cover = jnp.zeros((256,), jnp.float32)
    return g, r, mask, cover


def _k_corr():
    g, r, _, _ = _kernel_inputs()
    return ops.corr(g, r)


def _k_corr_argmax():
    g, _, mask, _ = _kernel_inputs()
    w = _pool(3, n=1, d=128)[0]
    return ops.corr_argmax(g, w, jnp.zeros((256,)), mask)


def _k_corr_batched():
    g, _, mask, _ = _kernel_inputs()
    w = _pool(3, n=2, d=128)
    return (ops.corr_batched(g, w),
            ops.corr_argmax_batched(g, w, jnp.zeros((256, 2)),
                                    jnp.ones((256, 2), bool)))


def _k_bound_max():
    g, r, mask, _ = _kernel_inputs()
    ones = jnp.ones((256,), jnp.float32)
    return ops.bound_max(g.astype(jnp.bfloat16), ones, 0.0 * ones, r,
                         jnp.float32(1e-3), jnp.float32(0.0), mask)


def _k_fl_gain():
    g, _, mask, cover = _kernel_inputs()
    sim = ops.sqdist(g, g)
    otf = ops.fl_gain_argmax_otf(g, cover, jnp.ones((256,), jnp.float32),
                                 mask, jnp.float32(100.0))
    return sim, ops.fl_gain_argmax(-sim, cover, mask), otf


KERNELS = {"corr": _k_corr, "corr_argmax": _k_corr_argmax,
           "corr_batched": _k_corr_batched, "bound_max": _k_bound_max,
           "fl_gain_sqdist": _k_fl_gain}


@pytest.mark.parametrize("mode", ["interpret", "ref"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_pallas_kernel_contractions_are_highest(tmp_path, kernel, mode):
    """Each kernel's Pallas body (interpret mode) and its jnp reference."""
    ops.set_backend(mode)
    try:
        _assert_highest(tmp_path, KERNELS[kernel])
    finally:
        ops.set_backend(None)
