"""The main-path Pallas kernels compile for a described TPU v5e chip.

Nothing runs: each kernel is lowered at the widths the chip serves and
compiled by the TPU compiler for a chip that is described, not attached,
so a tiling or fast-memory limit that interpret mode cannot see fails
here.  The topology is described inside a fixture, never at import, and
the persistent compilation cache is off around these compiles (their
entries could not be read back without a chip).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.corr import bound_max, corr, corr_argmax
from repro.kernels.fl_gain import fl_gain_argmax, fl_gain_argmax_otf


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


N, D = 8192, 512
N_OTF = 32768


F32, BF16, BOOL = jnp.float32, jnp.bfloat16, jnp.bool_

# kernel -> argument (shape, dtype) at the widths the chip serves
KERNELS = {
    "corr": (corr, [((N, D), F32), ((D,), F32)]),
    "corr_argmax": (corr_argmax, [((N, D), F32), ((D,), F32), ((N,), F32),
                                  ((N,), BOOL)]),
    "bound_max": (bound_max, [((N, D), BF16), ((N,), F32), ((N,), F32),
                              ((D,), F32), ((), F32), ((), F32),
                              ((N,), BOOL)]),
    "fl_gain_argmax": (fl_gain_argmax, [((N, N), F32), ((N,), F32),
                                        ((N,), BOOL)]),
    "fl_gain_argmax_otf": (fl_gain_argmax_otf, [
        ((N_OTF, D), F32), ((N_OTF,), F32), ((N_OTF,), BOOL),
        ((N_OTF,), BOOL), ((), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
