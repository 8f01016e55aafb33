"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see the real
single CPU device; multi-device behavior is tested via subprocesses
(test_distributed.py) and the dry-run (launch/dryrun.py)."""

import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True, scope="module")
def _bounded_compile_cache():
    """Drop compiled XLA programs between test modules.

    The suite compiles thousands of distinct (function, shape) programs;
    XLA:CPU keeps every live executable mapped and segfaults inside
    ``backend_compile`` once enough of them accumulate in one process
    (seen at the suite's tail on jaxlib 0.4.36; jaxlib 0.9.0 has not
    been shown to need the bound, which costs only the recompiles
    below, so it stays).  Modules are independent — each recompiles its
    own shapes on entry — so clearing per module bounds the
    live-executable count without changing any test's behavior."""
    yield
    jax.clear_caches()
