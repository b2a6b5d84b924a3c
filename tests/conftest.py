import os
import sys

# Tests run on a virtual 8-device CPU mesh (JAX_PLATFORMS=cpu), so the
# multi-device sharding logic is exercised without a chip;
# `python chip_smoke.py` is the run on the chip. The backend is not yet
# created when this file runs, so jax.config decides the platform.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The persistent compile cache stays off unless JAX_COMPILATION_CACHE_DIR
# places it: the suite must not fill <checkout>/.jax_cache with XLA:CPU
# entries. Set before jax is imported, so test subprocesses inherit it.
_CACHE_PLACED = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
if not _CACHE_PLACED:
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from mythril_tpu.support.devices import (  # noqa: E402
    enable_compile_cache,
    force_virtual_cpu,
)

force_virtual_cpu(8)
if _CACHE_PLACED:
    enable_compile_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` budget run "
        "(multi-process daemon lifecycles and similar long tails)")


@pytest.fixture(autouse=True)
def _fresh_execution_deadline():
    """Clear the global execution deadline around every test.

    `time_handler` is a process-wide singleton and `get_model` turns a
    passed deadline into an unconditional UnsatError — so any test
    that runs an analysis with a finite `execution_timeout` plants a
    time bomb for every later test that touches the solver without
    starting its own window. Which victim explodes depends on suite
    pacing (it surfaced as order-dependent lane_merge/propagate/repair
    failures only under full-suite wall times). Every engine entry
    point re-arms the deadline via start_execution, so clearing it
    here never changes a test's own semantics.
    """
    from mythril_tpu.laser.time_handler import time_handler

    time_handler.clear()
    yield
    time_handler.clear()


@pytest.fixture(autouse=True)
def _fresh_warm_store():
    """Reset the cross-run warm store's in-process state around every
    test (support/warm_store.py).

    The store is DESIGNED to persist banks across analyses in one
    process — which is exactly wrong between tests: a corpus-mode test
    configures the store against its tmp out-dir, and without this
    reset every later analysis in the session would silently save
    into (and warm-load from) that stale directory, coupling test
    outcomes to suite order the same way the deadline leak above did.
    """
    from mythril_tpu.support import warm_store

    warm_store.reset()
    yield
    warm_store.reset()
