import os

import jax
import pytest

# Tests run on the default single CPU device; the 512-device dry-run
# environment is exercised ONLY by repro.launch.dryrun (per the
# assignment, smoke tests must see 1 device).
#
# x64 stays off by default, but the CI decode-parity matrix runs the
# suite under JAX_ENABLE_X64=1 (wider accumulators shake out dtype
# assumptions in the decode paths) — honour an explicit opt-in.

if os.environ.get("JAX_ENABLE_X64", "0").lower() in ("", "0", "false"):
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop jit caches between test modules.

    The suite compiles several hundred distinct XLA programs in one
    process. Releasing executables at module boundaries keeps the number
    of live CPU programs, and the memory they hold, bounded however the
    suite grows (an older jaxlib segfaulted in ``backend_compile`` once
    too many had accumulated). Within-module cache-hit/jit-miss
    accounting (admission tests) is unaffected.
    """
    yield
    jax.clear_caches()


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def requires_multi_device():
    return pytest.mark.skipif(
        jax.device_count() < 2, reason="needs >1 device")
