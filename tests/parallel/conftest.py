"""The described chip: a v5e:2x2 that is named to the chip's own compiler
(installed here as libtpu) and not attached.  A test lowers or compiles a
program for it and reads the text and the memory; nothing runs on it and
nothing read from it is a time.  ``tests/parallel/compiled.py`` holds what
such tests share that is not a fixture.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="module")
def chip():
    """The four devices of the described v5e:2x2: a one-chip cell's test
    takes ``chip[:1]``."""
    from jax.experimental import topologies

    try:
        return list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture
def for_the_chip():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one; and the suite's session
    fixture puts pallas kernels into TPU interpret mode, for the CPU, under
    which a program with a kernel in it would lower host callbacks and not
    the kernel."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental.pallas import tpu as pltpu

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pltpu.force_tpu_interpret_mode(None):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
