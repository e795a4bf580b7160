"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; all device-path tests run on
8 virtual CPU devices (the reference's analogous trick is ras/simulator
fabricating fake nodes — orte/mca/ras/simulator/ras_sim_module.c:67-91 —
plus oversubscribed localhost launch).
"""

import os

# Force the virtual mesh even when the ambient environment points JAX at a
# real accelerator (JAX_PLATFORMS=tpu); OMPI_TPU_TEST_REAL=1 opts out.
_FLAGS = ["--xla_force_host_platform_device_count=8"]
if os.environ.get("OMPI_TPU_TEST_REAL") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Nearly every test is a compile for the CPU of a tiny model or of a
    # kernel in interpret mode, and the machine code of that stand-in is
    # nothing a user runs: LLVM compiles it at level 1, not 3 (a whole run
    # on one 8-core box, six workers: 8066 -> 6993 test-seconds, 1404 ->
    # 1229 s of wall, PR 72).  Level 0 is cheaper still and nothing pins
    # this backend's bits since PR 74, whose whole run at level 0 read 5015
    # test-seconds beside 5134 at level 1 on one box: 2.3%, and one flake;
    # declined in PR 75 (it removes nothing, and level-0 code rounds
    # otherwise), so the flag stays at 1.  The HLO
    # passes are as they were, and libtpu's compiles for a described v5e are
    # the same bytes whatever the level.  A run against an attached chip
    # compiles for it as ever.
    _FLAGS.append("--xla_backend_optimization_level=1")
flags = os.environ.get("XLA_FLAGS", "")
for _flag in _FLAGS:        # unless the ambient value names it
    if _flag[2:].split("=")[0] not in flags:
        flags += " " + _flag
os.environ["XLA_FLAGS"] = flags.strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The CPU client runs each virtual device on one thread of a pool sized to
# the core count.  A pallas kernel in TPU interpret mode (fixture below)
# parks that thread in a cross-device barrier while its host callbacks need
# further pool threads to read their arguments, so 8 devices on <= 8 cores
# deadlock.  PJRT_NPROC sizes the pool.
os.environ.setdefault("PJRT_NPROC", "32")
# The suite compiles the same programs again and again: the benchmark's
# harness jits a closure made anew a call (``lib/program.init_params`` twice
# a control's reading, a decoder a planted fault), which JAX's in-memory
# cache, keyed by the function object, cannot match.  The persistent cache
# is keyed by the program's text and can: one directory a process under the
# temporary directory, removed at exit (the controls of one decode cell go
# from 193 s to 82 s, PR 45).  An ambient directory is left as it is.
_CACHE = {}
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile

    _CACHE = {
        "jax_compilation_cache_dir": tempfile.mkdtemp(
            prefix="ompi_tpu_tests_jax_cache_"),
        "jax_persistent_cache_min_compile_time_secs": 0.2,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }
    atexit.register(shutil.rmtree, _CACHE["jax_compilation_cache_dir"],
                    ignore_errors=True)
    for _name, _value in _CACHE.items():
        os.environ[_name.upper()] = str(_value)

# Pytest plugins (jaxtyping) import jax before this conftest runs, so the
# env vars above may be too late for jax's config snapshot; push the platform
# choice through the live config instead (backends are not yet instantiated
# at collection time, so this is still safe).
import sys  # noqa: E402

if "jax" in sys.modules and os.environ.get("OMPI_TPU_TEST_REAL") != "1":
    import jax

    jax.config.update("jax_platforms", "cpu")
    for _name, _value in _CACHE.items():
        jax.config.update(_name, _value)


import pytest  # noqa: E402


def pytest_configure(config):
    """The driver runs tier-1 as ``-p xdist -n 6 --dist loadfile``: a file is
    one worker's from its first test to its last, and a worker takes the next
    file of the queue when two or fewer of its tests are pending.  xdist
    would queue the files by their number of tests, most first, and the
    suite's longest file (``tests/benchmarks/test_fits.py``: a compile a
    cell) has among the fewest: it started at minute ten and ran alone while
    five workers idled.  Without the reorder the queue is the collection
    order, which puts ``tests/benchmarks/`` and so the two longest files
    first.  ``tools/tier1_time.py`` replays either queue over a run's junit
    file.  A run without xdist has no such option."""
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


@pytest.fixture(scope="session", autouse=True)
def _pallas_tpu_interpret_mode():
    """The pallas kernels (ops/) carry no interpret selection of their own:
    they compile for the TPU or fail.  The suite runs them on the virtual
    CPU mesh under JAX's TPU interpret mode, which also models remote DMA
    and semaphores.  A test that wants the bare behaviour re-enters with
    ``pltpu.force_tpu_interpret_mode(None)``."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        yield
