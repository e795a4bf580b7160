"""The suite's own settings (``tests/conftest.py``) as a test process has
them: a setting that an ambient ``XLA_FLAGS`` dropped, or a compile cache
that became one a worker, costs tier-1 minutes and fails nothing else."""

import os
import sys

import jax


def test_a_worker_has_the_suites_flags_and_the_runs_one_compile_cache():
    wanted = {"--xla_force_host_platform_device_count=8"}
    if os.environ.get("OMPI_TPU_TEST_REAL") != "1":
        # the CPU stand-in's machine code: the backend's level 1
        wanted.add("--xla_backend_optimization_level=1")
    assert wanted <= set(os.environ["XLA_FLAGS"].split())
    assert jax.device_count() == 8

    directory = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert os.path.isdir(directory)
    assert jax.config.jax_compilation_cache_dir == directory
    if "PYTEST_XDIST_WORKER" in os.environ:
        # the controller made the directory before it started its workers,
        # which inherit it: a worker's conftest makes none of its own
        assert sys.modules["tests.conftest"]._CACHE == {}
