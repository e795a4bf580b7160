"""Every cell's programs compile at the cell's real sizes for the chip they
are measured on: the v5e's own compiler, installed here, compiling for a
2x2 topology that is described and not attached.  Nothing runs and no number
here is a measurement; a program that outgrows 15.75 GiB of HBM, or that the
compiler refuses for any other reason, fails here at no chip time.

These compiles run on demand and not in tier-1: ``case`` marks every one
``slow``, which the driver's ``-m 'not slow'`` leaves out (fourteen of them
were 1650 of tier-1's 8084 test-seconds on PR 73's tree, cell 6's alone
296).  What refuses a PR is the driver's own run of every cell on the chip,
where a program that does not compile or does not fit gives no result.  A
builder runs a cell's compile before spending chip time on it, for each
cell whose programs the PR touches and for a cell it adds:

    python -m pytest -m slow tests/benchmarks/test_cell_fits_<cell>.py

The body and the fixtures of the ``test_cell_fits_<cell>.py`` files: a file a
cell, so that one cell is one command (and one worker's, under ``--dist
loadfile``, where several are run).  Each file names its cell, imports the
fixtures and takes its test from ``case``; ``test_harness.py`` holds the set
of files to ``BENCHMARK.json``'s ``workloads`` and each file's test to its
mark, so a new cell's PR adds its file.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

import jax  # noqa: E402
import pytest  # noqa: E402

from benchmarks.lib import cells  # noqa: E402

STEM = "test_cell_fits_"
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start)?\(")


def slug(workload: str) -> str:
    """A cell's name as a module's: pytest imports a test file by its name,
    which a cell's dots and dashes would break."""
    return re.sub(r"[^A-Za-z0-9]+", "_", workload)


@pytest.fixture(scope="module")
def chips():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one: the next compile would warn
    and compile again."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def case(workload: str):
    """The test of ``workload``'s file, marked once for all of them."""
    @pytest.mark.slow
    @pytest.mark.parametrize("workload", [workload])
    def test_cell_programs_compile_for_the_chip(workload, chips):
        programs_compile_for_the_chip(workload, chips)

    return test_cell_programs_compile_for_the_chip


def programs_compile_for_the_chip(workload: str, chips) -> None:
    cell = cells.resolve(workload)
    job = cell.runner.build(cell.config, cell.traffic, chips[:cell.chips])
    programs = job.programs()
    assert programs
    for name, (fn, args) in programs.items():
        compiled = fn.lower(*args).compile()    # raises what the chip would
        collectives = COLLECTIVE.findall(compiled.as_text())
        if cell.chips == 1:
            assert not collectives, f"{name}: {collectives[:5]}"
        else:
            kinds = {kind for kind, _start in collectives}
            assert "all-reduce" in kinds, f"{name}: no all-reduce"
