"""Every cell's programs compile at the cell's real sizes for the chip they
are measured on: the v5e's own compiler, installed here, compiling for a
2x2 topology that is described and not attached.  Nothing runs and no number
here is a measurement; a program that outgrows 15.75 GiB of HBM, or that the
compiler refuses for any other reason, fails here at no chip time.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

import jax  # noqa: E402
import pytest  # noqa: E402

from benchmarks.lib import cells  # noqa: E402

WORKLOADS = cells.load_benchmark()["workloads"]
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start)?\(")


@pytest.fixture(scope="module")
def chips():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def _no_compile_cache():
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


@pytest.mark.parametrize("workload", WORKLOADS, ids=lambda w: w["name"])
def test_cell_programs_compile_for_the_chip(workload, chips):
    cell = cells.resolve(workload["name"])
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
