"""Cell 6's ``decode`` program, compiled at the cell's real sizes for a v5e
that is described and not attached (``tests/benchmarks/test_fits.py``'s
idiom): its cached steps stream the carry through ``selected_attention`` as
the carry lies.  Nothing runs and nothing here is a time: what is read is
the compiled program's text, for the kernel, for a gather of the selected
rows, and for any instruction that would copy or re-lay an array as large
as a layer of the carry (1.07 GB), which is what a layer sliced out of the
stack, or a carry in a layout the kernel does not take, would cost a step.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

import jax  # noqa: E402
import pytest  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$", re.M)
MOVES = ("copy", "transpose", "gather", "concatenate", "pad", "slice",
         "dynamic-slice", "convert")


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[:1]
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture
def for_the_chip():
    """As ``test_fits.py`` and its ``conftest.py``: a program compiled for a
    described chip cannot be read back from the persistent cache, and the
    suite's interpret mode would compile host callbacks, not the kernel."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental.pallas import tpu as pltpu

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pltpu.force_tpu_interpret_mode(None):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_cell_6_decodes_through_the_kernel_and_moves_no_layer(
        chip, for_the_chip):
    from benchmarks.lib import cells, program

    cell = cells.resolve(CELL)
    cfg = program.program_config(cell.config)
    job = cell.runner.build(cell.config, cell.traffic, chip)
    fn, args = job.programs()["decode_full"]
    text = fn.lower(*args).compile().as_text()

    assert "selected_attention" in text
    assert "attention.gather" not in text
    row = 2 * cfg.kv_heads * cfg.head_dim
    layer = job.batch * (job.prompt_len + job.max_new) * row
    selection = job.batch * cfg.index.topk * row
    moved = []
    for name, dims, op, rest in INSTRUCTION.findall(text):
        size = math.prod(int(d) for d in dims.split(",") if d)
        kind = name if op == "fusion" else op   # a fusion is named after
        if size >= layer and any(               # what it holds
                re.search(rf"(^|[_.]){m}([_.]|$)", kind) for m in MOVES):
            moved.append((name, dims, op))
        if op == "gather" and "decode.step" in rest:
            assert size < selection, (name, dims)
    assert not moved, moved
