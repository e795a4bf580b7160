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
import re

from tests.parallel.compiled import INSTRUCTION, MOVES, _cell

CELL = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"


def test_cell_6_decodes_through_the_kernel_and_moves_no_layer(
        chip, for_the_chip):
    cfg, job = _cell(CELL, chip)
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
