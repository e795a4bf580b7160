"""A routed layer's tail (``routed_moe``'s whole layout, the shared expert
beside it, the residual), compiled at cell 13's and cell 12's shapes for a
v5e that is described and not attached (``test_selected_attention_compiled.
py``'s idiom): ``moe.combine`` sums a token's ``top_k`` result rows as the
gather leaves them.  Nothing runs and nothing here is a time: what is read is
the compiled program's text, for float32 arrays that the scope writes out
(until PR 71 a standalone ``reshape`` wrote the gathered rows as ``f32[n, k,
D]`` with the picks on the sublane axis, six stored as eight and ten as
sixteen: 1074 MB a call of cell 13's pass for 335 MB of rows; and a sum that
the shared expert's down projection takes as its epilogue has the rows
converted on their own first, ``f32[n, D]`` k times: what the chip ran
before ``_sum_of_picks`` got its barrier), and the program's temporaries
beside the bytes the layer holds there.  ``kernel=True``, compiled outside
the suite's interpret mode: the program the cells run; through ``ragged_dot``
the compiler keeps float32 temporaries of the experts' own (1266 MB at cell
12's pass before and after), which hide the sum's.  Either way a pass's case
is 25 s of compile, of which its two sorts of ``n k`` keys are most.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from tests.parallel.compiled import _on

# an instruction of the entry computation: what it writes is an array in HBM
WRITES = re.compile(r"^\s*(?:ROOT )?%\S+ = \(?(\w+)\[([\d,]*)\]")

# picks, router outputs, experts held, D, the experts' F, the shared expert's
CELL_13 = dict(k=10, width=72, held=36, D=4096, F=768, shared=1536,
               gated=True)
CELL_12 = dict(k=6, width=128, held=64, D=2688, F=1856, shared=3712,
               gated=False)
# (sequences, positions) of a prefill's pass and of a cached step
CASES = [pytest.param((8, 512), CELL_13, id="cell13-pass"),
         pytest.param((160, 1), CELL_13, id="cell13-step"),
         pytest.param((8, 1024), CELL_12, id="cell12-pass"),
         pytest.param((256, 1), CELL_12, id="cell12-step")]


@pytest.mark.parametrize("tokens,cell", CASES)
def test_combine_writes_no_float32_copy_of_the_gathered_rows(
        tokens, cell, chip, for_the_chip):
    from ompi_tpu.models.transformer import _shared_expert
    from ompi_tpu.ops.grouped_matmul import tile_rows
    from ompi_tpu.parallel.moe import EXPERT_LEAVES, _window_rows, routed_moe

    k, width, held, D, F = (cell[name] for name in
                            ("k", "width", "held", "D", "F"))
    n = tokens[0] * tokens[1]
    tm = tile_rows(n * k / width, ((D, F), (F, D)), 2)
    assert _window_rows(n * k, tm, held, width) == n * k    # the whole layout

    def shape(*dims):
        return _on(chip, dims, jnp.bfloat16)

    lp = {"wg": shape(D, width), "w1": shape(held, D, F),
          "w2": shape(held, F, D), "sw1": shape(D, cell["shared"]),
          "sw2": shape(cell["shared"], D)}
    if cell["gated"]:
        lp.update(w3=shape(held, D, F), sw3=shape(D, cell["shared"]))

    def tail(h, lp):
        """``transformer._moe_ffn_tail``'s routed branch after the norm."""
        mo = routed_moe(
            h, {name: lp[name] for name in ("wg", *EXPERT_LEAVES)
                if name in lp},
            k, gated=cell["gated"], act="relu2", kernel=True, held=(0, held))
        return h + (mo + _shared_expert(h, lp, "relu2")) * 0.22

    compiled = jax.jit(tail).lower(shape(*tokens, D), lp).compile()
    text = compiled.as_text()
    # what the scope's instructions of the entry computation write:
    # (type, elements)
    written = [(m[1], math.prod(int(d) for d in m[2].split(",") if d))
               for m in map(WRITES.match,
                            text[text.index("\nENTRY"):].splitlines())
               if m and "moe.combine" in m.string]
    # the gathered rows once and their sum, both in the compute type
    assert sum(size for kind, size in written
               if kind == "bf16") == n * (k + 1) * D, written
    assert not [size for kind, size in written
                if kind == "f32" and size >= n * D], written
    # what lies between the experts and the sum: their result rows as laid
    # out and the rows gathered from them; a float32 copy is twice to three
    # times the latter again (until PR 71 cell 13's pass: 1409 MB of
    # temporaries; cell 12's: 969)
    laid_out = (-(-n * k // tm) + held) * tm
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.1 * 2 * (laid_out + n * k) * D, temp
