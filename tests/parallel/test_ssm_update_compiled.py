"""Cell 13's generating program, compiled at the cell's real sizes for a v5e
that is described and not attached (``tests/benchmarks/test_fits.py``'s
idiom).  Nothing runs and nothing here is a time: what is read is the
compiled program's text and memory.  Every cached step passes each of the
nine Mamba-2 layers' bfloat16 states through ``ssm_update`` and through
nothing else as large (no fusion that reads a state, no float32 array of a
state's shape, no copy: a copy of a layer's state is 335 MB a step), in the
buffer it lies in; and the state reaches the kernel through
``ssm._state_before``: with ``ssm_state_not_carried`` planted there while
the decoder is traced, the kernels are handed zeros and the program is
another.
"""

import math
import re

from benchmarks import controls_granite_h
from tests.parallel.compiled import INSTRUCTION, _cell, _peak, _program

CELL = "granite-4.0-h-small.decode-512-128-b160"
# the parent's generating program, compiled here (arguments + results +
# temporaries - written in place; ``memory_stats`` on the chip reads 14.064
# GiB with the prefill's pass): this PR's reads 12.821, the float32 update's
# temporaries gone
PARENT_PEAK_GIB = 13.022
# a name for a buffer, not a pass over it
NAMES = ("parameter", "get-tuple-element", "bitcast", "tuple", "while")


def _steps(job, chip):
    """The cell's generating program, lowered for the described chip."""
    fn, args = _program(job, chip, 1)
    return fn.lower(*args)


def test_cell_13_steps_pass_each_state_through_the_kernel_in_place(
        chip, for_the_chip):
    cfg, job = _cell(CELL, chip)
    sound = _steps(job, chip)
    compiled = sound.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()

    sz = cfg.plan.ssm
    state = (job.batch, sz.n_heads, sz.head_dim, sz.d_state)
    assert state == (160, 128, 64, 128) and sz.state_dtype == "bfloat16"
    layers = cfg.plan.count("ssm")
    assert layers == 9
    shapes = {",".join(map(str, dims)) for dims in (state, (1, *state))}
    passes = [(name, dims, op)
              for name, dims, op, _rest in INSTRUCTION.findall(text)
              if dims in shapes and op not in NAMES]
    assert not passes, passes       # no fusion, convert or copy of a state
    dims = ",".join(map(str, state))
    assert f"f32[{dims}]" not in text and f"f32[1,{dims}]" not in text
    # the kernel's result is a tuple (y, state), which the pattern above, of
    # single arrays, does not read: the calls are counted by name, and each
    # takes its state from the carried buffer itself (a parameter of the
    # steps' loop, under a bitcast) and hands it back there
    kernels = re.findall(
        rf"= \(f32\[[\d,]+\]\S* bf16\[1,{dims}\]\S*\) custom-call\("
        rf"([^\n]*)custom_call_target=\"tpu_custom_call\"[^\n]*ssm_update",
        text)
    assert len(kernels) == layers
    assert "output_to_operand_aliasing={{1}: (5, {})}" in text
    assert memory.alias_size_in_bytes > layers * 2 * math.prod(state)
    peak = _peak(memory)
    assert peak < (PARENT_PEAK_GIB * 2 ** 30) + (64 << 20), peak / 2 ** 30

    # the control: a decoder traced with zeros planted where the update
    # reads its state hands them to the kernels, a layer's zeros each, where
    # the sound steps hand over the carried state widened and narrowed back
    # (the pair the compiler removed above)
    faulty = _steps(controls_granite_h.FaultyJob(
        job, "ssm_state_not_carried"), chip).as_text()
    sound = sound.as_text()
    wide = f"tensor<{dims.replace(',', 'x')}xf32>"
    zeros = rf"stablehlo.broadcast_in_dim [^\n]*-> {wide}"
    widened = rf"stablehlo.convert [^\n]*xbf16>\) -> {wide}"
    assert len(re.findall(zeros, faulty)) == layers
    assert not re.findall(widened, faulty)
    assert len(re.findall(widened, sound)) == layers
    assert not re.findall(zeros, sound)
