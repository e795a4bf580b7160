"""What the tests that lower or compile for the described chip
(``conftest.py``'s ``chip`` and ``for_the_chip``) share that is not a
fixture: how a compiled program's text is read, and how one of a cell's
programs is had at the cell's real sizes.
"""

import re

import jax
import jax.numpy as jnp

# an instruction whose result is a single array: (name, sizes, opcode, rest)
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$", re.M)
MOVES = ("copy", "transpose", "gather", "concatenate", "pad", "slice",
         "dynamic-slice", "convert")


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def _on(chip, dims, dtype=jnp.float32):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(dims, dtype,
                                sharding=SingleDeviceSharding(chip[0]))


def _cell(name, chip):
    """(the program's configuration, the cell's job on as many of ``chip``'s
    devices as the cell has) from the configuration and traffic files;
    building traces nothing."""
    from benchmarks.lib import cells, program

    cell = cells.resolve(name)
    return (program.program_config(cell.config),
            cell.runner.build(cell.config, cell.traffic, chip[:cell.chips]))


def _program(job, chip, which):
    """One of the two programs of a decoder that has two, as it runs
    (``decode._two_programs``: the prefill's, then the generating one, which
    takes the carry donated), cut out of the job's ``full`` decoder, whose
    two programs ``job.programs()`` compiles as one."""
    from jax.extend.core import jaxpr_as_fun

    _fn, args = job.programs()["decode_full"]
    programs = [eqn for eqn in jax.make_jaxpr(job.full)(*args).eqns
                if eqn.params.get("name") == "decode"]
    assert len(programs) == 2
    closed = programs[which].params["jaxpr"]
    donated = [i for i, given in enumerate(
        programs[which].params["donated_invars"]) if given]
    # a prefill is given nothing; a generating program all of whose buffers
    # grow (cell 10: latent caches alone) is not either
    assert not donated or which
    return (jax.jit(jaxpr_as_fun(closed), donate_argnums=donated),
            [_on(chip, v.aval.shape, v.aval.dtype)
             for v in closed.jaxpr.invars])


def _peak(memory) -> int:
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
