"""``benchmarks/controls_minicpm_sala.py``: the faults of the block
selection's and the lightning mixer's own, planted in the cell's programs at
the configuration's ``tiny`` sizes, float32, on the CPU, at prompts of 40
positions and 56 new tokens (a cache of 96: twelve blocks of which a query
keeps six, and steps enough for the pooled keys they write to leave the
window, inside which a block is attended whatever its score), and read
through the runner's own ``compare`` and ``verdict`` with the limits the
configuration's file gives.  Whether the limits hold them at the real sizes
is the chip's to say (PERF.md); here each is planted, decodes, and is refused
by a limit for logits.  One fault is no fault of the mathematics: a state
rounded to bfloat16 where everything else is float32 reads the state's
rounding, which a check made for a bfloat16 program need not refuse, so at
tiny sizes it is only shown to move the logits."""

import json

import pytest

from benchmarks import controls_minicpm_sala as own
from benchmarks.lib import cells

CELL = "minicpm-sala.decode-16k-512-b24"
OWN = (*own.CONFIG_FAULTS, *own.TRACED_FAULTS)
REFUSED = tuple(f for f in OWN if f != "state_in_bfloat16")
SEEDS = [3, 4]

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in own.run(
                CELL, SEEDS, ["sound", "attention_layer_off",
                              "ffn_layer_off", *OWN],
                small=True, prompt_len=40, max_new=56, batch=4,
                reference_sequences=4)})
    return _readings


@pytest.mark.parametrize("seed", SEEDS)
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 4 * 56


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", ("attention_layer_off", "ffn_layer_off",
                                   *REFUSED))
def test_a_fault_is_refused(fault, seed):
    r = readings()[fault, seed]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_state_in_bfloat16_moves_the_logits_by_its_rounding(seed):
    r = readings()["state_in_bfloat16", seed]
    sound = readings()["sound", seed]
    assert 100 * sound["logit_err_max"] < r["logit_err_max"] < 1.0, r


def test_a_configuration_without_the_plan_is_refused():
    import jax

    from benchmarks import controls

    cell = cells.resolve("kimi-linear-48b-a3b.decode-512-128-b384")
    config, traffic = controls.tiny(cell)
    job = cell.runner.build(config, traffic, jax.devices()[:1])
    with pytest.raises(KeyError, match="lightning"):
        own.FaultyJob(job, "decay_off")
    with pytest.raises(ValueError, match="no fault"):
        own.run(CELL, [1], ["sonud"], small=True)
