"""``benchmarks/controls_falcon_h1.py``: the faults of the state-space
mixer's own, planted in the cell's programs at the configuration's ``tiny``
sizes, float32, on the CPU, and read through the runner's own ``compare`` and
``verdict`` with the limits the configuration's file gives.  Whether the
limits hold them at the real sizes is the chip's to say (PERF.md section 2);
here each is planted, decodes, and is refused by a limit for logits."""

import json

import pytest

from benchmarks import controls, controls_falcon_h1
from benchmarks.lib import cells

CELL = "falcon-h1-34b.decode-128-64-b192"
OWN = (*controls_falcon_h1.PARAM_FAULTS, *controls_falcon_h1.STATE_FAULTS)

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in controls_falcon_h1.run(
                CELL, [1, 2], ["sound", *OWN], small=True)})
    return _readings


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 8 * 24


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("fault", OWN)
def test_a_fault_of_the_mixer_is_refused(fault, seed):
    r = readings()[fault, seed]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"] and r["first_token_equal"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


def test_a_state_that_is_dropped_shows_at_the_first_positions():
    """The prefill's states dropped: the first generated token is the
    prefill's own and still right, the next ones miss the prompt's state,
    and the error fades as the cached steps build the state anew."""
    r = readings()["ssm_prefill_state_dropped", 1]
    assert r["first_token_equal"] and 0.5 < r["positions_over"] < 1.0


def test_the_faults_are_planted_for_a_trace_and_taken_back():
    from ompi_tpu.models import ssm

    sound = ssm.mixer, ssm._state_before, ssm._conv_before
    for fault in controls_falcon_h1.STATE_FAULTS:
        with controls_falcon_h1.planted(fault):
            assert (ssm.mixer, ssm._state_before, ssm._conv_before) != sound
        assert (ssm.mixer, ssm._state_before, ssm._conv_before) == sound
    assert "ssm_layer_off" in controls.PARAM_FAULTS


def test_a_configuration_without_a_mixer_or_an_unknown_fault_is_refused():
    with pytest.raises(KeyError, match="no mixer"):
        controls_falcon_h1.run("pythia-1.4b-widths.decode-1k-128", [1],
                               ["ssm_state_not_carried"], small=True)
    with pytest.raises(KeyError, match="ssm_out"):
        controls_falcon_h1.run("pythia-1.4b-widths.decode-1k-128", [1],
                               ["ssm_layer_off"], small=True)
    with pytest.raises(ValueError, match="no fault"):
        controls_falcon_h1.run(CELL, [1], ["ssm_state_lost"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    assert controls_falcon_h1.main([
        "--workload", CELL, "--seeds", "1", "--faults",
        "sound,conv_state_off", "--tiny", "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in printed] == ["sound", "conv_state_off"]
    assert [r["correct"] for r in printed] == [True, False]
    with open(out) as f:
        assert len(f.readlines()) == 2
    assert cells.resolve(CELL).config["entry"]["decoder_logits"]
