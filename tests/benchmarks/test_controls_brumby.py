"""``benchmarks/controls_brumby.py``: the faults of power retention's own,
planted in the cell's programs at the configuration's ``tiny`` sizes,
float32, on the CPU, and read through the runner's own ``compare`` and
``verdict`` with the limits the configuration's file gives.  Whether the
limits hold them at the real sizes is the chip's to say (PERF.md); here each
is planted, decodes, and is refused by a limit for logits.  One fault is no
fault of the mathematics: a state carried in bfloat16 where everything else
is float32 reads the state's rounding, which a check made for a bfloat16
program need not refuse, so at tiny sizes it is only shown to move the
logits."""

import dataclasses
import json

import pytest

from benchmarks import controls_brumby
from benchmarks.lib import cells

CELL = "brumby-14b-base.decode-2k-128-b48"
OWN = (*controls_brumby.CONFIG_FAULTS, *controls_brumby.TRACED_FAULTS)
REFUSED = tuple(f for f in OWN if f != "state_in_bfloat16")

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in controls_brumby.run(
                CELL, [1, 2], ["sound", *OWN], small=True)})
    return _readings


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 8 * 24


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("fault", REFUSED)
def test_a_fault_of_the_core_is_refused(fault, seed):
    r = readings()[fault, seed]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


@pytest.mark.parametrize("seed", [1, 2])
def test_a_state_in_bfloat16_moves_the_logits_by_its_rounding(seed):
    r = readings()["state_in_bfloat16", seed]
    sound = readings()["sound", seed]
    assert 1000 * sound["logit_err_max"] < r["logit_err_max"] < 1.0, r
    assert r["first_token_equal"] and r["tokens_are_argmax"]


def test_faults_of_the_cached_step_alone_leave_the_first_token():
    """The prefill is sound, so the first token and its logits are."""
    for fault in ("state_not_carried", "normaliser_not_carried"):
        r = readings()[fault, 1]
        assert r["first_token_equal"] and r["positions_over"] < 1.0, r
    assert readings()["gate_dropped", 1]["positions_over"] == 1.0


def test_the_faults_are_planted_for_a_trace_and_taken_back():
    from ompi_tpu.models import retention, transformer

    def held():
        return (retention.chunked, retention.read, retention.write,
                retention.phi, retention._power, retention._quotient,
                retention._state_before, transformer._rope)

    sound = held()
    for fault in controls_brumby.TRACED_FAULTS:
        with controls_brumby.planted(fault):
            assert held() != sound
        assert held() == sound
    with controls_brumby.planted("sound"):
        assert held() == sound


def test_a_faulty_configuration_differs_in_the_one_field():
    from benchmarks.lib import program

    cfg = program.program_config(cells.resolve(CELL).config)
    fields = {"gate_offset_dropped": ("gate_offset", cfg.retention.gate_offset,
                                      0.0),
              "state_in_bfloat16": ("state_dtype", "float32", "bfloat16")}
    for fault, (field, was, wrong) in fields.items():
        faulty = controls_brumby.faulty_config(cfg, fault)
        assert getattr(cfg.retention, field) == was
        assert getattr(faulty.retention, field) == wrong
        assert dataclasses.replace(faulty, retention=cfg.retention) == cfg
    bare = controls_brumby.faulty_config(cfg, "qk_norm_dropped")
    assert cfg.qk_norm == "head" and bare.qk_norm is False
    assert dataclasses.replace(bare, qk_norm="head") == cfg
    assert controls_brumby.faulty_config(cfg, "gate_dropped") == cfg


def test_a_configuration_without_the_core_or_an_unknown_fault_is_refused():
    with pytest.raises(KeyError, match="no power retention"):
        controls_brumby.run("pythia-1.4b-widths.decode-1k-128", [1],
                            ["state_not_carried"], small=True)
    with pytest.raises(ValueError, match="no fault"):
        controls_brumby.run(CELL, [1], ["state_lost"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    assert controls_brumby.main([
        "--workload", CELL, "--seeds", "1", "--faults",
        "sound,degree_one", "--tiny", "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in printed] == ["sound", "degree_one"]
    assert [r["correct"] for r in printed] == [True, False]
    with open(out) as f:
        assert len(f.readlines()) == 2
    assert cells.resolve(CELL).config["entry"]["decoder_logits"]
