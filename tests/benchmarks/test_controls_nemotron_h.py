"""``benchmarks/controls_nemotron_h.py``: the faults of the state-space
mixer's states, of the router and of the ungated experts, and a rotary
embedding where the model has none, planted in the cell's programs at the
configuration's ``tiny`` sizes, float32, on the CPU, and read through the
runner's own ``compare`` and ``verdict`` with the limits the configuration's
file gives.  Whether the limits hold them at the real sizes is the chip's to
say (PERF.md); here each is planted, decodes, and is refused by a limit for
logits, but two: in float32 on both sides a state carried in bfloat16 is a
rounding of a part of one branch, and at sixteen outputs a bias of 0.02 turns
a pick in a few tokens alone, so each is read and has to differ."""

import dataclasses
import json

import pytest

from benchmarks import controls_nemotron_h as own
from benchmarks.lib import cells

CELL = "nemotron-3-nano-30b-a3b.decode-1k-128-b256"
OWN = (*own.PARAM_FAULTS, *own.CONFIG_FAULTS, *own.TRACED_FAULTS)
TOO_SMALL_AT_TINY_SIZES = ("ssm_state_in_bfloat16", "selection_bias_dropped")

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once; the sound program on two seeds."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in own.run(
                CELL, [1], ["sound", *OWN, own.COUNTERS], small=True)
            + own.run(CELL, [2], ["sound"], small=True)})
    return _readings


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 8 * 24


@pytest.mark.parametrize("fault", OWN)
def test_a_fault_is_refused(fault):
    r = readings()[fault, 1]
    assert r["shape_ok"] and r["prompt_kept"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    if fault in TOO_SMALL_AT_TINY_SIZES:
        assert r["logit_err_max"] > 20 * readings()["sound", 1][
            "logit_err_max"], r
        return
    assert r["correct"] is False, r
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


def test_the_counters_are_the_programs_own_routing_over_the_whole_batch():
    r = readings()[own.COUNTERS, 1]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    # two routed layers x 23 cached steps of 8 sequences, 2 picks each of 16
    # experts, 8 of them held
    assert r["routed_calls_counted"] == 2 * 23
    assert 0.3 < r["moe_held_pick_share"] < 0.7
    assert r["moe_rows_a_held_expert"] == pytest.approx(
        8 * 2 * r["moe_held_pick_share"] / 8)
    assert 0 <= r["moe_empty_group_share"] < 0.5
    assert not own._counted


def test_the_faults_are_planted_for_a_trace_and_taken_back():
    from ompi_tpu.models import ssm
    from ompi_tpu.parallel import moe

    def held():
        return (ssm._state_before, ssm._conv_before, ssm.PLAN_KIND.mixer,
                moe.ACTIVATIONS["relu2"], moe.routed_moe)

    sound = held()
    for fault in (*own.TRACED_FAULTS, own.COUNTERS):
        with own.planted(fault):
            assert held() != sound
        assert held() == sound
    with own.planted("sound"):
        assert held() == sound


def test_a_faulty_configuration_differs_in_the_one_field():
    from benchmarks.lib import program

    cfg = program.program_config(cells.resolve(CELL).config)
    fields = {"selection_bias_dropped": ("moe_select_bias", True, False),
              "scale_dropped": ("moe_scale", 2.5, 1.0),
              "not_renormalised": ("moe_norm_topk", True, False)}
    for fault, (field, was, wrong) in fields.items():
        faulty = own.faulty_config(cfg, fault)
        assert getattr(cfg, field) == was and getattr(faulty, field) == wrong
        assert dataclasses.replace(faulty, **{field: was}) == cfg
    half = own.faulty_config(cfg, "ssm_state_in_bfloat16")
    assert (cfg.plan.ssm.state_dtype, half.plan.ssm.state_dtype) == (
        "float32", "bfloat16")
    assert dataclasses.replace(half, plan=dataclasses.replace(
        half.plan, ssm=cfg.plan.ssm)) == cfg
    turned = own.faulty_config(cfg, "rope_applied")
    assert not cfg.plan.attention.rope and turned.plan.attention.rope
    assert dataclasses.replace(turned, plan=dataclasses.replace(
        turned.plan, attention=cfg.plan.attention)) == cfg
    assert set(fields) | {"ssm_state_in_bfloat16", "rope_applied"} == set(
        own.CONFIG_FAULTS)
    assert own.faulty_config(cfg, "conv_state_off") == cfg


def test_another_plan_or_an_unknown_fault_is_refused():
    for other in ("pythia-1.4b-widths.decode-1k-128",
                  "kimi-vl-a3b.decode-16k-256-b32"):
        with pytest.raises(KeyError, match="no plan of single-mixer layers"):
            own.run(other, [1], ["conv_state_off"], small=True)
    with pytest.raises(ValueError, match="no fault"):
        own.run(CELL, [1], ["state_lost"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    assert own.main([
        "--workload", CELL, "--seeds", "1", "--faults",
        "sound,scale_dropped", "--tiny", "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in printed] == ["sound", "scale_dropped"]
    assert [r["correct"] for r in printed] == [True, False]
    with open(out) as f:
        assert len(f.readlines()) == 2
    assert cells.resolve(CELL).config["entry"]["decoder_logits"]
