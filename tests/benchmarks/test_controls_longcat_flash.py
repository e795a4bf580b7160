"""``benchmarks/controls_longcat_flash.py``: the faults of the
shortcut-connected layer, of its identity experts and router and of its
latents' corrections, planted in the cell's programs at the configuration's
``tiny`` sizes, float32, on the CPU, and read through the runner's own
``compare`` and ``verdict`` with the limits the configuration's file gives.
Whether the limits hold them at the real sizes is the chip's to say
(PERF.md); here each is planted, decodes, and is refused by a limit for
logits, but the selection bias: at twelve outputs a bias of 0.002 turns a
pick in a few tokens alone, so it is read and has to differ."""

import dataclasses
import json

import pytest

from benchmarks import controls_longcat_flash as own
from benchmarks.lib import cells

CELL = "longcat-flash-chat.decode-896-128-b160"
OWN = (*own.CONFIG_FAULTS, *own.TRACED_FAULTS)
TOO_SMALL_AT_TINY_SIZES = "selection_bias_dropped"

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
    if fault == TOO_SMALL_AT_TINY_SIZES:
        assert r["logit_err_max"] > 100 * readings()["sound", 1][
            "logit_err_max"], r
        return
    assert r["correct"] is False, r
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


def test_the_counters_are_the_programs_own_routing_over_the_whole_batch():
    r = readings()[own.COUNTERS, 1]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    # one branch x 23 cached steps of 8 sequences, 3 picks each of 16 experts
    # (8 held) and 8 identity experts
    assert r["routed_calls_counted"] == 23
    assert 0.2 < r["moe_identity_pick_share"] < 0.45
    assert 0.2 < r["moe_held_pick_share"] < 0.45
    assert 0 <= r["moe_empty_group_share"] < 0.3
    assert not own._counted


def test_the_faults_are_planted_for_a_trace_and_taken_back():
    from benchmarks.lib import program
    from ompi_tpu.models import plan, transformer
    from ompi_tpu.parallel import moe

    def held():
        return (plan._mlp, plan._mixer_leaves, moe.routed_moe,
                transformer._rmsnorm)

    cfg = program.program_config(program.tiny(cells.resolve(CELL).config))
    sound = held()
    for fault in (*own.TRACED_FAULTS, own.COUNTERS):
        with own.planted(fault, cfg):
            assert held() != sound
        assert held() == sound
    with own.planted("sound", cfg):
        assert held() == sound


def test_a_faulty_configuration_differs_in_the_one_field():
    from benchmarks.lib import program

    cfg = program.program_config(cells.resolve(CELL).config)
    fields = {"selection_bias_dropped": ("moe_select_bias", True, False),
              "renormalised": ("moe_norm_topk", False, True)}
    for fault, (field, was, wrong) in fields.items():
        faulty = own.faulty_config(cfg, fault)
        assert getattr(cfg, field) == was and getattr(faulty, field) == wrong
        assert dataclasses.replace(faulty, **{field: was}) == cfg
    for fault, field, was in (("scale_q_lora_dropped", "q_scale", 2.0),
                              ("scale_kv_lora_dropped", "kv_scale",
                               12 ** 0.5)):
        faulty = own.faulty_config(cfg, fault)
        assert getattr(cfg.plan.mla, field) == was
        assert getattr(faulty.plan.mla, field) == 1.0
        assert dataclasses.replace(faulty, plan=dataclasses.replace(
            faulty.plan, mla=cfg.plan.mla)) == cfg
    moved = own.faulty_config(cfg, "shortcut_reads_second_sublayer")
    assert cfg.plan.branches == tuple(("moe", 2 * i, 2 * i + 1)
                                      for i in range(4))
    assert moved.plan.branches == tuple(("moe", 2 * i + 1, 2 * i + 1)
                                        for i in range(4))
    assert set(fields) | {"scale_q_lora_dropped", "scale_kv_lora_dropped",
                          "shortcut_reads_second_sublayer"} == set(
        own.CONFIG_FAULTS)
    assert own.faulty_config(cfg, "shortcut_dropped") == cfg


def test_another_plan_or_an_unknown_fault_is_refused():
    for other in ("pythia-1.4b-widths.decode-1k-128",
                  "kimi-vl-a3b.decode-16k-256-b32"):
        with pytest.raises(KeyError, match="no plan with a branch"):
            own.run(other, [1], ["shortcut_dropped"], small=True)
    with pytest.raises(ValueError, match="no fault"):
        own.run(CELL, [1], ["shortcut_lost"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    assert own.main([
        "--workload", CELL, "--seeds", "1", "--faults",
        "sound,renormalised", "--tiny", "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in printed] == ["sound", "renormalised"]
    assert [r["correct"] for r in printed] == [True, False]
    with open(out) as f:
        assert len(f.readlines()) == 2
    assert cells.resolve(CELL).config["entry"]["decoder_logits"]
