"""``benchmarks/controls_deepseek_v32.py``: the faults of an indexed latent
layer and of the group-limited router beside it, planted in the cell's
programs at the configuration's ``tiny`` sizes, float32, on the CPU, and read
through the runner's own ``compare`` and ``verdict`` with the limits the
configuration's file gives.  Whether the limits hold them at the real sizes
is the chip's to say (PERF.md); here each is planted, decodes, and is refused
by a limit for logits."""

import dataclasses
import json

import pytest

from benchmarks import controls_deepseek_v32 as controls_v32
from benchmarks.lib import cells

CELL = "deepseek-v3.2-exp.decode-16k-512-b8"
OWN = (*controls_v32.CONFIG_FAULTS, *controls_v32.TRACED_FAULTS)

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once; the sound program on two seeds."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in controls_v32.run(
                CELL, [1], ["sound", *OWN, "counters"], small=True)
            + controls_v32.run(CELL, [2], ["sound"], small=True)})
    return _readings


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 8 * 24


# over 32 positions the blended frequencies turn the slow pairs by a few
# hundredths of a radian less: read, and for the chip's 16,384 to refuse
FAINT_WHEN_SHORT = ("yarn_dropped",)


def test_a_fault_that_needs_long_sequences_still_shows():
    r = readings()["yarn_dropped", 1]
    assert r["tokens_are_argmax"] and r["first_token_equal"]
    assert r["logit_err_median"] > 1e-3 and r["positions_over"] > 0.05, r
    assert r["logit_err_max"] > 3 * r["logit_err_position_limit"]


@pytest.mark.parametrize("fault", [f for f in OWN
                                   if f not in FAINT_WHEN_SHORT])
def test_a_fault_is_refused(fault):
    r = readings()[fault, 1]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


def test_a_fault_of_the_cached_step_alone_leaves_the_first_token():
    """The prefill is sound, so the first token and its logits are; and the
    selection binds only past ``index_topk`` positions (16: the prompt's 8
    and the first 8 steps read every row)."""
    r = readings()["index_keys_not_carried", 1]
    assert r["first_token_equal"] and r["positions_over"] < 1.0, r


def test_the_counters_are_the_routers_over_the_whole_batch():
    r = readings()["counters", 1]
    assert r["correct"] is True
    # 8 sequences x 23 cached steps x 4 routed layers
    assert r["routed_calls_counted"] == 23 * 4
    # 8 of 16 experts are held: half of the picks, by symmetry
    assert 0.35 < r["moe_held_pick_share"] < 0.65
    # a token leaves a held expert out with 12/16: 0.75^8 = 0.1 of the
    # (step, layer, held expert) triples have no row
    assert 0.02 < r["moe_empty_group_share"] < 0.25


def test_the_faults_are_planted_for_a_trace_and_taken_back():
    from ompi_tpu.models import mla, sparse_index
    from ompi_tpu.parallel import moe

    def held():
        return (mla.rotate, mla._index_rotation, sparse_index.project,
                moe.routed_moe)

    sound = held()
    for fault in (*controls_v32.TRACED_FAULTS, controls_v32.COUNTERS):
        with controls_v32.planted(fault):
            assert held() != sound
        assert held() == sound
    with controls_v32.planted("sound"):
        assert held() == sound


def test_a_faulty_configuration_differs_in_the_one_field():
    from benchmarks.lib import program

    cfg = program.program_config(cells.resolve(CELL).config)
    ml = cfg.plan.mla
    fields = {"group_limit_dropped": ("moe_groups", (8, 4), None),
              "selection_bias_dropped": ("moe_select_bias", True, False),
              "scale_dropped": ("moe_scale", 2.5, 1.0),
              "shared_expert_off": ("moe_shared", 2048, 0)}
    for fault, (field, was, wrong) in fields.items():
        faulty = controls_v32.faulty_config(cfg, fault)
        assert getattr(cfg, field) == was and getattr(faulty, field) == wrong
        assert dataclasses.replace(faulty, **{field: was}) == cfg
    latent = {"selection_dropped": ("index", ml.index.topk, 1 << 30),
              "topk_halved": ("index", 2048, 1024)}
    for fault, (_field, was, wrong) in latent.items():
        faulty = controls_v32.faulty_config(cfg, fault).plan.mla
        assert ml.index.topk == was and faulty.index.topk == wrong
        assert dataclasses.replace(
            faulty, index=dataclasses.replace(faulty.index, topk=was)) == ml
    dropped = controls_v32.faulty_config(cfg, "mscale_dropped").plan.mla
    assert dropped.yarn.softmax_factor == 1 and ml.yarn.softmax_factor > 1.8
    assert (dropped.frequencies() == ml.frequencies()).all()
    assert set(fields) | set(latent) | {"mscale_dropped"} == set(
        controls_v32.CONFIG_FAULTS)
    assert controls_v32.faulty_config(cfg, "yarn_dropped") == cfg


def test_another_form_or_an_unknown_fault_is_refused():
    for other in ("pythia-1.4b-widths.decode-1k-128",
                  "kimi-vl-a3b.decode-16k-256-b32"):
        with pytest.raises(KeyError, match="no plan of indexed latent"):
            controls_v32.run(other, [1], ["selection_dropped"], small=True)
    with pytest.raises(ValueError, match="no fault"):
        controls_v32.run(CELL, [1], ["selection_lost"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    assert controls_v32.main([
        "--workload", CELL, "--seeds", "1", "--faults",
        "sound,scale_dropped", "--tiny", "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in printed] == ["sound", "scale_dropped"]
    assert [r["correct"] for r in printed] == [True, False]
    with open(out) as f:
        assert len(f.readlines()) == 2
    assert cells.resolve(CELL).config["entry"]["decoder_logits"]
