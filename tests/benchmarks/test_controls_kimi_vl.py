"""``benchmarks/controls_kimi_vl.py``: the faults of rotary latent attention
and of the router beside it, planted in the cell's programs at the
configuration's ``tiny`` sizes, float32, on the CPU, and read through the
runner's own ``compare`` and ``verdict`` with the limits the configuration's
file gives.  Whether the limits hold them at the real sizes is the chip's to
say (PERF.md); here each is planted, decodes, and is refused by a limit for
logits."""

import dataclasses
import json

import pytest

from benchmarks import controls_kimi_vl
from benchmarks.lib import cells

CELL = "kimi-vl-a3b.decode-16k-256-b32"
OWN = (*controls_kimi_vl.CONFIG_FAULTS, *controls_kimi_vl.TRACED_FAULTS)

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once; the sound program on two seeds."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in controls_kimi_vl.run(
                CELL, [1], ["sound", *OWN], small=True)
            + controls_kimi_vl.run(CELL, [2], ["sound"], small=True)})
    return _readings


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 8 * 24


@pytest.mark.parametrize("fault", OWN)
def test_a_fault_is_refused(fault):
    r = readings()[fault, 1]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


def test_faults_of_the_cached_step_alone_leave_the_first_token():
    """The prefill is sound, so the first token and its logits are."""
    for fault in ("step_position_off_by_one", "latent_cache_lower_precision"):
        r = readings()[fault, 1]
        assert r["first_token_equal"] and r["positions_over"] < 1.0, r
    assert readings()["rotation_dropped", 1]["positions_over"] == 1.0


def test_the_faults_are_planted_for_a_trace_and_taken_back():
    from jax import lax

    from ompi_tpu.models import mla, transformer

    def held():
        return (mla.rotate, mla.mixer, transformer._rmsnorm,
                lax.dynamic_update_slice)

    sound = held()
    for fault in controls_kimi_vl.TRACED_FAULTS:
        with controls_kimi_vl.planted(fault):
            assert held() != sound
        assert held() == sound
    with controls_kimi_vl.planted("sound"):
        assert held() == sound


def test_a_faulty_configuration_differs_in_the_one_field():
    from benchmarks.lib import program

    cfg = program.program_config(cells.resolve(CELL).config)
    fields = {"one_shared_expert_dropped": ("moe_shared", 2816, 1408),
              "selection_bias_dropped": ("moe_select_bias", True, False),
              "scale_dropped": ("moe_scale", 2.446, 1.0),
              "not_renormalised": ("moe_norm_topk", True, False),
              "softmax_for_sigmoid": ("moe_score", "sigmoid", "softmax")}
    assert set(fields) == set(controls_kimi_vl.CONFIG_FAULTS)
    for fault, (field, was, wrong) in fields.items():
        faulty = controls_kimi_vl.faulty_config(cfg, fault)
        assert getattr(cfg, field) == was and getattr(faulty, field) == wrong
        assert dataclasses.replace(faulty, **{field: was}) == cfg
    assert controls_kimi_vl.faulty_config(cfg, "rotation_dropped") == cfg


def test_another_form_or_an_unknown_fault_is_refused():
    for other in ("pythia-1.4b-widths.decode-1k-128",
                  "kimi-linear-48b-a3b.decode-512-128-b384"):
        with pytest.raises(KeyError, match="no plan of rotary latent"):
            controls_kimi_vl.run(other, [1], ["rotation_dropped"], small=True)
    with pytest.raises(ValueError, match="no fault"):
        controls_kimi_vl.run(CELL, [1], ["rotation_lost"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    assert controls_kimi_vl.main([
        "--workload", CELL, "--seeds", "1", "--faults",
        "sound,scale_dropped", "--tiny", "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in printed] == ["sound", "scale_dropped"]
    assert [r["correct"] for r in printed] == [True, False]
    with open(out) as f:
        assert len(f.readlines()) == 2
    assert cells.resolve(CELL).config["entry"]["decoder_logits"]
