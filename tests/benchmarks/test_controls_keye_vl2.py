"""``benchmarks/controls_keye_vl2.py``: the faults of a learned sparse
attention's own, planted in the cell's programs at the configuration's
``tiny`` sizes, float32, on the CPU, and read through the runner's own
``compare`` and ``verdict`` with the limits the configuration's file gives.
Whether the limits hold them at the real sizes is the chip's to say (PERF.md
section 2); here each is planted, decodes, and is refused by a limit for
logits."""

import dataclasses
import json

import pytest

from benchmarks import controls_keye_vl2
from benchmarks.lib import cells

CELL = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"
OWN = (*controls_keye_vl2.CONFIG_FAULTS, *controls_keye_vl2.TRACED_FAULTS)

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in controls_keye_vl2.run(
                CELL, [1, 2], ["sound", *OWN], small=True)})
    return _readings


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 8 * 24


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("fault", OWN)
def test_a_fault_of_the_index_is_refused(fault, seed):
    r = readings()[fault, seed]
    assert r["correct"] is False, r
    assert r["shape_ok"] and r["prompt_kept"] and r["first_token_equal"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


def test_keys_that_are_not_carried_show_after_the_first_positions():
    """The prefill's keys are there, so the first generated token (the
    prefill's) and the first cached step (which selects among the prompt's
    keys and its own, which scores what a zero key scores) are nearly right;
    from then on the steps miss the keys of what was generated."""
    r = readings()["index_keys_not_carried", 1]
    assert r["first_token_equal"] and 0.5 < r["positions_over"] < 1.0


def test_the_faults_are_planted_for_a_trace_and_taken_back():
    from ompi_tpu.models import decode, sparse_index, transformer

    def held():
        # ``decode`` imports the norm's name and uses it nowhere: it is held
        # for as long as the module has it
        return (sparse_index.project, sparse_index.scores,
                transformer._qk_norm, getattr(decode, "_qk_norm", None))

    sound = held()
    for fault in controls_keye_vl2.TRACED_FAULTS:
        with controls_keye_vl2.planted(fault):
            assert held() != sound
        assert held() == sound
    with controls_keye_vl2.planted("sound"):
        assert held() == sound


def test_a_faulty_configuration_differs_in_the_one_field():
    from benchmarks.lib import program

    cfg = program.program_config(cells.resolve(CELL).config)
    dropped = controls_keye_vl2.faulty_config(cfg, "selection_dropped")
    assert dropped.index.topk > 1 << 20
    assert dataclasses.replace(dropped, index=cfg.index) == cfg
    assert controls_keye_vl2.faulty_config(
        cfg, "topk_halved").index.topk == 1024
    plain = controls_keye_vl2.faulty_config(cfg, "weights_not_renormalised")
    assert cfg.moe_norm_topk and not plain.moe_norm_topk
    assert controls_keye_vl2.faulty_config(cfg, "selection_shifted") == cfg


def test_a_configuration_without_an_index_or_an_unknown_fault_is_refused():
    with pytest.raises(KeyError, match="no index"):
        controls_keye_vl2.run("pythia-1.4b-widths.decode-1k-128", [1],
                              ["selection_dropped"], small=True)
    with pytest.raises(ValueError, match="no fault"):
        controls_keye_vl2.run(CELL, [1], ["selection_lost"], small=True)


def test_the_command_prints_one_line_a_reading(tmp_path, capsys):
    out = tmp_path / "deep" / "controls.jsonl"
    assert controls_keye_vl2.main([
        "--workload", CELL, "--seeds", "1", "--faults",
        "sound,topk_halved", "--tiny", "--out", str(out)]) == 0
    printed = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in printed] == ["sound", "topk_halved"]
    assert [r["correct"] for r in printed] == [True, False]
    with open(out) as f:
        assert len(f.readlines()) == 2
    assert cells.resolve(CELL).config["entry"]["decoder_logits"]
