"""``benchmarks/controls_phi4_flash.py``: the faults of the selective mixer's
states and of the memory it hands on, of the differential heads, of the
window and its ring, of what a cross layer reads, of the biases and of the
norm, planted in the cell's programs at the configuration's ``tiny`` sizes,
float32, on the CPU, and read through the runner's own ``compare`` and
``verdict`` with the limits the configuration's file gives.  Whether the
limits hold them at the real sizes is the chip's to say (PERF.md); here each
is planted, decodes, and is refused by a limit for logits, but the one that a
decoder shows in its first token alone and the one of the stream's type, which
at float32 sizes is the sound program.  The file's three shared faults are
``test_cell_controls_<cell>.py``'s, through ``controls_cases.py``."""

import json

import pytest

from benchmarks import controls_phi4_flash as own

CELL = "phi-4-mini-flash-reasoning.decode-16k-256-b16"
# a cached step carries no earlier memory: the fault is the prefill's
FIRST_TOKEN_ALONE = "memory_of_previous_step"
# float32 at the tiny sizes: a stream in the compute type is the same program
SAME_AT_FLOAT32 = "stream_in_compute_type"
OWN = (*own.SHARED[1:], *own.PARAM_FAULTS, *own.TRACED_FAULTS)

_readings: dict = {}


def readings() -> dict:
    """(fault, seed) -> the reading: the job built, and each faulty pair of
    decoders traced, once; the sound program on two seeds."""
    if not _readings:
        _readings.update({
            (r["fault"], r["seed"]): r for r in own.run(
                CELL, [1], ["sound", *OWN, *own.CONFIG_FAULTS], small=True)
            + own.run(CELL, [2], ["sound"], small=True)})
    return _readings


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sound_program_is_correct(seed):
    r = readings()["sound", seed]
    assert r["correct"] is True and r["logit_err_max"] < 1e-4, r
    assert r["tokens_checked"] == 8 * 24


@pytest.mark.parametrize("fault", [f for f in OWN if f != FIRST_TOKEN_ALONE])
def test_a_fault_is_refused(fault):
    r = readings()[fault, 1]
    assert r["shape_ok"] and r["prompt_kept"]
    assert r["tokens_are_argmax"]       # the logits are the faulty program's
    assert r["correct"] is False, r
    assert (r["logit_err_median"] > r["logit_err_median_limit"]
            or r["positions_over"] > r["positions_over_limit"]), r
    json.dumps(r)


def test_the_prefills_stale_memory_shows_in_the_first_token_alone():
    r = readings()[FIRST_TOKEN_ALONE, 1]
    assert r["tokens_are_argmax"] and r["logit_err_median"] < 1e-4
    # one position of 24 in each of the 8 checked sequences, at most
    assert r["positions_over"] <= 1 / 24 + 1e-9
    assert r["logit_err_max"] > 1e-2


def test_the_stream_in_the_compute_type_is_the_doors_other_branch():
    """Planted through the file's key: the decoders are made of a plan whose
    stream has no type of its own.  Float32 here, so the reading is the sound
    program's; what it reads in bfloat16 is the chip's to say (PERF.md)."""
    from benchmarks.lib import cells, program

    config = cells.resolve(CELL).config
    assert program.program_config(config).plan.stream_dtype == "float32"
    assert program.program_config(
        {**config, "residual_in_fp32": False}).plan.stream_dtype is None
    r, sound = readings()[SAME_AT_FLOAT32, 1], readings()["sound", 1]
    assert r["correct"] is True
    assert r["logit_err_median"] == pytest.approx(sound["logit_err_median"],
                                                  abs=1e-6)


def test_the_faults_are_the_issues_list():
    assert set(own.FAULTS) == {
        "sound", "all_lower_precision", "attention_layer_off",
        "ffn_layer_off", "differential_term_dropped", "sub_norm_dropped",
        "lambda_init_of_layer_0", "window_unbounded", "window_one_short",
        "ring_not_wrapped", "cross_reads_own_projection", "memory_after_gate",
        "memory_of_previous_step", "memory_unit_off",
        "skip_dropped_from_memory", "ssm_state_not_carried",
        "conv_state_off", "ssm_prefill_state_dropped", "dt_bias_dropped",
        "layernorm_mean_kept", "attention_bias_dropped",
        "stream_in_compute_type"}
    with pytest.raises(ValueError, match="no fault"):
        own.run(CELL, [1], ["rope_applied"], small=True)
