"""``kimi-vl-a3b``: the yardstick's arithmetic at the real sizes against
counts made by hand, the two new readers' operations and bytes and their
reading of made-up events, and what the declared draw of the selection bias
does to a token's picks.  Shapes and numpy only: no device metric."""

import math

import jax
import numpy as np
import pytest

from benchmarks.lib import cells, program, scopes, xplane
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event

CELL = "kimi-vl-a3b.decode-16k-256-b32"
PEAKS = device_peaks("TPU v5 lite")
D, V, H = 2048, 163_840, 16
MLA = D * H * 192 + D * 576 + 512 + 512 * H * 256 + H * 128 * D
EXPERT = 3 * D * 1408
DENSE_LAYER = MLA + 3 * D * 11264
ROUTED_LAYER = MLA + D * 64 + 64 + 64 * EXPERT + 2 * EXPERT
N_PARAMS = 2 * V * D + D + 2 * 5 * D + DENSE_LAYER + 4 * ROUTED_LAYER
B, TP, NEW = 32, 16_128, 256


def job():
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    table = program.param_table(made.reference, cell.config)
    made.n_params = sum(math.prod(dims) for dims, _std in table.values())
    return cell, made


def test_the_parameters_by_kind_are_the_hand_counts():
    _cell, made = job()
    assert MLA == 13_763_072
    assert DENSE_LAYER == 82_969_088                    # ISSUE 56: 82.97M
    assert ROUTED_LAYER == 584_843_840                  # 584.8M
    assert 2 * V * D == 671_088_640                     # 671M
    assert made.n_params == N_PARAMS == 3_093_455_616   # 6.19 GB in bfloat16


def test_facts_are_the_hand_counts():
    _cell, made = job()
    facts = made.facts()
    # what one token multiplies: no norm's scale and no selection bias; its
    # 6 picks and the two shared experts
    active = (5 * (MLA - 512) + 3 * D * 11264
              + 4 * (D * 64 + (6 + 2) * EXPERT) + V * D)
    assert facts["counts"]["active_params"] == active == 750_911_488
    assert facts["counts"]["attention_width"] == H * (192 + 128) // 2 == 2560
    assert facts["counts"]["kv_elements"] == 576
    assert facts["counts"]["attention_layers"] == 5
    assert facts["counts"]["lookup_params"] == V * D == 335_544_320
    assert facts["prefill_flops"] == B * TP * (
        2 * (active - V * D) + 4 * 5 * 2560 * TP) + B * 2 * V * D
    # all but the lookup table, and the live latent rows at 16,256 positions
    assert facts["decode_step_bytes"] == (
        2 * (N_PARAMS - V * D) + 5 * B * (TP + NEW // 2) * 576 * 2
    ) == 8_512_128_512                                  # ISSUE 56: 8.5 GB
    assert facts["counts"]["routed"] == {
        "layers": 4, "experts": 64, "top_k": 6, "d_model": D,
        "d_expert": 1408}


def test_the_cut_is_the_depth_alone():
    cell, _made = job()
    config, row = cell.config, next(
        c for c in cells.load_benchmark()["configs"]
        if c["name"] == "kimi-vl-a3b")
    assert row["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 27
    assert config["num_hidden_layers"] == 5
    assert (config["hidden_size"], config["num_attention_heads"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"],
            config["intermediate_size"], config["n_routed_experts"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["n_shared_experts"], config["vocab_size"],
            config["rope_theta"], config["routed_scaling_factor"],
            config["ep_size"], config["first_k_dense_replace"]) == (
        2048, 16, 128, 64, 128, 512, 11264, 64, 1408, 6, 2, 163_840,
        800_000, 2.446, 1, 1)
    assert "experts_held" not in config
    assert (cell.traffic["batch"], cell.traffic["prompt_len"],
            cell.traffic["max_new"], cell.chips) == (B, TP, NEW, 1)
    assert (TP + NEW) % 1024 == 0
    assert config["entry"]["options"]["prefill_tokens"] == 16_384
    for key in ("published", "reduced_why", "deployment", "departures",
                "check_why", "tiny_why", "assumed"):
        assert config[key], key
    said = " ".join(config["assumed"])
    for word in ("pairs", "selection bias", "Seeded weights"):
        assert word in said, word


def test_the_declared_bias_moves_about_one_of_a_tokens_six_picks():
    ref = program.reference(cells.resolve(CELL).config)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, D))
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    wg = rng.normal(scale=ref.ROUTER_SPREAD * D ** -0.5, size=(D, 64))
    score = 1 / (1 + np.exp(-(x @ wg)))
    bias = rng.normal(scale=ref.BIAS, size=64)
    plain = np.argsort(-score, -1)[:, :6]
    biased = np.argsort(-(score + bias), -1)[:, :6]
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(plain, biased)])
    assert 0.6 < moved < 1.4, moved


# ---- the two readers -------------------------------------------------------

@pytest.fixture(scope="module")
def read_metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "latent_read_roofline.py")


@pytest.fixture(scope="module")
def prefill_metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "latent_prefill_roofline.py")


def test_the_latent_read_is_the_live_rows_once_and_bound_by_bytes(read_metric):
    live = TP + NEW / 2
    ops, nbytes = read_metric.costs(B, 5, H, 512, 64, live, 2)
    assert nbytes == B * 5 * live * 576 * 2 == 2_996_305_920
    assert ops == B * 5 * live * H * 2 * (512 + 64 + 512)
    least = read_metric.least_seconds(PEAKS, B, 5, H, 512, 64, live, 2)
    assert least == nbytes / PEAKS["hbm_bytes_per_s"]
    assert 7 * ops / PEAKS["bf16_flops"] < least
    assert 3.6e-3 < least < 3.7e-3


def test_the_prefills_attention_is_the_triangle_and_bound_by_operations(
        prefill_metric):
    ops, nbytes = prefill_metric.costs(B, 5, H, 192, 128, TP, 2)
    assert ops == B * 5 * H * (TP * (TP + 1) // 2) * 2 * 320
    assert nbytes == B * 5 * H * TP * 2 * 320 * 2
    least = prefill_metric.least_seconds(PEAKS, B, 5, H, 192, 128, TP, 2)
    assert least == ops / PEAKS["bf16_flops"]
    assert 1.08 < least < 1.09 and nbytes / PEAKS["hbm_bytes_per_s"] < 0.07


def _run(step_ms: float, prefill_ms: float, jobs: int,
         scope: str = "attention") -> RunData:
    """A traced window of ``jobs`` samples: a ``first`` job, one run of the
    prefill's program, and a ``full`` job, that run again and one of the
    generating program, whose attention takes ``prefill_ms`` a prefill and
    ``step_ms`` over a job's cached steps."""
    cell, made = job()
    events, at = [], 0

    def program_run(name, under, took_ms):
        nonlocal at
        events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                            "jit_decode(1)", at + 1e6, 3e6))
        events.append(Event("/device:TPU:0", xplane.OPS_LINE, name,
                            at + 1e6, 1e6 * took_ms,
                            f"jit(decode)/shard_map/{under}/{scope}/dot"))
        at += 4e6

    for _ in range(jobs):
        for span, programs in (("first", 1), ("full", 2)):
            events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                                at, 4e6 * programs))
            program_run("latent_attention.5", "prefill/layers/jit(run)",
                        prefill_ms)
            if programs == 2:
                program_run("fusion.9", "decode.step/while/body/closed_call/"
                            "layers/jit(run)", step_ms)
    return RunData(durations={}, facts=made.facts(), peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


def test_readings_are_least_time_over_the_time_under_the_scope(
        read_metric, prefill_metric):
    step = read_metric.least_seconds(PEAKS, B, 5, H, 512, 64,
                                     TP + NEW / 2, 2)
    prompt = prefill_metric.least_seconds(PEAKS, B, 5, H, 192, 128, TP, 2)
    # made-up events: the arithmetic is what is held, not a share under 100
    for jobs in (1, 2):
        run = _run(2.0, 1.5, jobs)
        assert read_metric.read(run) == pytest.approx(
            100 * (NEW - 1) * step / 2.0e-3)
        assert prefill_metric.read(run) == pytest.approx(
            100 * prompt / 1.5e-3)


def test_a_run_with_nothing_to_read_reads_as_nothing(read_metric,
                                                     prefill_metric, capsys):
    run = _run(2.0, 1.5, 1, scope="attn_proj")
    assert read_metric.read(run) is None and prefill_metric.read(run) is None
    err = capsys.readouterr().err
    assert "latent_read_roofline" in err and "latent_prefill_roofline" in err
    empty = RunData(durations={}, facts={}, peaks=PEAKS, trace=None,
                    compiles_in_window=0, peak_bytes=None)
    run = _run(2.0, 1.5, 1)
    run.peaks = None
    other = _run(2.0, 1.5, 1)
    other.config = cells.resolve("minicpm-sala.decode-16k-512-b24").config
    for metric in (read_metric, prefill_metric):
        assert metric.read(empty) is None
        assert metric.read(run) is None
        assert metric.read(other) is None
