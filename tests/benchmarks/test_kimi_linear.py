"""``kimi-linear-48b-a3b``: the yardstick's arithmetic at the real sizes
against counts made by hand, and what the declared draw of the selection bias
does to a token's picks.  Shapes and numpy only: no device metric."""

import math

import jax
import numpy as np

from benchmarks.lib import cells, program

CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
D, V, HK = 2304, 163_840, 32 * 128
KDA = (4 * D * HK + 2 * (D * 128 + 128 * HK) + D * 32 + 3 * 4 * HK
       + 32 + HK + 128)                 # A_log, dt_bias and the head norm
MLA = D * 32 * 192 + D * 576 + 512 + 512 * 32 * 256 + 32 * 128 * D
EXPERT = 3 * D * 1024
ROUTED = D * 256 + 256 + 128 * EXPERT + EXPERT      # router, bias, held, shared
N_PARAMS = (2 * V * D + D + 2 * 5 * D + 4 * KDA + MLA + 3 * D * 9216
            + 4 * ROUTED)


def job():
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    table = program.param_table(made.reference, cell.config)
    made.n_params = sum(math.prod(dims) for dims, _std in table.values())
    return cell, made


def test_facts_are_the_hand_counts():
    cell, made = job()
    assert made.n_params == N_PARAMS == 4_660_423_552
    facts = made.facts()
    # what one token multiplies here: no norm's scale, no A_log or dt_bias,
    # no selection bias; 4 of its 8 picks on the mean, the shared expert
    active = (4 * (KDA - 32 - HK - 128) + (MLA - 512) + 3 * D * 9216
              + 4 * (D * 256 + (4 + 1) * EXPERT) + V * D)
    assert facts["counts"]["active_params"] == active == 772_259_840
    assert facts["prefill_flops"] == 384 * 512 * (
        2 * (active - V * D) + 4 * 1 * 5120 * 512) + 384 * 2 * V * D
    # a sequence's fixed-size state over the 4 KDA layers, in bytes: a
    # float32 matrix a head and the three convolutions' last three inputs in
    # bfloat16; ``decode_step_bytes`` has one itemsize, the cache's 2
    state = 4 * (32 * 128 * 128 * 4 + 3 * 3 * HK * 2)
    assert facts["counts"]["state_elements"] * 2 == state == 8_683_520
    assert facts["decode_step_bytes"] == (
        2 * (N_PARAMS - V * D)                  # all but the lookup table
        + 1 * 384 * (512 + 64) * 576 * 2        # the live latent cache
        + 384 * state) == 12_155_148_032
    assert facts["counts"]["routed"] == {
        "layers": 4, "experts": 128, "top_k": 4, "d_model": D,
        "d_expert": 1024}


def test_the_cut_is_the_depth_and_the_experts_held_alone():
    cell, _made = job()
    config, row = cell.config, next(
        c for c in cells.load_benchmark()["configs"]
        if c["name"] == "kimi-linear-48b-a3b")
    assert row["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts"]
    assert config["published"]["num_hidden_layers"] == 27
    assert config["num_hidden_layers"] == 5
    assert config["num_experts"] == 256         # the router's width
    assert config["experts_held"] == {"first": 0, "count": 128}
    assert (config["hidden_size"], config["vocab_size"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_token"], config["kv_lora_rank"]) == (
                2304, 163_840, 9216, 1024, 8, 512)
    assert cell.traffic["batch"] == 384 and cell.chips == 1
    assert (cell.traffic["prompt_len"], cell.traffic["max_new"]) == (512, 128)


def test_the_declared_bias_moves_about_one_of_a_tokens_eight_picks():
    ref = program.reference(cells.resolve(CELL).config)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, D))
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    wg = rng.normal(scale=ref.ROUTER_SPREAD * D ** -0.5, size=(D, 256))
    score = 1 / (1 + np.exp(-(x @ wg)))
    bias = rng.normal(scale=ref.BIAS, size=256)
    plain = np.argsort(-score, -1)[:, :8]
    biased = np.argsort(-(score + bias), -1)[:, :8]
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(plain, biased)])
    assert 0.8 < moved < 1.2, moved
