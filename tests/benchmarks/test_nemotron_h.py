"""``nemotron-3-nano-30b-a3b``: the yardstick's arithmetic at the real sizes
against counts made by hand, the cut as the configuration's file states it,
what the declared draw of the router and of its selection bias does to a
token's picks, and the reference's counters.  Shapes and numpy only: no device
metric."""

import json
import math

import jax
import numpy as np

from benchmarks.lib import cells, program

CELL = "nemotron-3-nano-30b-a3b.decode-1k-128-b256"
D, V = 2688, 65_536
MAMBA = D * 10_304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * D + D
ATTENTION = 2 * D * 4096 + 2 * D * 256 + D
EXPERT = 2 * D * 1856
SHARED = 2 * D * 3712
ROUTER = D * 128 + 128
ROUTED = 64 * EXPERT + SHARED + ROUTER + D
N_PARAMS = 6 * ROUTED + 6 * MAMBA + 2 * ATTENTION + 2 * V * D + D
B, TP, NEW = 256, 1024, 128


def job():
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    table = program.param_table(made.reference, cell.config)
    made.n_params = sum(math.prod(dims) for dims, _std in table.values())
    return cell, made


def test_the_parameters_by_kind_are_the_hand_counts():
    _cell, made = job()
    assert EXPERT == 9_977_856 and SHARED == 19_955_712     # ISSUE 62
    assert ROUTER == 344_064 + 128
    assert ROUTED == 658_885_376                    # a routed layer here
    assert MAMBA == 38_744_896 and ATTENTION == 23_399_040
    assert V * D == 176_160_768
    assert made.n_params == N_PARAMS == 4_584_903_936   # 9.17 GB in bfloat16


def test_facts_are_the_hand_counts():
    _cell, made = job()
    facts = made.facts()
    # what one token multiplies here: no norm's scale, no bias, no dt, A or
    # D; of its 6 picks 64 of 128 are here on the mean
    mamba = D * 10_304 + 4096 * D + 4 * 6144
    attention = ATTENTION - D
    routed = D * 128 + SHARED + 3 * EXPERT
    active = 6 * mamba + 2 * attention + 6 * routed + V * D
    assert facts["counts"]["active_params"] == active
    assert 580e6 < active - V * D < 582e6           # ISSUE 62: about 581 M
    assert facts["counts"]["attention_width"] == 32 * 128
    assert facts["counts"]["kv_elements"] == 2 * 2 * 128
    assert facts["counts"]["attention_layers"] == 2
    assert facts["counts"]["lookup_params"] == V * D
    assert facts["prefill_flops"] == B * TP * (
        2 * (active - V * D) + 4 * 2 * 4096 * TP) + B * 2 * V * D
    # a sequence's state over the six Mamba layers: 64 x 64 x 128 float32 and
    # the convolution's 3 x 6144 inputs in bfloat16, in bytes over 2
    state = 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert facts["counts"]["state_elements"] == state // 2 == 6_402_048
    assert B * 6 * 64 * 64 * 128 * 4 == 3_221_225_472      # 3.22 GB
    # all but the lookup table, the live K/V at 1088 positions in two
    # layers, and the state once
    assert facts["decode_step_bytes"] == (
        2 * (N_PARAMS - V * D) + 2 * B * (TP + NEW // 2) * 512 * 2
        + B * state) == 12_665_760_256
    # an expert by the harness's form of three matrices: the width at which
    # three would hold what its two do (the reference's ``counts`` says why)
    assert facts["counts"]["routed"] == {
        "layers": 6, "experts": 64, "top_k": 3, "d_model": D,
        "d_expert": 1237}
    assert 0.999 * EXPERT < 3 * D * 1237 <= EXPERT
    assert made.reference.ssm_update(made.shape) == {
        "layers": 6, "heads": 64, "head_dim": 64, "d_state": 128,
        "itemsize": 4}


def test_the_cut_is_depth_experts_held_and_vocabulary():
    cell, _made = job()
    config, row = cell.config, next(
        c for c in cells.load_benchmark()["configs"]
        if c["name"] == "nemotron-3-nano-30b-a3b")
    assert row["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131_072, "torch_dtype": "bfloat16"}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (14, 64, 65_536)
    assert config["experts_held"] == {"first": 0, "count": 64}
    assert config["hybrid_override_pattern"][:14] == "MEMEM*EMEMEM*E"
    # every key of the catalog's row as published but the three cut
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row for row in map(json.loads, f) if row["name"]
                         == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert config["source"] == published["source_url"]
    for key, value in published["config"].items():
        assert key in config["reduced"] or config[key] == value, key
    assert (config["router_experts"], config["ssm_state_dtype"],
            config["attention_use_rope"]) == (128, "float32", False)
    assert (cell.traffic["batch"], cell.traffic["prompt_len"],
            cell.traffic["max_new"], cell.chips) == (B, TP, NEW, 1)
    assert config["counters"]["sequences_a_step"] == B
    for key in ("published", "reduced_why", "deployment", "departures",
                "check_why", "tiny_why", "assumed"):
        assert config[key], key
    said = " ".join(config["assumed"])
    for word in ("rotary", "expand", "float32", "z, xBC, dt",
                 "Seeded weights"):
        assert word in said, word


def test_the_declared_router_renormalises_six_scores_and_its_bias_moves_few():
    """Six of 128 sigmoid scores at logits of deviation ROUTER_SPREAD: 0.8 to
    0.95, so renormalised each weighs about a sixth of 2.5; half the picks
    land on the 64 experts held here; and the declared bias moves one pick
    in three tokens."""
    ref = program.reference(cells.resolve(CELL).config)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1024, D))
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    wg = rng.normal(scale=ref.ROUTER_SPREAD * D ** -0.5, size=(D, 128))
    score = 1 / (1 + np.exp(-(x @ wg)))
    bias = rng.normal(scale=ref.BIAS, size=128)
    plain = np.argsort(-score, -1)[:, :6]
    biased = np.argsort(-(score + bias), -1)[:, :6]
    picked = np.take_along_axis(score, biased, -1)
    assert 0.75 < picked.min(-1).mean() and picked.max() < 1
    weights = 2.5 * picked / picked.sum(-1, keepdims=True)
    assert 0.35 < weights.min() and weights.max() < 0.5
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(plain, biased)])
    assert 0.15 < moved < 0.5, moved
    # and it leaves the load even: no expert at half or at twice its share
    load = np.bincount(biased.ravel(), minlength=128) / (1024 * 6 / 128)
    assert 0.5 < load.min() and load.max() < 1.7, (load.min(), load.max())
    assert 0.45 < (biased < 64).mean() < 0.55


def test_the_counters_are_shares_of_the_picks():
    ref = program.reference(cells.resolve(CELL).config)
    shape = ref.Shape.from_config(cells.resolve(CELL).config)
    weight = np.zeros((1, 4, 128), np.float32)
    # 4 tokens x 6 picks: 8 on the held experts, 16 elsewhere
    for t in range(4):
        weight[0, t, 60 + t:66 + t] = 0.4
    weight[0, 0, 60:62] = 0
    weight[0, 0, 100:102] = 0.4
    assert (weight[..., :64] > 0).sum() == 8 and (weight > 0).sum() == 24
    got = ref.counters(shape, [weight])
    assert got["moe_held_pick_share"] == 8 / 24
    assert got["moe_rows_a_held_expert"] == 256 * 6 * (8 / 24 / 64)
    assert got["moe_empty_group_share"] == (1 - 8 / 24 / 64) ** (256 * 6)
    after = ref.counters(shape, [weight], first=1)
    assert after["moe_held_pick_share"] == 6 / 18
    # the deployment's expectation: half the picks, 12 rows a held expert a
    # step, and no held expert without a row
    assert 256 * 6 * 0.5 / 64 == 12
    assert (1 - 0.5 / 64) ** (256 * 6) < 1e-5
