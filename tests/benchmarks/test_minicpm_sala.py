"""``minicpm-sala``: the yardstick's arithmetic at the real sizes against
counts made by hand, the file against the catalog's row, the counts of
``reduced_why`` against the tree, what the declared draw does to the decays
and the selection, and that the eight older cells never reach the two new
modules.  Shapes, numpy and tiny sizes on the CPU only: no device metric."""

import json
import math
import os

import jax
import numpy as np
import pytest

from benchmarks.lib import cells, program

CELL = "minicpm-sala.decode-16k-512-b24"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
D, V, F, HD = 4096, 73_448, 16_384, 128
SELECTED = 3 * D * 32 * HD + 2 * D * 2 * HD         # wq, wz, wo; wk, wv
LIGHTNING = 5 * D * 32 * HD + 3 * HD                # five matrices, three norms
MLP = 3 * D * F
N_PARAMS = (2 * V * D + D + 4 * (MLP + 2 * D) + SELECTED + 3 * LIGHTNING)
STATES = 3 * 32 * HD * HD                           # a sequence's, float32
OLDER = [w["name"] for w in cells.load_benchmark()["workloads"]
         if w["name"] != CELL]


def job():
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    table = program.param_table(made.reference, cell.config)
    made.n_params = sum(math.prod(dims) for dims, _std in table.values())
    return cell, made


def test_facts_are_the_hand_counts():
    cell, made = job()
    assert (SELECTED, LIGHTNING, MLP) == (52_428_800, 83_886_464,
                                          201_326_592)
    assert made.n_params == N_PARAMS == 1_711_117_440
    facts = made.facts()
    counts = facts["counts"]
    # what one token multiplies: no norm's scale; the table is looked up
    active = 4 * MLP + SELECTED + 3 * (LIGHTNING - 3 * HD) + V * D
    assert counts["active_params"] == active == 1_410_236_416
    assert counts["lookup_params"] == counts["projection_params"] == V * D
    assert counts["attention_layers"] == 1
    # the pooled keys: 2 K/V heads of 128 for every 16 positions
    assert counts["kv_elements"] == 16
    # 64 blocks of 64 rows of 2 x (128 + 128), and three float32 states
    # counted at the cache's two bytes
    assert counts["state_elements"] == 64 * 64 * 512 + 2 * STATES \
        == 5_242_880
    assert facts["prefill_flops"] == 24 * 15_872 * (
        2 * (active - V * D) + 4 * 1 * 2048 * 15_872) + 24 * 2 * V * D
    # every parameter but the table in bfloat16; the pooled keys of the
    # 16,128 positions live on the mean; the selection's rows and the states
    assert facts["decode_step_bytes"] == (
        2 * (N_PARAMS - V * D) + 24 * 16_128 * 16 * 2
        + 24 * (64 * 64 * 512 * 2 + STATES * 4)) == 3_084_593_408
    assert "routed" not in counts


def test_the_cut_is_the_depth_alone_and_every_width_is_the_rows():
    cell, _made = job()
    config = cell.config
    row = next(c for c in cells.load_benchmark()["configs"]
               if c["name"] == config["name"])
    assert row["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert row["source"] == config["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG, encoding="utf-8") as f:
        published = next(r for r in map(json.loads, f)
                         if r["name"] == "MiniCPM-SALA")
    assert published["source_url"] == config["source"]
    differs = {k for k, v in published["config"].items()
               if config.get(k, None) != v}
    assert differs == {"num_hidden_layers"}
    assert config["published"]["num_hidden_layers"] == \
        published["config"]["num_hidden_layers"] == len(config["mixer_types"])
    # one whole period, in the published ratio of one to three
    assert config["mixer_types"][:config["num_hidden_layers"]] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]


def test_the_counts_of_reduced_why_are_the_trees():
    cell, made = job()
    why = cell.config["reduced_why"]
    table = program.param_table(made.reference, cell.config)
    size = {k: math.prod(dims) for k, (dims, _std) in table.items()}
    selected = sum(size[k] for k in ("wq", "wk", "wv", "wz", "wo"))
    lightning = sum(v for k, v in size.items() if k.startswith("lt_")) // 3
    for number in (made.n_params, selected, lightning,
                   size["w1"] // 4 * 3, size["emb"]):
        assert f"{number:,}" in why, number
    assert f"{2 * made.n_params / 1e9:.2f} GB" in why
    assert f"{2 * (made.n_params - size['emb']) / 1e9:.2f} GB" in why


def test_every_assumed_size_is_listed_with_where_it_comes_from():
    config = cells.resolve(CELL).config
    said = " ".join(config["assumed"])
    for key in config["sparse_config"]:
        assert key in said, key
    for word in ("lightning_state_dtype", "mup_denominator", "rand_init",
                 "qk_norm", "use_output_norm", "use_output_gate", "slopes",
                 "InfLLM", "Lightning"):
        assert word in said, word
    for key in ("published", "reduced_why", "deployment", "departures",
                "check_why", "tiny_why"):
        assert config[key] and "TODO" not in str(config[key]), key
    for limit in config["check"].values():
        assert "TODO" not in limit["why"] and len(limit["why"]) > 100


def test_the_cut_layers_forget_within_the_continuation():
    """Layers 1 to 3 of 32: a head's horizon 1 / (s_a f_l) runs from about
    one position to under 300, so a fault in the prompt's state has faded
    from every head before the 512 steps end (PERF.md section 7)."""
    cell, made = job()
    shape = made.shape
    for layer in (1, 2, 3):
        horizon = -1 / np.asarray(made.reference.log_decay(shape, layer))
        assert 1.1 < horizon.min() < 1.4 and 250 < horizon.max() < 290
    last = -1 / np.asarray(made.reference.log_decay(shape, 30))
    assert last.max() > 7000      # the published model's last ones barely do


def test_the_older_cells_never_reach_the_new_kinds():
    for workload in OLDER:
        cfg = program.program_config(cells.resolve(workload).config)
        plan = getattr(cfg, "plan", None)
        assert plan is None or (plan.lightning is None
                                and plan.block_select is None), workload
