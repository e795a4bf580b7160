"""``phi-4-mini-flash-reasoning``: the parameters by kind against counts made
by hand (the card's 3.8B), that nothing of the published file is cut, the
yardstick's arithmetic at the real sizes and that it does not overcount a
prefill's attention, what the declared draw does to a token's own logit
through the tied head, that the cell's rows are behind the benchmark's last,
and that the cell runs at its tiny sizes in its own compute type.  No device
metric."""

import json
import math

import jax
import numpy as np
import pytest

from benchmarks.lib import cells, program
from tests.benchmarks.test_harness import measure, meter, tiny  # noqa: F401

CELL = "phi-4-mini-flash-reasoning.decode-16k-256-b16"
NAME = "phi-4-mini-flash-reasoning"
D, V, F, DI, N, R = 2560, 200_064, 10_240, 5120, 16, 160
MLP = 2 * D * F + F * D
MAMBA = (D * 2 * DI + 4 * DI + DI + DI * (R + 2 * N) + R * DI + DI + N * DI
         + DI + DI * D)
SELF = D * (2560 + 2 * 1280) + (2560 + 2 * 1280) + 2560 * D + D + 4 * 64 + 128
CROSS = D * 2560 + 2560 + 2560 * D + D + 4 * 64 + 128
GMU = D * DI + DI * D
NORM = 2 * D
N_PARAMS = V * D + 32 * MLP + 9 * MAMBA + 9 * SELF + 7 * CROSS + 7 * GMU + 65 * NORM
B, TP, NEW = 16, 16_128, 256


def job():
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    table = program.param_table(made.reference, cell.config)
    made.n_params = sum(math.prod(dims) for dims, _std in table.values())
    return cell, made


def test_the_parameters_by_kind_are_the_hand_counts():
    """ISSUE 77's table, recounted from the reference's leaves."""
    _cell, made = job()
    assert (V * D, MLP, MAMBA, SELF, CROSS, GMU, NORM) == (
        512_163_840, 78_643_200, 41_241_600, 19_668_864, 13_112_704,
        26_214_400, 5_120)
    assert made.reference.param_counts(made.shape) == {
        "embedding": V * D, "mlp": MLP, "mamba": MAMBA,
        "self_attention": SELF, "cross_attention": CROSS, "gmu": GMU,
        "norm": NORM}
    assert made.n_params == N_PARAMS == 3_852_562_944   # 7.71 GB in bfloat16
    kinds = made.shape.kinds
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert (kinds[16], kinds[17], kinds[18], kinds[31]) == (
        "mamba", "full", "gmu", "cross")
    table = program.param_table(made.reference, _cell.config)
    assert "head" not in table and table["sel_alog"][0] == (9, N, DI)
    # the program's own leaves are the reference's, name and shape
    from ompi_tpu.models import plan

    cfg = program.program_config(_cell.config)
    ours = {name: (n, *dims) for n, leaves in plan._kinds(cfg).values()
            for name, (dims, _std) in leaves.items()}
    ours.update({name: (n, D) for name, n in plan._norms(cfg.plan).items()})
    assert ours == {k: dims for k, (dims, _std) in table.items()
                    if k not in ("emb", "lnf")}


def test_nothing_of_the_published_file_is_cut():
    cell, _made = job()
    config, row = cell.config, next(
        c for c in cells.load_benchmark()["configs"] if c["name"] == NAME)
    assert row["reduced"] == config["reduced"] == []
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(r for r in map(json.loads, f)
                         if r["name"] == "Phi-4-mini-flash-reasoning")
    assert config["source"] == row["source"] == published["source_url"]
    for key, value in published["config"].items():
        assert config[key] == value, key
    for key, value in config["published"].items():
        assert key == "torch_dtype" or config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (32, V)
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["ssm_state_dtype"],
            config["kv_cache_dtype"], config["param_dtype"]) == (
        16, 4, 2, "float32", "bfloat16", "bfloat16")
    assert (cell.traffic["batch"], cell.traffic["prompt_len"],
            cell.traffic["max_new"], cell.chips) == (B, TP, NEW, 1)
    for key in ("published", "reduced_why", "deployment", "departures",
                "check_why", "tiny_why", "assumed"):
        assert config[key], key


def test_facts_are_the_hand_counts_and_do_not_overcount():
    _cell, made = job()
    facts = made.facts()
    counts = facts["counts"]
    # a prompt's token multiplies rows 0 to 17 and nothing above them
    lower = 9 * MAMBA + 9 * SELF + 18 * MLP
    assert counts["active_params"] == lower + V * D
    assert 1.96e9 < lower < 1.97e9                      # ISSUE 77: 1.96 G
    assert counts["projection_params"] == V * D
    assert counts["lookup_params"] == 0
    assert counts["kv_elements"] == 2 * 20 * 64 == 2560
    # layer 17 and the seven cross layers each read the one cache
    assert counts["attention_layers"] == 8
    # what a prompt's attention needs a token: layer 17's causal pairs and
    # eight windows of 512, scores 64 wide and values 128, 40 heads
    needs = 2 * 40 * (64 + 128) * ((TP + 1) / 2 + 8 * 512)
    assert 186e6 < needs < 188e6                        # ISSUE 77: 187 MFLOP
    counted = 4 * 8 * counts["attention_width"] * TP
    assert 0.99 * needs < counted <= needs      # a whole width: 0.3% steps
    assert facts["prefill_flops"] == B * TP * (2 * lower + counted) \
        + B * 2 * V * D
    assert 1.05e15 < facts["prefill_flops"] < 1.07e15   # 1.06 PFLOP
    state = (9 * (DI * N * 4 + 3 * DI * 2) + 8 * 512 * 2 * 1280 * 2)
    assert counts["state_elements"] == state // 2
    live = TP + NEW / 2
    assert facts["decode_step_bytes"] == (
        2 * N_PARAMS + 8 * B * live * 2560 * 2 + B * state)
    assert 18.7e9 < facts["decode_step_bytes"] < 18.9e9     # 18.8 GB
    assert made.reference.selective_scan(made.shape) == {
        "layers": 9, "d_inner": DI, "d_state": N}
    assert made.reference.shared_kv(made.shape) == {
        "readers": 7, "kv_elements": 2560}


def test_the_new_readers_costs_are_the_hand_counts():
    shared = cells.load_reader(cells.BENCH_DIR, "shared_kv_read_roofline")
    scan = cells.load_reader(cells.BENCH_DIR, "selective_scan_roofline")
    live = TP + NEW / 2
    assert shared.cost_bytes(B, 7, 2560, live, 2) == 7 * B * live * 5120
    assert 9.3e9 < shared.cost_bytes(B, 7, 2560, live, 2) < 9.4e9
    assert scan.cost_bytes(B, TP, 9, DI, N) == B * TP * 9 * (
        3 * DI + 2 * N) * 4
    # 143 GB a prefill: 0.17 s at 819 GB/s
    assert 0.17 < scan.cost_bytes(B, TP, 9, DI, N) / 819e9 < 0.18


def test_the_declared_draw_is_led_by_the_embedding_and_says_so():
    """The embedding at 8 beside 64 branches of about unit size: the stream at
    the last norm is about 11, so a token's own logit through the tied head
    lies some thirty-five deviations up and every greedy continuation is one
    token repeated, as the configuration's file says; at 0.25 it lay under
    two, where the largest of 200,064 lies near five (the readings that chose
    between them: the reference's comment)."""
    cell = cells.resolve(CELL)
    ref = program.reference(cell.config)
    assert ref.EMB == cell.config["embedding_deviation"] == 8
    assert cell.config["tiny"]["embedding_deviation"] == 0.25
    stream = (ref.EMB ** 2 + 64) ** 0.5
    lead = D ** 0.5 * ref.EMB / stream          # |e|^2 / rms(h) over |e|
    assert 30 < lead < 40 and np.sqrt(2 * np.log(V)) > 4.9
    assert D ** 0.5 * 0.25 / (0.25 ** 2 + 64) ** 0.5 < 2
    assert cell.config["residual_in_fp32"] is True
    assert program.program_config(cell.config).plan.stream_dtype == "float32"


def test_the_cells_rows_are_behind_the_benchmarks_last():
    """The configuration, the cell and the seven rows are the last of their
    lists, and the cell's name the last of every accepted row's ``workloads``
    that lists it: an addition, as ``BENCHMARK.json``'s rules ask."""
    bench = cells.load_benchmark()
    assert bench["configs"][-1]["name"] == NAME
    assert bench["configs"][-1]["reduced"] == []
    assert bench["workloads"][-1] == {
        **bench["workloads"][-1], "name": CELL, "config": NAME,
        "traffic": "decode-16k-256-b16", "chips": 1}
    assert [m["name"] for m in bench["per_layer"][-7:]] == [
        "shared_kv_step_share", "window_step_share", "gmu_step_share",
        "prefill_window_ms", "prefill_upper_rows_ms",
        "shared_kv_read_roofline", "selective_scan_roofline"]
    listed = [m for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert len(listed) == 26 + 7
    assert all(m["workloads"][-1] == CELL for m in listed)


def test_the_cell_runs_tiny_on_the_cpu_in_its_compute_type(meter):
    """``test_harness.py``'s test of every cell of ``BENCHMARK.json``, of
    this one: bfloat16 products under the float32 stream."""
    cell = tiny(cells.resolve(CELL))
    for trace in (False, True):
        line = measure(cell, meter, trace=trace)
        assert line["correct"] is True, line["checks"]
        assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {
        r["name"] for r, _ in cell.per_layer
        if r["source"] != "device_trace" and r["unit"] != "GiB"}
