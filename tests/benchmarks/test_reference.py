"""Every configuration's yardstick against the program at the configuration's
tiny sizes, and the yardstick's arithmetic against hand counts.  No
reference is named here: each configuration of ``BENCHMARK.json`` is held to
the one its file names (``program.reference``), so a model of another family
gets these tests by being added.  CPU only: agreement and counts, no device
metric."""

import copy
import math

import jax
import numpy as np
import pytest

from benchmarks.lib import cells, costs, program

BENCH = cells.load_benchmark()
CONFIGS = {c["name"]: cells.load_json(f"{cells.BENCH_DIR}/../{c['file']}")
           for c in BENCH["configs"]}

_built: dict[str, tuple] = {}


def tiny(name: str):
    """(reference module, configuration at tiny sizes, the program's config
    in float32, the configuration's mesh of CPU devices, parameters from the
    benchmark's initializer), made once a configuration."""
    if name not in _built:
        config = copy.deepcopy(program.tiny(CONFIGS[name]))
        # float32 on both sides: what is left is the order of summation
        config["entry"]["options"]["compute_dtype"] = "float32"
        ref = program.reference(config)
        cfg = program.program_config(config)
        mesh = program.mesh(config, jax.devices()[:config["chips"]])
        params = program.init_params(
            ref, config, program.param_shardings(config, cfg, mesh), seed=5)
        _built[name] = ref, config, cfg, mesh, params
    return _built[name]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_shapes_equal_the_programs(name):
    """Catches drift between the program's tree and the yardstick's."""
    config = program.tiny(CONFIGS[name])
    ref = program.reference(config)
    ours = {k: dims for k, (dims, _std) in
            program.param_table(ref, config).items()}
    init = program.import_dotted(config["entry"]["init_params"])
    theirs = {k: v.shape for k, v in
              init(program.program_config(config)).items()}
    assert ours == theirs


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_initializer_is_seeded_and_scaled(name):
    ref, config, cfg, mesh, params = tiny(name)
    shardings = program.param_shardings(config, cfg, mesh)
    again = program.init_params(ref, config, shardings, seed=5)
    other = program.init_params(ref, config, shardings, seed=6)
    assert all(np.array_equal(params[k], again[k]) for k in params)
    drawn = [k for k, (_dims, std) in
             program.param_table(ref, config).items() if std is not None]
    assert drawn and not any(np.array_equal(params[k], other[k])
                             for k in drawn)
    for leaf, (dims, std) in program.param_table(ref, config).items():
        assert params[leaf].shape == dims
        assert params[leaf].dtype == np.dtype(config["param_dtype"])
        if std is None:
            assert (np.asarray(params[leaf]) == 1).all()
        else:
            assert np.asarray(params[leaf]).std() == pytest.approx(std,
                                                                   rel=0.1)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_loss_equals_the_programs_in_float32(name):
    ref, config, cfg, mesh, params = tiny(name)
    shape = ref.Shape.from_config(config)
    seq = config["max_position_embeddings"]
    tokens = np.random.default_rng(0).integers(
        0, shape.vocab, size=(4, seq)).astype(np.int32)
    make_loss_fn = program.import_dotted(config["entry"]["loss_fn"])
    theirs = float(jax.jit(make_loss_fn(cfg, mesh))(params, tokens))
    for block in (1, 4):
        ours = ref.loss(shape, params, tokens, block=block)
        assert ours == pytest.approx(theirs, rel=1e-5)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_decode_check_accepts_the_decoder_and_rejects_a_swapped_token(name):
    ref, config, cfg, mesh, params = tiny(name)
    shape = ref.Shape.from_config(config)
    prompt_len, max_new = 12, 8
    prompts = np.random.default_rng(1).integers(
        0, shape.vocab, size=(2, prompt_len)).astype(np.int32)
    make_decoder = program.import_dotted(config["entry"]["decoder"])
    answer = np.asarray(make_decoder(cfg, mesh, max_new=max_new)(params,
                                                                 prompts))
    deficits = np.asarray(ref.token_deficits(shape, params, answer,
                                             prompt_len))
    assert deficits.shape == (2, max_new)
    assert deficits.max() < 1e-3    # float32 on both sides: the same argmax

    logits = np.asarray(ref.logits(shape, params, answer))
    at = prompt_len + 3                             # scored at position at-1
    swapped = answer.copy()
    swapped[0, at] = logits[0, at - 1].argmin()
    bad = np.asarray(ref.token_deficits(shape, params, swapped, prompt_len))
    assert bad[0, 3] > 1.0


MUST_COUNT = {"active_params", "projection_params", "kv_elements"}
MAY_COUNT = {"attention_layers", "attention_width", "state_elements",
             "lookup_params", "routed"}
ROUTED = {"layers", "experts", "top_k", "d_model", "d_expert"}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_are_of_the_references_own_tree(name):
    """``counts(shape)`` at the real sizes: the three keys every reference
    gives, of the five it may give none that the tree cannot bear out (no
    more active parameters than the tree holds, a projection among them, K
    and V of one position; no more layers that attend or route than there
    are layers, no more experts' parameters than are stored; no lookup
    table larger than what the tree holds beside the projection), and no
    key beyond them, which no cost function would read."""
    config = CONFIGS[name]
    ref = program.reference(config)
    shape = ref.Shape.from_config(config)
    stored = sum(math.prod(dims) for dims, _std in
                 program.param_table(ref, config).values())
    counts = ref.counts(shape)
    assert MUST_COUNT <= set(counts) <= MUST_COUNT | MAY_COUNT
    assert all(isinstance(counts[k], int) and counts[k] > 0
               for k in MUST_COUNT)
    assert counts["projection_params"] == shape.vocab * shape.d_model
    assert counts["projection_params"] < counts["active_params"] <= stored
    whole = program.counts(ref, shape)
    assert set(whole) - {"routed"} == (MUST_COUNT | MAY_COUNT) - {"routed"}
    assert {k: whole[k] for k in counts} == counts
    assert 0 < whole["attention_layers"] <= shape.n_layers
    assert whole["attention_width"] > 0 and whole["state_elements"] >= 0
    assert isinstance(whole["lookup_params"], int)
    assert 0 <= whole["lookup_params"] <= stored - counts["projection_params"]
    # a table that a token multiplies is no lookup table: the active
    # parameters and the table together are no more than the tree
    assert counts["active_params"] + whole["lookup_params"] <= stored
    if "routed" in counts:
        routed = counts["routed"]
        assert set(routed) == ROUTED
        assert all(isinstance(v, int) and v > 0 for v in routed.values())
        assert routed["layers"] <= shape.n_layers
        assert routed["top_k"] <= routed["experts"]
        one_expert = 3 * routed["d_model"] * routed["d_expert"]
        assert routed["layers"] * routed["experts"] * one_expert <= stored
        assert (routed["layers"] * routed["top_k"] * one_expert
                < counts["active_params"])


def test_a_reference_that_says_nothing_more_counts_every_layer():
    """The defaults of the keys a reference may leave out."""
    class Ref:
        @staticmethod
        def counts(shape):
            return {"active_params": 9, "projection_params": 2,
                    "kv_elements": 4, "attention_layers": 3}

    class Shape:
        n_layers, d_model = 7, 5

    assert program.counts(Ref, Shape) == {
        "active_params": 9, "projection_params": 2, "kv_elements": 4,
        "attention_layers": 3, "attention_width": 5, "state_elements": 0,
        "lookup_params": 0}


# ---- the arithmetic, against counts made by hand ---------------------------

# What ``facts()`` returned for the two Pythia configurations before a
# reference had ``counts`` (the parent of the PR that added them): every
# parameter active, the tied embedding the projection, K and V as wide as
# the model.  ``train_mfu``, ``prefill_mfu`` and ``decode_hbm_share`` are
# these integers over a time.  OLMoE's are what its reference's ``counts``
# gave before a reference could say which layers attend (the parent of the
# PR that let it): 7 layers, all of them attending and routed, the
# parameters stored in bfloat16.  ``decode_step_bytes`` alone is not the
# parent's: since PR 37 a cached step's parameters are counted as the step
# reads them.  The Pythia configurations store float32 and compute in
# bfloat16, so a step reads half of the stored bytes, the tied table once,
# as the projection; OLMoE's untied embedding is a lookup table and leaves
# the count (48 rows of it a step).
HAND_COUNTS = {
    "pythia-1.4b-widths": dict(
        n_params=405_039_104,           # 6 layers of 50.3M and 103M embedding
        flops_per_token=6 * 405_039_104 + 12 * 6 * 2048 * 2048,
        prefill_flops=48 * 1024 * (2 * (405_039_104 - 50304 * 2048)
                                   + 4 * 6 * 2048 * 1024)
        + 48 * 2 * 50304 * 2048,
        decode_step_bytes=2 * 405_039_104
        + 2 * 6 * 48 * (1024 + 64) * 2048 * 2),
    "pythia-6.9b-widths": dict(
        n_params=1_011_912_704,         # 4 layers of 201.3M and 206.6M embedding
        flops_per_token=6 * 1_011_912_704 + 12 * 4 * 4096 * 2048,
        prefill_flops=48 * 1024 * (2 * (1_011_912_704 - 50432 * 4096)
                                   + 4 * 4 * 4096 * 1024)
        + 48 * 2 * 50432 * 4096,
        decode_step_bytes=2 * 1_011_912_704
        + 2 * 4 * 48 * (1024 + 64) * 4096 * 2),
    "olmoe-1b-7b": dict(
        # 7 layers of 4 projections, two norms' scales, a router and 64
        # experts of three matrices; embedding, head and the last norm
        n_params=7 * (4 * 2048 * 2048 + 2 * 2048 + 2 * 2048 + 2048 * 64
                      + 64 * 3 * 2048 * 1024) + 2 * 50304 * 2048 + 2048,
        # a token multiplies 8 of the 64 experts, and the head once
        prefill_flops=48 * 1024 * (
            2 * 7 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024)
            + 4 * 7 * 2048 * 1024) + 48 * 2 * 50304 * 2048,
        decode_step_bytes=2 * (3_143_034_880 - 50304 * 2048)
        + 2 * 7 * 48 * (1024 + 64) * 2048 * 2),
}
# the parent's ``facts()``, printed (CPU box, shapes only), and under
# ``decode_step_bytes`` the parent's less what a step does not read: the
# float32 half of the Pythia parameters (1,620,156,416 and 4,047,650,816
# bytes) and OLMoE's embedding in bfloat16 (206,045,184 bytes)
PARENT_FACTS = {
    "pythia-1.4b-widths": dict(
        n_params=405039104, flops_per_token=2732224512,
        prefill_flops=32173222526976,
        decode_step_bytes=4187070464.0 - 1620156416 // 2),
    "pythia-6.9b-widths": dict(
        n_params=1011912704, flops_per_token=6474129408,
        prefill_flops=82486826631168,
        decode_step_bytes=7470202880.0 - 4047650816 // 2),
    "olmoe-1b-7b": dict(
        n_params=3143034880, prefill_flops=49165790871552,
        decode_step_bytes=9280802816.0 - 206045184),
}


@pytest.mark.parametrize("name", sorted(HAND_COUNTS))
def test_facts_of_the_pythia_configurations_are_the_hand_counts(name):
    """Through the runners' own ``facts()`` at the real sizes (shapes only:
    nothing is placed or run), at the sizes of both traffic files; a
    configuration pinned without ``flops_per_token`` is one that no cell
    trains, and is held at the decode mix alone."""
    config, want = CONFIGS[name], HAND_COUNTS[name]
    assert want == PARENT_FACTS[name]
    ref = program.reference(config)
    table = program.param_table(ref, config)
    params = {leaf: jax.ShapeDtypeStruct(dims, config["param_dtype"])
              for leaf, (dims, _std) in table.items()}
    assert costs.tree_count(params) == want["n_params"]
    devices = jax.devices()[:config["chips"]]

    class Counted:
        """The stream of a train job, as ``facts()`` reads it."""
        def stats(self):
            return {"batches": 7, "starved": 2}

    mixes = ("train-2k", "decode-1k-128")
    for mix in mixes if "flops_per_token" in want else mixes[1:]:
        traffic = cells.load_json(f"{cells.BENCH_DIR}/traffic/{mix}.json")
        runner = cells.load_module(
            f"{cells.BENCH_DIR}/runners/{traffic['runner']}.py")
        job = runner.build(config, traffic, devices)
        job.n_params, job.params = want["n_params"], params
        job.stream, job.stream_warm = Counted(), {"batches": 3, "starved": 2}
        facts = job.facts()
        for key in facts.keys() & want.keys():
            assert facts[key] == want[key], key
            assert facts[key] == int(facts[key])
        assert facts["counts"] == program.counts(ref, job.shape)
        if "stream" in facts:
            assert facts["stream"] == {"batches": 4, "starved": 0}
        assert facts.keys() & {"flops_per_token", "prefill_flops"}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_lookup_table_the_reference_names_leaves_a_cached_steps_bytes(name):
    """Through the decode runner's ``facts()``, shapes only: the same
    configuration under a reference that counts 1000 more elements of a
    table that a step only looks rows up in reads that many elements fewer a
    step, at the narrower of the stored and the computing type, and nothing
    else of ``facts()`` moves."""
    config = CONFIGS[name]
    traffic = cells.load_json(f"{cells.BENCH_DIR}/traffic/decode-1k-128.json")
    runner = cells.load_module(
        f"{cells.BENCH_DIR}/runners/{traffic['runner']}.py")
    job = runner.build(config, traffic, jax.devices()[:config["chips"]])
    job.n_params = sum(math.prod(dims) for dims, _std in
                       program.param_table(job.reference, config).values())
    before, ref = job.facts(), job.reference

    class WithTable:
        @staticmethod
        def counts(shape):
            counted = ref.counts(shape)
            return {**counted, "lookup_params":
                    counted.get("lookup_params", 0) + 1000}

    job.reference = WithTable
    after = job.facts()
    narrower = min(jax.numpy.dtype(dtype).itemsize for dtype in (
        config["param_dtype"], config["entry"]["options"]["compute_dtype"]))
    assert (before["decode_step_bytes"] - after["decode_step_bytes"]
            == 1000 * narrower)
    assert (after["counts"]["lookup_params"]
            == before["counts"]["lookup_params"] + 1000)
    for key in before.keys() - {"decode_step_bytes", "counts"}:
        assert after[key] == before[key], key


def test_costs_count_only_what_the_counts_say():
    """A routed model with an untied head: 1000 active block parameters, a
    projection of 35 and an embedding that counts nothing; K and V narrower
    than the model."""
    active, proj, L, D, B, T, S = 1000 + 35, 35, 2, 5, 3, 11, 13
    assert costs.train_flops_per_token(active, L, D, S) == (
        6 * 1035 + 12 * L * D * S)
    # 2 per block parameter and 4·L·D·T per position, and one projection
    # onto the vocabulary per prompt
    assert costs.prefill_flops(active, proj, L, D, B, T) == (
        B * T * (2 * 1000 + 4 * L * D * T) + B * 2 * 35)


def test_decode_bytes_are_parameters_as_read_plus_live_kv():
    params = {"a": np.zeros((10, 3), np.float32), "b": np.zeros(7, np.int8)}
    assert costs.tree_count(params) == 37
    # float32 stored and bfloat16 read: half the bytes; bfloat16 stored and
    # float32 computed: the step reads what is stored; a table of 7 that a
    # step only looks rows up in leaves the count
    assert costs.step_param_bytes(37, 0, 4, 4) == 148
    assert costs.step_param_bytes(37, 0, 4, 2) == 74
    assert costs.step_param_bytes(37, 0, 2, 4) == 74
    assert costs.step_param_bytes(37, 7, 4, 2) == 60
    L, B, Tp, N, D = 6, 48, 1024, 128, 2048
    live = 2 * L * B * (Tp + N / 2) * D * 2      # k and v, bfloat16
    assert costs.kv_bytes(L, B, Tp + N / 2, 2 * D, 2) == live
    assert costs.decode_step_bytes(127, L, B, Tp, N, 2 * D, 2) == 127 + live
    # grouped K/V heads: a quarter of the elements, a quarter of the bytes
    assert costs.kv_bytes(L, B, Tp + N / 2, 2 * D // 4, 2) == live / 4


def test_layers_that_do_not_attend_hold_a_state_and_no_cache():
    """A model of 9 layers of which 2 attend, 32 query heads of 64 over 8
    K/V heads, and 7 layers that keep the last 3 positions of 2048 channels
    a sequence: the attention terms count the 2 layers at the query heads'
    width, the cache the 2 layers at the K/V heads', and a cached step
    reads every sequence's state once."""
    attend, D, S, B, Tp, N = 2, 32 * 64, 2048, 48, 1024, 128
    kv, state = 2 * 8 * 64, 7 * 3 * 2048
    assert costs.train_flops_per_token(1000, attend, D, S) == (
        6 * 1000 + 12 * 2 * 2048 * 2048)
    assert costs.prefill_flops(1000 + 35, 35, attend, D, B, Tp) == (
        B * Tp * (2 * 1000 + 4 * 2 * 2048 * Tp) + B * 2 * 35)
    cache = 2 * B * (Tp + N / 2) * kv * 2
    assert costs.decode_step_bytes(127, attend, B, Tp, N, kv, 2) == (
        127 + cache)
    assert costs.decode_step_bytes(127, attend, B, Tp, N, kv, 2, state) == (
        127 + cache + B * 7 * 3 * 2048 * 2)
