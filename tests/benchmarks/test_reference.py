"""The yardstick against the program at a tiny size, and its arithmetic
against hand counts.  CPU only: agreement and counts, no device metric."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmarks.lib import cells, costs, program
from benchmarks.reference import dense
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel.mesh import make_mesh

CONFIGS = {c["name"]: cells.load_json(f"{cells.BENCH_DIR}/../{c['file']}")
           for c in cells.load_benchmark()["configs"]}


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict at tiny sizes, the program's config in float32,
    a one-device mesh, parameters from the benchmark's initializer)."""
    config = program.tiny(CONFIGS["pythia-1.4b-widths"])
    cfg = dataclasses.replace(program.program_config(config),
                              compute_dtype="float32")
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    params = program.init_params(
        config, program.param_shardings(config, cfg, mesh), seed=5)
    return config, cfg, mesh, params


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_shapes_equal_the_programs(name):
    """Catches drift between the program's tree and the yardstick's."""
    config = program.tiny(CONFIGS[name])
    ours = dense.param_shapes(dense.Shape.from_config(config))
    theirs = {k: v.shape for k, v in
              tfm.init_params(program.program_config(config)).items()}
    assert ours == theirs


def test_initializer_is_seeded_and_scaled(tiny):
    config, cfg, mesh, params = tiny
    shardings = program.param_shardings(config, cfg, mesh)
    again = program.init_params(config, shardings, seed=5)
    other = program.init_params(config, shardings, seed=6)
    assert all(np.array_equal(params[k], again[k]) for k in params)
    assert not np.array_equal(params["wq"], other["wq"])
    table = dense.param_init(dense.Shape.from_config(config))
    for name, (dims, std) in table.items():
        assert params[name].shape == dims
        assert params[name].dtype == np.float32
        if std is None:
            assert (np.asarray(params[name]) == 1).all()
        else:
            assert np.asarray(params[name]).std() == pytest.approx(std,
                                                                   rel=0.1)


def test_reference_loss_equals_make_loss_fn_in_float32(tiny):
    config, cfg, mesh, params = tiny
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(4, cfg.seq)).astype(np.int32)
    theirs = float(jax.jit(tfm.make_loss_fn(cfg, mesh))(params, tokens))
    shape = dense.Shape.from_config(config)
    for block in (1, 4):
        ours = dense.loss(shape, params, tokens, block=block)
        # both float32: what is left is the order of summation
        assert ours == pytest.approx(theirs, rel=1e-5)


def test_decode_check_accepts_the_decoder_and_rejects_a_swapped_token(tiny):
    config, cfg, mesh, params = tiny
    shape = dense.Shape.from_config(config)
    prompt_len, max_new = 12, 8
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab, size=(2, prompt_len)).astype(np.int32)
    answer = np.asarray(make_decoder(cfg, mesh, max_new=max_new)(params,
                                                                 prompts))
    deficits = np.asarray(dense.token_deficits(shape, params, answer,
                                               prompt_len))
    assert deficits.shape == (2, max_new)
    assert deficits.max() < 1e-3    # float32 on both sides: the same argmax

    logits = np.asarray(dense.logits(shape, params, answer))
    at = prompt_len + 3                             # scored at position at-1
    swapped = answer.copy()
    swapped[0, at] = logits[0, at - 1].argmin()
    bad = np.asarray(dense.token_deficits(shape, params, swapped, prompt_len))
    assert bad[0, 3] > 1.0


def test_train_flops_per_token_is_the_hand_count():
    shape = dense.Shape.from_config(CONFIGS["pythia-1.4b-widths"])
    assert shape.n_layers == 6
    n_params = sum(int(np.prod(d)) for d in dense.param_shapes(shape).values())
    assert n_params == 405_039_104
    assert (costs.train_flops_per_token(n_params, 6, 2048, 2048)
            == 6 * 405_039_104 + 12 * 6 * 2048 * 2048)


def test_prefill_flops_is_the_hand_count():
    # 2 per block parameter and 4·L·D·T per position, and one projection
    # onto the vocabulary per prompt
    n, V, L, D, B, T = 1000 + 7 * 5, 7, 2, 5, 3, 11
    assert costs.prefill_flops(n, V, L, D, B, T) == (
        B * T * (2 * 1000 + 4 * L * D * T) + B * 2 * V * D)


def test_decode_bytes_are_parameters_plus_live_kv():
    params = {"a": np.zeros((10, 3), np.float32), "b": np.zeros(7, np.int8)}
    assert costs.tree_count(params) == 37
    assert costs.tree_bytes(params) == 127
    L, B, Tp, N, D = 6, 48, 1024, 128, 2048
    live = 2 * L * B * (Tp + N / 2) * D * 2      # k and v, bfloat16
    assert costs.kv_bytes(L, B, Tp + N / 2, D, 2) == live
    assert costs.decode_step_bytes(127, L, B, Tp, N, D, 2) == 127 + live
