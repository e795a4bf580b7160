"""``brumby-14b-base``: the yardstick's arithmetic at the real sizes against
counts made by hand, the reference against a second, position-by-position
writing of it, what the declared draw of the gate does to a step's decay,
and that the seven older cells never reach the new module.  Shapes, numpy
and tiny sizes on the CPU only: no device metric."""

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program

CELL = "brumby-14b-base.decode-2k-128-b48"
D, V, F, HD = 5120, 151_936, 17_408, 128
LAYER = (2 * D * 40 * HD + 2 * D * 8 * HD + D * 8 + 3 * D * F
         + 2 * D + 2 * HD)              # matrices, the gate, four norms
N_PARAMS = 2 * V * D + D + 4 * LAYER
STATE = 8 * 8320 * (HD + 1)             # a sequence's S and z in one layer
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
    assert LAYER == 330_352_896
    assert made.n_params == N_PARAMS == 2_877_241_344
    facts = made.facts()
    # what one token multiplies: no norm's scale; the table is looked up
    active = 4 * (LAYER - 2 * D - 2 * HD) + V * D
    assert facts["counts"]["active_params"] == active
    # no K/V cache; the harness asks a whole number above zero, so 1
    assert facts["counts"]["kv_elements"] == 1
    # the quadratic form's causal half: 2 x 5120 x T a position and layer
    assert facts["prefill_flops"] == 48 * 2048 * (
        2 * (active - V * D) + 4 * 4 * 2560 * 2048) + 48 * 2 * V * D
    assert facts["counts"]["state_elements"] == 4 * STATE == 34_344_960
    # every parameter but the table in bfloat16, and the float32 state once
    assert facts["decode_step_bytes"] == (
        2 * (N_PARAMS - V * D) + 48 * 4 * STATE * 4
        + 4 * 48 * (2048 + 64) * 1 * 4) == 10_794_512_384
    assert "routed" not in facts["counts"]


def test_the_cut_is_the_depth_alone():
    cell, _made = job()
    config, row = cell.config, next(
        c for c in cells.load_benchmark()["configs"]
        if c["name"] == "brumby-14b-base")
    assert row["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 40
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["intermediate_size"],
            config["vocab_size"], config["rope_theta"],
            config["rms_norm_eps"]) == (4, D, 40, 8, HD, F, V, 1_000_000,
                                        1e-6)
    assert config["retention_state_dim"] >= HD * (HD + 1) // 2
    assert config["retention_state_dtype"] == "float32"
    assert config["retention_gate_offset"] == pytest.approx(math.log(999))
    assert cell.traffic["batch"] == 48 and cell.chips == 1
    assert (cell.traffic["prompt_len"], cell.traffic["max_new"]) == (2048, 128)
    for key in ("assumed", "departures", "deployment", "reduced_why",
                "tiny_why", "check_why"):
        assert config[key], key


# ---- the reference against a second writing of it ------------------------------

def _second_writing(shape, p, x):
    """The retention half a position at a time, through the symmetric power
    as the reference's docstring writes it (``u_a u_b`` for ``a <= b``, the
    off-diagonal entries times sqrt 2) and a state a head: numpy, float64."""
    ref = program.reference(cells.resolve(CELL).config)
    q, k, v, c = (np.asarray(y, np.float64)
                  for y in ref.projections(shape, p, x))
    B, T, H, d = q.shape
    logg = np.diff(c, axis=1, prepend=0.0)
    rows, cols = np.triu_indices(d)
    weight = np.where(rows == cols, 1.0, math.sqrt(2.0))

    def phi(u):
        return u[..., rows] * u[..., cols] * weight

    S = np.zeros((B, H, rows.size, d))
    z = np.zeros((B, H, rows.size))
    out = np.zeros((B, T, H, d))
    for t in range(T):
        g = np.exp(logg[:, t])
        S = g[..., None, None] * S + phi(k[:, t])[..., None] * v[:, t][
            ..., None, :]
        z = g[..., None] * z + phi(k[:, t])
        pq = phi(q[:, t])
        out[:, t] = np.einsum("bhn,bhnv->bhv", pq, S) / (
            np.einsum("bhn,bhn->bh", pq, z) + shape.retention_eps * d)[
                ..., None]
    return out.reshape(B, T, H * d) @ np.asarray(p["wo"], np.float64)


@pytest.mark.parametrize("length", [5, 16, 23])
def test_the_reference_is_its_recurrence_written_out(length, monkeypatch):
    config = program.tiny(cells.resolve(CELL).config)
    ref = program.reference(config)
    shape = ref.Shape.from_config(config)
    # a block of queries shorter than the sequence, so that blocks are read
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    rng = np.random.default_rng(length)
    table = ref.param_init(shape)
    p = {k: jnp.asarray(rng.normal(size=dims[1:]) * (std or 1.0)
                        + (1.0 if std is None else 0.0), jnp.float32)
         for k, (dims, std) in table.items() if k in ref.LAYER_LEAVES}
    x = jnp.asarray(rng.normal(size=(2, length, shape.d_model)), jnp.float32)
    got = np.asarray(ref._retention(shape, p, x))
    want = _second_writing(shape, p, x)
    assert np.abs(got - want).max() < 2e-4 * want.std()


# ---- the declared draw ---------------------------------------------------------

def test_the_declared_gate_decays_from_nine_tenths_to_all_but_nothing():
    cell = cells.resolve(CELL)
    ref = program.reference(cell.config)
    shape = ref.Shape.from_config(cell.config)
    dims, std = ref.param_init(shape)["wd"]
    assert dims == (4, D, 8) and std == pytest.approx(ref.GATE * D ** -0.5)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, D))
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    gamma = x @ rng.normal(scale=std, size=(D, 8))
    assert gamma.std() == pytest.approx(ref.GATE, rel=0.05)
    g = 1 / (1 + np.exp(-(shape.gate_offset + gamma)))
    # one step in a thousand under 0.92, one in a thousand over 0.99999
    assert 0.88 < np.quantile(g, 0.001) < 0.94
    assert np.quantile(g, 0.999) > 0.9999
    assert np.median(g) == pytest.approx(0.999, abs=2e-4)
    # a state forgets by 1/e over a few hundred positions
    assert 200 < -1 / np.log(g).mean() < 500


# ---- the older cells -----------------------------------------------------------

@pytest.mark.parametrize("workload", OLDER)
def test_an_older_cell_never_reaches_the_new_module(workload, monkeypatch):
    """A configuration without the field lowers its programs with
    ``models/retention.py`` out of reach: no line of it is on their path."""
    from ompi_tpu.models import block

    monkeypatch.setitem(sys.modules, "ompi_tpu.models.retention", None)
    monkeypatch.delattr("ompi_tpu.models.retention", raising=False)
    cell = cells.resolve(workload)
    config = program.tiny(cell.config)
    traffic = {**cell.traffic, "batch": 4, "seq": 32, "prompt_len": 16,
               "max_new": 4}
    job = cell.runner.build(config, traffic, jax.devices()[:cell.chips])
    assert job.cfg.retention is None
    assert all("retention" not in m.__name__
               for m in block.mechanisms(job.cfg))
    for fn, args in job.programs().values():
        assert "retention" not in fn.lower(*args).as_text()
