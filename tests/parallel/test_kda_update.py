"""The delta rule's cached step as one pallas pass (``ops/kda_update.py``),
in TPU interpret mode, against the ``jax.numpy`` form it stands in for
(``models/kda.update``'s own, which the CPU runs); the write in place; the
kernel under ``kda.mixer`` against the whole-sequence form and under the
planted faults that replace ``kda.update``; and the rule that says which
form a program takes (``kda_update.block``).  Agreement and control flow
only: nothing here is a time.

Both sides are float32 and differ in the order of their sums over the key
axis alone: 1e-5 of the result's largest value; 1e-7 is read.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import controls_kimi_linear
from benchmarks.lib import cells, program
from ompi_tpu.models import kda
from ompi_tpu.ops import _chip
from ompi_tpu.ops import kda_update as kernel_module
from ompi_tpu.ops.kda_update import block, kda_update
from tests.parallel.compiled import _pallas_calls
from tests.parallel.test_plan import PARITY, error, tiny

CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
K = 128


def _case(batch, heads, seed=0, beta=None):
    """(state, q, k, v, g, beta), float32, seeded: channels that decay by
    e^-20 a position beside channels that hardly decay, q and k as the
    mixer norms them."""
    rng = np.random.default_rng(seed)
    state = rng.normal(size=(batch, heads, K, K))
    q, k, v = rng.normal(size=(3, batch, heads, K))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * K ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(20.0),
                            size=(batch, heads, K)))
    if beta is None:
        beta = rng.uniform(0, 1, size=(batch, heads))
    else:
        beta = np.full((batch, heads), beta)
    return tuple(jnp.asarray(y, jnp.float32)
                 for y in (state, q, k, v, g, beta))


def _jnp_form(*args):
    """``kda.update`` off the TPU: the form the kernel stands in for."""
    assert not _chip._traced_for_tpus()
    return kda.update(*args)


def _close(got, want):
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("batch,heads,beta", [
    pytest.param(1, 8, None, id="one-block"),
    pytest.param(3, 8, None, id="a-block-a-sequence"),
    pytest.param(2, 16, None, id="sixteen-heads-a-block"),
    pytest.param(2, 8, 0.0, id="beta-0-writes-nothing"),
    pytest.param(2, 8, 1.0, id="beta-1-replaces-the-value"),
])
def test_the_kernel_equals_the_jnp_form(batch, heads, beta):
    args = _case(batch, heads, seed=batch + heads, beta=beta)
    assert block(True, jnp.float32, heads, K) == (1, heads, K, K)
    g = np.asarray(args[4])
    assert g.min() < -15 and g.max() > -2e-3      # fast and slow channels
    want_o, want_s = _jnp_form(*args)
    got_o, got_s = jax.jit(kda_update)(*args)
    _close(got_o, want_o)
    _close(got_s, want_s)
    if beta == 0.0:         # the state only decays, and o reads it
        np.testing.assert_allclose(
            got_s, args[0] * jnp.exp(args[4])[..., None], rtol=1e-6)


def test_several_blocks_of_heads_a_sequence(monkeypatch):
    """A budget that holds eight matrices twice each way and no more: two
    blocks a sequence of sixteen heads."""
    monkeypatch.setattr(kernel_module, "_VMEM_BUDGET_BYTES",
                        kernel_module._working_set_bytes(8, K))
    assert block(True, jnp.float32, 16, K) == (1, 8, K, K)
    args = _case(2, 16, seed=5)
    want_o, want_s = _jnp_form(*args)
    kernel_module._call.clear_cache()   # traced under the budget it found
    try:
        got_o, got_s = jax.jit(kda_update)(*args)
    finally:
        kernel_module._call.clear_cache()
    _close(got_o, want_o)
    _close(got_s, want_s)


def test_the_state_is_written_into_the_buffer_it_was_read_from():
    args = _case(2, 8)
    [call] = _pallas_calls(jax.make_jaxpr(kda_update)(*args).jaxpr)
    assert call.params["name"] == "kda_update"
    state_like = [i for i, v in enumerate(call.outvars)
                  if v.aval.shape == args[0].shape]
    assert len(state_like) == 1
    [(operand, result)] = call.params["input_output_aliases"]
    assert result == state_like[0]
    aval = call.invars[operand].aval
    assert (aval.shape, aval.dtype) == (args[0].shape, jnp.float32)
    assert all(v.aval.dtype == jnp.float32 for v in call.outvars)


def _kernel_under_update(monkeypatch):
    """``kda.update`` told that it is traced for TPUs, so that it takes the
    kernel (which the suite's interpret mode runs here); counts the kernel's
    calls."""
    calls = []
    kernel = kernel_module.kda_update

    def counted(*args):
        calls.append(args[0].shape)
        return kernel(*args)

    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    monkeypatch.setattr(kernel_module, "kda_update", counted)
    return calls


def _wide(cfg):
    """The tiny configuration with heads as wide as the kernel's tiles."""
    import dataclasses

    wide = dataclasses.replace(cfg.plan.kda, n_heads=8, head_dim=K, rank=K)
    return dataclasses.replace(
        cfg, plan=dataclasses.replace(cfg.plan, kda=wide))


def _wide_leaves(cfg, seed=3):
    rng = np.random.default_rng(seed)
    lp = {"ln1": jnp.asarray(rng.uniform(0.5, 1.5, size=(cfg.d_model,)),
                             jnp.float32)}
    for name, (dims, std) in kda.leaf_shapes(cfg, cfg.plan.kda).items():
        lp[name] = jnp.asarray(
            rng.uniform(0.5, 1.5, size=dims) if std is None
            else rng.normal(0, std, size=dims), jnp.float32)
    return lp


def _steps(cfg, lp, h):
    """``mixer`` with a carry, position by position from zero states.  The
    step is traced here, under whatever the caller has planted, and as one
    program: interpret-mode kernels between eagerly dispatched operations
    deadlock the CPU client's threads when the box is busy."""
    B, T, _ = h.shape
    conv_shape, state_shape = kda.state_shapes(cfg.plan.kda, B)
    conv = jnp.zeros(conv_shape, jnp.float32)
    state = jnp.zeros(state_shape, jnp.float32)
    step = jax.jit(lambda x, conv, state: kda.mixer(
        cfg, lp, x, carry=(conv, state)))
    outs = []
    for t in range(T):
        o, conv, state = step(h[:, t:t + 1], conv, state)
        outs.append(o)
    return jnp.concatenate(outs, axis=1), conv, state


def test_a_step_through_the_kernel_is_the_next_position(monkeypatch):
    """As ``test_plan_mixers.py``'s of a like name but for the heads' width,
    and with the kernel where the ``jax.numpy`` form ran."""
    _ref, _shape, cfg, _mesh, _params = tiny()
    cfg = _wide(cfg)
    lp = _wide_leaves(cfg)
    B, T = 2, 19
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(B, T, cfg.d_model)), jnp.float32)
    whole, conv, state = kda.mixer(cfg, lp, h)
    calls = _kernel_under_update(monkeypatch)
    got, got_conv, got_state = _steps(cfg, lp, h)
    assert calls == [(B, 8, K, K)]         # traced once, run T times
    assert error(got, whole) < PARITY
    assert error(got_conv, conv) < PARITY
    assert error(got_state, state) < PARITY


@pytest.mark.parametrize("fault", ["state_not_carried", "decay_dropped",
                                   "delta_term_dropped"])
def test_a_fault_planted_over_update_reaches_the_kernel(fault, monkeypatch):
    """The controls replace ``kda.update`` by a wrapper that hands the sound
    function other arguments: with the kernel under it the steps read other
    numbers than the sound ones, by as much as with the ``jax.numpy`` form
    under it."""
    _ref, _shape, cfg, _mesh, _params = tiny()
    cfg = _wide(cfg)
    lp = _wide_leaves(cfg)
    h = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 6, cfg.d_model)), jnp.float32)
    with controls_kimi_linear.planted(fault):
        faulty_jnp, _conv, _state = _steps(cfg, lp, h)
    calls = _kernel_under_update(monkeypatch)
    sound, _conv, _state = _steps(cfg, lp, h)
    assert len(calls) == 1
    with controls_kimi_linear.planted(fault):
        faulty, _conv, _state = _steps(cfg, lp, h)
    assert len(calls) == 2
    assert error(faulty, sound) > 100 * PARITY
    assert error(faulty, faulty_jnp) < PARITY


def _cell_state(**changes):
    """(state dtype, batch, heads, K) of the cell's cached step, from its
    configuration and traffic files, with ``changes`` to the configuration
    as it is published."""
    cell = cells.resolve(CELL)
    config = copy.deepcopy(cell.config)
    for key, value in changes.items():
        if isinstance(value, dict):
            config[key].update(value)
        else:
            config[key] = value
    kd = program.program_config(config).plan.kda
    _conv, state = kda.state_shapes(kd, cell.traffic["batch"])
    return (kd.state_dtype, *state[:3])


# Which form every cached step of a plan's cell takes (PERF.md section 5).
@pytest.mark.parametrize("tpu,changes,takes", [
    pytest.param(True, {}, (1, 32, 128, 128), id="kimi-step-on-the-chip"),
    pytest.param(False, {}, None, id="kimi-step-on-the-cpu"),
    pytest.param(True, {"kda_state_dtype": "bfloat16"}, None,
                 id="a-bfloat16-state"),
    pytest.param(True, {"linear_attn_config": {"head_dim": 64}}, None,
                 id="heads-of-64"),
])
def test_which_update_each_plan_cell_takes(tpu, changes, takes):
    dtype, batch, heads, width = _cell_state(**changes)
    assert (batch, heads) == (384, 32)
    assert block(tpu, dtype, heads, width) == takes
    if takes:
        held = kernel_module._working_set_bytes(takes[1], width)
        # the state's blocks twice each way are nearly all of it
        assert 4 * 4 * heads * width * width < held < 9 << 20


def test_the_rule_takes_the_most_heads_that_fit():
    # heads of 256: a block of 32 is 8 MiB each way and does not fit twice
    # over, 16 with their vectors just do not, 8 do
    assert block(True, "float32", 32, 256) == (1, 8, 256, 256)
    assert block(True, "float32", 12, 128) is None       # no 8 | 12
    assert block(True, "float32", 24, 128) == (1, 24, 128, 128)
    assert block(True, "float32", 64, 128) == (1, 32, 128, 128)
    with pytest.raises(ValueError, match="does not tile"):
        kda_update(*_case(1, 4))


def test_off_the_tpu_update_is_the_jnp_form():
    """On this box nothing is traced for TPUs, under a mesh or under none:
    ``update`` calls no kernel (tier-1's decoders and ``test_plan*.py`` run
    the form they ran)."""
    from jax.sharding import PartitionSpec as P

    args = _case(1, 8)
    assert not list(_pallas_calls(jax.make_jaxpr(kda.update)(*args).jaxpr))
    mesh = jax.make_mesh((1,), ("dp",))
    seen = []

    def local(*args):
        seen.append(_chip._traced_for_tpus())
        return kda.update(*args)

    mapped = jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
    assert not list(_pallas_calls(jax.make_jaxpr(mapped)(*args).jaxpr))
    assert seen == [False]
