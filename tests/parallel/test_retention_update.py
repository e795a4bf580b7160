"""Power retention's cached step as one pallas pass over a layer of the
stack (``ops/retention_update.py``), in TPU interpret mode, against the
``jax.numpy`` ``read`` and ``write`` it stands in for (which the CPU runs);
the write in the stack's own buffer, one layer of it; the kernel under
``retention.core`` against the whole-sequence form and under the planted
faults of ``benchmarks/controls_brumby.py``; and the rule that says which
form a program takes (``retention_update.block``).  Agreement and control
flow only: nothing here is a time.

Both sides are float32 and differ in the order of their sums over the
state's axis alone: 1e-5 of the result's largest value; 1e-6 is read.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import controls_brumby
from ompi_tpu.models import retention
from ompi_tpu.ops import _chip
from ompi_tpu.ops import retention_update as kernel_module
from ompi_tpu.ops.retention_update import block, retention_update
from tests.parallel.compiled import _pallas_calls
from tests.parallel.test_retention import EPS, PARITY, drawn, error, tiny

d = 128
D = retention.state_dim(d)                  # 8320: 65 sweeps of 128 rows


def _case(layers, batch, groups, heads, gate, seed=0):
    """(stack, q, k, v, log g), float32, seeded: a stack of states as large
    as a few hundred positions leave them, and a gate near ``gate``."""
    rng = np.random.default_rng(seed)
    stack = rng.normal(size=(layers, batch, groups, D, d))
    q = rng.normal(size=(batch, groups, heads, d))
    k, v = rng.normal(size=(2, batch, groups, d))
    logg = np.log(gate) * rng.uniform(0.5, 1.5, size=(batch, groups))
    return tuple(jnp.asarray(y, jnp.float32) for y in (stack, q, k, v, logg))


def _operands(q, k, v, logg):
    """What ``core`` hands the kernel of a position."""
    return (retention.phi(q, 1.0 / d), jnp.exp(logg), retention.phi(k), v)


def _close(got, want):
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


def _budget_for(rows, heads):
    """A budget that holds blocks of ``rows`` and no more."""
    return kernel_module._working_set_bytes(rows, d, heads) + 1024


@pytest.mark.parametrize("rows", [8320, 1664, 640],
                         ids=["one-block", "five-blocks", "thirteen-blocks"])
@pytest.mark.parametrize("gate", [1e-4, 0.999], ids=["g-near-0", "g-near-1"])
def test_the_kernel_equals_read_and_write(rows, gate, monkeypatch):
    monkeypatch.setattr(kernel_module, "_VMEM_BUDGET_BYTES",
                        _budget_for(rows, 5))
    assert block(True, jnp.float32, D, d) == (rows, d)
    stack, q, k, v, logg = _case(2, 2, 2, 5, gate, seed=rows)
    g = np.exp(np.asarray(logg))
    assert g.max() < 0.02 if gate < 0.5 else g.min() > 0.998
    z = jnp.zeros(stack.shape[1:4], jnp.float32)
    S = stack[1]
    want_new, _z = retention.write(S, z, k, v, logg)
    want_sums = jnp.einsum("bgnv,bgrn->bgrv", S, retention.phi(q, 1.0 / d),
                           precision=jax.lax.Precision.HIGHEST)
    kernel_module._call.clear_cache()   # traced under the budget it found
    try:
        sums, new = jax.jit(retention_update)(
            stack, jnp.int32(1), *_operands(q, k, v, logg))
    finally:
        kernel_module._call.clear_cache()
    _close(sums, want_sums)
    _close(new[1], want_new)
    assert float(jnp.abs(new[0] - stack[0]).max()) == 0


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_a_layer_is_written_where_it_lies_and_the_others_stay(layer):
    """Layer ``l`` of the stack is the kernel's aliased operand's layer ``l``,
    and every other layer comes back bit for bit."""
    stack, q, k, v, logg = _case(3, 1, 2, 2, 0.9, seed=layer)
    call = jax.jit(retention_update, donate_argnums=0)
    kept = np.asarray(stack)
    _sums, new = call(stack, jnp.int32(layer), *_operands(q, k, v, logg))
    new = np.asarray(new)
    for other in range(3):
        if other != layer:
            np.testing.assert_array_equal(new[other], kept[other])
    assert np.abs(new[layer] - kept[layer]).max() > 0.01


def test_the_stack_is_the_aliased_operand_and_the_layer_a_scalar():
    stack, q, k, v, logg = _case(3, 1, 2, 2, 0.9)
    [call] = _pallas_calls(jax.make_jaxpr(retention_update)(
        stack, jnp.int32(1), *_operands(q, k, v, logg)).jaxpr)
    assert call.params["name"] == "retention_update"
    assert call.params["grid_mapping"].num_index_operands == 1
    [(operand, result)] = call.params["input_output_aliases"]
    given, made = call.invars[operand].aval, call.outvars[result].aval
    assert given.shape == made.shape == stack.shape
    assert given.dtype == made.dtype == jnp.float32
    # nothing as large as a layer is handed over beside it
    others = [v.aval for i, v in enumerate(call.invars) if i != operand]
    assert max(a.size for a in others) <= 8 * D * stack.shape[1] * 2
    assert all(v.aval.dtype == jnp.float32 for v in call.outvars)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None


# ---- the kernel under the core -------------------------------------------------

def _kernel_under_core(monkeypatch):
    """``retention._state_before`` told that it is traced for TPUs, so that a
    float32 state of heads that tile is handed over where it lies (the
    suite's interpret mode runs the kernel here); counts the kernel's
    calls."""
    calls = []
    kernel = kernel_module.retention_update

    def counted(stack, *args):
        calls.append(stack.shape)
        return kernel(stack, *args)

    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    monkeypatch.setattr(kernel_module, "retention_update", counted)
    return calls


def _wide(cfg):
    """The tiny configuration with heads as wide as the kernel's tiles."""
    return dataclasses.replace(cfg, head_width=d)


LAYERS, B, G, H = 2, 2, 2, 4


def _steps(cfg, q, k, v, logg, layer=1):
    """``core`` with a carry, position by position from zero stacks, on
    layer ``layer`` of them.  The step is traced here, under whatever the
    caller has planted, and as one program (interpret-mode kernels between
    eagerly dispatched operations deadlock the CPU client's threads when the
    box is busy)."""
    T = q.shape[1]
    stacks = [jnp.zeros((LAYERS, *shape), jnp.float32)
              for shape in ((B, G, D, d), (B, G, D))]
    step = jax.jit(lambda q, k, v, logg, stacks: retention.core(
        cfg, q, k, v, logg, (stacks, jnp.int32(layer))))
    ys = []
    for t in range(T):
        y, stacks = step(*(x[:, t:t + 1] for x in (q, k, v, logg)), stacks)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), stacks


def test_prefill_then_steps_through_the_kernel_are_the_whole_sequence(
        monkeypatch):
    """As ``test_retention.py`` holds the recurrence to the chunked form, with
    the kernel where ``read`` and ``write`` ran: the steps' outputs and the
    state they leave are the whole-sequence form's."""
    _ref, _shape, cfg, _mesh, _params = tiny()
    cfg = _wide(cfg)
    q, k, v, logg = drawn(7, B=B, H=H, G=G, d=d)
    # unit q and k, as the layer's q/k norm leaves them but for its scale
    q, k = (y / jnp.linalg.norm(y, axis=-1, keepdims=True) for y in (q, k))
    whole, S, z = retention.chunked(q, k, v, logg, 4, cfg.retention.eps)
    calls = _kernel_under_core(monkeypatch)
    got, (S_c, z_c) = _steps(cfg, q, k, v, logg)
    assert calls == [(LAYERS, B, G, D, d)]      # traced once, run T times
    assert error(got, whole) < PARITY
    assert error(S_c[1], S) < PARITY and error(z_c[1], z) < PARITY
    assert float(jnp.abs(S_c[0]).max()) == 0    # the other layer as it was


# which of the traced faults reach a cached step through the kernel: all but
# the one whose ``_state_before`` comes back as an array of zeros, which takes
# the ``jax.numpy`` form (``rope_dropped`` is planted before the core, in
# ``block.mixer``, and a decoder reads it in ``tests/benchmarks``)
THROUGH_THE_KERNEL = {
    "state_not_carried": False, "normaliser_not_carried": True,
    "normaliser_dropped": True, "gate_dropped": True, "degree_one": True,
    "cross_terms_unscaled": True, "group_state_mixed": True}


def test_every_traced_fault_of_the_cached_step_is_placed():
    assert set(THROUGH_THE_KERNEL) == set(
        controls_brumby.TRACED_FAULTS) - {"rope_dropped"}


@pytest.mark.parametrize("fault", sorted(THROUGH_THE_KERNEL))
def test_a_planted_fault_reaches_the_kernels_step(fault, monkeypatch):
    """The controls wrap ``read``, ``write``, ``phi``, ``_power``,
    ``_quotient`` and ``_state_before`` and hand the sound functions other
    arguments: with the kernel under them the steps read other numbers than
    the sound ones, by as much as with the ``jax.numpy`` form under them."""
    _ref, _shape, cfg, _mesh, _params = tiny()
    cfg = _wide(cfg)
    q, k, v, logg = drawn(5, B=B, H=H, G=G, d=d, seed=3)
    q, k = (y / jnp.linalg.norm(y, axis=-1, keepdims=True) for y in (q, k))
    with controls_brumby.planted(fault):
        faulty_jnp, _stacks = _steps(cfg, q, k, v, logg)
    calls = _kernel_under_core(monkeypatch)
    sound, _stacks = _steps(cfg, q, k, v, logg)
    assert len(calls) == 1
    with controls_brumby.planted(fault):
        faulty, _stacks = _steps(cfg, q, k, v, logg)
    assert len(calls) == 1 + THROUGH_THE_KERNEL[fault]
    assert error(faulty, sound) > 100 * PARITY
    assert error(faulty, faulty_jnp) < PARITY


# ---- the rule ------------------------------------------------------------------

def test_the_rule_takes_the_most_rows_that_fit():
    # a head of 128: the whole state twice each way is 17 MB, a fifth fits
    assert block(True, "float32", 8320, 128) == (1664, 128)
    held = kernel_module._working_set_bytes(1664, 128, 5)
    assert 4 * 4 * 1664 * 128 < held < 4 << 20
    # a head of 256 (state_dim 33024, 258 = 2 x 3 x 43 sweeps): 43 sweeps a
    # block are 22 MB, six fit
    assert block(True, "float32", retention.state_dim(256), 256) == (
        6 * 128, 256)
    assert block(True, "float32", 1024, 128) == (1024, 128)
    assert block(True, "float32", 8320, 64) is None         # half a tile
    assert block(True, "float32", 1000, 128) is None        # no whole sweeps
    assert block(True, "bfloat16", 8320, 128) is None
    assert block(False, "float32", 8320, 128) is None
    stack, q, k, v, logg = _case(1, 1, 1, 2, 0.9)
    with pytest.raises(ValueError, match="does not tile"):
        retention_update(stack.astype(jnp.bfloat16), 0,
                         *_operands(q, k, v, logg))


def test_off_the_tpu_the_core_is_the_jnp_form():
    """On this box nothing is traced for TPUs: ``_state_before`` hands out a
    slice and ``core`` calls no kernel, at the kernel's own widths too
    (tier-1's decoders and ``test_retention.py`` run the form they ran)."""
    _ref, _shape, cfg, _mesh, _params = tiny()
    cfg = _wide(cfg)
    assert not _chip._traced_for_tpus()
    stack = jnp.zeros((LAYERS, B, G, D, d), jnp.float32)
    assert not isinstance(retention._state_before(stack, 1),
                          retention.InPlace)
    q, k, v, logg = drawn(1, B=B, H=H, G=G, d=d)
    stacks = [stack, jnp.zeros((LAYERS, B, G, D), jnp.float32)]
    jaxpr = jax.make_jaxpr(lambda *a: retention.core(
        cfg, *a, (stacks, jnp.int32(1))))(q, k, v, logg)
    assert not list(_pallas_calls(jaxpr.jaxpr))
    assert "optimization_barrier" in str(jaxpr)
