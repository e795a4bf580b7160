"""Mamba-2's cached step as one pallas pass (``ops/ssm_update.py``), in TPU
interpret mode, against the ``jax.numpy`` form it stands in for
(``models/ssm._mix``'s carried branch, which the CPU runs); the kernel under
``_mix`` with the planted fault that replaces ``ssm._state_before``; and the
rule that says which form a program takes (``ssm.in_place``).  Agreement and
control flow only: nothing here is a time.

The new state is the same float32 arithmetic in the same order on both
sides, rounded once: bit-equal, or a unit in the last place where a backend
fuses the product and the sum.  ``y`` differs in where its float32 sums
round (the kernel reads the stored state through ``C`` and decays the sum;
the ``jax.numpy`` form sums the decayed state): 1e-5 of the result's
largest value; 4e-7 is read.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import controls_granite_h
from benchmarks.lib import cells, program
from ompi_tpu.models import ssm
from ompi_tpu.ops import _chip

N = 128
CELLS = {13: "granite-4.0-h-small.decode-512-128-b160",
         12: "nemotron-3-nano-30b-a3b.decode-1k-128-b256",
         5: "falcon-h1-34b.decode-128-64-b192"}


def _case(batch, heads, width, groups, dtype, dt=None, seed=0):
    """(state, x, dt, a, b, c): a bfloat16 state, the position's vectors in
    ``dtype`` as the convolution leaves them, dt log-uniform in [1e-3, 1e-1]
    (or ``dt`` everywhere) and A uniform in [1, 16] as the model draws them."""
    rng = np.random.default_rng(seed)
    state = rng.normal(size=(batch, heads, width, N))
    x = rng.normal(size=(batch, heads, width))
    if dt is None:
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                size=(batch, heads)))
    else:
        dt = np.full((batch, heads), dt)
    a = -rng.uniform(1, 16, size=(heads,))
    b, c = rng.normal(size=(2, batch, groups, N))
    return (jnp.asarray(state, jnp.bfloat16), jnp.asarray(x, dtype),
            jnp.asarray(dt, jnp.float32), jnp.asarray(a, jnp.float32),
            jnp.asarray(b, dtype), jnp.asarray(c, dtype))


def _jnp_form(state, x, dt, a, b, c):
    """``_mix``'s carried branch off the TPU, line for line: the form the
    kernel stands in for."""
    f32 = jnp.float32
    B, H, P, _ = state.shape
    G = b.shape[1]
    h = state.astype(f32).reshape(B, G, H // G, P, N)
    dth = dt.reshape(B, G, H // G)
    xh = x.astype(f32).reshape(B, G, H // G, P) * dth[..., None]
    h = (h * jnp.exp(dth * a.reshape(G, H // G))[..., None, None]
         + xh[..., None] * b.astype(f32).reshape(B, G, 1, 1, N))
    y = jnp.einsum("bgrpn,bgn->bgrp", h, c.astype(f32).reshape(B, G, N),
                   precision="highest").reshape(B, H, P)
    return y, h.reshape(B, H, P, N).astype(state.dtype)


def _units_apart(got, want) -> int:
    """The most units in the last place between two bfloat16 arrays."""
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.bfloat16
    bits = [np.asarray(y).view(np.int16).astype(np.int32) for y in (got, want)]
    return int(np.abs(bits[0] - bits[1]).max())


@pytest.mark.parametrize("batch,heads,width,groups,dtype,dt,layer", [
    # a block of the state is a sequence's heads; of B and C eight sequences'
    # rows, of which these batches are a part
    pytest.param(3, 8, 16, 1, "float32", None, None, id="its-own-buffer"),
    pytest.param(2, 8, 32, 2, "bfloat16", None, 1,
                 id="a-layer-of-a-stack-two-groups"),
    pytest.param(1, 16, 64, 2, "float32", 1e3, 0, id="decays-of-0"),
    pytest.param(2, 8, 16, 1, "bfloat16", 1e-9, None, id="decays-near-1"),
])
def test_the_kernel_equals_the_jnp_form(batch, heads, width, groups, dtype,
                                        dt, layer):
    from ompi_tpu.ops.ssm_update import block, ssm_update

    state, *now = _case(batch, heads, width, groups, dtype, dt,
                        seed=batch + heads)
    assert block(True, state.dtype, heads, width, N, groups) == (
        1, heads, width, N)
    decay = np.exp(np.asarray(now[1]) * np.asarray(now[2]))
    if dt == 1e3:
        assert decay.max() == 0.0
    elif dt == 1e-9:
        assert decay.min() > 1 - 1e-7
    want_y, want_s = _jnp_form(state, *now)
    if layer is None:
        got_y, got_s = jax.jit(ssm_update)(state, *now)
    else:
        others = [jnp.full_like(state, 3 + i) for i in range(3)]
        others[layer] = state
        got_y, stack = jax.jit(ssm_update)(jnp.stack(others), *now,
                                           layer=jnp.int32(layer))
        got_s = stack[layer]
        for i in set(range(3)) - {layer}:       # not touched
            assert bool((stack[i] == 3 + i).all())
    assert got_y.shape == want_y.shape and got_y.dtype == jnp.float32
    assert float(jnp.abs(got_y - want_y).max()) < 1e-5 * float(
        jnp.abs(want_y).max())
    assert _units_apart(got_s, want_s) <= 1
    if dt == 1e3:       # nothing of the old state is left, and y reads none
        np.testing.assert_array_equal(
            np.asarray(got_s, np.float32), np.asarray(_jnp_form(
                jnp.zeros_like(state), *now)[1], np.float32))


def _layer():
    """(sizes, leaves, input, zero carry) of one small mixer whose state the
    kernel takes: 8 heads of 16 over two groups, carried in bfloat16."""
    sz = ssm.Mamba2(d_ssm=128, d_state=N, n_groups=2, n_heads=8, d_conv=4,
                    chunk=8, state_dtype="bfloat16")
    rng = np.random.default_rng(3)
    D, B = 32, 2
    lp = {"ssm_in": rng.normal(0, D ** -0.5, size=(D, sz.in_dim)),
          "ssm_out": rng.normal(0, 128 ** -0.5, size=(sz.d_ssm, D)),
          "conv_w": ssm._conv_w(rng, (sz.d_conv, sz.conv_dim)),
          "conv_b": np.zeros((sz.conv_dim,)),
          "a_log": ssm._a_log(rng, (sz.n_heads,)),
          "dt_bias": ssm._dt_bias(rng, (sz.n_heads,)),
          "ssm_d": np.ones((sz.n_heads,)), "ssm_norm": np.ones((sz.d_ssm,))}
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
    u = jnp.asarray(rng.normal(size=(B, 5, D)), jnp.float32)
    carry = (jnp.zeros((B, sz.d_conv - 1, sz.conv_dim), jnp.float32),
             jnp.zeros((B, sz.n_heads, sz.head_dim, N), sz.state_dtype))
    return sz, lp, u, carry


def _steps(sz, lp, u, carry):
    """``_mix`` with a carry, position by position, as one traced program a
    step (under whatever the caller has planted)."""
    step = jax.jit(lambda x, conv, state: ssm._mix(
        sz, 1e-5, lp, x, (conv, state, None)))
    outs = []
    for t in range(u.shape[1]):
        s, *carry = step(u[:, t:t + 1], *carry)
        outs.append(s)
    return jnp.concatenate(outs, axis=1), carry[1]


def test_the_kernel_under_mix_and_the_planted_state_that_reaches_it(
        monkeypatch):
    """Told that it is traced for TPUs, ``_mix`` takes the kernel (which the
    suite's interpret mode runs here) and its steps are the ``jax.numpy``
    form's; ``ssm_state_not_carried`` replaces ``ssm._state_before``, and
    with the kernel under it the steps start from zeros as they do with the
    ``jax.numpy`` form under it."""
    from ompi_tpu.ops import ssm_update as kernel_module

    sz, lp, u, carry = _layer()
    want, want_state = _steps(sz, lp, u, carry)
    with controls_granite_h.planted("ssm_state_not_carried"):
        faulty_jnp, _ = _steps(sz, lp, u, carry)
    calls = []
    kernel = kernel_module.ssm_update
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    monkeypatch.setattr(kernel_module, "ssm_update", lambda *args: (
        calls.append(args[0].shape), kernel(*args))[1])
    got, got_state = _steps(sz, lp, u, carry)
    assert calls == [want_state.shape]      # traced once, run five times
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert _units_apart(got_state, want_state) <= 1
    with controls_granite_h.planted("ssm_state_not_carried"):
        faulty, _ = _steps(sz, lp, u, carry)
    assert len(calls) == 2
    assert float(jnp.abs(faulty - got).max()) > 1e-2
    assert float(jnp.abs(faulty - faulty_jnp).max()) < 1e-4


def _cell_state(number):
    """(the mixer's sizes, the carried state's type, whether a layer's state
    is its own buffer) of a cell's cached step, from its configuration."""
    cfg = program.program_config(cells.resolve(CELLS[number]).config)
    if cfg.hybrid is not None:
        return cfg.hybrid, cfg.hybrid.state_dtype, False
    return cfg.plan.ssm, cfg.plan.ssm.state_dtype, True


# Which form every cached step of a state-space cell takes (PERF.md
# section 5), and that a no leaves the kernel's module (and pallas) alone.
@pytest.mark.parametrize("tpu,number,state,takes", [
    pytest.param(True, 13, ("bfloat16", 128, 64, 128), True,
                 id="cell-13-on-the-chip"),
    pytest.param(False, 13, ("bfloat16", 128, 64, 128), False,
                 id="cell-13-on-the-cpu"),
    pytest.param(True, 12, ("float32", 64, 64, 128), False,
                 id="cell-12-a-float32-state"),
    pytest.param(True, 5, ("bfloat16", 32, 128, 256), False,
                 id="cell-5-a-depth-of-256"),
    pytest.param(False, 5, ("bfloat16", 32, 128, 256), False,
                 id="cell-5-on-the-cpu"),
])
def test_which_update_each_state_space_cell_takes(tpu, number, state, takes,
                                                  monkeypatch):
    sz, dtype, own = _cell_state(number)
    assert (dtype, sz.n_heads, sz.head_dim, sz.d_state) == state
    assert own == (number != 5)     # cell 5's are layers of one stack
    import ompi_tpu.ops

    monkeypatch.delitem(sys.modules, "ompi_tpu.ops.ssm_update", raising=False)
    monkeypatch.delattr(ompi_tpu.ops, "ssm_update", raising=False)
    assert ssm.in_place(sz, tpu, dtype) is takes
    assert ("ompi_tpu.ops.ssm_update" in sys.modules) is takes
    if takes:
        from ompi_tpu.ops import ssm_update

        held = ssm_update._working_set_bytes(sz.n_heads, sz.head_dim,
                                             sz.n_groups)
        # the state's blocks twice each way are nearly all of it
        assert 4 * 2 * sz.d_ssm * sz.d_state < held < 9 << 20


def test_the_rule_and_the_kernel_refuse_what_does_not_tile():
    from ompi_tpu.ops.ssm_update import block, ssm_update

    assert block(True, "bfloat16", 128, 64, N) == (1, 128, 64, N)
    assert block(True, "float16", 128, 64, N) is None   # the MXU's operand
    assert block(True, "bfloat16", 128, 24, N) is None  # no whole sixteens
    assert block(True, "bfloat16", 128, 64, N, 3) is None
    assert block(True, "bfloat16", 512, 64, N) is None  # 8 MiB each way
    with pytest.raises(ValueError, match="does not tile"):
        ssm_update(*_case(1, 8, 24, 1, "float32"))
