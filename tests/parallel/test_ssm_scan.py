"""The Mamba-2 scan as one kernel (``ops/ssm_scan.py`` under ``ssm._mix``),
in TPU interpret mode, float32 and bfloat16, seeded, small (2 sequences, 2
groups of 2 heads 64 wide, 3 chunks of 128): the kernel's ``y`` and end state
against ``chunked_scan``, which it stands in for, and against the recurrence a
position at a time; other widths and a sequence of several spans; a group
cut into blocks of heads, a grid cell each (one group of 16 heads of 64 in
two blocks: each block's end state, ``B`` and ``C`` read by every block; two
groups of two blocks); a tail of ``dt = 0``; the rule that says which form a
program takes (``ssm.fused``) and how many heads a grid cell, VMEM counted;
and the mixer under the rule either way.
Agreement and control flow only: nothing here is a time.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import ssm
from ompi_tpu.ops import _chip
from ompi_tpu.ops import ssm_scan as kernel_module
from ompi_tpu.ops.ssm_scan import CHUNK, ssm_scan
from tests.parallel.compiled import _pallas_calls

B, H, P, G, N = 2, 4, 64, 2, 128
T = 3 * CHUNK
SIZES = ssm.Mamba2(d_ssm=H * P, d_state=N, n_groups=G, n_heads=H, d_conv=4,
                   chunk=CHUNK, state_dtype="float32")
# cell 5's mixer (``falcon-h1-34b``): heads of 128 over states 256 deep
CELL_5 = ssm.Mamba2(d_ssm=4096, d_state=256, n_groups=2, n_heads=32, d_conv=4,
                    chunk=128)


def _case(dtype, t=T, seed=0):
    """The convolution's output (x, B and C side by side) in ``dtype``, dt as
    the model draws it (log-uniform in [1e-3, 1e-1]) and A in [-16, -1]."""
    rng = np.random.default_rng(seed)
    xbc = rng.normal(size=(B, t, H * P + 2 * G * N))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(B, t, H)))
    a = -rng.uniform(1, 16, size=H)
    return (jnp.asarray(xbc, dtype), jnp.asarray(dt, jnp.float32),
            jnp.asarray(a, jnp.float32))


def _split(xbc):
    """x (B, T, H, P), b and c (B, T, G, N) of the convolution's output."""
    x, b, c = jnp.split(xbc, [H * P, H * P + G * N], -1)
    t = xbc.shape[1]
    return (x.reshape(B, t, H, P), b.reshape(B, t, G, N),
            c.reshape(B, t, G, N))


@jax.jit
def _kernel(xbc, dt, a, d=jnp.zeros(H)):
    return ssm_scan(xbc, dt, a, d, G, N)


@jax.jit
def _chunked(xbc, dt, a):
    x, b, c = _split(xbc)
    y, end = ssm.chunked_scan(x, dt, a, b, c, CHUNK)
    return y.reshape(*dt.shape[:2], H * P), end


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


@pytest.mark.parametrize("dtype,parity,rounding", [
    ("float32", 3e-5, 3e-5), ("bfloat16", 1e-1, 8e-2)])
def test_the_kernel_is_chunked_scan_and_the_recurrence(dtype, parity,
                                                       rounding):
    """Against ``chunked_scan`` on the same operands (``parity``), and
    against the recurrence in float32 on the same operands a position at a
    time (``rounding``).  In bfloat16 each chunked form rounds its own way
    (``chunked_scan`` the dt-weighted input and the weights, the kernel the
    weights with a key's dt in them): the worst of 200,000 elements reads
    0.04 to 0.05 of a deviation from the recurrence for ``chunked_scan``
    (0.0024 at the root mean square) and 0.02 to 0.03 for the kernel
    (0.0017), 0.05 to 0.07 between the two."""
    ref = program.reference(cells.resolve(
        "falcon-h1-34b.decode-128-64-b192").config)
    xbc, dt, a = _case(dtype)
    y, end = _kernel(xbc, dt, a)
    assert (y.shape, y.dtype) == ((B, T, H * P), jnp.float32)
    assert (end.shape, end.dtype) == ((B, H, P, N), jnp.float32)
    want_y, want_end = _chunked(xbc, dt, a)
    assert error(y, want_y) < parity and error(end, want_end) < parity
    x, b, c = (t.astype(jnp.float32) for t in _split(xbc))
    each_y, each_end = ref.recurrence(
        x, dt, a, *(jnp.repeat(t, H // G, axis=2) for t in (b, c)))
    assert error(y, each_y.reshape(B, T, H * P)) < rounding
    assert error(end, each_end) < rounding


def _recurrence(x, dt, a, b, c):
    """``h_t = exp(dt a) h_{t-1} + dt x_t (x) B_t``, ``y_t = h_t C_t``, a
    position at a time from a zero state.  x (B, T, H, P), dt (B, T, H), a
    (H,), b and c (B, T, G, N), head ``h`` reading group ``h // (H / G)``;
    returns y (B, T, H, P) and the last state (B, H, P, N)."""
    r = x.shape[2] // b.shape[2]

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(m, r, axis=1) for m in (b_t, c_t))
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    zero = jnp.zeros((*x.shape[::2], x.shape[3], b.shape[3]), jnp.float32)
    last, ys = jax.lax.scan(
        step, zero, tuple(jnp.moveaxis(m, 1, 0) for m in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), last


def _scan_case(b, t, h, p, g, seed):
    rng = np.random.default_rng(seed)
    xbc = jnp.asarray(rng.normal(size=(b, t, h * p + 2 * g * N)), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        size=(b, t, h))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=h), jnp.float32)
    d = jnp.asarray(rng.uniform(0.5, 1.5, size=h), jnp.float32)
    return xbc, dt, a, d


@pytest.mark.parametrize("b,t,h,p,g", [
    (1, 2 * CHUNK, 2, 128, 2), (1, 2 * CHUNK, 8, 32, 2),
    (2, 9 * CHUNK, 2, 64, 1)],
    ids=["a head a tile", "four heads a tile", "nine chunks in three spans"])
def test_other_widths_and_a_sequence_of_several_spans(b, t, h, p, g):
    """Heads of a whole lane tile and of a quarter of one, and a sequence
    longer than ``SPAN`` chunks, whose spans hand the state on in scratch."""
    assert kernel_module.tiles(t, CHUNK, p, N, h // g, jnp.float32)
    xbc, dt, a, d = _scan_case(b, t, h, p, g, seed=t + p)
    scan = jax.jit(ssm_scan, static_argnums=(4, 5))
    [call] = _pallas_calls(jax.make_jaxpr(scan, static_argnums=(4, 5))(
        xbc, dt, a, d, g, N).jaxpr)
    spans = call.params["grid_mapping"].grid[2]
    assert spans == (3 if t == 9 * CHUNK else 1)
    y, end = scan(xbc, dt, a, d, g, N)
    x, bm, cm = jnp.split(xbc, [h * p, h * p + g * N], -1)
    x = x.reshape(b, t, h, p)
    want, want_end = jax.jit(ssm.chunked_scan, static_argnums=5)(
        x, dt, a, bm.reshape(b, t, g, N), cm.reshape(b, t, g, N), CHUNK)
    want = (want + d[:, None] * x).reshape(b, t, h * p)
    assert error(y, want) < 5e-5 and error(end, want_end) < 5e-5


@pytest.mark.parametrize("h,g,named", [(16, 1, False), (16, 1, True),
                                       (32, 2, True)],
                         ids=["one group, the rule's two blocks",
                              "one group, two blocks named",
                              "two groups of two blocks"])
def test_a_group_in_blocks_of_heads_a_grid_cell_each(h, g, named,
                                                     monkeypatch):
    """A group of 16 heads of 64 as two blocks of 8 (what the rule takes
    where the whole group is past its VMEM budget, here made small, or what
    this test hands the call the rule stands before): the grid's second axis
    is the blocks, every block reads its group's ``B`` and ``C``, and ``y``
    and **each block's end state** are ``chunked_scan``'s and the
    recurrence's."""
    b, t, p = 1, 2 * CHUNK, 64
    heads = 8
    if not named:
        whole = kernel_module._vmem_bytes(t, h // g, p, N, 4)
        monkeypatch.setattr(kernel_module, "VMEM_BUDGET", whole - 1)
        assert kernel_module.heads_a_block(t, p, N, h // g,
                                           jnp.float32) == heads
    xbc, dt, a, d = _scan_case(b, t, h, p, g, seed=h + g)

    def scan(*args):
        if not named:
            return ssm_scan(*args, g, N)
        y, end = kernel_module._call(*args, g, N, heads)
        return y, end.reshape(b, h, p, N)

    [call] = _pallas_calls(jax.make_jaxpr(scan)(xbc, dt, a, d).jaxpr)
    assert call.params["grid_mapping"].grid == (b, h // heads, 1)
    assert [v.aval.shape for v in call.invars[3:]] == [
        (b, h // heads, heads, t), (h // heads, heads, 1),
        (h // heads, 1, heads)]
    y, end = scan(xbc, dt, a, d)
    x, bm, cm = jnp.split(xbc, [h * p, h * p + g * N], -1)
    x = x.reshape(b, t, h, p)
    bm, cm = bm.reshape(b, t, g, N), cm.reshape(b, t, g, N)
    want, want_end = jax.jit(ssm.chunked_scan, static_argnums=5)(
        x, dt, a, bm, cm, CHUNK)
    skip = d[:, None] * x
    assert error(y, (want + skip).reshape(b, t, h * p)) < 5e-5
    each, each_end = jax.jit(_recurrence)(x, dt, a, bm, cm)
    assert error(y, (each + skip).reshape(b, t, h * p)) < 5e-5
    for block in range(h // heads):
        mine = slice(block * heads, (block + 1) * heads)
        assert np.abs(np.asarray(each_end[:, mine])).max() > 0.1
        assert error(end[:, mine], want_end[:, mine]) < 5e-5
        assert error(end[:, mine], each_end[:, mine]) < 5e-5


BLOCKS = [      # why, positions, head width, heads a group, type, heads a cell
    ("cell 12: a group of 8 whole", 1024, 64, 8, "bfloat16", 8),
    ("this cell's pass: 16 of 128", 512, 64, 128, "bfloat16", 16),
    ("longer prompts: 8 of 128", 1024, 64, 128, "bfloat16", 8),
    ("float32 operands: still 16", 512, 64, 128, "float32", 16),
    ("a small group whole", 256, 64, 16, "float32", 16),
    ("heads of 128: 8 are past the budget", 1024, 128, 8, "bfloat16", 0),
    ("heads of 128, 4 a group", 1024, 128, 4, "bfloat16", 4),
]


@pytest.mark.parametrize("t,p,r,dtype,heads", [case[1:] for case in BLOCKS],
                         ids=[case[0] for case in BLOCKS])
def test_the_heads_a_grid_cell_takes_fit_vmem(t, p, r, dtype, heads):
    """The rule counts VMEM and not shapes alone: the whole group where its
    cell's blocks fit, else the most heads that do in whole sublane tiles of
    ``dt``, and where none does the kernel is not taken."""
    assert kernel_module.heads_a_block(t, p, N, r, jnp.dtype(dtype)) == heads
    assert kernel_module.tiles(t, CHUNK, p, N, r, jnp.dtype(dtype)) is (
        heads > 0)
    if heads:
        rows = min(t, kernel_module.SPAN * CHUNK)
        assert kernel_module._vmem_bytes(
            rows, heads, p, N, jnp.dtype(dtype).itemsize
        ) <= kernel_module.VMEM_BUDGET


def test_the_skip_adds_each_heads_own_input():
    """``y + d x``: the mixer's skip, a scalar a head, made where x is."""
    xbc, dt, a = _case("float32")
    d = jnp.asarray(np.random.default_rng(5).uniform(0.5, 1.5, size=H),
                    jnp.float32)
    y, end = _kernel(xbc, dt, a, d)
    bare, same_end = _kernel(xbc, dt, a)
    x, _b, _c = _split(xbc)
    want = bare + (d[:, None] * x).reshape(B, T, H * P)
    assert error(y, want) < 1e-6
    np.testing.assert_array_equal(np.asarray(end), np.asarray(same_end))


def test_a_dt_of_zero_in_the_tail_leaves_the_state():
    """What ``chunked_scan`` pads a ragged length with: a position of dt = 0
    decays nothing and adds nothing, so the state after a last chunk of them
    is the state before it, to the bit."""
    xbc, dt, a = _case("float32")
    dt = dt.at[:, 2 * CHUNK:].set(0.0)
    _y, end = _kernel(xbc, dt, a)
    _y, before = _kernel(xbc[:, :2 * CHUNK], dt[:, :2 * CHUNK], a)
    np.testing.assert_array_equal(np.asarray(end), np.asarray(before))
    assert np.abs(np.asarray(end)).max() > 0.1


RULE = [      # why, sizes, forward_only, tpu, positions, type, what it says
    ("cell 12's prefill", SIZES, True, True, 1024, "bfloat16", True),
    ("float32", SIZES, True, True, 2 * CHUNK, "float32", True),
    ("one chunk", SIZES, True, True, CHUNK, "bfloat16", False),
    ("a gradient asked", SIZES, False, True, 1024, "bfloat16", False),
    ("the CPU", SIZES, True, False, 1024, "bfloat16", False),
    ("a ragged length", SIZES, True, True, 1000, "bfloat16", False),
    ("a half", SIZES, True, True, 1024, "float16", False),
    ("cell 5's prefill", CELL_5, True, True, 128, "bfloat16", False),
    ("cell 5's states, 256 deep", CELL_5, True, True, 1024, "bfloat16",
     False),
    ("one group of 128 heads", ssm.Mamba2(
        d_ssm=8192, d_state=N, n_groups=1, n_heads=128, d_conv=4,
        chunk=CHUNK), True, True, 512, "bfloat16", True),
    ("a published chunk of 256", ssm.Mamba2(
        d_ssm=8192, d_state=N, n_groups=1, n_heads=128, d_conv=4,
        chunk=256), True, True, 512, "bfloat16", False),
]


@pytest.mark.parametrize("sizes,forward_only,tpu,t,dtype,says",
                         [case[1:] for case in RULE],
                         ids=[case[0] for case in RULE])
def test_the_rule(sizes, forward_only, tpu, t, dtype, says):
    assert ssm.fused(sizes, forward_only, tpu, t, jnp.dtype(dtype)) is says


def test_what_does_not_tile_is_refused():
    xbc, dt, a = _case("float32", t=CHUNK + 8)
    with pytest.raises(ValueError, match="do not tile"):
        ssm_scan(xbc, dt, a, a, G, N)
    assert not kernel_module.tiles(1024, 64, P, N, H // G)      # the chunk
    assert not kernel_module.tiles(1024, CHUNK, 48, N, 8)       # the head
    assert not kernel_module.tiles(1024, CHUNK, 64, N, 3)       # the group
    assert not kernel_module.tiles(1024, CHUNK, 256, N, 1)      # two tiles
    assert kernel_module.tiles(1024, CHUNK, 128, N, 1)


@pytest.fixture
def on_tpus(monkeypatch):
    """``_mix`` told that it is traced for TPUs, so that the rule takes the
    kernel (which the suite's interpret mode runs here)."""
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)


def _leaves(seed=3):
    rng = np.random.default_rng(seed)
    D = 32
    shapes = ssm._kind_leaf_shapes(
        types.SimpleNamespace(d_model=D, n_layers=2), SIZES)
    lp = {}
    for name, (shape, draw) in shapes.items():
        lp[name] = jnp.asarray(
            rng.uniform(0.5, 1.5, size=shape) if draw is None
            else draw(rng, shape) if callable(draw)
            else rng.normal(0, draw, size=shape), jnp.float32)
    return lp, jnp.asarray(rng.normal(size=(B, 2 * CHUNK, D)), jnp.float32)


def test_the_mixer_takes_the_kernel_where_the_rule_says_so(on_tpus):
    """``_mix`` on whole sequences of two chunks: a decoder's prefill calls
    the kernel once and ``chunked_scan``'s running sum never; a trainer's
    pass is ``chunked_scan``'s; both give the same output and states."""
    lp, u = _leaves()

    def mix(forward_only):
        return lambda lp, u: ssm._mix(SIZES, 1e-5, lp, u,
                                      forward_only=forward_only)

    prefill = jax.make_jaxpr(mix(True))(lp, u)
    [call] = _pallas_calls(prefill.jaxpr)
    assert call.params["name"] == "ssm_scan" and "cumsum" not in str(prefill)
    trained = jax.make_jaxpr(mix(False))(lp, u)
    assert not list(_pallas_calls(trained.jaxpr)) and "cumsum" in str(trained)
    got, want = jax.jit(mix(True))(lp, u), jax.jit(mix(False))(lp, u)
    for mine, theirs in zip(got, want):
        assert mine.shape == theirs.shape and mine.dtype == theirs.dtype
        assert error(mine, theirs) < 3e-4
