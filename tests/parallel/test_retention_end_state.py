"""The state a prefill leaves, formed in one kernel
(``ops/retention_end_state.py`` under ``retention._end_state_in_vmem``, with
``_direct``'s decays and constants), in TPU
interpret mode, seeded, at cheap sizes: the kernel's ``S`` and ``z`` against
``retention.end_state``, the ``jax.numpy`` form it stands in for, and against
the sum over every position in float64; what the kernel refuses; and a
``retention.phi`` wrapped as ``benchmarks/controls_brumby.py`` wraps it,
which has to reach the state through the kernel as it does through
``end_state``.  Agreement and control flow only: nothing here is a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import controls_brumby
from ompi_tpu.models import retention
from ompi_tpu.ops import _chip
from ompi_tpu.ops import retention_end_state as kernel_module
from ompi_tpu.ops.retention_end_state import ROWS, retention_end_state
from tests.parallel.compiled import _pallas_calls
from tests.parallel.test_retention import EPS, PARITY, error

d = 128
D = retention.state_dim(d)


def _case(T, G, gates, dtype=jnp.float32, B=1, seed=0, real=None):
    """k (unit, as the layer's q/k norm leaves it but for its scale), v and
    a log decay whose gates are drawn between ``gates``; past ``real``
    positions zeros, as ``retention._blocks`` pads a tail."""
    rng = np.random.default_rng(seed + T + G)
    k, v = rng.normal(size=(2, B, T, G, d))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    logg = np.log(rng.uniform(*gates, size=(B, T, G)))
    if real is not None:
        k[:, real:], v[:, real:], logg[:, real:] = 0.0, 0.0, 0.0
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(logg, jnp.float32))


def _every_position(k, v, logg):
    """``S`` and ``z`` as they are written, float64 on the host."""
    pk = np.asarray(retention.phi(k), np.float64)
    v, logg = (np.asarray(y, np.float64) for y in (v, logg))
    c = np.cumsum(logg, axis=1)
    pk = pk * np.exp(c[:, -1:] - c)[..., None]
    return np.einsum("bkgn,bkgv->bgnv", pk, v), pk.sum(axis=1)


NEAR_HALF, MIXED, NEAR_ONE = (0.5, 0.6), (0.5, 0.99999), (0.999, 0.99999)
# (positions, K/V heads, gates, chunk of the decays, type, real positions)
CASES = {
    "one-block": (ROWS, 1, MIXED, ROWS, jnp.float32, None),
    "three-blocks-two-heads": (3 * ROWS, 2, NEAR_ONE, ROWS, jnp.float32,
                               None),
    "two-blocks-two-sequences": (2 * ROWS, 1, MIXED, ROWS, jnp.float32,
                                 None),
    # under gates near a half 768 positions decay by e^-440: the first
    # blocks' weights are zeros, and nothing overflows on the way
    "early-weights-underflow": (3 * ROWS, 1, NEAR_HALF, ROWS, jnp.float32,
                                None),
    # 300 real positions and a tail of zeros to whole blocks
    "a-zero-padded-tail": (2 * ROWS, 1, NEAR_ONE, ROWS, jnp.float32, 300),
    # the decays summed over chunks that are not the kernel's blocks, and
    # do not divide the length
    "chunks-of-192": (2 * ROWS, 1, MIXED, 192, jnp.float32, None),
    # the cell's types: phi(k) w rounded to bfloat16 before the product
    "bfloat16-operands": (2 * ROWS, 2, NEAR_ONE, ROWS, jnp.bfloat16, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_state_is_end_states_and_the_sum_over_every_position(
        case):
    T, G, gates, chunk, dtype, real = CASES[case]
    B = 2 if "two-sequences" in case else 1
    k, v, logg = _case(T, G, gates, dtype, B=B, real=real)
    if case == "early-weights-underflow":
        assert float(-logg[:, ROWS:].sum(axis=1).max()) > 104   # e^-104 = 0
    S, z = jax.jit(retention._end_state_in_vmem, static_argnums=3)(k, v, logg, chunk)
    assert S.dtype == z.dtype == jnp.float32
    assert S.shape == (B, G, D, d) and z.shape == (B, G, D)
    want_S, want_z = jax.jit(retention.end_state, static_argnums=3)(
        k, v, logg, chunk)
    bf16 = dtype == jnp.bfloat16
    # float32 sides differ in the order of their sums alone; in bfloat16 a
    # product whose last float32 bit differs may round the other way
    for got, want in ((S, want_S), (z, want_z)):
        assert got.shape == want.shape
        assert error(got, want) < (20 * PARITY if bf16 else PARITY)
    if real is not None:        # the tail added and decayed nothing
        short = jax.jit(retention.end_state, static_argnums=3)(
            k[:, :real], v[:, :real], logg[:, :real], chunk)
        assert error(S, short[0]) < PARITY and error(z, short[1]) < PARITY
    if not bf16:
        S64, z64 = _every_position(k, v, logg)
        assert error(S, S64) < PARITY and error(z, z64) < PARITY


REFUSED = {
    "a-narrow-head": (2 * ROWS, 64),
    "a-wide-head": (2 * ROWS, 256),
    "a-length-off-the-block": (2 * ROWS - 64, 128),
    "under-a-block": (ROWS // 2, 128),
    "no-position": (0, 128),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_tiles_says_no_and_the_kernel_refuses(case):
    T, width = REFUSED[case]
    assert not kernel_module.tiles(T, width)
    assert kernel_module.tiles(2048, 128) and kernel_module.tiles(ROWS, 128)
    if not T:
        return
    k = jnp.zeros((1, T, 1, width), jnp.float32)
    with pytest.raises(ValueError, match="do not tile"):
        retention_end_state(k, k, jnp.ones((1, T, 1)),
                            jnp.ones((retention.state_dim(width),)))


@pytest.fixture
def on_tpus(monkeypatch):
    """``chunked`` told that it is traced for TPUs, so that a prefill takes
    both kernels (which the suite's interpret mode runs here)."""
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)


def test_a_prefill_on_tpus_forms_its_state_in_the_kernel_once(on_tpus):
    """``retention._direct`` under ``chunked``: one call of each kernel, no
    scan and no array as wide as a chunk of ``phi(k)``; a trainer's trace of
    the same lengths has neither kernel."""
    G, R, T = 2, 3, 2 * ROWS
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, T, G * R, d), (1, T, G, d), (1, T, G, d), (1, T, G))]
    jaxpr = jax.make_jaxpr(lambda *a: retention.chunked(
        *a, 256, EPS, True))(*shapes)
    names = [c.params["name"] for c in _pallas_calls(jaxpr.jaxpr)]
    assert names == ["retention_prefill", "retention_end_state"]
    assert not [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    outside = {v.aval.shape for e in jaxpr.eqns for v in e.outvars}
    assert not [s for s in outside if 256 in s and (
        D in s or (D // d in s and d in s))]
    trainer = jax.make_jaxpr(lambda *a: retention.chunked(
        *a, 256, EPS))(*shapes)
    assert not list(_pallas_calls(trainer.jaxpr))


# what the kernel's state is under a wrapped ``phi``, beside ``end_state``'s
# under the same wrapper: the same (``cross_terms_unscaled`` divides the
# constants out, and the kernel asks ``phi`` for its constants), or the same
# past the squares' rows (``degree_one`` is another form, ``u`` for ``u_a
# u_{a+s} c``: its constants are ones on the squares and zeros elsewhere, so
# the kernel's cross rows are zeros as ``end_state``'s are, and its squares'
# rows keep ``k_a^2`` where ``end_state``'s hold ``k_a``; the control plants
# ``_power`` with it, which the prefill's sums and the reference's logits
# see)
WRAPPED = {"cross_terms_unscaled": "the-same", "degree_one": "past-the-squares"}


@pytest.mark.parametrize("fault", sorted(WRAPPED))
def test_a_wrapped_phi_reaches_the_state_through_the_kernel(fault, on_tpus):
    """The controls wrap ``retention.phi`` while a decoder is traced.  A
    kernel with the constants built in would form the sound state under
    them, and ``cross_terms_unscaled``, which a prefill shows in its state
    alone, would pass through the prefill."""
    G, R, T = 1, 2, 2 * ROWS
    k, v, logg = _case(T, G, (0.9, 0.999), seed=5)
    q = jnp.asarray(np.random.default_rng(6).normal(size=(1, T, G * R, d)),
                    jnp.float32)

    def prefill():          # y, S, z by the two kernels; traced anew a call
        return jax.jit(lambda *a: retention.chunked(*a, 256, EPS, True))

    _y, sound_S, sound_z = prefill()(q, k, v, logg)
    with controls_brumby.planted(fault):
        _y, S, z = prefill()(q, k, v, logg)
        want_S, want_z = jax.jit(
            lambda *a: retention.end_state(*a, 256))(k, v, logg)
    for got, sound, want in ((S, sound_S, want_S), (z, sound_z, want_z)):
        assert got.shape == want.shape
        assert error(got, sound) > 100 * PARITY
        if WRAPPED[fault] == "the-same":
            assert error(got, want) < PARITY
        else:
            assert not got[:, :, d:].any() and not want[:, :, d:].any()
            assert error(got[:, :, :d], sound[:, :, :d]) < PARITY
