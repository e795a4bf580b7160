"""Power retention's prefill read directly (``ops/retention_prefill.py``
under ``retention.chunked``), in TPU interpret mode, float32, seeded: the
kernel's sums and the state formed once at the end against the chunked form
they stand in for and against the layer's equations summed over every earlier
position; the rule that says which form a program takes
(``retention.direct``); and the planted faults of
``benchmarks/controls_brumby.py`` under the direct form.  Agreement and
control flow only: nothing here is a time.

Both sides are float32 and differ in the order of their sums alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import controls_brumby
from ompi_tpu.models import retention
from ompi_tpu.ops import _chip
from ompi_tpu.ops import retention_prefill as kernel_module
from ompi_tpu.ops.retention_prefill import ROWS, retention_prefill
from tests.parallel.compiled import _pallas_calls
from tests.parallel.test_retention import EPS, PARITY, error, tiny

d = 128
D = retention.state_dim(d)
CHUNK = 64


def _case(T, heads, gates, B=1, G=2, seed=0):
    """q, k (unit, as the layer's q/k norm leaves them but for its scale),
    v and a log decay whose gates are drawn between ``gates``, float32."""
    rng = np.random.default_rng(seed + T + heads)
    q = rng.normal(size=(B, T, G * heads, d))
    k, v = rng.normal(size=(2, B, T, G, d))
    q, k = (y / np.linalg.norm(y, axis=-1, keepdims=True) for y in (q, k))
    logg = np.log(rng.uniform(*gates, size=(B, T, G)))
    return tuple(jnp.asarray(y, jnp.float32) for y in (q, k, v, logg))


def _every_position(q, k, v, logg):
    """The layer's equations as they are written, in float64 on the host:
    every pair, no state; and the state after the last position, ``phi`` of
    every key under its decay to the end.  (num, den, S, z); a matrix
    product a (sequence, head), which is what the host's library is fast
    at."""
    pk = np.asarray(retention.phi(k), np.float64)
    q, k, v, logg = (np.asarray(y, np.float64) for y in (q, k, v, logg))
    B, T, H, _d = q.shape
    G = k.shape[2]
    R = H // G
    c = np.cumsum(logg, axis=1)                         # (B, T, G)
    pk = pk * np.exp(c[:, -1:] - c)[..., None]
    causal = np.tril(np.ones((T, T), bool))
    num, den = np.zeros((B, T, G, R, d)), np.zeros((B, T, G, R))
    S = np.zeros((B, G, D, d))
    for b in range(B):
        for g in range(G):
            at = c[b, :, g]
            fade = np.exp(np.where(causal, at[:, None] - at[None, :],
                                   -np.inf))
            S[b, g] = pk[b, :, g].T @ v[b, :, g]
            for r in range(R):
                a = (q[b, :, g * R + r] @ k[b, :, g].T) ** 2 / d * fade
                num[b, :, g, r], den[b, :, g, r] = a @ v[b, :, g], a.sum(-1)
    return num, den, S, pk.sum(axis=1)


@pytest.fixture
def on_tpus(monkeypatch):
    """``chunked`` told that it is traced for TPUs, so that the rule takes
    the kernel (which the suite's interpret mode runs here)."""
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)


NEAR_HALF, MIXED, NEAR_ONE = (0.5, 0.6), (0.5, 0.99999), (0.999, 0.99999)


@pytest.mark.parametrize("heads,gates,T", [
    (1, NEAR_HALF, ROWS), (5, MIXED, ROWS), (5, NEAR_ONE, ROWS),
    (1, NEAR_ONE, 3 * ROWS), (5, NEAR_HALF, 3 * ROWS)],
    ids=["R1-g-near-half-one-tile", "R5-g-mixed-one-tile",
         "R5-g-near-1-one-tile", "R1-g-near-1-three-tiles",
         "R5-g-near-half-three-tiles"])
def test_the_direct_sums_equal_chunked_and_the_sum_over_every_position(
        heads, gates, T):
    q, k, v, logg = _case(T, heads, gates)
    # what would overflow if an exponent were taken above zero
    assert gates[1] > 0.9 or float(-logg.sum(axis=1).max()) > 88 * T / ROWS

    def parts(q, k, v, logg):       # ``retention._direct``'s, with its sums
        num, den = retention_prefill(q, k, v, logg, retention._power)
        return (num, den, retention._quotient(num, den, EPS),
                *retention.end_state(k, v, logg, CHUNK))

    num, den, y, S, z = jax.jit(parts)(q, k, v, logg)
    assert num.dtype == den.dtype == jnp.float32
    want_y, want_S, want_z = jax.jit(
        lambda *a: retention.chunked(*a, CHUNK, EPS))(q, k, v, logg)
    assert S.shape == want_S.shape == (1, 2, D, d) and z.shape == want_z.shape
    for got, want in ((y, want_y.reshape(y.shape)), (S, want_S), (z, want_z)):
        assert got.shape == want.shape and error(got, want) < PARITY
    num64, den64, S64, z64 = _every_position(q, k, v, logg)
    for got, want in ((S, S64), (z, z64),
                      (y, num64 / (den64[..., None] + EPS))):
        assert got.shape == want.shape and error(got, want) < PARITY
    # under gates near a half the two sums are their last few keys', and a
    # position's weights are digits of a block's sum of decays (as the
    # chunked form's are of a chunk's: its S reads 1.9e-4 at chunks of 256)
    for got, want in ((num, num64), (den, den64)):
        assert got.shape == want.shape and error(got, want) < 3 * PARITY


# (forward only, traced for TPUs, T, head width) -> the direct form
RULE = {
    "a-prefill-on-tpus": ((True, True, 2048, 128), True),
    "one-tile": ((True, True, ROWS, 128), True),
    "at-the-crossover": ((True, True, retention.CROSSOVER, 128), True),
    "a-trainer": ((False, True, 2048, 128), False),
    "the-cpu": ((True, False, 2048, 128), False),
    "over-the-crossover": ((True, True, retention.CROSSOVER + ROWS, 128),
                           False),
    "a-length-that-does-not-tile": ((True, True, 2048 - 128, 128), False),
    "a-narrow-head": ((True, True, 2048, 64), False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_reads_static_facts_alone(case, monkeypatch):
    (forward_only, tpu, T, width), direct = RULE[case]
    assert retention.direct(forward_only, tpu, T, width) is direct
    assert retention.CROSSOVER % ROWS == 0
    assert retention.CROSSOVER <= kernel_module.MAX_ROWS
    # and ``chunked`` follows it: the kernel once, or today's scan
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: tpu)
    G, R = 2, 3
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, T, G * R, width), (1, T, G, width), (1, T, G, width), (1, T, G))]
    jaxpr = jax.make_jaxpr(lambda *a: retention.chunked(
        *a, 256, EPS, forward_only))(*shapes)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    # (the state it leaves by the second: ``test_retention_end_state.py``)
    assert [c.params["name"] for c in calls] == (
        ["retention_prefill", "retention_end_state"] if direct else [])
    widths = {v.aval.shape for eqn in jaxpr.eqns for v in eqn.outvars}
    # phi(q), a query head's expansion, is the scan's alone
    scan = any(eqn.primitive.name == "scan" and any(
        R in v.aval.shape and retention.state_dim(width) in v.aval.shape
        for sub in jax.core.jaxprs_in_params(eqn.params)
        for inner in sub.eqns for v in inner.outvars) for eqn in jaxpr.eqns)
    assert scan is not direct
    assert not (direct and T > ROWS) or (T, T) not in {
        s[-2:] for s in widths}


def test_without_the_flag_chunked_is_the_form_it_was(on_tpus):
    """A trainer's call (no ``forward_only``) traces the same program on
    TPUs as off them."""
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, 512, 4, d), (1, 512, 2, d), (1, 512, 2, d), (1, 512, 2))]
    on = str(jax.make_jaxpr(lambda *a: retention.chunked(*a, 256, EPS))(
        *shapes))
    _chip._traced_for_tpus = lambda: False      # the fixture puts it back
    assert on == str(jax.make_jaxpr(
        lambda *a: retention.chunked(*a, 256, EPS))(*shapes))


def test_the_kernel_refuses_what_does_not_tile():
    q, k, v, logg = _case(ROWS - 8, 1, (0.9, 0.99))
    with pytest.raises(ValueError, match="do not tile"):
        retention_prefill(q, k, v, logg, retention._power)


# which of the traced faults a prefill's core holds under the direct form,
# and what of it is then what ``chunked`` reads under the same fault: all of
# it; the state's rows past the squares (``degree_one``: weights of either
# sign, so a position's quotient is over a sum near zero and says nothing;
# and the state is the kernel's of ``ops/retention_end_state.py``, which asks
# ``phi`` for its constants and not for its form: the cross terms' rows are
# zero as the chunked form's are, the squares' rows keep ``k_a^2`` where the
# chunked form's hold ``k_a``); or nothing (``cross_terms_unscaled``: the
# direct read is exact and the fault shows in the state alone).
# ``rope_dropped`` is planted before the core, in ``block.mixer``; the two of
# ``_state_before`` are a cached step's
IN_THE_PREFILL = {
    "gate_dropped": "all", "group_state_mixed": "all", "degree_one": "cross",
    "normaliser_dropped": "all", "cross_terms_unscaled": "exact",
    "state_not_carried": None, "normaliser_not_carried": None,
    "rope_dropped": None}


_sound: list = []


def _prefill(cfg, *args):
    """``core`` as a prefill calls it, traced here under whatever the caller
    has planted, as one program."""
    return jax.jit(lambda *a: retention.core(cfg, *a, None, True))(*args)


@pytest.mark.parametrize("fault", sorted(IN_THE_PREFILL))
def test_a_planted_fault_changes_the_prefill_under_the_direct_form(
        fault, on_tpus):
    """The controls wrap ``chunked``, ``phi``, ``_power`` and ``_quotient``
    and hand the sound functions other arguments: with the direct form under
    them a prefill's core is another program and reads other numbers than the
    sound one, the numbers the chunked form reads under the same fault."""
    assert set(IN_THE_PREFILL) == set(controls_brumby.TRACED_FAULTS)
    _ref, _shape, cfg, _mesh, _params = tiny()
    args = _case(ROWS, 2, (0.9, 0.999), seed=3)

    def traced():
        return str(jax.make_jaxpr(lambda *a: retention.core(
            cfg, *a, None, True))(*args))

    sound_program = traced()
    assert "retention_prefill" in sound_program
    with controls_brumby.planted(fault):
        faulty_program = traced()
    assert "retention_prefill" in faulty_program
    if IN_THE_PREFILL[fault] is None:
        assert faulty_program == sound_program
        return
    assert faulty_program != sound_program
    if not _sound:
        _sound.append(_prefill(cfg, *args))
    [sound] = _sound
    with controls_brumby.planted(fault):
        faulty = _prefill(cfg, *args)
        _chip._traced_for_tpus = lambda: False  # the fixture puts it back
        faulty_chunked = _prefill(cfg, *args)
    flat = jax.tree.leaves
    assert max(error(a, b) for a, b in zip(flat(faulty), flat(sound))) > (
        100 * PARITY)
    if IN_THE_PREFILL[fault] == "all":
        for a, b in zip(flat(faulty), flat(faulty_chunked)):
            assert error(a, b) < PARITY
    elif IN_THE_PREFILL[fault] == "cross":
        for a, b, c in zip(faulty[1], faulty_chunked[1], sound[1]):  # S, z
            assert not a[:, :, d:].any() and not b[:, :, d:].any()
            assert error(a[:, :, :d], c[:, :, :d]) < PARITY
            assert error(a[:, :, :d], b[:, :, :d]) > 100 * PARITY
    else:       # what the prompt's positions read is sound, the state is not
        assert error(faulty[0], sound[0]) < PARITY
        assert error(faulty[1][0], sound[1][0]) > 100 * PARITY


def test_the_direct_forms_scopes_are_in_the_vocabulary():
    from ompi_tpu.core import scopes

    assert {"retention.direct", "retention.end_state"} <= set(scopes.SCOPES)
