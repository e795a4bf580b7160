"""The lightning mixer (``models/lightning.py``) against the plain
reference, ``benchmarks/reference/minicpm_sala.py``, float32, seeded, on the
CPU: the chunked form against the recurrence for chunks that do and do not
divide the length, one cached update against the recurrence's next position,
the decays of a layer's place, and the mixer with each of its parts switched
off.  Agreement only: nothing here is a time."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import lightning

CELL = "minicpm-sala.decode-16k-512-b24"
PARITY = 1e-4


def reference():
    config = program.tiny(cells.resolve(CELL).config)
    ref = program.reference(config)
    return ref, ref.Shape.from_config(config)


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


def inputs(seed, B=2, T=37, H=4, K=8):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(B, T, H, K)).astype(np.float32)
               for _ in range(3))
    # from a head that forgets within a position to one that barely does
    log_decay = -np.geomspace(2.0, 1e-3, H).astype(np.float32)
    return q, k, v, log_decay


@pytest.mark.parametrize("T,chunk", [(37, 16), (16, 16), (5, 16), (33, 4),
                                     (128, 128), (130, 64), (100, 32)])
def test_the_chunked_form_is_the_recurrence(T, chunk):
    ref, _ = reference()
    q, k, v, log_decay = inputs(T, T=T)
    want_y, want_s = ref.recurrence(*map(jnp.asarray, (q, k, v, log_decay)))
    got_y, got_s = jax.jit(lightning.chunked, static_argnums=4)(
        q, k, v, jnp.asarray(log_decay), chunk)
    assert np.isfinite(np.asarray(got_y)).all()
    assert error(got_y, want_y) < PARITY
    assert error(got_s, want_s) < PARITY


@pytest.mark.parametrize("dtype,tol", [("float32", PARITY),
                                       ("bfloat16", 2e-2)])
def test_one_update_is_the_recurrences_next_position(dtype, tol):
    ref, _ = reference()
    q, k, v, log_decay = map(jnp.asarray, inputs(3, T=21))
    want_y, want_s = ref.recurrence(q, k, v, log_decay)
    _, before = ref.recurrence(q[:, :-1], k[:, :-1], v[:, :-1], log_decay)
    got_y, got_s = jax.jit(lightning.update)(
        before.astype(dtype), q[:, -1], k[:, -1], v[:, -1], log_decay)
    assert got_s.dtype == jnp.dtype(dtype) and got_y.dtype == jnp.float32
    assert error(got_y, want_y[:, -1]) < tol
    assert error(got_s.astype(jnp.float32), want_s) < tol


@pytest.mark.parametrize("layer", [0, 1, 3, 31])
def test_a_layers_decays_are_those_of_its_place_in_the_model(layer):
    ref, shape = reference()
    lt = lightning.Lightning(n_heads=shape.lt_heads,
                             head_dim=shape.lt_head_dim, depth=shape.depth)
    got = lightning.constants(lt, layer)["log_decay"]
    assert got.shape == (shape.lt_heads,) and got.dtype == np.float32
    np.testing.assert_allclose(got, np.asarray(ref.log_decay(shape, layer)),
                               rtol=1e-6)
    assert (got < 0).all() and (np.diff(got) > 0).all()
    # the last layer of the model barely forgets, the first forgets most
    assert got[0] == pytest.approx(-2.0 ** (-8 / shape.lt_heads)
                                   * (1 - layer / 31 + 1e-5), rel=1e-6)


def test_the_leaves_follow_the_parts_the_configuration_has():
    cfg = program.program_config(program.tiny(cells.resolve(CELL).config))
    lt = cfg.plan.lightning
    whole = set(lightning.leaf_shapes(cfg, lt))
    assert whole == {"lt_q", "lt_k", "lt_v", "lt_z", "lt_o", "lt_qn",
                     "lt_kn", "lt_on"}
    bare = dataclasses.replace(lt, gate=False, rope=False)
    assert set(lightning.leaf_shapes(cfg, bare)) == whole - {"lt_z"}
    (shape, dtype, axis), = lightning.buffers(cfg, lt, 3, 99)
    assert shape == (3, lt.n_heads, lt.head_dim, lt.head_dim)
    assert dtype == "float32" and axis is None
