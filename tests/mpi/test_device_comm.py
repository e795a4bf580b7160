"""Device-path collectives on the virtual 8-device CPU mesh.

Validates the coll/xla equivalents against numpy references — the same
cross-checking discipline the reference applies between coll/tuned and basic.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ompi_tpu.mpi import op as op_mod
from ompi_tpu.mpi.device_comm import DeviceCommunicator, device_world


@pytest.fixture(scope="module")
def mesh8():
    devs = np.array(jax.devices())
    assert devs.size == 8, "tests expect the 8-device virtual CPU mesh"
    return Mesh(devs, axis_names=("world",))


@pytest.fixture(scope="module")
def mesh24():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, axis_names=("dp", "tp"))


def _global(n=64, dtype=np.float32):
    return np.arange(n, dtype=dtype).reshape(8, n // 8)


def test_allreduce_psum(mesh8):
    comm = device_world(mesh8)
    x = _global()
    out = comm.run(lambda c, s: c.allreduce(s), x)
    want = np.tile(x.sum(axis=0), (8, 1))
    np.testing.assert_allclose(np.asarray(out), want)


def test_allreduce_max(mesh8):
    comm = device_world(mesh8)
    x = _global()
    out = comm.run(lambda c, s: c.allreduce(s, op_mod.MAX), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(x.max(axis=0), (8, 1)))


def test_allreduce_generic_noncommutative(mesh8):
    comm = device_world(mesh8)
    mats = np.stack([np.array([[1.0, r + 1], [0, 1]]) for r in range(8)])
    matmul = op_mod.create_op(lambda a, b: a @ b, commutative=False,
                              device_fn=lambda a, b: a @ b)
    out = comm.run(lambda c, s: c.allreduce(s[0], matmul)[None], mats)
    want = mats[0]
    for r in range(1, 8):
        want = want @ mats[r]
    np.testing.assert_allclose(np.asarray(out)[0], want)


def test_bcast_from_nonzero_root(mesh8):
    comm = device_world(mesh8)
    x = _global()
    out = comm.run(lambda c, s: c.bcast(s, root=3), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(x[3], (8, 1)))


def test_reduce_root_only(mesh8):
    comm = device_world(mesh8)
    x = _global()
    out = comm.run(lambda c, s: c.reduce(s, root=2), x)
    got = np.asarray(out)
    np.testing.assert_allclose(got[2], x.sum(axis=0))
    np.testing.assert_allclose(got[0], 0)


def test_reduce_scatter_matches_mpi(mesh8):
    comm = device_world(mesh8)
    x = np.tile(np.arange(16, dtype=np.float32), (8, 1))  # same on each rank
    out = comm.run(lambda c, s: c.reduce_scatter(s[0])[None], x)
    got = np.asarray(out)  # rank r gets block r of 8*x
    for r in range(8):
        np.testing.assert_allclose(got[r], 8 * np.arange(16)[2 * r:2 * r + 2])


def test_allgather(mesh8):
    comm = device_world(mesh8)
    x = _global(32)
    out = comm.run(lambda c, s: c.allgather(s)[None], x)
    got = np.asarray(out)
    for r in range(8):
        np.testing.assert_allclose(got[r].reshape(8, 4), x)


def test_alltoall(mesh8):
    comm = device_world(mesh8)
    x = np.arange(64, dtype=np.float32)  # shard (8,) → 1 element per peer
    out = comm.run(lambda c, s: c.alltoall(s), x)
    got = np.asarray(out).reshape(8, 8)
    np.testing.assert_allclose(got, _global(64).reshape(8, 8).T)


def test_scan_inclusive(mesh8):
    comm = device_world(mesh8)
    x = np.ones((8, 4), np.float32)
    out = comm.run(lambda c, s: c.scan(s), x)
    got = np.asarray(out)
    for r in range(8):
        np.testing.assert_allclose(got[r], r + 1)


def test_ring_shift(mesh8):
    comm = device_world(mesh8)
    x = _global()
    out = comm.run(lambda c, s: c.shift(s, 1), x)
    got = np.asarray(out)
    for r in range(8):
        np.testing.assert_allclose(got[(r + 1) % 8], x[r])


def test_scatter(mesh8):
    comm = device_world(mesh8)
    # root holds the full 16-element buffer; everyone passes same shape
    x = np.tile(np.arange(16, dtype=np.float32), (8, 1))
    out = comm.run(lambda c, s: c.scatter(s[0], root=0)[None], x)
    got = np.asarray(out)
    for r in range(8):
        np.testing.assert_allclose(got[r], np.arange(16)[2 * r:2 * r + 2])


def test_rank_and_coords_2d(mesh24):
    comm = DeviceCommunicator(mesh24)
    assert comm.size == 8 and comm.axis_sizes == (2, 4)
    out = comm.run(lambda c, s: s * 0 + c.rank(), np.zeros((8, 1), np.int32))
    np.testing.assert_array_equal(np.asarray(out).ravel(), np.arange(8))


def test_sub_communicator_axes(mesh24):
    comm = DeviceCommunicator(mesh24)
    tp = comm.sub(["tp"])
    assert tp.size == 4

    # psum over tp only: rows (dp groups) reduce independently
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def fn(c, s):
        return tp.allreduce(s)

    out = comm.run(fn, x)
    got = np.asarray(out).ravel()
    np.testing.assert_allclose(got[:4], np.full(4, 0 + 1 + 2 + 3.0))
    np.testing.assert_allclose(got[4:], np.full(4, 4 + 5 + 6 + 7.0))


def test_2d_allreduce_over_both_axes(mesh24):
    comm = DeviceCommunicator(mesh24)
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    out = comm.run(lambda c, s: c.allreduce(s), x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(8, 28.0))


def test_inside_user_jit_composes(mesh8):
    """The traced API composes with user compute inside one jit program."""
    comm = device_world(mesh8)

    def step(c, s):
        y = jnp.sin(s) * 2.0
        total = c.allreduce(y)
        return total / c.size

    x = _global()
    out = comm.run(step, x)
    want = np.tile((np.sin(x) * 2).sum(axis=0) / 8, (8, 1))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
