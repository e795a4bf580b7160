"""Device-path v-collectives and exscan on the virtual 8-device CPU mesh.

The ragged convention (pad to max(counts), static counts vector) is checked
against per-rank numpy references.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ompi_tpu.mpi import op as op_mod
from ompi_tpu.mpi.device_comm import device_world


@pytest.fixture(scope="module")
def mesh8():
    devs = np.array(jax.devices())
    assert devs.size == 8, "tests expect the 8-device virtual CPU mesh"
    return Mesh(devs, axis_names=("world",))


def _global(n=64, dtype=np.float32):
    return np.arange(n, dtype=dtype).reshape(8, n // 8)


# -- exscan -----------------------------------------------------------------

def test_exscan_sum(mesh8):
    comm = device_world(mesh8)
    x = _global()
    out = np.asarray(comm.run(lambda c, s: c.exscan(s), x))
    want = np.zeros_like(x)
    for r in range(1, 8):
        want[r] = x[:r].sum(axis=0)
    np.testing.assert_allclose(out, want)


def test_exscan_noncommutative(mesh8):
    comm = device_world(mesh8)
    mats = np.stack([np.array([[1.0, r + 1], [0, 1]]) for r in range(8)])
    matmul = op_mod.create_op(lambda a, b: a @ b, commutative=False,
                              device_fn=lambda a, b: a @ b)
    out = np.asarray(comm.run(
        lambda c, s: c.exscan(s[0], matmul)[None], mats))
    # rank 0 → zeros; rank r → fold of ranks < r in order
    np.testing.assert_allclose(out[0], np.zeros((2, 2)))
    want = mats[0]
    for r in range(1, 8):
        np.testing.assert_allclose(out[r], want)
        want = want @ mats[r]


# -- v-collectives (ragged, pad + static counts) ----------------------------

COUNTS = (3, 1, 4, 2, 0, 4, 1, 3)   # ragged, includes an empty rank


def _ragged_padded(counts, width=5, seed=0):
    """(8, max(counts), width): rank r holds counts[r] valid rows."""
    rng = np.random.default_rng(seed)
    maxc = max(counts)
    x = np.zeros((8, maxc, width), np.float32)
    for r, c in enumerate(counts):
        x[r, :c] = rng.normal(size=(c, width))
    return x


def test_allgatherv_ragged(mesh8):
    comm = device_world(mesh8)
    x = _ragged_padded(COUNTS)
    # run() splits axis 0 → shard (1, maxc, w); s[0] is my padded block
    out = np.asarray(comm.run(
        lambda c, s: c.allgatherv(s[0], COUNTS),
        x, out_specs=jax.sharding.PartitionSpec()))
    want = np.concatenate([x[r, :c] for r, c in enumerate(COUNTS)], axis=0)
    np.testing.assert_allclose(out, want)


def test_allgatherv_uniform_is_dense(mesh8):
    comm = device_world(mesh8)
    x = _global(64)
    a = np.asarray(comm.run(lambda c, s: c.allgatherv(s), x))
    b = np.asarray(comm.run(lambda c, s: c.allgather(s), x))
    np.testing.assert_allclose(a, b)


def test_gatherv_root_only(mesh8):
    comm = device_world(mesh8)
    x = _ragged_padded(COUNTS)
    total = sum(COUNTS)
    out = np.asarray(comm.run(
        lambda c, s: c.gatherv(s[0], COUNTS, root=2), x,
        out_specs=jax.sharding.PartitionSpec("world")))
    # driver-mode convention: axis 0 is per-device concat → rank 2's block
    out = out.reshape(8, total, -1)
    want = np.concatenate([x[r, :c] for r, c in enumerate(COUNTS)], axis=0)
    np.testing.assert_allclose(out[2], want)
    np.testing.assert_allclose(out[3], np.zeros_like(want))


def test_scatterv_ragged(mesh8):
    comm = device_world(mesh8)
    counts = COUNTS
    total = sum(counts)
    rng = np.random.default_rng(1)
    full = rng.normal(size=(total, 5)).astype(np.float32)
    xin = np.tile(full, (8, 1)).reshape(8 * total, 5)
    out = np.asarray(comm.run(
        lambda c, s: c.scatterv(s, counts, root=0), xin))
    maxc = max(counts)
    out = out.reshape(8, maxc, 5)
    offs = np.concatenate([[0], np.cumsum(counts)])
    for r, c in enumerate(counts):
        np.testing.assert_allclose(out[r, :c], full[offs[r]:offs[r] + c],
                                   err_msg=f"rank {r}")
        np.testing.assert_allclose(out[r, c:], 0.0)


def test_alltoallv_ragged(mesh8):
    comm = device_world(mesh8)
    rng = np.random.default_rng(2)
    m = rng.integers(0, 4, size=(8, 8))            # send counts matrix
    maxc = int(m.max())
    x = np.zeros((8, 8, maxc, 3), np.float32)      # [src, dst, row, col]
    for s in range(8):
        for d in range(8):
            x[s, d, :m[s, d]] = rng.normal(size=(int(m[s, d]), 3))
    out = np.asarray(comm.run(
        lambda c, sh: c.alltoallv(sh, m),
        x.reshape(64, maxc, 3)))
    out = out.reshape(8, 8, maxc, 3)               # [dst, src, row, col]
    for d in range(8):
        for s in range(8):
            np.testing.assert_allclose(out[d, s, :m[s, d]],
                                       x[s, d, :m[s, d]],
                                       err_msg=f"src {s} dst {d}")
            np.testing.assert_allclose(out[d, s, m[s, d]:], 0.0)
