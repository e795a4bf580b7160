"""coll/xla: MCA-gated device collective path + buffer-location dispatch.

VERDICT round-1 item 2: ``--mca coll host`` vs ``xla`` must select paths
observably, and a jax.Array through comm.allreduce must never cross
np.asarray (no silent host staging).
"""

import numpy as np
import pytest

from ompi_tpu.core import config
from ompi_tpu.core.buffer import BufferLocationError
from ompi_tpu.mpi import op as op_mod
from tests.mpi.harness import run_ranks

jax = pytest.importorskip("jax")

from jax.sharding import PartitionSpec as P  # noqa: E402

from ompi_tpu.mpi.comm import Communicator  # noqa: E402
from ompi_tpu.mpi.device_comm import device_world  # noqa: E402
from ompi_tpu.mpi.group import Group  # noqa: E402
from ompi_tpu.mpi.pml import PmlOb1  # noqa: E402
from ompi_tpu.parallel.mesh import make_mesh  # noqa: E402


@pytest.fixture
def coll_directive():
    """Set the coll selection directive for the test, restore after."""
    old = config.var_registry.get("coll_")

    def set_directive(value):
        config.var_registry.set("coll_", value)

    yield set_directive
    config.var_registry.set("coll_", old or "")


def _solo_comm():
    """A size-1 communicator (no sockets needed) bound to the full mesh."""
    pml = PmlOb1(0)
    pml.set_peers({0: pml.address})
    comm = Communicator(Group([0]), cid=7, pml=pml, my_world_rank=0,
                        name="xla_test")
    mesh = make_mesh(devices=jax.devices())
    comm.bind_device(device_world(mesh))
    return comm, pml


def test_dispatch_table_records_both_providers():
    comm, pml = _solo_comm()
    try:
        assert comm.coll.providers["allreduce"] == "self"  # size-1 host path
        assert comm.coll.device_providers["allreduce"] == "xla"
    finally:
        pml.close()


def test_device_allreduce_routes_to_mesh_no_host_staging(monkeypatch):
    comm, pml = _solo_comm()
    n = comm.device.size
    x = jax.numpy.arange(n * 4, dtype=jax.numpy.float32)

    # trip any host staging: np.asarray on a jax.Array must not happen
    orig = np.asarray

    def guarded(a, *args, **kw):
        assert not isinstance(a, jax.Array) or a.ndim == 0, \
            "jax.Array crossed np.asarray inside the collective"
        return orig(a, *args, **kw)

    monkeypatch.setattr(np, "asarray", guarded)
    try:
        out = comm.allreduce(x)
    finally:
        monkeypatch.undo()
        pml.close()
    assert isinstance(out, jax.Array)
    # psum over the mesh: every shard position sums across devices
    shards = np.asarray(x).reshape(n, 4)
    np.testing.assert_allclose(np.asarray(out).reshape(n, 4),
                               np.tile(shards.sum(0), (n, 1)))


def test_traced_allreduce_inside_shard_map():
    comm, pml = _solo_comm()
    mesh = comm.device.mesh
    n = comm.device.size
    x = np.arange(n * 2, dtype=np.float32)

    def kernel(shard):
        return comm.allreduce(shard)  # TRACED → lax.psum via coll/xla

    try:
        fn = jax.jit(jax.shard_map(kernel, mesh=mesh, in_specs=P("world"),
                                   out_specs=P("world"), check_vma=False))
        out = np.asarray(fn(x))
    finally:
        pml.close()
    expected = np.tile(x.reshape(n, 2).sum(0), n)
    np.testing.assert_allclose(out, expected)


def test_device_max_and_reduce_scatter():
    comm, pml = _solo_comm()
    n = comm.device.size
    # each device's shard (n elems) must itself split n ways in psum_scatter
    x = jax.numpy.arange(n * n, dtype=jax.numpy.float32)
    try:
        mx = comm.allreduce(x, op=op_mod.MAX)
        rs = comm.reduce_scatter(x)
    finally:
        pml.close()
    host = np.asarray(x).reshape(n, n)
    np.testing.assert_allclose(np.asarray(mx).reshape(n, n),
                               np.tile(host.max(0), (n, 1)))
    # psum_scatter: device i gets element i of the summed shard vector
    np.testing.assert_allclose(np.asarray(rs), host.sum(0))


def test_pml_rejects_device_buffer():
    def body(comm):
        x = jax.numpy.ones((4,), jax.numpy.float32)
        if comm.rank == 0:
            with pytest.raises(BufferLocationError):
                comm.send(x, dest=1, tag=5)
        else:
            with pytest.raises(BufferLocationError):
                comm.recv(buf=x, source=0, tag=5)
        return True

    assert run_ranks(2, body) == [True, True]


def test_directive_excluding_xla_makes_device_buffers_error(coll_directive):
    coll_directive("^xla")
    comm, pml = _solo_comm()
    try:
        with pytest.raises(BufferLocationError):
            comm.allreduce(jax.numpy.ones((4,)))
        # host path still works
        out = comm.allreduce(np.ones(4, np.float32))
        np.testing.assert_allclose(np.asarray(out), np.ones(4))
    finally:
        pml.close()


def test_directive_xla_only_makes_host_buffers_error(coll_directive):
    coll_directive("xla")
    comm, pml = _solo_comm()
    try:
        with pytest.raises(BufferLocationError):
            comm.allreduce(np.ones(4, np.float32))
        out = comm.allreduce(jax.numpy.ones((8,), jax.numpy.float32))
        assert isinstance(out, jax.Array)
    finally:
        pml.close()


def test_unbound_comm_gives_actionable_error():
    def body(comm):
        with pytest.raises(BufferLocationError, match="bind_device"):
            comm.allreduce(jax.numpy.ones((4,)))
        return True

    assert run_ranks(2, body) == [True, True]


def test_dup_propagates_device_binding():
    comm, pml = _solo_comm()
    try:
        dup = comm.dup()
        assert dup.device is comm.device
    finally:
        pml.close()


def _primitives(jaxpr) -> set:
    """Names of every primitive in a jaxpr, nested jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


_NATIVE = {"allreduce": "psum", "allgather": "all_gather", "bcast": "psum"}


@pytest.mark.parametrize("how", ["traced-1KiB", "traced-64MiB", "committed-1KiB"])
@pytest.mark.parametrize("coll", sorted(_NATIVE))
def test_one_lowering_per_device_collective(coll, how):
    """Whatever the size and the buffer kind, a device collective through
    the MPI surface is XLA's own lowering: no hand-written ring, no
    reduce-scatter + all-gather, at 64 MiB a shard as at 1 KiB."""
    comm, pml = _solo_comm()
    dc = comm.device
    n = dc.size
    per_shard = (64 << 20 if how == "traced-64MiB" else 1 << 10) // 4
    call = {"allreduce": comm.allreduce, "allgather": comm.allgather,
            "bcast": lambda b: comm.bcast(b, 1)}[coll]
    try:
        if how == "committed-1KiB":
            x = jax.numpy.arange(n * per_shard, dtype=jax.numpy.float32)
            out = np.asarray(call(x))
            (program,) = dc._method_cache.values()
            jaxpr = jax.make_jaxpr(program)(x)
            shards = np.asarray(x).reshape(n, per_shard)
            want = {"allreduce": np.tile(shards.sum(0), n),
                    "allgather": np.tile(shards.reshape(-1), n),
                    "bcast": np.tile(shards[1], n)}[coll]
            np.testing.assert_allclose(out, want)
        else:
            fn = jax.shard_map(call, mesh=dc.mesh, in_specs=P("world"),
                               out_specs=P("world"), check_vma=False)
            jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(
                (n * per_shard,), jax.numpy.float32))
    finally:
        pml.close()
    names = _primitives(jaxpr.jaxpr)
    assert _NATIVE[coll] in names, names
    assert not names & {"ppermute", "reduce_scatter", "all_to_all"}, names
