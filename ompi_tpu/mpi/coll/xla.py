"""coll/xla — the device collective component (MCA slot ≈ ompi/mca/coll/cuda).

The reference's coll/cuda (coll_cuda_allreduce.c:30-69) intercepts device
buffers, stages them through host bounce buffers, and delegates to the CPU
algorithms.  This component is the TPU-first inversion of that slot: device
buffers NEVER cross to host — every collective lowers to an XLA collective
(lax.psum / all_gather / all_to_all / ppermute) over the communicator's
bound ``DeviceCommunicator`` mesh axes, so the data plane is pure ICI/HBM.

Two buffer kinds reach this component (the CollModule dispatcher routes by
``core.buffer.classify()``; host buffers go to coll/host):

- **TRACED** — the call site is inside ``jit``/``shard_map`` over the mesh:
  delegate straight to the DeviceCommunicator method; the collective fuses
  into the surrounding compiled program.
- **DEVICE** — a committed ``jax.Array`` in driver mode: wrap the same
  method in a one-off ``shard_map``+``jit`` over the bound mesh (the array's
  axis 0 is the concatenation of per-device shards, matching
  ``DeviceCommunicator.run``'s convention).

Selection: ``--mca coll xla`` forces this path exclusively (host buffers
then error); ``--mca coll ^xla`` removes it (device buffers then raise
``BufferLocationError`` at the dispatcher).  Default: stacked above host,
chosen per-buffer — the behavior-gated substitution BASELINE.json names as
the north star.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ompi_tpu.core.buffer import BufferKind, BufferLocationError, classify
from ompi_tpu.core.mca import Component
from ompi_tpu.mpi.coll import coll_framework
from ompi_tpu.mpi.op import Op

__all__ = ["XlaColl"]


def _device_comm(comm):
    dc = getattr(comm, "device", None)
    if dc is None:
        raise BufferLocationError(
            f"{comm.name}: device buffer in a collective but no device "
            f"communicator is bound; call comm.bind_device(device_comm) "
            f"(e.g. device_world(mesh)) so coll/xla knows the mesh axes")
    return dc


def _run(comm, method: str, buf, *args, **kw):
    """Dispatch traced vs committed-device execution of one collective."""
    dc = _device_comm(comm)
    fn = getattr(dc, method)
    if classify(buf) is BufferKind.TRACED:
        return fn(buf, *args, **kw)
    # driver mode rides the compiled-program cache: repeated collectives
    # with the same (method, args, shapes) reuse one jitted shard_map
    return dc.run_method(method, buf, margs=args,
                         mkw=tuple(sorted(kw.items())))


@coll_framework.component
class XlaColl(Component):
    """Device collectives: each slot is one ``DeviceCommunicator`` method,
    which is one XLA lowering (psum / all_gather / all_to_all / ppermute).
    XLA picks the ICI algorithm: the explicit reduce-scatter + all-gather,
    ring and chain forms this component once chose among took 1.4 to 4.2
    times as long at every size from 4 KiB to 256 MiB a device on four
    v5e chips (PERF.md section 6, PR 29)."""

    NAME = "xla"
    PRIORITY = 60        # above host (40); the dispatcher routes by buffer
    HANDLES = frozenset({"device", "traced"})

    def query(self, comm=None, **ctx) -> Optional[int]:
        return self.PRIORITY

    # -- table slots (device implementations) ------------------------------

    def coll_barrier(self, comm) -> None:
        # host-driven barrier semantics: an empty psum over the mesh,
        # blocking the driver until every device participated (compiled
        # once per mesh via the run_method cache — round-2 weak #5)
        dc = _device_comm(comm)
        dc.run_method("barrier", np.zeros((dc.size,), "int32"))

    def coll_bcast(self, comm, buf, root: int):
        return _run(comm, "bcast", buf, root)

    def coll_reduce(self, comm, sendbuf, op: Op, root: int):
        return _run(comm, "reduce", sendbuf, op, root)

    def coll_allreduce(self, comm, sendbuf, op: Op):
        return _run(comm, "allreduce", sendbuf, op)

    def coll_gather(self, comm, sendbuf, root: int):
        return _run(comm, "gather", sendbuf, root)

    def coll_allgather(self, comm, sendbuf):
        return _run(comm, "allgather", sendbuf)

    def coll_scatter(self, comm, sendbuf, root: int):
        return _run(comm, "scatter", sendbuf, root)

    def coll_alltoall(self, comm, sendbuf):
        return _run(comm, "alltoall", sendbuf)

    def coll_reduce_scatter(self, comm, sendbuf, op: Op):
        return _run(comm, "reduce_scatter", sendbuf, op)

    def coll_reduce_scatter_block(self, comm, sendbuf, op: Op):
        return _run(comm, "reduce_scatter", sendbuf, op)

    def coll_scan(self, comm, sendbuf, op: Op):
        return _run(comm, "scan", sendbuf, op)

    def coll_exscan(self, comm, sendbuf, op: Op):
        return _run(comm, "exscan", sendbuf, op)

    # v-collectives: through the MPI API the device path sees one uniform
    # shard per rank (SPMD programs are single-shape), so these lower to
    # the dense forms; ragged counts are first-class on DeviceCommunicator
    # (allgatherv/scatterv/alltoallv with a static counts vector → pad+mask)

    def coll_gatherv(self, comm, sendbuf, root: int):
        return _run(comm, "gatherv", sendbuf, None, root)

    def coll_scatterv(self, comm, sendparts, root: int):
        return _run(comm, "scatterv", sendparts, None, root)

    def coll_allgatherv(self, comm, sendbuf):
        return _run(comm, "allgatherv", sendbuf)

    def coll_alltoallv(self, comm, sendparts):
        return _run(comm, "alltoallv", sendparts)
