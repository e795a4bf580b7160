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

from ompi_tpu.core import output
from ompi_tpu.core.buffer import BufferKind, BufferLocationError, classify
from ompi_tpu.core.config import VarType, register_var, var_registry
from ompi_tpu.core.mca import Component
from ompi_tpu.mpi.coll import coll_framework, rules
from ompi_tpu.mpi.op import Op

__all__ = ["XlaColl"]

_log = output.get_stream("coll")


def _dev_nbytes(buf) -> int:
    """Static byte size of a jax array OR tracer (shape/dtype are always
    static under jit — no materialization)."""
    try:
        return int(np.prod(buf.shape)) * buf.dtype.itemsize
    except Exception:  # noqa: BLE001 — unshaped input: decide as "small"
        return 0


import os as _os

_MEASURED_PATH = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                               "xla_measured_rules.conf")
_measured_cache: list = []  # [(mtime|None, RuleSet|None)] — len-1 memo


def _measured_rules():
    """The shipped measured-crossover RuleSet, or None when the file is
    absent, empty of rules, or was measured on a different platform than
    the one running now (cpu-measured crossovers must not steer TPU)."""
    import os

    try:
        mtime = os.stat(_MEASURED_PATH).st_mtime
    except OSError:
        return None
    if _measured_cache and _measured_cache[0][0] == mtime:
        return _measured_cache[0][1]
    rs = None
    try:
        loaded = rules.load_rules(_MEASURED_PATH)
    except Exception as e:  # noqa: BLE001 — a bad shipped file must not
        # break collectives; memoized below, so this is logged once
        _log.error("shipped rules file %s does not load, fixed decision "
                   "rules apply: %r", _MEASURED_PATH, e)
    else:
        import jax

        if (len(loaded) > 0
                and loaded.meta.get("platform") == jax.default_backend()):
            rs = loaded
    _measured_cache[:] = [(mtime, rs)]
    return rs


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
    """Device collectives with a tuned-style decision layer.

    ≈ coll/tuned's fixed decision (coll_tuned_decision_fixed.c:44-87)
    transposed to the device path: per collective the choice is between the
    XLA-native lowering (psum / all_gather — latency-optimal, lets XLA pick
    the ICI algorithm) and an explicit ppermute/2-phase form whose
    communication shape favors bandwidth or a DCN-crossing axis (the
    btl.h:1181-1183 latency/bandwidth ranking axis, SURVEY §2.6).  The
    selection is (bytes × comm size × axis kind), overridable per
    collective by config var or the same dynamic rules file the host path
    honors."""

    NAME = "xla"
    PRIORITY = 60        # above host (40); the dispatcher routes by buffer
    HANDLES = frozenset({"device", "traced"})

    # "qint8" (EQuARX-style int8 wire format, device_comm.allreduce_qint8)
    # is in the menu for forcing/tuning but is LOSSY and never chosen by
    # the auto decision
    ALGORITHMS = {
        "allreduce": ("psum", "rs_ag", "segmented", "qint8"),
        "allgather": ("all_gather", "ring"),
        "bcast": ("psum_mask", "ring"),
    }
    # collective → algorithm → DeviceCommunicator method
    _IMPL = {
        "allreduce": {"psum": "allreduce", "rs_ag": "allreduce_rs_ag",
                      "segmented": "allreduce_segmented",
                      "qint8": "allreduce_qint8"},
        "allgather": {"all_gather": "allgather", "ring": "allgather_ring"},
        "bcast": {"psum_mask": "bcast", "ring": "bcast_ring"},
    }
    # algorithms that change RESULTS, not just schedules: measured and
    # forceable, but never auto-picked (tools/tune excludes them from
    # generated crossover rules; _decide never returns them)
    LOSSY = {"allreduce": frozenset({"qint8"})}

    def register_params(self) -> None:
        register_var("coll", "xla_dcn_axes", VarType.STRING, "",
                     "comma-separated mesh axis names that cross DCN "
                     "(inter-slice); collectives over them prefer "
                     "neighbor-shaped algorithms (ring/2-phase)")
        register_var("coll", "xla_allreduce_large", VarType.SIZE, 32 << 20,
                     "allreduce: at/above this PER-SHARD byte size switch "
                     "to the 2-phase reduce_scatter+all_gather form "
                     "(bandwidth-optimal ring shape; below, XLA's fused "
                     "psum wins on latency)")
        register_var("coll", "xla_dynamic_rules", VarType.STRING, "",
                     "path to a dynamic rules file for the DEVICE path "
                     "(same format as coll_host_dynamic_rules)")
        for name in self.ALGORITHMS:
            register_var("coll", f"xla_{name}_algorithm", VarType.STRING, "",
                         f"force a device {name} algorithm (empty = decide "
                         f"by size/axis kind)")

    def query(self, comm=None, **ctx) -> Optional[int]:
        return self.PRIORITY

    # -- decision layer ----------------------------------------------------

    def _crosses_dcn(self, dc) -> bool:
        spec = var_registry.get("coll_xla_dcn_axes") or ""
        dcn = {a.strip() for a in spec.split(",") if a.strip()}
        return bool(dcn.intersection(dc.axes))

    def _decide(self, coll: str, comm, dc, nbytes: int) -> str:
        """forced var > user rules file > shipped measured rules > fixed
        (bytes × size × axis kind)."""
        valid = self.ALGORITHMS[coll]
        alg = var_registry.get(f"coll_xla_{coll}_algorithm")
        src = f"config var coll_xla_{coll}_algorithm"
        if not alg:
            path = var_registry.get("coll_xla_dynamic_rules")
            if path:
                alg = rules.load_rules(path).lookup(coll, dc.size, nbytes)
                src = f"rules file {path}"
        if not alg and not self._crosses_dcn(dc):
            # measured crossovers from ompi_tpu.tools.tune, shipped next
            # to this component (the reference's fixed tables were also
            # measured numbers, coll_tuned_decision_fixed.c:56-74) —
            # consulted only when the file's provenance platform matches
            # the running backend AND this communicator's size is within
            # 2× of the measured mesh (8-device crossover points must not
            # steer a 2-device comm); DCN-spanning axes keep the
            # neighbor-shaped fixed decision (the measurement was
            # single-slice)
            rs = _measured_rules()
            if rs is not None:
                try:
                    meta_n = int(rs.meta.get("n_devices", 0))
                except ValueError:
                    meta_n = 0
                if meta_n and meta_n / 2 <= dc.size <= meta_n * 2:
                    alg = rs.lookup(coll, dc.size, nbytes)
                    src = "measured rules (xla_measured_rules.conf)"
        if alg:
            from ompi_tpu.mpi.constants import MPIException

            if alg not in valid:
                raise MPIException(
                    f"unknown device {coll} algorithm {alg!r} (from {src}); "
                    f"valid: {', '.join(valid)}")
            if (alg in self.LOSSY.get(coll, frozenset())
                    and not src.startswith("config var")):
                # a rules FILE must not silently change results; lossy
                # algorithms are an explicit per-run opt-in only
                raise MPIException(
                    f"device {coll} algorithm {alg!r} (from {src}) is "
                    f"lossy and may only be forced via the "
                    f"coll_xla_{coll}_algorithm config var")
            return alg
        # fixed decision: neighbor-shaped on DCN axes or huge payloads;
        # XLA-native (fused, ICI-aware) otherwise
        dcn = self._crosses_dcn(dc)
        if coll == "allreduce":
            large = var_registry.get("coll_xla_allreduce_large")
            return "rs_ag" if (dcn or nbytes >= large) else "psum"
        if coll == "allgather":
            return "ring" if dcn else "all_gather"
        return "ring" if dcn else "psum_mask"

    def _run_decided(self, coll: str, comm, buf, *args, **kw):
        dc = _device_comm(comm)
        nbytes = _dev_nbytes(buf)
        # canonical decision unit: PER-SHARD bytes (what each ICI link
        # moves).  A traced call sees the per-shard tracer already; a
        # driver-mode call sees the committed global array — normalize so
        # both modes look up the same rule boundary (and the tuner's
        # measured crossovers, recorded per-shard, apply uniformly).
        if classify(buf) is BufferKind.DEVICE:
            nbytes //= max(1, dc.size)
        alg = self._decide(coll, comm, dc, nbytes)
        return _run(comm, self._IMPL[coll][alg], buf, *args, **kw)

    # -- table slots (device implementations) ------------------------------

    def coll_barrier(self, comm) -> None:
        # host-driven barrier semantics: an empty psum over the mesh,
        # blocking the driver until every device participated (compiled
        # once per mesh via the run_method cache — round-2 weak #5)
        dc = _device_comm(comm)
        dc.run_method("barrier", np.zeros((dc.size,), "int32"))

    def coll_bcast(self, comm, buf, root: int):
        return self._run_decided("bcast", comm, buf, root)

    def coll_reduce(self, comm, sendbuf, op: Op, root: int):
        return _run(comm, "reduce", sendbuf, op, root)

    def coll_allreduce(self, comm, sendbuf, op: Op):
        # both impls take (x, op); rs_ag falls back to psum for non-SUM
        return self._run_decided("allreduce", comm, sendbuf, op)

    def coll_gather(self, comm, sendbuf, root: int):
        return _run(comm, "gather", sendbuf, root)

    def coll_allgather(self, comm, sendbuf):
        return self._run_decided("allgather", comm, sendbuf)

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
