"""Multi-host mesh bootstrap: join the job-wide JAX coordination service.

The runtime side of the modex (≈ opal/mca/pmix/pmix.h:328-861: the
business-card exchange that feeds transport bring-up — fence :384, put
:396, get :407).  The launcher (plm) exports three facts into every rank's
environment:

- ``OMPI_TPU_COORD``  — ``host:port`` of the coordination service (a free
  port on rank 0's host, picked by the HNP);
- ``OMPI_TPU_NHOSTS`` — how many hosts the job spans;
- rank identity (``OMPI_TPU_RANK``/``SIZE``) from pmix.

``initialize_from_env()`` turns those into a global JAX view: every rank
becomes one ``jax.distributed`` process (rank 0 hosts the coordinator),
after which ``jax.devices()`` enumerates the chips of ALL hosts and a
``Mesh`` built over them shards programs across the pod — XLA collectives
ride ICI within a host/slice and DCN between them, which is the reference's
btl latency/bandwidth ranking (btl.h:1181-1183) decided by mesh layout
instead of parameters.

Not yet run on TPU hosts: the chip tool offers one host.  The sim-plm test
joins N CPU processes through the same coordinator and checks the fused
global device view (``jax.process_count()``), which exercises every line of
this path except the TPU topology fan-in.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from ompi_tpu.core import output
from ompi_tpu.core.config import VarType, register_var, var_registry

__all__ = ["ENV_COORD", "ENV_NHOSTS", "is_multihost_env",
           "initialize_from_env", "global_mesh"]

_log = output.get_stream("multihost")

ENV_COORD = "OMPI_TPU_COORD"
ENV_NHOSTS = "OMPI_TPU_NHOSTS"

register_var("multihost", "init_timeout", VarType.DOUBLE, 60.0,
             "seconds to wait for all ranks to join the jax.distributed "
             "coordination service")
register_var("multihost", "auto_init", VarType.BOOL, True,
             "join the job-wide device view during MPI init when the "
             "launcher exported a coordinator address")

_lock = threading.Lock()
_state = {"initialized": False}


def is_multihost_env() -> bool:
    """Did a multi-host launcher export a coordinator for this job?"""
    return ENV_COORD in os.environ


def initialize_from_env() -> bool:
    """Join the job-wide jax.distributed service if the env names one.

    Returns True once this process is part of the global device view
    (idempotent), False when the job is not multi-host.  Must run before
    any JAX backend use in this process — call it early (mpi.runtime.init
    does, when ``multihost_auto_init`` is on).
    """
    with _lock:
        if _state["initialized"]:
            return True
        if not is_multihost_env():
            return False
        coord = os.environ[ENV_COORD]
        rank = int(os.environ.get("OMPI_TPU_RANK", "0"))
        size = int(os.environ.get("OMPI_TPU_SIZE", "1"))
        timeout = int(var_registry.get("multihost_init_timeout") or 60)

        import jax

        # one jax.distributed process per rank: rank 0 hosts the
        # coordinator (the HNP picked its port on rank 0's host)
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=size,
            process_id=rank,
            initialization_timeout=timeout,
        )
        _state["initialized"] = True
        # NOTE: do NOT call jax.process_count()/device_count() here — they
        # force accelerator-backend initialization inside MPI init (the
        # join itself is pure coordination-service gRPC).  The device view
        # materializes lazily on first backend use.
        _log.verbose(1, "multihost: rank %d/%d joined %s",
                     rank, size, coord)
        return True


def is_initialized() -> bool:
    return _state["initialized"]


def shutdown(graceful: bool = True) -> None:
    """Leave the coordination service (call after the final barrier, so
    every rank disconnects before rank 0's coordinator goes away).

    ``graceful=False`` skips the synchronized jax.distributed.shutdown —
    required when a rank was respawned mid-job: its coordination-service
    task never rejoined (a new incarnation is rejected), so the shutdown
    barrier would wait on it forever.  Process exit reclaims everything.
    """
    with _lock:
        if not _state["initialized"]:
            return
        _state["initialized"] = False
    if not graceful:
        _log.verbose(1, "multihost: skipping synchronized shutdown "
                     "(respawned rank in the job)")
        return

    def _do() -> None:
        try:
            import jax

            jax.distributed.shutdown()
        except Exception as e:  # pragma: no cover - teardown best-effort
            _log.verbose(1, "multihost shutdown: %r", e)

    # watchdog: the synchronized shutdown blocks on every task arriving.
    # If ranks DISAGREE about graceful (a respawn raced the decision) the
    # barrier would never fill — bound the wait so the worst case is a
    # delay, not a hang; process exit reclaims the service either way.
    t = threading.Thread(target=_do, daemon=True)
    t.start()
    t.join(timeout=10.0)
    if t.is_alive():  # pragma: no cover - requires a raced respawn
        _log.error("multihost: synchronized shutdown did not complete "
                   "in 10s (peer skipped it?); abandoning the wait")


def global_mesh(axes: Optional[dict | list] = None):
    """A Mesh over the job's GLOBAL device set (all hosts).

    In a multi-host job this first joins the coordination service; in a
    single-host job it is plain ``make_mesh`` over the local devices.
    """
    initialize_from_env()
    from ompi_tpu.parallel.mesh import make_mesh

    return make_mesh(axes)
