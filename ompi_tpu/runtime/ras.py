"""RAS — resource allocation framework.

≈ orte/mca/ras: turns "where can I run" into a list of Nodes.  Components:

- ``localhost`` — N slots on this host (cpu count by default); the analog of
  oversubscribed local launch, the workhorse for tests.
- ``simulator`` — fabricates an arbitrary cluster from config vars, cloning
  orte/mca/ras/simulator/ras_sim_module.c:67-91 (ras_sim num_nodes /
  slots_per_node); lets mapping/binding logic be tested with no real machines.
- ``tpu``      — ``tpurun --tpu``: this host gets ONE slot.  A TPU chip belongs
  to one process at a time, so one rank drives all the chips of its host
  and the launcher itself never initialises a JAX backend (a launcher that
  did would hold the chips its ranks need).
- ``hostfile`` — parses a hostfile (``name slots=N`` lines), the reference's
  --hostfile path.
"""

from __future__ import annotations

import os
from typing import Optional

from ompi_tpu.core.config import VarType, register_var, var_registry
from ompi_tpu.core.mca import Component, Framework
from ompi_tpu.runtime.job import Job, Node

__all__ = ["ras_framework", "allocate"]

ras_framework = Framework("ras", "resource allocation")


@ras_framework.component
class LocalhostRAS(Component):
    NAME = "localhost"
    PRIORITY = 10

    def register_params(self) -> None:
        register_var("ras", "localhost_slots", VarType.INT, 0,
                     "slots on localhost (0 = discovered topology: "
                     "cpus this process may schedule on)")

    def allocate(self, job: Job, **ctx) -> list[Node]:
        slots = var_registry.get("ras_localhost_slots")
        if not slots:
            # topology-derived default (≈ hwloc feeding ras): the cpuset
            # width, not raw cpu count — a containerized launcher sees its
            # quota, not the whole machine
            from ompi_tpu.core.hwtopo import discover

            slots = discover().allowed_cpus
        # mpirun-style oversubscription: never under-allocate the job
        slots = max(slots, job.np)
        return [Node(name="localhost", slots=slots)]


@ras_framework.component
class SimulatorRAS(Component):
    """Fake clusters for tests (≈ ras_sim: num_nodes/topofiles params)."""

    NAME = "simulator"
    PRIORITY = 0  # never auto-selected; opt in via --mca ras simulator

    def register_params(self) -> None:
        register_var("ras", "sim_num_nodes", VarType.INT, 2,
                     "simulator: number of fake nodes")
        register_var("ras", "sim_slots_per_node", VarType.INT, 4,
                     "simulator: slots per fake node")

    def query(self, **ctx):
        return self.PRIORITY if ctx.get("allow_simulator", True) else None

    def allocate(self, job: Job, **ctx) -> list[Node]:
        n = var_registry.get("ras_sim_num_nodes")
        slots = var_registry.get("ras_sim_slots_per_node")
        return [Node(name=f"sim{i:03d}", slots=slots) for i in range(n)]


@ras_framework.component
class TpuRAS(Component):
    """``--tpu``: one slot on this host; its rank owns every local chip.

    Nothing here imports jax: the process that first touches the TPU
    runtime holds the chips, and that must be the rank, not the launcher.
    """

    NAME = "tpu"
    PRIORITY = 50

    def query(self, **ctx):
        return self.PRIORITY if ctx.get("want_tpu", False) else None

    def allocate(self, job: Job, **ctx) -> list[Node]:
        if job.np > 1:
            raise ValueError(
                f"--tpu places one rank on this host (asked for {job.np}): "
                f"a TPU chip belongs to one process at a time, so a second "
                f"rank could not open the chips the first one holds.  Use "
                f"-np 1 and let that rank drive all local chips.")
        return [Node(name=os.uname().nodename, slots=1)]


@ras_framework.component
class HostfileRAS(Component):
    NAME = "hostfile"
    PRIORITY = 40

    def register_params(self) -> None:
        register_var("ras", "hostfile", VarType.STRING, "",
                     "path to hostfile (lines: <name> [slots=N])")

    def query(self, **ctx):
        path = ctx.get("hostfile") or var_registry.get("ras_hostfile")
        return self.PRIORITY if path else None

    def allocate(self, job: Job, hostfile: Optional[str] = None, **ctx) -> list[Node]:
        path = hostfile or var_registry.get("ras_hostfile")
        nodes = []
        with open(path) as fh:
            for line in fh:
                line = line.split("#", 1)[0].strip()
                if not line:
                    continue
                parts = line.split()
                slots = 1
                for p in parts[1:]:
                    if p.startswith("slots="):
                        slots = int(p.split("=", 1)[1])
                nodes.append(Node(name=parts[0], slots=slots))
        return nodes


def allocate(job: Job, **context) -> Job:
    """Run the allocation phase: fill job.nodes (≈ orte_ras_base_allocate)."""
    comp = ras_framework.select(**context)
    job.nodes = comp.allocate(job, **context)
    if not job.nodes or sum(n.slots for n in job.nodes) == 0:
        raise RuntimeError("allocation produced no usable slots")
    return job
