"""Tests for allocation + mapping (≈ ras/simulator-driven rmaps tests)."""

import pytest

from ompi_tpu.core.config import var_registry
from ompi_tpu.runtime import ras, rmaps
from ompi_tpu.runtime.job import AppContext, Job


def mkjob(np):
    return Job([AppContext(argv=["true"], np=np)])


@pytest.fixture(autouse=True)
def _reset_vars():
    yield
    var_registry.set("ras_", "")
    var_registry.set("rmaps_", "")
    var_registry.set("rmaps_rr_policy", "byslot")


def sim(num_nodes, slots):
    var_registry.set("ras_", "simulator")
    var_registry.set("ras_sim_num_nodes", num_nodes)
    var_registry.set("ras_sim_slots_per_node", slots)


def test_localhost_allocation():
    job = ras.allocate(mkjob(4))
    assert len(job.nodes) == 1
    assert job.nodes[0].slots >= 4


def test_simulator_allocation():
    sim(3, 4)
    job = ras.allocate(mkjob(6))
    assert [n.name for n in job.nodes] == ["sim000", "sim001", "sim002"]
    assert all(n.slots == 4 for n in job.nodes)


def test_roundrobin_byslot_fills_nodes():
    sim(2, 4)
    job = rmaps.map_job(ras.allocate(mkjob(6)))
    placement = [p.node.name for p in job.procs]
    assert placement == ["sim000"] * 4 + ["sim001"] * 2
    assert [p.local_rank for p in job.procs] == [0, 1, 2, 3, 0, 1]


def test_roundrobin_bynode_spreads():
    sim(2, 4)
    var_registry.set("rmaps_rr_policy", "bynode")
    job = rmaps.map_job(ras.allocate(mkjob(6)))
    assert [p.node.name for p in job.procs] == [
        "sim000", "sim001", "sim000", "sim001", "sim000", "sim001"]


def test_oversubscription_wraps():
    sim(2, 2)
    job = rmaps.map_job(ras.allocate(mkjob(6)))
    assert len(job.procs) == 6
    assert [p.rank for p in job.procs] == list(range(6))


def test_ppr_mapping():
    sim(3, 4)
    var_registry.set("rmaps_", "ppr")
    var_registry.set("rmaps_ppr_n", 2)
    job = rmaps.map_job(ras.allocate(mkjob(6)))
    assert [p.node.name for p in job.procs] == [
        "sim000", "sim000", "sim001", "sim001", "sim002", "sim002"]


def test_ppr_does_not_fit():
    sim(2, 4)
    var_registry.set("rmaps_", "ppr")
    var_registry.set("rmaps_ppr_n", 1)
    with pytest.raises(RuntimeError, match="do not fit"):
        rmaps.map_job(ras.allocate(mkjob(6)))


def test_seq_mapping():
    sim(2, 8)
    var_registry.set("rmaps_", "seq")
    job = rmaps.map_job(ras.allocate(mkjob(4)))
    assert [p.node.name for p in job.procs] == [
        "sim000", "sim001", "sim000", "sim001"]


def test_hostfile(tmp_path):
    hf = tmp_path / "hosts"
    hf.write_text("nodeA slots=2\nnodeB slots=3  # comment\n\n")
    var_registry.set("ras_", "hostfile")
    var_registry.set("ras_hostfile", str(hf))
    job = ras.allocate(mkjob(5))
    assert [(n.name, n.slots) for n in job.nodes] == [("nodeA", 2), ("nodeB", 3)]
