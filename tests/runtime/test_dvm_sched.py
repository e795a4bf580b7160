"""Multi-tenant standing pool: gang scheduling, admission control, and
doctor-driven remediation.

Unit layer: ``gang_place`` (pure placement over fake host pools) and
``plan_remediation`` (the verdict → action ladder) are decision
functions with no VM attached — every arm is pinned here.

E2E layer: a real standing DVM serves concurrent tenants; admission at
capacity returns a machine-readable verdict (exit 75) instead of
hanging; two tenants share the pool without output or exit-code
bleed-through.  The full remediation cycles (SIGCONT probe on a seeded
straggler; requeue → budget → reject on a seeded mismatch) are
slow-marked — the pool-smoke CI job runs the live ladder on every push.
"""

import json
import sys
import time

import pytest

from ompi_tpu.runtime.dvm import gang_place, plan_remediation
from ompi_tpu.runtime.job import Node
from tests.runtime.test_dvm import (_hold, _standing_vm, _tpurun,
                                    _tpurun_bg)


# ---------------------------------------------------------------------------
# gang_place: pure placement over fake pools
# ---------------------------------------------------------------------------

def test_gang_place_spans_two_hosts():
    """A 4-rank gang over a 2+2 pool takes both hosts, pool order."""
    nodes = [Node("a", slots=2), Node("b", slots=2)]
    placed = gang_place(nodes, 4)
    assert placed is not None
    assert [n.name for n in placed] == ["a", "b"]


def test_gang_place_prefers_least_loaded():
    """1 free + 3 free and a 3-rank ask: the emptier host alone covers
    it — the loaded one is never touched."""
    nodes = [Node("a", slots=4, slots_inuse=3),
             Node("b", slots=4, slots_inuse=1)]
    placed = gang_place(nodes, 3)
    assert placed == [nodes[1]]
    # a 4-rank ask needs both, least-loaded FIRST
    placed = gang_place(nodes, 4)
    assert placed is not None
    assert placed[0] is nodes[1] and placed[1] is nodes[0]


def test_gang_place_skips_dead_and_silent_hosts():
    nodes = [Node("a", slots=2), Node("b", slots=2), Node("c", slots=2)]
    placed = gang_place(nodes, 2, dead=frozenset({1}),
                        hb_ages={2: 9.0}, hb_timeout=5.0)
    assert placed == [nodes[2]]
    # the silent host is usable again when its heartbeat is fresh —
    # though at equal load the quieter host (fresher heartbeat) leads
    placed = gang_place(nodes, 4, dead=frozenset({1}),
                        hb_ages={2: 0.1}, hb_timeout=5.0)
    assert placed == [nodes[2], nodes[1]]


def test_gang_place_all_or_nothing():
    """An impossible gang returns None and consumes NOTHING — a partial
    fit must never strand slots."""
    nodes = [Node("a", slots=2), Node("b", slots=2)]
    assert gang_place(nodes, 5) is None
    assert all(n.slots_inuse == 0 for n in nodes)
    # full hosts don't count toward the gang at all
    nodes[0].slots_inuse = 2
    assert gang_place(nodes, 3) is None


def test_gang_place_busy_tiebreak():
    """Equal subscription: the host whose tenants are busier (live
    metrics weight) loses the tie."""
    nodes = [Node("a", slots=4), Node("b", slots=4)]
    placed = gang_place(nodes, 2, busy={"a": 1.25})
    assert placed[0] is nodes[1]


# ---------------------------------------------------------------------------
# plan_remediation: every rung of the ladder
# ---------------------------------------------------------------------------

def test_plan_remediation_ladder():
    # not actionable: healthy / idle / no verdict never trigger anything
    assert plan_remediation("healthy", 0, 0, 2) == "none"
    assert plan_remediation("idle", -1, 0, 2) == "none"
    assert plan_remediation(None, -1, 0, 2) == "none"
    assert plan_remediation("no_data", 0, 0, 2) == "none"
    # straggler with a localized rank: cheapest rung first
    assert plan_remediation("straggler", 1, 0, 2) == "sigcont_probe"
    assert plan_remediation("straggler", 0, 1, 2) == "sigcont_probe"
    # straggler the doctor could not localize: placement is suspect
    assert plan_remediation("straggler", -1, 0, 2) == "requeue"
    # deadlock / mismatch: this placement is poisoned, try a fresh one
    assert plan_remediation("deadlock", -1, 0, 2) == "requeue"
    assert plan_remediation("mismatch", 0, 1, 2) == "requeue"
    # budget exhausted: degrade to reject, NEVER livelock
    assert plan_remediation("straggler", 0, 2, 2) == "reject"
    assert plan_remediation("deadlock", -1, 3, 2) == "reject"
    assert plan_remediation("mismatch", 1, 2, 2) == "reject"
    # a zero budget rejects on the first actionable verdict
    assert plan_remediation("deadlock", -1, 0, 0) == "reject"


# ---------------------------------------------------------------------------
# admission control on a live pool
# ---------------------------------------------------------------------------

def test_submit_over_pool_capacity_rejected(tmp_path):
    """np greater than the whole pool can NEVER fit: the verdict is an
    immediate machine-readable rejection (exit 75), not a hang."""
    with _standing_vm(tmp_path) as uri:        # 4 slots total (2+2)
        r = _tpurun("--dvm-submit", "-np", "9", "--dvm-uri", uri, "--",
                    sys.executable, "-c", "print('unreachable')")
        assert r.returncode == 75, (r.returncode, r.stderr)
        verdict = json.loads(r.stdout.strip().splitlines()[-1])
        assert verdict["verdict"] == "rejected"
        assert "can never fit" in verdict["reason"]


def test_admission_queue_full_then_fifo_drain(tmp_path):
    """Pool saturated + queue at dvm_queue_max: the next submission is
    REJECTED with the queue depth in the reason; the queued tenant still
    runs (FIFO) once the pool frees up."""
    with _standing_vm(tmp_path, "--mca", "dvm_queue_max", "1",
                      "--mca", "dvm_max_concurrent", "1") as uri:
        bounced = tmp_path / "bounced"
        a = _tpurun_bg("--dvm-submit", "-np", "4", "--dvm-uri", uri, "--",
                       sys.executable, "-c", _hold(bounced, 6, "HOLD up"))
        # wait until A is RUNNING (out of the pending queue) so B takes
        # the single queue slot
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            table = json.loads(
                _tpurun("--dvm-ps", "--dvm-uri", uri).stdout)
            if any(j.get("state") == "running"
                   for j in table.get("jobs", [])):
                break
            time.sleep(0.3)
        else:
            raise AssertionError("tenant A never started running")
        b = _tpurun_bg("--dvm-submit", "-np", "4", "--dvm-uri", uri,
                       "--", sys.executable, "-c", "print('B ran')")
        while time.monotonic() < deadline:
            table = json.loads(
                _tpurun("--dvm-ps", "--dvm-uri", uri).stdout)
            if table.get("queue_depth") == 1:
                queued = [j for j in table.get("jobs", [])
                          if j.get("state") == "queued"]
                assert queued and queued[0]["queue_age_s"] >= 0.0
                break
            time.sleep(0.3)
        else:
            raise AssertionError("tenant B never showed as queued")
        # the queue is full NOW: C must bounce, machine-readably
        c = _tpurun("--dvm-submit", "-np", "4", "--dvm-uri", uri, "--",
                    sys.executable, "-c", "print('unreachable')")
        assert c.returncode == 75, (c.returncode, c.stderr)
        verdict = json.loads(c.stdout.strip().splitlines()[-1])
        assert verdict["verdict"] == "rejected"
        assert "queue full" in verdict["reason"]
        bounced.touch()
        # FIFO drain: A then B both finish clean
        out_a, err_a = a.communicate(timeout=120)
        assert a.returncode == 0, (out_a[-1000:], err_a[-1000:])
        out_b, err_b = b.communicate(timeout=120)
        assert b.returncode == 0, (out_b[-1000:], err_b[-1000:])
        assert "B ran" in out_b


# ---------------------------------------------------------------------------
# tenant isolation on a shared pool
# ---------------------------------------------------------------------------

def test_two_tenants_no_output_or_exit_bleed(tmp_path):
    """Concurrent tenants on one pool: each client sees ONLY its own
    job's IOF, and a tenant's nonzero exit never leaks into its
    co-tenant's rc."""
    with _standing_vm(tmp_path) as uri:
        b_ended = tmp_path / "b_ended"
        a = _tpurun_bg("--dvm-submit", "-np", "2", "--dvm-uri", uri,
                       "--", sys.executable, "-c",
                       _hold(b_ended, 6, "TENANT_A")
                       + "print('A_DONE', flush=True)")
        time.sleep(1.0)
        b = _tpurun("--dvm-submit", "-np", "2", "--dvm-uri", uri, "--",
                    sys.executable, "-c",
                    "import sys; print('TENANT_B', flush=True); "
                    "sys.exit(3)")
        assert b.returncode == 3, (b.returncode, b.stderr)
        assert "TENANT_B" in b.stdout
        assert "TENANT_A" not in b.stdout        # jobid-routed IOF
        b_ended.touch()
        out_a, err_a = a.communicate(timeout=120)
        assert a.returncode == 0, (out_a[-1000:], err_a[-1000:])
        assert "TENANT_A" in out_a and "A_DONE" in out_a
        assert "TENANT_B" not in out_a           # jobid-routed IOF


# ---------------------------------------------------------------------------
# the live remediation ladder (slow: pool-smoke CI runs these per push)
# ---------------------------------------------------------------------------

STRAGGLER_APP = r"""
import numpy as np
import ompi_tpu
from ompi_tpu.testing import faultinject

comm = ompi_tpu.init()
acc = 0.0
for step in range(8):
    faultinject.step()
    acc += float(comm.allreduce(np.full(8, float(comm.rank + step)))[0])
print(f"rank {comm.rank} straggler-app done acc={acc:.0f}", flush=True)
ompi_tpu.finalize()
"""


def _scrape(uri, path):
    import urllib.request

    with open(uri + ".metrics") as f:
        http = f.read().strip()
    with urllib.request.urlopen(http + path, timeout=10) as resp:
        return resp.read().decode()


@pytest.mark.slow
def test_straggler_sigcont_probe_recovers(tmp_path):
    """The cheapest remediation rung, live: a rank self-SIGSTOPs inside
    its 3rd collective, survivors push stuck events, the watchdog's
    doctor verdict names the straggler, the actor SIGCONTs it — and the
    job exits 0 with the remediation on the FT timeline and counter."""
    with _standing_vm(tmp_path, "--metrics-port", "0",
                      "--mca", "trace_metrics_push_period", "0.5",
                      "--mca", "coll_stuck_timeout", "2",
                      "--mca", "dvm_remediate_grace_s", "2.0") as uri:
        r = _tpurun("--dvm-submit", "-np", "2", "--dvm-uri", uri,
                    "--mca", "faultinject_plan", "rank=1:stall@coll=3",
                    "--mca", "faultinject_seed", "0", "--",
                    sys.executable, "-c", STRAGGLER_APP, timeout=180)
        out = r.stdout + r.stderr
        assert r.returncode == 0, (r.returncode, out[-3000:])
        assert "rank 1 straggler-app done" in out, out[-3000:]
        metrics = _scrape(uri, "/metrics")
        assert "ompi_tpu_dvm_remediations_total 1" in metrics, \
            metrics[-2000:]
        # the actor's grace window outlives the job: poll for the
        # probe's conclusion instead of scraping once
        deadline = time.monotonic() + 30
        actions, events = set(), []
        while time.monotonic() < deadline:
            status = json.loads(_scrape(uri, "/status"))
            events = [e for j in status["jobs"]
                      for e in j.get("ft_events", [])
                      if e["kind"] == "remediate"]
            actions = {e.get("info", {}).get("action") for e in events}
            if "recovered" in actions:
                break
            time.sleep(0.5)
        assert "sigcont" in actions, (actions, events)
        assert "recovered" in actions, (actions, events)
        recovered = [e for e in events
                     if e.get("info", {}).get("action") == "recovered"]
        assert recovered and recovered[0]["info"].get("latency_ms", 0) > 0


@pytest.mark.slow
def test_mismatch_requeue_then_budget_reject(tmp_path):
    """The top of the ladder, live: a seeded collective mismatch poisons
    every placement (the fault plan re-fires each life), so requeue
    burns the budget and the job degrades to a REJECTED verdict — never
    a livelock."""
    with _standing_vm(tmp_path, "--metrics-port", "0",
                      "--mca", "trace_metrics_push_period", "0.5",
                      "--mca", "coll_stuck_timeout", "2",
                      "--mca", "dvm_remediation_max", "1",
                      "--mca", "dvm_requeue_max", "1") as uri:
        r = _tpurun("--dvm-submit", "-np", "2", "--dvm-uri", uri,
                    "--mca", "faultinject_plan",
                    "rank=1:mismatch@coll=3",
                    "--mca", "faultinject_seed", "0", "--",
                    sys.executable, "-c", STRAGGLER_APP, timeout=300)
        assert r.returncode != 0, "a poisoned job must not exit 0"
        verdict = json.loads(r.stdout.strip().splitlines()[-1])
        assert verdict.get("verdict") == "rejected", (verdict, r.stderr)
        assert "budget" in verdict.get("reason", ""), verdict
        status = json.loads(_scrape(uri, "/status"))
        kinds = [e["kind"] for j in status["jobs"]
                 for e in j.get("ft_events", [])]
        assert "requeue" in kinds, status
        actions = {e.get("info", {}).get("action")
                   for j in status["jobs"]
                   for e in j.get("ft_events", [])
                   if e["kind"] == "remediate"}
        assert "requeue" in actions and "reject" in actions, status
