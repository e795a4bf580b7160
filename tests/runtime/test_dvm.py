"""Persistent DVM: a standing daemon VM runs many jobs without
re-launching (≈ orte-dvm + orte-submit + orte-ps).

The second submission must be measurably faster than the first full
launch because the daemon tree (and on real pods, the TPU runtime
warm-up) is already up.
"""

import contextlib
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _env():
    env = dict(os.environ)
    env.pop("OMPI_TPU_RANK", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def _tpurun_bg(*args):
    return subprocess.Popen(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", *args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_env(), cwd=REPO)


def _tpurun(*args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", *args],
        capture_output=True, text=True, timeout=timeout, env=_env(),
        cwd=REPO)


def _hold(release, seconds, said):
    """A job's program: say ``said``, then run until the file ``release``
    exists, ``seconds`` at most.  The test that watches the job run touches
    the file when it has seen what it waits for."""
    return (f"import os, time\nprint({said!r}, flush=True)\n"
            f"end = time.monotonic() + {seconds}\n"
            f"while not os.path.exists({str(release)!r}) "
            "and time.monotonic() < end:\n    time.sleep(0.05)\n")


@contextlib.contextmanager
def _standing_vm(tmp_path, *extra_args):
    """Start a DVM, wait for its URI, always stop it."""
    uri = str(tmp_path / "dvm.uri")
    server = _tpurun_bg("--dvm-start", "--hosts", "2", "--slots", "4",
                        *extra_args, "--dvm-uri", uri)
    deadline = time.monotonic() + 60
    try:
        while not os.path.exists(uri):
            if server.poll() is not None:
                raise AssertionError(f"dvm died: {server.stderr.read()}")
            if time.monotonic() > deadline:
                raise AssertionError("dvm uri never appeared")
            time.sleep(0.1)
        yield uri
    finally:
        _tpurun("--dvm-stop", "--dvm-uri", uri, timeout=30)
        try:
            server.wait(timeout=15)
        except subprocess.TimeoutExpired:
            server.kill()


@pytest.fixture
def dvm(tmp_path):
    with _standing_vm(tmp_path) as uri:
        yield uri


@pytest.fixture
def dvm_respawn(tmp_path):
    """A standing VM whose errmgr policy is respawn (set at start)."""
    with _standing_vm(tmp_path, "--mca", "errmgr", "respawn") as uri:
        yield uri


def test_two_jobs_one_vm_are_served_by_the_same_daemons(dvm):
    """Two jobs on one VM: the SAME daemons serve both (the daemons' pids
    before and after: no re-launch), each job's four ranks span both sim
    hosts.  That a warm submission skips the daemons' spawn and the tree's
    wiring is this structure; no wall clock is compared (two subprocess
    times a twentieth apart failed under load: PERF.md section 7, PR 74)."""
    prog = ("import os; print('JOB', os.environ['OMPI_TPU_RANK'], "
            "os.environ.get('OMPI_TPU_FAKE_HOST'))")
    pids_before = [d["pid"] for d in json.loads(
        _tpurun("--dvm-ps", "--dvm-uri", dvm).stdout)["daemons"]]

    for _ in range(2):
        r = _tpurun("--dvm-submit", "-np", "4", "--dvm-uri", dvm, "--",
                    sys.executable, "-c", prog)
        assert r.returncode == 0, r.stderr
        hosts = {ln.split()[1]: ln.split()[2]
                 for ln in r.stdout.splitlines() if "JOB" in ln}
        assert len(hosts) == 4
        assert len(set(hosts.values())) == 2     # spans both sim hosts

    pids_after = [d["pid"] for d in json.loads(
        _tpurun("--dvm-ps", "--dvm-uri", dvm).stdout)["daemons"]]
    assert pids_before == pids_after         # daemons persisted, no respawn
    assert all(p is not None for p in pids_before)


def test_dvm_ps_shows_daemons_and_history(dvm):
    r = _tpurun("--dvm-submit", "-np", "2", "--dvm-uri", dvm, "--",
                sys.executable, "-c", "print('hi')")
    assert r.returncode == 0, r.stderr
    ps = _tpurun("--dvm-ps", "--dvm-uri", dvm)
    assert ps.returncode == 0, ps.stderr
    table = json.loads(ps.stdout)
    assert len(table["daemons"]) == 2
    assert {d["host"] for d in table["daemons"]} == {"sim000", "sim001"}
    assert table["history"], table
    assert table["history"][-1]["rc"] == 0
    assert table["history"][-1]["np"] == 2


def test_dvm_ps_live_job(dvm, tmp_path):
    """orte-ps semantics: querying DURING a run shows running procs."""
    # a generous hold + window: on a loaded 1-core host each --dvm-ps
    # poll is a full interpreter start (seconds); a 6s job could finish
    # between two polls and the test would flake
    seen = tmp_path / "seen"
    slow = _tpurun_bg("--dvm-submit", "-np", "2", "--dvm-uri", dvm, "--",
                      sys.executable, "-c", _hold(seen, 20, "start"))
    try:
        deadline = time.monotonic() + 60
        live = None
        while time.monotonic() < deadline:
            ps = _tpurun("--dvm-ps", "--dvm-uri", dvm)
            table = json.loads(ps.stdout)
            cur = table.get("current_job")
            if cur and any(p["state"] == "running" for p in cur["procs"]):
                live = cur
                # a poll can land in the spawn window where the HNP
                # already marked procs RUNNING but the orteds have not
                # registered the pids yet (their stats reply is empty):
                # keep polling until a running snapshot carries usage —
                # the assertion below still fails if it never does
                if any("rss_mb" in p for p in cur["procs"]
                       if p["state"] == "running"):
                    break
            time.sleep(0.3)
        assert live is not None, "never observed a running job via ps"
        assert live["np"] == 2
        assert {p["host"] for p in live["procs"]} <= {"sim000", "sim001"}
        # orte-top columns: running ranks report live resource usage
        running = [p for p in live["procs"] if p["state"] == "running"]
        with_usage = [p for p in running if "rss_mb" in p]
        assert with_usage, live
        assert all(p["rss_mb"] > 0 and p["pid"] > 0 for p in with_usage)
    finally:
        seen.touch()
        slow.wait(timeout=60)


def test_dvm_metrics_scrape_end_to_end(tmp_path):
    """The live observability plane, end to end on a real standing VM:
    a 2-rank job's pvar snapshots ride the rank→orted UDP uplink and
    TAG_METRICS up the tree; the DVM's /metrics serves them under the
    job's label, and /status carries the FT event timeline after a
    seeded rank death."""
    import urllib.request

    # errmgr is a VM-level selection on a standing DVM (the policy runs
    # in the server process): notify lets the seeded-kill job below
    # continue instead of being torn down by the default abort
    with _standing_vm(tmp_path, "--metrics-port", "0",
                      "--mca", "errmgr", "notify") as uri:
        with open(uri + ".metrics") as f:
            http = f.read().strip()

        prog = ("import numpy as np, ompi_tpu\n"
                "comm = ompi_tpu.init()\n"
                "peer = (comm.rank + 1) % comm.size\n"
                "r = comm.irecv(source=(comm.rank - 1) % comm.size, tag=1)\n"
                "comm.send(np.ones(64), dest=peer, tag=1)\n"
                "r.wait()\n"
                "import time; time.sleep(1.5)\n"   # one on-period push
                "ompi_tpu.finalize()\n")
        # host-plane test: the jax.distributed bootstrap adds nothing
        # here and its coordinator handshake can flake a loaded 2-core
        # box (preemption SIGTERM racing job teardown)
        r = _tpurun("--dvm-submit", "-np", "2", "--dvm-uri", uri,
                    "--mca", "multihost_auto_init", "0", "--",
                    sys.executable, "-c", prog)
        assert r.returncode == 0, r.stderr

        def scrape(path):
            with urllib.request.urlopen(http + path, timeout=10) as resp:
                return resp.read().decode()

        metrics = scrape("/metrics")
        # per-rank series under the job label, both ranks
        assert 'ompi_tpu_pml_zero_copy_sends_total{job="' in metrics, \
            metrics[:2000]
        assert ',rank="0"}' in metrics and ',rank="1"}' in metrics
        # the per-job aggregated family
        assert "ompi_tpu_job_pml_zero_copy_sends_total{job=" in metrics
        # DVM gauges
        assert "ompi_tpu_dvm_jobs_completed_total 1" in metrics

        # seeded rank death under notify → a detect event on the
        # timeline (rank 0 exits via os._exit: a finalize barrier with
        # a dead peer would fail fast by design and muddy the rc)
        kill = ("import os, time, ompi_tpu\n"
                "comm = ompi_tpu.init()\n"
                "if comm.rank == 1:\n"
                "    os._exit(9)\n"
                "time.sleep(2.0)\n"
                "os._exit(0)\n")
        r = _tpurun("--dvm-submit", "-np", "2", "--dvm-uri", uri,
                    "--mca", "multihost_auto_init", "0", "--",
                    sys.executable, "-c", kill)
        assert r.returncode == 9, (r.returncode, r.stderr)

        status = json.loads(scrape("/status"))
        assert status["daemons"], status
        jobs = {j["jobid"]: j for j in status["jobs"]}
        completed = [j for j in jobs.values()
                     if j.get("state") == "completed"]
        assert completed, status
        kinds = [e["kind"] for j in jobs.values()
                 for e in j.get("ft_events", [])]
        assert "detect" in kinds, status
        # both jobs kept separate label spaces in the aggregate
        assert len(jobs) >= 2, jobs.keys()


def test_dvm_propagates_nonzero_exit(dvm):
    r = _tpurun("--dvm-submit", "-np", "2", "--dvm-uri", dvm, "--",
                sys.executable, "-c", "import sys; sys.exit(3)")
    assert r.returncode == 3, (r.returncode, r.stderr)


def test_dvm_submit_ships_mca_env(dvm):
    """--mca on --dvm-submit must configure the APP procs (which run
    under the DVM server), not the client process."""
    r = _tpurun("--dvm-submit", "-np", "1", "--dvm-uri", dvm,
                "--mca", "pml_eager_limit", "4097", "--",
                sys.executable, "-c",
                "import os; print('MCA',"
                " os.environ.get('OMPI_TPU_MCA_pml_eager_limit'))")
    assert r.returncode == 0, r.stderr
    assert "MCA 4097" in r.stdout


def test_no_dvm_running_clear_error(tmp_path):
    r = _tpurun("--dvm-ps", "--dvm-uri", str(tmp_path / "nope.uri"))
    assert r.returncode != 0
    combined = r.stderr + r.stdout
    assert "no DVM running" in combined or "cannot reach" in combined


def test_clean_sweeps_dead_inboxes(tmp_path, monkeypatch):
    """≈ orte-clean: a dead rank's shm inbox (doorbell with no reader)
    and an unmapped old segment go; a LIVE inbox and a MAPPED segment
    stay.  Hermetic: the sweep roots and the DVM-uri probe are pinned
    into tmp_path (the real per-user uri file must never be touched)."""
    import mmap
    import os

    from ompi_tpu.runtime import clean as clean_mod
    from ompi_tpu.runtime import dvm as dvm_mod

    base = str(tmp_path)
    monkeypatch.setattr(clean_mod, "_dirs", lambda: [base])
    monkeypatch.setattr(dvm_mod, "default_uri_path",
                        lambda: os.path.join(base, "no-such-uri"))
    # dead inbox: fifo exists, nobody reads it
    dead = os.path.join(base, "otpu-shm-dead1")
    os.mkdir(dead)
    os.mkfifo(os.path.join(dead, "doorbell"))
    # live inbox: hold the read end open like a running poller
    live = os.path.join(base, "otpu-shm-live1")
    os.mkdir(live)
    os.mkfifo(os.path.join(live, "doorbell"))
    rd = os.open(os.path.join(live, "doorbell"),
                 os.O_RDONLY | os.O_NONBLOCK)
    # old UNMAPPED segment: swept by the no-process-maps-it rule
    seg = os.path.join(base, "otpu-shfp-0-deadbeef-1")
    open(seg, "wb").write(b"\0" * 8)
    os.utime(seg, (1, 1))
    # old but MAPPED segment: a live job's shared window — must stay
    mapped = os.path.join(base, "otpu-shwin-x-0-2")
    with open(mapped, "wb") as f:
        f.write(b"\0" * 4096)
    os.utime(mapped, (1, 1))
    mfd = os.open(mapped, os.O_RDWR)
    mem = mmap.mmap(mfd, 4096)
    try:
        removed = clean_mod.clean()
        assert dead in removed and seg in removed
        assert os.path.isdir(live) and os.path.exists(mapped)
        # dry run reports without removing
        would = clean_mod.clean(age=0.0001, dry_run=True)
        assert mapped in would and os.path.exists(mapped)
        # the big hammer takes everything of mine
        mem.close()
        os.close(mfd)
        removed = clean_mod.clean(age=0.0001)
        assert mapped in removed and not os.path.exists(mapped)
    finally:
        os.close(rd)


def test_dvm_runs_mpi4py_facade_script(dvm):
    """Launcher × compat composition: an mpi4py-spelled script (the
    migration on-ramp) submitted through the standing DVM — facade
    collectives + p2p must work under daemon-tree launch, not just
    direct tpurun."""
    prog = (
        "import numpy as np\n"
        "from ompi_tpu.compat import MPI\n"
        "comm = MPI.COMM_WORLD\n"
        "rank, size = comm.Get_rank(), comm.Get_size()\n"
        "got = np.zeros(size * 2, np.float64)\n"
        "comm.Allgather(np.full(2, float(rank)), got)\n"
        "assert got.tolist() == [float(r) for r in range(size) for _ in (0, 1)], got\n"
        "obj = comm.bcast({'n': size} if rank == 0 else None, root=0)\n"
        "assert obj['n'] == size\n"
        "print(f'facade rank {rank}/{size} ok')\n"
        "MPI.Finalize()\n")
    r = _tpurun("--dvm-submit", "-np", "3", "--dvm-uri", dvm, "--",
                sys.executable, "-c", prog)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    for rank in range(3):
        assert f"facade rank {rank}/3 ok" in r.stdout


def test_dvm_respawn_recovers_rank(dvm_respawn, tmp_path):
    """errmgr/respawn through the STANDING VM: a rank dies mid-job, the
    daemon revives it from its snapshot, p2p heals — and the job exits
    cleanly (the launcher runs respawn jobs device-plane-off
    automatically: a revived rank can't rejoin the coordination
    service, whose threads would otherwise pin survivors at exit)."""
    from tests.runtime.test_respawn import RESPAWN_APP

    env = _env()
    env["CKPT_DIR"] = str(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun",
         "--dvm-submit", "-np", "3", "--dvm-uri", dvm_respawn, "--",
         sys.executable, "-c", RESPAWN_APP],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-2000:]
    assert "rank 1 resumed at step 3" in out
    assert "rank 1 got rndv payload" in out
