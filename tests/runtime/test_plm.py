"""Multi-host launch: the daemon tree (plm/sim) end to end.

≈ the reference's plm/rsh + orted on localhost (SURVEY.md §4 mechanism 2),
with simulated host identities: ranks on different sim-hosts refuse the shm
BTL and ride tcp, so the cross-host data path runs for real on one machine
(orte/mca/plm/rsh/plm_rsh_module.c:102,697; orte/orted/orted_main.c:223).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tpurun(*args, timeout=120, stdin_data=None):
    env = dict(os.environ)
    env.pop("OMPI_TPU_RANK", None)
    env.setdefault("JAX_PLATFORMS", "cpu")  # keep children light
    return subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
        input=stdin_data)


def test_sim_hello_two_hosts():
    r = tpurun("-np", "4", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "-c",
               "import os; print('RANKHOST', os.environ['OMPI_TPU_RANK'],"
               " os.environ.get('OMPI_TPU_FAKE_HOST'))")
    assert r.returncode == 0, r.stderr
    hosts = {}
    for line in r.stdout.splitlines():
        if "RANKHOST" in line:  # IOF may prefix a [mh,rank] tag
            rank, host = line.split("RANKHOST", 1)[1].split()
            hosts[rank] = host
    assert len(hosts) == 4, r.stdout
    # ranks actually landed on two distinct simulated hosts
    assert len(set(hosts.values())) == 2, hosts


def test_sim_cross_host_allgather():
    # a real collective spanning the fake host boundary: shm must refuse
    # (different OMPI_TPU_FAKE_HOST) and tcp carry the traffic
    prog = (
        "import os\n"
        "import ompi_tpu\n"
        "comm = ompi_tpu.init()\n"
        "vals = comm.allgather(comm.rank * 10)\n"
        "assert [int(v) for v in vals] == "
        "[r * 10 for r in range(comm.size)], vals\n"
        "host = os.environ['OMPI_TPU_FAKE_HOST']\n"
        "peers = comm.allgather(int(host[3:]))\n"  # 'sim000' → 0
        "assert len(set(int(p) for p in peers)) == 2, peers\n"
        "print(f'rank {comm.rank} on {host}: allgather ok')\n"
        "ompi_tpu.finalize()\n"
    )
    r = tpurun("-np", "4", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "-c", prog)
    assert r.returncode == 0, r.stderr + r.stdout
    for rank in range(4):
        assert f"rank {rank} on " in r.stdout


def test_sim_ring_example():
    r = tpurun("-np", "4", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "examples/ring.py")
    assert r.returncode == 0, r.stderr
    assert "Process 0 decremented value: 0" in r.stdout


def test_sim_nonzero_exit_propagates():
    r = tpurun("-np", "4", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "-c",
               "import os, sys, time\n"
               "rank = int(os.environ['OMPI_TPU_RANK'])\n"
               "if rank == 1: sys.exit(7)\n"
               "time.sleep(30)")
    assert r.returncode == 7, (r.returncode, r.stderr)
    assert "aborted" in r.stderr.lower()


def test_sim_app_abort_kills_job():
    prog = (
        "import time\n"
        "from ompi_tpu.runtime.pmix import PMIxClient\n"
        "c = PMIxClient()\n"
        "if c.rank == 2:\n"
        "    c.abort('deliberate', status=5)\n"
        "time.sleep(30)\n"
    )
    r = tpurun("-np", "4", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "-c", prog, timeout=60)
    assert r.returncode != 0
    assert "abort" in r.stderr.lower()


def test_sim_stdin_to_rank0():
    prog = (
        "import os, sys\n"
        "rank = int(os.environ['OMPI_TPU_RANK'])\n"
        "data = sys.stdin.read()\n"
        "print(f'rank {rank} stdin: {data!r}')\n"
    )
    r = tpurun("-np", "2", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "-c", prog, stdin_data="ping\n")
    assert r.returncode == 0, r.stderr
    assert "rank 0 stdin: 'ping\\n'" in r.stdout
    # non-target ranks read EOF from /dev/null immediately
    assert "rank 1 stdin: ''" in r.stdout


def test_sim_daemon_death_aborts_job():
    # a rank SIGKILLs its own orted (its parent): the HNP must detect the
    # lost lifeline and abort instead of waiting forever
    prog = (
        "import os, signal, time\n"
        "rank = int(os.environ['OMPI_TPU_RANK'])\n"
        "if rank == 3:\n"
        "    time.sleep(0.5)\n"
        "    os.kill(os.getppid(), signal.SIGKILL)\n"
        "time.sleep(60)\n"
    )
    r = tpurun("-np", "4", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "-c", prog, timeout=60)
    assert r.returncode != 0
    assert "died" in r.stderr.lower() or "daemon" in r.stderr.lower(), r.stderr


def test_sim_pmix_modex_across_hosts():
    prog = (
        "from ompi_tpu.runtime.pmix import PMIxClient\n"
        "c = PMIxClient()\n"
        "c.put('card', f'addr-of-{c.rank}')\n"
        "data = c.fence(collect=True)\n"
        "peer = (c.rank + 1) % c.size\n"
        "assert data[f'card@{peer}'] == f'addr-of-{peer}', data\n"
        "print(f'rank {c.rank} modex ok')\n"
        "c.finalize()\n"
    )
    r = tpurun("-np", "4", "--plm", "sim", "--hosts", "2", "--",
               sys.executable, "-c", prog)
    assert r.returncode == 0, r.stderr
    for rank in range(4):
        assert f"rank {rank} modex ok" in r.stdout


def test_sim_multihost_jax_bootstrap():
    # 2 sim "hosts" × 1 rank: both join the jax.distributed coordinator the
    # HNP exported (OMPI_TPU_COORD) and observe the same fused device view
    prog = (
        "import jax\n"
        "import ompi_tpu\n"
        "comm = ompi_tpu.init()\n"
        "assert jax.process_count() == 2, jax.process_count()\n"
        "counts = comm.allgather(jax.device_count())\n"
        "assert int(counts[0]) == int(counts[1]) == 4, counts\n"
        "print(f'rank {comm.rank}: global devices {jax.device_count()}')\n"
        "ompi_tpu.finalize()\n"
    )
    env = dict(os.environ)
    env.pop("OMPI_TPU_RANK", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-np", "2",
         "--plm", "sim", "--hosts", "2", "--", sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "rank 0: global devices 4" in r.stdout
    assert "rank 1: global devices 4" in r.stdout


def _ssh_localhost_ok() -> bool:
    import shutil

    if shutil.which("ssh") is None:
        return False
    return subprocess.run(
        ["ssh", "-o", "BatchMode=yes", "-o", "StrictHostKeyChecking=no",
         "-o", "ConnectTimeout=2", "localhost", "true"],
        capture_output=True).returncode == 0


@pytest.mark.skipif(not _ssh_localhost_ok(),
                    reason="passwordless ssh to localhost not available")
def test_ssh_plm_localhost():
    # exercise the real ssh transport once (≈ plm/rsh with rsh_agent=ssh)
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".hf", delete=False) as fh:
        fh.write("localhost slots=2\n")
        hf = fh.name
    try:
        r = tpurun("-np", "2", "--plm", "ssh", "--hostfile", hf, "--",
                   sys.executable, "-c",
                   "import os; print('ssh rank', os.environ['OMPI_TPU_RANK'])")
        assert r.returncode == 0, r.stderr
        assert "ssh rank 0" in r.stdout and "ssh rank 1" in r.stdout
    finally:
        os.unlink(hf)


def _ssh_localhost_ok() -> bool:
    import shutil

    if shutil.which("ssh") is None:
        return False
    try:
        r = subprocess.run(
            ["ssh", "-o", "BatchMode=yes", "-o", "StrictHostKeyChecking=no",
             "-o", "ConnectTimeout=3", "localhost", "true"],
            capture_output=True, timeout=10)
        return r.returncode == 0
    except (subprocess.TimeoutExpired, OSError):
        return False


def test_ssh_plm_localhost_real():
    """Opt-in real exercise of plm/ssh: 2 ranks over `ssh localhost`
    (≈ plm_rsh_module.c:697 tree-spawn degenerated to one remote).

    The probe runs INSIDE the test (not in a skipif decorator) so plain
    collection of this module never pays the multi-second ssh attempt.
    """
    if not _ssh_localhost_ok():
        pytest.skip("passwordless ssh to localhost unavailable")
    prog = ("import ompi_tpu\n"
            "comm = ompi_tpu.init()\n"
            "out = comm.allreduce(__import__('numpy').ones(4))\n"
            "print(f'rank {comm.rank} ssh-ok {float(out[0]):.0f}')\n"
            "ompi_tpu.finalize()\n")
    import os as _os
    hf = os.path.join(REPO, ".pytest-ssh-hostfile")
    with open(hf, "w") as f:
        f.write("localhost\nlocalhost\n")
    try:
        r = tpurun("-np", "2", "--plm", "ssh", "--hostfile", hf, "--",
                   sys.executable, "-c", prog, timeout=90)
        assert r.returncode == 0, (r.stdout, r.stderr)
        for rank in range(2):
            assert f"rank {rank} ssh-ok 2" in r.stdout
    finally:
        _os.unlink(hf)
