"""Smoke-run the host-path examples under tpurun — examples are the
first thing a migrating user executes, so they must not rot.

Device-path examples (generate.py, osc_device_window.py, …) are
exercised by the parallel/ suites on the virtual mesh and by
tests/test_chip_smoke.py instead.

Nothing here needs an install: ranks get the checkout on PYTHONPATH from
the launcher, and an example run directly is run as a module from the repo
root (``python -m examples.<name>``), which puts the root on ``sys.path``.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CASES = [
    # (script, expected marker, np — darray needs a square rank count)
    ("ring.py", "3 processes in ring", 3),
    ("hello.py", "Hello, world", 3),
    ("connectivity.py", "Connectivity test on 3 processes PASSED", 3),
    ("ring_oshmem.py", "exiting", 3),
    ("oshmem_shmalloc.py", "shmalloc/shfree ok", 3),
    ("oshmem_circular_shift.py", "circular shift ok", 3),
    ("oshmem_symmetric_data.py", "verified symmetric data", 3),
    ("mprobe_task_queue.py", "no duplicates, no losses", 3),
    ("mpi4py_ring.py", "exiting", 3),
    ("rma_pscw.py", "dynamic window ok", 3),
    ("mpi4py_cart_halo.py", "halo exchange ok", 3),
    ("mpiio_darray.py", "darray collective IO ok", 4),
]


@pytest.mark.parametrize("script,marker,np_",
                         CASES, ids=[c[0] for c in CASES])
def test_example_runs_under_tpurun(script, marker, np_):
    proc = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun",
         "-np", str(np_), "--",
         sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, timeout=180, cwd=REPO)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-2000:]
    assert marker in out, out[-2000:]


def test_facade_collectives_bench_runs():
    """The facade-overhead microbench (examples/facade_collectives_bench)
    completes and prints per-collective ratios; the ratio VALUES are
    advisory on a 1-core box, so only the structure is asserted."""
    proc = subprocess.run(
        [sys.executable, "-m", "examples.facade_collectives_bench"],
        capture_output=True, text=True, timeout=400, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    for coll in ("allreduce", "allgather", "bcast"):
        assert coll in proc.stdout
    assert "ratio" in proc.stdout


def test_timeout_flag_kills_hung_job():
    """tpurun --timeout (mpirun parity): a hung job dies with a message
    and nonzero status; an unexpired timeout doesn't disturb exit 0."""
    import time

    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-np", "2",
         "--timeout", "5", "--",
         sys.executable, "-c", "import time; time.sleep(120)"],
        capture_output=True, text=True, timeout=90)
    assert r.returncode != 0
    assert time.time() - t0 < 60
    assert "timed out after 5" in r.stderr

    ok = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-np", "2",
         "--timeout", "120", "--",
         sys.executable, "-c", "print('fast')"],
        capture_output=True, text=True, timeout=90)
    assert ok.returncode == 0, ok.stderr[-500:]
