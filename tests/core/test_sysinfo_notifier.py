"""sysinfo (timer/pstat/backtrace), notifier, mpiext, schizo — the small
always-built frameworks (≈ opal/mca/{timer,pstat,backtrace},
orte/mca/notifier, ompi/mpiext, orte/mca/schizo)."""

import os
import subprocess
import sys

import pytest

from ompi_tpu.core.sysinfo import Timer, install_backtrace_handlers, proc_stats


def test_timer_monotone_interval():
    t = Timer()
    a = Timer.cycles()
    b = Timer.cycles()
    assert b >= a
    dt = t.restart()
    assert dt >= 0
    assert t.elapsed_s() < 10


def test_proc_stats_self():
    st = proc_stats()
    assert st["pid"] == os.getpid()
    assert st["rss_bytes"] > 1 << 20       # a python process is > 1 MiB
    assert st["utime_s"] >= 0
    if st.get("threads") is not None:
        assert st["threads"] >= 1


def test_proc_stats_other_pid():
    st = proc_stats(os.getppid())
    assert st["pid"] == os.getppid()


def test_backtrace_handlers_idempotent():
    assert install_backtrace_handlers()
    assert install_backtrace_handlers()   # second call: already active
    import faulthandler

    assert faulthandler.is_enabled()


def test_notifier_log_component_and_threshold(capsys):
    from ompi_tpu.core.config import var_registry
    from ompi_tpu.runtime.notifier import Severity, notify

    notify(Severity.ERROR, "test-event", "the details")
    err = capsys.readouterr().err
    assert "test-event" in err and "the details" in err
    # below threshold (default warn): silent
    notify(Severity.DEBUG, "invisible-event", "x")
    assert "invisible-event" not in capsys.readouterr().err


def test_mpiext_registry():
    from ompi_tpu.mpi import mpiext

    assert {"tpu", "device_heap", "sequence_parallel"} <= mpiext.extensions()
    # probes never raise; on the CPU test rig tpu probe is simply False/True
    assert mpiext.query_tpu_support() in (True, False)
    assert mpiext.query_sequence_parallel_support() is True
    assert mpiext.has_extension("no-such-ext") is False
    mpiext.register_extension("always", lambda: True)
    assert mpiext.has_extension("always") is True


def test_schizo_translates_mpirun_cli():
    from ompi_tpu.tools.schizo import translate_mpirun

    targv, env = translate_mpirun(
        ["-np", "4", "--mca", "coll", "host", "-x", "FOO=bar",
         "--machinefile", "hf", "--map-by", "node", "--bind-to", "core",
         "--timeout", "30",
         "--report-bindings", "./a.out", "arg1"])
    assert targv[:2] == ["-np", "4"]
    assert ["--mca", "coll", "host"] == targv[2:5]
    assert ["--hostfile", "hf"] == targv[5:7]
    assert ["--map-by", "bynode"] == targv[7:9]
    assert ["--timeout", "30"] == targv[9:11]
    assert targv[11:] == ["--", "./a.out", "arg1"]
    assert env == {"FOO": "bar"}


def test_schizo_rejects_unknown_option():
    from ompi_tpu.tools.schizo import translate_mpirun

    with pytest.raises(ValueError):
        translate_mpirun(["--definitely-not-a-flag", "x", "./a.out"])


def test_schizo_end_to_end_mpirun():
    env = dict(os.environ)
    env.pop("OMPI_TPU_RANK", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.schizo", "-np", "2",
         "-x", "SCHIZO_PROBE=42", "--",
         sys.executable, "-c",
         "import os, ompi_tpu\n"
         "comm = ompi_tpu.init()\n"
         "print(f'rank {comm.rank} sees {os.environ[\"SCHIZO_PROBE\"]}')\n"
         "ompi_tpu.finalize()\n"],
        capture_output=True, text=True, timeout=90, env=env, cwd=repo)
    assert r.returncode == 0, (r.stdout, r.stderr)
    for rank in range(2):
        assert f"rank {rank} sees 42" in r.stdout


def test_hwtopo_discover():
    from ompi_tpu.core.hwtopo import discover

    t = discover()
    assert t.logical_cpus >= 1
    assert 1 <= t.physical_cores <= t.logical_cpus
    assert t.packages >= 1
    assert 1 <= t.allowed_cpus <= t.logical_cpus
    assert t.smt >= 1


def test_ras_localhost_uses_topology():
    from ompi_tpu.core.hwtopo import discover
    from ompi_tpu.runtime.job import AppContext, Job
    from ompi_tpu.runtime import ras

    job = Job([AppContext(argv=["true"], np=1)])
    ras.allocate(job)
    assert job.nodes[0].slots >= max(1, discover().allowed_cpus)


def test_rtc_bind_child():
    import os

    from ompi_tpu.core.config import var_registry
    from ompi_tpu.runtime.rtc import bind_child

    assert bind_child(os.getpid(), 0) is None     # default: none
    var_registry.set("rtc_bind", "core")
    allowed = sorted(os.sched_getaffinity(0))
    try:
        cpu = bind_child(os.getpid(), 1)
        if len(allowed) < 2:
            assert cpu is None            # single-cpu host: no-op
        else:
            assert cpu == allowed[1 % len(allowed)]
            assert os.sched_getaffinity(0) == {cpu}
    finally:
        # restore INSIDE finally: a failed assert must not leave the
        # whole pytest process pinned to one cpu
        os.sched_setaffinity(0, set(allowed))
        var_registry.set("rtc_bind", "none")
