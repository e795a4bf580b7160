"""Local process launcher: fork/exec + IOF forwarding + state machine.

The HNP role of the reference, collapsed to one host: orterun's event-driven
launch DAG (orte/mca/state/hnp/state_hnp.c:74-112:
INIT→ALLOCATE→MAP→LAUNCH_APPS→RUNNING→TERMINATED), odls's fork/exec with
error reporting (orte/mca/odls/default/odls_default_module.c:47-56,140), and
iof's stdout/stderr forwarding with rank tagging (orte/mca/iof).

Multi-host launch (the reference's plm/rsh ssh tree) is out of scope for the
local launcher; the TPU analog — one launcher per TPU host, coordinated via
jax.distributed — plugs in as a different plm component later, reusing this
state machine.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from typing import Optional

from ompi_tpu.core import output
from ompi_tpu.core.config import VarType, register_var, var_registry
from ompi_tpu.runtime import errmgr as errmgr_mod
from ompi_tpu.runtime import pmix, ras, rmaps
from ompi_tpu.runtime.job import AppContext, Job, JobState, Proc, ProcState
from ompi_tpu.runtime.state import StateMachine

__all__ = ["LocalLauncher", "launch"]

_log = output.get_stream("launcher")

register_var("launcher", "tag_output", VarType.BOOL, True,
             "prefix forwarded stdout/stderr with [jobid,rank]")
register_var("launcher", "kill_grace_s", VarType.DOUBLE, 2.0,
             "seconds between SIGTERM and SIGKILL when aborting a job")


class LocalLauncher:
    """Launches a job's ranks as local OS processes (device-per-rank aware)."""

    def __init__(self, want_tpu: bool = False,
                 stdin_target: Optional[str] = None, **select_ctx) -> None:
        self.want_tpu = want_tpu
        # ≈ iof.h:27-43: launcher stdin goes to rank 0 by default;
        # "all" duplicates it to every rank, "none" gives ranks /dev/null.
        self.stdin_target = "0" if stdin_target is None else str(stdin_target)
        self.select_ctx = select_ctx
        self.sm = StateMachine()
        self.sm.add_state(JobState.INIT, self._st_init)
        self.sm.add_state(JobState.ALLOCATE, self._st_allocate)
        self.sm.add_state(JobState.MAP, self._st_map)
        self.sm.add_state(JobState.LAUNCH_APPS, self._st_launch)
        self.sm.add_state(JobState.RUNNING, self._st_running)
        self.server: Optional[pmix.PMIxServer] = None
        self._popen: dict[int, subprocess.Popen] = {}
        self._iof_threads: list[threading.Thread] = []
        self._errmgr = errmgr_mod.errmgr_framework.select(**select_ctx)
        self._kill_lock = threading.Lock()
        self._stdin_sinks: dict[int, object] = {}   # rank → _StdinWriter
        self._respawned: set[int] = set()  # ranks revived since last reap

    # -- state handlers (the launch DAG) ---------------------------------

    def _st_init(self, sm: StateMachine, job: Job) -> JobState:
        return JobState.ALLOCATE

    def _st_allocate(self, sm: StateMachine, job: Job) -> JobState:
        ras.allocate(job, want_tpu=self.want_tpu, **self.select_ctx)
        return JobState.MAP

    def _st_map(self, sm: StateMachine, job: Job) -> JobState:
        rmaps.map_job(job, **self.select_ctx)
        return JobState.LAUNCH_APPS

    def _proc_env(self, job: Job, proc: Proc) -> dict:
        # ≈ plm_rsh prefixing PATH/LD_LIBRARY_PATH with its install prefix
        # (orte/mca/plm/rsh/plm_rsh_module.c): make this framework importable
        # in children no matter their cwd.
        from ompi_tpu.core import pkg_root as _pkg_root

        root = _pkg_root()
        app = job.apps[proc.app_idx]
        env = dict(os.environ)
        env.update(app.env)
        errmgr_mod.apply_host_plane_policy(self._errmgr, env)
        pypath = env.get("PYTHONPATH", "")
        if root not in pypath.split(os.pathsep):
            env["PYTHONPATH"] = (
                root + (os.pathsep + pypath if pypath else ""))
        env[pmix.ENV_URI] = self.server.uri
        env[pmix.ENV_RANK] = str(proc.rank)
        env[pmix.ENV_SIZE] = str(job.np)
        env[pmix.ENV_JOBID] = str(job.jobid)
        env[pmix.ENV_LOCAL_RANK] = str(proc.local_rank)
        if proc.lives:
            env["OMPI_TPU_RESTART"] = str(proc.lives)
        return env

    def _launch_proc(self, job: Job, proc: Proc) -> bool:
        """Fork/exec one rank (first launch or errmgr respawn); False on
        failure to start (proc.state records why)."""
        app = job.apps[proc.app_idx]
        want_stdin = (self.stdin_target == "all"
                      or self.stdin_target == str(proc.rank))
        from ompi_tpu.runtime.rtc import bind_child

        try:
            p = subprocess.Popen(
                app.argv, env=self._proc_env(job, proc), cwd=app.cwd,
                stdin=(subprocess.PIPE if want_stdin
                       else subprocess.DEVNULL),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                start_new_session=True)
        except OSError as e:
            # ≈ odls error-pipe protocol: exec failure surfaces here.
            proc.state = ProcState.FAILED_TO_START
            proc.exit_code = 127
            output.show_help(
                "launcher", "failed-to-start",
                rank=proc.rank, argv0=app.argv[0], error=str(e))
            return False
        proc.pid = p.pid
        proc.state = ProcState.RUNNING
        # uptime clock (errmgr crash-loop governor) starts at the rank's
        # PMIx registration, not here — interpreter+jax boot (seconds on
        # a loaded box) must not count toward errmgr_min_uptime_s
        proc.launched_at = None
        bind_child(p.pid, proc.local_rank)
        with self._kill_lock:  # kill_job may iterate concurrently
            self._popen[proc.rank] = p
        if want_stdin:
            from ompi_tpu.runtime.orted import _StdinWriter

            # a respawned rank replaces its dead incarnation's writer —
            # retire the old one (its pipe is broken anyway) so sinks and
            # threads don't accumulate per restart
            old = self._stdin_sinks.pop(proc.rank, None)
            if old is not None:
                old.feed(None)
            self._stdin_sinks[proc.rank] = _StdinWriter(proc.rank, p.stdin)
        self._start_iof(job, proc, p)
        return True

    def respawn_proc(self, job: Job, proc: Proc) -> bool:
        """errmgr/respawn hook: revive a failed rank in place (same rank,
        same env plus OMPI_TPU_RESTART=<n>).  The running reap loop picks
        the new child up; the PMIx server counts the rank live again."""
        from ompi_tpu.runtime import ftevents

        proc.restarts += 1   # budget burn (governor may reset it)
        proc.lives += 1      # identity: monotone, survives budget resets
        proc.exit_code = None
        if not self._launch_proc(job, proc):
            return False
        ftevents.record("revive", jobid=job.jobid, rank=proc.rank,
                        lives=proc.lives)
        if self.server is not None:
            self.server.proc_revived(proc.rank, proc.lives)
        with self._kill_lock:
            self._respawned.add(proc.rank)
        return True

    def _st_launch(self, sm: StateMachine, job: Job) -> JobState:
        self.server = pmix.PMIxServer(
            size=job.np, on_abort=lambda r, s, m: self._on_abort(job, r, s, m))
        # rank-plane gossip feedback: a client-reported hung rank (alive
        # pid, silent to its peers) gets its pid reaped so the reap loop
        # sees a real exit and the errmgr policy runs
        self.server.on_failed_report = \
            lambda r, reason: self._reap_reported(r, reason)
        # the rank's first PMIx contact starts its uptime clock — the
        # crash-loop governor must not count interpreter boot as uptime
        self.server.on_client_contact = \
            lambda r: self._mark_contact(job, r)
        for proc in job.procs:
            if not self._launch_proc(job, proc):
                # Failure to start is fatal regardless of errmgr policy —
                # the job never assembled, so no policy (not even respawn)
                # is consulted: record the abort and reap what launched.
                if job.aborted_proc is None:
                    job.aborted_proc = proc
                    job.abort_reason = f"rank {proc.rank} failed to start"
                self.kill_job(job, exclude=proc)
                return JobState.RUNNING  # reap launched ranks, then ABORTED
        if self._stdin_sinks:
            self._start_stdin_pump()
        return JobState.RUNNING

    def _st_running(self, sm: StateMachine, job: Job) -> Optional[JobState]:
        # Reap children; first abnormal exit triggers the errmgr policy.
        with self._kill_lock:
            pending = dict(self._popen)
        while pending:
            for rank, p in list(pending.items()):
                rc = p.poll()
                if rc is None:
                    continue
                proc = job.procs[rank]
                proc.exit_code = rc
                if proc.state == ProcState.KILLED_BY_CMD:
                    pass  # we killed it during abort
                elif rc == 0:
                    proc.state = ProcState.TERMINATED
                    # late gossip suspicions about a clean finisher
                    # (its beats stopped with its transports) must not
                    # read as failures — tell the report_failed gate
                    if self.server is not None:
                        self.server.proc_finished(rank)
                else:
                    proc.state = ProcState.ABORTED
                    # wake fence/get waiters so surviving ranks don't hang
                    # on a dead peer (matters under errmgr/continue)
                    if self.server is not None:
                        self.server.proc_died(rank)
                    self._errmgr.proc_failed(self, job, proc)
                del pending[rank]
            # adopt ranks the errmgr revived (≈ rmaps/resilient re-map +
            # relaunch: same rank, fresh pid, reap continues seamlessly)
            with self._kill_lock:
                while self._respawned:
                    r = self._respawned.pop()
                    pending[r] = self._popen[r]
            if pending:
                time.sleep(0.01)
        for t in self._iof_threads:
            t.join(timeout=2.0)
        if self.server is not None:
            self.server.close()
        return (JobState.ABORTED if job.aborted_proc is not None
                else JobState.TERMINATED)

    # -- IOF --------------------------------------------------------------

    def _start_iof(self, job: Job, proc: Proc, p: subprocess.Popen) -> None:
        tag = var_registry.get("launcher_tag_output")

        def reader(pipe, sink):
            prefix = f"[{job.jobid},{proc.rank}]" if tag else ""
            for raw in iter(pipe.readline, b""):
                line = raw.decode(errors="replace")
                sink.write(f"{prefix}{line}" if prefix else line)
                sink.flush()
            pipe.close()

        for pipe, sink in ((p.stdout, sys.stdout), (p.stderr, sys.stderr)):
            t = threading.Thread(target=reader, args=(pipe, sink), daemon=True)
            t.start()
            self._iof_threads.append(t)

    def _start_stdin_pump(self) -> None:
        """Forward launcher stdin to the target rank(s) (≈ iof hnp stdin).

        Each sink is a bounded-queue ``_StdinWriter`` (shared with orted),
        so one rank that never drains stdin cannot head-of-line block the
        others under ``--stdin all``.
        """
        def pump() -> None:
            # raw-fd reads, NOT sys.stdin.buffer: a daemon thread blocked
            # in BufferedReader.read1 holds the buffer lock, and CPython's
            # shutdown aborts the whole launcher (_enter_buffered_busy,
            # SIGABRT masking the job's real exit code) when it cannot
            # reacquire it — os.read involves no Python-level lock
            import os as _os

            try:
                fd = sys.stdin.fileno()
            except (AttributeError, ValueError, OSError):
                fd = None   # stdin replaced (pytest capture) — nothing here
            try:
                while fd is not None:
                    chunk = _os.read(fd, 1 << 16)
                    if not chunk:
                        break
                    for w in list(self._stdin_sinks.values()):
                        w.feed(chunk)
            except (OSError, ValueError):
                pass
            for w in list(self._stdin_sinks.values()):
                w.feed(None)  # EOF

        threading.Thread(target=pump, daemon=True).start()

    def _mark_contact(self, job: Job, rank: int) -> None:
        """PMIx server hook: the rank's current life registered — start
        its uptime clock (errmgr_min_uptime_s measures from here, so a
        slow boot can't earn the crash-loop budget back)."""
        if 0 <= rank < len(job.procs):
            job.procs[rank].launched_at = time.monotonic()

    def _reap_reported(self, rank: int, reason: str) -> None:
        """SIGKILL one reported-dead rank (it is hung, not exited — a
        SIGSTOP'd or deadlocked pid never reports on its own).  The reap
        loop then accounts the exit and the errmgr policy decides."""
        with self._kill_lock:
            p = self._popen.get(rank)
        if p is None or p.poll() is not None:
            return
        from ompi_tpu.runtime import ftevents

        _log.verbose(1, "reaping reported-dead rank %d (pid %d): %s",
                     rank, p.pid, reason or "gossip-declared")
        ftevents.record("reap", rank=rank,
                        reason=reason or "gossip-declared")
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass

    # -- abort path --------------------------------------------------------

    def _on_abort(self, job: Job, rank: int, status: int, msg: str) -> None:
        proc = job.procs[rank]
        if job.aborted_proc is None:
            job.aborted_proc = proc
            job.abort_reason = f"rank {rank} called abort: {msg}"
            job.abort_status = status
        # The aborting rank asked for job teardown; it gets killed too (its
        # requested status is preserved via job.abort_status).
        self.kill_job(job)

    def kill_job(self, job: Job, exclude: Optional[Proc] = None) -> None:
        """SIGTERM all live ranks, then SIGKILL stragglers after a grace."""
        with self._kill_lock:
            victims = []
            for rank, p in list(self._popen.items()):
                proc = job.procs[rank]
                if proc is exclude or p.poll() is not None:
                    continue
                proc.state = ProcState.KILLED_BY_CMD
                try:
                    os.killpg(p.pid, signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    continue
                victims.append(p)
        if not victims:
            return
        deadline = time.monotonic() + var_registry.get("launcher_kill_grace_s")
        for p in victims:
            remaining = deadline - time.monotonic()
            try:
                p.wait(timeout=max(0.0, remaining))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

    # -- entry -------------------------------------------------------------

    def run(self, job: Job) -> int:
        """Drive the job to completion; return the job exit code."""
        self.sm.run_to_completion(job, JobState.INIT)
        if job.aborted_proc is not None:
            from ompi_tpu.runtime.notifier import Severity, notify

            notify(Severity.ERROR, "job-abort",
                   f"job {job.jobid}: {job.abort_reason or 'unknown'}")
            output.show_help(
                "launcher", "job-aborted",
                jobid=job.jobid, reason=job.abort_reason or "unknown")
            if job.abort_status is not None:
                return job.abort_status or 1
            rc = job.aborted_proc.exit_code or 1
            # signal death: report the shell convention 128+signum, not a
            # negative value that the OS would truncate meaninglessly
            return 128 - rc if rc < 0 else rc
        return 0


def launch(argv: list[str], np: int, want_tpu: bool = False,
           env: Optional[dict[str, str]] = None,
           stdin_target: Optional[str] = None, **select_ctx) -> int:
    """One-call launch: build the job, run it, return exit code."""
    job = Job([AppContext(argv=argv, np=np, env=env or {})])
    return LocalLauncher(want_tpu=want_tpu, stdin_target=stdin_target,
                         **select_ctx).run(job)
