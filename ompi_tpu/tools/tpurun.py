"""tpurun — the mpirun equivalent.

≈ orte/tools/orterun (orterun.c:131-236): parse the command line, apply
--mca directives, build the job, drive the launch state machine, forward
output, propagate the first failure's exit code.

    tpurun -np 4 python ring.py
    tpurun -np 8 --mca coll host --tpu python app.py
    tpurun -np 4 --hostfile hf --map-by bynode ./a.out args...
    tpurun -np 4 --plm sim --hosts 2 python ring.py   # multi-host (simulated)
    tpurun -np 8 --plm ssh --hostfile hf python app.py
"""

from __future__ import annotations

import argparse
import sys

from ompi_tpu.core.config import var_registry


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpurun",
        description="Launch an ompi_tpu job (mpirun equivalent).")
    p.add_argument("-np", "-n", type=int, default=1, dest="np",
                   help="number of ranks to launch")
    p.add_argument("--mca", nargs=2, action="append", default=[],
                   metavar=("PARAM", "VALUE"),
                   help="set a config variable (repeatable)")
    p.add_argument("--tpu", action="store_true",
                   help="ranks drive TPU chips: one rank per host, which owns "
                        "all of that host's chips")
    p.add_argument("--hostfile", default=None, help="hostfile path")
    p.add_argument("--map-by", default=None, choices=["byslot", "bynode"],
                   help="round-robin mapping policy")
    p.add_argument("--plm", default=None, choices=["sim", "ssh"],
                   help="multi-host launch via a daemon tree: 'sim' runs "
                        "one daemon per simulated host on this machine, "
                        "'ssh' spawns daemons over ssh (≈ plm/rsh)")
    p.add_argument("--hosts", type=int, default=2,
                   help="number of simulated hosts for --plm sim")
    p.add_argument("--trace", action="store_true",
                   help="arm the per-rank flight recorder "
                        "(OMPI_TPU_TRACE=1 in every rank); each rank "
                        "flushes a Chrome-trace JSON to "
                        "$TMPDIR/ompi_tpu_trace_<jobid>_rank<r>.json at "
                        "finalize/abort — merge with tools/trace_export.py")
    p.add_argument("--timeout", type=float, default=None, metavar="SECS",
                   help="kill the job and exit nonzero after SECS "
                        "seconds (mpirun --timeout; CI hang guard)")
    p.add_argument("--stdin", default=None, metavar="RANK|all|none",
                   help="forward launcher stdin to this rank (default 0)")
    # persistent DVM (≈ orte-dvm / orte-submit / orte-ps)
    p.add_argument("--dvm-start", action="store_true",
                   help="bring up a persistent daemon VM and serve job "
                        "submissions (≈ orte-dvm)")
    p.add_argument("--dvm-submit", action="store_true",
                   help="run the command on a standing DVM (fast: skips "
                        "VM bring-up; ≈ orte-submit)")
    p.add_argument("--dvm-ps", action="store_true",
                   help="print a standing DVM's daemon/queue/job/proc "
                        "table (≈ orte-ps)")
    p.add_argument("--dvm-shrink", default=None, metavar="JOBID:RANK",
                   help="planned elastic shrink: retire one rank of a "
                        "running DVM job (no revive; the survivors "
                        "continue smaller per the ULFM recipe)")
    p.add_argument("--dvm-stop", action="store_true",
                   help="shut a standing DVM down")
    p.add_argument("--dvm-uri", default=None, metavar="FILE|HOST:PORT",
                   help="DVM control URI or the file holding it "
                        "(default: the per-user uri file in TMPDIR)")
    p.add_argument("--slots", type=int, default=None,
                   help="total rank slots the DVM allocates at start "
                        "(--dvm-start; default: np or hosts*ceil)")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="with --dvm-start: serve a long-lived HTTP "
                        "observability endpoint on 127.0.0.1:PORT — "
                        "/metrics (Prometheus text, per-job labels) and "
                        "/status (proc table + FT event timeline).  "
                        "Arms the per-rank metrics uplink "
                        "(trace_metrics_push_period, default 1.0 s when "
                        "this flag is given).  PORT 0 binds an "
                        "ephemeral port, recorded in <uri>.metrics")
    p.add_argument("--clean", action="store_true",
                   help="remove stale job debris (shm inboxes/segments "
                        "of dead ranks, dead DVM uri) — ≈ orte-clean; "
                        "liveness-checked unless --clean-age is given")
    p.add_argument("--clean-age", type=float, default=0.0, metavar="SECS",
                   help="with --clean: also remove ANY artifact older "
                        "than SECS (use when none of your jobs run)")
    p.add_argument("--clean-dry-run", action="store_true",
                   help="with --clean: report, remove nothing")
    p.add_argument("--tag-output", dest="tag", action="store_true",
                   default=None, help="tag output lines with [jobid,rank]")
    p.add_argument("--no-tag-output", dest="tag", action="store_false")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="program and arguments to launch")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.clean:
        from ompi_tpu.runtime import clean as clean_mod

        try:
            removed = clean_mod.clean(
                age=args.clean_age, dry_run=args.clean_dry_run,
                report=lambda s: print(f"tpurun: {s}", file=sys.stderr))
        except OSError as e:
            print(f"tpurun: {e}", file=sys.stderr)
            return 1
        verb = "would remove" if args.clean_dry_run else "removed"
        print(f"tpurun: {verb} {len(removed)} stale artifact(s)",
              file=sys.stderr)
        return 0
    if args.dvm_ps:
        import json as _json

        from ompi_tpu.runtime import dvm

        try:
            print(_json.dumps(dvm.ps(args.dvm_uri), indent=1))
        except RuntimeError as e:
            print(f"tpurun: {e}", file=sys.stderr)
            return 1
        return 0
    if args.dvm_shrink:
        import json as _json

        from ompi_tpu.runtime import dvm

        try:
            jobid, _, rank = args.dvm_shrink.partition(":")
            reply = dvm.shrink(int(jobid), int(rank), uri=args.dvm_uri)
        except ValueError:
            print(f"tpurun: --dvm-shrink wants JOBID:RANK "
                  f"(got {args.dvm_shrink!r})", file=sys.stderr)
            return 2
        except RuntimeError as e:
            print(f"tpurun: {e}", file=sys.stderr)
            return 1
        print(_json.dumps(reply))
        return 0
    if args.dvm_stop:
        from ompi_tpu.runtime import dvm

        try:
            dvm.stop(args.dvm_uri)
        except RuntimeError as e:
            print(f"tpurun: {e}", file=sys.stderr)
            return 1
        print("dvm: stopped", file=sys.stderr)
        return 0
    if not args.command and not args.dvm_start:
        print("tpurun: no command given (try: tpurun -np 4 python app.py)",
              file=sys.stderr)
        return 2
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]

    if args.timeout is not None:
        if args.timeout <= 0:
            print("tpurun: --timeout must be > 0 seconds "
                  f"(got {args.timeout:g})", file=sys.stderr)
            return 2
        import os as _os
        import signal as _signal
        import threading as _threading
        import time as _time

        # become our own process-group leader so the expiry kill hits
        # exactly the launcher + its ranks, not the invoking shell/CI
        # harness (trade-off: terminal ^C no longer fans out to the job
        # group — acceptable for the CI hang-guard this flag exists for)
        try:
            _os.setpgrp()
        except OSError:
            pass

        # The expiry killpg below hits our own process too; without a
        # handler the launcher dies of that SIGTERM (status 143) before
        # reaching _exit(124).  The handler shields exactly the expiry
        # window — an external SIGTERM before expiry still terminates.
        _expiring = _threading.Event()

        def _on_term(signum, frame) -> None:
            if _expiring.is_set():
                return              # our own group-kill; _exit(124) follows
            _signal.signal(_signal.SIGTERM, _signal.SIG_DFL)
            _os.kill(_os.getpid(), _signal.SIGTERM)

        _signal.signal(_signal.SIGTERM, _on_term)   # main thread only

        def _expire() -> None:
            _time.sleep(args.timeout)
            _expiring.set()
            print(f"tpurun: job timed out after {args.timeout:g}s — "
                  f"aborting (mpirun --timeout semantics)",
                  file=sys.stderr, flush=True)
            try:
                # our process group holds the launcher and local ranks;
                # daemon-tree members notice the HNP's death via their
                # lifelines and tear down
                _os.killpg(_os.getpgid(0), _signal.SIGTERM)
            except OSError:
                pass
            _time.sleep(2.0)
            _os._exit(124)

        _threading.Thread(target=_expire, daemon=True,
                          name="tpurun-timeout").start()

    # CLI --mca pairs get top precedence; framework-selection vars use the
    # bare framework name (e.g. --mca coll xla → synonym of coll_).  They are
    # also exported to the environment so app processes inherit them — most
    # frameworks (pml/coll/...) select inside the app, not the launcher.
    import os

    if args.trace:
        # local fork/exec and --dvm-submit inherit the launcher's
        # os.environ; the ssh daemon tree does NOT (env doesn't travel
        # over ssh), so the flag ALSO rides the job's app env below
        os.environ["OMPI_TPU_TRACE"] = "1"
    trace_env = {"OMPI_TPU_TRACE": "1"} if args.trace else {}
    var_registry.load_cli([(k, v) for k, v in args.mca])
    for k, v in args.mca:
        os.environ[var_registry.ENV_PREFIX + k] = v
    if args.map_by:
        var_registry.load_cli([("rmaps_rr_policy", args.map_by)])
    if args.tag is not None:
        var_registry.load_cli([("launcher_tag_output", "1" if args.tag else "0")])
    if args.hostfile:
        var_registry.load_cli([("ras_hostfile", args.hostfile)])

    def _configure_sim_ras(total_slots: int) -> None:
        """Shared sim-RAS setup for --plm sim and --dvm-start."""
        import math

        var_registry.load_cli([
            ("ras", "simulator"),
            ("ras_sim_num_nodes", str(args.hosts)),
            ("ras_sim_slots_per_node",
             str(math.ceil(total_slots / max(1, args.hosts)))),
        ])

    if args.dvm_submit:
        from ompi_tpu.runtime import dvm
        from ompi_tpu.runtime import pmix as _pmix

        # ship the CLIENT's environment as the job env (orte-submit /
        # mpirun semantics: app processes see the submitter's variables,
        # overlaid on the daemon's own env) — minus the per-rank/per-job
        # identity vars the launcher owns and the HOST-LOCAL vars whose
        # client values would break ranks on remote (ssh) daemons.  The
        # --mca pairs were exported into os.environ above, so they ride
        # along.
        _skip = {_pmix.ENV_URI, _pmix.ENV_RANK, _pmix.ENV_SIZE,
                 _pmix.ENV_JOBID, _pmix.ENV_LOCAL_RANK,
                 "OMPI_TPU_RESTART", "OMPI_TPU_FAKE_HOST",
                 "PATH", "HOME", "TMPDIR", "TMP", "TEMP", "PWD",
                 "OLDPWD", "SHLVL", "HOSTNAME", "LD_LIBRARY_PATH",
                 "LD_PRELOAD", "VIRTUAL_ENV", "PYTHONHOME"}
        job_env = {k: v for k, v in os.environ.items() if k not in _skip}
        if args.tag is not None:
            job_env[var_registry.ENV_PREFIX + "launcher_tag_output"] = \
                "1" if args.tag else "0"
        try:
            return dvm.submit(cmd, np_=args.np, uri=args.dvm_uri,
                              env=job_env)
        except dvm.DvmRejected as e:
            # machine-readable admission verdict on stdout + EX_TEMPFAIL
            # (75): schedulers and scripts can parse-and-retry instead of
            # hanging against a full pool
            import json as _json

            print(_json.dumps(e.verdict))
            print(f"tpurun: dvm rejected the job: {e}", file=sys.stderr)
            return 75
        except RuntimeError as e:
            print(f"tpurun: {e}", file=sys.stderr)
            return 1

    if args.dvm_start:
        from ompi_tpu.runtime import dvm

        slots = args.slots or max(args.np, args.hosts)
        plm_name = args.plm or "sim"
        if plm_name == "sim" and not args.hostfile:
            _configure_sim_ras(slots)
        if args.metrics_port is not None:
            # the scrape endpoint is only useful with the uplink armed:
            # default the push period on (daemons inherit it via their
            # spawn env, ranks via the launch env overlay) unless the
            # user pinned it with --mca / the environment
            os.environ.setdefault(
                var_registry.ENV_PREFIX + "trace_metrics_push_period",
                "1.0")
        hnp = dvm.DvmHnp(plm_name=plm_name, want_tpu=args.tpu,
                         uri_path=args.dvm_uri,
                         metrics_port=args.metrics_port,
                         remote_hosts=plm_name == "ssh")
        hnp.start(np_slots=slots)
        print(f"dvm: up ({args.hosts} hosts, {slots} slots); "
              f"uri file {hnp.uri_path}", file=sys.stderr)
        if hnp.metrics_uri:
            print(f"dvm: metrics at {hnp.metrics_uri}/metrics and "
                  f"{hnp.metrics_uri}/status", file=sys.stderr)
        try:
            return hnp.serve_forever()
        except KeyboardInterrupt:
            hnp.shutdown()
            return 0

    if args.plm:
        # multi-host path: one orted per host, routed tree, IOF up the tree
        if args.plm == "sim" and not args.hostfile:
            _configure_sim_ras(args.np)
        from ompi_tpu.runtime.job import AppContext, Job
        from ompi_tpu.runtime.plm import MultiHostLauncher

        job = Job([AppContext(argv=cmd, np=args.np, env=trace_env)])
        return MultiHostLauncher(
            plm_name=args.plm, want_tpu=args.tpu,
            stdin_target=args.stdin if args.stdin is not None else "0",
            remote_hosts=args.plm == "ssh",
        ).run(job)

    from ompi_tpu.runtime.launcher import launch

    return launch(cmd, np=args.np, want_tpu=args.tpu, env=trace_env,
                  stdin_target=args.stdin)


if __name__ == "__main__":
    sys.exit(main())
