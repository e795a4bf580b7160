"""Capture an XLA profiler trace of flagship train steps + summarize it.

Closes SURVEY §5's tracing row (the reference pairs per-peer pvar
counters — ompi/mca/common/monitoring/common_monitoring.h:20 — with
external tracers; the TPU-native equivalent is the XLA profiler): wrap
train steps in ``jax.profiler.trace``, keep the TensorBoard-loadable
artifact, and print ONE JSON line summarizing where the step time went —
fraction in MXU-class ops (dot/conv), copies/layout, collectives, and
everything else — which is exactly the breakdown the MFU hunt needs.

Usage:
    python tools/xprof_capture.py                 # live backend, flagship
    python tools/xprof_capture.py --cpu 1 --small # CPU smoke (tests use)

Artifacts: <out>/plugins/profile/<ts>/*.xplane.pb (open in
tensorboard/xprof) and the JSON summary on stdout (also written next to
the trace as summary.json).
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Event-name → category. Checked against TPU and CPU xplane naming: TPU op
# events carry HLO op names (fusion.N with the root op leading, dot.N,
# all-reduce.N, copy.N, dynamic-slice...); CPU client lines carry the same
# HLO names plus region markers we skip.
_COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective", "send", "recv",
               "psum", "ppermute")
_MXU = ("dot", "convolution", "einsum", "matmul")
_COPY = ("copy", "transpose", "memset", "bitcast", "reshape", "slice",
         "concatenate", "pad", "broadcast", "gather", "scatter",
         "dynamic-update", "convert")
_SKIP_PREFIX = ("end:", "threadpoollistener", "$", "pjitfunction",
                "xla modules", "steps", "thunkexecutor",
                # control-flow envelopes re-time the ops they contain
                "while", "conditional", "call")


def categorize(name: str) -> str:
    n = name.lower()
    for k in _COLLECTIVE:
        if k in n:
            return "collective"
    for k in _MXU:
        if k in n:
            return "mxu"
    for k in _COPY:
        if k in n:
            return "copy"
    return "other"


def summarize_xplane(pb_path: str) -> dict:
    """Aggregate per-op durations from one .xplane.pb into category
    fractions.  Prefers device planes (/device:TPU:N); falls back to the
    host XLA-client lines (the CPU-backend layout)."""
    import jax.profiler

    pd = jax.profiler.ProfileData.from_file(pb_path)
    per_cat: dict[str, float] = {}
    per_op: dict[str, float] = {}
    n_events = 0

    def eat(line) -> None:
        nonlocal n_events
        # the event list is FLAT: ops executed inside a while/call appear
        # as their own events between the envelope's start and its
        # "end:" marker — skipping the envelope names (in _SKIP_PREFIX)
        # avoids double-counting without losing the inner ops
        for ev in line.events:
            name = ev.name or ""
            low = name.lower()
            if any(low.startswith(p) for p in _SKIP_PREFIX):
                continue
            dur = float(ev.duration_ns or 0.0)
            if dur <= 0:
                continue
            n_events += 1
            cat = categorize(name)
            per_cat[cat] = per_cat.get(cat, 0.0) + dur
            key = name.split(".")[0]
            per_op[key] = per_op.get(key, 0.0) + dur

    device_planes = [p for p in pd.planes
                     if p.name.lower().startswith("/device:")]
    if device_planes:
        for plane in device_planes:
            for line in plane.lines:
                ln = line.name.lower()
                if "module" in ln or ln == "steps":
                    continue  # module envelopes double-count their ops
                eat(line)
    else:
        for plane in pd.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                if "client" not in line.name.lower():
                    continue  # python-frame lines, not XLA ops
                eat(line)

    total = sum(per_cat.values()) or 1.0
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:8]
    return {
        "events": n_events,
        "total_op_ms": round(total / 1e6, 3),
        "fractions": {k: round(v / total, 4)
                      for k, v in sorted(per_cat.items(),
                                         key=lambda kv: -kv[1])},
        "top_ops_ms": {k: round(v / 1e6, 3) for k, v in top},
    }


def capture(out_dir: str, steps: int, small: bool) -> dict:
    import jax

    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel.mesh import make_mesh

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    cfg, batch = tfm.FLAGSHIP, tfm.FLAGSHIP_BATCH
    if small:    # the trace-reduction test's size, not a measurement
        cfg = dataclasses.replace(cfg, vocab=512, d_model=128, n_heads=8,
                                  n_layers=2, d_ff=256, seq=64, ce_chunk=0)
        batch = 2
    params = tfm.init_params(cfg)
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-3)
    opt_state = init_opt(params)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(batch, cfg.seq)).astype(np.int32)

    # warm outside the trace so compile time doesn't pollute it
    params, opt_state, loss = step(params, opt_state, tokens)
    jax.block_until_ready(loss)

    os.makedirs(out_dir, exist_ok=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(out_dir):
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready(loss)
    wall = time.perf_counter() - t0

    pbs = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not pbs:
        raise RuntimeError(f"no .xplane.pb produced under {out_dir}")
    summary = summarize_xplane(pbs[-1])
    summary.update(
        backend=kind, steps=steps,
        traced_wall_ms=round(wall * 1e3, 1),
        params=int(sum(np.prod(np.shape(p))
                       for p in jax.tree_util.tree_leaves(params))),
        trace=pbs[-1])
    with open(os.path.join(os.path.dirname(pbs[-1]), "summary.json"),
              "w") as f:
        json.dump(summary, f, indent=1)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(REPO, "xprof_trace"))
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--small", action="store_true",
                    help="tiny model (CPU smoke / tests)")
    ap.add_argument("--cpu", type=int, metavar="N", default=0,
                    help="force an N-device virtual CPU platform")
    args = ap.parse_args(argv)

    from ompi_tpu.core import enable_compile_cache

    enable_compile_cache()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    summary = capture(args.out, args.steps, args.small)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
