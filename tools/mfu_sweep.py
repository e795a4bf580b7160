#!/usr/bin/env python
"""Flagship MFU config sweep on the TPU.

Runs each (batch, ce_chunk, remat, attention) config in a fresh subprocess
with its own wall-clock budget, so a config that runs out of memory costs
one row, not the sweep.  This parent never creates a JAX backend: a chip
belongs to one process at a time and each child needs it in turn.
Appends one JSON line per config to MFU_SWEEP.jsonl.

Usage:  python tools/mfu_sweep.py            # full grid
        python tools/mfu_sweep.py --quick    # the two head-to-head configs
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "MFU_SWEEP.jsonl")

CHILD = r"""
import dataclasses, json, sys, time
import numpy as np
cfg = json.loads(sys.argv[1])
t0 = time.time()
sys.path.insert(0, {repo!r})
from bench import (_time_train_loop, device_peaks,
                   flagship_flops_per_token, require_tpu)
from ompi_tpu.core import enable_compile_cache
from ompi_tpu.models.transformer import FLAGSHIP
from ompi_tpu.parallel.mesh import make_mesh

devices = require_tpu()
enable_compile_cache()
kind = devices[0].device_kind
mesh = make_mesh({{"dp": 1, "sp": 1, "tp": 1}}, devices=devices[:1])
batch = cfg.pop("batch")
chain = cfg.pop("chain", 8)
outer = cfg.pop("outer", 2)
model = dataclasses.replace(FLAGSHIP, **cfg)
rng = np.random.default_rng(0)
tokens = rng.integers(0, model.vocab, size=(batch, model.seq)).astype(np.int32)
t_dev = time.time()
dt, n_params, loss = _time_train_loop(model, mesh, tokens, chain, outer)
n_tokens = tokens.size
fpt = flagship_flops_per_token(model, n_params)
mfu = fpt * n_tokens / dt / device_peaks(kind)["bf16_flops"]
print("RESULT " + json.dumps({{
    "batch": batch, **{{k: v for k, v in cfg.items()}},
    "backend": kind, "mfu_pct": round(mfu * 100, 2),
    "step_ms": round(dt * 1e3, 2), "tokens_per_s": round(n_tokens / dt, 1),
    "loss": round(float(loss), 4), "params": n_params,
    "import_s": round(t_dev - t0, 1), "wall_s": round(time.time() - t0, 1),
}}))
""".format(repo=REPO)

MATMUL_PEAK = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
sys.path.insert(0, {repo!r})
from bench import device_peaks, require_tpu
require_tpu()
# Per-dispatch host cost: time a trivial program end to end.  The matmul
# loop below runs INSIDE one compiled program (fori_loop) and the
# train-loop rows chain steps in-jit (bench.py's chain) so that this cost
# is paid once per measurement, not once per iteration.
tiny = jax.jit(lambda a: a + 1.0)
z = jax.device_put(np.zeros((8, 128), np.float32))
z = tiny(z); jax.block_until_ready(z)
t0 = time.perf_counter(); z = tiny(z); _ = float(z[0, 0])
dispatch_ms = (time.perf_counter() - t0) * 1e3
# two-point method: time the SAME program shape at two in-jit iteration
# counts; the slope cancels the per-dispatch cost
n, lo, hi = 8192, 8, 72
x = jax.device_put(np.random.default_rng(0).standard_normal(
    (n, n)).astype(jnp.bfloat16))


def make(iters):
    f = jax.jit(lambda a: lax.fori_loop(
        0, iters, lambda i, y: (y @ y) * jnp.bfloat16(1e-4), a))
    y = f(x)
    jax.block_until_ready(y)  # compile + warm

    def timed():
        t0 = time.perf_counter()
        out = f(x)
        _ = float(jnp.float32(out[0, 0]))
        return time.perf_counter() - t0

    return min(timed() for _ in range(3))


t_lo, t_hi = make(lo), make(hi)
dt = (t_hi - t_lo) / (hi - lo)
tf = 2 * n ** 3 / dt / 1e12
peak = device_peaks(jax.devices()[0].device_kind)["bf16_flops"]
print("RESULT " + json.dumps({{
    "n": n, "iters": [lo, hi], "ms": round(dt * 1e3, 3),
    "dispatch_rt_ms": round(dispatch_ms, 1),
    "wall_lo_s": round(t_lo, 3), "wall_hi_s": round(t_hi, 3),
    "tflops": round(tf, 1),
    "pct_of_peak": round(tf / peak * 100, 1),
    "peak_tflops": round(peak / 1e12),
    "backend": jax.devices()[0].device_kind}}))
""".format(repo=REPO)

GRID = [
    # (label, config, per-config budget seconds).  "matmul_peak" is the
    # calibration row: fraction of the 197TF bf16 peak a plain 8k matmul
    # hits — the realistic ceiling for every MFU row.
    ("matmul_peak", None, 600),
    ("b16-chunk128-dots", {"batch": 16, "ce_chunk": 128, "remat": "dots",
                           "attention": "flash"}, 1500),
    ("b16-chunk128-noremat", {"batch": 16, "ce_chunk": 128, "remat": None,
                              "attention": "flash"}, 1500),
    # plain XLA dot-product attention instead of the pallas flash kernel
    # (attention is ~7% of model FLOPs; a slow custom kernel could still
    # dominate wall time)
    ("b16-chunk128-xla", {"batch": 16, "ce_chunk": 128,
                          "remat": "dots", "attention": "xla"}, 1500),
    ("b16-noremat-xla", {"batch": 16, "ce_chunk": 128, "remat": None,
                         "attention": "xla"}, 1500),
    # CE chunk size: fewer scan trips, bigger unembed matmuls
    ("b16-chunk256-dots", {"batch": 16, "ce_chunk": 256, "remat": "dots",
                           "attention": "flash"}, 1500),
    ("b16-chunk512-dots", {"batch": 16, "ce_chunk": 512, "remat": "dots",
                           "attention": "flash"}, 1500),
    ("b32-chunk128-dots", {"batch": 32, "ce_chunk": 128, "remat": "dots",
                           "attention": "flash", "chain": 4, "outer": 1},
     1800),
    ("b32-chunk128-noremat", {"batch": 32, "ce_chunk": 128, "remat": None,
                              "attention": "flash", "chain": 4, "outer": 1},
     1800),
    ("b16-full-dots", {"batch": 16, "ce_chunk": 0, "remat": "dots",
                       "attention": "flash"}, 1500),
    # long chain amortizes the per-dispatch cost (the matmul_peak row
    # measures it) — the steady-state number
    ("b16-chunk128-dots-chain32", {"batch": 16, "ce_chunk": 128,
                                   "remat": "dots", "attention": "flash",
                                   "chain": 32, "outer": 1}, 1800),
    # combos on the measured winner (xla local attention beat the pallas
    # kernel 909 vs 1014 ms/step at b16)
    ("b16-xla-ce512", {"batch": 16, "ce_chunk": 512, "remat": "dots",
                       "attention": "xla"}, 1500),
    ("b16-xla-chain32", {"batch": 16, "ce_chunk": 128, "remat": "dots",
                         "attention": "xla", "chain": 32, "outer": 1},
     1800),
    ("b32-xla", {"batch": 32, "ce_chunk": 128, "remat": "dots",
                 "attention": "xla", "chain": 4, "outer": 1}, 1800),
    ("b16-flash-ce256-chain32", {"batch": 16, "ce_chunk": 256,
                                 "remat": "dots", "attention": "flash",
                                 "chain": 32, "outer": 1}, 1800),
    ("b16-xla-ce256-chain32", {"batch": 16, "ce_chunk": 256,
                               "remat": "dots", "attention": "xla",
                               "chain": 32, "outer": 1}, 1800),
    # ---- round-4 continuation: push past 34.6% toward the 40% bar ----
    # bigger batch between the 16 winner and the 32 OOM
    ("b24-xla-ce256-chain24", {"batch": 24, "ce_chunk": 256,
                               "remat": "dots", "attention": "xla",
                               "chain": 24, "outer": 1}, 1800),
    # b32 fits if every layer activation is rematerialized (full remat
    # costs ~33% more FLOPs on paper but bigger matmuls may win it back)
    ("b32-xla-full-chain16", {"batch": 32, "ce_chunk": 256,
                              "remat": "full", "attention": "xla",
                              "chain": 16, "outer": 1}, 1800),
    ("b32-flash-full-chain16", {"batch": 32, "ce_chunk": 256,
                                "remat": "full", "attention": "flash",
                                "chain": 16, "outer": 1}, 1800),
    # longer chain: halves whatever per-dispatch cost 32 steps leave
    ("b16-xla-ce256-chain64", {"batch": 16, "ce_chunk": 256,
                               "remat": "dots", "attention": "xla",
                               "chain": 64, "outer": 1}, 2400),
    # bf16 first moment frees ~0.9 GiB — the cheap path to batch 32
    # with the fast "dots" remat (full remat pays ~33% extra FLOPs)
    ("b32-xla-mubf16-chain16", {"batch": 32, "ce_chunk": 256,
                                "remat": "dots", "attention": "xla",
                                "adam_mu_dtype": "bfloat16",
                                "chain": 16, "outer": 1}, 1800),
    ("b24-xla-mubf16-chain24", {"batch": 24, "ce_chunk": 256,
                                "remat": "dots", "attention": "xla",
                                "adam_mu_dtype": "bfloat16",
                                "chain": 24, "outer": 1}, 1800),
    # bf16 param storage + f32 master (param_dtype): HBM-neutral on one
    # chip (the master cancels the savings) — this row measures the
    # halved param-read bandwidth per step, not a memory win
    ("b16-xla-pbf16-chain32", {"batch": 16, "ce_chunk": 256,
                               "remat": "dots", "attention": "xla",
                               "param_dtype": "bfloat16",
                               "adam_mu_dtype": "bfloat16",
                               "chain": 32, "outer": 1}, 1800),
    # effective batch 32 via 2 in-jit microbatches: b16's activation
    # peak, one optimizer pass per 32-sample step
    ("b32-accum2-xla-chain16", {"batch": 32, "grad_accum": 2,
                                "ce_chunk": 256, "remat": "dots",
                                "attention": "xla",
                                "chain": 16, "outer": 1}, 1800),
    # longer sequence at constant tokens/step: attention FLOPs per token
    # double (12·L·D·S) while weight-read overhead stays flat, so MFU
    # usually rises IF the attention backward fits; flash may retake the
    # lead from XLA attention at 2048 (it lost at 1024)
    ("b8-s2048-xla-chain16", {"batch": 8, "seq": 2048, "ce_chunk": 256,
                              "remat": "dots", "attention": "xla",
                              "chain": 16, "outer": 1}, 1800),
    ("b8-s2048-flash-chain16", {"batch": 8, "seq": 2048, "ce_chunk": 256,
                                "remat": "dots", "attention": "flash",
                                "chain": 16, "outer": 1}, 1800),
    ("b4-s4096-flash-chain16", {"batch": 4, "seq": 4096, "ce_chunk": 256,
                                "remat": "dots", "attention": "flash",
                                "chain": 16, "outer": 1}, 1800),
]

_QUICK_LABELS = ["matmul_peak", "b16-chunk128-dots", "b32-chunk128-dots"]
QUICK = [row for row in GRID if row[0] in _QUICK_LABELS]


def run_one(label: str, cfg: dict | None, budget: float) -> dict:
    t0 = time.time()
    try:
        if cfg is None:  # calibration row
            argv = [sys.executable, "-c", MATMUL_PEAK]
        else:
            argv = [sys.executable, "-c", CHILD, json.dumps(cfg)]
        proc = subprocess.run(
            argv, capture_output=True, text=True, timeout=budget, cwd=REPO)
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT "):
                rec = json.loads(line[len("RESULT "):])
                rec["label"] = label
                return rec
        return {"label": label, "error": "no result",
                "rc": proc.returncode,
                "stderr_tail": proc.stderr[-800:],
                "wall_s": round(time.time() - t0, 1)}
    except subprocess.TimeoutExpired:
        return {"label": label, "error": f"timeout after {budget}s",
                "wall_s": round(time.time() - t0, 1)}


def main() -> None:
    names = [a for a in sys.argv[1:] if not a.startswith("-")]
    if "--quick" in sys.argv:
        grid = QUICK
    elif names:
        by = {label: (label, cfg, budget) for label, cfg, budget in GRID}
        unknown = [n for n in names if n not in by]
        if unknown:
            sys.exit(f"unknown row(s) {unknown}; known: {sorted(by)}")
        grid = [by[n] for n in names]
    else:
        grid = GRID
    for label, cfg, budget in grid:
        print(f"[sweep] {label} (budget {budget}s) ...", flush=True)
        rec = run_one(label, dict(cfg) if cfg else None, budget)
        rec["ts"] = time.strftime("%Y-%m-%dT%H:%MZ", time.gmtime())
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"[sweep] {label}: {json.dumps(rec)}", flush=True)


if __name__ == "__main__":
    main()
