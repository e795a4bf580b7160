#!/usr/bin/env python
"""Flagship step-time breakdown on the TPU: forward-only vs
forward+backward vs full optimizer step, each as an in-jit chain (per-step
cost via chained steps, so the per-dispatch cost amortizes out).  One child
process per phase; this parent never creates a JAX backend, because a chip
belongs to one process at a time.

Tells us where the non-MXU time goes: if fwd-only MFU is far above the
train-step MFU, the backward (remat recompute, attention transpose) is
the target; if fwd-only is already low, the forward itself (softmax,
layout, HBM) is.

Appends one JSON line per phase to MFU_SWEEP.jsonl with label
"breakdown-<phase>".
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "MFU_SWEEP.jsonl")

CHILD = r"""
import json, sys, time, functools
import numpy as np
phase = sys.argv[1]
t0 = time.time()
import jax
from jax import lax
sys.path.insert(0, {repo!r})
from bench import (_count_params, device_peaks, flagship_flops_per_token,
                   require_tpu)
from ompi_tpu.core import enable_compile_cache
from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel.mesh import make_mesh

devices = require_tpu()
enable_compile_cache()
kind = devices[0].device_kind
mesh = make_mesh({{"dp": 1, "sp": 1, "tp": 1}}, devices=devices[:1])
cfg = tfm.FLAGSHIP
batch, chain = tfm.FLAGSHIP_BATCH, 32
rng = np.random.default_rng(0)
tokens = jax.device_put(rng.integers(
    0, cfg.vocab, size=(batch, cfg.seq)).astype(np.int32), devices[0])
params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg))
n_params = _count_params(params)
loss_fn = tfm.make_loss_fn(cfg, mesh)

import jax.numpy as jnp


def _perturb(p, carry):
    # Thread the loop carry into the params (one leaf + carry*1e-20):
    # numerically invisible, but a REAL data dependency between scan
    # iterations -- without it XLA hoists the loss computation out of
    # the scan (p and toks are loop-invariant) and the chain times
    # nothing.  (# comments, not a docstring: this code lives inside
    # the CHILD triple-quoted literal.)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    bump = (carry * 1e-20).astype(leaves[0].dtype)
    return jax.tree_util.tree_unflatten(
        treedef, [leaves[0] + bump] + leaves[1:])


if phase == "fwd":
    @jax.jit
    def run(p, toks):
        def body(carry, _):
            loss = loss_fn(_perturb(p, carry), toks)
            return loss, loss
        _, losses = lax.scan(body, jnp.float32(0), None, length=chain)
        return losses
    w = run(params, tokens); _ = float(w[-1])
    t1 = time.perf_counter(); w = run(params, tokens); loss = float(w[-1])
    dt = (time.perf_counter() - t1) / chain
    flop_scale = 1.0 / 3.0        # fwd ≈ 1/3 of the 6N fwd+bwd accounting
elif phase == "grad":
    g_fn = jax.value_and_grad(loss_fn)

    @jax.jit
    def run(p, toks):
        def body(carry, _):
            loss, grads = g_fn(_perturb(p, carry), toks)
            lk = jax.tree_util.tree_leaves(grads)[0]
            return loss + lk[(0,) * lk.ndim].astype(jnp.float32) * 0, loss
        _, losses = lax.scan(body, jnp.float32(0), None, length=chain)
        return losses
    w = run(params, tokens); _ = float(w[-1])
    t1 = time.perf_counter(); w = run(params, tokens); loss = float(w[-1])
    dt = (time.perf_counter() - t1) / chain
    flop_scale = 1.0
else:  # full
    loop, init_opt = tfm.make_train_loop(cfg, mesh, lr=1e-3, steps=chain)
    opt_state = init_opt(params)
    params, opt_state, losses = loop(params, opt_state, tokens)
    _ = float(losses[-1])
    t1 = time.perf_counter()
    params, opt_state, losses = loop(params, opt_state, tokens)
    loss = float(losses[-1])
    dt = (time.perf_counter() - t1) / chain
    flop_scale = 1.0

n_tokens = tokens.size
fpt = flagship_flops_per_token(cfg, n_params) * flop_scale
mfu = fpt * n_tokens / dt / device_peaks(kind)["bf16_flops"]
print("RESULT " + json.dumps({{
    "phase": phase, "backend": kind, "mfu_pct": round(mfu * 100, 2),
    "step_ms": round(dt * 1e3, 2), "loss": round(float(loss), 4),
    "params": n_params, "wall_s": round(time.time() - t0, 1),
}}))
""".format(repo=REPO)


def main() -> None:
    for phase in (sys.argv[1:] or ["fwd", "grad", "full"]):
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable, "-c", CHILD, phase], capture_output=True,
                text=True, timeout=1500, cwd=REPO)
            rec = None
            for line in proc.stdout.splitlines():
                if line.startswith("RESULT "):
                    rec = json.loads(line[len("RESULT "):])
            if rec is None:
                rec = {"error": "no result", "rc": proc.returncode,
                       "stderr_tail": proc.stderr[-700:]}
        except subprocess.TimeoutExpired:
            rec = {"error": "timeout", "wall_s": round(time.time() - t0, 1)}
        rec["label"] = f"breakdown-{phase}"
        rec["ts"] = time.strftime("%Y-%m-%dT%H:%MZ", time.gmtime())
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"[breakdown] {phase}: {json.dumps(rec)}", flush=True)


if __name__ == "__main__":
    main()
