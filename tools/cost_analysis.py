#!/usr/bin/env python
"""XLA cost analysis of the flagship train step: FLOPs and bytes
accessed as the COMPILER counts them, turned into a roofline bound.

step_time >= max(flops / peak_flops, bytes / hbm_bw) — if the measured
step (MFU_SWEEP.jsonl) sits well above both bounds, the gap is
scheduling/fusion, not physics; if the bytes bound dominates, the model
is HBM-bound and the remat/fusion knobs are the lever.

Usage:  python tools/cost_analysis.py          (from the repo root, on a TPU)
Appends a JSON line to MFU_SWEEP.jsonl (label "cost-analysis").
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "MFU_SWEEP.jsonl")


def main() -> None:
    t0 = time.time()
    import numpy as np

    from bench import device_peaks, require_tpu
    from ompi_tpu.core import enable_compile_cache
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel.mesh import make_mesh

    devices = require_tpu()
    enable_compile_cache()
    kind = devices[0].device_kind
    peaks = device_peaks(kind)
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=devices[:1])
    cfg, batch = tfm.FLAGSHIP, tfm.FLAGSHIP_BATCH
    params = tfm.init_params(cfg)
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-3)
    opt_state = init_opt(params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab,
                        size=(batch, cfg.seq)).astype(np.int32)

    lowered = step.lower(params, opt_state, toks)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):      # one entry per device program
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    peak, bw = peaks["bf16_flops"], peaks["hbm_bytes_per_s"]
    rec = {
        "label": "cost-analysis",
        "backend": kind, "batch": batch, "seq": cfg.seq,
        "xla_flops": flops, "xla_bytes_accessed": bytes_acc,
        "flops_bound_ms": round(flops / peak * 1e3, 2),
        "bytes_bound_ms": round(bytes_acc / bw * 1e3, 2),
        "arith_intensity": round(flops / bytes_acc, 1) if bytes_acc
        else None,
        "wall_s": round(time.time() - t0, 1),
        "ts": time.strftime("%Y-%m-%dT%H:%MZ", time.gmtime()),
    }
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
