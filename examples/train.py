"""End-to-end training on a device mesh: the full stack in one file.

data pipeline (deterministic windows, dp-sharded double-buffered
prefetch) → 3D-parallel transformer (dp × sp × tp shard_map) → in-jit
chained train steps → snapshot checkpoint → resume reproducing the
exact batch stream from the saved step.

Run from the repo root (no install needed):
    python -m examples.train [--steps 6] [--ckpt-dir /tmp/train_ckpt]
"""

import argparse
import tempfile

import numpy as np


def _rounded(row: dict) -> dict:
    return {k: round(v, 3) if isinstance(v, float) else v
            for k, v in row.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    import jax

    from ompi_tpu.ckpt.store import SnapshotStore
    from ompi_tpu.core import enable_compile_cache
    from ompi_tpu.models import data as data_mod
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel.mesh import make_mesh, mesh_shape_for

    enable_compile_cache()
    n = len(jax.devices())
    shape = mesh_shape_for(n, ["dp", "tp"])
    mesh = make_mesh({"dp": shape["dp"], "sp": 1, "tp": shape["tp"]},
                     devices=jax.devices())
    cfg = tfm.TransformerConfig(
        vocab=512, d_model=128, n_heads=8, n_layers=2, d_ff=512,
        seq=64, attention="xla", compute_dtype="float32",
        adam_mu_dtype="bfloat16")
    batch = 4 * shape["dp"]

    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg))
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=3e-3)
    opt_state = init_opt(params)

    corpus = (np.arange(32_768) * 2654435761 % cfg.vocab).astype(np.int32)
    src = data_mod.ArraySource(corpus, seed=0)
    stream = data_mod.train_stream(src, mesh, batch, cfg.seq)

    store = SnapshotStore(args.ckpt_dir or tempfile.mkdtemp(), job="demo")
    half = args.steps // 2
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, next(stream))
        print(f"step {i}: loss {float(loss):.4f}")
        if i + 1 == half:
            store.write_rank(0, 0, {"w1": params["w1"],
                                    "step": np.int64(i + 1)})
            store.commit(0, nranks=1)
            print(f"checkpoint at step {i + 1} -> {store.snapshot_dir(0)}")

    # where the host's time went before the first step, from the program's
    # own record (core/scopes.py), and what the stream waited for
    from ompi_tpu.core import scopes

    started = scopes.startup()
    row = started["programs"]["train_step"]
    print(f"startup: spans {_rounded(started['spans'])}; train_step "
          f"{_rounded(row)}; retraces {started['retraces']}; stream "
          f"{_rounded(stream.stats())}")

    # resume: the (seed, step) contract reproduces the stream exactly
    resumed = data_mod.train_stream(src, mesh, batch, cfg.seq,
                                    start_step=half)
    live = data_mod.train_stream(src, mesh, batch, cfg.seq)
    for _ in range(half + 1):     # batches 0..half; keep batch[half]
        ref = next(live)
    np.testing.assert_array_equal(np.asarray(next(resumed)),
                                  np.asarray(ref))
    print("resume: batch stream reproduced from checkpointed step — ok")


if __name__ == "__main__":
    main()
