"""KV-cache greedy generation on a device mesh (models/decode.py).

One compiled program: prefill through the training backbone, then a
lax.scan of cached single-token steps — batch sharded over dp, heads
(and the KV cache) over tp.

Run from the repo root (no install needed), on all local devices:
    python -m examples.generate
or as one rank that owns the host's chips:
    python -m ompi_tpu.tools.tpurun -np 1 --tpu -- python examples/generate.py
"""

import numpy as np


def main() -> None:
    import jax

    from ompi_tpu.core import enable_compile_cache
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.models.decode import make_decoder
    from ompi_tpu.parallel.mesh import make_mesh, mesh_shape_for

    enable_compile_cache()
    n = len(jax.devices())
    shape = mesh_shape_for(n, ["dp", "tp"])
    mesh = make_mesh({"dp": shape["dp"], "sp": 1, "tp": shape["tp"]},
                     devices=jax.devices())
    cfg = tfm.TransformerConfig(
        vocab=512, d_model=128, n_heads=8, n_layers=2, d_ff=512,
        seq=64, attention="xla", compute_dtype="float32")
    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab,
                          size=(2 * shape["dp"], 8)).astype(np.int32)
    dec = make_decoder(cfg, mesh, max_new=12)
    out = np.asarray(dec(params, prompt))
    print(f"platform {jax.devices()[0].platform}; mesh {dict(mesh.shape)}; "
          f"prompt {prompt.shape} -> {out.shape}")
    for row in out[:2]:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
