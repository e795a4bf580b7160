"""MoE flagship family: the routed (dropless top-k) transformer trains over
dp with every expert on every device."""

import numpy as np

import jax

from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.transformer import TransformerConfig
from ompi_tpu.parallel.mesh import make_mesh

CFG = dict(vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
           seq=32, attention="ring", compute_dtype="float32",
           moe_experts=8, moe_top_k=2, remat=False)


def _mesh(shape):
    devs = np.array(jax.devices())[:int(np.prod(list(shape.values())))]
    return make_mesh(shape, devices=devs)


def test_moe_model_trains():
    cfg = TransformerConfig(**CFG)
    mesh = _mesh({"dp": 2, "sp": 1, "tp": 1, "ep": 1})
    params = tfm.init_params(cfg)
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
    opt_state = init_opt(params)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab, size=(4, cfg.seq)).astype(np.int32)
    first = None
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, toks)
        first = float(loss) if first is None else first
    assert np.isfinite(float(loss))
    assert float(loss) < first   # memorizing one batch must reduce loss
