"""``transformer._qk_norm``: both forms against numpy at 4 query heads over 2
K/V heads, on one device and with the heads split over ``tp``.  The
whole-projection form divides the summed squares by the projection's own
width (the query heads' for q, the K/V heads' for k), which is not
``d_model`` where K is narrower than Q; the per-head form norms each head's
width with one scale and sums nothing over ``tp``.  CPU only: agreement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ompi_tpu.models import transformer as tfm
from ompi_tpu.mpi.device_comm import DeviceCommunicator
from ompi_tpu.parallel.mesh import make_mesh

HEADS, KV_HEADS, WIDTH, EPS = 4, 2, 8, 1e-6


def by_hand(x, scale, over: int):
    """RMSNorm of (..., n x over) over each run of ``over`` elements."""
    runs = x.reshape(*x.shape[:-1], -1, over).astype(np.float64)
    normed = runs / np.sqrt((runs * runs).mean(-1, keepdims=True) + EPS)
    return (normed * scale.reshape(-1, over)).reshape(x.shape)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("heads", [HEADS, KV_HEADS], ids=["q", "k"])
@pytest.mark.parametrize("form", [True, "head"], ids=["whole", "head"])
def test_qk_norm_equals_numpy(form, heads, tp):
    cfg = tfm.TransformerConfig(d_model=48, n_heads=HEADS, n_kv_heads=KV_HEADS,
                                head_width=WIDTH, qk_norm=form, norm_eps=EPS)
    wide = heads * WIDTH        # 32 for q, 16 for k: neither is d_model
    rng = np.random.default_rng(heads)
    x = rng.normal(size=(2, 5, wide)).astype(np.float32) * 3
    scale = rng.uniform(0.5, 1.5, size=WIDTH if form == "head" else wide
                        ).astype(np.float32)
    want = by_hand(x, scale, WIDTH if form == "head" else wide)
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": tp}, devices=jax.devices()[:tp])
    comm = DeviceCommunicator(mesh, ("dp", "sp", "tp"))
    spec = P() if form == "head" else P("tp")
    got = jax.jit(jax.shard_map(
        lambda x, scale: tfm._qk_norm(cfg, x, scale, comm), mesh=mesh,
        in_specs=(P(None, None, "tp"), spec), out_specs=P(None, None, "tp"),
        check_vma=False))(jnp.asarray(x), jnp.asarray(scale))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_the_leaves_are_as_wide_as_what_they_scale():
    whole = tfm.TransformerConfig(d_model=48, n_heads=HEADS, n_layers=2,
                                  n_kv_heads=KV_HEADS, head_width=WIDTH,
                                  qk_norm=True, vocab=64, d_ff=32)
    params = tfm.init_params(whole)
    assert params["qn"].shape == (2, 32) and params["kn"].shape == (2, 16)
    head = tfm.init_params(tfm.TransformerConfig(
        d_model=48, n_heads=HEADS, n_layers=2, n_kv_heads=KV_HEADS,
        head_width=WIDTH, qk_norm="head", vocab=64, d_ff=32))
    assert head["qn"].shape == head["kn"].shape == (2, WIDTH)
    assert tfm.param_specs(P, whole)["kn"] == P(None, "tp")
    assert tfm.param_specs(P, tfm.TransformerConfig(qk_norm="head"))[
        "qn"] == P()
