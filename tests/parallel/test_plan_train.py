"""A layer plan as a trainer, the path no cell runs: cell 7's tiny
configuration's loss and gradient against the reference's, float32, and a
train step on two devices.  ``test_plan.py`` has the configuration
(``tiny``).  Agreement only: nothing here is a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel.mesh import make_mesh
from tests.parallel.test_plan import prompts_of, tiny


def test_loss_and_gradient_are_the_references():
    ref, shape, cfg, mesh, params = tiny()
    tokens = prompts_of(cfg, 2, cfg.seq, seed=5)
    loss_fn = tfm.make_loss_fn(cfg, mesh)
    ours, grads = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)

    def ref_loss(p):
        return ref.nll_sum(shape, p, jnp.asarray(tokens)) / (
            tokens.shape[0] * (tokens.shape[1] - 1))

    theirs, want = jax.value_and_grad(ref_loss)(params)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-5)
    for leaf in ("kda_q", "kda_a", "kda_dt", "kda_b", "kda_f2", "kda_cv",
                 "mla_kvb", "mla_q", "wg", "w2", "sw1", "dw2", "emb", "ln1"):
        scale = float(jnp.abs(want[leaf]).max())
        assert scale > 0, leaf
        assert float(jnp.abs(grads[leaf] - want[leaf]).max()) < 2e-3 * scale, leaf
    # the selection bias picks and does not weigh: no gradient reaches it
    assert not np.asarray(grads["wgb"]).any()


def test_a_train_step_runs_on_two_devices():
    """dp = 2: the layers' gradients are summed where the loop starts."""
    _ref, _shape, cfg, _mesh, params = tiny()
    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 1}, devices=jax.devices()[:2])
    tokens = prompts_of(cfg, 4, cfg.seq, seed=6)
    one = tfm.make_loss_fn(cfg, make_mesh(
        {"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1]))
    want = jax.jit(jax.grad(one))(params, tokens)
    loss_and_grads = jax.jit(tfm._make_loss_and_grads(cfg, mesh))
    _loss, got = loss_and_grads(tfm.shard_params(cfg, mesh, params), tokens)
    for leaf in ("kda_q", "mla_q", "w2", "dw1", "emb", "head", "ln2"):
        a, b = np.asarray(got[leaf]), np.asarray(want[leaf])
        assert np.abs(a - b).max() < 1e-3 * np.abs(b).max(), leaf
