"""Mesh helpers + flagship model: 3D-parallel (dp×sp×tp) train step on the
virtual 8-device mesh; ring vs gathered attention parity; loss decreases."""

import numpy as np
import pytest

import jax

from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel.mesh import make_mesh, mesh_shape_for


def test_mesh_shape_factoring():
    # the innermost (last) axis always gets the largest factor
    assert mesh_shape_for(8, ["dp", "tp"]) == {"dp": 2, "tp": 4}
    assert mesh_shape_for(8, ["dp", "sp", "tp"]) == {"dp": 2, "sp": 2, "tp": 2}
    assert mesh_shape_for(6, ["dp", "sp", "tp"]) == {"dp": 1, "sp": 2, "tp": 3}
    assert mesh_shape_for(1, ["dp", "tp"]) == {"dp": 1, "tp": 1}
    for n in (2, 3, 4, 5, 6, 8, 12, 16):
        s = mesh_shape_for(n, ["a", "b", "c"])
        assert int(np.prod(list(s.values()))) == n
        assert s["c"] == max(s.values())


def test_make_mesh_variants():
    m = make_mesh()
    assert m.axis_names == ("world",) and m.size == 8
    m2 = make_mesh({"dp": 2, "tp": -1})
    assert m2.shape == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError):
        make_mesh({"dp": 3, "tp": 3})


CFG = tfm.TransformerConfig(
    vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
    attention="ring", compute_dtype="float32")


def _mesh222():
    return make_mesh({"dp": 2, "sp": 2, "tp": 2})


def _tokens(cfg, batch=4, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, size=(batch, cfg.seq)).astype(np.int32)


def test_forward_shapes():
    mesh = _mesh222()
    params = tfm.init_params(CFG)
    fwd = jax.jit(tfm.make_forward(CFG, mesh))
    logits = fwd(params, _tokens(CFG))
    assert logits.shape == (4, CFG.seq, CFG.vocab)
    assert np.isfinite(np.asarray(logits)).all()


def test_ring_equals_gathered_loss():
    import dataclasses

    mesh = _mesh222()
    params = tfm.init_params(CFG)
    toks = _tokens(CFG)
    l_ring = jax.jit(tfm.make_loss_fn(CFG, mesh))(params, toks)
    cfg_g = dataclasses.replace(CFG, attention="gathered")
    l_gath = jax.jit(tfm.make_loss_fn(cfg_g, mesh))(params, toks)
    np.testing.assert_allclose(float(l_ring), float(l_gath), rtol=1e-5)


def test_train_step_decreases_loss():
    mesh = _mesh222()
    params = tfm.init_params(CFG)
    step, init_opt = tfm.make_train_step(CFG, mesh, lr=1e-2)
    opt_state = init_opt(params)
    toks = _tokens(CFG)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, toks)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_bf16_adam_moments_track_f32():
    """adam_mu_dtype="bfloat16" must store the first moment in bf16 and
    train indistinguishably at tiny scale (the HBM lever for batch 32 on
    the flagship — see TransformerConfig.adam_mu_dtype)."""
    import dataclasses

    import jax.numpy as jnp

    mesh = _mesh222()
    toks = _tokens(CFG)
    losses = {}
    for mu in (None, "bfloat16"):
        cfg = dataclasses.replace(CFG, adam_mu_dtype=mu)
        params = tfm.init_params(cfg)
        step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
        opt_state = init_opt(params)
        for _ in range(4):
            params, opt_state, loss = step(params, opt_state, toks)
        losses[mu] = float(loss)
        mu_leaf = opt_state[0].mu["w1"]
        want = jnp.bfloat16 if mu == "bfloat16" else jnp.float32
        assert mu_leaf.dtype == want, (mu, mu_leaf.dtype)
    assert np.isfinite(losses["bfloat16"])
    # same trajectory to a loose tolerance (bf16 m rounds each update)
    assert abs(losses[None] - losses["bfloat16"]) < 0.05 * abs(losses[None])


def test_bf16_param_storage_master_weights():
    """param_dtype="bfloat16": live params/grads in bf16, f32 master in
    the optimizer state, training still converges (small lr*update
    increments land in the master, not the bf16 lattice)."""
    import dataclasses

    import jax.numpy as jnp

    mesh = _mesh222()
    cfg = dataclasses.replace(CFG, param_dtype="bfloat16")
    params = tfm.init_params(cfg)
    assert np.asarray(params["w1"]).dtype == jnp.bfloat16
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
    opt_state = init_opt(params)
    assert opt_state["master"]["w1"].dtype == jnp.float32
    toks = _tokens(cfg)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, toks)
        losses.append(float(loss))
    assert params["w1"].dtype == jnp.bfloat16
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_lr_schedule_accepted():
    """lr may be an optax schedule (callable step -> lr) — warmup/decay
    flows straight through to adamw."""
    import optax

    mesh = _mesh222()
    sched = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=1e-2, warmup_steps=2,
        decay_steps=10)
    params = tfm.init_params(CFG)
    step, init_opt = tfm.make_train_step(CFG, mesh, lr=sched)
    opt_state = init_opt(params)
    toks = _tokens(CFG)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, toks)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses


def test_grad_accum_matches_single_pass():
    """grad_accum=4 must produce the same trajectory as one full-batch
    pass (mean of microbatch grads == full-batch grad for a mean loss
    over equal-sized microbatches)."""
    import dataclasses

    mesh = _mesh222()
    toks = _tokens(CFG, batch=8)  # microbatch (8/4=2) must still cover dp=2
    losses = {}
    for acc in (1, 4):
        cfg = dataclasses.replace(CFG, grad_accum=acc)
        params = tfm.init_params(cfg)
        step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
        opt_state = init_opt(params)
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, toks)
        losses[acc] = float(loss)
    assert np.isfinite(losses[4])
    assert abs(losses[1] - losses[4]) < 2e-3 * max(1.0, abs(losses[1])), \
        losses


def test_zero1_optimizer_state_sharded_and_converges():
    """zero1_axis="dp": optimizer leaves are (dp, n/dp) sharded over dp
    (each rank holds 1/dp), training matches the replicated baseline."""
    import dataclasses

    import jax.numpy as jnp
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = _mesh222()  # tp=2: zero1 must NOT destroy Megatron sharding
    toks = _tokens(CFG)
    losses = {}
    for z in (None, "dp"):
        cfg = dataclasses.replace(CFG, zero1_axis=z)
        params = tfm.init_params(cfg)
        step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
        opt_state = init_opt(params)

        def _assert_sharded(state):
            for leaf in (state["master"]["w1"], state["opt"][0].mu["w1"],
                         state["opt"][0].nu["w1"]):
                assert leaf.ndim == 2 and leaf.shape[0] == 2
                # each device row-shards the (dp, n) leaf: 1/dp resident
                assert leaf.sharding.shard_shape(leaf.shape)[0] == 1, (
                    leaf.sharding)

        if z:
            _assert_sharded(opt_state)
        for _ in range(4):
            params, opt_state, loss = step(params, opt_state, toks)
        if z:
            # ...and the state must STAY sharded after real steps, and
            # updated live params must keep their tp sharding
            _assert_sharded(opt_state)
            shard_shape = params["w1"].sharding.shard_shape(
                params["w1"].shape)
            assert shard_shape[-1] == CFG.d_ff // 2, params["w1"].sharding
        losses[z] = float(loss)
        assert params["w1"].dtype == jnp.float32
    assert np.isfinite(losses["dp"])
    assert abs(losses[None] - losses["dp"]) < 0.02 * abs(losses[None])


def test_tp_sharding_is_real():
    """The compiled train step must actually shard tp weights (not silently
    replicate): check the output sharding of the updated params."""
    mesh = _mesh222()
    params = tfm.init_params(CFG)
    step, init_opt = tfm.make_train_step(CFG, mesh, lr=1e-3)
    opt_state = init_opt(params)
    new_params, _, _ = step(params, opt_state, _tokens(CFG))
    shard_shape = new_params["w1"].sharding.shard_shape(
        new_params["w1"].shape)
    assert shard_shape[-1] == CFG.d_ff // 2  # tp=2
