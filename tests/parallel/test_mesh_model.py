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


# ---- the train step's gradients and where they are summed ----------------
#
# On a mesh of several devices the step takes the gradient inside its
# shard_map and sums each leaf itself (``_make_loss_and_grads``): a layer's
# in that layer's backward, inside the loop over layers.  What
# ``jax.value_and_grad`` of ``make_loss_fn`` gives, the sums made by
# shard_map's transpose after the whole backward, is the form the step had
# before and stays the reference here.

SUM_CFG = tfm.TransformerConfig(
    vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
    attention="xla", compute_dtype="float32")
SUM_MESHES = {
    "dp2": {"dp": 2, "sp": 1, "tp": 1},
    "tp2": {"dp": 1, "sp": 1, "tp": 2},
    "dp2tp2": {"dp": 2, "sp": 1, "tp": 2},
    "dp2sp2": {"dp": 2, "sp": 2, "tp": 1},
    "dp4": {"dp": 4, "sp": 1, "tp": 1},
}


def _mesh_of(axes):
    import math

    return make_mesh(axes, devices=jax.devices()[:math.prod(axes.values())])


def _worst(got, want):
    """The largest difference of a leaf, of that leaf's largest element."""
    return max(float(np.abs(np.asarray(got[k]) - np.asarray(want[k])).max()
                     / np.abs(np.asarray(want[k])).max()) for k in want)


@pytest.mark.parametrize("accum", [1, 2])
@pytest.mark.parametrize("mesh_name,batch", [
    *((name, 8) for name in sorted(SUM_MESHES)),
    # a replica's 6 sequences: two halves of 3 in one pass, and 3 a pass in
    # two, an odd number, which the psums' transpose leaves whole
    ("dp2tp2", 12), ("tp2", 6)])
def test_step_gradients_equal_the_no_mesh_gradient(mesh_name, batch, accum):
    """Loss and every leaf's gradient of one step, in float32, are those of
    ``jax.grad`` on one device.  2e-6 of a leaf's largest element: what the
    sums of shard_map's transpose read against one device on the meshes
    with ``tp`` (1.2e-6; a matmul split over two devices adds in another
    order), and with one pass the step's gradients are those bit for bit:
    the same addends, two or four to a sum, also where a block's psum over
    ``tp`` is transposed by halves of an even number of sequences
    (``layers.row_parallel``: each half's sum and product are the
    transpose's own rows)."""
    import dataclasses

    cfg = dataclasses.replace(SUM_CFG, grad_accum=accum)
    params, toks = tfm.init_params(cfg), _tokens(cfg, batch=batch)
    one = _mesh_of({"dp": 1, "sp": 1, "tp": 1})
    l_want, g_want = jax.jit(jax.value_and_grad(
        tfm.make_loss_fn(SUM_CFG, one)))(params, toks)
    mesh = _mesh_of(SUM_MESHES[mesh_name])
    l_got, g_got = jax.jit(tfm._make_loss_and_grads(cfg, mesh))(params, toks)
    np.testing.assert_allclose(float(l_got), float(l_want), rtol=1e-6)
    assert set(g_got) == set(g_want)
    assert _worst(g_got, g_want) <= 2e-6
    if accum == 1:
        _, g_before = jax.jit(jax.value_and_grad(
            tfm.make_loss_fn(SUM_CFG, mesh)))(params, toks)
        for k in g_before:
            np.testing.assert_array_equal(np.asarray(g_got[k]),
                                          np.asarray(g_before[k]), err_msg=k)


def _all_reduces(text, trips):
    """[(replica groups, operand bytes a step, in a loop)] of every
    all-reduce of a tensor in a lowered StableHLO text; one inside a
    ``stablehlo.while``, or in a function called from inside one, counts
    once a trip (``trips``: the loops here are the loops over layers).
    Scalars (the loss's sum and count) are left out."""
    import re

    functions, name = {}, None
    for line in text.splitlines():
        opened = re.match(r"\s*func\.func .*@([\w.]+)\(", line)
        if opened:
            name = opened.group(1)
            functions[name] = []
        elif name is not None:
            functions[name].append(line)

    found = []

    def walk(function, in_loop):
        whiles, groups = [], None  # the indentation of the open while loops
        for line in functions[function]:
            indent = len(line) - len(line.lstrip())
            while (whiles and indent <= whiles[-1]
                   and line.strip() not in ("", "cond {", "} do {")):
                whiles.pop()
            if "stablehlo.while" in line:
                whiles.append(indent)
            looped = in_loop or bool(whiles)
            called = re.search(r"call @([\w.]+)\(", line)
            if called:
                walk(called.group(1), looped)
            if '"stablehlo.all_reduce"' in line:
                groups = re.search(
                    r"replica_groups = dense<(\[\[.*?\]\])>", line).group(1)
            # the operand's type follows the reduction's region
            closed = re.match(r"\s*\}\) : \(tensor<((?:\d+x)*)f32>\)", line)
            if groups and closed:
                dims = [int(d) for d in closed.group(1).split("x") if d]
                if dims:
                    found.append((groups, 4 * int(np.prod(dims))
                                  * (trips if looped else 1), looped))
                groups = None

    walk("main", False)
    return found


@pytest.mark.parametrize("vocab,rows,over_dp,over_every", [
    pytest.param(128, 64, [False] + [True] * 6, [False, True, True],
                 id="rows-over-tp"),
    pytest.param(127, 127, [True] * 6, [False, False, True, True],
                 id="whole-table")])
def test_dp2tp2_step_sums_each_leaf_once_over_the_same_devices(
        vocab, rows, over_dp, over_every):
    """The lowered dp2 x tp2 step holds one all-reduce a leaf, a layer's
    inside the backward loop, over the replica groups and with the total
    operand bytes of the sums shard_map's transpose made.  The table, by
    rows over ``tp``, is summed over the dp pair after the loop; whole (a
    vocabulary ``tp`` does not divide), over all four devices.  Over the
    ``tp`` pairs the step moves one block's psum a layer less: each block's
    psum is made and transposed by halves of the sequences, the same bytes
    in two all-reduces, and the sum the attention block made in the forward
    is kept (``layers.SUMMED_NAME``) where the reference's backward loop
    makes it again."""
    import dataclasses

    cfg = dataclasses.replace(SUM_CFG, vocab=vocab)
    L = cfg.n_layers
    mesh = _mesh_of(SUM_MESHES["dp2tp2"])
    params, toks = tfm.init_params(cfg), _tokens(cfg, batch=8)
    before = _all_reduces(jax.jit(jax.value_and_grad(tfm.make_loss_fn(
        cfg, mesh))).lower(params, toks).as_text(), L)
    now = _all_reduces(jax.jit(tfm._make_loss_and_grads(
        cfg, mesh)).lower(params, toks).as_text(), L)

    def totals(rows):
        out = {}
        for groups, size, _ in rows:
            out[groups] = out.get(groups, 0) + size
        return out

    dp, every, tp = "[[0, 2], [1, 3]]", "[[0, 1, 2, 3]]", "[[0, 1], [2, 3]]"
    assert set(totals(now)) == set(totals(before)) == {dp, every, tp}
    assert all(totals(now)[g] == totals(before)[g] for g in (dp, every))
    psum = 4 * (toks.shape[0] // 2) * cfg.seq * cfg.d_model
    assert totals(now)[tp] == totals(before)[tp] - L * psum
    halves = [size for g, size, in_loop in now if g == tp and in_loop
              and size == L * psum // 2]
    assert len(halves) == 8     # two blocks, two passes, two halves each
    # the six matrices of a layer over dp and its two norms over dp and tp,
    # in the loop; the table and lnf after it
    assert sorted(in_loop for g, _, in_loop in now if g == dp) == over_dp
    assert sorted(in_loop for g, _, in_loop in now if g == every) == over_every
    assert not any(in_loop for g, _, in_loop in before if g in (dp, every))
    # the table's sum: the ``rows`` a rank holds, after the loop
    assert [size for g, size, in_loop in now if not in_loop
            and g == (every if rows == vocab else dp)
            and size > 4 * cfg.d_model] == [4 * rows * cfg.d_model]


@pytest.mark.parametrize("mesh_name", ["tp2", "dp2tp2", "dp2sp2tp2"])
def test_forward_with_the_tables_rows_over_tp_equals_one_device(mesh_name):
    """``make_forward`` on a mesh whose ``tp`` splits the table's rows (each
    rank looks up its own rows and makes the logits of its own, gathered
    over ``tp``) hands back the whole vocabulary's logits, those of one
    device."""
    from jax.sharding import PartitionSpec as P

    axes = {**SUM_MESHES, "dp2sp2tp2": {"dp": 2, "sp": 2, "tp": 2}}[mesh_name]
    mesh = _mesh_of(axes)
    assert tfm.param_specs(P, SUM_CFG, mesh)["emb"] == P("tp", None)
    params, toks = tfm.init_params(SUM_CFG), _tokens(SUM_CFG, batch=4)
    want = np.asarray(jax.jit(tfm.make_forward(
        SUM_CFG, _mesh_of({"dp": 1, "sp": 1, "tp": 1})))(params, toks))
    got = np.asarray(jax.jit(tfm.make_forward(SUM_CFG, mesh))(
        tfm.shard_params(SUM_CFG, mesh, params), toks))
    assert got.shape == (4, SUM_CFG.seq, SUM_CFG.vocab)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * want.std())


def test_forward_only_programs_know_nothing_of_the_gradient_sums(monkeypatch):
    """``make_forward``, ``make_loss_fn`` and the decoder's prefill trace
    without ever asking where a gradient is summed: their lowered text
    cannot have changed with the train step."""
    from ompi_tpu.models import decode

    def never(*_a, **_k):
        raise AssertionError("a forward-only program asked for gradient sums")

    monkeypatch.setattr(tfm, "_sum_in_backward", never)
    monkeypatch.setattr(tfm, "grad_sum_axes", never)
    mesh = _mesh_of(SUM_MESHES["dp2tp2"])
    params, toks = tfm.init_params(SUM_CFG), _tokens(SUM_CFG, batch=8)
    assert "all_reduce" in jax.jit(tfm.make_forward(SUM_CFG, mesh)).lower(
        params, toks).as_text()
    jax.jit(tfm.make_loss_fn(SUM_CFG, mesh)).lower(params, toks)
    tp = _mesh_of(SUM_MESHES["tp2"])
    decode.make_decoder(SUM_CFG, tp, max_new=2)(
        tfm.shard_params(SUM_CFG, tp, params), toks[:, :8])
