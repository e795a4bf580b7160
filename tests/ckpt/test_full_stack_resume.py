"""Everything-composed resume: every training feature ON at once.

The per-feature trajectory tests (zero1, grad-accum, param-dtype, data
pipeline) each pass alone; this test turns them ALL on over one
dp×sp×tp mesh — ZeRO-1 sharded optimizer + bf16 param storage with f32
master + bf16 Adam moments + 2-microbatch gradient accumulation + the
prefetching data pipeline — snapshots mid-run, restores into fresh
arrays, resumes the data stream by step counter, and requires the
resumed trajectory to EQUAL the uninterrupted one.  Cross-feature
interactions (master-weight trees inside the zero1 state, bf16 leaves
through the npz store, stream step accounting under accumulation) have
nowhere to hide.
"""

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ompi_tpu.ckpt.store import SnapshotStore
from ompi_tpu.models import data as data_mod
from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel.mesh import make_mesh

CFG = tfm.TransformerConfig(
    vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
    attention="xla", compute_dtype="float32",
    zero1_axis="dp", param_dtype="bfloat16", adam_mu_dtype="bfloat16",
    grad_accum=2)

BATCH = 4          # 2 microbatches of 2 under grad_accum
SNAP_AT = 3        # steps before the snapshot
MORE = 2           # steps after


def _flat(tree):
    return {f"k{i}": np.asarray(leaf) for i, leaf in
            enumerate(jax.tree_util.tree_leaves(tree))}


def _unflat(tree_like, blobs):
    leaves = jax.tree_util.tree_leaves(tree_like)
    treedef = jax.tree_util.tree_structure(tree_like)
    out = [jax.device_put(blobs[f"k{i}"], like.sharding)
           for i, like in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _stream(source, mesh, start_step):
    return data_mod.train_stream(source, mesh, batch=BATCH, seq=CFG.seq,
                                 start_step=start_step)


def test_all_features_resume_exactly(tmp_path):
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    source = data_mod.ArraySource(
        (np.arange(4096) % CFG.vocab).astype(np.int32), seed=3)

    params = tfm.init_params(CFG)
    assert str(jax.tree_util.tree_leaves(params)[0].dtype) == "bfloat16"
    step, init_opt = tfm.make_train_step(CFG, mesh, lr=1e-2)
    opt_state = init_opt(params)

    stream = _stream(source, mesh, 0)
    for _ in range(SNAP_AT):
        params, opt_state, _ = step(params, opt_state, next(stream))

    store = SnapshotStore(str(tmp_path), job="fullstack")
    store.write_rank(0, 0, {**{f"p_{k}": v for k, v in params.items()},
                            **_flat(opt_state)})
    store.commit(0, nranks=1, extra={"step": SNAP_AT})

    # uninterrupted reference trajectory
    ref_p, ref_s = params, opt_state
    ref_losses = []
    for _ in range(MORE):
        ref_p, ref_s, loss = step(ref_p, ref_s, next(stream))
        ref_losses.append(float(loss))
    stream.close()

    # restore into FRESH arrays + resume the stream at the saved step
    meta = store.metadata(0)
    assert meta["step"] == SNAP_AT
    blobs = store.load_rank(0, 0)
    specs = tfm.param_specs(P, CFG, mesh)
    params2 = {k: jax.device_put(blobs[f"p_{k}"],
                                 NamedSharding(mesh, specs[k]))
               for k in params}
    assert str(jax.tree_util.tree_leaves(params2)[0].dtype) == "bfloat16"
    opt_state2 = _unflat(opt_state, blobs)
    stream2 = _stream(source, mesh, meta["step"])
    got_losses = []
    for _ in range(MORE):
        params2, opt_state2, loss2 = step(params2, opt_state2,
                                          next(stream2))
        got_losses.append(float(loss2))
    stream2.close()

    # exact trajectory: same losses, same final params bit for bit
    assert got_losses == ref_losses
    for k in ref_p:
        np.testing.assert_array_equal(np.asarray(ref_p[k]),
                                      np.asarray(params2[k]), err_msg=k)


def test_moe_composed_resume_exactly(tmp_path):
    """Same composition with the MoE family: routed (dropless top-2)
    experts over dp + ZeRO-1 + bf16 storage/f32 master + grad accumulation,
    snapshot/restore mid-run, exact trajectory."""
    cfg = tfm.TransformerConfig(
        vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
        attention="xla", compute_dtype="float32", moe_experts=8,
        moe_top_k=2, remat=False, zero1_axis="dp", param_dtype="bfloat16",
        adam_mu_dtype="bfloat16", grad_accum=2)
    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 1, "ep": 1},
                     devices=jax.devices()[:2])
    rng = np.random.default_rng(7)
    toks = [rng.integers(0, cfg.vocab, size=(BATCH, cfg.seq))
            .astype(np.int32) for _ in range(SNAP_AT + MORE)]

    params = tfm.init_params(cfg)
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
    opt_state = init_opt(params)
    for i in range(SNAP_AT):
        params, opt_state, _ = step(params, opt_state, toks[i])

    store = SnapshotStore(str(tmp_path), job="moe")
    store.write_rank(0, 0, {**{f"p_{k}": v for k, v in params.items()},
                            **_flat(opt_state)})
    store.commit(0, nranks=1)

    ref_p, ref_s, ref_losses = params, opt_state, []
    for i in range(MORE):
        ref_p, ref_s, loss = step(ref_p, ref_s, toks[SNAP_AT + i])
        ref_losses.append(float(loss))

    blobs = store.load_rank(0, 0)
    specs = tfm.param_specs(P, cfg, mesh)
    params2 = {k: jax.device_put(blobs[f"p_{k}"],
                                 NamedSharding(mesh, specs[k]))
               for k in params}
    opt_state2 = _unflat(opt_state, blobs)
    got_losses = []
    for i in range(MORE):
        params2, opt_state2, loss2 = step(params2, opt_state2,
                                          toks[SNAP_AT + i])
        got_losses.append(float(loss2))
    assert got_losses == ref_losses


def test_train_snapshot_restore_decode(tmp_path):
    """The serving handoff: train, snapshot, restore into fresh arrays,
    greedy-decode — the decoder's output from restored params must equal
    its output from the live ones (bf16 storage included)."""
    from ompi_tpu.models.decode import make_decoder

    cfg = tfm.TransformerConfig(
        vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=48,
        attention="xla", compute_dtype="float32",
        param_dtype="bfloat16", adam_mu_dtype="bfloat16")
    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 2},
                     devices=jax.devices()[:4])
    rng = np.random.default_rng(9)
    toks = rng.integers(0, cfg.vocab, size=(4, 32)).astype(np.int32)

    params = tfm.init_params(cfg)
    step, init_opt = tfm.make_train_step(cfg, mesh, lr=1e-2)
    opt_state = init_opt(params)
    for _ in range(2):
        params, opt_state, _ = step(params, opt_state, toks)

    dec = make_decoder(cfg, mesh, max_new=8)
    prompt = toks[:, :16]
    want = np.asarray(dec(params, prompt))

    store = SnapshotStore(str(tmp_path), job="serve")
    store.write_rank(0, 0, {k: v for k, v in params.items()})
    store.commit(0, nranks=1)
    blobs = store.load_rank(0, 0)
    specs = tfm.param_specs(P, cfg, mesh)
    params2 = {k: jax.device_put(blobs[k], NamedSharding(mesh, specs[k]))
               for k in params}
    got = np.asarray(dec(params2, prompt))
    np.testing.assert_array_equal(got, want)
    assert got.shape == (4, 16 + 8)
