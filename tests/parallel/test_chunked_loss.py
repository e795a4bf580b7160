"""The chunked cross entropy (``TransformerConfig.ce_chunk``): a
``jax.custom_vjp`` that forms each chunk's gradient in the pass that makes
its logits.  Held to the full-logits path, to the checkpointed scan it
replaced (a local copy), and to its own shape: one vocabulary matmul a
chunk for the value, three for value and gradient, none recomputed.  And
the split of its work over ``tp`` (``_local_loss``): by vocabulary rows
where ``param_specs`` stores the table by rows over ``tp`` (each rank scans
every local position against its ``V/tp`` rows and the softmax is completed
over ``tp`` inside the chunk), by positions where ``tp`` does not divide the
vocabulary and the table stays whole; loss and gradients are those of the
mesh without ``tp`` either way."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel.mesh import make_mesh

CFG = tfm.TransformerConfig(
    vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
    attention="ring", compute_dtype="float32")
MESHES = {
    "dp2sp2tp2": {"dp": 2, "sp": 2, "tp": 2},
    "dp4sp1tp2": {"dp": 4, "sp": 1, "tp": 2},
}
TP4 = {"dp": 2, "sp": 1, "tp": 4}
TP_MESHES = {**MESHES, "dp2sp1tp4": TP4}


def _tokens(cfg, batch=4, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, size=(batch, cfg.seq)).astype(np.int32)


def _value_and_grad(cfg, mesh, scale=1.0):
    loss = tfm.make_loss_fn(cfg, mesh)
    return jax.jit(jax.value_and_grad(lambda p, t: scale * loss(p, t)))


def _mesh(axes):
    return make_mesh(axes, devices=jax.devices()[:math.prod(axes.values())])


def _assert_same_loss_and_gradients(got, want, tol=1e-5):
    """Loss to ``tol``, every leaf's gradient to ``tol`` of its largest
    element."""
    (l_got, g_got), (l_want, g_want) = got, want
    np.testing.assert_allclose(float(l_got), float(l_want), rtol=tol)
    assert set(g_got) == set(g_want)
    for k in g_want:
        a, b = np.asarray(g_got[k]), np.asarray(g_want[k])
        assert np.abs(b).max() > 0, k
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), k


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_matches_full_logits_float32(mesh_name):
    """(a) ce_chunk is numerically invisible in float32: the same loss and
    the same gradient of every leaf as the full-logits path."""
    mesh = make_mesh(MESHES[mesh_name])
    params, toks = tfm.init_params(CFG), _tokens(CFG)
    cfg_c = dataclasses.replace(CFG, ce_chunk=8)  # 2 or 4 chunks a device
    l_full, g_full = _value_and_grad(CFG, mesh)(params, toks)
    l_chunk, g_chunk = _value_and_grad(cfg_c, mesh)(params, toks)
    np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-6)
    for k in g_full:
        np.testing.assert_allclose(
            np.asarray(g_full[k]), np.asarray(g_chunk[k]),
            rtol=2e-5, atol=1e-6, err_msg=k)


def _checkpointed_nll_sum(chunk, h, emb, labels, weight):
    """The scan this repo ran up to PR 30: the chunk's forward under
    ``jax.checkpoint``, run again in the backward pass."""
    B, T, D = h.shape
    n = T // chunk
    emb_c = emb.astype(h.dtype)

    def body(acc, inp):
        h_c, lab_c, w_c = inp
        logits = jnp.einsum("btd,vd->btv", h_c, emb_c,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab_logit = jnp.take_along_axis(
            logits, lab_c[..., None], axis=-1)[..., 0]
        return acc + ((lse - lab_logit) * w_c).sum(), None

    hs = jnp.moveaxis(h.reshape(B, n, chunk, D), 1, 0)
    labs = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    ws = jnp.moveaxis(weight.reshape(B, n, chunk), 1, 0)
    total, _ = lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                        (hs, labs, ws))
    return total


def _loss_inputs(dtype, B=4, T=32, D=64, V=128, seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((B, T, D)), dtype)
    emb = jnp.asarray(0.2 * rng.standard_normal((V, D)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)
    weight = jnp.asarray(rng.random((B, T)) < 0.8, jnp.float32)
    return h, emb, labels, weight


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_matches_checkpointed_scan(dtype, tol):
    """(b) against the scan it replaced: the same operands meet the matmuls
    at the same precision, so bfloat16 agrees to bfloat16's own rounding
    (the hidden states' gradient is rounded once more, after the scaling)."""
    cfg = dataclasses.replace(CFG, ce_chunk=8, compute_dtype=dtype)
    h, emb, labels, weight = _loss_inputs(dtype)
    new = jax.jit(jax.value_and_grad(
        lambda h, e: 0.01 * tfm._chunked_nll_sum(cfg, h, e, labels, weight),
        argnums=(0, 1)))
    old = jax.jit(jax.value_and_grad(
        lambda h, e: 0.01 * _checkpointed_nll_sum(8, h, e, labels, weight),
        argnums=(0, 1)))
    (l_new, (dh_new, de_new)), (l_old, (dh_old, de_old)) = new(h, emb), old(h, emb)
    np.testing.assert_allclose(float(l_new), float(l_old), rtol=1e-6)
    assert dh_new.dtype == h.dtype and de_new.dtype == emb.dtype
    for name, a, b in (("h", dh_new, dh_old), ("emb", de_new, de_old)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), name


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_cotangent_scales_every_gradient(mesh_name):
    """(c) the backward rule scales by the scalar cotangent: grad of
    3·loss is 3·grad of loss, leaf by leaf (on a ``tp`` mesh the scale
    reaches every rank's part of the positions)."""
    mesh = make_mesh(MESHES[mesh_name])
    cfg = dataclasses.replace(CFG, ce_chunk=8)
    params, toks = tfm.init_params(cfg), _tokens(cfg)
    l1, g1 = _value_and_grad(cfg, mesh)(params, toks)
    l3, g3 = _value_and_grad(cfg, mesh, scale=3.0)(params, toks)
    np.testing.assert_allclose(float(l3), 3 * float(l1), rtol=1e-6)
    for k in g1:
        assert np.abs(np.asarray(g1[k])).max() > 0, k
        np.testing.assert_allclose(
            np.asarray(g3[k]), 3 * np.asarray(g1[k]),
            rtol=1e-5, atol=1e-6, err_msg=k)


def test_weight_zero_positions_get_no_gradient():
    """(d) a position of weight 0 is not in the sum: its hidden state's
    gradient is exactly zero, and the others' is not."""
    cfg = dataclasses.replace(CFG, ce_chunk=8)
    h, emb, labels, weight = _loss_inputs("float32")
    assert 0 < float(weight.sum()) < weight.size
    d_h = jax.jit(jax.grad(
        lambda h: tfm._chunked_nll_sum(cfg, h, emb, labels, weight)))(h)
    norms = np.abs(np.asarray(d_h)).max(axis=-1)
    dropped = np.asarray(weight) == 0
    assert (norms[dropped] == 0).all()
    assert (norms[~dropped] > 0).all()


def _equations(jaxpr, under=()):
    """Every equation of a jaxpr and of the jaxprs its equations hold, with
    the equations it sits under."""
    for eqn in jaxpr.eqns:
        yield eqn, under
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, under + (eqn,))


def _vocabulary_dots(fn, *args, vocab):
    """Of each matmul with the vocabulary among its dimensions: the names
    of the primitives it sits under, and the length of the innermost scan
    among them."""
    found = []
    for eqn, under in _equations(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "dot_general" and any(
                vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            scans = [e.params["length"] for e in under
                     if e.primitive.name == "scan"]
            found.append((tuple(e.primitive.name for e in under),
                          scans[-1] if scans else None))
    return found


# a vocabulary no other width of the model equals, nor its half or quarter;
# and one that tp = 4 does not divide
_DOTS_CFG = dataclasses.replace(CFG, ce_chunk=8, attention="xla", vocab=160)
_WHOLE_CFG = dataclasses.replace(_DOTS_CFG, vocab=162)


@pytest.mark.parametrize("tp", [1, 2, 4])
@pytest.mark.parametrize("what,per_chunk", [("value", 1), ("value_and_grad", 3)])
def test_vocabulary_matmuls_a_chunk(what, per_chunk, tp):
    """(e) the value alone holds one vocabulary matmul in its scan's body,
    value and gradient hold three (logits, the hidden states' gradient, the
    head's), and none of them sits under a checkpoint: nothing is made
    twice.  (The checkpointed scan held four.)  With the table's rows over
    ``tp`` every rank scans all ``T / ce_chunk`` chunks and its matmuls run
    over ``V / tp`` rows; no matmul over the whole vocabulary is left."""
    mesh = _mesh({"dp": 1, "sp": 1, "tp": tp})
    cfg = _DOTS_CFG
    params, toks = tfm.init_params(cfg), _tokens(cfg)
    loss = tfm.make_loss_fn(cfg, mesh)
    fn = loss if what == "value" else jax.value_and_grad(loss)
    dots = _vocabulary_dots(fn, params, toks, vocab=cfg.vocab // tp)
    assert len(dots) == per_chunk, dots
    for under, length in dots:
        assert "scan" in under, under
        assert not {"checkpoint", "remat", "remat2"} & set(under), under
        assert length == cfg.seq // cfg.ce_chunk, (length, under)
    if tp > 1:
        assert not _vocabulary_dots(fn, params, toks, vocab=cfg.vocab)


@pytest.mark.parametrize("mesh_name", sorted(TP_MESHES))
def test_rows_split_over_tp_match_no_tp(mesh_name):
    """(f) each ``tp`` rank holds ``1/tp`` of the table's rows and takes the
    cross entropy of every local position against them (eight chunks a
    rank on ``dp4sp1tp2`` and ``dp2sp1tp4``, four on ``dp2sp2tp2``): the
    loss and the gradient of every leaf, the table's rows put back
    together, the replicated ``lnf``, ``ln1`` and the sharded ``wq``,
    ``wo``, ``w1``, ``w2`` alike, are those of the same mesh without
    ``tp``, where the table is whole."""
    axes = TP_MESHES[mesh_name]
    cfg = dataclasses.replace(CFG, ce_chunk=4)
    params, toks = tfm.init_params(cfg), _tokens(cfg)
    split = _value_and_grad(cfg, _mesh(axes))(params, toks)
    whole = _value_and_grad(cfg, _mesh({**axes, "tp": 1}))(params, toks)
    assert {"emb", "lnf", "ln1", "wq", "wo", "w1", "w2"} <= set(split[1])
    assert split[1]["emb"].shape == (cfg.vocab, cfg.d_model)
    _assert_same_loss_and_gradients(split, whole)


def test_vocabulary_tp_does_not_divide_keeps_the_split_by_positions():
    """(g) 162 rows over ``tp`` = 4: the table stays whole on every rank and
    each rank takes its quarter of the positions, one chunk of three
    matmuls over all 162 rows; loss and gradients are those of the mesh
    without ``tp``."""
    cfg = _WHOLE_CFG
    mesh = _mesh(TP4)
    params, toks = tfm.init_params(cfg), _tokens(cfg)
    dots = _vocabulary_dots(jax.value_and_grad(tfm.make_loss_fn(cfg, mesh)),
                            params, toks, vocab=cfg.vocab)
    assert [length for _under, length in dots] == [1, 1, 1], dots
    split = _value_and_grad(cfg, mesh)(params, toks)
    whole = _value_and_grad(cfg, _mesh({**TP4, "tp": 1}))(params, toks)
    _assert_same_loss_and_gradients(split, whole)


@pytest.mark.parametrize("cfg,rows", [
    pytest.param(_DOTS_CFG, 40, id="rows-split"),
    pytest.param(_WHOLE_CFG, 162, id="nothing-divides")])
def test_length_tp_does_not_divide(cfg, rows):
    """(h) 30 local positions over ``tp`` = 4: no rank takes a part of the
    positions and the scan runs over all six chunks on every rank, against
    its quarter of the rows where ``tp`` divides the vocabulary and against
    the whole table where it divides neither; loss and gradients are still
    those of the mesh without ``tp``."""
    cfg = dataclasses.replace(cfg, seq=30, ce_chunk=5)
    params, toks = tfm.init_params(cfg), _tokens(cfg)
    loss = tfm.make_loss_fn(cfg, _mesh(TP4))
    dots = _vocabulary_dots(jax.value_and_grad(loss), params, toks,
                            vocab=rows)
    assert [length for _under, length in dots] == [6, 6, 6], dots
    split = _value_and_grad(cfg, _mesh(TP4))(params, toks)
    whole = _value_and_grad(cfg, _mesh({**TP4, "tp": 1}))(params, toks)
    _assert_same_loss_and_gradients(split, whole)


def _split_nll(cfg, h, emb, labels, weight):
    """``_chunked_nll_sum`` on two ranks of ``tp``, each with its half of
    ``emb``'s rows: (sum, the hidden states' gradient with the two ranks'
    parts added, the head's gradient with its rows put back together).
    Each rank differentiates its half of the sum, which every rank has
    whole: the two halves are the objective."""
    from jax.sharding import PartitionSpec as P

    def local(h, emb):
        total, (d_h, d_emb) = jax.value_and_grad(
            lambda h, e: 0.5 * tfm._chunked_nll_sum(cfg, h, e, labels,
                                                    weight),
            argnums=(0, 1))(h, emb)
        return 2 * total, lax.psum(d_h, "tp"), d_emb

    return jax.jit(jax.shard_map(
        local, mesh=_mesh({"dp": 1, "sp": 1, "tp": 2}),
        in_specs=(P(), P("tp", None)), out_specs=(P(), P(), P("tp", None)),
        check_vma=False))(h, emb)


@pytest.mark.parametrize("case", ["label-on-the-other-rank",
                                  "label-on-this-rank", "last-weight-zero"])
def test_rows_split_chunk_against_the_whole_table(case):
    """(i) the chunk's own pieces on two ranks of ``tp``.  Every label's
    row on rank 1 (rank 0 gives the label's logit nothing and its one-hot
    is empty), every label's row on rank 0, and a last position of weight
    zero, whose hidden state gets exactly no gradient from either rank:
    sum and both gradients are the whole table's."""
    cfg = dataclasses.replace(CFG, ce_chunk=8)
    h, emb, labels, weight = _loss_inputs("float32")
    half = emb.shape[0] // 2
    if case == "label-on-the-other-rank":
        labels = half + labels % half
    elif case == "label-on-this-rank":
        labels = labels % half
    else:
        weight = jnp.ones_like(weight).at[:, -1].set(0.0)
    want = jax.jit(jax.value_and_grad(
        lambda h, e: tfm._chunked_nll_sum(cfg, h, e, labels, weight),
        argnums=(0, 1)))(h, emb)
    total, d_h, d_emb = _split_nll(cfg, h, emb, labels, weight)
    _assert_same_loss_and_gradients(
        (total, {"h": d_h, "emb": d_emb}),
        (want[0], {"h": want[1][0], "emb": want[1][1]}))
    if case == "last-weight-zero":
        norms = np.abs(np.asarray(d_h)).max(axis=-1)
        assert (norms[:, -1] == 0).all() and (norms[:, :-1] > 0).all()


@pytest.mark.parametrize("vocab,spec,axes", [
    pytest.param(128, ("tp", None), ("dp",), id="rows-over-tp"),
    pytest.param(127, (), ("dp", "tp"), id="tp-does-not-divide")])
def test_the_table_is_summed_over_the_axes_it_is_not_split_over(vocab, spec,
                                                                axes):
    """(j) on the four-chip cell's mesh, dp2 x tp2: the table by rows over
    ``tp`` and its gradient summed over the dp pair alone, an untied head
    alike; a vocabulary ``tp`` does not divide whole, summed over all four."""
    from jax.sharding import PartitionSpec as P

    cfg = dataclasses.replace(CFG, vocab=vocab, tie_head=False)
    mesh = _mesh({"dp": 2, "sp": 1, "tp": 2})
    specs, sums = tfm.param_specs(P, cfg, mesh), tfm.grad_sum_axes(cfg, mesh)
    for leaf in ("emb", "head"):
        assert specs[leaf] == P(*spec), leaf
        assert sums[leaf] == axes, leaf
    assert tfm.param_specs(P, cfg)["emb"] == P()      # no mesh: whole
    assert sums["lnf"] == ("dp", "tp") and sums["w1"] == ("dp",)
