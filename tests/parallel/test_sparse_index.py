"""Learned sparse attention (``models/sparse_index.py``: an index picks the
``topk`` positions a query attends to) against its plain reference,
``benchmarks/reference/keye_vl2.py``, at the configuration's tiny sizes,
float32, seeded, on the CPU: loss and logits of the whole-sequence path,
prefill then cached steps against the full forward, the selected sets
against ``lax.top_k``, a prompt under ``topk`` against dense attention, the
prefill in groups, the renormalised routing weights, where the index keys
are written, what the compiled step reads of its cache, and the layouts that
are refused.  Agreement and control flow only: nothing here is a time.

Tolerances.  Program and reference are both float32 and differ in the order
of their sums alone, so logits agree to ``PARITY`` (1e-4 of a deviation of
the logits; 2e-6 is read) as long as both select the same positions and
experts; at 32 positions and 8 experts no two scores lie within float32's
rounding of each other in these seeds, and a test that meets such a pair
would read an error of a tenth, not of 1e-4.  Sets are compared exactly.
"""

import copy
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.lib import cells, program
from ompi_tpu.models import sparse_index
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import _prefill_group, make_decoder
from ompi_tpu.parallel.mesh import make_mesh
from ompi_tpu.parallel.moe import routed_moe

CELL = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"
PARITY = 1e-4       # of a deviation of the logits; float32 on both sides

_built: dict = {}


def tiny():
    """(reference, its shape, the program's config in float32, a one-device
    mesh, parameters from the benchmark's initializer with every leaf that
    starts at one drawn away from it), made once."""
    if not _built:
        config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
        config["entry"]["options"]["compute_dtype"] = "float32"
        ref = program.reference(config)
        cfg = program.program_config(config)
        mesh = program.mesh(config, jax.devices()[:1])
        params = program.init_params(
            ref, config, program.param_shardings(config, cfg, mesh), seed=11)
        rng = np.random.default_rng(12)
        ones = [k for k, (_dims, std) in
                program.param_table(ref, config).items() if std is None]
        params = {k: (jnp.asarray(rng.uniform(0.5, 1.5, size=v.shape),
                                  v.dtype) if k in ones else v)
                  for k, v in params.items()}
        _built.update(ref=ref, shape=ref.Shape.from_config(config), cfg=cfg,
                      mesh=mesh, params=params)
    return (_built[k] for k in ("ref", "shape", "cfg", "mesh", "params"))


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


def prompts_of(cfg, batch, length, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


def decoded(cfg, mesh, params, prompts, max_new=6, **kwargs):
    tokens, logits = make_decoder(cfg, mesh, max_new=max_new,
                                  keep_logits=prompts.shape[0],
                                  **kwargs)(params, prompts)
    return np.asarray(tokens), np.asarray(logits)


# ---- the factory ------------------------------------------------------------

def test_the_factory_reads_the_published_group():
    _ref, _shape, cfg, _mesh, _params = tiny()
    assert isinstance(cfg, tfm.TransformerConfig)
    assert cfg.index == sparse_index.SparseIndex(n_heads=4, head_dim=64,
                                                 topk=4, q_slice=4)
    assert cfg.qk_norm == "head" and cfg.moe_norm_topk is True
    assert isinstance(cfg.rope_theta, float) and cfg.rope_theta == 1e7
    assert (cfg.head_dim, cfg.kv_heads, cfg.n_heads) == (64, 2, 4)
    real = program.program_config(cells.resolve(CELL).config)
    assert real.index == sparse_index.SparseIndex(16, 64, 2048, 512)
    assert (real.moe_experts, real.moe_top_k, real.d_ff) == (128, 8, 768)
    # the configurations that have no index are what they were
    assert tfm.TransformerConfig().index is None
    assert tfm.TransformerConfig().moe_norm_topk is False
    with pytest.raises(ValueError, match="one key a position"):
        sparse_index.sparse_config(sa_config={"indexer_num_kv_heads": 2})


# ---- the whole-sequence path ------------------------------------------------

def test_loss_and_logits_equal_the_references():
    ref, shape, cfg, mesh, params = tiny()
    tokens = prompts_of(cfg, 4, cfg.seq, seed=0)
    theirs = float(jax.jit(tfm.make_loss_fn(cfg, mesh))(params, tokens))
    assert ref.loss(shape, params, tokens, block=2) == pytest.approx(
        theirs, rel=1e-5)
    logits = jax.jit(tfm.make_forward(cfg, mesh))(params, tokens)
    assert error(logits, ref.logits(shape, params, tokens)) < PARITY


def test_the_loss_is_differentiable_and_the_selection_passes_no_gradient():
    """A trainer moves every leaf but the index's: the selection is a set
    and the index's alignment loss is not built."""
    _ref, _shape, cfg, mesh, params = tiny()
    tokens = prompts_of(cfg, 2, cfg.seq, seed=3)
    grads = jax.jit(jax.grad(tfm.make_loss_fn(cfg, mesh)))(params, tokens)
    for leaf, g in grads.items():
        assert np.isfinite(np.asarray(g)).all(), leaf
        moved = bool(np.abs(np.asarray(g)).max() > 0)
        assert moved == (leaf not in sparse_index.leaf_names()), leaf


@pytest.mark.parametrize("length,k", [(23, 5), (16, 4), (9, 8), (12, 1)],
                         ids=["no-multiple", "slices", "one-over", "one"])
def test_the_selected_sets_are_top_ks_at_every_position(length, k):
    """``select`` (a threshold found by bisection, applied as a mask) against
    ``lax.top_k`` of the same causal scores, a row a query: the same set at
    every position, and every earlier position while there are no more than
    ``k``."""
    rng = np.random.default_rng(length)
    found = jnp.asarray(rng.normal(size=(3, length, length)), jnp.float32)
    at = np.arange(length)
    causal = at[None, :] <= at[:, None]
    mask = np.asarray(sparse_index.select(found, jnp.asarray(causal)[None],
                                          k))
    best, chosen = lax.top_k(jnp.where(causal, found, -jnp.inf), k)
    want = np.zeros_like(mask)
    np.put_along_axis(want, np.asarray(chosen), np.asarray(best) > -np.inf,
                      -1)
    np.testing.assert_array_equal(mask, want)
    np.testing.assert_array_equal(mask[:, :k], np.broadcast_to(
        causal[:k], (3, k, length)))
    assert (mask.sum(-1) == np.minimum(at + 1, k)).all()


def test_ties_go_to_the_lower_position_as_top_ks_do():
    found = np.zeros((2, 3, 12), np.float32)
    found[0, 0, [2, 5, 7]] = 1.0            # three over a row of ties
    found[0, 1] = -1.5                      # a row of one value
    found[0, 2, 6:] = np.inf
    found[1] = np.random.default_rng(0).integers(0, 3, size=(3, 12))
    live = np.ones((1, 1, 12), bool)
    for k in (1, 4, 5, 11):
        mask = np.asarray(sparse_index.select(jnp.asarray(found),
                                              jnp.asarray(live), k))
        want = np.zeros_like(mask)
        np.put_along_axis(want, np.asarray(
            lax.top_k(jnp.asarray(found), k)[1]), True, -1)
        np.testing.assert_array_equal(mask, want)


def test_a_prompt_under_topk_is_dense_attention():
    """With no more positions than ``topk`` every query sees every earlier
    position: the logits are those of the same parameters without an index,
    and no index score is computed."""
    _ref, _shape, cfg, mesh, params = tiny()
    wide = dataclasses.replace(cfg, index=dataclasses.replace(
        cfg.index, topk=14))
    dense = dataclasses.replace(cfg, index=None)
    plain = {k: v for k, v in params.items()
             if k not in sparse_index.leaf_names()}
    prompts = prompts_of(cfg, 3, 8)
    tokens, logits = decoded(wide, mesh, params, prompts)     # 14 positions
    want_tokens, want = decoded(dense, mesh, plain, prompts)
    np.testing.assert_array_equal(tokens, want_tokens)
    assert error(logits, want) < 1e-5
    # and past topk the selection changes what attention reads
    _tokens, sparse = decoded(cfg, mesh, params, prompts)
    assert error(sparse, want) > 0.05
    text = jax.jit(tfm.make_forward(wide, mesh)).lower(
        params, prompts_of(cfg, 2, 12)).as_text(debug_info=True)
    assert "attn_proj" in text and "index.score" not in text


# ---- prefill, then cached steps ---------------------------------------------

@pytest.mark.parametrize("prompt_len", [12, 9, 2],
                         ids=["three-slices", "no-multiple", "under-topk"])
def test_prefill_then_cached_steps_give_the_references_logits(prompt_len):
    """K, V and the index keys handed over by the prefill and carried by the
    cached steps: the logits every generated token was picked from are the
    reference's full forward over prompt plus continuation, at every
    generated position."""
    ref, shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 3, prompt_len)
    tokens, logits = decoded(cfg, mesh, params, prompts)
    assert tokens.shape == (3, prompt_len + 6)
    assert logits.shape == (3, 6, cfg.vocab) and logits.dtype == np.float32
    np.testing.assert_array_equal(tokens[:, :prompt_len], prompts)
    np.testing.assert_array_equal(logits.argmax(-1), tokens[:, prompt_len:])
    want = ref.logits(shape, params, tokens)[:, prompt_len - 1:-1]
    assert error(logits, want) < PARITY


def test_prefill_in_groups_and_in_one_pass_agree():
    _ref, _shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 6, 8)
    one_pass = dataclasses.replace(cfg, prefill_tokens=0)
    whole = decoded(one_pass, mesh, params, prompts)
    for tokens_a_pass, groups in ((48, 1), (16, 3), (8, 6), (20, 3)):
        sliced = dataclasses.replace(cfg, prefill_tokens=tokens_a_pass)
        assert 6 // _prefill_group(6, 8, tokens_a_pass) == groups
        tokens, logits = decoded(sliced, mesh, params, prompts)
        np.testing.assert_array_equal(tokens, whole[0])
        assert error(logits, whole[1]) < 1e-5


def test_heads_over_tp_and_the_index_whole_on_every_rank():
    """Over ``tp`` the query and K/V heads split and every rank computes the
    index whole: the same tokens, the same logits."""
    _ref, _shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 2, 10)
    tokens, logits = decoded(cfg, mesh, params, prompts)
    two = make_mesh({"dp": 1, "sp": 1, "tp": 2}, devices=jax.devices()[:2])
    split_tokens, split = decoded(cfg, two, tfm.shard_params(cfg, two, params),
                                  prompts)
    np.testing.assert_array_equal(split_tokens, tokens)
    assert error(split, logits) < PARITY


def test_an_index_is_refused_over_sp_and_beside_a_mixer():
    _ref, _shape, cfg, _mesh, params = tiny()
    mesh = make_mesh({"dp": 1, "sp": 2, "tp": 1}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="sp == 1 only"):
        jax.jit(tfm.make_loss_fn(cfg, mesh))(params,
                                             prompts_of(cfg, 2, cfg.seq))
    one = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="beside a hybrid block"):
        sparse_index.check_mesh(dataclasses.replace(cfg, hybrid=object()),
                                one)


# ---- the carry --------------------------------------------------------------

def test_the_index_keys_are_written_at_pos_and_nowhere_else():
    """One cached step of one layer: of the index keys' stack ``(L, B, width,
    Tmax)`` only ``(layer, :, :, pos)`` changes, to the key the
    whole-sequence path computes for that position; of the one stack of K
    and V only the row ``(layer, :, pos)``; and there is no second stack."""
    from ompi_tpu.models.block import block
    from ompi_tpu.mpi.device_comm import DeviceCommunicator

    _ref, _shape, cfg, mesh, params = tiny()
    comm = DeviceCommunicator(mesh, ("dp", "sp", "tp"))
    B, Tmax, layer, pos = 2, 10, 1, 6
    rng = np.random.default_rng(5)
    kv = (cfg.n_layers, B, Tmax, 2 * cfg.kv_heads, cfg.head_dim)
    kc = jnp.asarray(rng.normal(size=kv), jnp.float32)
    ic = jnp.asarray(rng.normal(size=(*kv[:2], cfg.index.head_dim, Tmax)),
                     jnp.float32)
    h = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    whole = tfm.EXPERT_LEAVES if hasattr(tfm, "EXPERT_LEAVES") else (
        "w1", "w2", "w3")
    lp = {k: params[k] if k in whole else params[k][layer]
          for k in tfm.layer_leaves(cfg)}

    def step(h, kc, ic):
        at = jnp.int32(pos)
        h, ((kc, ic),) = block(cfg, comm, lp, h, at[None],
                               carry=([[kc, ic]], jnp.int32(layer), at))
        return h, kc, ic

    _h, kc2, ic2 = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False))(h, kc, ic)
    changed = np.array(ic2 != ic)
    assert changed[layer, :, :, pos].all()
    changed[layer, :, :, pos] = False
    assert not changed.any()
    x = tfm._rmsnorm(h, lp["ln1"], cfg.norm_eps)
    _qi, ki, _wi = sparse_index.project(cfg, lp, x, jnp.asarray([pos]))
    np.testing.assert_allclose(ic2[layer, :, :, pos], ki[:, 0], rtol=1e-5,
                               atol=1e-6)
    moved = np.array(kc != kc2)
    assert moved[layer, :, pos].all()
    moved[layer, :, pos] = False
    assert not moved.any()


def _heavy_ops_on(text: str, dims: tuple) -> list[str]:
    """The dot_general and reduce instructions of a lowered program that
    have an operand of the type ``dims``."""
    shape = "tensor<" + "x".join(str(d) for d in dims) + "x"
    return [line.strip()[:160] for line in text.splitlines()
            if shape in line and re.search(
                r"stablehlo\.(dot_general|reduce)\b", line)]


def test_the_cached_step_reads_the_selection_and_not_the_whole_cache():
    """The lowered decoder at tiny sizes: no product and no reduction has an
    operand of a layer's whole K and V, (B, Tmax, 2 Hkv, hd), nor of either
    half of it; the stacked cache is read by one gather, of ``topk`` rows a
    sequence.  The same program without an index does multiply the layer's
    whole K and V."""
    _ref, _shape, cfg, mesh, params = tiny()
    B, Tp, new = 3, 13, 7
    half = (B, Tp + new, cfg.kv_heads, cfg.head_dim)
    layer = (B, Tp + new, 2 * cfg.kv_heads, cfg.head_dim)
    prompts = prompts_of(cfg, B, Tp)

    def lowered(cfg, params):
        return jax.jit(make_decoder(cfg, mesh, max_new=new)).lower(
            params, prompts).as_text()

    text = lowered(cfg, params)
    for dims in (layer, half):
        assert not _heavy_ops_on(text, dims), _heavy_ops_on(text, dims)
    stack = "tensor<" + "x".join(map(str, (cfg.n_layers, *layer))) + "x"
    gathers = [line for line in text.splitlines()
               if "stablehlo.gather" in line and stack in line]
    assert len(gathers) == 1, gathers
    picked = "tensor<" + "x".join(map(str, (
        B, cfg.index.topk, 2 * cfg.kv_heads, cfg.head_dim))) + "x"
    assert picked in gathers[0]
    dense = dataclasses.replace(cfg, index=None)
    plain = {k: v for k, v in params.items()
             if k not in sparse_index.leaf_names()}
    assert len(_heavy_ops_on(lowered(dense, plain), half)) >= 2


# ---- routing and norms ------------------------------------------------------

def test_renormalised_weights_sum_to_one():
    """With ``renorm`` a token's experts weigh one together; without it they
    weigh their probabilities as they are, well under one.  Both against the
    layer written out by hand, an assignment at a time."""
    rng = np.random.default_rng(2)
    D, E, F, k = 16, 8, 12, 3
    x = jnp.asarray(rng.normal(size=(2, 5, D)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(D, E)), jnp.float32) * 0.1
    w1 = jnp.asarray(rng.normal(size=(E, D, F)), jnp.float32) * 0.3
    w3 = jnp.asarray(rng.normal(size=(E, D, F)), jnp.float32) * 0.3
    w2 = jnp.asarray(rng.normal(size=(E, F, D)), jnp.float32) * 0.3
    params = {"wg": wg, "w1": w1, "w2": w2, "w3": w3}
    probs = jax.nn.softmax(x.reshape(-1, D) @ wg, axis=-1)
    gate, expert = lax.top_k(probs, k)

    def by_hand(weights):
        out = np.zeros((10, D), np.float32)
        xf = np.asarray(x).reshape(10, D)
        for t in range(10):
            for w, e in zip(np.asarray(weights[t]), np.asarray(expert[t])):
                hid = jax.nn.silu(xf[t] @ w1[e]) * (xf[t] @ w3[e])
                out[t] += w * np.asarray(hid @ w2[e])
        return out.reshape(2, 5, D)

    normed = gate / gate.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(normed.sum(-1)), 1.0, rtol=1e-6)
    assert float(gate.sum(-1).max()) < 0.7
    got = routed_moe(x, params, k, gated=True, renorm=True)
    np.testing.assert_allclose(got, by_hand(normed), rtol=2e-4, atol=2e-5)
    as_they_are = routed_moe(x, params, k, gated=True)
    np.testing.assert_allclose(as_they_are, by_hand(gate), rtol=2e-4,
                               atol=2e-5)


def test_the_index_scopes_are_in_the_vocabulary_and_in_both_programs():
    from ompi_tpu.core import scopes

    names = {"index_proj", "index.score", "index.select", "attention.gather"}
    assert names <= set(scopes.SCOPES)
    _ref, _shape, cfg, mesh, params = tiny()
    text = jax.jit(make_decoder(cfg, mesh, max_new=4)).lower(
        params, prompts_of(cfg, 2, 12)).compile().as_text()
    for root, scope in (("prefill", "index_proj"), ("prefill", "index.score"),
                        ("prefill", "index.select"),
                        ("decode.step", "index_proj"),
                        ("decode.step", "index.score"),
                        ("decode.step", "index.select"),
                        ("decode.step", "attention.gather")):
        assert re.search(rf"/{re.escape(root)}/[^\"]*layers/[^\"]*/"
                         rf"{re.escape(scope)}/", text), (root, scope)
    assert not re.search(r"/prefill/[^\"]*attention\.gather/", text)


# ---- the prefill's kernel ---------------------------------------------------

@pytest.mark.parametrize("sizes", [(2, 64, 200, 4, 2), (1, 32, 32, 8, 2),
                                   (2, 96, 1100, 4, 4)],
                         ids=["padded-keys", "one-block", "three-key-blocks"])
def test_the_masked_attention_kernel_equals_the_jnp_form(sizes):
    """``ops/masked_attention.py`` (in TPU interpret mode here) against the
    jnp form it stands in for, under a random mask that leaves some rows one
    key and blanks a run of keys for a whole sequence: float32 on both
    sides, so what is left is the order of the sums."""
    from ompi_tpu.ops.masked_attention import masked_attention, tiles

    B, Tq, Tk, H, Hkv = sizes
    rng = np.random.default_rng(Tk)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, heads, 128)), jnp.float32)
               for T, heads in ((Tq, H), (Tk, Hkv), (Tk, Hkv)))
    mask = rng.random((B, Tq, Tk)) < 0.3
    mask[0, :, Tk // 2:] = False
    mask[:, :, 0] = True
    mask[-1, 5, 1:] = False
    got = masked_attention(q, k, v, jnp.asarray(mask))
    want = sparse_index._grouped_attention(q, k, v, jnp.asarray(mask))
    assert got.shape == (B, Tq, H, 128) and error(got, want) < 1e-5
    np.testing.assert_allclose(got[-1, 5], jnp.repeat(v[-1, 0], H // Hkv, 0),
                               rtol=1e-6)
    assert tiles(384, 128) and not tiles(384, 64) and not tiles(20, 128)
    with pytest.raises(ValueError, match="do not tile"):
        masked_attention(q[:, :20], k, v, jnp.asarray(mask[:, :20]))


def test_the_prefill_takes_the_kernel_where_it_is_asked_and_tiles(
        monkeypatch):
    """``attend`` with ``kernel``: slices that tile go through the kernel,
    the same context and index keys come back; at the tiny head width
    nothing tiles and the jnp form answers."""
    from ompi_tpu.ops import masked_attention as kernel_module

    calls = []
    kernel = kernel_module.masked_attention

    def counted(q, k, v, mask):
        calls.append(q.shape[1])
        return kernel(q, k, v, mask)

    monkeypatch.setattr(kernel_module, "masked_attention", counted)
    _ref, _shape, cfg, _mesh, params = tiny()
    wide = dataclasses.replace(cfg, head_width=128, index=dataclasses.replace(
        cfg.index, q_slice=32, topk=24))
    rng = np.random.default_rng(7)
    B, T = 2, 64
    lp = {k: params[k][0] for k in sparse_index.leaf_names()}
    x = jnp.asarray(rng.normal(size=(B, T, cfg.d_model)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, T, 4, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, T, 2, 128)), jnp.float32)
            for _ in "kv")
    at = jnp.arange(T)
    want, keys = sparse_index.attend(wide, lp, x, q, k, v, at)
    assert not calls
    got, same = sparse_index.attend(wide, lp, x, q, k, v, at, kernel=True)
    assert calls == [32, 32] and error(got, want) < 1e-5
    np.testing.assert_array_equal(same, keys)
    sparse_index.attend(cfg, lp, x, q[..., :64], k[..., :64], v[..., :64],
                        at, kernel=True)
    assert calls == [32, 32]
