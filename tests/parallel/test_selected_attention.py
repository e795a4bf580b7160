"""The cached step's streaming read of its selection
(``ops/selected_attention.py``), in TPU interpret mode, against the read it
stands in for: ``lax.top_k``, a gather of the selected rows and
``sparse_index._attend_rows`` over them; the mask against the set of
``lax.top_k``'s indices; and the rule that says which read a program takes
(``sparse_index.streams``).  Agreement and control flow only: nothing here
is a time.

Both sides are float32 and differ in the order of their sums alone (a
running softmax a block at a time against one softmax over the gathered
rows): 1e-5 of the context's largest value; 3e-7 is read.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.models import sparse_index
from ompi_tpu.models.sparse_index import SparseIndex
from ompi_tpu.ops import selected_attention as kernel_module
from ompi_tpu.ops.selected_attention import selected_attention, tiles

BLOCK = kernel_module._BLOCK
D = 128


def _case(layers=3, batch=2, t_max=2 * BLOCK, kv_heads=2, group=4, seed=0,
          ties=False):
    """(q, the flat stack, index scores): float32, seeded.  With ``ties``
    the scores take eight values, so every threshold is tied many times."""
    rng = np.random.default_rng(seed)
    kv = rng.normal(size=(layers, batch, t_max, 2 * kv_heads * D))
    q = rng.normal(size=(batch, 1, kv_heads * group, D))
    found = rng.normal(size=(batch, t_max))
    if ties:
        found = np.round(found * 2)
    return (jnp.asarray(q, jnp.float32), jnp.asarray(kv, jnp.float32),
            jnp.asarray(found, jnp.float32))


def _by_gather(q, kv, found, layer, pos, topk):
    """What ``attend_cached`` does over a 5-D carry: (context, the selected
    positions as a mask)."""
    batch, t_max = found.shape
    found = jnp.where(jnp.arange(t_max) <= pos, found, -jnp.inf)
    best, chosen = lax.top_k(found, topk)
    picked = kv[layer, jnp.arange(batch)[:, None], chosen]
    picked = picked.reshape(batch, topk, -1, D)
    allowed = best > -jnp.inf
    mask = np.zeros((batch, t_max), bool)
    for b in range(batch):
        mask[b, np.asarray(chosen[b])[np.asarray(allowed[b])]] = True
    return sparse_index._attend_rows(q, picked, allowed), mask


def _close(got, want):
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("layer", [0, 2], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("pos", [BLOCK + BLOCK // 2 + 3, 2 * BLOCK - 1],
                         ids=["pos-inside-a-block", "pos-at-the-end"])
def test_the_stream_equals_the_gather_of_the_same_set(layer, pos):
    q, kv, found = _case()
    topk = BLOCK // 2
    mask = sparse_index.select(found, jnp.arange(found.shape[1]) <= pos, topk)
    want, chosen = _by_gather(q, kv, found, layer, pos, topk)
    np.testing.assert_array_equal(np.asarray(mask), chosen)
    _close(jax.jit(selected_attention)(q, kv, mask, jnp.int32(layer)), want)


def test_ties_at_the_threshold_give_top_ks_set_and_its_context():
    q, kv, found = _case(seed=1, ties=True)
    pos, topk = 2 * BLOCK - 7, BLOCK // 4
    mask = sparse_index.select(found, jnp.arange(found.shape[1]) <= pos, topk)
    want, chosen = _by_gather(q, kv, found, 1, pos, topk)
    assert (np.asarray(mask).sum(axis=1) == topk).all()
    np.testing.assert_array_equal(np.asarray(mask), chosen)
    _close(selected_attention(q, kv, mask, jnp.int32(1)), want)


def test_fewer_live_positions_than_topk_are_all_selected():
    q, kv, found = _case(seed=2)
    pos, topk = BLOCK // 8, BLOCK // 2
    mask = sparse_index.select(found, jnp.arange(found.shape[1]) <= pos, topk)
    want, chosen = _by_gather(q, kv, found, 0, pos, topk)
    assert (np.asarray(mask).sum(axis=1) == pos + 1).all()
    np.testing.assert_array_equal(np.asarray(mask), chosen)
    _close(selected_attention(q, kv, mask, jnp.int32(0)), want)


def test_the_heads_of_one_tp_rank():
    """Half of cell 6's heads: 2 K/V heads of 8 query heads each, rows of
    512."""
    q, kv, found = _case(layers=2, batch=1, t_max=BLOCK, kv_heads=2, group=8,
                         seed=3)
    pos, topk = BLOCK - 2, BLOCK // 4
    mask = sparse_index.select(found, jnp.arange(BLOCK) <= pos, topk)
    want, _chosen = _by_gather(q, kv, found, 1, pos, topk)
    _close(selected_attention(q, kv, mask, jnp.int32(1)), want)


def test_a_sequence_whose_mask_allows_nothing_reads_zero():
    q, kv, found = _case(layers=1, batch=2, t_max=BLOCK, seed=4)
    mask = jnp.zeros(found.shape, bool).at[1, 5].set(True)
    got = np.asarray(selected_attention(q, kv, mask, jnp.int32(0)))
    assert not got[0].any()
    want = np.asarray(kv[0, 1, 5]).reshape(2, 2, D)[1]      # its V heads
    np.testing.assert_allclose(got[1, 0].reshape(2, 4, D),
                               np.repeat(want[:, None], 4, axis=1), rtol=1e-6)


def test_sizes_that_do_not_tile_are_refused():
    assert tiles(8192, 128) and tiles(BLOCK, 256)
    assert not tiles(8192, 64) and not tiles(BLOCK + 128, 128)
    assert not tiles(8065, 128)
    q, kv, found = _case(layers=1, batch=1, t_max=BLOCK)
    with pytest.raises(ValueError, match="do not tile"):
        selected_attention(q, kv[:, :, :BLOCK - 8], found[:, :BLOCK - 8] > 0,
                           jnp.int32(0))


# ---- the rule ---------------------------------------------------------------

def _index(topk):
    return SparseIndex(n_heads=16, head_dim=64, topk=topk, q_slice=512)


def test_cell_6_streams():
    assert sparse_index.streams(_index(2048), 8064 + 128, 128, tpu=True)


@pytest.mark.parametrize("topk,t_max,head_dim,tpu,why", [
    (2048, 8192, 128, False, "a mesh that is not of TPUs"),
    (2048, 8065, 128, True, "the max_new=1 program: no whole blocks"),
    (2048, 8192, 64, True, "heads of half a lane tile"),
    (512, 8192, 128, True, "a cache of 16 selections"),
    (2048, 32768, 128, True, "a long cache"),
    (2048, 2048, 128, True, "a cache within its selection: every row"),
    (1 << 30, 8192, 128, True, "the control that drops the selection"),
    (4, 16, 64, True, "test_sparse_index's tiny sizes"),
    (4, 20, 64, True, "test_sparse_index's tiny sizes"),
    (4, 24, 64, False, "test_sparse_index's tiny sizes"),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else None)
def test_everything_else_gathers(topk, t_max, head_dim, tpu, why):
    assert not sparse_index.streams(_index(topk), t_max, head_dim, tpu), why


def test_the_crossover_is_the_constants():
    up_to = sparse_index._STREAM_UP_TO
    assert sparse_index.streams(_index(1024), up_to * 1024, 128, True)
    assert not sparse_index.streams(_index(1024), (up_to + 1) * 1024, 128,
                                    True)


def test_every_tiny_configuration_of_the_benchmark_gathers():
    """The ``tiny`` sizes of the one configuration with an index, at every
    cache length up to a few blocks, on a TPU or off it."""
    from benchmarks.lib import cells, program

    config = program.tiny(cells.resolve(
        "keye-vl-2.0-30b-a3b.decode-8k-128-b64").config)
    cfg = program.program_config(config)
    assert cfg.index is not None
    for t_max in range(1, 4 * BLOCK + 1):
        assert not sparse_index.streams(cfg.index, t_max, cfg.head_dim, True)


# ---- the step ---------------------------------------------------------------

def test_a_flat_carry_is_streamed_and_a_5d_carry_gathered():
    """``attend_cached`` over both carries of the same rows: the same
    context, the kernel under ``attention`` and nothing under
    ``attention.gather`` over the flat one, the gather over the other."""
    import dataclasses

    from ompi_tpu.models.transformer import TransformerConfig

    q, kv, _found = _case(layers=2, batch=2, t_max=BLOCK, seed=5)
    ix = SparseIndex(n_heads=2, head_dim=16, topk=BLOCK // 4, q_slice=32)
    cfg = dataclasses.replace(TransformerConfig(), index=ix)
    rng = np.random.default_rng(6)
    ic = jnp.asarray(rng.normal(size=(2, 2, 16, BLOCK)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(2, 1, 2, 16)), jnp.float32)
    wi = jnp.asarray(rng.uniform(0.5, 1.5, size=(2, 1, 2)), jnp.float32)
    layer, pos = jnp.int32(1), jnp.int32(BLOCK - 9)

    def step(carry):
        return sparse_index.attend_cached(cfg, q, carry, ic, qi, wi, layer,
                                          pos)

    five = kv.reshape(*kv.shape[:3], 4, D)
    _close(jax.jit(step)(kv), jax.jit(step)(five))
    flat_text = jax.jit(step).lower(kv).as_text(debug_info=True)
    five_text = jax.jit(step).lower(five).as_text(debug_info=True)
    # the operation, not the name: a jaxpr cached by an earlier test of the
    # worker brings that test's name into the locations
    for of_the_gather in ("stablehlo.gather", "attention.gather",
                          "chlo.top_k"):
        assert of_the_gather in five_text, of_the_gather
        assert of_the_gather not in flat_text, of_the_gather


@pytest.mark.parametrize("prefill_tokens", [0, 1000],
                         ids=["one-pass", "a-sequence-a-group"])
def test_a_decoder_that_streams_gives_the_gathering_decoders_logits(
        monkeypatch, prefill_tokens):
    """Prefill, then 23 cached steps, float32, one CPU device: the decoder
    as it is built here (a 5-D carry, the gather) against the one a mesh of
    TPUs would get (the rule is told so: a flat carry, written by the prefill
    in one pass or a group at a time and by every step, and streamed)."""
    import dataclasses

    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.models.decode import make_decoder
    from ompi_tpu.parallel.mesh import make_mesh

    cfg = dataclasses.replace(
        tfm.TransformerConfig(), vocab=64, d_model=64, n_heads=4,
        n_kv_heads=2, head_width=D, n_layers=2, d_ff=64, seq=BLOCK,
        compute_dtype="float32", param_dtype="float32", remat=None,
        tie_head=False, qk_norm="head", rope_theta=1e4,
        prefill_tokens=prefill_tokens,
        index=SparseIndex(n_heads=2, head_dim=16, topk=BLOCK // 4,
                          q_slice=BLOCK // 4))
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    params = tfm.init_params(cfg, seed=3)
    prompts = np.random.default_rng(4).integers(
        0, cfg.vocab, size=(2, BLOCK - 24)).astype(np.int32)

    def decoded():
        tokens, logits = make_decoder(cfg, mesh, max_new=24, keep_logits=2)(
            params, prompts)
        return np.asarray(tokens), np.asarray(logits)

    tokens, logits = decoded()
    rule, asked = sparse_index.streams, []

    def on_a_tpu(ix, t_max, head_dim, tpu):
        asked.append(rule(ix, t_max, head_dim, True))
        return asked[-1]

    monkeypatch.setattr(sparse_index, "streams", on_a_tpu)
    streamed_tokens, streamed_logits = decoded()
    assert asked and all(asked)
    np.testing.assert_array_equal(streamed_tokens, tokens)
    assert np.abs(streamed_logits - logits).max() < 1e-4 * logits.std()
