"""The block-selected mixer (``models/block_select.py``) against the plain
reference, ``benchmarks/reference/minicpm_sala.py``, at the configuration's
tiny sizes, float32, seeded, on the CPU: the pooled keys, the selected set at
every position of a sequence that crosses ``dense_len``, a kernel's
completion and a block's, in the whole-sequence path and in the cached step,
the pooled keys a step writes, prefill then cached steps against the
reference's full forward, the same tokens whatever ``prefill_tokens``, and
the gradient through both of the plan's new mixers; the whole-sequence
path's scan over slices of one shape (with and without a tail, gradient
included), ``masked_attention`` stopped at a key length, and what a prefill
lowers to for a described v5e: one or two kernel calls whatever the number
of slices.  Agreement only: nothing here is a time."""

import copy
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import block_select
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder

CELL = "minicpm-sala.decode-16k-512-b24"
PARITY = 1e-4

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


def qkv(seed, T, B=2):
    """q (B, T, H, hd) at the gain the seeded queries have, k, v (B, T, Hkv,
    hd)."""
    _, s, *_ = tiny()
    rng = np.random.default_rng(seed)
    q = 3 * rng.normal(size=(B, T, s.n_heads, s.head_dim))
    k, v = (rng.normal(size=(B, T, s.n_kv_heads, s.head_dim))
            for _ in range(2))
    return (jnp.asarray(a, jnp.float32) for a in (q, k, v))


def test_the_tiny_sizes_keep_the_published_ratios():
    _, s, cfg, *_ = tiny()
    bs = cfg.plan.block_select
    assert (bs.kernel, bs.stride, bs.block) == (4, 2, 8)
    assert bs.kernel == 2 * bs.stride and bs.block == 4 * bs.stride
    # the forced blocks are about half of a query's: the window's and one
    assert bs.topk == 2 * (bs.window // bs.block + bs.init_blocks)
    assert bs.dense_len > bs.window and s.n_heads // s.n_kv_heads > 1


@pytest.mark.parametrize("T", [3, 4, 5, 31, 100])
def test_the_pooled_keys_are_the_kernels_means(T):
    ref, s, cfg, *_ = tiny()
    bs = cfg.plan.block_select
    _q, k, _v = qkv(T, T)
    got = block_select.pool_keys(bs, k.swapaxes(1, 2))
    want = ref.pooled_keys(s, k).swapaxes(1, 2)
    assert got.shape == want.shape == (2, s.n_kv_heads,
                                       block_select.pooled_count(bs, T),
                                       s.head_dim)
    if got.size:
        assert error(got, want) < PARITY


def reference_sets(T, seed=5):
    """The reference's ``B_t`` at every position of a sequence of T, (B, Hkv,
    T, blocks), and what they were made from."""
    ref, s, *_ = tiny()
    q, k, v = qkv(seed, T)
    want = ref.selected_blocks(s, q, ref.pooled_keys(s, k), jnp.arange(T),
                               -(-T // s.block))
    return q, k, v, np.asarray(want)


def test_the_selection_is_the_references_at_every_position():
    """A sequence of 100: past ``dense_len`` (24), 49 kernels' completions
    (every 2nd position from 3) and 12 blocks' (every 8th)."""
    _, s, cfg, *_ = tiny()
    bs, T = cfg.plan.block_select, 100
    q, k, _v, want = reference_sets(T)
    blocks = -(-T // bs.block)
    pooled = block_select.pool_keys(bs, k.swapaxes(1, 2))
    qh = jnp.moveaxis(q.reshape(2, T, s.n_kv_heads, -1, s.head_dim), 1, 3)
    t = jnp.arange(T)
    got = np.asarray(block_select.chosen(
        bs, block_select.block_scores(bs, qh, pooled, t, blocks), t))
    assert got.shape == want.shape == (2, s.n_kv_heads, T, blocks)
    assert (got == want).all()
    live = np.minimum(np.arange(T) // bs.block + 1, bs.topk)
    assert (got.sum(-1) == live[None, None]).all()
    # the forced blocks are in it; beyond them the two heads' sets differ
    assert got[..., 0].all()
    assert all(got[..., p, p // bs.block].all() for p in range(T))
    assert (got[:, 0] != got[:, 1]).any()
    # and a query past the first topk blocks leaves live blocks out
    assert (got[..., -1, :].sum(-1) < blocks).all()


def test_whole_sequences_attend_under_the_references_sets():
    ref, s, cfg, *_ = tiny()
    bs, T = cfg.plan.block_select, 100
    q, k, v, sets = reference_sets(T)
    got, rows, pooled = jax.jit(
        lambda q, k, v: block_select.attend(bs, q, k, v))(q, k, v)
    mask = (np.repeat(sets, bs.block, -1)[..., :T]
            & (np.arange(T) <= np.arange(T)[:, None]))      # (B, g, T, T)
    qg = np.asarray(q).reshape(2, T, s.n_kv_heads, -1, s.head_dim)
    sc = np.einsum("bqgrd,bkgd->bgrqk", qg, np.asarray(k)) * s.head_dim ** -.5
    w = np.where(mask[:, :, None], np.exp(sc - sc.max(-1, keepdims=True)), 0)
    want = np.einsum("bgrqk,bkgd->bqgrd", w / w.sum(-1, keepdims=True),
                     np.asarray(v)).reshape(2, T, s.n_heads, s.head_dim)
    assert error(got, want) < PARITY
    assert rows.shape == (2, s.n_kv_heads, T, 2 * s.head_dim)
    assert np.array_equal(rows[..., :s.head_dim], k.swapaxes(1, 2))
    assert np.array_equal(rows[..., s.head_dim:], v.swapaxes(1, 2))
    assert pooled.shape[2] == block_select.pooled_count(bs, T)


def _under(sets, bs, q, k, v):
    """Attention of q (B, T, H, hd) over k, v (B, T, Hkv, hd) at the earlier
    positions of the blocks ``sets`` (B, Hkv, T, blocks) holds, in
    ``jax.numpy``, every query against every key at once."""
    B, T, H, hd = q.shape
    mask = (np.repeat(sets, bs.block, -1)[..., :T]
            & (np.arange(T) <= np.arange(T)[:, None]))      # (B, g, T, T)
    qg = q.reshape(B, T, k.shape[2], -1, hd)
    sc = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) * hd ** -.5
    w = jax.nn.softmax(jnp.where(mask[:, :, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", w, v).reshape(B, T, H, hd)


@pytest.mark.parametrize("T", [96, 100])
def test_slices_of_one_shape_attend_under_the_references_sets(T):
    """Slices of 16 queries over a sequence of 96 or 100: past ``dense_len``
    (24), so it selects; three slices within the first ``topk`` blocks (48
    positions), which score nothing, then three that select, each kind one
    scan over one shape; and at 100 a tail of 4 beside them.  The context
    and its gradient (the trainer's ``jax.numpy`` path) are those of the
    reference's sets, a position at a time."""
    _, s, cfg, *_ = tiny()
    bs = dataclasses.replace(cfg.plan.block_select, q_slice=16)
    assert bs.dense_len < bs.topk * bs.block < T - T % bs.q_slice
    q, k, v, sets = reference_sets(T)
    weight = jnp.asarray(np.random.default_rng(3).normal(size=q.shape),
                         jnp.float32)

    @jax.jit
    def both(q, k, v):
        def ours(q, k, v):
            o, *_ = block_select.attend(bs, q, k, v)
            return (o * weight).sum(), o

        def theirs(q, k, v):
            o = _under(sets, bs, q, k, v)
            return (o * weight).sum(), o

        return [jax.grad(f, (0, 1, 2), has_aux=True)(q, k, v)
                for f in (ours, theirs)]

    (got_grads, got), (want_grads, want) = both(q, k, v)
    assert error(got, want) < PARITY
    for mine, its in zip(got_grads, want_grads):
        assert error(mine, its) < PARITY
        assert float(jnp.abs(mine).max()) > 0


@pytest.mark.parametrize("n", [40, 512, 700, 1280])
def test_masked_attention_stops_at_the_key_length_it_is_handed(n):
    """1280 keys are three blocks of 512 with the padding: a length inside
    the first block, on a block's edge, inside the second and the whole.
    The mask allows every key past the length (junk there, which the call
    without a length reads): the call with ``k_len`` (traced) reads what the
    call on the first ``n`` keys reads."""
    from ompi_tpu.ops.masked_attention import masked_attention

    rng = np.random.default_rng(n)
    B, Tq, Tk, H, Hkv, hd = 1, 32, 1280, 4, 2, 128
    q = jnp.asarray(rng.normal(size=(B, Tq, H, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, Tk, Hkv, hd)), jnp.float32)
            for _ in range(2))
    mask = rng.random(size=(B, Tq, Tk)) < 0.3
    mask[..., 0] = True             # every query has a key it may see
    mask[..., n:] = True
    mask = jnp.asarray(mask)

    @jax.jit
    def both(q, k, v, mask, k_len):
        return (masked_attention(q, k, v, mask, k_len),
                masked_attention(q, k[:, :n], v[:, :n], mask[..., :n]),
                masked_attention(q, k, v, mask))

    got, want, junk = both(q, k, v, mask, jnp.int32(n))
    assert error(got, want) < 1e-6
    assert n == Tk or error(junk, want) > 1e-2


def test_a_short_sequence_attends_densely():
    _, s, cfg, *_ = tiny()
    bs = cfg.plan.block_select
    T = bs.dense_len
    q, k, v = qkv(2, T)
    narrow = dataclasses.replace(bs, topk=1, dense_len=T)
    got, *_ = block_select.attend(narrow, q, k, v)
    wide, *_ = block_select.attend(
        dataclasses.replace(bs, topk=99, dense_len=0), q, k, v)
    assert error(got, wide) < PARITY
    sparse, *_ = block_select.attend(
        dataclasses.replace(narrow, dense_len=T - 1), q, k, v)
    assert error(sparse, wide) > 0.1


def test_a_cached_step_selects_writes_and_attends_as_the_reference():
    """Steps from position 60 to 99 against buffers of 104 positions: at
    every one the set is the reference's, the pooled keys are those of the
    keys so far and nothing else is written, and the context is that of the
    whole-sequence path."""
    _, s, cfg, *_ = tiny()
    bs, T, start, t_max = cfg.plan.block_select, 100, 60, 104
    q, k, v, sets = reference_sets(T)
    whole, rows_all, pooled_all = block_select.attend(bs, q, k, v)
    (rows_shape, *_), (pooled_shape, *_) = block_select.buffers(
        cfg, bs, 2, t_max)
    rows = jnp.zeros(rows_shape).at[:, :, :T].set(rows_all)
    rows = rows.at[:, :, start:].set(0)
    n0 = block_select.pooled_count(bs, start)
    pooled = jnp.zeros(pooled_shape).at[:, :, :n0].set(pooled_all[:, :, :n0])
    seen = []
    chosen = block_select.chosen
    block_select.chosen = lambda bs, scores, t: seen.append(
        chosen(bs, scores, t)) or seen[-1]

    def step(rows, pooled, pos):
        rows = jax.lax.dynamic_update_slice(
            rows, jax.lax.dynamic_slice_in_dim(rows_all, pos, 1, axis=2),
            (0, 0, pos, 0))
        pooled = block_select.written_pooled(bs, rows, pooled, pos)
        o = block_select.attend_cached(
            bs, jax.lax.dynamic_slice_in_dim(q, pos, 1, axis=1), rows, pooled,
            pos)
        return rows, pooled, o

    try:
        for pos in range(start, T):
            before = np.asarray(pooled)
            rows, pooled, o = step(rows, pooled, jnp.int32(pos))
            n = block_select.pooled_count(bs, pos + 1)
            assert error(pooled[:, :, :n], pooled_all[:, :, :n]) < PARITY
            changed = np.any(np.asarray(pooled) != before, axis=(0, 1, 3))
            done = (pos + 1 - bs.kernel) % bs.stride == 0
            assert changed.sum() == done and (not done or changed[n - 1])
            got = np.asarray(seen[-1])[:, :, 0, :sets.shape[-1]]
            assert (got == sets[:, :, pos]).all(), pos
            assert not np.asarray(seen[-1])[..., sets.shape[-1]:].any()
            assert error(o[:, 0], whole[:, pos]) < PARITY, pos
    finally:
        block_select.chosen = chosen


def decoded(cfg, mesh, params, prompts, max_new, **kw):
    tokens, kept = make_decoder(cfg, mesh, max_new=max_new,
                                keep_logits=len(prompts), **kw)(params,
                                                                prompts)
    return np.asarray(tokens), np.asarray(kept)


def test_prefill_then_cached_steps_are_the_references_full_forward():
    """Prompts of 37 and 24 new tokens: the cache passes ``dense_len`` (24),
    so every step selects; the prefill's slices (a ``q_slice`` of 16) cross
    it too."""
    ref, s, cfg, mesh, params = tiny()
    cfg = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, block_select=dataclasses.replace(cfg.plan.block_select,
                                                   q_slice=16)))
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab, size=(4, 37)).astype(np.int32)
    tokens, kept = decoded(cfg, mesh, params, prompts, 24)
    want = np.asarray(ref.logits(s, params, tokens)[:, 36:-1])
    assert error(kept, want) < PARITY
    assert np.array_equal(tokens[:, 37:], want.argmax(-1))
    for prefill_tokens in (37, 74):     # a sequence a pass, two a pass
        again, _ = decoded(dataclasses.replace(
            cfg, prefill_tokens=prefill_tokens), mesh, params, prompts, 24)
        assert np.array_equal(again, tokens)
    one, first = decoded(cfg, mesh, params, prompts, 1)
    assert np.array_equal(one, tokens[:, :38])
    assert np.array_equal(first[:, 0], kept[:, 0])


def test_the_carry_has_two_growing_buffers_of_different_lengths():
    from ompi_tpu.models import plan

    _, s, cfg, mesh, _ = tiny()
    bs = cfg.plan.block_select
    carry = plan.carry(cfg, mesh, 3, 61)
    assert [b.shape for b in carry] == [
        (1, 3, s.n_kv_heads, 61, 2 * s.head_dim),
        (1, 3, s.n_kv_heads, 29, s.head_dim),
        *[(1, 3, s.lt_heads, s.lt_head_dim, s.lt_head_dim)] * 3]
    assert plan.grows(cfg) == (True, True, False, False, False)
    shorter = plan.carry(cfg, mesh, 3, 37)
    assert shorter[1].shape[3] == block_select.pooled_count(bs, 37) == 17
    longer = plan.carried(cfg, mesh, iter(shorter), 61)
    assert [b.shape for b in longer] == [b.shape for b in carry]


def test_the_gradient_passes_both_mixers_and_not_the_selection():
    ref, s, cfg, mesh, params = tiny()
    cfg = dataclasses.replace(cfg, remat=None)
    tokens = np.random.default_rng(2).integers(
        0, cfg.vocab, size=(2, cfg.seq)).astype(np.int32)
    loss, grads = jax.jit(jax.value_and_grad(tfm.make_loss_fn(cfg, mesh)))(
        params, tokens)
    want, theirs = jax.value_and_grad(
        lambda p: ref.nll_sum(s, p, jnp.asarray(tokens)) / tokens[:, 1:].size
    )(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for leaf in params:
        assert error(grads[leaf], theirs[leaf]) < 1e-3, leaf
        assert float(jnp.abs(grads[leaf]).max()) > 0, leaf


def test_on_tpus_both_paths_take_the_kernels_and_read_the_same(monkeypatch):
    """Heads of 128 and a cache of one 1024-position block, told that the
    trace is for TPUs (the pallas kernels run in the suite's interpret mode):
    the prefill's slices go through ``masked_attention`` with the jnp form's
    backward pass behind it (three slices, two traced: the scan of the two
    within the first ``topk`` blocks and the scan of the one past them), a
    cached step through ``selected_attention``, a K/V head a sequence of its
    own under its own mask, and both read what the ``jax.numpy`` forms
    read."""
    from ompi_tpu.ops import _chip
    from ompi_tpu.models.block_select import BlockSelect
    from ompi_tpu.ops import masked_attention as masked
    from ompi_tpu.ops import selected_attention as selected

    _, _, cfg, *_ = tiny()
    cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=2, head_width=128)
    bs = BlockSelect(kernel=32, stride=16, block=64, topk=4, init_blocks=1,
                     window=64, dense_len=128, q_slice=128)
    T, t_max, hd = 384, 1024, 128
    rng = np.random.default_rng(9)
    q = jnp.asarray(3 * rng.normal(size=(1, T, 4, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, T, 2, hd)), jnp.float32)
            for _ in range(2))
    plain, rows, pooled = jax.jit(
        lambda q, k, v: block_select.attend(bs, q, k, v))(q, k, v)
    grad = jax.jit(jax.grad(lambda q: block_select.attend(
        bs, q, k, v)[0].sum()))
    want_grad = grad(q)
    (rows_shape, *_), (pooled_shape, *_) = block_select.buffers(
        cfg, bs, 1, t_max)
    carry = (jnp.zeros(rows_shape).at[:, :, :T].set(rows),
             jnp.zeros(pooled_shape).at[:, :, :pooled.shape[2]].set(pooled))

    def step(rows, pooled):
        return block_select.attend_cached(bs, q[:, -1:], rows, pooled,
                                          jnp.int32(T - 1))

    want_step = jax.jit(step)(*carry)
    assert error(want_step[:, 0], plain[:, -1]) < PARITY
    calls = []
    for module, name in ((masked, "masked_attention"),
                         (selected, "selected_attention")):
        real = getattr(module, name)
        monkeypatch.setattr(module, name, lambda *a, _f=real, _n=name:
                            calls.append(_n) or _f(*a))
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    got, *_ = jax.jit(
        lambda q, k, v: block_select.attend(bs, q, k, v))(q, k, v)
    assert calls == ["masked_attention"] * 2
    assert error(got, plain) < PARITY
    assert error(jax.jit(jax.grad(lambda q: block_select.attend(
        bs, q, k, v)[0].sum()))(q), want_grad) < PARITY
    got_step = jax.jit(lambda *a: step(*a))(*carry)     # traced anew
    assert calls[-1] == "selected_attention"
    assert error(got_step, want_step) < PARITY


def _lowered(fn, *args) -> str:
    """``fn``'s StableHLO for the devices its arguments are placed on, the
    kernels as the chip's compiler gets them (under ``for_the_chip``: not the
    suite's interpreter), with what moves with the checkout set aside: a
    kernel's serialized body holds the files' paths and lines."""
    return re.sub(r'backend_config = "[^"]*"', 'backend_config = ""',
                  jax.jit(fn).lower(*args).as_text())


@pytest.mark.parametrize("slices", [3, 9])
def test_a_prefill_lowers_one_or_two_kernel_calls_whatever_its_slices(
        chip, for_the_chip, slices):
    """A decoder's prefill over the tiny plan with heads of 128 (the kernel's
    lanes) and slices of 128 queries, lowered for the described chip and not
    compiled: three slices (two within the first ``topk`` blocks, one past
    them) and nine hold the same kernel calls, where a loop in python over
    the slices held one a slice."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
    config["head_dim"] = 128
    ref, cfg = program.reference(config), program.program_config(config)
    cfg = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, block_select=block_select.BlockSelect(
            kernel=32, stride=16, block=64, topk=4, init_blocks=1, window=64,
            dense_len=128, q_slice=128)))
    mesh = program.mesh(config, chip[:1])
    params = program.abstract_params(
        ref, config, program.param_shardings(config, cfg, mesh))
    prompt = jax.ShapeDtypeStruct((1, 128 * slices), np.int32,
                                  sharding=NamedSharding(mesh, P("dp", None)))
    text = _lowered(make_decoder(cfg, mesh, max_new=1), params, prompt)
    kernels = re.findall(r'kernel_name = "(\w+)"', text)
    assert 1 <= kernels.count("masked_attention") <= 2
    assert text.count("stablehlo.while") >= 2   # the two scans


def test_the_indexed_prefill_lowers_to_what_it_did(chip, for_the_chip):
    """``sparse_index.attend`` (cell 6's prefill) passes ``masked_attention``
    no length and unrolls its slices as it did: its text for the described
    chip is the one it lowered to before the kernel took a length (PR 66; a
    pinned sha256: a PR that changes that program on purpose replaces it)."""
    from jax.sharding import SingleDeviceSharding

    from ompi_tpu.models import sparse_index

    cfg = tfm.TransformerConfig(index=sparse_index.SparseIndex(
        n_heads=2, head_dim=32, topk=64, q_slice=128))
    on = SingleDeviceSharding(chip[0])
    lp = {name: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=on)
          for name, dims in (("wiq", (64, 64)), ("wik", (64, 32)),
                             ("wiw", (64, 2)), ("ikn", (32,)),
                             ("ikb", (32,)))}
    B, T = 2, 384
    x, q, k, v = (jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=on)
                  for dims in ((B, T, 64), (B, T, 4, 128), (B, T, 2, 128),
                               (B, T, 2, 128)))
    text = _lowered(lambda lp, x, q, k, v: sparse_index.attend(
        cfg, lp, x, q, k, v, jnp.arange(T), kernel=True), lp, x, q, k, v)
    assert re.findall(r'kernel_name = "(\w+)"', text) == [
        "masked_attention"] * 3
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "041507389508f99216e0d2374c7122aa05416fedd42ed1ce2f8556130f90397c")
