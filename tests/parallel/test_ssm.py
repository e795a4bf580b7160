"""The hybrid block (``models/ssm.py``: a Mamba-2 mixer beside grouped-query
attention) against its plain reference, ``benchmarks/reference/falcon_h1.py``,
at the configuration's tiny sizes, float32, seeded, on the CPU: the chunked
scan against the recurrence, prefill then cached steps against the full
forward on logits, loss and gradient of the train path, which K/V head a
query head reads, every multiplier, the prefill in groups, and the layouts
that are refused; and the scan as one kernel (``ops/ssm_scan.py``) where
``ssm.fused`` says so: a decoder whose prompts are two chunks of widths that
tile against the same reference, and, traced for a v5e that is described and
not attached, which of the two cells' programs call it (cell 12's prefill six
times; cell 5's never, and they are the text they were before the kernel
came).  Agreement and control flow only: nothing here is a time.
(Where the compiled step keeps its state is asked of the chip's compiler, in
``test_decode.py`` beside the same question of the K/V cache.)
"""

import copy
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import ssm
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import _prefill_group, make_decoder
from ompi_tpu.parallel.mesh import make_mesh
from tests.parallel.compiled import _cell, _pallas_calls, _program

CELL = "falcon-h1-34b.decode-128-64-b192"
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


# ---- the scan ----------------------------------------------------------------

@pytest.mark.parametrize("length", [12, 10, 3, 4],
                         ids=["three-chunks", "no-multiple", "under-a-chunk",
                              "one-chunk"])
def test_chunked_scan_equals_the_recurrence(length):
    ref, *_ = tiny()
    rng = np.random.default_rng(length)
    B, H, P, G, N = 2, 6, 4, 2, 5
    x = jnp.asarray(rng.normal(size=(B, length, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 1.5, size=(B, length, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.1, 3.0, size=H), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(B, length, G, N)), jnp.float32)
            for _ in range(2))
    y, last = ssm.chunked_scan(x, dt, a, b, c, chunk=4)
    want_y, want_last = ref.recurrence(
        x, dt, a, *(jnp.repeat(t, H // G, axis=2) for t in (b, c)))
    assert y.shape == (B, length, H, P) and last.shape == (B, H, P, N)
    assert error(y, want_y) < 1e-5 and error(last, want_last) < 1e-5


# ---- prefill, then cached steps ------------------------------------------------

def decoded(cfg, mesh, params, prompts, max_new=6, **kwargs):
    tokens, logits = make_decoder(cfg, mesh, max_new=max_new,
                                  keep_logits=prompts.shape[0],
                                  **kwargs)(params, prompts)
    return np.asarray(tokens), np.asarray(logits)


def prompts_of(cfg, batch, length, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


@pytest.mark.parametrize("prompt_len", [12, 9, 2],
                         ids=["three-chunks", "no-multiple", "under-the-taps"])
def test_prefill_then_cached_steps_give_the_references_logits(prompt_len):
    """K/V, the convolution's inputs and the heads' states handed over by the
    prefill and carried by the cached steps: the logits every generated token
    was picked from are the reference's full forward over prompt plus
    continuation, at every generated position."""
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
    assert cfg.prefill_tokens >= 6 * 8      # as the file has it: one group
    whole = decoded(cfg, mesh, params, prompts)
    for tokens_a_pass, groups in ((16, 3), (8, 6), (20, 3)):
        sliced = dataclasses.replace(cfg, prefill_tokens=tokens_a_pass)
        assert 6 // _prefill_group(6, 8, tokens_a_pass) == groups
        tokens, logits = decoded(sliced, mesh, params, prompts)
        np.testing.assert_array_equal(tokens, whole[0])
        assert error(logits, whole[1]) < 1e-5


def test_sampling_hands_back_what_it_sampled_from():
    _ref, _shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 2, 8)
    decode = make_decoder(cfg, mesh, max_new=4, temperature=0.7,
                          keep_logits=1)
    tokens, logits = decode(params, prompts, np.int32(3))
    greedy = decoded(cfg, mesh, params, prompts, max_new=4)[1]
    assert logits.shape == (1, 4, cfg.vocab)
    # the first token's logits are the prefill's, whatever is then sampled
    assert error(logits[:, 0], greedy[:1, 0]) < 1e-5
    assert np.asarray(tokens).shape == (2, 12)


# ---- the train path ------------------------------------------------------------

def test_loss_and_gradient_equal_the_references():
    """``make_loss_fn`` and ``jax.grad`` of it, which is what
    ``make_train_step`` differentiates, against the reference's loss and
    ``jax.grad`` of that: through the chunked scan's backward pass."""
    ref, shape, cfg, mesh, params = tiny()
    tokens = prompts_of(cfg, 2, cfg.seq, seed=2)
    loss_fn = jax.jit(jax.value_and_grad(tfm.make_loss_fn(cfg, mesh)))
    loss, grads = loss_fn(params, tokens)
    positions = tokens.shape[0] * (tokens.shape[1] - 1)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.nll_sum(shape, p, tokens) / positions)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert set(grads) == set(want_grads) == set(params)
    for leaf in sorted(params):
        scale = float(np.abs(np.asarray(want_grads[leaf])).max())
        assert scale > 0, leaf          # every leaf is in the loss
        assert float(np.abs(np.asarray(grads[leaf])
                            - np.asarray(want_grads[leaf])).max()
                     ) < 1e-3 * scale, leaf


def test_a_train_step_moves_every_leaf():
    _ref, _shape, cfg, mesh, params = tiny()
    step, init = tfm.make_train_step(cfg, mesh)
    before = {k: np.asarray(v) for k, v in params.items()}
    placed = tfm.shard_params(cfg, mesh, before)
    after, _state, loss = step(placed, init(before),
                               prompts_of(cfg, 2, cfg.seq, seed=3))
    assert np.isfinite(float(loss))
    assert all(not np.array_equal(before[k], np.asarray(after[k]))
               for k in before)


# ---- grouped K/V heads ---------------------------------------------------------

@pytest.mark.parametrize("kv_head,reads", [(0, True), (1, False)])
def test_a_query_head_reads_its_own_kv_head(kv_head, reads):
    """4 query heads over 2 K/V heads: query head 1 reads K/V head 0.  With
    ``wo`` zero but for query head 1's rows and ``wv`` zero but for one K/V
    head's columns, attention adds something exactly where that head is 0:
    under the other mapping (head h reads h mod 2) it would be head 1."""
    _ref, _shape, cfg, mesh, params = tiny()
    hd = cfg.head_dim
    assert (cfg.n_heads, cfg.kv_heads) == (4, 2)
    wo = np.zeros_like(params["wo"])
    wo[:, hd:2 * hd] = np.asarray(params["wo"])[:, hd:2 * hd]
    wv = np.zeros_like(params["wv"])
    at = slice(kv_head * hd, (kv_head + 1) * hd)
    wv[:, :, at] = np.asarray(params["wv"])[:, :, at]
    prompts = prompts_of(cfg, 2, 8)
    without = decoded(cfg, mesh, {**params, "wo": jnp.zeros_like(
        params["wo"])}, prompts)[1]
    got = decoded(cfg, mesh, {**params, "wo": jnp.asarray(wo),
                              "wv": jnp.asarray(wv)}, prompts)[1]
    # the first position is the prefill's attention, the rest the cached step's
    for positions in (slice(0, 1), slice(1, None)):
        moved = error(got[:, positions], without[:, positions])
        assert (moved > 1e-3) == reads, (positions, moved)


# ---- the multipliers -----------------------------------------------------------

MULTIPLIERS = ([(f.name, None) for f in dataclasses.fields(ssm.HybridBlock)
                if f.name.endswith("_multiplier")]
               + [("ssm_multipliers", i) for i in range(5)]
               + [("mlp_multipliers", i) for i in range(2)])


@pytest.mark.parametrize("name,index", MULTIPLIERS,
                         ids=[n if i is None else f"{n}-{i}"
                              for n, i in MULTIPLIERS])
def test_each_multiplier_moved_alone_moves_the_logits(name, index):
    """None is dropped, on either path: the prefill's logits (the first
    position) and the cached steps' (the rest) both move, and both follow
    the reference given the same multiplier."""
    ref, shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 2, 8)
    tokens, base = decoded(cfg, mesh, params, prompts, max_new=3)
    value = getattr(cfg.hybrid, name)
    moved = (1.5 * value if index is None else
             tuple(1.5 * v if i == index else v for i, v in enumerate(value)))
    other = dataclasses.replace(
        cfg, hybrid=dataclasses.replace(cfg.hybrid, **{name: moved}))
    _, logits = decoded(other, mesh, params, tokens[:, :8], max_new=3)
    assert error(logits[:, :1], base[:, :1]) > 1e-3
    # later positions follow the moved program's own tokens: compare on the
    # reference, which is handed the same multiplier
    tokens, logits = decoded(other, mesh, params, prompts, max_new=3)
    want = ref.logits(dataclasses.replace(shape, **{name: moved}), params,
                      tokens)[:, 7:-1]
    assert error(logits, want) < PARITY
    unmoved = ref.logits(shape, params, tokens)[:, 7:-1]
    assert error(logits[:, 1:], unmoved[:, 1:]) > 1e-3


def test_the_factory_reads_flat_keys():
    _ref, _shape, cfg, _mesh, _params = tiny()
    assert isinstance(cfg, tfm.TransformerConfig)
    assert isinstance(cfg.hybrid, ssm.HybridBlock)
    assert isinstance(cfg.rope_theta, float) and cfg.rope_theta == 1e11
    assert (cfg.head_dim, cfg.kv_heads) == (8, 2) != (cfg.d_model // 4, 4)
    assert cfg.hybrid.n_heads == 64 and cfg.n_heads == 4
    assert cfg.hybrid.in_dim == 128 + (128 + 2 * 2 * 32) + 64
    # the configurations that have no such block are what they were
    assert tfm.TransformerConfig().hybrid is None
    assert tfm.TransformerConfig(d_model=64, n_heads=4).head_dim == 16
    assert tfm.TransformerConfig(n_heads=4).kv_heads == 4


# ---- what is refused -----------------------------------------------------------

@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_the_block_is_refused_over_sp_and_tp(axis):
    _ref, _shape, cfg, _mesh, params = tiny()
    shape = {"dp": 1, "sp": 1, "tp": 1, axis: 2}
    mesh = make_mesh(shape, devices=jax.devices()[:2])
    tokens = prompts_of(cfg, 2, cfg.seq)
    with pytest.raises(ValueError, match=f"{axis} == 1 only"):
        jax.jit(tfm.make_loss_fn(cfg, mesh))(params, tokens)
    if axis == "tp":
        with pytest.raises(ValueError, match="tp == 1 only"):
            make_decoder(cfg, mesh, max_new=2)


def test_keep_logits_is_refused_over_dp_and_beyond_the_batch():
    _ref, _shape, cfg, mesh, params = tiny()
    two = make_mesh({"dp": 2, "sp": 1, "tp": 1}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="dp == 1"):
        make_decoder(cfg, two, max_new=2, keep_logits=1)
    with pytest.raises(ValueError, match="keep_logits=3 of 2"):
        make_decoder(cfg, mesh, max_new=2, keep_logits=3)(
            params, prompts_of(cfg, 2, 8))


def test_the_dense_decoder_hands_its_logits_back_too():
    """``keep_logits`` is the decoder's, not the block's: the dense
    configuration's tokens are what they are without it, and the logits are
    the full forward's."""
    cfg = tfm.TransformerConfig(
        vocab=97, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=64,
        attention="xla", compute_dtype="float32")
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 2}, devices=jax.devices()[:2])
    params = tfm.init_params(cfg)
    prompts = prompts_of(cfg, 4, 8)
    plain = np.asarray(make_decoder(cfg, mesh, max_new=5)(params, prompts))
    tokens, logits = make_decoder(cfg, mesh, max_new=5, keep_logits=2)(
        params, prompts)
    np.testing.assert_array_equal(np.asarray(tokens), plain)
    want = jax.jit(tfm.make_forward(cfg, mesh))(params, plain[:2])[:, 7:-1]
    assert logits.shape == (2, 5, 97) and error(logits, want) < PARITY


# ---- the scan as one kernel ----------------------------------------------------

CELL_12 = "nemotron-3-nano-30b-a3b.decode-1k-128-b256"
# sha256 of the StableHLO of cell 5's two programs (the ``first`` job's and
# the ``full`` job's) lowered for a described v5e, as they were at PR 63,
# before ``ssm.mixer`` was told ``forward_only``: its prompts are one chunk
# and its states 256 deep, so nothing of them may change.  A PR that changes
# cell 5's programs on purpose replaces these.
CELL_5_PROGRAMS = {
    "decode_first":
        "517068187efeb16b3e1920af4ca6451d1c3bbda33311b38db1caaff6f9bc9e70",
    "decode_full":
        "425f3ac62dbe148db2314c9db46044f0533740cb3d53cb88432722e4505bc78c",
}


def test_a_prefill_through_the_kernel_then_cached_steps_give_the_references_logits(
        monkeypatch):
    """``test_prefill_then_cached_steps...``'s form at widths the kernel
    takes (2 groups of 2 heads 64 wide, states 128 deep, chunks of 128) and
    prompts of two chunks, the decoder told that it is traced for TPUs: the
    prefill's scan is the kernel (interpret mode here), the states it hands
    over are carried by the cached steps, and every generated position's
    logits are the reference's."""
    from ompi_tpu.ops import _chip

    config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
    config.update(mamba_d_ssm=256, mamba_n_heads=4, mamba_d_head=64,
                  mamba_d_state=128, mamba_chunk_size=128,
                  max_position_embeddings=384)
    # an interpreted kernel is a host callback, which no checkpoint holds
    config["entry"]["options"].update(compute_dtype="float32", remat=None)
    ref = program.reference(config)
    cfg = program.program_config(config)
    mesh = program.mesh(config, jax.devices()[:1])
    params = program.init_params(
        ref, config, program.param_shardings(config, cfg, mesh), seed=11)
    prompts = prompts_of(cfg, 2, 256)
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    decoder = make_decoder(cfg, mesh, max_new=3, keep_logits=2)
    calls = [c.params["name"] for c in _pallas_calls(
        jax.make_jaxpr(decoder)(params, prompts).jaxpr)]
    assert calls == ["ssm_scan"]        # under the loop over layers, once
    tokens, logits = decoder(params, prompts)
    tokens, logits = np.asarray(tokens), np.asarray(logits)
    np.testing.assert_array_equal(logits.argmax(-1), tokens[:, 256:])
    want = ref.logits(ref.Shape.from_config(config), params,
                      tokens)[:, 255:-1]
    assert error(logits, want) < PARITY


def _running_sums(jaxpr):
    """The ``cumsum`` equations of ``jaxpr`` over floats (the experts'
    offsets are running sums too, of counts), at any depth."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "cumsum"
                and jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.floating)):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _running_sums(sub)


def test_cell_12s_prefill_calls_the_kernel_six_times_and_sums_no_decay_outside(
        chip, for_the_chip):
    """The cell's two programs traced at its real sizes for the described
    chip: the prefill scans each of its six Mamba-2 layers through the
    kernel and ``chunked_scan``'s running sum is nowhere in it; the
    generating program has no whole sequence and no such call."""
    _cfg, job = _cell(CELL_12, chip)
    scans = []
    for which in (0, 1):
        fn, args = _program(job, chip, which)
        jaxpr = jax.make_jaxpr(fn)(*args)
        scans.append([c for c in _pallas_calls(jaxpr.jaxpr)
                      if c.params["name"] == "ssm_scan"])
        assert not list(_running_sums(jaxpr.jaxpr))
    assert [len(calls) for calls in scans] == [6, 0]
    for call in scans[0]:
        assert call.params["grid_mapping"].grid == (8, 8, 1)


def test_cell_5s_programs_are_what_they_were(chip, for_the_chip):
    _cfg, job = _cell(CELL, chip)
    programs = job.programs()
    assert set(programs) == set(CELL_5_PROGRAMS)
    for name, (fn, args) in programs.items():
        text = fn.lower(*args).as_text()
        assert "custom_call" not in text, name
        assert (hashlib.sha256(text.encode()).hexdigest()
                == CELL_5_PROGRAMS[name]), name


# ---- the scopes ----------------------------------------------------------------

def test_the_mixers_scopes_are_in_the_vocabulary():
    from ompi_tpu.core import scopes

    assert {"ssm_proj", "ssm.conv", "ssm.scan",
            "ssm.update"} <= set(scopes.SCOPES)
