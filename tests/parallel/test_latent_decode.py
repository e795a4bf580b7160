"""The cached step's one pass over its latent cache
(``ops/latent_decode.py``), in TPU interpret mode, against the ``jax.numpy``
absorbed step it stands in for (``models/mla.mixer``'s ``carry`` branch): the
kernel alone, the mixer told that it is traced for TPUs, a whole decoder
(prefill, then cached steps through the kernel), and the rule that says
which form a step takes (``latent_decode.tiles`` beside
``_chip._traced_for_tpus``), read from the benchmark's own configuration and
traffic files.  Agreement and control flow only: nothing here is a time.

Both sides are float32 and differ in the order of their sums alone (a
running softmax a block at a time against one softmax over the cache).
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import mla, plan
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.ops import _chip
from ompi_tpu.ops import latent_decode as kernel_module
from ompi_tpu.ops.latent_decode import latent_decode, tiles
from tests.benchmarks import test_harness
# a decoder's tolerance on logits over their deviation, and that measure
from tests.parallel.test_mla_rope import PARITY, error

BLOCK = kernel_module._BLOCK
CELL_10 = "kimi-vl-a3b.decode-16k-256-b32"
CELL_7 = "kimi-linear-48b-a3b.decode-512-128-b384"
# one block of 1024 positions a sequence, 64 heads
CELL_11 = "longcat-flash-chat.decode-896-128-b160"
# first block only, a block's last row, the next block's first, the last row
POSITIONS = (5, BLOCK - 1, BLOCK, 2 * BLOCK - 1)


def absorbed(q_abs, cache, pos, scale, rank):
    """``mla.mixer``'s ``jax.numpy`` step, from the absorbed query on."""
    f32 = jnp.float32
    s = jnp.einsum("bhc,bkc->bhk", q_abs, cache,
                   preferred_element_type=f32) * scale
    s = jnp.where(jnp.arange(cache.shape[1]) <= pos, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", w.astype(cache.dtype),
                      cache[..., :rank], preferred_element_type=f32)


@pytest.mark.parametrize("heads", [16, 32])
@pytest.mark.parametrize("pos", POSITIONS)
def test_the_kernel_is_the_absorbed_step(heads, pos):
    """Rows past ``pos`` hold large values: a kernel that read one would not
    agree."""
    rank, rope, batch = 128, 64, 2
    keys = jax.random.split(jax.random.key(heads + pos), 2)
    q = jax.random.normal(keys[0], (batch, heads, rank + rope), jnp.float32)
    cache = jax.random.normal(keys[1], (batch, 2 * BLOCK, rank + rope),
                              jnp.float32)
    cache = jnp.where(jnp.arange(2 * BLOCK)[:, None] <= pos, cache, 50.0)
    scale = 192 ** -0.5
    got = jax.jit(lambda q, c, p: latent_decode(q, c, p, scale, rank))(
        q, cache, jnp.int32(pos))
    want = absorbed(q, cache, pos, scale, rank)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert error(got, want) < 1e-5


def test_sizes_that_do_not_tile_are_refused():
    assert tiles(16_384, 512) and tiles(BLOCK, 128)
    assert not tiles(640, 512)              # cell 7: no whole block
    assert not tiles(16_384, 32) and not tiles(16_384, 576)
    q = jnp.zeros((1, 4, 136))
    for t_max, rank, width in ((BLOCK + 128, 128, 136), (BLOCK, 64, 136),
                               (BLOCK, 128, 144)):
        with pytest.raises(ValueError, match="do not tile"):
            latent_decode(q, jnp.zeros((1, t_max, width)), 0, 1.0, rank)


def _layer(ml, d_model=32, seed=4):
    cfg = tfm.TransformerConfig(
        vocab=64, d_model=d_model, n_heads=ml.n_heads, n_layers=1, d_ff=64,
        norm_eps=1e-5, compute_dtype="float32", plan=plan.LayerPlan(
            layers=(("mla", "dense"),), mla=ml))
    rng = np.random.default_rng(seed)
    lp = {"ln1": jnp.ones((d_model,)), **{
        name: jnp.asarray(rng.normal(0, std, size=dims), jnp.float32)
        if std else jnp.ones(dims)
        for name, (dims, std) in mla.leaf_shapes(cfg, ml).items()}}
    return cfg, lp, rng


@pytest.mark.parametrize("theta", [0.0, 800_000.0], ids=["nope", "rotary"])
@pytest.mark.parametrize("heads", [16, 32])
def test_the_mixer_takes_the_kernel_on_tpus_where_the_cache_tiles(
        monkeypatch, theta, heads):
    """Both published forms, a step at each of ``POSITIONS`` against a cache
    of two blocks: the mixer through the kernel is the mixer through
    ``jax.numpy``, and the row it writes is the same row."""
    ml = mla.MLA(n_heads=heads, nope=16, rope=8, v_dim=16, kv_rank=128,
                 theta=theta)
    cfg, lp, rng = _layer(ml)
    h = jnp.asarray(rng.normal(size=(2, 1, 32)), jnp.float32)
    cache = jnp.asarray(rng.normal(size=(2, 2 * BLOCK, ml.cached)),
                        jnp.float32)

    def steps(lp, h, cache):
        return [mla.mixer(cfg, lp, h, (cache, jnp.int32(pos)))
                for pos in POSITIONS]

    # a jit of its own each: one of ``steps`` itself would hand the first
    # trace back to the second
    want = jax.jit(lambda *a: steps(*a))(lp, h, cache)
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    traced = jax.jit(lambda *a: steps(*a))
    assert "latent_decode" in str(traced.trace(lp, h, cache).jaxpr)
    for (got, got_cache), (out, out_cache) in zip(traced(lp, h, cache), want):
        assert error(got, out) < 1e-5
        assert np.array_equal(got_cache, out_cache)
    # a cache that is no whole block stays ``jax.numpy`` on TPUs too
    assert "latent_decode" not in str(jax.jit(lambda *a: steps(*a)).trace(
        lp, h, cache[:, :BLOCK + 128]).jaxpr)


def _decoder_config():
    """Cell 10's tiny configuration in float32 with a latent that tiles."""
    config = copy.deepcopy(program.tiny(cells.resolve(CELL_10).config))
    config["entry"]["options"]["compute_dtype"] = "float32"
    cfg = program.program_config(config)
    return dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, mla=dataclasses.replace(cfg.plan.mla, kv_rank=128)))


def test_a_decoder_through_the_kernel_gives_the_jnp_decoders_logits(
        monkeypatch):
    """Prefill, then cached steps whose positions cross from the cache's
    second block into its third (blocks of 16 positions here, so that a few
    steps do): every kept position's logits are those of the decoder that
    takes ``jax.numpy``, and the tokens are the same."""
    cfg = _decoder_config()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             ("dp", "sp", "tp"))
    params = tfm.init_params(cfg, 3)
    max_new = 12
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab, size=(2, 36)).astype(np.int32)
    monkeypatch.setattr(kernel_module, "_BLOCK", 16)    # a cache of 48: three
    want_tokens, want = make_decoder(cfg, mesh, max_new=max_new,
                                     keep_logits=2)(params, prompts)
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    decoder = make_decoder(cfg, mesh, max_new=max_new, keep_logits=2)
    table = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in params.items()}
    jaxpr = str(jax.jit(decoder).trace(
        table, jax.ShapeDtypeStruct(prompts.shape, jnp.int32)).jaxpr)
    assert "latent_decode" in jaxpr and "latent_attention" not in jaxpr
    tokens, logits = decoder(params, prompts)
    for at in range(max_new):
        assert error(logits[:, at], want[:, at]) < PARITY, at
    assert np.array_equal(tokens, want_tokens)


def _latent_step(workload, tiny):
    """(one latent layer's cached step for one sequence, its abstract
    arguments) at a decode cell's sizes, from its configuration and traffic
    files; ``tiny``: as the harness's CPU runs size it."""
    cell = cells.resolve(workload)
    if tiny:
        cell = test_harness.tiny(cell)
    cfg = dataclasses.replace(program.program_config(cell.config),
                              compute_dtype="float32")
    ml, f32 = cfg.plan.mla, jnp.float32
    positions = cell.traffic["prompt_len"] + cell.traffic["max_new"]
    lp = {name: jax.ShapeDtypeStruct(dims, f32)
          for name, (dims, _std) in mla.leaf_shapes(cfg, ml).items()}
    lp["ln1"] = jax.ShapeDtypeStruct((cfg.d_model,), f32)

    def step(lp, h, cache, pos):
        return mla.mixer(cfg, lp, h, (cache, pos))

    return step, (lp, jax.ShapeDtypeStruct((1, 1, cfg.d_model), f32),
                  jax.ShapeDtypeStruct((1, positions, ml.cached), f32),
                  jax.ShapeDtypeStruct((), jnp.int32))


# Which form every cell with latent layers takes in a cached step (PERF.md
# section 5): the mixer's own rule, traced and not run.  Cell 10's 16,384
# positions are sixteen blocks of a latent four lane tiles wide; cell 7's 640
# are no whole block, and the measured constant (``latent_decode._BLOCK``'s
# comment) puts the kernel ahead from one block on, so whole blocks are all
# the rule asks of a length.  The harness's tiny programs have a latent of 32
# and caches of 24 positions, and a trace for the CPU takes ``jax.numpy``
# whatever the sizes.
@pytest.mark.parametrize("workload,tiny,tpus,positions,kernel", [
    pytest.param(CELL_10, False, True, 16_384, True, id="cell-10"),
    pytest.param(CELL_7, False, True, 640, False, id="cell-7"),
    pytest.param(CELL_10, False, False, 16_384, False, id="cell-10-cpu"),
    pytest.param(CELL_7, False, False, 640, False, id="cell-7-cpu"),
    pytest.param(CELL_10, True, True, 24, False, id="cell-10-tiny"),
    pytest.param(CELL_7, True, True, 24, False, id="cell-7-tiny"),
    pytest.param(CELL_11, False, True, 1024, True, id="cell-11"),
    pytest.param(CELL_11, False, False, 1024, False, id="cell-11-cpu"),
    pytest.param(CELL_11, True, True, 24, False, id="cell-11-tiny"),
])
def test_which_form_each_latent_cells_step_takes(monkeypatch, workload, tiny,
                                                 tpus, positions, kernel):
    step, args = _latent_step(workload, tiny)
    assert args[2].shape[1] == positions
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: tpus)
    jaxpr = str(jax.jit(step).trace(*args).jaxpr)
    assert ("latent_decode" in jaxpr) == kernel


def test_no_other_cell_has_latent_layers():
    """The three above are every cell the rule is asked about."""
    planned = {row["name"] for row in cells.load_benchmark()["workloads"]
               if getattr(getattr(program.program_config(cells.resolve(
                   row["name"]).config), "plan", None), "mla", None)}
    # and, since PR 67, the cell whose latent layers carry an index: its
    # steps hand the kernel a selection (``tests/benchmarks/test_v32.py``)
    assert planned == {CELL_10, CELL_7, CELL_11,
                       "deepseek-v3.2-exp.decode-16k-512-b8"}
