"""Latent attention with a rotary embedding (``models/mla.py`` with a theta,
``models/plan.mla_moe_config``, ``ops/latent_attention.py``) against the plain
reference, ``benchmarks/reference/kimi_vl.py``, at the configuration's tiny
sizes, float32, seeded, on the CPU: prefill then cached steps against the full
forward on logits position by position, the rotation at positions past the
first, what the carry holds, the loss, the kernel against the ``jax.numpy``
form by length and tile, the mixer's rule and its gradient through the
kernel, what the ``entry.config`` refuses, and the NoPE form left as it was.
Agreement only: nothing here is a time.
"""

import copy
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import mla, plan
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.ops import _chip
from ompi_tpu.ops import latent_attention
from tests.parallel.compiled import _cell, _pallas_calls

CELL = "kimi-vl-a3b.decode-16k-256-b32"
NOPE_CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
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
                      mesh=mesh, params=params, config=config)
    return (_built[k] for k in ("ref", "shape", "cfg", "mesh", "params"))


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


def prompts_of(cfg, batch, length, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


def test_the_plan_is_latent_attention_over_one_dense_layer_then_experts():
    _ref, shape, cfg, _mesh, params = tiny()
    assert cfg.plan.layers == (("mla", "dense"),) + (("mla", "moe"),) * 4
    ml = cfg.plan.mla
    assert (ml.n_heads, ml.nope, ml.rope, ml.v_dim, ml.kv_rank, ml.theta) == (
        4, 16, 8, 16, 32, 800_000.0)
    assert ml.nope + ml.rope != ml.v_dim
    assert ml.kv_rank < ml.n_heads * (ml.nope + ml.rope)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_shared) == (8, 2, 128)
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.moe_norm_topk,
            cfg.moe_scale, cfg.moe_held) == ("sigmoid", True, True, 2.446,
                                             None)
    assert set(plan.leaf_names(cfg)) | {"emb", "head", "lnf"} == set(params)
    assert params["mla_q"].shape == (5, 128, 4 * 24)
    assert params["dw1"].shape == (1, 128, 192)
    assert params["w1"].shape == (4, 8, 128, 64)
    assert params["sw1"].shape == (4, 128, 128)
    assert shape.n_dense == 1 and shape.n_routed == 4


@pytest.mark.parametrize("prompt_len,max_new", [(17, 12), (12, 20)])
def test_prefill_then_cached_steps_are_the_full_forward(prompt_len, max_new):
    """Every generated position's logits, the prefill's for the first and the
    cached steps' after, against the reference's forward over the whole
    continuation: the steps rotate at positions ``prompt_len`` and on."""
    ref, shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 3, prompt_len)
    tokens, logits = make_decoder(cfg, mesh, max_new=max_new, keep_logits=3)(
        params, prompts)
    tokens = np.asarray(tokens)
    assert np.array_equal(tokens[:, :prompt_len], prompts)
    want = ref.logits(shape, params, tokens)[:, prompt_len - 1:-1]
    for at in range(max_new):
        assert error(logits[:, at], want[:, at]) < PARITY, at
    assert np.array_equal(np.asarray(logits).argmax(-1),
                          tokens[:, prompt_len:])


def test_the_loss_is_the_references():
    ref, shape, cfg, mesh, params = tiny()
    tokens = prompts_of(cfg, 2, 32, seed=5)
    got = float(tfm.make_loss_fn(cfg, mesh)(params, jnp.asarray(tokens)))
    assert abs(got - ref.loss(shape, params, jnp.asarray(tokens))) < 1e-4


def test_the_rotation_is_the_published_codes_up_to_where_the_pairs_lie():
    """``mla.rotate`` keeps pair i at (2i, 2i + 1); the reference, as the
    published code, moves it to (i, i + P/2).  At positions past the first
    the two agree element for element under that map, for a head's part (B,
    T, H, P) and for the shared key (B, T, P)."""
    ref, *_ = tiny()
    rng = np.random.default_rng(0)
    for dims in ((2, 37, 3, 8), (2, 37, 8)):
        x = jnp.asarray(rng.normal(size=dims), jnp.float32)
        got = np.asarray(mla.rotate(x, jnp.arange(37), 800_000.0))
        want = np.asarray(ref.rotary(x, 800_000.0))
        assert np.allclose(got[..., 0::2], want[..., :4], atol=1e-5)
        assert np.allclose(got[..., 1::2], want[..., 4:], atol=1e-5)
        assert np.allclose(got[:, 0], x[:, 0])          # position 0: no turn
        assert np.abs(got[:, 1:] - np.asarray(x)[:, 1:]).max() > 0.5
    one = mla.rotate(x[:, 20:21], jnp.asarray([20]), 800_000.0)
    assert np.allclose(one, got[:, 20:21], atol=1e-5)


def test_the_carry_holds_the_rotated_shared_key():
    """What the prefill hands over of a layer is ``[normed latent, rotated
    k_r]`` a position, and a cached step writes its own row at its own
    position, rotated there."""
    ref, shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 2, 9)
    R, layer = cfg.plan.mla.kv_rank, 0
    comm = tfm._mesh_comm(mesh)

    def prefill(params, tokens):
        return plan.backbone(cfg, comm, params, tokens, collect_kv=True)[1]

    rows = jax.jit(jax.shard_map(
        prefill, mesh=mesh, in_specs=(tfm.param_specs(
            jax.sharding.PartitionSpec, cfg, mesh),
            jax.sharding.PartitionSpec()),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False))(
            params, jnp.asarray(prompts))
    assert len(rows) == 5 and rows[layer].shape == (1, 2, 9, R + 8)
    x = ref._rmsnorm(jnp.asarray(params["emb"])[prompts],
                     params["ln1"][layer], shape.eps)
    kva = x @ params["mla_kva"][layer]
    want = np.asarray(ref.rotary(kva[..., R:], shape.theta))
    got = np.asarray(rows[layer][0, ..., R:])
    assert np.allclose(got[..., 0::2], want[..., :4], atol=1e-5)
    assert np.allclose(got[..., 1::2], want[..., 4:], atol=1e-5)
    assert np.abs(got - np.asarray(kva[..., R:])).max() > 0.1
    assert np.allclose(rows[layer][0, ..., :R], ref._rmsnorm(
        kva[..., :R], params["mla_n"][layer], shape.eps), atol=1e-5)


def _operands(T, B=1, H=2, dtype=jnp.float32):
    N, P, W = 128, 64, 128
    keys = jax.random.split(jax.random.key(T), 3)
    return (jax.random.normal(keys[0], (B, T, H, N + P), dtype),
            jax.random.normal(keys[1], (B, T, H, N + W), dtype),
            jax.random.normal(keys[2], (B, T, P), dtype), (N + P) ** -0.5)


def _kernel_call(q, kv, k_r, scale, rows=None):
    """(grid, block shapes, stated VMEM) of the one kernel call traced for
    these operands (shapes are enough)."""
    (eqn,) = _pallas_calls(jax.make_jaxpr(
        lambda *a: latent_attention.latent_attention(*a, scale, rows))(
            q, kv, k_r).jaxpr)
    mapping = eqn.params["grid_mapping"]
    return (mapping.grid,
            [tuple(getattr(d, "block_size", d) for d in m.block_shape)
             for m in mapping.block_mappings],
            eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes)


# (positions, (rows of q, rows of k) or None for the rule's): one tile and
# three with two visited below the diagonal, positions that are no multiple
# of a tile (300, 1100, 200: padded); cell 11's 896 as the rule takes them
# (one tile of queries, seven slabs, no padding), as square tiles of 128
# (seven a head, none padded) and of 256 (padded to 1024); 1024 in square
# tiles of 512
@pytest.mark.parametrize("T,rows", [
    (300, None), (1100, None), (896, None), (896, (128, 128)),
    (896, (256, 256)), (200, None), (1024, (512, 512)), (1024, (512, 256))])
def test_the_kernel_is_the_jnp_attention(T, rows):
    q, kv, k_r, scale = _operands(T)
    want = latent_attention.jnp_form(q, kv, k_r, scale)
    got = jax.jit(lambda *a: latent_attention.latent_attention(
        *a, scale, rows))(q, kv, k_r)
    assert got.shape == want.shape and error(got, want) < 1e-5
    rows_q, rows_k = rows or latent_attention.tile(T)
    grid, blocks, _limit = _kernel_call(q, kv, k_r, scale, rows)
    padded = -(-T // rows_q) * rows_q
    assert grid == (1, 2, padded // rows_q)
    assert blocks[0] == (1, rows_q, 128) and blocks[2] == (1, padded, 128)
    assert (padded == T) == (T % 128 == 0 and T % rows_q == 0)


def test_what_the_kernel_refuses():
    q, kv, k_r, scale = _operands(300)
    assert latent_attention.tiles(300, 2, 128, 64, 128)
    assert not latent_attention.tiles(300, 2, 64, 64, 128)
    assert not latent_attention.tiles(300, 3, 128, 64, 128)     # a head alone
    assert latent_attention.tiles(300, 3, 128, 128, 128)
    assert not latent_attention.tiles(300, 2, 128, 48, 128)
    assert not latent_attention.tiles(latent_attention.MAX_ROWS + 1, 2, 128,
                                      64, 128)
    with pytest.raises(ValueError, match="do not tile"):
        latent_attention.latent_attention(q[..., :100], kv, k_r, scale)
    for rows in ((512, 384), (256, 64), (128, 256)):
        with pytest.raises(ValueError, match="a tile of"):
            latent_attention.latent_attention(q, kv, k_r, scale, rows)


def test_the_tile_and_the_stated_vmem_follow_the_length():
    """896 positions are one tile of queries whose keys go by 128, unpadded;
    a length that is no multiple of 128 is padded to one; past 1024 positions
    square tiles of 512.  A short call states what its blocks count (under
    what the compiler gives unasked); cell 10's 16,128 positions state what
    they did, on the grid and the blocks they had."""
    assert latent_attention.tile(896) == (896, 128)
    assert latent_attention.tile(512) == (512, 128)
    assert latent_attention.tile(200) == (256, 128)
    assert latent_attention.tile(1024) == (1024, 128)
    assert latent_attention.tile(1025) == (512, 512)
    assert latent_attention.tile(16_128) == (512, 512)
    short = latent_attention.vmem_limit(
        1024, *latent_attention.tile(1024), 128, 64, 128)
    assert 4 << 20 < short < 16 << 20
    assert latent_attention.vmem_limit(1024, 512, 512, 128, 64, 128) < 16 << 20
    assert latent_attention.vmem_limit(16_384, 512, 512, 128, 64,
                                       128) == 96 << 20
    bf16 = jnp.bfloat16
    cell_10 = (jax.ShapeDtypeStruct((1, 16_128, 16, 192), bf16),
               jax.ShapeDtypeStruct((1, 16_128, 16, 256), bf16),
               jax.ShapeDtypeStruct((1, 16_128, 64), bf16), 192 ** -0.5)
    assert _kernel_call(*cell_10) == (
        (1, 16, 32),
        [(1, 512, 128), (1, 512, 128), (1, 16_384, 128), (1, 16_384, 128),
         (1, 16_384, 128), (1, 512, 128)], 96 << 20)
    cell_11 = (jax.ShapeDtypeStruct((4, 896, 64, 192), bf16),
               jax.ShapeDtypeStruct((4, 896, 64, 256), bf16),
               jax.ShapeDtypeStruct((4, 896, 64), bf16), 192 ** -0.5)
    grid, blocks, limit = _kernel_call(*cell_11)
    assert grid == (4, 64, 1) and blocks[:2] == [(1, 896, 128)] * 2
    assert blocks[2] == (1, 896, 128) and limit < 16 << 20


def test_cell_10s_prefill_holds_the_kernel_call_it_had(chip, for_the_chip):
    """The cell that ran the kernel before any other did: its prefill at the
    real sizes, lowered for the described chip, holds one kernel function
    for its five call sites, over 16,384 padded positions, the keys, values
    and output where they were and 96 MiB of VMEM stated (the grid and the
    blocks: ``test_the_tile_and_the_stated_vmem_follow_the_length``).  Its
    text is not the parent's: since PR 61 the queries are read as they lie
    and not transposed to the heads, which took 0.39 s off this cell's
    prefill too (PERF.md section 6, PR 61)."""
    _cfg, job = _cell(CELL, chip)
    fn, args = job.programs()["decode_first"]
    text = fn.lower(*args).as_text()
    (call,) = [line for line in text.splitlines()
               if 'kernel_name = "latent_attention"' in line]
    kinds = re.search(r" : \((.*)\) -> (tensor<\S+>)$", call)
    assert kinds.group(1).split(", ") == [
        "tensor<1x16384x2048xbf16>", "tensor<1x16384x1024xbf16>",
        "tensor<1x16384x4096xbf16>", "tensor<1x16384x128xbf16>",
        "tensor<1x16384x4096xbf16>"]
    assert kinds.group(2) == "tensor<1x16384x2048xbf16>"
    assert re.search(r'scoped_memory_configs[^]]*\\22size\\22: 100663296',
                     call)


def _latent_layer(H, dtype="float32"):
    """(config, one layer's leaves) of a latent layer of ``H`` heads that
    tile (128 + 64 for the scores, 128 for the values) over a small latent
    and a small stream."""
    ml = mla.MLA(n_heads=H, nope=128, rope=64, v_dim=128, kv_rank=48,
                 theta=800_000.0)
    cfg = tfm.TransformerConfig(
        vocab=64, d_model=32, n_heads=H, n_layers=1, d_ff=64, norm_eps=1e-5,
        compute_dtype=dtype, plan=plan.LayerPlan(
            layers=(("mla", "dense"),), mla=ml))
    rng = np.random.default_rng(4)
    lp = {"ln1": jnp.ones((32,)), **{
        name: jnp.asarray(rng.normal(0, std or 1, size=dims), jnp.float32)
        if std else jnp.ones(dims)
        for name, (dims, std) in mla.leaf_shapes(cfg, ml).items()}}
    return cfg, lp


# the three cells' whole-sequence attentions: cell 11's prefill pass, cell
# 7's, and cell 10's (whose 16,128 positions are only traced here: the
# interpreter would take minutes over them, and the jnp form 17 GB)
@pytest.mark.parametrize("T,H,run", [(896, 64, True), (512, 32, True),
                                     (16_128, 16, False)])
def test_the_mixer_takes_the_kernel_by_the_rule(monkeypatch, T, H, run):
    """Heads that tile: the whole-sequence mixer traced for TPUs holds the
    kernel from ``KERNEL_FROM`` positions on, on the tile the length gives,
    and the ``jax.numpy`` form below it and in a trace for anything else;
    through the kernel it is the mixer through ``jax.numpy``, and the rows it
    hands on are the same."""
    cfg, lp = _latent_layer(H)
    h = jax.ShapeDtypeStruct((1, T, 32), jnp.float32)
    traced = lambda T=T: jax.make_jaxpr(                        # noqa: E731
        lambda lp, h: mla.mixer(cfg, lp, h))(
            lp, jax.ShapeDtypeStruct((1, T, 32), jnp.float32)).jaxpr
    assert not list(_pallas_calls(traced()))        # the CPU's trace
    if run:
        h = jnp.asarray(np.random.default_rng(T).normal(size=h.shape),
                        jnp.float32)
        want, want_rows = jax.jit(lambda lp, h: mla.mixer(cfg, lp, h))(lp, h)
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    assert T >= mla.KERNEL_FROM
    (call,) = _pallas_calls(traced())
    assert call.params["name"] == "latent_attention"
    rows_q, _rows_k = latent_attention.tile(T)
    assert call.params["grid_mapping"].grid == (1, H, -(-T // rows_q))
    assert not list(_pallas_calls(traced(mla.KERNEL_FROM - 128)))
    if run:
        got, got_rows = jax.jit(lambda lp, h: mla.mixer(cfg, lp, h))(lp, h)
        assert error(got, want) < 1e-5 and error(got_rows, want_rows) < 1e-6


def test_the_gradient_through_the_kernel_is_the_jnp_forms(monkeypatch):
    """The kernel has no backward pass of its own: ``jax.grad`` through the
    mixer with the kernel engaged is the gradient through the ``jax.numpy``
    form, for the stream and for every leaf (a loss over a latent
    configuration on TPUs works at any length)."""
    cfg, lp = _latent_layer(2)
    T = max(mla.KERNEL_FROM, 256)
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(2, T, 32)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(2, T, 32)), jnp.float32)

    def loss(lp, h):
        out, rows = mla.mixer(cfg, lp, h)
        return (out * g).sum() + rows.sum()

    want = jax.jit(jax.grad(loss, (0, 1)))(lp, h)
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    grad = jax.jit(jax.grad(loss, (0, 1)))
    assert list(_pallas_calls(grad.trace(lp, h).jaxpr))
    got = grad(lp, h)
    for name in lp:
        assert error(got[0][name], want[0][name]) < 1e-5, name
    assert error(got[1], want[1]) < 1e-5
    # the kernel alone, every operand's cotangent
    q, kv, k_r, scale = _operands(256)
    w = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), jnp.float32)
    for got, want in zip(*(jax.jit(jax.grad(
            lambda q, kv, k_r, form=form: (form(q, kv, k_r, scale) * w).sum(),
            (0, 1, 2)))(q, kv, k_r) for form in (
                latent_attention.latent_attention,
                latent_attention.jnp_form))):
        assert error(got, want) < 1e-5


def test_the_entry_config_refuses_what_is_not_built():
    config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
    # a query latent is built (``MLA.q_rank``); this family's reference is
    # written without one
    latent = program.program_config({**config, "q_lora_rank": 24})
    assert latent.plan.mla.q_rank == 24 and {
        "mla_qa", "mla_qn", "mla_qb"} <= set(plan.leaf_names(latent))
    assert "mla_q" not in plan.leaf_names(latent)
    with pytest.raises(ValueError, match="written for"):
        program.reference(config).Shape.from_config(
            {**config, "q_lora_rank": 24})
    yarn = {"type": "yarn", "factor": 40, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}
    for key, value in (("rope_scaling", {"type": "linear", "factor": 4}),
                       ("rope_scaling", {**yarn, "mscale": 0.5}),
                       ("moe_layer_freq", 2), ("scoring_func", "softmax"),
                       ("topk_method", "greedy")):
        with pytest.raises(ValueError, match=f"not built for {key}"):
            program.program_config({**config, key: value})
        with pytest.raises(ValueError, match="written for"):
            program.reference(config).Shape.from_config(
                {**config, key: value})
    # built since PR 67 (``tests/benchmarks/test_v32.py``): group-limited
    # picks and a yarn rotation; this family's reference is written for
    # neither
    grouped = program.program_config({**config, "n_group": 4,
                                      "topk_group": 2})
    assert grouped.moe_groups == (4, 2)
    assert program.program_config(config).moe_groups is None
    scaled = program.program_config({**config, "rope_scaling": yarn})
    assert scaled.plan.mla.yarn.factor == 40
    assert program.program_config(config).plan.mla.yarn is None
    for key, value in (("n_group", 4), ("topk_group", 2),
                       ("rope_scaling", yarn)):
        with pytest.raises(ValueError, match="written for"):
            program.reference(config).Shape.from_config(
                {**config, key: value})


def test_every_planned_configurations_door_is_listed():
    doors = {program.import_dotted(c["entry"]["config"])
             for c in (cells.load_json(f"{cells.BENCH_DIR}/../{row['file']}")
                       for row in cells.load_benchmark()["configs"])
             if ".plan." in c["entry"]["config"]}
    assert doors == set(plan.ENTRY_CONFIGS) and len(doors) == 7


def test_the_nope_form_has_no_rotation_in_it():
    """Cell 7's latent layer: ``theta`` 0, and its programs trace no
    rotation, none of the rotary form's scopes and no kernel; the rotary
    form's trace ``mla.rotate`` inside ``mla_proj.rope``."""
    config = copy.deepcopy(program.tiny(cells.resolve(NOPE_CELL).config))
    nope = program.program_config(config)
    assert nope.plan.mla.theta == 0.0
    assert dataclasses.replace(nope.plan.mla, theta=0.0) == nope.plan.mla
    _ref, _shape, cfg, mesh, _params = tiny()
    for of, has in ((nope, False), (cfg, True)):
        table = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in
                 tfm.init_params(of, 0).items()}
        traced = jax.jit(make_decoder(of, mesh, max_new=3)).trace(
            table, jax.ShapeDtypeStruct((2, 8), jnp.int32))
        text = traced.lower().as_text(debug_info=True)
        assert ("mla_proj.rope/mla.rotate" in text) == has
        assert ("attn_proj/mla_proj/" in text) != has
        assert not list(_pallas_calls(traced.jaxpr))
