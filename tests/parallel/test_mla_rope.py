"""Latent attention with a rotary embedding (``models/mla.py`` with a theta,
``models/plan.mla_moe_config``, ``ops/latent_attention.py``) against the plain
reference, ``benchmarks/reference/kimi_vl.py``, at the configuration's tiny
sizes, float32, seeded, on the CPU: prefill then cached steps against the full
forward on logits position by position, the rotation at positions past the
first, what the carry holds, the loss, the kernel against the ``jax.numpy``
form, what the ``entry.config`` refuses, and the NoPE form left as it was.
Agreement only: nothing here is a time.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import kda, mla, plan
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.ops import latent_attention

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

    _aux, *rows = jax.jit(jax.shard_map(
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


@pytest.mark.parametrize("T", [300, 1100])
def test_the_kernel_is_the_jnp_attention(T):
    """One tile, and three with two visited below the diagonal; positions
    that are no multiple of a tile."""
    B, H, N, P, W = 1, 2, 128, 64, 128
    keys = jax.random.split(jax.random.key(T), 3)
    q = jax.random.normal(keys[0], (B, T, H, N + P), jnp.float32)
    kv = jax.random.normal(keys[1], (B, T, H, N + W), jnp.float32)
    k_r = jax.random.normal(keys[2], (B, T, P), jnp.float32)
    scale = (N + P) ** -0.5
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :N], kv[..., :N])
         + jnp.einsum("bqhd,bkd->bhqk", q[..., N:], k_r)) * scale
    w = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30),
                       axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., N:])
    got = jax.jit(lambda *a: latent_attention.latent_attention(*a, scale))(
        q, kv, k_r)
    assert got.shape == want.shape and error(got, want) < 1e-5
    assert latent_attention.tiles(T, N, W)
    assert not latent_attention.tiles(T, 64, W)
    assert not latent_attention.tiles(latent_attention.MAX_ROWS + 1, N, W)
    with pytest.raises(ValueError, match="do not tile"):
        latent_attention.latent_attention(q[..., :100], kv, k_r, scale)


def test_the_mixer_takes_the_kernel_on_tpus_from_kernel_from_on(monkeypatch):
    """Heads that tile, told that the trace is for TPUs: the whole-sequence
    mixer through the kernel is the mixer through ``jax.numpy``, and the rows
    it hands on are the same."""
    ml = mla.MLA(n_heads=2, nope=128, rope=64, v_dim=128, kv_rank=48,
                 theta=800_000.0)
    cfg = tfm.TransformerConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64, norm_eps=1e-5,
        compute_dtype="float32", plan=plan.LayerPlan(
            layers=(("mla", "dense"),), mla=ml))
    rng = np.random.default_rng(4)
    lp = {"ln1": jnp.ones((32,)), **{
        name: jnp.asarray(rng.normal(0, std or 1, size=dims), jnp.float32)
        if std else jnp.ones(dims)
        for name, (dims, std) in mla.leaf_shapes(cfg, ml).items()}}
    h = jnp.asarray(rng.normal(size=(2, 200, 32)), jnp.float32)
    want, want_rows = jax.jit(lambda lp, h: mla.mixer(cfg, lp, h))(lp, h)
    monkeypatch.setattr(kda, "_traced_for_tpus", lambda: True)
    monkeypatch.setattr(mla, "KERNEL_FROM", 128)
    traced = jax.jit(lambda lp, h: mla.mixer(cfg, lp, h))
    assert "latent_attention" in str(traced.trace(lp, h).jaxpr)
    got, got_rows = traced(lp, h)
    assert error(got, want) < 1e-5 and error(got_rows, want_rows) < 1e-6
    monkeypatch.setattr(mla, "KERNEL_FROM", 2048)       # the cell's rule
    assert "latent_attention" not in str(jax.jit(
        lambda lp, h: mla.mixer(cfg, lp, h)).trace(lp, h).jaxpr)


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
    for key, value in (("n_group", 8),
                       ("topk_group", 4), ("rope_scaling", {"type": "yarn"}),
                       ("moe_layer_freq", 2), ("scoring_func", "softmax"),
                       ("topk_method", "greedy")):
        with pytest.raises(ValueError, match=f"not built for {key}"):
            program.program_config({**config, key: value})
        with pytest.raises(ValueError, match="written for"):
            program.reference(config).Shape.from_config(
                {**config, key: value})


def test_every_planned_configurations_door_is_listed():
    doors = {program.import_dotted(c["entry"]["config"])
             for c in (cells.load_json(f"{cells.BENCH_DIR}/../{row['file']}")
                       for row in cells.load_benchmark()["configs"])
             if ".plan." in c["entry"]["config"]}
    assert doors == set(plan.ENTRY_CONFIGS) and len(doors) == 4


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
        text = jax.jit(make_decoder(of, mesh, max_new=3)).lower(
            table, jax.ShapeDtypeStruct((2, 8), jnp.int32)).as_text(
                debug_info=True)
        assert ("mla_proj.rope/mla.rotate" in text) == has
        assert ("attn_proj/mla_proj/" in text) != has
        assert "latent_attention" not in text
