"""A plan whose upper rows own no state and read the lower rows'
(``plan.shared_state_config``: "selective" and "window" rows under one
"selective" row that hands its scan output on and one "differential" row that
hands its K and V on, then "gmu" and "shared" rows that read them), LayerNorm
with a bias and a tied head, against the plain reference,
``benchmarks/reference/phi4_flash.py``, at the configuration's tiny sizes
(twelve rows, a window of 5, 4 query heads over 2 K/V heads, a step's rank of
7), float32, seeded, on the CPU: the door's plan, whole sequences, prefill
then cached steps while the ring wraps, loss and gradient, the prefill's
upper rows at one position, what a decoder carries, what is refused.
Agreement only: nothing here is a time.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import differential, plan, selective
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder

CELL = "phi-4-mini-flash-reasoning.decode-16k-256-b16"
PARITY = 1e-4


@pytest.fixture(scope="module")
def tiny():
    """(reference, its shape, the program's config in float32, a one-device
    mesh, parameters from the benchmark's initializer with every leaf that
    starts at one drawn away from it, the tiny configuration)."""
    config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
    ref = program.reference(config)
    cfg = program.program_config(config)
    mesh = program.mesh(config, jax.devices()[:1])
    params = program.init_params(
        ref, config, program.param_shardings(config, cfg, mesh), seed=11)
    rng = np.random.default_rng(12)
    ones = [k for k, (_dims, std) in
            program.param_table(ref, config).items() if std is None]
    params = {k: (jnp.asarray(rng.uniform(0.5, 1.5, size=v.shape), v.dtype)
                  if k in ones else v) for k, v in params.items()}
    return ref, ref.Shape.from_config(config), cfg, mesh, params, config


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


def prompts_of(cfg, batch, length, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


def test_the_doors_plan_is_the_published_layout(tiny):
    """The tiny plan, and the cell's at its real sizes, read and not run."""
    ref, shape, cfg, mesh, _params, _config = tiny
    pl = cfg.plan
    kinds = [mixer for mixer, _mlp in pl.layers]
    assert kinds == ["selective", "window"] * 3 + [
        "selective", "differential"] + ["gmu", "shared"] * 2
    assert all(mlp == "dense" for _mixer, mlp in pl.layers)
    assert pl.reads == ((8, 6), (9, 7), (10, 6), (11, 7))
    assert pl.layernorm and plan._upper(pl) == 8
    assert (pl.window.window, pl.selective.dt_rank, cfg.head_dim) == (5, 7, 26)
    assert set(plan.leaf_names(cfg)) | {"emb", "lnf"} == set(
        ref.param_init(shape))

    cfg = program.program_config(cells.resolve(CELL).config)
    pl = cfg.plan
    kinds = [mixer for mixer, _mlp in pl.layers]
    assert len(kinds) == 32 and kinds[16:18] == ["selective", "differential"]
    assert kinds[:16] == ["selective", "window"] * 8
    assert kinds[18:] == ["gmu", "shared"] * 7
    assert dict(pl.reads) == {**{row: 16 for row in range(18, 32, 2)},
                              **{row: 17 for row in range(19, 32, 2)}}
    assert plan._upper(pl) == 18
    sz = pl.selective
    assert (sz.d_inner, sz.d_state, sz.d_conv, sz.dt_rank, sz.state_dtype) == (
        5120, 16, 4, 160, "float32")
    assert pl.window == differential.Differential(40, 20, 64, 512, False, "w")
    assert pl.differential == differential.Differential(40, 20, 64, 0, False,
                                                        "a")
    assert pl.shared == differential.Differential(40, 20, 64, 0, True, "x")
    assert pl.gmu.width == 5120 and cfg.tie_head and cfg.vocab == 200_064
    # readers own nothing: 9 x 2 + 8 x 2 + 2 buffers for 32 rows, and only
    # the full layer's K and V grow
    grows = plan.grows(cfg)
    assert len(grows) == 9 * 2 + 8 * 2 + 2 == 36
    assert [i for i, g in enumerate(grows) if g] == [34, 35]
    shapes = [shape for shape, _dtype, _axis in plan._buffers(cfg, 16, 16384)]
    assert shapes[:4] == [(16, 3, 5120), (16, 16, 5120),
                          (16, 512, 20, 64), (16, 512, 20, 64)]
    assert shapes[34:] == [(16, 16384, 20, 64)] * 2
    assert [differential.constants(pl.window, layer)["lam0"]
            for layer in (0, 17)] == pytest.approx([0.2, 0.79634], abs=1e-4)


def test_whole_sequences_are_the_references(tiny):
    ref, shape, cfg, mesh, params, _config = tiny
    tokens = prompts_of(cfg, 2, 23, seed=5)     # no multiple of the window
    got = jax.jit(tfm.make_forward(cfg, mesh))(params, tokens)
    assert error(got, ref.logits(shape, params, tokens)[:, :]) < PARITY


def test_blocks_of_queries_and_bands_of_keys_are_the_whole_scores(
        tiny, monkeypatch):
    """With blocks of 4 queries a window layer attends bands (4 + 128 keys is
    longer than these sequences, so the band is told to be 8 back) and the
    full and cross layers blocks over all keys."""
    ref, shape, cfg, mesh, params, _config = tiny
    tokens = prompts_of(cfg, 2, 23, seed=6)
    want = ref.logits(shape, params, tokens)[:, :]
    monkeypatch.setattr(differential, "QUERY_BLOCK", 4)
    got = jax.jit(tfm.make_forward(cfg, mesh))(params, tokens)
    assert error(got, want) < PARITY
    q = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 300, 1, 2, 2, 8)), jnp.float32)
    k = jnp.asarray(np.random.default_rng(1).normal(
        size=(1, 300, 1, 2, 8)), jnp.float32)
    v = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, 300, 1, 16)), jnp.float32)
    banded = differential._whole(q, k, v, 7)        # 4 + 128 < 300: a band
    monkeypatch.setattr(differential, "QUERY_BLOCK", 512)
    assert error(banded, differential._whole(q, k, v, 7)) < 1e-5


@pytest.mark.parametrize("prompt_len,max_new", [(13, 14), (3, 12)])
def test_prefill_then_cached_steps_are_the_full_forward(tiny, prompt_len,
                                                        max_new):
    """Prompts of 13 (no multiple of the window of 5, and 14 steps wrap the
    ring more than twice) and of 3 (under the window and under the
    convolution's taps)."""
    ref, shape, cfg, mesh, params, _config = tiny
    prompts = prompts_of(cfg, 4, prompt_len, seed=prompt_len)
    cfg = dataclasses.replace(cfg, prefill_tokens=2 * prompt_len)
    answer, z = make_decoder(cfg, mesh, max_new=max_new, keep_logits=4)(
        params, prompts)
    want = ref.logits(shape, params, np.asarray(answer))[:, prompt_len - 1:-1]
    assert error(z, want) < PARITY
    assert np.array_equal(np.asarray(z).argmax(-1),
                          np.asarray(answer)[:, prompt_len:])
    whole = jax.jit(tfm.make_forward(cfg, mesh))(params, answer)
    assert error(z, whole[:, prompt_len - 1:-1]) < PARITY


def test_loss_and_gradient_are_the_references(tiny):
    ref, shape, cfg, mesh, params, _config = tiny
    tokens = jnp.asarray(prompts_of(cfg, 2, cfg.seq, seed=3))
    loss_fn = tfm.make_loss_fn(cfg, mesh)
    got, got_grad = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)

    def theirs(p):
        return ref.nll_sum(shape, p, tokens) / (tokens.shape[0]
                                                * (tokens.shape[1] - 1))

    want, want_grad = jax.value_and_grad(theirs)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in params:
        assert np.asarray(got_grad[name]).any(), name
        assert error(got_grad[name], want_grad[name]) < 3e-3, name


def test_a_train_step_runs(tiny):
    """On the program's own initial parameters (biases at nought, the
    family's ``A`` and steps)."""
    _ref, _shape, cfg, mesh, _params, _config = tiny
    step, init = tfm.make_train_step(cfg, mesh)
    tokens = jnp.asarray(prompts_of(cfg, 2, cfg.seq, seed=4))
    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg, 0))
    assert not np.asarray(params["lnfb"]).any()
    _params, _state, loss = step(params, init(params), tokens)
    assert np.isfinite(float(loss)) and float(loss) < 6


def test_a_prefill_runs_the_upper_rows_at_one_position(tiny):
    """With and without the skip the last position's hidden state is the
    same, the collected states are the same, and the skipping pass's jaxpr
    holds the upper rows' products at one position."""
    _ref, _shape, cfg, mesh, params, _config = tiny
    from jax.sharding import PartitionSpec as P

    tokens = jnp.asarray(prompts_of(cfg, 2, 13, seed=9))
    comm = tfm._mesh_comm(mesh)

    def passes(forward_only):
        def local(params, tokens):
            return plan.backbone(cfg, comm, params, tokens, collect_kv=True,
                                 forward_only=forward_only)
        return jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_vma=False)

    h_skip, states_skip = jax.jit(passes(True))(params, tokens)
    h_all, states_all = jax.jit(passes(False))(params, tokens)
    assert h_skip.shape == (2, 1, cfg.d_model) and h_all.shape[1] == 13
    assert error(h_skip[:, -1], h_all[:, -1]) < PARITY
    assert len(states_skip) == len(states_all) == len(plan.grows(cfg))
    for a, b in zip(states_skip, states_all):
        assert a.shape == b.shape and error(a, b) < PARITY

    def widths(forward_only):
        """The positions of every product against an upper row's leaf."""
        jaxpr = jax.make_jaxpr(passes(forward_only))(params, tokens)
        found = []

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "dot_general":
                    shapes = [v.aval.shape for v in eqn.invars]
                    if shapes[1] in ((cfg.d_model, cfg.plan.gmu.width),
                                     (cfg.plan.gmu.width, cfg.d_model)):
                        found.append(shapes[0][1])
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    # the gated memory units' two products (and the selective rows' output
    # projection, which has the second's shape, on every position)
    assert sorted(set(widths(True))) == [1, 13]
    assert widths(True).count(1) == 2 * 2 and set(widths(False)) == {13}


def test_the_kernels_are_taken_where_the_rule_says(tiny, monkeypatch):
    """Told that it is traced for TPUs, a prefill of whole chunks scans
    through ``ops/selective_scan.py`` (interpret mode here) and reads what
    ``selective.scan`` reads."""
    from ompi_tpu.ops import _chip, selective_scan

    sz = selective.Selective(d_inner=1024, d_state=4, dt_rank=3)
    assert not selective.fused(sz, 256, True)
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: True)
    assert selective.fused(sz, 256, True)
    assert not selective.fused(sz, 256, False)      # a trainer
    assert not selective.fused(sz, 200, True)       # no whole chunks
    assert not selective.fused(dataclasses.replace(sz, d_inner=80), 256, True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 256, 1024)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(2, 256, 1024)),
                                     jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.normal(0, 2, size=(4, 1024)), jnp.float32))
    b, c = (jnp.asarray(rng.normal(size=(2, 256, 4)), jnp.float32)
            for _ in "bc")
    y, end = jax.jit(selective_scan.selective_scan)(x, dt, a, b, c)
    want_y, want_end = jax.jit(selective.scan)(x, dt, a, b, c)
    assert error(y, want_y) < 1e-5 and error(end, want_end) < 1e-5


def test_a_reader_before_its_source_or_of_the_wrong_kind_is_refused(tiny):
    *_rest, cfg, _mesh, _params, config = tiny
    pl = cfg.plan
    for reads, said in (
            (((8, 9),) + pl.reads[1:], "an earlier row"),      # a later row
            (((8, 7),) + pl.reads[1:], "hands that on"),        # K/V for m
            (pl.reads[1:], "names the source None"),            # none named
            (pl.reads + ((0, 0),), "reads None")):              # no reader
        with pytest.raises(ValueError, match=said):
            plan.check_reads(dataclasses.replace(pl, reads=reads))
    for key, value, said in (("mb_per_layer", 1, "mb_per_layer"),
                             ("hidden_act", "gelu", "hidden_act"),
                             ("mlp_bias", True, "a bias"),
                             ("num_hidden_layers", 10, "a depth"),
                             ("num_key_value_heads", 1, "paired")):
        with pytest.raises(ValueError, match=f"not built for .*{said}"):
            program.program_config({**config, key: value})
        with pytest.raises(ValueError, match="written for"):
            program.reference(config).Shape.from_config(
                {**config, key: value})
    mesh = program.mesh({"mesh": {"dp": 1, "sp": 1, "tp": 2}},
                        jax.devices()[:2]) if len(jax.devices()) > 1 else None
    if mesh is not None:
        with pytest.raises(ValueError, match="tp == 1"):
            plan.check_mesh(cfg, mesh)
