"""Power retention (``models/retention.py``) against its plain reference,
``benchmarks/reference/brumby.py``, at the configuration's tiny sizes,
float32, seeded, on the CPU: the feature map, the chunked form against the
recurrence and the quadratic form, prefill then cached steps against the full
forward on logits, which K/V head's state a query head reads, the carry and
its in-place write, loss and gradient of the train path, the form a cell's
cached update takes (the rule of ``ops/retention_update.py``, from the cell's
own files), and the layouts that are refused.  Agreement and
control flow only: nothing here is a time.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import block, retention
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel.mesh import make_mesh

CELL = "brumby-14b-base.decode-2k-128-b48"
# of a deviation of the logits, float32 on both sides: what is left is the
# order of summation (the chunked form and a step's read through the state
# against the reference's one sum over positions); the sound program reads
# 1e-6, and the nearest lower precision, weights rounded to bfloat16,
# reads over ten times the limit
PARITY = 1e-4
EPS = 1e-6

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


def prompts_of(cfg, batch, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


def decoded(cfg, mesh, params, prompts, max_new=6):
    tokens, logits = make_decoder(cfg, mesh, max_new=max_new,
                                  keep_logits=prompts.shape[0])(params,
                                                                prompts)
    return np.asarray(tokens), np.asarray(logits)


def drawn(length, B=2, H=4, G=2, d=8, seed=0, gate=2.0):
    """q, k, v and a log decay that forgets within the sequence."""
    rng = np.random.default_rng(seed + length)
    q = jnp.asarray(rng.normal(size=(B, length, H, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, length, G, d)), jnp.float32)
            for _ in range(2))
    logg = jax.nn.log_sigmoid(jnp.asarray(
        gate + 2 * rng.normal(size=(B, length, G)), jnp.float32))
    return q, k, v, logg


def quadratic(q, k, v, logg):
    """The layer's equations as they are written: every pair, no state."""
    B, T, H, d = q.shape
    R = H // k.shape[2]
    k, v, c = (jnp.repeat(y, R, axis=2)
               for y in (k, v, jnp.cumsum(logg, axis=1)))
    c = jnp.moveaxis(c, 1, -1)
    fade = jnp.where(jnp.tril(jnp.ones((T, T), bool)),
                     c[..., :, None] - c[..., None, :], -jnp.inf)
    a = jnp.einsum("bqhd,bkhd->bhqk", q, k) ** 2 / d * jnp.exp(fade)
    return (jnp.einsum("bhqk,bkhd->bqhd", a, v)
            / (jnp.moveaxis(a.sum(-1), 1, -1)[..., None] + EPS))


def recurrence(q, k, v, logg):
    """``update`` a position at a time from a zero state."""
    B, T, H, d = q.shape
    G = k.shape[2]
    S = jnp.zeros((B, G, retention.state_dim(d), d))
    z = jnp.zeros((B, G, retention.state_dim(d)))
    ys = []
    for t in range(T):
        y, S, z = retention.update(S, z, q[:, t].reshape(B, G, H // G, d),
                                   k[:, t], v[:, t], logg[:, t], EPS)
        ys.append(y.reshape(B, H, d))
    return jnp.stack(ys, axis=1), S, z


# ---- the feature map -----------------------------------------------------------

@pytest.mark.parametrize("d", [1, 2, 3, 8, 15, 16, 128])
def test_phi_of_q_times_phi_of_k_is_the_square_of_q_times_k(d):
    rng = np.random.default_rng(d)
    q, k = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
            for _ in range(2))
    assert retention.phi(q).shape == (5, retention.state_dim(d))
    assert retention.state_dim(d) >= d * (d + 1) // 2
    want = np.sum(np.asarray(q, np.float64) * np.asarray(k, np.float64),
                  -1) ** 2
    got = np.sum(np.asarray(retention.phi(q), np.float64)
                 * np.asarray(retention.phi(k, 0.25), np.float64), -1)
    np.testing.assert_allclose(got, 0.25 * want, rtol=2e-5,
                               atol=2e-6 * want.max())


def test_the_stated_layout_is_the_modules():
    config = cells.resolve(CELL).config
    assert config["retention_state_dim"] == retention.state_dim(
        config["head_dim"]) == 8320
    assert program.tiny(config)["retention_state_dim"] == retention.state_dim(
        program.tiny(config)["head_dim"])
    # every entry is its constant times a product of two of u's, and every
    # unordered pair is somewhere (distinct primes: a product names its pair)
    u = np.array([2.0, 3, 5, 7, 11, 13, 17, 19])
    entries = np.asarray(retention.phi(jnp.asarray(u, jnp.float32))) / (
        np.repeat(retention._weights(8), 8))
    assert {int(a * b) for a in u for b in u} == set(
        np.round(entries).astype(int))


# ---- the three forms -----------------------------------------------------------

@pytest.mark.parametrize("length", [12, 17, 32],
                         ids=["under-a-chunk", "no-multiple", "two-chunks"])
def test_chunked_form_recurrence_and_quadratic_form_agree(length):
    q, k, v, logg = drawn(length)
    want = quadratic(q, k, v, logg)
    y, S, z = retention.chunked(q, k, v, logg, 16, EPS)
    step_y, step_S, step_z = recurrence(q, k, v, logg)
    assert y.shape == want.shape and y.dtype == jnp.float32
    assert error(y, want) < 1e-5 and error(step_y, want) < 1e-5
    assert error(S, step_S) < 1e-5 and error(z, step_z) < 1e-5
    # and a chunk of any length gives the same thing
    other = retention.chunked(q, k, v, logg, 5, EPS)
    assert error(other[0], want) < 1e-5 and error(other[1], S) < 1e-5


def test_the_state_forgets_as_the_gate_says():
    q, k, v, logg = drawn(12)
    _y, S, _z = retention.chunked(q, k, v, logg, 16, EPS)
    _y, kept, _z = retention.chunked(q, k, v, jnp.zeros_like(logg), 16, EPS)
    assert error(S, kept) > 0.1


@pytest.mark.parametrize("kv_head,reads", [(0, (0, 1)), (1, (2, 3))])
def test_a_kv_heads_state_serves_exactly_its_query_heads(kv_head, reads):
    q, k, v, logg = drawn(9)
    moved = v.at[:, :, kv_head].add(1.0)
    for form in (lambda *a: retention.chunked(*a, 4, EPS)[0],
                 lambda *a: recurrence(*a)[0]):
        delta = np.abs(np.asarray(form(q, k, moved, logg)
                                  - form(q, k, v, logg))).max(axis=(0, 1, 3))
        assert all(delta[h] > 1e-2 for h in reads), delta
        assert all(delta[h] == 0 for h in range(4) if h not in reads), delta


# ---- the decoder ---------------------------------------------------------------

@pytest.mark.parametrize("prompt_len", [12, 17, 3],
                         ids=["two-chunks", "no-multiple", "under-a-chunk"])
def test_prefill_then_cached_steps_give_the_references_logits(prompt_len):
    """The state and the normaliser handed over by the prefill and carried
    by the cached steps: the logits every generated token was picked from
    are the reference's full forward over prompt plus continuation."""
    ref, shape, cfg, mesh, params = tiny()
    assert prompt_len % cfg.retention.chunk
    prompts = prompts_of(cfg, 3, prompt_len)
    tokens, logits = decoded(cfg, mesh, params, prompts)
    assert tokens.shape == (3, prompt_len + 6)
    assert logits.shape == (3, 6, cfg.vocab) and logits.dtype == np.float32
    np.testing.assert_array_equal(tokens[:, :prompt_len], prompts)
    np.testing.assert_array_equal(logits.argmax(-1), tokens[:, prompt_len:])
    want = ref.logits(shape, params, tokens)[:, prompt_len - 1:-1]
    assert error(logits, want) < PARITY


def test_the_parity_limit_fails_at_the_nearest_lower_precision():
    """The program on weights rounded to bfloat16 (this CPU multiplies no
    bfloat16 pair into float32, so the products stay float32), against the
    reference on the weights as drawn."""
    ref, shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 3, 12)
    rounded = {k: v.astype(jnp.bfloat16).astype(v.dtype)
               for k, v in params.items()}
    tokens, logits = decoded(cfg, mesh, rounded, prompts)
    want = ref.logits(shape, params, tokens)[:, 11:-1]
    assert error(logits, want) > 10 * PARITY


def test_prefill_in_groups_and_in_one_pass_agree():
    _ref, _shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 6, 8)
    whole = decoded(dataclasses.replace(cfg, prefill_tokens=0), mesh, params,
                    prompts)
    for tokens_a_pass in (16, 8):
        sliced = dataclasses.replace(cfg, prefill_tokens=tokens_a_pass)
        tokens, logits = decoded(sliced, mesh, params, prompts)
        np.testing.assert_array_equal(tokens, whole[0])
        assert error(logits, whole[1]) < 1e-5


def test_the_carry_is_the_state_alone_and_a_step_writes_its_layer_in_place():
    _ref, _shape, cfg, mesh, _params = tiny()
    assert [m.__name__ for m in block.mechanisms(cfg)] == [
        "ompi_tpu.models.retention"]
    G, d, D = cfg.kv_heads, cfg.head_dim, retention.state_dim(cfg.head_dim)
    S_c, z_c = retention.carry(cfg, mesh, 3, 99)
    assert S_c.shape == (cfg.n_layers, 3, G, D, d) and S_c.dtype == jnp.float32
    assert z_c.shape == (cfg.n_layers, 3, G, D)         # no position in them
    S, z = (jnp.ones((cfg.n_layers, 2, *s.shape[2:])) for s in (S_c, z_c))
    into = retention.carried(cfg, mesh, iter([S, z]), 99, [S_c, z_c], g=1,
                             group=1)
    assert float(into[0][:, 1:].min()) == 1 and float(into[0][:, 0].max()) == 0
    q, k, v, logg = drawn(1, B=3, H=cfg.n_heads, G=G, d=d)
    step = jax.jit(lambda stacks: retention.core(
        cfg, q, k, v, logg, (stacks, jnp.int32(1))))
    y, (S_n, z_n) = step([S_c + 1.0, z_c + 1.0])
    assert y.shape == (3, 1, cfg.n_heads, d)
    assert float(jnp.abs(S_n[0] - 1.0).max()) == 0      # layer 0 as it was
    assert float(jnp.abs(S_n[1] - 1.0).max()) > 0 and S_n.shape == S_c.shape
    assert float(jnp.abs(z_n[1] - 1.0).max()) > 0
    text = step.lower([S_c, z_c]).as_text()
    assert text.count("dynamic_update_slice") == 2
    assert "optimization_barrier" in text       # the read before the write


# ---- the form a cell's cached update takes -------------------------------------

def _cell_state(**changes):
    """(state dtype, the matrix state's shape a layer) of the cell's cached
    step, from its configuration and traffic files, with ``changes``."""
    cell = cells.resolve(CELL)
    cfg = program.program_config({**cell.config, **changes})
    shape, _z = retention.state_shapes(cfg, cell.traffic["batch"])
    return cfg, shape


# Which form every cached step of a retention cell takes (PERF.md section 5).
@pytest.mark.parametrize("tpu,changes,takes", [
    pytest.param(True, {}, (1664, 128), id="brumby-step-on-the-chip"),
    pytest.param(False, {}, None, id="brumby-step-on-the-cpu"),
    pytest.param(True, {"retention_state_dtype": "bfloat16"}, None,
                 id="a-bfloat16-state"),
    pytest.param(True, {"head_dim": 64}, None, id="heads-of-64"),
])
def test_which_update_each_retention_cell_takes(tpu, changes, takes,
                                                monkeypatch):
    """One rule from static facts (``retention_update.block``): on TPUs a
    float32 state of heads that tile goes through the kernel where it lies
    in the stack, a fifth of a (sequence, K/V head)'s rows a block, once a
    layer and step; anywhere else the state is read by one float32 product
    on the matrix unit (a sum of products makes the compiler copy the
    layer's state out of the stack first), a barrier, then the write."""
    from ompi_tpu.ops import _chip
    from ompi_tpu.ops import retention_update

    cfg, shape = _cell_state(**changes)
    d = changes.get("head_dim", 128)
    D = retention.state_dim(d)
    assert shape == (48, 8, D, d)
    assert cfg.retention.state_dtype == changes.get(
        "retention_state_dtype", "float32")
    B, G, _D, _d = shape
    assert retention_update.block(tpu, cfg.retention.state_dtype,
                                  D, d) == takes
    # and the core asks that rule of the stack it is handed, and nothing else
    monkeypatch.setattr(_chip, "_traced_for_tpus", lambda: tpu)
    stack = jax.ShapeDtypeStruct((cfg.n_layers, *shape),
                                 cfg.retention.state_dtype)
    if takes:
        held = retention._state_before(stack, 1)
        assert isinstance(held, retention.InPlace) and held.stack is stack
        assert (held.shape, held.dtype) == (shape, jnp.float32)
        return
    before = jax.eval_shape(lambda s: retention._state_before(s, 1), stack)
    assert (before.shape, before.dtype) == (shape, jnp.float32)
    R = cfg.n_heads // G
    f32, cdt = jnp.float32, jnp.bfloat16
    jaxpr = jax.make_jaxpr(retention.read, static_argnums=6)(
        *(jax.ShapeDtypeStruct(s, t) for s, t in (
            ((B, G, D, d), f32), ((B, G, D), f32), ((B, G, R, d), cdt),
            ((B, G, d), cdt), ((B, G, d), cdt), ((B, G), f32))), EPS)
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    over_state = [e for e in dots if (B, G, D, d) in
                  [tuple(v.aval.shape) for v in e.invars]]
    assert len(over_state) == 1
    assert "HIGHEST" in str(over_state[0].params["precision"])
    assert not any("pallas" in e.primitive.name for e in jaxpr.eqns)


# ---- the train path ------------------------------------------------------------

def test_loss_and_gradient_equal_the_references():
    """``make_loss_fn`` and ``jax.grad`` of it, which is what
    ``make_train_step`` differentiates, against the reference's loss and
    ``jax.grad`` of that: through the chunked form's backward pass."""
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


def test_the_programs_own_initializer_has_the_trees_leaves():
    ref, shape, cfg, _mesh, params = tiny()
    made = tfm.init_params(cfg, seed=0)
    assert {k: v.shape for k, v in made.items()} == {
        k: v.shape for k, v in params.items()}
    assert set(tfm.layer_leaves(cfg)) == set(made) - {"emb", "head", "lnf"}


# ---- what is refused -----------------------------------------------------------

def test_the_factory_reads_flat_keys():
    _ref, _shape, cfg, _mesh, _params = tiny()
    rt = cfg.retention
    assert isinstance(rt, retention.Retention)
    assert (rt.degree, rt.chunk, rt.state_dtype) == (2, 5, "float32")
    assert cfg.kv_heads == 2 and cfg.head_dim == 8 != cfg.d_model // 4
    assert isinstance(cfg.rope_theta, float) and cfg.qk_norm == "head"
    with pytest.raises(ValueError, match="degree 3"):
        retention.Retention(degree=3)


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_the_core_is_refused_over_sp_and_tp(axis):
    _ref, _shape, cfg, _mesh, params = tiny()
    shape = {"dp": 1, "sp": 1, "tp": 1, axis: 2}
    mesh = make_mesh(shape, devices=jax.devices()[:2])
    tokens = prompts_of(cfg, 2, cfg.seq)
    with pytest.raises(ValueError, match=f"{axis} == 1 only"):
        jax.jit(tfm.make_loss_fn(cfg, mesh))(params, tokens)
    if axis == "tp":
        with pytest.raises(ValueError, match="tp == 1 only"):
            make_decoder(cfg, mesh, max_new=2)


def test_the_cores_scopes_are_in_the_vocabulary():
    from ompi_tpu.core import scopes

    assert {"retention.scan", "retention.update"} <= set(scopes.SCOPES)
