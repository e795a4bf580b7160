"""KV-cache greedy decode (models/decode.py) vs the full-forward path.

The cached single-token steps must reproduce exactly the tokens a
(recomputed-from-scratch) full forward picks — the standard
cache-consistency contract.  And the compiled step must not move its
cache: the last tests read the compiler's output, they time nothing.
"""

import dataclasses
import math
import re

import numpy as np
import pytest

from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel.mesh import make_mesh
from ompi_tpu.parallel.moe import EXPERT_LEAVES
from tests.parallel.compiled import _cell

CFG = tfm.TransformerConfig(
    vocab=97, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=64,
    attention="xla", compute_dtype="float32")


def _mesh():
    return make_mesh({"dp": 4, "sp": 1, "tp": 2})


def _greedy_reference(fwd, params, prompt, max_new):
    """Grow the sequence one token at a time via full forwards."""
    cur = prompt
    for _ in range(max_new):
        logits = np.asarray(fwd(params, cur))
        nxt = logits[:, -1, :].argmax(-1).astype(np.int32)[:, None]
        cur = np.concatenate([cur, nxt], axis=1)
    return cur


def _mesh_of(mesh_shape):
    import jax

    return make_mesh(mesh_shape,
                     devices=jax.devices()[:math.prod(mesh_shape.values())])


def _assert_cached_equals_full(cfg, mesh_shape, max_new, prompt_len, seed,
                               forward_on=None):
    """``forward_on``: the mesh of the full forward where it is not the
    decoder's."""
    import jax

    mesh = _mesh_of(mesh_shape)
    params = tfm.init_params(cfg)
    fwd = jax.jit(tfm.make_forward(cfg, _mesh_of(forward_on or mesh_shape)))
    prompt = np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(4, prompt_len)).astype(np.int32)
    got = np.asarray(make_decoder(cfg, mesh, max_new=max_new)(params, prompt))
    assert got.shape == (4, prompt_len + max_new)
    np.testing.assert_array_equal(got[:, :prompt_len], prompt)
    np.testing.assert_array_equal(
        got, _greedy_reference(fwd, params, prompt, max_new))


# max_new 1 and 2: a token loop of length 0 and 1
@pytest.mark.parametrize("mesh_shape,max_new,n_layers", [
    pytest.param({"dp": 4, "sp": 1, "tp": 2}, 5, 2, id="dp4tp2"),
    pytest.param({"dp": 4, "sp": 1, "tp": 2}, 1, 2, id="max_new1"),
    pytest.param({"dp": 4, "sp": 1, "tp": 2}, 2, 2, id="max_new2"),
    pytest.param({"dp": 4, "sp": 1, "tp": 2}, 4, 1, id="one_layer"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1}, 4, 3, id="dp2"),
    pytest.param({"dp": 1, "sp": 1, "tp": 2}, 4, 3, id="tp2"),
])
def test_cached_decode_matches_full_forward(mesh_shape, max_new, n_layers):
    _assert_cached_equals_full(dataclasses.replace(CFG, n_layers=n_layers),
                               mesh_shape, max_new, prompt_len=8, seed=0)


# ---- the table by vocabulary rows over tp (``param_specs``) -----------------

ONE = {"dp": 1, "sp": 1, "tp": 1}
ROWS = dataclasses.replace(CFG, vocab=96)       # tp = 2 and 4 divide it


@pytest.mark.parametrize("mesh_shape", [
    pytest.param({"dp": 1, "sp": 1, "tp": 2}, id="tp2"),
    pytest.param({"dp": 2, "sp": 1, "tp": 2}, id="dp2tp2"),
    pytest.param({"dp": 1, "sp": 1, "tp": 4}, id="tp4")])
def test_cached_decode_with_the_tables_rows_over_tp(mesh_shape):
    """Each rank of ``tp`` holds ``1/tp`` of the tied table's rows: the
    prefill's and the cached step's lookup are completed by a psum and
    their logits gathered over ``tp`` before the argmax, and the tokens are
    those of full forward passes on one device, which holds the table
    whole."""
    from jax.sharding import PartitionSpec as P

    assert tfm.param_specs(P, ROWS, _mesh_of(mesh_shape))["emb"] == P(
        "tp", None)
    _assert_cached_equals_full(ROWS, mesh_shape, 5, prompt_len=8, seed=0,
                               forward_on=ONE)


def test_kept_logits_are_the_whole_vocabularys_with_the_rows_over_tp():
    """``keep_logits`` on ``tp`` = 2 with the table split: the tokens are
    what they are without it, and the logits are (n, max_new, vocab), the
    one-device full forward's."""
    import jax

    mesh = _mesh_of({"dp": 1, "sp": 1, "tp": 2})
    params = tfm.init_params(ROWS)
    prompts = np.random.default_rng(6).integers(
        0, ROWS.vocab, size=(4, 8)).astype(np.int32)
    plain = np.asarray(make_decoder(ROWS, mesh, max_new=5)(params, prompts))
    tokens, logits = make_decoder(ROWS, mesh, max_new=5, keep_logits=2)(
        tfm.shard_params(ROWS, mesh, params), prompts)
    np.testing.assert_array_equal(np.asarray(tokens), plain)
    want = np.asarray(jax.jit(tfm.make_forward(ROWS, _mesh_of(ONE)))(
        params, plain[:2]))[:, 7:-1]
    assert logits.shape == (2, 5, ROWS.vocab)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=0,
                               atol=2e-5 * want.std())


def test_decoder_called_again_returns_the_same_tokens():
    """The cache is loop carry written in place: nothing of one call may
    be left in a buffer the next call reads."""
    mesh = _mesh()
    params = tfm.init_params(CFG)
    rng = np.random.default_rng(5)
    prompt, other = rng.integers(0, CFG.vocab,
                                 size=(2, 4, 8)).astype(np.int32)
    dec = make_decoder(CFG, mesh, max_new=6)
    first = np.asarray(dec(params, prompt))
    between = np.asarray(dec(params, other))
    again = np.asarray(dec(params, prompt))
    np.testing.assert_array_equal(first, again)
    assert (between != first).any()


def test_sampled_decode_deterministic_and_valid():
    """temperature>0: same seed → same tokens; different seeds diverge;
    top_k truncation keeps tokens in-vocab."""
    mesh = _mesh()
    params = tfm.init_params(CFG)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab, size=(4, 8)).astype(np.int32)
    dec = make_decoder(CFG, mesh, max_new=6, temperature=0.8, top_k=10)
    a = np.asarray(dec(params, prompt, np.int32(7)))
    b = np.asarray(dec(params, prompt, np.int32(7)))
    c = np.asarray(dec(params, prompt, np.int32(8)))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()          # different seed, different draws
    assert a.min() >= 0 and a.max() < CFG.vocab
    np.testing.assert_array_equal(a[:, :8], prompt)

    with pytest.raises(ValueError, match="top_k"):
        make_decoder(CFG, _mesh(), max_new=2, top_k=5)


def test_decode_rejects_sp():
    mesh_sp = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    with pytest.raises(ValueError, match="sp == 1"):
        make_decoder(CFG, mesh_sp, max_new=2)


@pytest.mark.parametrize("mesh_shape,max_new,n_layers", [
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 1}, 4, 2, id="two_layers"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 1}, 1, 2, id="max_new1"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 1}, 2, 2, id="max_new2"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 1}, 4, 1, id="one_layer"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 1}, 4, 3, id="dp2"),
    pytest.param({"dp": 1, "sp": 1, "tp": 2, "ep": 1}, 4, 3, id="tp2"),
])
def test_moe_cached_decode_matches_full_forward(mesh_shape, max_new,
                                                n_layers):
    """A routed configuration's decode: the same dropless routing as
    training (a token's experts depend on that token alone), so the cached
    path reproduces the full forward."""
    cfg = dataclasses.replace(CFG, moe_experts=4, moe_top_k=2,
                              n_layers=n_layers)
    _assert_cached_equals_full(cfg, mesh_shape, max_new, prompt_len=6, seed=1)


# ---- an OLMoE-shaped block: dropless routed experts, q/k-norm, untied head --

OLMOE = tfm.TransformerConfig(
    vocab=97, d_model=64, n_heads=4, n_layers=2, d_ff=32, seq=64,
    attention="xla", compute_dtype="float32", remat=False,
    moe_experts=8, moe_top_k=2, moe_gated=True, qk_norm=True,
    norm_eps=1e-5, tie_head=False)


def _cached_logits(cfg, mesh, params, tokens, prompt_len):
    """Logits of positions ``prompt_len - 1 ..`` of ``tokens`` through the
    decoder's own pieces: the backbone as prefill, then ``block.block`` with
    a carry a token at a time against the cache, fed the given tokens."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.models import block
    from ompi_tpu.mpi.device_comm import DeviceCommunicator

    comm = DeviceCommunicator(mesh, tuple(mesh.axis_names))
    cdt = jnp.dtype(cfg.compute_dtype)
    steps = tokens.shape[1] - prompt_len

    def local(params, tokens):
        head = tfm._head(cfg, params).astype(cdt)
        h, (ks, vs) = tfm._local_backbone(
            cfg, comm, params, tokens[:, :prompt_len], collect_kv=True)
        pad = [(0, 0), (0, 0), (0, steps), (0, 0), (0, 0)]
        stacks = [[jnp.pad(ks, pad), jnp.pad(vs, pad)]]
        out = [tfm._whole_vocab(cfg, h[:, -1, :] @ head.T)]
        for pos in range(prompt_len, prompt_len + steps):
            h = tfm._lookup(cfg, params["emb"], tokens[:, pos])[:, None, :]
            for l in range(cfg.n_layers):
                lp = {k: params[k] if k in EXPERT_LEAVES
                      else params[k][l] for k in tfm.layer_leaves(cfg)}
                h, stacks = block.block(
                    cfg, comm, lp, h, jnp.int32(pos)[None],
                    carry=(stacks, l, jnp.int32(pos)))
            out.append(tfm._whole_vocab(cfg, tfm._rmsnorm(
                h, params["lnf"], cfg.norm_eps)[:, 0, :] @ head.T))
        return jnp.stack(out, axis=1)

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(tfm.param_specs(P, cfg, mesh),
                                    P("dp", None)),
        out_specs=P("dp", None, None), check_vma=False))(params, tokens)


@pytest.mark.parametrize("mesh_shape,vocab", [
    pytest.param({"dp": 1, "sp": 1, "tp": 1}, 97, id="one"),
    pytest.param({"dp": 2, "sp": 1, "tp": 2}, 97, id="dp2tp2"),
    pytest.param({"dp": 2, "sp": 1, "tp": 2}, 96, id="dp2tp2-rows-over-tp")])
def test_olmoe_shaped_cached_logits_equal_the_full_forwards(mesh_shape,
                                                            vocab):
    """Logits, not tokens: the routed tail at one token a sequence (8 rows
    over 8 experts), the q/k-norm (summed over tp where the heads are
    split) and the untied head in the cached step, against the full
    forward on one device, both in float32.  What is left is the order of
    summation.  At 96 rows ``tp`` = 2 splits the lookup table and the
    untied head, each a leaf of its own, by rows; 97 it leaves whole."""
    import jax
    from jax.sharding import PartitionSpec as P

    cfg = dataclasses.replace(OLMOE, vocab=vocab)
    mesh = _mesh_of(mesh_shape)
    split = P("tp", None) if vocab == 96 else P()
    specs = tfm.param_specs(P, cfg, mesh)
    assert specs["emb"] == specs["head"] == split
    params = tfm.init_params(cfg, seed=3)
    # norm scales away from one, so that a norm left out would show
    rng = np.random.default_rng(4)
    for leaf in ("qn", "kn", "ln1", "ln2", "lnf"):
        params[leaf] = rng.uniform(0.5, 1.5, size=params[leaf].shape).astype(
            np.float32)
    tokens = rng.integers(0, cfg.vocab, size=(4, 12)).astype(np.int32)
    full = np.asarray(jax.jit(tfm.make_forward(cfg, _mesh_of(ONE)))(
        params, tokens))
    got = np.asarray(_cached_logits(cfg, mesh, params, tokens, prompt_len=9))
    assert got.shape == (4, 4, cfg.vocab)
    np.testing.assert_allclose(got, full[:, 8:], rtol=0, atol=2e-5 * full.std())


def test_olmoe_shaped_cached_decode_matches_full_forward():
    _assert_cached_equals_full(OLMOE, {"dp": 2, "sp": 1, "tp": 1}, 3,
                               prompt_len=8, seed=2)


# At the tiny sizes of benchmarks/configs/olmoe-1b-7b.json, float32 on both
# sides, the program's logits sit within 1e-5 of their deviation of the
# plain reference's (measured 1.9e-6: the order of summation).  Each
# variant below moves them by half a deviation or more (0.63 to 2.6), so
# 1e-4 tells them apart by orders either way.
OLMOE_PARITY = 1e-4


@pytest.mark.parametrize("variant,holds", [
    pytest.param({}, True, id="as-published"),
    pytest.param({"moe_top_k": -1}, False, id="one-expert-fewer"),
    pytest.param({"qk_norm": False}, False, id="no-qk-norm"),
    pytest.param({"norm_eps": 1e-6}, False, id="eps-1e-6"),
])
def test_olmoe_program_logits_against_the_plain_reference(variant, holds):
    """The test with teeth: the program as the configuration file builds it
    agrees with ``benchmarks/reference/olmoe.py`` on logits, and a program
    with one expert a token fewer (7 of 8 at the published size), without
    the q/k-norm or with the dense block's eps does not."""
    import copy

    import jax

    from benchmarks.lib import cells, program

    config = copy.deepcopy(program.tiny(cells.resolve(
        "olmoe-1b-7b.decode-1k-128").config))
    config["entry"]["options"]["compute_dtype"] = "float32"
    ref = program.reference(config)
    cfg = program.program_config(config)
    mesh = program.mesh(config, jax.devices()[:1])
    params = program.init_params(
        ref, config, program.param_shardings(config, cfg, mesh), seed=11)
    rng = np.random.default_rng(12)
    params = {k: (jax.numpy.asarray(rng.uniform(0.5, 1.5, size=v.shape),
                                    v.dtype)
                  if k in ("qn", "kn", "ln1", "ln2", "lnf") else v)
              for k, v in params.items()}
    tokens = rng.integers(0, cfg.vocab, size=(4, cfg.seq)).astype(np.int32)
    want = np.asarray(ref.logits(ref.Shape.from_config(config), params,
                                 tokens))

    if "moe_top_k" in variant:
        variant = {"moe_top_k": cfg.moe_top_k - 1}
    cfg = dataclasses.replace(cfg, **variant)
    if not cfg.qk_norm:
        params = {k: v for k, v in params.items() if k not in ("qn", "kn")}
    got = np.asarray(jax.jit(tfm.make_forward(cfg, mesh))(params, tokens))
    error = np.abs(got - want).max() / want.std()
    assert (error < OLMOE_PARITY) == holds, error


# ---- one block for both passes (models/block.py) -----------------------------

def _hybrid(**changes):
    """A tiny hybrid block, float32 throughout, every multiplier away from
    one; ``key_multiplier`` far from it."""
    from ompi_tpu.models.ssm import HybridBlock

    block = HybridBlock(
        d_ssm=48, d_state=6, n_groups=2, n_heads=6, d_conv=4, chunk=4,
        embedding_multiplier=1.5, attention_in_multiplier=0.8,
        attention_out_multiplier=1.25, key_multiplier=3.0,
        lm_head_multiplier=0.7, ssm_in_multiplier=1.1,
        ssm_multipliers=(0.9, 1.2, 0.8, 1.1, 0.95), ssm_out_multiplier=0.6,
        mlp_multipliers=(1.3, 0.75), state_dtype="float32")
    return dataclasses.replace(CFG, n_kv_heads=2, remat=False, hybrid=block,
                               **changes)


def _drawn(cfg, seed):
    """Seeded parameters, every norm's scale drawn away from one."""
    params = tfm.init_params(cfg, seed=seed)
    rng = np.random.default_rng(seed + 1)
    for leaf in ("qn", "kn", "ln1", "ln2", "lnf"):
        if leaf in params:
            params[leaf] = rng.uniform(0.5, 1.5, size=params[leaf].shape
                                       ).astype(np.float32)
    return params


@pytest.mark.parametrize("qk_norm", [True, "head"], ids=["whole", "head"])
def test_a_key_multiplier_beside_qk_norm_is_the_same_in_both_passes(qk_norm):
    """A hybrid block's ``key_multiplier`` AND q/k-norm: norm, then multiply,
    in the whole-sequence pass and in the cached step alike.  A multiplier
    before an RMS norm is taken away by it; while the two passes were written
    apart the prefill dropped the multiplier so and every cached step applied
    it, and the decoder's first token and its second came from different
    models.  Logits of the decoder at every generated position against the
    whole-sequence forward's over prompt plus continuation."""
    import jax

    cfg, mesh = _hybrid(qk_norm=qk_norm), _mesh_of(ONE)
    params = _drawn(cfg, seed=5)
    prompt = np.random.default_rng(6).integers(
        0, cfg.vocab, size=(3, 7)).astype(np.int32)
    tokens, logits = make_decoder(cfg, mesh, max_new=5, keep_logits=3)(
        params, prompt)
    full = np.asarray(jax.jit(tfm.make_forward(cfg, mesh))(
        params, np.asarray(tokens)))[:, 6:-1]
    assert logits.shape == full.shape == (3, 5, cfg.vocab)
    np.testing.assert_allclose(np.asarray(logits), full, rtol=0,
                               atol=2e-5 * full.std())
    # the multiplier is felt: without it the logits are others
    flat = dataclasses.replace(cfg, hybrid=dataclasses.replace(
        cfg.hybrid, key_multiplier=1.0))
    other = np.asarray(jax.jit(tfm.make_forward(flat, mesh))(
        params, np.asarray(tokens)))[:, 6:-1]
    assert np.abs(other - full).max() > 1e-2 * full.std()


def _index():
    from ompi_tpu.models.sparse_index import SparseIndex

    return SparseIndex(n_heads=2, head_dim=8, topk=4, q_slice=4)


def _retention():
    from ompi_tpu.models.retention import Retention

    return dataclasses.replace(
        CFG, remat=False, n_kv_heads=2, qk_norm="head",
        retention=Retention(gate_offset=1.0, chunk=3))


@pytest.mark.parametrize("cfg", [
    pytest.param(dataclasses.replace(CFG, remat=False), id="dense"),
    pytest.param(dataclasses.replace(CFG, remat=False, n_kv_heads=2,
                                     qk_norm="head"), id="grouped-query"),
    pytest.param(_hybrid(), id="hybrid"),
    pytest.param(dataclasses.replace(CFG, remat=False, n_kv_heads=2,
                                     index=_index()), id="indexed"),
    pytest.param(dataclasses.replace(OLMOE, n_kv_heads=2), id="dropless"),
    pytest.param(_retention(), id="retention"),
])
def test_the_blocks_two_forms_agree(cfg):
    """``block.block`` is one function for both passes: the whole-sequence
    form over 12 positions against its own carry form, the states that the
    form without a carry collected over the first 8 handed over by each
    mechanism's ``carried`` and 4 steps taken with a carry: the same hidden
    states at the last 4 positions, whatever the configuration carries."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.models import block
    from ompi_tpu.mpi.device_comm import DeviceCommunicator

    mesh = _mesh_of(ONE)
    comm = DeviceCommunicator(mesh, tuple(mesh.axis_names))
    params = _drawn(cfg, seed=7)
    tokens = np.random.default_rng(8).integers(
        0, cfg.vocab, size=(2, 12)).astype(np.int32)
    whole = EXPERT_LEAVES if cfg.moe_top_k else ()
    scale = (1.0, 1.0) if cfg.hybrid is None else (
        cfg.hybrid.embedding_multiplier, cfg.hybrid.lm_head_multiplier)

    def local(params, tokens):
        want = tfm._local_backbone(cfg, comm, params, tokens)
        _h, collected = tfm._local_backbone(
            cfg, comm, params, tokens[:, :8], collect_kv=True)
        collected = iter(collected)
        stacks = [mechanism.carried(cfg, mesh, collected, 12)
                  for mechanism in block.mechanisms(cfg)]
        assert not list(collected)
        got = []
        for at in range(8, 12):
            pos = jnp.int32(at)
            h = tfm._lookup(cfg, params["emb"], tokens[:, at])[:, None]
            h = h * scale[0]
            for layer in range(cfg.n_layers):
                lp = {k: params[k] if k in whole else params[k][layer]
                      for k in tfm.layer_leaves(cfg)}
                h, stacks = block.block(cfg, comm, lp, h, pos[None],
                                        carry=(stacks, layer, pos))
            got.append(tfm._rmsnorm(h, params["lnf"], cfg.norm_eps)[:, 0]
                       * scale[1])
        return want[:, 8:], jnp.stack(got, axis=1)

    want, got = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(tfm.param_specs(P, cfg, mesh), P()),
        out_specs=P(), check_vma=False))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * np.asarray(want).std())


def test_decode_odd_prompt_length():
    """Prompt lengths need no special tiling — seq 7 prefill + decode."""
    _assert_cached_equals_full(CFG, {"dp": 4, "sp": 1, "tp": 2}, 3,
                               prompt_len=7, seed=4)


def test_models_namespace_exports():
    import ompi_tpu.models as m

    assert m.TransformerConfig is tfm.TransformerConfig
    assert callable(m.make_decoder) and callable(m.train_stream)


# ---- the compiled step: where the cache goes -------------------------------

_SHAPE = re.compile(r"\b[a-z]+\d+\[([\d,]*)\]")
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply)=%([\w.\-]+)")
_NO_DATA = {"while", "tuple", "get-tuple-element", "parameter", "bitcast"}


def _elements(type_text: str) -> int:
    """Elements of the largest array in an instruction's output type."""
    return max((math.prod(int(d) for d in dims.split(",") if d)
                for dims in _SHAPE.findall(type_text)), default=0)


def _parse_hlo(text: str) -> dict:
    """computation -> [instruction], each a dict of name, root, elements
    (of its largest output array), opcode, operands, called, op_name."""
    computations, current = {}, None
    for line in text.splitlines():
        if line.endswith("{") and " -> " in line and " = " not in line:
            name = line.removeprefix("ENTRY ").split(" ", 1)[0]
            current = computations.setdefault(name.lstrip("%"), [])
        elif line.startswith("}"):
            current = None
        elif current is not None and " = " in line:
            left, right = line.strip().split(" = ", 1)
            depth = end = 0         # the output type: one token, or a tuple
            for end, ch in enumerate(right):
                depth += (ch == "(") - (ch == ")")
                if ch == " " and depth == 0:
                    break
            opcode, _, rest = right[end + 1:].partition("(")
            args = rest.split("), ", 1)[0]
            op_name = re.search(r'op_name="([^"]*)"', rest)
            current.append({
                "name": left.split("%")[-1], "root": left.startswith("ROOT"),
                "elements": _elements(right[:end]), "opcode": opcode,
                "operands": re.findall(r"%([\w.\-]+)", args),
                "called": _CALLED.findall(rest),
                "op_name": op_name.group(1) if op_name else ""})
    return computations


def cache_sized_operations(text: str, floor: int, position: int):
    """(writes, moves) among the generation loop's instructions whose output
    has ``floor`` elements or more.  The loop's instructions are those the
    ``decode.step`` loop runs, directly or through a computation it calls,
    and any other whose ``op_name`` lies under ``decode.step``.  A write is
    a ``dynamic-update-slice``, alone or as a fusion's root, whose update
    operand has at most ``position`` elements; everything else that makes
    an array of that size is a move, and so is a ``copy`` of that size
    anywhere in the program (a copy carries no ``op_name``)."""
    computations = _parse_hlo(text)
    fused = {c for body in computations.values() for i in body
             if i["opcode"] == "fusion" for c in i["called"]}

    def in_step(instruction):
        return "decode.step" in instruction["op_name"].split("/")

    reach = [c for body in computations.values() for i in body
             if in_step(i) for c in i["called"]]
    for c in reach:                 # grows while it is walked
        reach += [d for i in computations[c] for d in i["called"]
                  if d not in reach]
    assert reach, "no loop under decode.step in the compiled program"
    writes, moves = [], []
    for name, body in computations.items():
        if name in fused:
            continue        # inside a fusion nothing is an array in memory
        for i in body:
            if (i["elements"] < floor or i["opcode"] in _NO_DATA
                    or not (name in reach or in_step(i)
                            or i["opcode"] == "copy")):
                continue
            dus, scope = i, body
            if i["opcode"] == "fusion":
                scope = computations[i["called"][0]]
                dus = next(j for j in scope if j["root"])
            shapes = {j["name"]: j["elements"] for j in scope}
            if (dus["opcode"] == "dynamic-update-slice"
                    and shapes[dus["operands"][1]] <= position):
                writes.append(i["name"])
            else:
                moves.append(f"{i['name']} = {i['opcode']}"
                             f" [{i['elements']}] {i['op_name']}")
    return writes, moves


def _check_cache_stays(compiled, floor: int, position: int) -> None:
    writes, moves = cache_sized_operations(compiled.as_text(), floor,
                                           position)
    assert not moves, "\n".join(moves)
    assert len(writes) == 2, writes         # K and V, one position each


def test_compiled_step_for_the_chip_writes_the_cache_and_moves_nothing(
        chip, for_the_chip):
    """The benchmark's decode cell at its real sizes, compiled by the v5e's
    own compiler for a chip that is described and not attached.  This
    checks a compile, not a time: of everything the generation loop runs,
    only the two writes of one position (K and V) may produce an array of
    a layer's cache or more, and no copy of that size may exist: so
    attention reads the carried stack itself, through a fused slice."""
    _cfg, job = _cell("pythia-1.4b-widths.decode-1k-128", chip)
    fn, args = job.programs()["decode_full"]
    position = job.batch * job.cfg.d_model          # B·Hl·hd
    _check_cache_stays(fn.lower(*args).compile(),
                       position * (job.prompt_len + job.max_new), position)


def test_compiled_hybrid_step_for_the_chip_keeps_its_state_in_place(
        chip, for_the_chip):
    """The state-space cell at its real sizes, for the described v5e: the
    mixer's stacked state (6 layers x 192 sequences x 32 x 128 x 256) is loop
    carry like K/V.  Of everything the generation loop runs, only the one
    write of a layer's states may produce an array of the stack's size, and
    no copy of that size may exist: the update reads the carried stack
    through a fused slice and writes it back in place by layer index (as the
    ``xs`` and ``ys`` of a scan it would be rebuilt and copied a step).  The
    CPU's compiler does copy it, twice a layer, so this is asked here only."""
    from ompi_tpu.models import ssm

    _cfg, job = _cell("falcon-h1-34b.decode-128-64-b192", chip)
    fn, args = job.programs()["decode_full"]
    layer = math.prod(ssm.state_shapes(job.cfg, job.batch)[1])
    writes, moves = cache_sized_operations(
        fn.lower(*args).compile().as_text(), job.cfg.n_layers * layer, layer)
    assert not moves, "\n".join(moves)
    assert len(writes) == 1, writes


def test_compiled_step_on_the_cpu_keeps_the_stacked_cache_in_place():
    """The CPU backend's program at a tiny shape, for where the chip's
    compiler is not installed.  Its dot wants another layout, so it does
    transpose the layer it reads, and the assertion holds a level up: in
    the generation loop only the two writes of one position produce an
    array of the stacked cache's size, and nothing copies the stack (as
    ``xs`` and ``ys`` of a layer scan it was rebuilt and copied)."""
    import jax

    batch, prompt_len, max_new = 4, 60, 12      # cache above every weight
    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    dec = make_decoder(CFG, mesh, max_new=max_new)
    compiled = jax.jit(dec).lower(
        jax.eval_shape(lambda: tfm.init_params(CFG)),
        jax.ShapeDtypeStruct((batch, prompt_len), np.int32)).compile()
    position = batch * CFG.d_model
    _check_cache_stays(
        compiled, CFG.n_layers * position * (prompt_len + max_new), position)
