"""KV-cache greedy decode (models/decode.py) vs the full-forward path.

The cached single-token steps must reproduce exactly the tokens a
(recomputed-from-scratch) full forward picks — the standard
cache-consistency contract.  And the compiled step must not move its
cache: the last tests read the compiler's output, they time nothing.
"""

import dataclasses
import math
import os
import re

import numpy as np
import pytest

from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel.mesh import make_mesh

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


def _assert_cached_equals_full(cfg, mesh_shape, max_new, prompt_len, seed):
    import jax

    mesh = make_mesh(mesh_shape,
                     devices=jax.devices()[:math.prod(mesh_shape.values())])
    params = tfm.init_params(cfg)
    fwd = jax.jit(tfm.make_forward(cfg, mesh))
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
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 4}, 4, 2, id="dp2ep4"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 4}, 1, 2, id="max_new1"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 4}, 2, 2, id="max_new2"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 4}, 4, 1, id="one_layer"),
    pytest.param({"dp": 2, "sp": 1, "tp": 1, "ep": 1}, 4, 3, id="dp2"),
    pytest.param({"dp": 1, "sp": 1, "tp": 2, "ep": 1}, 4, 3, id="tp2"),
])
def test_moe_cached_decode_matches_full_forward(mesh_shape, max_new,
                                                n_layers):
    """Expert-parallel decode: same switch routing as training; with a
    non-binding capacity the cached path reproduces the full forward
    exactly."""
    cfg = dataclasses.replace(CFG, moe_experts=4, moe_capacity_factor=4.0,
                              n_layers=n_layers)
    _assert_cached_equals_full(cfg, mesh_shape, max_new, prompt_len=6, seed=1)


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


@pytest.fixture
def chips():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture
def no_compile_cache():
    """As ``tests/benchmarks/test_fits.py``: a program compiled for a
    described chip cannot be read back from the persistent cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_compiled_step_for_the_chip_writes_the_cache_and_moves_nothing(
        chips, no_compile_cache):
    """The benchmark's decode cell at its real sizes, compiled by the v5e's
    own compiler for a chip that is described and not attached.  This
    checks a compile, not a time: of everything the generation loop runs,
    only the two writes of one position (K and V) may produce an array of
    a layer's cache or more, and no copy of that size may exist: so
    attention reads the carried stack itself, through a fused slice."""
    from benchmarks.lib import cells

    cell = cells.resolve("pythia-1.4b-widths.decode-1k-128")
    job = cell.runner.build(cell.config, cell.traffic, chips[:cell.chips])
    fn, args = job.programs()["decode_full"]
    position = job.batch * job.cfg.d_model          # B·Hl·hd
    _check_cache_stays(fn.lower(*args).compile(),
                       position * (job.prompt_len + job.max_new), position)


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
