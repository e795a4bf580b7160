"""The scopes inside the device programs, and the reader of their names.

Every cell's programs are lowered and compiled at the configuration's tiny
sizes on the CPU, and their ``op_name`` metadata is read from the compiled
text: every scope a metric of the cell reads has to be there, in each pass
it should be in, and ``classify`` has to say of it what the metric assumes.
Nothing here is a time.
"""

import re

import jax
import numpy as np
import pytest

from benchmarks.lib import cells, program, scopes, xplane
from benchmarks.lib.scopes import Where, classify
from ompi_tpu.core import scopes as program_scopes

BENCH = cells.load_benchmark()
WORKLOADS = {w["name"]: w for w in BENCH["workloads"]}
TINY_TRAFFIC = {"batch": 4, "seq": 32, "prompt_len": 16, "max_new": 8,
                "trace_samples": 2}
# traffic at which a cell's selection binds at its tiny sizes, or its
# programs hold no scope of it: cell 9's prefill ends past ``topk`` blocks
# and its steps' cache past ``dense_len``; cell 14's prompts are longer than
# the tiny index's ``topk`` of 16
BINDS = {"minicpm-sala.decode-16k-512-b24":
         {"batch": 2, "prompt_len": 64, "max_new": 8},
         "deepseek-v3.2-exp.decode-16k-512-b8": {"prompt_len": 24}}
# a key that a data file reads for the day a program has it: a step of cell
# 9 streams its rows under the mask and gathers none
# (``models/block_select.py``)
UNUSED = {("minicpm-sala.decode-16k-512-b24",
           "scope/attention.gather@decode.step")}
OP_NAME = re.compile(r'op_name="([^"]*)"')
# a computation's first line in a program's text, and an instruction's
# attributes that name another computation
COMPUTATION = re.compile(r"(?:ENTRY\s+)?%?([\w.-]+)\s*\(")
CALLEE = re.compile(r"\b(to_apply|select|scatter|calls)=%?([\w.-]+)")

_tables: dict[str, dict] = {}


def by_computation(text: str):
    """(the computation it is in, the line) of every line of a program's
    text: a computation begins at a line that is not indented and ends in
    an opening brace."""
    name = None
    for line in text.splitlines():
        if line.endswith("{") and not line[:1].isspace():
            found = COMPUTATION.match(line)
            name = found and found.group(1)
        yield name, line


def reducers_bodies(text: str) -> set:
    """The computations of a program's text that a reducer applies (the
    ``to_apply`` of a ``reduce``, an ``all-reduce``, a ``sort``, a
    ``scatter``; a ``select-and-scatter``'s two) and whatever those call.
    JAX names the inside of one by the scopes of the enclosing loop's body
    alone (``layers/attention/reduce_max`` under ``decode.step``), and no
    profile has an event for it: it runs inside the reducer's own."""
    callees, applied = {}, set()
    for name, line in by_computation(text):
        for how, callee in CALLEE.findall(line):
            callees.setdefault(name, set()).add(callee)
            # a ``call``'s ``to_apply`` is a function of the program's own
            if how != "calls" and " call(" not in line:
                applied.add(callee)
    bodies, queue = set(), list(applied)
    while queue:
        body = queue.pop()
        if body not in bodies:
            bodies.add(body)
            queue.extend(callees.get(body, ()))
    return bodies


def names_of(compiled) -> tuple[set, set]:
    """(every op_name, those of collective instructions) of a program, but
    of what a reducer applies: a profile has no event there to name."""
    text = compiled.as_text()
    skipped = reducers_bodies(text)
    every, collective = set(), set()
    for name, line in by_computation(text):
        found = OP_NAME.search(line)
        if found and name not in skipped:
            every.add(found.group(1))
            if xplane.collective_kind(line.strip()) is not None:
                collective.add(found.group(1))
    return every, collective


def table_of(every: set, collective: set) -> dict:
    """What ``reduce_scopes`` would have keys for, with the names behind
    each key."""
    table: dict[str, set] = {}
    for name in every:
        where = classify(name)
        for key in scopes.keys_of(where):
            table.setdefault(key, set()).add(name)
        if name in collective:
            table.setdefault("coll/" + scopes.coll_site(where),
                             set()).add(name)
    return table


def cell_table(workload: str) -> dict:
    """The cell's programs at tiny sizes, compiled once for all the cases."""
    if workload not in _tables:
        cell = cells.resolve(workload)
        config = program.tiny(cell.config)
        traffic = {k: TINY_TRAFFIC.get(k, v) for k, v in cell.traffic.items()}
        traffic.update(BINDS.get(workload, {}))
        job = cell.runner.build(config, traffic, jax.devices()[:cell.chips])
        every, collective = set(), set()
        for fn, args in job.programs().values():
            one, two = names_of(fn.lower(*args).compile())
            every |= one
            collective |= two
        _tables[workload] = table_of(every, collective)
    return _tables[workload]


def kind_of(workload: str) -> str:
    return cells.resolve(workload).traffic["runner"]


TRAIN = [w for w in WORKLOADS if kind_of(w) == "train"]
DECODE = [w for w in WORKLOADS if kind_of(w) == "decode"]


def keyed_metrics(workloads) -> dict[str, list[str]]:
    """Per-layer metric -> the scope-table keys its data file hands to a
    shared reader, of the metrics a cell among ``workloads`` reports."""
    found = {}
    for workload in workloads:
        for row, reader in cells.resolve(workload).per_layer:
            if "keys" in getattr(reader, "spec", {}):
                found[row["name"]] = reader.spec["keys"]
    return found


def share_cases():
    for workload in WORKLOADS:
        for name, keys in keyed_metrics([workload]).items():
            for key in keys:
                yield pytest.param(workload, name, key,
                                   id=f"{workload}-{name}-{key}")


def test_the_reader_holds_no_vocabulary_of_its_own(monkeypatch):
    """A name appended to the program's tuple is a scope to the reader, a
    key of its table, with no edit under ``benchmarks/``; before that it is
    an element like any other."""
    op_name = "jit(decode)/shard_map/decode.step/layers/conv.state/mul"
    assert not any(isinstance(v, tuple) and "attention" in v
                   for v in vars(scopes).values())
    classify.cache_clear()
    assert classify(op_name).chain == ("decode.step", "layers")
    monkeypatch.setattr(program_scopes, "SCOPES",
                        program_scopes.SCOPES + ("conv.state",))
    classify.cache_clear()
    try:
        where = classify(op_name)
        assert where.chain == ("decode.step", "layers", "conv.state")
        assert scopes.keys_of(where)[-2:] == ("self/conv.state",
                                              "self/conv.state@decode.step")
        with program_scopes.scope("conv.state"):
            pass
        site = f"jit(f)/{program_scopes.COLL}.allreduce.tp/psum"
        assert classify(site).coll == "allreduce.tp"
    finally:
        classify.cache_clear()


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError):
        program_scopes.scope("attnetion")


@pytest.mark.parametrize("workload,name,key", share_cases())
def test_every_key_a_share_reads_is_in_the_cells_programs(workload, name, key):
    table = {k: 1.0 for k in cell_table(workload)}
    if (workload, key) in UNUSED:
        assert key not in table
    else:
        assert scopes.seconds(table, [key]), sorted(table)
    # and no metric that only another kind of job reports finds anything
    # here, but the one any program has and the collectives' (the CPU's
    # compiler keeps the one-device all-reduces that the chip's removes)
    mine = set(TRAIN if workload in TRAIN else DECODE)
    ours, theirs = keyed_metrics(mine), keyed_metrics(set(WORKLOADS) - mine)
    found = {other for other, keys in theirs.items()
             if other not in ours
             and keys != ["unscoped"] and not keys[0].startswith("coll/")
             and scopes.seconds(table, keys)}
    assert not found, found


@pytest.mark.parametrize("scope", ["embed", "layers", "attn_proj",
                                   "attention", "ffn", "loss"])
@pytest.mark.parametrize("workload", TRAIN)
def test_train_scope_is_there_forward_and_backward(workload, scope):
    names = cell_table(workload)["scope/" + scope]
    phases = {classify(n).phase for n in names}
    assert {"fwd", "bwd"} <= phases, phases
    if scope in ("attn_proj", "attention", "ffn"):
        assert "recompute" in phases    # both cells checkpoint their layers
    if scope in ("attn_proj", "attention", "ffn"):
        assert all(classify(n).chain[0] == "layers" for n in names
                   if "while" in n)


@pytest.mark.parametrize("workload", TRAIN)
def test_optimizer_is_in_no_pass_and_the_program_has_its_name(workload):
    table = cell_table(workload)
    assert {classify(n).phase for n in table["scope/optimizer"]} == {None}
    assert all("jit(train_step)" in n for n in table["scope/optimizer"])
    # nothing with metadata of its own is left to "unscoped" but the
    # arguments' names
    assert all("/" not in n for n in table.get("unscoped", ())), \
        sorted(table["unscoped"])[:5]


@pytest.mark.parametrize("workload", [w for w in TRAIN
                                      if WORKLOADS[w]["chips"] > 1])
def test_collective_sites_of_the_sharded_train_cell(workload):
    table = cell_table(workload)
    tp = table["coll/allreduce.tp"]
    assert {classify(n).phase for n in tp} >= {"fwd", "bwd"}
    assert {classify(n).scope for n in tp} == {"attn_proj", "ffn"}
    sync = table["coll/grad_sync"]
    assert all(classify(n) == Where("bwd", (), None) for n in sync)
    assert "coll/other" not in table


@pytest.mark.parametrize("scope,root", [
    ("prefill", None), ("decode.step", None), ("embed", "prefill"),
    ("embed", "decode.step"), ("layers", "prefill"),
    ("layers", "decode.step"), ("attn_proj", "decode.step"),
    ("attention", "prefill"), ("attention", "decode.step"),
    ("ffn", "decode.step"), ("kv_cache", "decode.step"),
    ("unembed", "decode.step"), ("sample", "decode.step")])
@pytest.mark.parametrize("workload", DECODE)
def test_decode_scope_is_there_under_its_root(workload, scope, root):
    table = cell_table(workload)
    key = f"scope/{scope}@{root}" if root else f"scope/{scope}"
    names = table[key]
    assert all(classify(n).phase is None for n in names)
    assert any("jit(decode)" in n for n in names)
    if scope == "kv_cache":
        assert any(n.endswith("dynamic_update_slice") for n in names)
        assert all(classify(n).chain == ("decode.step", "layers", "kv_cache")
                   for n in names)


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp()/shard_map/layers/while/body/closed_call/"
     "attention/bqhd,bkhd->bhqk/dot_general",
     Where("fwd", ("layers", "attention"), None)),
    ("jit(train_step)/transpose(jvp())/shard_map/layers/while/body/"
     "closed_call/checkpoint/ffn/coll.allreduce.tp/psum",
     Where("bwd", ("layers", "ffn"), "allreduce.tp")),
    ("jit(train_step)/transpose(jvp())/shard_map/layers/while/body/"
     "closed_call/checkpoint/rematted_computation/attn_proj/mul",
     Where("recompute", ("layers", "attn_proj"), None)),
    # a scope entered at the top of the differentiated function sits inside
    # the transform's parentheses
    ("jit(step)/jvp(loss)/reduce_sum", Where("fwd", ("loss",), None)),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "attention/neg", Where("bwd", ("layers", "attention"), None)),
    ("jit(train_step)/transpose(jvp())/shard_map/psum",
     Where("bwd", (), None)),
    ("jit(train_step)/optimizer/mul", Where(None, ("optimizer",), None)),
    ("jit(decode)/shard_map/decode.step/while/body/closed_call/layers/while/"
     "body/closed_call/kv_cache/dynamic_update_slice",
     Where(None, ("decode.step", "layers", "kv_cache"), None)),
    ("jit(decode)/shard_map/prefill/layers/while/body/closed_call/checkpoint/"
     "attention/jit(_where)/select_n",
     Where(None, ("prefill", "layers", "attention"), None)),
    # a function called loss is no scope; a reducer's name is a bare path
    ("jit(loss)/mul", Where(None, (), None)),
    ("layers/while/body/closed_call/attention/reduce_max",
     Where(None, ("layers", "attention"), None)),
    ("jit(f)/shard_map/coll.reduce.dp-tp/coll.allreduce.dp-tp/psum",
     Where(None, (), "reduce.dp-tp")),
    ("params['emb']", Where(None, (), None)),
    ("", Where(None, (), None)),
])
def test_classify(op_name, want):
    assert classify(op_name) == want
    if want.chain:
        assert classify(op_name).scope == want.chain[-1]


def test_keys_and_sites_of_a_place():
    assert scopes.keys_of(Where("fwd", ("layers", "attention"), None)) == (
        "phase/fwd", "scope/layers", "scope/attention",
        "scope/attention@layers", "self/attention", "self/attention@layers")
    assert scopes.keys_of(Where(None, ("optimizer",), None)) == (
        "scope/optimizer", "self/optimizer")
    assert scopes.keys_of(Where(None, (), None)) == ("unscoped",)
    assert scopes.keys_of(Where(None, (), "allreduce.tp")) == ()
    assert scopes.coll_site(Where("bwd", (), None)) == "grad_sync"
    assert scopes.coll_site(Where("bwd", ("optimizer",), None)) == "other"
    assert scopes.coll_site(Where("fwd", (), None)) == "other"
    assert scopes.coll_site(Where("bwd", ("ffn",), "x.tp")) == "x.tp"


# ---- layouts no cell runs yet: experts over ep, a ring over sp ------------

def _train_table(mesh_shape: dict, **config) -> dict:
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.parallel.mesh import make_mesh

    n = int(np.prod(list(mesh_shape.values())))
    mesh = make_mesh(mesh_shape, devices=jax.devices()[:n])
    cfg = tfm.TransformerConfig(vocab=128, d_model=64, n_heads=4, n_layers=2,
                                d_ff=128, seq=32, **config)
    step, init = tfm.make_train_step(cfg, mesh)
    params = tfm.init_params(cfg)
    tokens = np.zeros((4, cfg.seq), np.int32)
    return table_of(*names_of(
        step.lower(params, init(params), tokens).compile()))


def _ep_exchange_table() -> dict:
    """What the reader calls an exchange over ``ep`` and a sum over every
    axis of an ``ep: 2`` mesh, from a program that asks the communicator
    for both itself: no layer of the model has to exchange for the reader's
    naming of one to be held."""
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.mpi.device_comm import DeviceCommunicator
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1, "ep": 2},
                     devices=jax.devices()[:2])
    comm = DeviceCommunicator(mesh)
    fn = jax.jit(jax.shard_map(
        lambda v: comm.allreduce(comm.alltoall_stacked(v, axis="ep")),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    return table_of(*names_of(
        fn.lower(np.ones((2, 8), np.float32)).compile()))


@pytest.fixture(scope="module")
def moe_table():
    """The routed layer's four scopes, from a train step that routes
    (``routed_moe``, every routed cell's path), beside the two sites of an
    exchange over ``ep``."""
    table = _train_table({"dp": 1, "sp": 1, "tp": 1, "ep": 1},
                         attention="xla", moe_experts=4, moe_top_k=2,
                         remat=False)
    for key, names in _ep_exchange_table().items():
        table.setdefault(key, set()).update(names)
    return table


@pytest.fixture(scope="module")
def ring_table():
    return _train_table({"dp": 1, "sp": 2, "tp": 1}, attention="ring",
                        remat=False)


@pytest.mark.parametrize("key", [
    "scope/moe.route@layers", "scope/moe.dispatch@layers",
    "scope/moe.experts@layers", "scope/moe.combine@layers",
    "coll/alltoall_stacked.ep", "coll/allreduce.dp-sp-tp-ep"])
def test_expert_parallel_layout_shows_its_scopes(moe_table, key):
    assert key in moe_table, sorted(moe_table)
    if key.startswith("scope/moe."):
        names = moe_table[key]
        assert all(classify(n).chain[:2] == ("layers", "ffn") for n in names)
        assert {"fwd", "bwd"} <= {classify(n).phase for n in names}


@pytest.mark.parametrize("key", [
    "scope/attention.ring@layers", "coll/permute.sp",
    "coll/allreduce.dp-sp", "coll/grad_sync"])
def test_ring_layout_shows_its_scopes(ring_table, key):
    assert key in ring_table, sorted(ring_table)
    if key == "scope/attention.ring@layers":
        assert all(classify(n).chain[:3] == ("layers", "attention",
                                             "attention.ring")
                   for n in ring_table[key])
    if key == "coll/permute.sp":
        # the K/V ring inside attention, and the labels' shift outside it
        assert {classify(n).scope for n in ring_table[key]} >= {
            "attention.ring", None}


def test_every_traced_communicator_method_names_its_site():
    from ompi_tpu.mpi import device_comm
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"x": 2, "y": 2}, devices=jax.devices()[:4])
    comm = device_comm.DeviceCommunicator(mesh)
    x = np.ones((4, 8), np.float32)

    def site(method, *args, **kw):
        fn = jax.jit(jax.shard_map(
            lambda v: getattr(comm, method)(v, *args, **kw), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec(("x", "y")),
            out_specs=jax.sharding.PartitionSpec(("x", "y")),
            check_vma=False))
        every, _ = names_of(fn.lower(x).compile())
        return {classify(n).coll for n in every} - {None}

    assert site("allreduce") == {"allreduce.x-y"}
    assert site("shift", 1, axis="x") == {"shift.x"}
    assert site("shift") == {"shift.y"}             # the last axis
    assert site("bcast") == {"bcast.x-y"}
    # the outermost call is the site: reduce asks allreduce
    assert site("reduce") == {"reduce.x-y"}
    public = {name for name in vars(device_comm.DeviceCommunicator)
              if not name.startswith("_")}
    not_traced = {"size", "axis_sizes", "rank", "coords", "sub", "run",
                  "run_method"}
    assert public - not_traced == set(device_comm._TRACED)
