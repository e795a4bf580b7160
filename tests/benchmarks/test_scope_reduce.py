"""The scope reduction and the clock, stage by stage: .xplane.pb -> op_name
per instruction on a profile built by hand; events -> seconds per key and
the device clock's lead on intervals counted by hand; and both on events
recorded on the chip in the PR that added the scopes.  The numbers the
earlier reduction gave for the first recording are pinned first."""

import glob
import gzip
import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import cells, clock, hlo_names, scopes, xplane
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event

TESTDATA = os.path.join(os.path.dirname(xplane.__file__), "testdata")
SCOPED = sorted(glob.glob(TESTDATA + "/scoped/*.json.gz"))
# A whole profile as the v5e's runtime wrote it (chip run of the PR that
# added the scopes), gzipped: two samples under ``bench:sample`` of a jitted
# ``train_step`` that scans four checkpointed layers (scopes ``attention``
# and ``ffn`` under ``layers``) under ``value_and_grad``, then ``loss`` and
# ``optimizer``.  It holds ``hlo_names``' field numbers to a real file.
V5E_PROFILE = os.path.join(TESTDATA, "scoped",
                           "probe-train-step.v5e.xplane.pb.gz")
BENCH = cells.load_benchmark()


def readers(workload: str) -> dict:
    """Per-layer metric -> its reader, of the metrics ``workload`` reports
    through a shared reader: data files, found as ``run.py`` finds them."""
    return {row["name"]: reader
            for row, reader in cells.resolve(workload).per_layer
            if hasattr(reader, "spec")}


BY_CELL = {w["name"]: readers(w["name"]) for w in BENCH["workloads"]}
TRAIN = next(r for r in BY_CELL.values() if "coll_tp_share" in r)
DECODE = next(r for r in BY_CELL.values() if "prefill_device_ms" in r)


def traced_run(events, table="whole", window_s=None) -> RunData:
    """What ``run.measure`` hands the readers of a traced run's events."""
    if table == "whole":
        table = scopes.reduce_scopes(events)
    summary = xplane.reduce_events(events)
    if window_s is not None:
        summary = types.SimpleNamespace(window_s=window_s)
    return RunData(durations={}, facts={}, peaks=None, trace=summary,
                   compiles_in_window=0, peak_bytes=None, scopes=table,
                   events=list(events))


def test_the_first_recording_still_reads_what_it_read():
    """``reduce_events`` of the four-chip recording of the PR that added the
    benchmark, as that PR's code computed it: no existing metric may read
    another number from the same events."""
    path = os.path.join(TESTDATA,
                        "pythia-6.9b-widths.train-2k-dp2tp2.events.json.gz")
    events = xplane.load_events(path)
    assert all(e.scope == "" for e in events)       # five columns, defaulted
    s = xplane.reduce_events(events)
    assert (s.devices, s.window_s, s.busy_s, s.collective_s,
            s.exposed_collective_s) == (4, 1.305604004, 1.29811002575,
                                        0.2568543915, 0.2568543915)
    assert s.device_ops[0][1] == 0.0650882755
    assert s.device_ops[0][0].startswith(
        "bitcast_dynamic-update-slice_fusion.32 = bf16[8,4,256,4096]")
    assert s.idle_gaps == [["readback", 0.0072520005],
                           ["dispatch", 0.00024197775]]
    # a program without scopes: passes from JAX's name stack are not there
    # either, because these events carry no op_name at all
    table = scopes.reduce_scopes(events)
    assert set(table) == {"unscoped", "executions", "coll/other"}
    assert table["unscoped"] == pytest.approx(s.busy_s)
    assert table["coll/other"] == pytest.approx(s.collective_s)
    assert table["executions"] == 3


# ---- .xplane.pb -> op_name, on a profile encoded by hand ------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(name, opcode, op_name="", calls=(), packed=True):
    called = (field(38, b"".join(varint(c) for c in calls)) if packed
              else b"".join(field(38, c) for c in calls))
    return field(2, field(1, name) + field(2, opcode)
                 + field(7, field(1, "ignored") + field(2, op_name))
                 + (called if calls else b""))


def test_op_names_are_read_from_the_profiles_own_programs(tmp_path):
    fused = field(3, field(1, "fused_computation") + field(5, 3)
                  + instruction("param.1", "parameter")
                  + instruction("mul.1", "multiply", "jit(f)/ffn/mul")
                  + instruction("add.1", "add", "jit(f)/ffn/add")
                  + instruction("exp.1", "exponential", "jit(f)/attention/exp"))
    body = field(3, field(1, "body") + field(5, 2)
                 + instruction("fusion.7", "fusion", calls=[3])
                 + instruction("copy.9", "copy")
                 + instruction("dot.3", "dot", "jit(f)/layers/while/body/dot"))
    entry = field(3, field(1, "main") + field(5, 1)
                  + instruction("while.2", "while", "jit(f)/layers/while",
                                calls=[2, 9], packed=False)
                  + instruction("copy.1", "copy"))
    proto = field(1, field(1, "jit_f") + fused + body + entry)   # HloProto
    metadata = (field(2, "/host:metadata")
                + field(5, field(1, 4) + field(2, field(1, 4)
                                               + field(2, "Hlo Proto")))
                + field(4, field(1, 77) + field(2, field(1, 77)
                        + field(2, "jit_f(123)")
                        + field(5, field(1, 4) + field(6, proto)))))
    other = field(2, "/device:TPU:0") + field(3, field(2, "XLA Ops"))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(field(1, other) + field(1, metadata))

    programs = hlo_names.program_op_names(str(path))
    assert programs == {"jit_f(123)": {
        "mul.1": "jit(f)/ffn/mul", "add.1": "jit(f)/ffn/add",
        "exp.1": "jit(f)/attention/exp",
        # a fusion without metadata: the path most of what it fused shares
        "fusion.7": "jit(f)/ffn/mul",
        # anything else without: the instruction that calls its computation
        "param.1": "jit(f)/ffn/mul",
        "copy.9": "jit(f)/layers/while",
        "dot.3": "jit(f)/layers/while/body/dot",
        "while.2": "jit(f)/layers/while",
        "copy.1": ""}}

    ops = [Event("/device:TPU:0", "XLA Modules", "jit_f(123)", 100, 50),
           Event("/device:TPU:0", "XLA Ops", "%fusion.7 = f32[8] fusion(...)",
                 110, 10),
           Event("/device:TPU:0", "XLA Ops", "%copy.9 = f32[8] copy(...)",
                 120, 10),
           Event("/device:TPU:0", "XLA Ops", "%copy.9 = f32[8] copy(...)",
                 200, 10),                  # outside every run of a program
           Event("/device:TPU:1", "XLA Ops", "%dot.3 = f32[8] dot(...)",
                 110, 10),                  # a plane with no run at all
           Event("/host:CPU", "python3", "bench:sample", 0, 300)]
    named = hlo_names.with_op_names(ops, programs)
    assert [e.scope for e in named] == [
        "", "jit(f)/ffn/mul", "jit(f)/layers/while", "", "", ""]
    assert [e[:5] for e in named] == [e[:5] for e in ops]
    assert hlo_names.with_op_names(ops, {}) == ops
    assert all(isinstance(e, Event) for e in named)
    # what xplane saves of them, six columns, loads again
    saved = str(tmp_path / "scoped.json.gz")
    xplane.save_events(named, saved)
    assert xplane.load_events(saved) == named


# ---- the same, on a profile the v5e wrote ----------------------------------

@pytest.fixture(scope="module")
def v5e_profile(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("profile") / "probe.xplane.pb"
    with gzip.open(V5E_PROFILE, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_op_names_are_read_from_a_profile_the_v5e_wrote(v5e_profile):
    (name, table), = hlo_names.program_op_names(v5e_profile).items()
    assert name.startswith("jit_train_step(") and len(table) == 449
    named = {inst: op for inst, op in table.items() if op}
    assert len(named) > 0.9 * len(table)
    # (a reducer's computation and an argument have bare names)
    assert sum(op.startswith("jit(train_step)/")
               for op in named.values()) > 0.9 * len(named)
    where = {scopes.classify(op) for op in named.values()}
    assert {(w.phase, w.chain) for w in where} >= {
        ("fwd", ("layers", "attention")), ("fwd", ("layers", "ffn")),
        ("recompute", ("layers", "attention")), ("bwd", ("layers", "ffn")),
        ("fwd", ("loss",)), (None, ("optimizer",))}

    events = xplane.read_events(v5e_profile)
    device_ops = [e for e in events if e.line == xplane.OPS_LINE
                  and xplane.DEVICE_PLANE.match(e.plane)]
    assert len(device_ops) == 140
    # all named but the four asynchronous copies of an argument a run
    assert sorted({xplane.instruction(e.name) for e in device_ops
                   if not e.scope}) == ["copy-done", "copy-done.1",
                                        "copy-start", "copy-start.1"]
    s = xplane.reduce_events(events)
    table = scopes.reduce_scopes(events)
    # the device's stamps are 0.8 ms behind the host's here: of the two
    # runs one lies in the window the two host spans open
    assert table["executions"] == 1
    for key in ("phase/fwd", "phase/bwd", "phase/recompute", "scope/loss",
                "scope/attention@layers", "scope/ffn@layers",
                "self/layers", "scope/optimizer"):
        assert table[key] > 0, key
    assert table["unscoped"] < 0.05 * s.busy_s
    parts = sum(table[k] for k in ("phase/fwd", "phase/bwd",
                                   "phase/recompute", "scope/optimizer",
                                   "unscoped"))
    assert parts == pytest.approx(s.busy_s, rel=0.01)


def test_the_wire_walk_agrees_with_the_generated_classes(v5e_profile):
    """``hlo_names`` walks the protobuf wire format with field numbers
    written out, because the generated classes come only with tensorflow,
    which the process that holds the chip does not import.  Where they can
    be imported, they read the same from the real file."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    with open(v5e_profile, "rb") as f:
        space = xplane_pb2.XSpace.FromString(f.read())
    plane, = [p for p in space.planes if p.name == hlo_names.METADATA_PLANE]
    stat_id, = [i for i, m in plane.stat_metadata.items()
                if m.name == hlo_names.HLO_PROTO_STAT]
    embedded = {meta.name: hlo_pb2.HloProto.FromString(stat.bytes_value)
                for meta in plane.event_metadata.values()
                for stat in meta.stats if stat.metadata_id == stat_id}
    ours = hlo_names.program_op_names(v5e_profile)
    assert set(ours) == set(embedded)
    for program, proto in embedded.items():
        table = ours[program]
        instructions = {i.name: (i, c.id)
                        for c in proto.hlo_module.computations
                        for i in c.instructions}
        assert set(table) == set(instructions)
        caller = {called: i.name for i, _ in instructions.values()
                  for called in i.called_computation_ids}
        assert caller                    # a while, fusions: field 38 is read
        inherited = 0
        for name, (inst, computation) in instructions.items():
            if inst.metadata.op_name:
                assert table[name] == inst.metadata.op_name
            elif inst.opcode != "fusion" and computation in caller:
                assert table[name] == table[caller[computation]]
                inherited += 1
        assert inherited


# ---- events -> seconds per key, counted by hand ----------------------------

FWD = "jit(train_step)/jvp()/shard_map/"
BWD = "jit(train_step)/transpose(jvp())/shard_map/"


def ops(device, rows):
    return [Event(f"/device:TPU:{device}", xplane.OPS_LINE, name, lo, hi - lo,
                  scope) for name, lo, hi, scope in rows]


def host(rows, prefix=TRACE_PREFIX):
    return [Event(xplane.HOST_PLANE, "python3", prefix + name, lo, hi - lo)
            for name, lo, hi in rows]


# One device under a sample of [0,200] ns that holds one run of the program.
SYNTHETIC = (
    host([("sample", 0, 200), ("dispatch", 0, 20), ("readback", 20, 200)])
    + [Event("/device:TPU:0", "XLA Modules", "jit_train_step(1)", 10, 180)]
    + ops(0, [
        ("while.1", 10, 100, FWD + "layers/while"),     # an envelope: dropped
        ("fusion.1", 10, 30, FWD + "layers/while/body/attention/exp"),
        ("fusion.2", 30, 40, FWD + "layers/while/body/dynamic_slice"),
        ("psum.3 = f32[8] all-reduce(f32[8] %x)", 40, 50,
         FWD + "layers/while/body/ffn/coll.allreduce.tp/psum"),
        ("fusion.4", 50, 60, FWD + "loss/reduce_sum"),
        ("fusion.5", 60, 90, BWD + "layers/while/body/checkpoint/"
                                   "rematted_computation/attention/exp"),
        ("fusion.6", 90, 100, BWD + "layers/while/body/checkpoint/"
                                    "attention/mul"),
        ("all-reduce-start.7", 100, 102, BWD + "psum"),
        ("fusion.8", 102, 120, "jit(train_step)/optimizer/mul"),
        ("all-reduce-done.7", 125, 130, BWD + "psum"),
        ("copy.9", 130, 140, ""),                       # no metadata at all
        ("all-gather.10", 150, 160, "jit(train_step)/optimizer/all_gather"),
        ("fusion.11", 170, 190, BWD + "mul"),           # a pass and no scope
    ])
)

NS = 1e-9
HAND_COUNTED = {
    "executions": 1,
    "phase/fwd": 50 * NS,           # [10,60]
    "phase/recompute": 30 * NS,     # [60,90]
    # [90,100], the two halves of the pair [100,102] and [125,130], [170,190]
    "phase/bwd": (10 + 2 + 5 + 20) * NS,
    "scope/layers": 80 * NS,        # [10,50] forward, [60,100] backward
    "self/layers": 10 * NS,         # the scan's own slicing, [30,40]
    "scope/attention": 60 * NS,     # [10,30], [60,100]
    "scope/attention@layers": 60 * NS,
    "self/attention": 60 * NS,
    "self/attention@layers": 60 * NS,
    "scope/ffn": 10 * NS, "scope/ffn@layers": 10 * NS,
    "self/ffn": 10 * NS, "self/ffn@layers": 10 * NS,
    "scope/loss": 10 * NS, "self/loss": 10 * NS,
    "scope/optimizer": 28 * NS,     # [102,120] and the all-gather [150,160]
    "self/optimizer": 28 * NS,
    "coll/allreduce.tp": 10 * NS,
    "coll/grad_sync": 30 * NS,      # the pair counts once, [100,130]
    "coll/other": 10 * NS,          # the all-gather under the optimizer
    "unscoped": 10 * NS,            # copy.9
}


def test_scope_reduction_on_hand_counted_intervals():
    table = scopes.reduce_scopes(SYNTHETIC)
    assert table == {k: pytest.approx(v) for k, v in HAND_COUNTED.items()}
    assert list(table) == sorted(table)
    # the same window and the same collective arithmetic as reduce_events
    s = xplane.reduce_events(SYNTHETIC)
    assert s.window_s == pytest.approx(200 * NS)
    assert sum(v for k, v in table.items() if k.startswith("coll/")) \
        == pytest.approx(s.collective_s)
    # passes, optimizer and unscoped are all there is: what was busy, less
    # the wait between the two halves of the asynchronous pair
    parts = sum(table[k] for k in ("phase/fwd", "phase/bwd",
                                   "phase/recompute", "unscoped"))
    parts += 28 * NS
    assert parts == pytest.approx(s.busy_s - 5 * NS)


def test_scope_reduction_is_a_mean_over_devices_clipped_to_the_window():
    second = ops(1, [("fusion.1", 150, 250, FWD + "embed/gather")])
    table = scopes.reduce_scopes(SYNTHETIC + second)
    assert table["scope/embed"] == pytest.approx(50 * NS / 2)
    assert table["phase/fwd"] == pytest.approx((50 + 50) * NS / 2)
    assert table["executions"] == pytest.approx(0.5)
    assert scopes.reduce_scopes(host([("sample", 0, 5)])) is None
    clipped = scopes.reduce_scopes(SYNTHETIC, window=(0, 20))
    assert clipped["scope/attention"] == pytest.approx(10 * NS)
    assert "scope/loss" in clipped and clipped["scope/loss"] == 0.0
    # a run counts where most of it lies in the window, wherever it begins:
    # the device's clock may put its start before the job's first host span
    assert clipped["executions"] == 0
    assert scopes.reduce_scopes(SYNTHETIC, window=(50, 200))["executions"] == 1


def test_seconds_tell_no_time_from_no_such_scope():
    table = {"coll/allreduce.tp": 2.0, "coll/permute.tp": 1.0,
             "coll/allreduce.dp-tp": 8.0, "unscoped": 0.0}
    assert scopes.seconds(table, ["coll/*.tp"]) == 3.0
    assert scopes.seconds(table, ["unscoped"]) == 0.0
    assert scopes.seconds(table, ["scope/optimizer"]) is None
    assert scopes.seconds(None, ["unscoped"]) is None


def test_shares_of_the_hand_counted_table(capsys):
    table = scopes.reduce_scopes(SYNTHETIC)
    run = traced_run(SYNTHETIC, table, window_s=200 * NS)
    got = {name: reader.read(run) for name, reader in TRAIN.items()}
    assert got == {
        "train_fwd_share": pytest.approx(25.0),
        "train_bwd_share": pytest.approx(100 * (37 + 30) / 200),
        "train_recompute_share": pytest.approx(15.0),
        "train_optimizer_share": pytest.approx(14.0),
        "train_attention_share": pytest.approx(30.0),
        "train_ffn_share": pytest.approx(5.0),
        "train_loss_share": pytest.approx(5.0),
        "train_unscoped_share": pytest.approx(5.0),
        "coll_tp_share": pytest.approx(5.0),
        "coll_grad_sync_share": pytest.approx(15.0)}
    assert capsys.readouterr().err == ""
    # a train program that lost a scope says which, and what to suspect
    for key in ("scope/optimizer", "self/optimizer"):
        del table[key]
    lost = {name: reader.read(run) for name, reader in TRAIN.items()}
    assert lost == {**got, "train_optimizer_share": None}
    said = capsys.readouterr().err
    assert said.count("\n") == 1 and said.startswith("train_optimizer_share: ")
    assert "scope/optimizer" in said and "stale executable" in said
    assert "JAX_COMPILATION_CACHE_DIR" in said
    # a program without any scope (the parent's) names every share it lacks
    bare = traced_run(SYNTHETIC, {"unscoped": 1e-7, "executions": 1.0},
                      window_s=200 * NS)
    assert {name: reader.read(bare) for name, reader in DECODE.items()
            if reader.spec["reader"] == "scope_share"} == {
        "decode_cache_move_share": None, "decode_attention_share": None,
        "decode_unscoped_share": pytest.approx(50)}
    assert [line.split(":")[0] for line in
            capsys.readouterr().err.splitlines()] == [
        "decode_cache_move_share", "decode_attention_share"]
    # a run without a device trace (the CPU) has no share and says nothing
    nothing = traced_run(SYNTHETIC, None)
    assert {reader.read(nothing) for reader in
            list(TRAIN.values()) + list(DECODE.values())} == {None}
    assert capsys.readouterr().err == ""


def decode_sample(prefill_first, prefill_full, lead=0.0):
    """One sample of a decode job: under ``first`` a program whose prefill
    takes ``prefill_first`` ns, under ``full`` another with the same scope
    names whose prefill takes ``prefill_full`` and which then generates."""
    gen = "jit(decode)/shard_map/decode.step/while/body/"
    first, full = 1000, 2000 + prefill_first
    end = full + 10 + prefill_full + 50
    return (
        host([("sample", 900, end + 100), ("first", 995, full - 5),
              ("full", full - 5, end + 10)])
        + [Event("/device:TPU:0", "XLA Modules", "jit_decode(1)",
                 first + lead, prefill_first + 5),
           Event("/device:TPU:0", "XLA Modules", "jit_decode(2)",
                 full + lead, prefill_full + 65)]
        + ops(0, [
            ("fusion.1", first + lead, first + lead + prefill_first,
             "jit(decode)/shard_map/prefill/layers/while/body/ffn/dot"),
            ("copy.2", first + lead + prefill_first,
             first + lead + prefill_first + 5, ""),
            ("fusion.1", full + lead, full + lead + prefill_full,
             "jit(decode)/shard_map/prefill/layers/while/body/ffn/dot"),
            ("fusion.3", full + lead + prefill_full,
             full + lead + prefill_full + 60, gen + "attention/dot"),
        ]))


@pytest.mark.parametrize("lead", [0.0, -40.0, 40.0])
def test_prefill_is_read_from_the_program_that_ttft_times(lead, capsys):
    events = decode_sample(400, 600, lead)
    whole = scopes.reduce_scopes(events)
    assert whole["scope/prefill"] == pytest.approx(1000 * NS)
    assert whole["executions"] == 2
    first = scopes.reduce_scopes(events, span="first")
    assert first["scope/prefill"] == pytest.approx(400 * NS)
    assert first["unscoped"] == pytest.approx(5 * NS)
    assert first["executions"] == 1
    assert "scope/decode.step" not in first
    full = scopes.reduce_scopes(events, span="full")
    assert full["scope/prefill"] == pytest.approx(600 * NS)
    assert full["scope/attention@decode.step"] == pytest.approx(60 * NS)
    prefill_ms = DECODE["prefill_device_ms"]
    assert prefill_ms.spec["span"] == "first"
    assert prefill_ms.read(traced_run(events)) == pytest.approx(400e-6)
    assert capsys.readouterr().err == ""
    # no run under such a span, or no prefill in it: said, and left out
    nowhere = scopes.reduce_scopes(events, span="nowhere")
    assert nowhere == {"executions": 0.0, "unscoped": 0.0}
    bare = [e._replace(scope="") for e in events]
    assert prefill_ms.read(traced_run(bare)) is None
    said = capsys.readouterr().err
    assert said.startswith("prefill_device_ms: ")
    assert "'first'" in said and "stale" in said


# ---- the clock -------------------------------------------------------------

def traced_jobs(lead, jobs):
    """Host spans of ``jobs`` (d0, d1, r1) and, ``lead`` later on the
    device's clock, a run that starts ``wake`` after d0 and ends ``tail``
    before r1."""
    events = []
    for d0, d1, r1, wake, tail in jobs:
        events += host([("sample", d0 - 5, r1 + 5), ("dispatch", d0, d1),
                        ("readback", d1, r1)])
        s, e = d0 + wake + lead, r1 - tail + lead
        events += [Event("/device:TPU:0", "XLA Modules", "jit_f(1)", s, e - s)]
        events += ops(0, [("fusion.1", s, e, "jit(f)/ffn/mul")])
    return events


@pytest.mark.parametrize("lead", [1000.0, -400.0, 0.0])
def test_a_known_lead_is_recovered_within_its_band(lead):
    events = traced_jobs(lead, [(1000, 1100, 9000, 60, 30),
                                (10000, 10100, 18000, 20, 80),
                                (20000, 20100, 28000, 90, 10)])
    found = clock.estimate(events)
    # lead - 10 <= it <= lead + 20: the tightest tail and the tightest wake
    assert found.band_ns == pytest.approx(30.0)
    assert found.offset_ns == pytest.approx(lead + 5.0)
    assert abs(found.offset_ns - lead) <= found.band_ns / 2


def test_the_clock_needs_a_job_and_bounds_that_do_not_cross():
    assert clock.estimate(SYNTHETIC).band_ns == pytest.approx(20.0)
    assert clock.estimate(host([("sample", 0, 9)])) is None
    no_run = [e for e in SYNTHETIC if e.line != "XLA Modules"]
    assert clock.estimate(no_run) is None
    assert clock.breakdown(no_run) == {}
    # two jobs that no one lead explains: -30..60 and 470..560
    crossed = (traced_jobs(0.0, [(1000, 1100, 9000, 60, 30)])
               + traced_jobs(500.0, [(10000, 10100, 18000, 60, 30)]))
    assert clock.estimate(crossed) is None


def test_gaps_are_named_after_the_shift():
    # The device's clock runs 1000 ahead.  On it the runs are [2060,9970] and
    # [11020,18920], so the gap between the two jobs, and the wait before
    # the first, lie under a readback at their middles.
    events = traced_jobs(1000.0, [(1000, 1100, 9000, 60, 30),
                                  (10000, 10100, 18000, 20, 80)])
    events += host([("data.produce", 9400, 9600)], clock.PROGRAM_PREFIX)
    plain = dict(xplane.reduce_events(events).idle_gaps)
    assert plain == {"readback": pytest.approx((1065 + 1050) * NS)}
    # 970 <= lead <= 1020.  Moved back by 995 the runs are [1065,8975] and
    # [10025,17925]: 70 idle under the first dispatch, 1050 between the
    # jobs, whose middle 9500 lies in the worker's span, the shortest one
    # open there, and 80 under the last readback.
    lead = clock.estimate(events)
    assert lead == (pytest.approx(995.0), pytest.approx(50.0))
    assert dict(clock.aligned_gaps(events, lead)) == {
        "ompi_tpu:data.produce": pytest.approx(1050 * NS),
        "readback": pytest.approx(80 * NS),
        "dispatch": pytest.approx(70 * NS)}
    # a band of 100 could move either short gap under another span
    assert dict(clock.aligned_gaps(events, clock.Lead(995.0, 100.0))) == {
        "ompi_tpu:data.produce": pytest.approx(1050 * NS),
        clock.BELOW_BAND: pytest.approx(150 * NS)}
    out = clock.breakdown(events)
    assert out["clock"] == {"offset_us": pytest.approx(0.995),
                            "band_us": pytest.approx(0.050)}
    assert out["idle_gaps_aligned"][0][0] == "ompi_tpu:data.produce"


def test_the_streams_own_spans_reach_the_profile():
    """``ompi_tpu:data.produce`` of ``models.data.prefetch``, made on the
    worker's thread, is a host span of the trace this JAX writes."""
    import numpy as np

    from ompi_tpu.models import data

    class Job:
        stream = data.prefetch(iter([np.zeros((2, 4), np.int32)] * 64),
                               depth=1)

        def sample(self):
            next(self.stream)

    job = Job()
    try:
        events = bench_run.trace_samples(job, 8)
    finally:
        job.stream.close()
    ours = clock.program_annotations(events)
    assert ours and {e.name for e in ours} == {"ompi_tpu:data.produce"}
    assert all(e.duration_ns > 0 for e in ours)
    assert not xplane.device_and_span_events(ours)      # left as it was


# ---- events recorded on the chip, with their scopes -----------------------

def busy_by_the_selection_of_scopes(events) -> tuple[float, float]:
    """(window, busy) seconds as ``reduce_events`` defines them, computed
    from what ``xplane.split`` selects and ``xplane.window_of`` bounds."""
    per_device, hosts, _runs = xplane.split(events)
    window = xplane.window_of(per_device, hosts)
    busy = sum(xplane.length(xplane.union(xplane.clip(
        [(e.start_ns, e.start_ns + e.duration_ns) for e in device_ops]
        + xplane.collective_intervals(device_ops), window)))
        for device_ops in per_device.values())
    return (window[1] - window[0]) / 1e9, busy / len(per_device) / 1e9


@pytest.mark.parametrize("path", [None] + sorted(
    glob.glob(TESTDATA + "/**/*.json.gz", recursive=True)),
    ids=lambda p: "synthetic" if p is None else os.path.relpath(p, TESTDATA))
def test_the_scope_tables_window_is_reduce_events_window(path):
    """``lib/scopes.py`` and ``xplane.reduce_events`` select and bound by
    the same two functions.  Held together here all the same, so that the
    sites' shares of the collectives keep adding up to ``coll_time_share``."""
    events = SYNTHETIC if path is None else xplane.load_events(path)
    s = xplane.reduce_events(events)
    window_s, busy_s = busy_by_the_selection_of_scopes(events)
    assert window_s == pytest.approx(s.window_s, rel=1e-12)
    assert busy_s == pytest.approx(s.busy_s, rel=1e-12)
    table = scopes.reduce_scopes(events)
    assert sum(v for k, v in table.items() if k.startswith("coll/")) \
        == pytest.approx(s.collective_s, rel=1e-9)


def test_scoped_recordings_are_there_and_small():
    assert len(SCOPED) >= 2
    everything = glob.glob(TESTDATA + "/**/*.gz", recursive=True)
    assert V5E_PROFILE in everything
    assert sum(os.path.getsize(f) for f in everything) < 1 << 20


@pytest.mark.parametrize("path", SCOPED, ids=os.path.basename)
def test_scope_reduction_on_events_recorded_on_the_chip(path):
    events = xplane.load_events(path)
    # a recording is named after its cell: the cell's own metrics read it
    mine = BY_CELL[os.path.basename(path).removesuffix(".events.json.gz")]
    data = traced_run(events)
    named = [e for e in events if e.scope]
    assert named and all(e.line == xplane.OPS_LINE for e in named)
    s = xplane.reduce_events(events)
    table = scopes.reduce_scopes(events)
    assert table["executions"] >= 1
    assert table["unscoped"] < 0.05 * s.busy_s
    assert all(0.0 <= v <= s.window_s for k, v in table.items()
               if k != "executions")
    for name in scopes.vocabulary.SCOPES:
        if "scope/" + name in table:
            assert table["self/" + name] <= table["scope/" + name] * (1 + 1e-9)
    if "phase/bwd" in table:                # a train step
        assert s.devices == 4
        for key in ("phase/fwd", "phase/recompute", "scope/optimizer",
                    "scope/attention", "scope/ffn", "scope/loss",
                    "coll/allreduce.tp", "coll/grad_sync"):
            assert table[key] > 0, key
        parts = sum(table[k] for k in ("phase/fwd", "phase/bwd",
                                       "phase/recompute", "scope/optimizer",
                                       "unscoped"))
        assert parts == pytest.approx(s.busy_s, rel=0.02)
        assert len(mine) == 10
        assert {name: reader.read(data) for name, reader in mine.items()} == {
            name: pytest.approx(100 * scopes.seconds(table, reader.spec["keys"])
                                / s.window_s)
            for name, reader in mine.items()}
    else:                                   # prefill and cached steps
        for key in ("scope/prefill", "scope/decode.step",
                    "scope/kv_cache@decode.step", "self/layers@decode.step",
                    "scope/attention@decode.step", "scope/unembed"):
            assert table[key] > 0, key
        assert not any(k.startswith("phase/") for k in table)
        # the sample's two programs each have a prefill; ttft_ms times the
        # one under ``first``, whose run the host span encloses
        first = scopes.reduce_scopes(events, span="first")
        assert first["executions"] == 1 and "scope/decode.step" not in first
        run = min((e for e in events if e.line == "XLA Modules"),
                  key=lambda e: e.start_ns)     # the sample's first job
        took, = [e.duration_ns for e in events if e.name == "bench:first"]
        ms = mine["prefill_device_ms"].read(data)
        assert ms == pytest.approx(1e3 * first["scope/prefill"])
        assert 0.95 * run.duration_ns < ms * 1e6 < run.duration_ns < took
        assert table["scope/prefill"] > 2 * first["scope/prefill"]
