"""HLO ``op_name`` of every instruction of every program in a profile.

``jax.named_scope`` and JAX's own name stack reach the compiled program as
the ``op_name`` of each instruction's metadata
(``jit(train_step)/jvp()/shard_map/layers/while/body/closed_call/attention/
dot_general``).  Looked at by hand on a v5e in the PR that added this file
(``PERF.md``, Findings): an event of the ``XLA Ops`` line carries three stats
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale Multiplier``)
and none that holds the ``op_name``; ``hlo_op`` is on the ``Async XLA Ops``
line alone and names a pair's other half, ``tf_op`` is on host lines, and
``hlo_module`` exists on the CPU backend only.  But the
profile embeds the programs themselves: the plane ``/host:metadata`` has one
event metadata per program that ran, named as the ``XLA Modules`` line names
its runs (``jit_train_step(17313137475348989986)``), whose stat ``Hlo
Proto`` is the serialized ``HloProto``.  That is the executable that ran, a
stale cached one included, which ``compiled.as_text()`` of a program built
again would not show.  ``jax.profiler.ProfileData`` does not expose event
metadata, so this module walks the protobuf wire format of the few messages
it needs (field numbers from ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto``).  The generated classes (``xplane_pb2``,
``hlo_pb2``) come only inside tensorflow, which this repo does not depend
on; importing it in the process that holds the chip works and takes 25 s
there.  ``tests/benchmarks/test_scope_reduce.py`` holds the field numbers to
a whole profile the v5e wrote (``testdata/scoped/``) and, where tensorflow
can be imported, to those classes.

An instruction the compiler made (a copy of a loop's carry, a bitcast
fusion) has no metadata of its own.  A fusion then takes the ``op_name``
most of its fused instructions share the path of, and anything else that of
the instruction that calls its computation: an operation of a loop's body
belongs to the ``while`` that runs it.
"""

from __future__ import annotations

import bisect
import collections
from typing import Iterable, Iterator

from benchmarks.lib import xplane
from benchmarks.lib.xplane import Event

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[tuple[int, int | bytes]]:
    """(field number, value) of one message: an int for a varint, the bytes
    of a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _ints(value: int | bytes) -> list[int]:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        one, i = _varint(value, i)
        out.append(one)
    return out


def _embedded_programs(pb: bytes) -> Iterator[tuple[str, bytes]]:
    """(name as the ``XLA Modules`` line spells it, HloProto bytes)."""
    for field, plane in _fields(pb):                    # XSpace.planes
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and v == METADATA_PLANE.encode() for f, v in parts):
            continue
        stat_names = {}                                 # XPlane.stat_metadata
        for f, entry in parts:
            if f == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[meta.get(1)] = meta.get(2, b"").decode()
        for f, entry in parts:                          # XPlane.event_metadata
            if f != 4:
                continue
            name, protos = "", []
            for f2, value in _fields(dict(_fields(entry)).get(2, b"")):
                if f2 == 2:
                    name = value.decode()
                elif f2 == 5:                           # XEventMetadata.stats
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT:
                        protos.append(stat.get(6, b""))  # XStat.bytes_value
            for proto in protos:
                yield name, proto


def _module_op_names(hlo_proto: bytes) -> dict[str, str]:
    """instruction name -> op_name, its own or the one it inherits."""
    module = dict(_fields(hlo_proto)).get(1, b"")       # HloProto.hlo_module
    own: dict[str, str] = {}
    home: dict[str, int] = {}           # instruction -> its computation's id
    calls: dict[str, list[int]] = {}    # instruction -> computations it calls
    fusions: set[str] = set()
    members: dict[int, list[str]] = {}  # computation id -> its instructions
    for field, computation in _fields(module):          # .computations
        if field != 3:
            continue
        comp_id, names = 0, []
        for f, value in _fields(computation):
            if f == 5:
                comp_id = value
            elif f == 2:                                # .instructions
                name = opcode = op_name = ""
                called: list[int] = []
                for f2, v2 in _fields(value):
                    if f2 == 1:
                        name = v2.decode()
                    elif f2 == 2:
                        opcode = v2.decode()
                    elif f2 == 7:                       # OpMetadata.op_name
                        op_name = dict(_fields(v2)).get(2, b"").decode()
                    elif f2 == 38:                      # called_computation_ids
                        called += _ints(v2)
                names.append(name)
                own[name], calls[name] = op_name, called
                if opcode == "fusion":
                    fusions.add(name)
        members[comp_id] = names
        for name in names:
            home[name] = comp_id
    caller = {comp: name for name, called in calls.items() for comp in called}

    resolved: dict[str, str] = {}

    def resolve(name: str) -> str:
        if name in resolved:
            return resolved[name]
        resolved[name] = ""             # a cycle cannot be, but must not hang
        found = own[name]
        if not found and name in fusions:
            inside = [own[m] for c in calls[name] for m in members.get(c, [])
                      if own[m]]
            paths = collections.Counter(n.rpartition("/")[0] for n in inside)
            if paths:
                best = paths.most_common(1)[0][0]
                found = next(n for n in inside
                             if n.rpartition("/")[0] == best)
        if not found and home[name] in caller:
            found = resolve(caller[home[name]])
        resolved[name] = found
        return found

    return {name: resolve(name) for name in own}


def program_op_names(pb_path: str) -> dict[str, dict[str, str]]:
    """program (as ``XLA Modules`` names it) -> instruction -> op_name; empty
    where the profile embeds no program, as on the CPU backend."""
    with open(pb_path, "rb") as f:
        pb = f.read()
    return {name: _module_op_names(proto)
            for name, proto in _embedded_programs(pb)}


def run_at(plane_runs: list[tuple[float, float, str]],
           t: float) -> tuple[float, float, str] | None:
    """The (start, end, name) of ``plane_runs``, sorted, that holds ``t``."""
    i = bisect.bisect_right(plane_runs, (t, float("inf"), ""))
    return plane_runs[i - 1] if i and t < plane_runs[i - 1][1] else None


def with_op_names(events: Iterable[Event],
                  programs: dict[str, dict[str, str]]) -> list[Event]:
    """``events`` with ``scope`` filled on the device's operations: the
    ``op_name`` of the event's instruction in the program whose run, an
    event of the same plane's ``XLA Modules`` line, encloses it in time."""
    events = list(events)
    runs: dict[str, list[tuple[float, float, str]]] = {}
    for e in events:
        if e.line == xplane.MODULES_LINE and e.name in programs:
            runs.setdefault(e.plane, []).append(
                (e.start_ns, e.start_ns + e.duration_ns, e.name))
    for plane_runs in runs.values():
        plane_runs.sort()
    out = []
    for e in events:
        if e.line == xplane.OPS_LINE and xplane.DEVICE_PLANE.match(e.plane):
            run = run_at(runs.get(e.plane, []), e.start_ns)
            if run:
                e = e._replace(scope=programs[run[2]].get(
                    xplane.instruction(e.name), ""))
        out.append(e)
    return out
