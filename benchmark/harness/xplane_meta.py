"""The `.xplane.pb` read whole, with the stats that sit on an event's METADATA.

`jax.profiler.ProfileData` (what `trace_reduce.load_planes` uses) shows an
event's own stats only (`device_offset_ps`, `device_duration_ps`). What ties a
device op to its program and to the part of the model it came from sits one
hop away, on the op's `XEventMetadata`: `tf_op` (the `op_name` jax wrote, so
every `jax.named_scope` around the op: `jit(decode_block)/…/attention/mix/…`),
`program_id` (the fingerprint in the module's name `jit_<kind>(<id>)`),
XLA's own `flops` and `bytes_accessed` (also `hlo_category`, `source`, not
taken). This module reads them with `google.protobuf` alone: the seven messages of `xplane.proto`
are declared below (a map field is declared as its wire form, a repeated
key/value entry) in a descriptor pool of their own, so nothing of
`tensorflow` is loaded into the process that holds the chip.

A plane comes back as `{"name", "ops": [Op], "modules": [Module], "dispatch":
[(name, start_ns, dur_ns, stats)], "lines": {line name: [(name, start_ns,
dur_ns)]}}`: `lines` is what `trace_reduce.load_planes` gives (for the window
mark and the host's spans), `ops` and `modules` are a chip's "XLA Ops" and
"XLA Modules" lines with their metadata (empty on any other plane), `dispatch`
the host's `dispatch/<program>` annotations with their own stats (`m`,
`bucket`, `tokens`; empty on a chip's plane). Times are ns on the trace's own
clock, as `ProfileData` reports them.
"""
from __future__ import annotations

import os
import re
import sys
import time
from typing import NamedTuple

from benchmark.harness import trace_reduce as TRD

_I64, _U64, _DBL, _STR, _BYT, _MSG = 3, 4, 1, 9, 12, 11  # FieldDescriptorProto.Type
_PKG = "localai_tpu.bench.xplane"
# message -> [(field, number, type, repeated, message type)]
_SCHEMA = {
    "XStat": [("metadata_id", 1, _I64, 0, None), ("double_value", 2, _DBL, 0, None),
              ("uint64_value", 3, _U64, 0, None), ("int64_value", 4, _I64, 0, None),
              ("str_value", 5, _STR, 0, None), ("bytes_value", 6, _BYT, 0, None),
              ("ref_value", 7, _U64, 0, None)],
    "XEvent": [("metadata_id", 1, _I64, 0, None), ("offset_ps", 2, _I64, 0, None),
               ("num_occurrences", 5, _I64, 0, None),
               ("duration_ps", 3, _I64, 0, None), ("stats", 4, _MSG, 1, "XStat")],
    "XLine": [("id", 1, _I64, 0, None), ("display_id", 10, _I64, 0, None),
              ("name", 2, _STR, 0, None), ("display_name", 11, _STR, 0, None),
              ("timestamp_ns", 3, _I64, 0, None), ("duration_ps", 9, _I64, 0, None),
              ("events", 4, _MSG, 1, "XEvent")],
    "XEventMetadata": [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None),
                       ("display_name", 4, _STR, 0, None),
                       ("metadata", 3, _BYT, 0, None),
                       ("stats", 5, _MSG, 1, "XStat"),
                       ("child_id", 6, _I64, 1, None)],
    "XStatMetadata": [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None),
                      ("description", 3, _STR, 0, None)],
    # the wire form of map<int64, XEventMetadata> / map<int64, XStatMetadata>
    "EventMetadataEntry": [("key", 1, _I64, 0, None),
                           ("value", 2, _MSG, 0, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _I64, 0, None),
                          ("value", 2, _MSG, 0, "XStatMetadata")],
    "XPlane": [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None),
               ("lines", 3, _MSG, 1, "XLine"),
               ("event_metadata", 4, _MSG, 1, "EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, 1, "StatMetadataEntry"),
               ("stats", 6, _MSG, 1, "XStat")],
    "XSpace": [("planes", 1, _MSG, 1, "XPlane"), ("errors", 2, _STR, 1, None),
               ("warnings", 3, _STR, 1, None), ("hostnames", 4, _STR, 1, None)],
}
_space_cls = None


class Op(NamedTuple):
    """One event of a chip's "XLA Ops" line."""
    name: str  # the HLO instruction's text, as `trace_reduce` names the event
    start_ns: float
    dur_ns: float
    program_id: int | None  # the fingerprint of the module it ran in
    tf_op: str  # jax's op_name with XLA's `:<op type>` tail, "" where unnamed
    flops: float  # XLA's count (0 for a Pallas custom call)
    bytes_accessed: float


class Module(NamedTuple):
    """One event of a chip's "XLA Modules" line: one program execution."""
    name: str  # `jit_<kind>(<fingerprint>)`
    start_ns: float
    dur_ns: float
    program_id: int | None


def _xspace():
    """The XSpace message class, built once from `_SCHEMA`."""
    global _space_cls
    if _space_cls is None:
        from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

        fd = descriptor_pb2.FileDescriptorProto(
            name="localai_tpu_bench_xplane.proto", package=_PKG, syntax="proto3")
        for msg, fields in _SCHEMA.items():
            m = fd.message_type.add(name=msg)
            for fname, num, typ, rep, mtype in fields:
                f = m.field.add(name=fname, number=num, type=typ,
                                label=3 if rep else 1)
                if mtype:
                    f.type_name = f".{_PKG}.{mtype}"
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        _space_cls = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{_PKG}.XSpace"))
    return _space_cls


def _value(stat, stat_names):
    """A stat's value; a `ref_value` is the NAME of the stat metadata it
    points at (how the profiler interns strings)."""
    for field in ("str_value", "int64_value", "uint64_value", "double_value"):
        v = getattr(stat, field)
        if v:
            return v
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    if stat.bytes_value:
        return stat.bytes_value
    return 0


def _stats(stats, stat_names) -> dict:
    return {stat_names.get(s.metadata_id, ""): _value(s, stat_names)
            for s in stats}


def fingerprint(module_name: str) -> int | None:
    """`jit_admit(15110319609580777085)` -> 15110319609580777085."""
    m = re.search(r"\((\d+)\)\s*$", module_name)
    return int(m.group(1)) if m else None


def read_planes(path: str) -> list[dict]:
    """Every plane of the capture (module docstring)."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: (e.value.name, _stats(e.value.stats, stat_names))
                for e in plane.event_metadata}
        chip = bool(TRD.device_planes([{"name": plane.name}]))
        host = plane.name.startswith("/host:")
        lines: dict[str, list] = {}
        ops: list[Op] = []
        modules: list[Module] = []
        dispatch: list[tuple] = []
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            base = float(line.timestamp_ns)
            for ev in line.events:
                name, stats = meta.get(ev.metadata_id, ("", {}))
                start, dur = base + ev.offset_ps / 1e3, ev.duration_ps / 1e3
                evs.append((name, start, dur))
                if host and name.startswith("dispatch/"):
                    dispatch.append(
                        (name, start, dur, _stats(ev.stats, stat_names)))
                if not chip:
                    continue
                if line.name == TRD.OPS_LINE:
                    pid = stats.get("program_id")
                    ops.append(Op(
                        name, start, dur, int(pid) if pid else None,
                        str(stats.get("tf_op") or ""),
                        float(stats.get("flops") or 0.0),
                        float(stats.get("bytes_accessed") or 0.0)))
                elif line.name == TRD.MODULES_LINE:
                    pid = stats.get("program_id") or fingerprint(name)
                    modules.append(Module(name, start, dur,
                                          int(pid) if pid else None))
        out.append({"name": plane.name, "lines": lines, "ops": ops,
                    "modules": modules, "dispatch": dispatch})
    return out


_last: dict = {}


def load(ctx) -> list[dict] | None:
    """The planes of this run's capture, or None where the run was not
    traced or the capture cannot be read. One capture is up to 136 MB and
    several metrics read it, so the last one parsed is kept. A test may put
    planes into the context directly (`ctx["trace"]["xplanes"]`)."""
    tr = ctx.get("trace") or {}
    if tr.get("xplanes") is not None:
        return tr["xplanes"]
    if not tr.get("dir"):
        return None
    try:
        path = TRD.find_xplane(tr["dir"])
        if _last.get("path") != path:
            _last.clear()
            t0 = time.monotonic()
            _last.update(path=path, planes=read_planes(path))
            print(f"[xplane_meta] read {os.path.getsize(path)} bytes, "
                  f"{sum(len(p['ops']) for p in _last['planes'])} device ops "
                  f"in {time.monotonic() - t0:.1f} s", file=sys.stderr, flush=True)
    except (FileNotFoundError, ValueError, OSError):
        return None
    return _last["planes"]


def self_ns(ops: list[Op]) -> tuple[list[float], list[bool]]:
    """Per op of `ops`, in their order: the time spent in the op itself (its
    duration less that of the ops nested inside it, as
    `trace_reduce.self_times` reckons: a `while` takes none of its body's),
    and whether anything was nested inside it. Per event and not per name,
    so each keeps its own metadata."""
    own = [0.0] * len(ops)
    parent = [False] * len(ops)
    stack: list[list] = []  # [index, end, self]
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    for i in order:
        start, dur = ops[i].start_ns, ops[i].dur_ns
        while stack and start >= stack[-1][1] - 1e-6:
            done = stack.pop()
            own[done[0]] = max(0.0, done[2])
        if stack:
            stack[-1][2] -= dur
            parent[stack[-1][0]] = True
        stack.append([i, start + dur, dur])
    for done in stack:
        own[done[0]] = max(0.0, done[2])
    return own, parent
