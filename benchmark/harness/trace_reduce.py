"""From a profiler trace (`.xplane.pb`) to device numbers. Reads the file with
`jax.profiler.ProfileData` and nothing else.

What a TPU trace holds (read by hand, PERF.md "Reading the trace"): one plane
per chip named `/device:TPU:<n>`, with a line "XLA Modules" (one event per
execution of a compiled program, named after the jitted function) and a line
"XLA Ops" (one event per HLO op; an op that contains others, a `while` or a
fusion's parent, spans them, so durations nest). Host threads are lines of
the plane `/host:CPU`, on the same clock; the benchmark wraps the traced
span in a `TraceAnnotation` named WINDOW_MARK, and that host event is the
window every share is taken over. The capture itself starts before the marker
and ends after it, and cuts whatever was running at either end.

The reduction works on plain tuples so that a test can feed it hand-made
planes: a plane is {"name", "lines": {line name: [(name, start_ns, dur_ns)]}}.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench_window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)


def short_name(name: str, limit: int = 96) -> str:
    """An HLO op's event name is its whole instruction; keep what identifies
    it: `closed_call.67 custom-call f32[32,8,4,128]` (name, op, first shape)."""
    if " = " not in name:
        return name[:limit]
    lhs, rhs = name.split(" = ", 1)
    op = re.search(r"\b([a-z][a-z0-9\-]*)\(", rhs)
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rhs)
    parts = [lhs.lstrip("%"), op.group(1) if op else "", shape.group(0) if shape else ""]
    return " ".join(x for x in parts if x)[:limit]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines: dict[str, list] = {}
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list[dict]) -> list[dict]:
    """Chip planes only: `/device:TPU:3`, not a chip's side planes."""
    return [p for p in planes
            if re.fullmatch(r"/device:[A-Za-z]+:\d+", p["name"])
            and not p["name"].startswith("/device:CPU")]


def busy_events(plane: dict) -> list[tuple]:
    """The events whose union is "an operation ran": the op line, or where a
    trace has none, the module line."""
    return plane["lines"].get(OPS_LINE) or plane["lines"].get(MODULES_LINE) or []


def union_ns(events: list[tuple], lo: float | None = None,
             hi: float | None = None) -> tuple[float, list[tuple]]:
    """Length of the union of the events' intervals, clipped to [lo, hi],
    and the merged intervals."""
    spans = []
    for _, start, dur in events:
        a, b = start, start + dur
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b > a:
            spans.append((a, b))
    spans.sort()
    merged: list[list[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def self_times(events: list[tuple]) -> dict[str, float]:
    """Per op name, the time spent in the op itself: its duration less that
    of the events nested inside it (a `while` spans its body's ops)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1] - 1e-6:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + max(0.0, done[2])
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        done = stack.pop()
        out[done[0]] = out.get(done[0], 0.0) + max(0.0, done[2])
    return out


def whole_runs(plane: dict) -> dict[str, list[float]]:
    """Per module name, the durations (s) of its executions on this chip that
    the capture holds whole. A chip runs one program at a time, so only the
    first and the last event of its module line can have been cut by the
    capture's two ends: those two are left out, by position, whatever their
    length (a cut event is recorded, with the part that was seen)."""
    events = sorted(plane["lines"].get(MODULES_LINE, []), key=lambda e: e[1])
    out: dict[str, list[float]] = {}
    for name, _start, dur in events[1:-1]:
        out.setdefault(name, []).append(dur / 1e9)
    return out


def marked_window(planes: list[dict]) -> tuple[float, float] | None:
    """[start, end] in ns of the host's WINDOW_MARK annotation, if the trace
    has one."""
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for events in p["lines"].values():
            for name, start, dur in events:
                if name == WINDOW_MARK:
                    return start, start + dur
    return None


def device_span(planes: list[dict]) -> tuple[float, float]:
    """[first event start, last event end] over the chip planes, in ns."""
    lo, hi = float("inf"), 0.0
    for p in device_planes(planes):
        for _, start, dur in busy_events(p):
            lo, hi = min(lo, start), max(hi, start + dur)
    return lo, hi


def reduce(planes: list[dict]) -> dict:
    """Everything the per-layer readers take from a trace.

    The window is the host's WINDOW_MARK span, so a chip that stalls at
    either edge of it shows as idle; a trace without the mark (the recorded
    test trace) falls back to the span from the first to the last device
    event. Busy time, gaps and collective time are clipped to the window;
    the op breakdown is of the whole capture (an envelope op cut by the
    window would otherwise take its children's time). busy_s is averaged
    over the chips, idle_share is the worst chip's.
    """
    chips = device_planes(planes)
    if not chips:
        return {}
    mark = marked_window(planes)
    lo, hi = mark or device_span(planes)
    window = (hi - lo) / 1e9 if hi > lo else 0.0
    busy, gaps_all = [], []
    op_self: dict[str, float] = {}
    modules: dict[str, dict] = {}
    collective_ns = 0.0
    for p in chips:
        total, merged = union_ns(busy_events(p), lo, hi)
        busy.append(total / 1e9)
        edges = [lo] + [x for span in merged for x in span] + [hi]
        gaps_all += [(b - a) / 1e9 for a, b in zip(edges[::2], edges[1::2])
                     if b > a]
        ops = p["lines"].get(OPS_LINE, [])
        for name, t in self_times(ops).items():
            key = short_name(name)
            op_self[key] = op_self.get(key, 0.0) + t / 1e9 / len(chips)
        collective_ns += union_ns(
            [e for e in ops if COLLECTIVE.search(e[0])], lo, hi)[0] / len(chips)
        for name, _start, dur in p["lines"].get(MODULES_LINE, []):
            m = modules.setdefault(name, {"count": 0, "total_s": 0.0, "whole": []})
            m["count"] += 1
            m["total_s"] += dur / 1e9
        for name, durs in whole_runs(p).items():
            modules[name]["whole"] += durs
    for m in modules.values():
        w = m.pop("whole")
        m["count"] //= len(chips)
        m["total_s"] /= len(chips)
        m["whole"] = {"count": len(w) // len(chips),
                      "mean_s": sum(w) / len(w) if w else None}
    top_ops = sorted(op_self.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gaps_all, reverse=True)[:10]
    span = device_span(planes)
    return {
        "chips": len(chips),
        "window_from": "host_mark" if mark else "device_events",
        "window_s": window,
        "device_span_s": (span[1] - span[0]) / 1e9,
        "mark_to_first_event_s": (span[0] - lo) / 1e9,
        "last_event_to_mark_s": (hi - span[1]) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_chip": busy,
        "idle_share_worst": (1.0 - min(busy) / window) if window else None,
        "collective_s": collective_ns / 1e9,
        "device_ops": [[n, t] for n, t in top_ops],
        "idle_gaps": [["unattributed", g] for g in gaps],
        "modules": modules,
    }
