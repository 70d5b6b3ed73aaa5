"""Share of the device's idle time, inside the host's marked window, that
lies under a `loop/<phase>` span of the engine loop: a gap then has a cause
to read. Overlap is taken on the trace's own clock, summed over chips. Idle
ms per phase go to standard error. The first and the last `edge_ms` of the
window are left out: the profiler records a span when it ends, so the span
that is open when the capture stops is lost, and the engine closes and
re-opens a long span every 10 ms for that reason (`runtime.SPAN_SLICE_S`);
the device op cut by the capture's end is lost the same way and would read
as a gap. None without a capture, without the window mark or the spans (the
parent of PR 24), or without a chip's plane. Otherwise always a value, since
a traced run has to report the metric: where the chips idle for a fraction
of a millisecond in all (the saturated cells), the share is of the few
microseconds between programs and of the seams between spans, so read it
with the idle ms on standard error beside it. No idle at all reads 100:
nothing is left without a cause.
"""
import sys

from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import capture as CAP

PREFIX = "loop/"


def loop_spans(planes):
    """[(phase, start, end)] of the host's `loop/<phase>` events, by start."""
    out = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for events in p["lines"].values():
                out += [(n[len(PREFIX):], s, s + d) for n, s, d in events
                        if n.startswith(PREFIX)]
    return sorted(out, key=lambda x: x[1])


def idle_gaps(plane, lo, hi):
    _busy, merged = TRD.union_ns(TRD.busy_events(plane), lo, hi)
    edges = [lo] + [x for span in merged for x in span] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def by_phase(planes, edge_ms=0.0):
    """(idle ns in the window, {phase: idle ns under its spans}) over all
    chips, or None where the capture has no window mark, no loop span or no
    chip's plane."""
    mark = TRD.marked_window(planes)
    spans = loop_spans(planes)
    if mark is None or not spans or not TRD.device_planes(planes):
        return None
    lo, hi = mark[0] + edge_ms * 1e6, mark[1] - edge_ms * 1e6
    idle, per = 0.0, {}
    for p in TRD.device_planes(planes):
        for a, b in idle_gaps(p, lo, hi):
            idle += b - a
            for phase, s, e in spans:
                if s >= b:
                    break
                if e > a:
                    per[phase] = per.get(phase, 0.0) + min(e, b) - max(s, a)
    return idle, per


def read(ctx, edge_ms=10.0):
    cap = CAP.load(ctx)
    found = by_phase(cap["planes"], edge_ms) if cap else None
    if found is None:
        return None
    idle, per = found
    print("[idle_phases] idle ms in the window: %.3f; under " % (idle / 1e6)
          + ", ".join(f"loop/{k} {v / 1e6:.3f}" for k, v in
                      sorted(per.items(), key=lambda kv: -kv[1])),
          file=sys.stderr, flush=True)
    return 100.0 * sum(per.values()) / idle if idle else 100.0
