"""Share of the device's idle time, inside the host's marked window, that
lies under a host span whose name starts with `prefix` (PR 51: `host/gc`, a
collection anywhere in the process while the capture ran). The window, its
edges, the idle gaps and the overlap are `idle_phases`' own: the capture is
handed to `idle_phases.by_phase` with the spans of the prefix standing where
it looks for the loop's. 0 where the capture holds no such span. None without
a capture, the window mark or a chip's plane, and on a program that writes no
such span (the parent of PR 51, known by its journal: `loop_causes`)."""
import sys

from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import capture as CAP
from benchmark.reducers import idle_phases as IP
from benchmark.reducers import loop_causes as LC


def as_loop_spans(planes, prefix):
    """The planes with every host line cut down to the window mark and the
    spans of `prefix`, those renamed so that `idle_phases.loop_spans` takes
    them; and how many there were."""
    out, n = [], 0
    for p in planes:
        if not p["name"].startswith("/host:"):
            out.append(p)
            continue
        lines = {}
        for line, events in p["lines"].items():
            spans = [(IP.PREFIX + name, s, d) for name, s, d in events
                     if name.startswith(prefix)]
            n += len(spans)
            lines[line] = spans + [e for e in events
                                   if e[0] == TRD.WINDOW_MARK]
        out.append({"name": p["name"], "lines": lines})
    return out, n


def read(ctx, prefix, edge_ms=10.0):
    cap = CAP.load(ctx)
    if not cap or not LC.has_account(ctx["journal"]):
        return None
    planes = cap["planes"]
    if TRD.marked_window(planes) is None or not TRD.device_planes(planes):
        return None
    view, n = as_loop_spans(planes, prefix)
    if not n:
        print(f"[span_idle] no {prefix} span in the capture", file=sys.stderr,
              flush=True)
        return 0.0
    idle, per = IP.by_phase(view, edge_ms)
    under = sum(per.values())
    print(f"[span_idle] {n} {prefix} spans in the capture; idle ms in the "
          f"window: {idle / 1e6:.3f}, of it under {prefix}: {under / 1e6:.3f}",
          file=sys.stderr, flush=True)
    return 100.0 * under / idle if idle else 0.0
