"""The traced run's capture, opened once more for the readers that need the
op events themselves (`trace_reduce.reduce` keeps only the ten largest) and
the stats of the engine's dispatch annotations. One capture is up to 136 MB
and several metrics read it, so the last one opened is kept.

What the program writes into a capture (PERF.md "Reading the trace"): a
Pallas kernel's `name=` is the name of its HLO instruction, so its "XLA Ops"
events are named `%<kernel>.<n> = ... custom-call(...)`; a jitted engine
program is the module `jit_<kind>(<fingerprint>)`; the engine loop's phases
are host events `loop/<phase>`, and each decode-block dispatch is a host
event `dispatch/decode_block` with the stats `n` (steps) and `live` (rows).
"""
from __future__ import annotations

import re

from benchmark.harness import trace_reduce as TRD

KERNELS = ("paged_attention", "int8_matmul", "int4_matmul", "int8_unembed",
           "flash_prefill", "lora_matmul")
_KERNEL = re.compile(r"^%?(" + "|".join(KERNELS) + r")(?:\.\d+)?(?: = |$)")
DECODE_BLOCK = "jit_decode_block"
_last: dict = {}


def read_file(path: str) -> dict:
    """{"planes": as `trace_reduce.load_planes`, "dispatch": [(name, start_ns,
    dur_ns, stats)] for the host's `dispatch/...` events}."""
    from jax.profiler import ProfileData

    planes, dispatch = [], []
    for plane in ProfileData.from_file(path).planes:
        lines: dict[str, list] = {}
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
                if host and ev.name.startswith("dispatch/"):
                    dispatch.append(evs[-1] + (dict(ev.stats),))
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "dispatch": dispatch}


def load(ctx) -> dict | None:
    """The capture of this run, or None where the run was not traced or the
    capture cannot be read. A test may put it into the context directly
    (`ctx["trace"]["capture"]`)."""
    tr = ctx.get("trace") or {}
    if tr.get("capture") is not None:
        return tr["capture"]
    if not tr.get("dir"):
        return None
    try:
        path = TRD.find_xplane(tr["dir"])
        if _last.get("path") != path:
            _last.clear()
            _last.update(path=path, capture=read_file(path))
    except (FileNotFoundError, ValueError, OSError):
        return None
    return _last["capture"]


def kernel_of(event_name: str) -> str | None:
    m = _KERNEL.match(event_name)
    return m.group(1) if m else None


def kernel_self_ns(ops: list[tuple]) -> dict[str, float]:
    """Per named kernel, the self time of its op events (an envelope such as
    a `while` that spans them takes none of it; nor does the kernel take the
    time of anything nested inside it)."""
    out: dict[str, float] = {}
    for name, t in TRD.self_times(ops).items():
        k = kernel_of(name)
        if k:
            out[k] = out.get(k, 0.0) + t
    return out


def whole_runs_of(plane: dict, prefix: str) -> list[tuple[float, float]]:
    """[start, end) in ns of the executions of the modules named `prefix...`
    that the capture holds whole on this chip: as `trace_reduce.whole_runs`,
    the first and the last event of the chip's module line are left out."""
    events = sorted(plane["lines"].get(TRD.MODULES_LINE, []), key=lambda e: e[1])
    return [(s, s + d) for name, s, d in events[1:-1] if name.startswith(prefix)]


def block_steps(capture: dict) -> float | None:
    """Steps of a decode block, from the dispatch annotations the capture
    holds (their mean, if the engine dispatched more than one size)."""
    ns = [float(st["n"]) for name, _s, _d, st in capture["dispatch"]
          if name == "dispatch/decode_block" and "n" in st]
    return sum(ns) / len(ns) if ns else None
