"""Where the engine loop's host time went (PR 51): the `loop_iter` journal
events carry, beside each phase's ms, how much of it lay inside a jax call
(`calls`), in the collector on the loop's own thread (`gc`) and off the CPU
(`off`, a signed sum: see `split`); what is left of a working phase is the loop thread running Python.
`wait` and `pull` are not work (`loop_busy.IDLE`). A window also carries how
late its timed waits came back (`late`) and its longest single stretch of one
working phase (`longest`); a stretch of 100 ms or more is a `loop_stall` event
of its own, and a collection of 1 ms or more a `gc_pause`.

`what` picks the metric:

- `python` / `call` / `off`: ms a decode block dispatched, over the span
  `loop_busy` reads (the marked one in a traced run, the first `loop_iter` of
  it left out), so that python + call + collector + off = loop_host_busy.
- `stretch_max`, `late_max`, `gc_pause_max`, `stall_explained`: over the
  CLEAN window. The benchmark's own thread starts, stops and then parses the
  capture in this process and takes the interpreter from the loop for seconds
  (`loop_busy`'s docstring), so of a traced run only `[t0, capture begin)` and
  the marked span `[t_start, t_end]` are the system's; capture begin is
  recomputed as `run.py` `traced_window` places it, and held against the
  run's `capture_wall_s` (`clean_pieces`). An untraced run's window
  is clean whole. A `loop_iter` holds the time since the one before it, so the
  first of each piece is left out; a `loop_stall` or `gc_pause` counts where
  it lies in a piece whole. `stall_explained` is the share of the stalls' ms
  that has a cause on record (call + collector + off-CPU); 100 where no
  stretch reached 100 ms. `gc_pause_max` is 0 where no pause reached 1 ms.

Every reader returns None on a journal without the new fields (the parent of
PR 51). The first reader of a run prints to standard error the table all of
them reduce: per phase ms, in call, collector, off the CPU, Python; the late
wake-ups; every `loop_stall` with its parts; the same for the stretch of the
window that was left out (what a capture costs the loop); and the identity.
"""
import sys

from benchmark.reducers.loop_busy import IDLE

_CAUSES = ("calls", "gc", "off")
LIST_PAUSE_MS = 20.0  # the table names a collection this long; sums the rest
_printed: list = []  # the journal whose table went to standard error last


def has_account(journal) -> bool:
    return any(e["event"] == "loop_iter" and "calls" in e for e in journal)


def marked_span(ctx):
    """(lo, hi) of the span `loop_busy` reads, None for an open end."""
    tr = ctx.get("trace") or {}
    return tr.get("t_start"), tr.get("t_end")


def clean_pieces(ctx):
    """[(lo, hi)] of the clean window; (None, None) is the whole journal."""
    lo, hi = marked_span(ctx)
    if lo is None or hi is None:
        return [(None, None)]
    t0, seconds = ctx["t0"], float(ctx["seconds"])
    span = min(float(ctx["cell"]["cell"].get("trace_s", 4.0)), seconds * 0.5)
    begin = t0 + (seconds - span) * 0.5
    # Held against the run's own record: the capture began no later than the
    # mark, and no earlier than its measured length before the mark's end.
    # Outside that, `run.py` places it otherwise now: the marked span alone.
    wall = (ctx.get("trace") or {}).get("capture_wall_s")
    if begin > lo or (wall is not None and begin < hi - wall - 1.0):
        print(f"[loop_causes] capture begin recomputed as {begin:.3f} does "
              f"not fit the run (mark {lo:.3f}-{hi:.3f}, capture {wall} s): "
              "the clean window is the marked span alone", file=sys.stderr,
              flush=True)
        return [(lo, hi)]
    return [(t0, begin), (lo, hi)]


def _inside(t, piece, ms=0.0):
    lo, hi = piece
    return ((lo is None or lo <= t - ms / 1000.0)
            and (hi is None or t <= hi))


def windows(journal, piece, first=False):
    """The `loop_iter` events that lie in the piece whole: by their own time,
    the first left out (it began before the piece did) unless asked for."""
    return [e for e in journal if e["event"] == "loop_iter"
            and _inside(e["t"], piece)][0 if first else 1:]


def blocks_in(journal, piece) -> int:
    return sum(1 for e in journal
               if e["event"] == "decode_block" and _inside(e["t"], piece))


def durations(journal, event, piece):
    """The `loop_stall` / `gc_pause` events (b = ms, ending at t) that lie
    in the piece from start to end."""
    return [e for e in journal if e["event"] == event
            and _inside(e["t"], piece, e["b"])]


def account(iters):
    """{phase: [ms, call, collector, off]} summed over `loop_iter` events."""
    per: dict[str, list] = {}
    for e in iters:
        for phase, v in (e.get("phases") or {}).items():
            per.setdefault(phase, [0.0, 0.0, 0.0, 0.0])[0] += v
        for k, cause in enumerate(_CAUSES, 1):
            for phase, v in (e.get(cause) or {}).items():
                per.setdefault(phase, [0.0, 0.0, 0.0, 0.0])[k] += v
    return per


def split(per):
    """(busy, call, collector, off, python) ms over the working phases. The
    program's `off` is a signed sum (a thread's CPU clock may tick in steps
    of 10 ms: an interval reads a tick too much or too little): held at 0 or
    above here, after the summing."""
    work = [v for phase, v in per.items() if phase not in IDLE]
    busy, call, pause, off = (sum(v[k] for v in work) for k in range(4))
    off = max(off, 0.0)
    return busy, call, pause, off, busy - call - pause - off


def _parts(st):
    return (f"{st['phase']} {st['ms']:.1f} ms = call {st['call']:.1f} + "
            f"collector {st['gc']:.1f} + off-CPU {st['off']:.1f} + python "
            f"{st['ms'] - st['call'] - st['gc'] - st['off']:.1f}; did "
            f"{st['did'][0]:.0f} / {st['did'][1]:.0f}")


def table(journal, pieces, title, first=False):
    """The lines of the account of `pieces` of a journal."""
    iters = [e for p in pieces for e in windows(journal, p, first)]
    blocks = sum(blocks_in(journal, p) for p in pieces)
    per = account(iters)
    out = [f"[loop_causes] {title}: {blocks} blocks, {len(iters)} loop_iter "
           "windows; ms per phase: total = in call + collector + off-CPU + "
           "python"]
    for phase, (ms, call, pause, off) in sorted(per.items(),
                                                key=lambda kv: -kv[1][0]):
        rest = "" if phase in IDLE else f" + {ms - call - pause - off:.1f}"
        out.append(f"[loop_causes]   {phase:12s} {ms:9.1f} = {call:.1f} + "
                   f"{pause:.1f} + {off:.1f}{rest}")
    late = [e["late"] for e in iters if e.get("late")]
    longest = max((e["longest"] for e in iters if e.get("longest")),
                  key=lambda st: st["ms"], default=None)
    out.append("[loop_causes]   late wake-ups: sum "
               f"{sum(x['ms'] for x in late):.1f} ms, largest "
               f"{max((x['max'] for x in late), default=0.0):.1f} ms; longest "
               "stretch: " + (_parts(longest) if longest else "none"))
    for p in pieces:
        for e in durations(journal, "loop_stall", p):
            out.append(f"[loop_causes]   loop_stall at {e['t']:.3f}: "
                       + _parts(e["stretch"]))
    pauses = [e for p in pieces for e in durations(journal, "gc_pause", p)]
    out.append(f"[loop_causes]   gc_pause: {len(pauses)} collections of 1 ms "
               f"and more, {sum(e['b'] for e in pauses):.1f} ms in all, "
               f"{sum(e['b'] for e in pauses if e['slot'] == 0):.1f} ms of it "
               f"on the loop thread; those of {LIST_PAUSE_MS:.0f} ms and more:")
    for e in pauses:
        if e["b"] >= LIST_PAUSE_MS:
            out.append(f"[loop_causes]   gc_pause at {e['t']:.3f}: generation "
                       f"{e['a']:.0f}, {e['b']:.1f} ms, "
                       + ("on the loop thread" if e["slot"] == 0
                          else "on another thread"))
    return out


def report(ctx) -> None:
    """The run's table, once, to standard error."""
    journal = ctx["journal"]
    if _printed and _printed[0] is journal:
        return
    _printed[:] = [journal]
    pieces = clean_pieces(ctx)
    lines = table(journal, [marked_span(ctx)], "the marked span")
    busy, call, pause, off, python = split(account(
        windows(journal, marked_span(ctx))))
    blocks = max(1, blocks_in(journal, marked_span(ctx)))
    lines.append(f"[loop_causes]   identity, ms a block: python "
                 f"{python / blocks:.3f} + call {call / blocks:.3f} + "
                 f"collector {pause / blocks:.3f} + off-CPU "
                 f"{off / blocks:.3f} = "
                 f"{(python + call + pause + off) / blocks:.3f}; "
                 f"loop_host_busy of the same span {busy / blocks:.3f}")
    if pieces != [(None, None)]:
        lines += table(journal, pieces, "the clean window")
        left_out = [(pieces[0][1], pieces[1][0]), (pieces[1][1], None)]
        lines += table(journal, left_out,
                       "LEFT OUT (capture start, stop and parse)", first=True)
    print("\n".join(lines), file=sys.stderr, flush=True)


def read(ctx, what):
    journal = ctx["journal"]
    if not has_account(journal):
        return None
    report(ctx)
    if what in ("python", "call", "off"):
        span = marked_span(ctx)
        blocks = blocks_in(journal, span)
        if not blocks:
            return None
        _busy, call, _pause, off, python = split(account(
            windows(journal, span)))
        return {"python": python, "call": call, "off": off}[what] / blocks
    pieces = clean_pieces(ctx)
    iters = [e for p in pieces for e in windows(journal, p)]
    if what == "stretch_max":
        return max((e["longest"]["ms"] for e in iters if e.get("longest")),
                   default=None)
    if what == "late_max":
        return max((e["late"]["max"] for e in iters), default=None)
    if what == "gc_pause_max":
        return max((e["b"] for p in pieces
                    for e in durations(journal, "gc_pause", p)), default=0.0)
    if what == "stall_explained":
        stalls = [e["stretch"] for p in pieces
                  for e in durations(journal, "loop_stall", p)]
        ms = sum(st["ms"] for st in stalls)
        if not ms:
            return 100.0
        return 100.0 * sum(st["call"] + st["gc"] + st["off"]
                           for st in stalls) / ms
    raise ValueError(f"loop_causes: no metric {what!r}")
