"""A percentile, in ms, of the time each request spent between two of its
journal events (`start`, then the first `end` after it), over the requests
whose both events lie in the measured window: `queued` -> `admitted` is the
wait for a slot, `first_token` -> `decode_first` the wait to join the decode
stream. With `until_capture_read`, in a traced run only the waits that end
before the capture stops count: from then on the benchmark reads the capture
in this process, the loop falls behind the device, and a result it is late
for is followed at once by the next. The spread of the waits, and of those
left out, goes to standard error. None where no request has both events (the
program does not journal one, or no request got that far)."""
import sys

from benchmark.harness.stamps import percentile


def waits_ms(events, start, end, until=None):
    """[ms] per request; with `until`, only the waits that end before it."""
    began: dict[str, float] = {}
    out = []
    for e in events:
        rid = e.get("rid")
        if not rid:
            continue
        if e["event"] == start:
            began.setdefault(rid, e["t"])
        elif e["event"] == end and rid in began:
            wait = (e["t"] - began.pop(rid)) * 1000.0
            if until is None or e["t"] <= until:
                out.append(wait)
    return out


def spread(waits):
    return f"{len(waits)} requests, ms at " + ", ".join(
        f"p{p} {percentile(waits, p):.1f}" for p in (0, 10, 25, 50, 75, 90, 100))


def read(ctx, start, end, q, until_capture_read=False):
    until = (ctx.get("trace") or {}).get("t_end") if until_capture_read else None
    waits = waits_ms(ctx["journal"], start, end, until)
    if not waits:
        return None
    print(f"[journal_wait] {start} -> {end}: {spread(waits)}"
          + (f"; all of the window: {spread(waits_ms(ctx['journal'], start, end))}"
             if until is not None else ""), file=sys.stderr, flush=True)
    return percentile(waits, q)
