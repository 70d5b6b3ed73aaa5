"""Host milliseconds the engine loop worked per decode block dispatched: the
`loop_iter` journal events' phase vectors, all phases but `wait` (idle) and
`pull` (blocked on a device result), over the `decode_block` events of the
same span. In a traced run the span is the marked one: outside it the
benchmark starts, stops and reads the capture in this process and takes the
interpreter from the loop (starting a capture stalled one admission dispatch
for 3.3 s in my chip run 5, PR 24). A `loop_iter` event holds the time since
the event before it, so the first one of the span, which began outside it, is
left out. None where the program has no `pull` phase (its only measure of
host time then includes the blocked time). The ms of every phase go to
standard error."""
import sys

IDLE = ("wait", "pull")


def read(ctx):
    tr = ctx.get("trace") or {}
    lo, hi = tr.get("t_start"), tr.get("t_end")
    events = [e for e in ctx["journal"]
              if lo is None or hi is None or lo <= e["t"] <= hi]
    iters = [e.get("phases") or {} for e in events
             if e["event"] == "loop_iter"][1:]
    blocks = sum(1 for e in events if e["event"] == "decode_block")
    if not blocks or not any("pull" in ph for ph in iters):
        return None
    per: dict[str, float] = {}
    for ph in iters:
        for k, v in ph.items():
            per[k] = per.get(k, 0.0) + v
    print(f"[loop_busy] {blocks} blocks, {len(iters)} loop_iter windows, ms: "
          + ", ".join(f"{k} {v:.1f}" for k, v in
                      sorted(per.items(), key=lambda kv: -kv[1])),
          file=sys.stderr, flush=True)
    return sum(v for k, v in per.items() if k not in IDLE) / blocks
