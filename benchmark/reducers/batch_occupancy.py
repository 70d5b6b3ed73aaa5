"""Share of the decode batch's rows that held a live request, weighted by
decode steps: each `decode_block` journal event (a = steps) is followed by the
`loop_iter` that closed its window (a = active slots)."""


def blocks(events):
    """[(time, steps, active slots)] for every decode block in `events`."""
    out, pending = [], None
    for e in events:
        if e["event"] == "decode_block":
            pending = e
        elif e["event"] == "loop_iter" and pending is not None:
            out.append((pending["t"], pending["a"], e["a"]))
            pending = None
    return out


def read(ctx):
    bl = blocks(ctx["journal"])
    steps = sum(n for _, n, _ in bl)
    if not steps:
        return None
    slots = float(ctx["engine_cfg"].max_slots)
    return 100.0 * sum(n * a for _, n, a in bl) / (steps * slots)
