"""Device milliseconds per decode step, from the trace's "XLA Modules" line.

Every engine program is a jitted `wrapped`, so module names tell programs
apart only by their fingerprint; read by hand (PERF.md), the module that
takes most of the device's time in every cell is the decode block. Its mean
duration over the executions the trace holds whole (none: no number), divided
by the steps of a block, is the device time of one decode step
for the whole batch (one token for every live request). The block's steps
are the journal's most frequent `decode_block` size in the window (64 in
steady state): a name on the program would make this exact, see PERF.md
"for the tracing issue". Admission programs are other modules and are not
in this number.
"""
from collections import Counter

from benchmark.reducers.batch_occupancy import blocks


def dominant_module(ctx):
    red = (ctx.get("trace") or {}).get("reduced") or {}
    mods = red.get("modules") or {}
    if not mods:
        return None
    name = max(mods, key=lambda n: mods[n]["total_s"])
    m = mods[name]
    # Only executions traced whole count: a block is seconds long, and a
    # window that cuts every execution it holds says nothing about one.
    return name, m["whole"]["mean_s"]


def block_steps(ctx):
    sizes = Counter(int(n) for _, n, _ in blocks(ctx["journal"]))
    return sizes.most_common(1)[0][0] if sizes else None


def read(ctx):
    dom, n = dominant_module(ctx), block_steps(ctx)
    if dom is None or dom[1] is None or not n:
        return None
    return 1000.0 * dom[1] / n
