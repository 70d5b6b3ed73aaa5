"""Share of the device time of all programs in the capture that went to the
programs whose module name starts with one of `prefixes` (`jit_admit`,
`jit_prefill_chunk`: the admission programs), in %, from the "XLA Modules"
line as `trace_reduce.reduce` sums it. None without a trace, and where the
program does not name its modules (no module is `named`, the decode block's
name: every module is then `jit_wrapped`)."""


def read(ctx, prefixes, named="jit_decode_block"):
    red = (ctx.get("trace") or {}).get("reduced") or {}
    mods = red.get("modules") or {}
    total = sum(m["total_s"] for m in mods.values())
    if not total or not any(n.startswith(named) for n in mods):
        return None
    part = sum(m["total_s"] for n, m in mods.items()
               if any(n.startswith(p) for p in prefixes))
    return 100.0 * part / total
