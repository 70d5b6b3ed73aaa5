"""Device time inside collective ops over device busy time."""


def read(ctx):
    red = (ctx.get("trace") or {}).get("reduced") or {}
    if not red.get("busy_s"):
        return None
    return 100.0 * red["collective_s"] / red["busy_s"]
