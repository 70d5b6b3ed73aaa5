"""1 - device busy over the traced window, worst chip."""


def read(ctx):
    red = (ctx.get("trace") or {}).get("reduced") or {}
    if red.get("idle_share_worst") is None:
        return None
    return 100.0 * red["idle_share_worst"]
