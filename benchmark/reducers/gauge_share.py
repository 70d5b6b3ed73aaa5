"""One Engine.metrics() gauge over another, at the window's end, in %."""


def read(ctx, num, den):
    a = ctx["after"]["metrics"]
    if not a.get(den):
        return None
    return 100.0 * a.get(num, 0.0) / a[den]
