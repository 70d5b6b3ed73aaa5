"""Window delta of one Engine.metrics() counter over that of another."""


def read(ctx, num, den):
    b, a = ctx["before"]["metrics"], ctx["after"]["metrics"]
    d = a.get(den, 0.0) - b.get(den, 0.0)
    if d <= 0:
        return None
    return (a.get(num, 0.0) - b.get(num, 0.0)) / d
