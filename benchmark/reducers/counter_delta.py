"""Window delta of one Engine.metrics() counter."""


def read(ctx, key):
    b, a = ctx["before"]["metrics"], ctx["after"]["metrics"]
    if key not in a:
        return None
    return a[key] - b.get(key, 0.0)
