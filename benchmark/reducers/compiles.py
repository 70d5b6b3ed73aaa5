"""Programs compiled, or loaded from the persistent cache, in the window."""


def read(ctx):
    return float(ctx["after"]["compiles"]["requests"]
                 - ctx["before"]["compiles"]["requests"])
