"""Most bytes in use on the fullest chip over the measured window's samples,
in GB (1e9 bytes)."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
