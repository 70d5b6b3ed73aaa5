"""Share of the HBM roofline a decode step reaches: the least time the chip
needs to read the step's weights and live keys and values at its peak bytes/s,
over the measured device time per step. The bound is `hbm` (a decode step at
these batch sizes is far below the compute roof)."""
from benchmark.harness import costs
from benchmark.reducers.step_device_ms import read as step_ms


def live_tokens(ctx):
    """Mean over the traced window of the tokens held by live requests:
    prompt + tokens streamed so far, from the generator's stamps."""
    tr = ctx["trace"]
    grid = [tr["t_start"] + (tr["t_end"] - tr["t_start"]) * (i + 0.5) / 16
            for i in range(16)]
    total = 0.0
    for t in grid:
        for r in ctx["stamps"]["requests"]:
            if r.get("send") is None or r["send"] > t:
                continue
            if r.get("end") is not None and r["end"] < t:
                continue
            total += r["prompt_tokens"] + sum(1 for c in r["chunks"] if c <= t)
    return total / len(grid)


def read(ctx):
    ms = step_ms(ctx)
    if ms is None or ctx.get("peaks") is None:
        return None
    cfg = ctx["config"]
    chips = ctx["cell"]["chips"]
    need = costs.decode_step_bytes(cfg, live_tokens(ctx), cfg["bytes_per_weight"],
                                   cfg["bytes_per_kv"], chips)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ms / 1000.0)
