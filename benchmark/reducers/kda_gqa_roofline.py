"""HBM roofline shares of a KDA + gated-GQA hybrid's decode step (the
Solar-Open2 cell). Bytes from `benchmark/harness/costs_kda_gqa.py`, counted
as the program's kernels move them (every compiled row of the state, whole
pages of keys and values, every held expert); times from the capture, as
`hybrid_roofline.kernel_step_s` takes them.

`metric`:
- `kda_state`: the float32 state matrices of every compiled batch row, read
  and written once a step, over the `kda_decode` kernel's self time a step
  inside whole `jit_decode_block` executions, in %.
- `paged_attention`: the live requests' keys and values in the GQA layers,
  each request's tokens rounded up to whole pages, over the `paged_attention`
  kernel's self time a step.
- `held_experts`: every held routed expert's int8 bytes over the self time a
  step of the `int8_matmul` calls on the expert stack (those whose result
  leads with the held experts' count; a projection's leads with 1).
- `step`: the whole step (`costs_kda_gqa.decode_step_bytes`) over
  `step_device_ms`.

None where the program has no such kernel (a parent that cannot run the
cell), without a capture, or without a whole decode block.
"""
from benchmark.harness import costs_kda_gqa as costs
from benchmark.reducers import capture as CAP
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.reducers.step_device_ms import read as step_ms


def paged_tokens(ctx) -> float:
    """Mean over the traced window of the rows the live requests' pages
    hold: each request's prompt + tokens streamed so far rounded up to whole
    pages. A request that has been sent and has no token yet waits in the
    queue (a closed loop of more clients than slots always has some): it
    holds no page and is left out."""
    tr = ctx["trace"]
    page = int(ctx["engine_cfg"].kv_page_size)
    grid = [tr["t_start"] + (tr["t_end"] - tr["t_start"]) * (i + 0.5) / 16
            for i in range(16)]
    total = 0.0
    for t in grid:
        for r in ctx["stamps"]["requests"]:
            if r.get("send") is None or r["send"] > t:
                continue
            if r.get("end") is not None and r["end"] < t:
                continue
            got = sum(1 for c in r["chunks"] if c <= t)
            if got:
                total += -(-(r["prompt_tokens"] + got) // page) * page
    return total / len(grid)


def read(ctx, metric):
    if ctx.get("peaks") is None or not ctx.get("trace"):
        return None
    cfg = ctx["config"]
    rows = float(ctx["engine_cfg"].max_slots)
    if metric == "step":
        ms = step_ms(ctx)
        if not ms or CAP.load(ctx) is None:
            return None
        step = ms / 1000.0
        need = costs.decode_step_bytes(
            cfg, rows, paged_tokens(ctx), cfg["bytes_per_weight"],
            cfg["bytes_per_kv"])
    else:
        cap = CAP.load(ctx)
        if cap is None:
            return None
        if metric == "kda_state":
            step = kernel_step_s(cap, "kda_decode")
            need = rows * costs.kda_matrix_bytes_per_row(cfg)
        elif metric == "paged_attention":
            step = kernel_step_s(cap, "paged_attention")
            need = paged_tokens(ctx) * costs.kv_bytes_per_token(
                cfg, cfg["bytes_per_kv"])
        elif metric == "held_experts":
            step = kernel_step_s(cap, "int8_matmul",
                                 lead=cfg["n_routed_experts"])
            need = costs.held_expert_bytes(cfg, cfg["bytes_per_weight"])
        else:
            raise ValueError(metric)
        if not step:
            return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
