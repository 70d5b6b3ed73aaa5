"""HBM roofline shares of a mixture-of-experts decode step. The bytes are
`costs_moe`'s: attention projections, output head, the experts weighted by
the share of them that some row of the batch chose (the `moe_experts` journal
events of the measured window, b over a), and for the whole step the live
keys and values.

`metric`:
- `step`: those bytes at the chip's peak HBM bytes/s over the measured device
  time of one decode step (`step_device_ms`), in %.
- `quant_matmul`: the matrix bytes alone over the self time per step of the
  kernels the quantized matrices go through (`kernel_ops.QUANT`), inside
  whole `jit_decode_block` executions, in %.

None where the program journals no routing (a dense model; the parent of the
PR that added the events), without a capture, or without a whole decode block.
"""
from benchmark.harness import costs_moe
from benchmark.reducers import capture as CAP
from benchmark.reducers import kernel_ops
from benchmark.reducers.decode_roofline import live_tokens
from benchmark.reducers import journal_ratio
from benchmark.reducers.step_device_ms import read as step_ms


def active_share(ctx):
    pct = journal_ratio.read(ctx, ["moe_experts", "b"], ["moe_experts", "a"])
    return None if pct is None else pct / 100.0


def read(ctx, metric):
    share = active_share(ctx)
    if share is None or ctx.get("peaks") is None:
        return None
    cfg = ctx["config"]
    if metric == "step":
        ms = step_ms(ctx)
        if not ms:
            return None
        step = ms / 1000.0
        need = costs_moe.decode_step_bytes(
            cfg, live_tokens(ctx), cfg["bytes_per_weight"],
            cfg["bytes_per_kv"], share)
    elif metric == "quant_matmul":
        cap = CAP.load(ctx)
        step = kernel_ops.per_step_s(cap, kernel_ops.QUANT) if cap else None
        if not step:
            return None
        need = costs_moe.weight_bytes(cfg, cfg["bytes_per_weight"], share)
    else:
        raise ValueError(metric)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
