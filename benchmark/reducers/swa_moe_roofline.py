"""HBM roofline shares of a decode step of a decoder of window and full
attention layers over sparse experts (the Laguna-XS.2 cell), and what its
window saves. Bytes from `benchmark/harness/costs_swa_moe.py`; times from the
capture, as `hybrid_roofline.kernel_step_s` takes them.

The rows a step reads come from the program's own account, the `window_rows`
journal events (one a dispatched decode block: a = rows ONE window layer's
reader walks in it, each live slot's rows at dispatch cut to its ring, x its
steps; b = the same at full length, which is what the full layers' page
walks read), and not from the generator's stamps, which also count the
prompts of the clients that wait in the queue. Steps in the window: the
`decode_rows` events' compiled rows over the engine's slots.

`metric`:
- `window_attention`: rows a step x 4,096 B x the window layers
  (`costs_swa_moe.window_bytes`) over the `window_attention` kernel's self
  time a step inside whole `jit_decode_block` executions, in %.
- `paged_attention`: live tokens a step x 4,096 B x the full layers
  (`costs_swa_moe.paged_bytes`) over the `paged_attention` kernel's.
- `held_experts`: the held routed experts' int8 bytes x the share of (layer,
  held expert) pairs some row chose (the `moe_experts` journal events, b over
  a) over the self time a step of the `int8_matmul` calls on the expert stack
  (those whose result leads with the held experts' count).
- `proj_matmul`: the int8 matrices outside the experts and the head
  (`costs_swa_moe.proj_matmul_bytes`) over the self time a step of the
  `int8_matmul` calls whose result leads with 1.
- `step`: the whole step (`costs_swa_moe.decode_step_bytes`) over
  `step_device_ms`.
- `rows_saved`: 100 x (1 - sum a / sum b) of the `window_rows` events: the
  share of a full-length walk the window layers did not read. No capture
  needed.

None where the program journals no `window_rows` or no routing, has no such
kernel (a parent that cannot run the cell), without a capture, or without a
whole decode block.
"""
from benchmark.harness import costs_swa_moe as costs
from benchmark.reducers import capture as CAP
from benchmark.reducers import journal_ratio
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.reducers.step_device_ms import read as step_ms


def rows_a_step(ctx):
    """(rows one window layer's reader walks a step, live tokens a step),
    means over the window's dispatched blocks; None where the program
    journals none."""
    a = journal_ratio.total(ctx["journal"], "window_rows", "a")
    b = journal_ratio.total(ctx["journal"], "window_rows", "b")
    compiled = journal_ratio.total(ctx["journal"], "decode_rows", "a")
    slots = float(ctx["engine_cfg"].max_slots)
    if a is None or b is None or not compiled or not slots:
        return None
    steps = compiled / slots
    return a / steps, b / steps


def read(ctx, metric):
    if metric == "rows_saved":
        a = journal_ratio.total(ctx["journal"], "window_rows", "a")
        b = journal_ratio.total(ctx["journal"], "window_rows", "b")
        return None if a is None or not b else 100.0 * (1.0 - a / b)
    if ctx.get("peaks") is None or not ctx.get("trace"):
        return None
    rows = rows_a_step(ctx)
    share = journal_ratio.read(ctx, ["moe_experts", "b"], ["moe_experts", "a"])
    cap = CAP.load(ctx)
    if cap is None or rows is None:
        return None
    cfg = ctx["config"]
    bw, bkv = cfg["bytes_per_weight"], cfg["bytes_per_kv"]
    if metric == "step":
        ms = step_ms(ctx)
        if share is None or not ms:
            return None
        step = ms / 1000.0
        need = costs.decode_step_bytes(cfg, rows[0], rows[1], bw, bkv,
                                       share / 100.0)
    elif metric == "window_attention":
        step = kernel_step_s(cap, "window_attention")
        need = costs.window_bytes(cfg, rows[0], bkv)
    elif metric == "paged_attention":
        step = kernel_step_s(cap, "paged_attention")
        need = costs.paged_bytes(cfg, rows[1], bkv)
    elif metric == "proj_matmul":
        step = kernel_step_s(cap, "int8_matmul", lead=1)
        need = costs.proj_matmul_bytes(cfg, bw)
    elif metric == "held_experts":
        if share is None:
            return None
        step = kernel_step_s(cap, "int8_matmul", lead=cfg["num_experts"])
        need = costs.held_expert_bytes(cfg, bw, share / 100.0)
    else:
        raise ValueError(metric)
    if not step:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
