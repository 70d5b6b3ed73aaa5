"""HBM roofline shares of a hybrid (KDA + latent attention + expert share)
decode step. Bytes from `benchmark/harness/costs_hybrid.py`; times from the
capture, as `kernel_ops` takes them, for the two kernels this model brought
(their names are not in `capture.KERNELS`, so they are matched here).

`metric`:
- `kda_state`: the float32 state matrices of the live tenants' rows, read and
  written once a step (live rows from the `state_rows` journal events: b over
  a, times the compiled rows), over the `kda_decode` kernel's self time a
  step inside whole `jit_decode_block` executions, in %.
- `latent_attention`: live tokens x the latent rows' bytes over the
  `latent_paged_attention` kernel's self time a step, in %.
- `held_experts`: the held routed experts' int8 bytes x the share of (layer,
  held expert) pairs some row chose (the `moe_experts` events) over the self
  time a step of the `int8_matmul` calls on the expert stack: those whose
  result leads with the held experts' count (a projection's leads with 1).
- `step`: the whole step (matrices, held experts x their active share, head,
  state, latent rows) over `step_device_ms`, in %.

None where the program has no such kernel or journals no such event (the
parent of the PR that added them), without a capture, or without a whole
decode block.
"""
import re

from benchmark.harness import costs_hybrid
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import capture as CAP
from benchmark.reducers import journal_ratio
from benchmark.reducers.decode_roofline import live_tokens
from benchmark.reducers.step_device_ms import read as step_ms


def kernel_step_s(cap, kernel: str, lead: int | None = None):
    """Seconds of `kernel`'s self time per decode step, mean over chips
    (`kernel_ops.per_step_s` with the name matched here). `lead`: only the
    calls whose (first) result has this leading dimension."""
    n = CAP.block_steps(cap)
    if not n:
        return None
    head = r"^%?" + re.escape(kernel) + r"(?:\.\d+)?"
    named = re.compile(head + (r"(?: = |$)" if lead is None
                               else r" = \(?[a-z]+[0-9]*\[%d," % lead))
    out = []
    for p in TRD.device_planes(cap["planes"]):
        runs = CAP.whole_runs_of(p, CAP.DECODE_BLOCK)
        if not runs:
            continue
        inside = [e for e in p["lines"].get(TRD.OPS_LINE, [])
                  if any(a <= e[1] < b for a, b in runs)]
        t = sum(v for k, v in TRD.self_times(inside).items() if named.match(k))
        if t:
            out.append(t / 1e9 / (len(runs) * n))
    return sum(out) / len(out) if out else None


def live_rows(ctx):
    """Mean live rows a step over the window's decode blocks: the
    `state_rows` events' live share of the compiled batch rows."""
    pct = journal_ratio.read(ctx, ["state_rows", "b"], ["state_rows", "a"])
    if pct is None:
        return None
    return pct / 100.0 * float(ctx["engine_cfg"].max_slots)


def read(ctx, metric):
    rows = live_rows(ctx)
    if rows is None or ctx.get("peaks") is None or not ctx.get("trace"):
        return None
    cfg = ctx["config"]
    share = journal_ratio.read(ctx, ["moe_experts", "b"], ["moe_experts", "a"])
    if metric == "step":
        ms = step_ms(ctx)
        if share is None or not ms:
            return None
        step = ms / 1000.0
        need = costs_hybrid.decode_step_bytes(
            cfg, rows, live_tokens(ctx), cfg["bytes_per_weight"],
            cfg["bytes_per_kv"], share / 100.0)
    else:
        cap = CAP.load(ctx)
        if cap is None:
            return None
        if metric == "kda_state":
            step = kernel_step_s(cap, "kda_decode")
            need = rows * costs_hybrid.kda_matrix_bytes_per_row(cfg)
        elif metric == "latent_attention":
            step = kernel_step_s(cap, "latent_paged_attention")
            need = live_tokens(ctx) * costs_hybrid.latent_bytes_per_token(
                cfg, cfg["bytes_per_kv"])
        elif metric == "held_experts":
            if share is None:
                return None
            step = kernel_step_s(cap, "int8_matmul", lead=cfg["num_experts"])
            need = (costs_hybrid.held_params(cfg)["experts_held"]
                    * cfg["bytes_per_weight"] * share / 100.0)
        else:
            raise ValueError(metric)
        if not step:
            return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
