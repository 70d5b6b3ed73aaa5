"""HBM roofline shares of a conv + GQA hybrid's decode step (the LFM2 cell),
and the conv operator's share of it. Bytes from
`benchmark/harness/costs_conv_gqa.py`; times from the capture, as
`hybrid_roofline.kernel_step_s` takes them.

`metric`:
- `step`: the whole step (`costs_conv_gqa.decode_step_bytes`: every matrix
  outside the experts once, the experts x their active share, the bfloat16
  head, the compiled rows' conv inputs read and written, the live requests'
  whole pages of keys and values) at the chip's peak HBM bytes/s over
  `step_device_ms`, in %.
- `experts`: the experts' int8 bytes x the share of (layer, expert) pairs
  some row chose (the `moe_experts` journal events, b over a) over the self
  time a step of the `int8_matmul` calls on the expert stack: those whose
  result leads with the experts' count (a projection's leads with 1).
- `paged_attention`: the live requests' keys and values in the attention
  layers, each request's tokens rounded up to whole pages, as stored (two
  64-wide heads a 128-lane row: the same bytes), over the `paged_attention`
  kernel's self time a step.
- `conv_mix`: self time, inside the marked window, of the decode block's ops
  written under `conv_mix` (the gated short convolution, the operator whole:
  its in- and out-projection, u = b * z, the rows read and written, the taps
  and the gate; a name written AROUND the scope leaves that book its parts,
  `localai_tpu/observe/scopes.py`, so that every op of the operator carries
  it whatever op XLA names a fusion after) over the self time of all the
  decode block's ops, mean over chips, in %. `scope_share` cannot tell the
  conv layers' operator from the attention layers': its vocabulary holds
  leaves only.

None where the program has no such kernel or scope or journals no routing (a
parent that cannot run the cell), without a capture, or without a whole
decode block.
"""
from benchmark.harness import costs_conv_gqa as costs
from benchmark.harness import trace_reduce as TRD
from benchmark.harness import xplane_meta as X
from benchmark.reducers import capture as CAP
from benchmark.reducers import journal_ratio
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.reducers.kda_gqa_roofline import paged_tokens
from benchmark.reducers.scope_share import kind_of
from benchmark.reducers.step_device_ms import read as step_ms

CONV_MIX = "conv_mix"


def conv_mix_share(planes):
    """The decode block's ops with `conv_mix` anywhere in their name over
    all its ops, self time in the marked window, mean over chips, in %; None
    where no op is."""
    lo, hi = TRD.marked_window(planes) or TRD.device_span(planes)
    shares = []
    for p in planes:
        if not p.get("ops"):
            continue
        kinds = {m.program_id: kind_of(m.name) for m in p["modules"]}
        own, _ = X.self_ns(p["ops"])
        mine = total = 0.0
        for op, t in zip(p["ops"], own):
            if not lo <= op.start_ns < hi:
                continue
            if not kinds.get(op.program_id, "").startswith(CAP.DECODE_BLOCK):
                continue
            total += t
            if CONV_MIX in op.tf_op.rsplit(":", 1)[0].split(";")[0].split("/"):
                mine += t
        if mine and total:
            shares.append(100.0 * mine / total)
    return sum(shares) / len(shares) if shares else None


def read(ctx, metric):
    if metric == "conv_mix":
        planes = X.load(ctx)
        return None if planes is None else conv_mix_share(planes)
    if ctx.get("peaks") is None or not ctx.get("trace"):
        return None
    share = journal_ratio.read(ctx, ["moe_experts", "b"], ["moe_experts", "a"])
    cap = CAP.load(ctx)
    if cap is None:
        return None
    cfg = ctx["config"]
    if metric == "step":
        ms = step_ms(ctx)
        if share is None or not ms:
            return None
        step = ms / 1000.0
        need = costs.decode_step_bytes(
            cfg, float(ctx["engine_cfg"].max_slots), paged_tokens(ctx),
            cfg["bytes_per_weight"], cfg["bytes_per_kv"], share / 100.0)
    elif metric == "experts":
        if share is None:
            return None
        step = kernel_step_s(cap, "int8_matmul", lead=cfg["num_experts"])
        need = costs.expert_bytes(cfg, cfg["bytes_per_weight"], share / 100.0)
    elif metric == "paged_attention":
        step = kernel_step_s(cap, "paged_attention")
        need = paged_tokens(ctx) * costs.kv_bytes_per_token(
            cfg, cfg["bytes_per_kv"])
    else:
        raise ValueError(metric)
    if not step:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
