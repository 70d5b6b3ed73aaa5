"""HBM roofline shares of an SSD + NoPE-GQA hybrid's decode step (the
Granite-4.0-H cell), and the SSD operator's share of it. Bytes from
`benchmark/harness/costs_ssd_gqa.py`, counted as the program's kernels move
them (every compiled row of the state read and written, whole pages of keys
and values, the held experts some row chose); times from the capture, as
`hybrid_roofline.kernel_step_s` takes them.

`metric`:
- `ssd_state`: the float32 state matrices of every compiled batch row read
  and written once a step, and the kernel's operands
  (`costs_ssd_gqa.ssd_kernel_bytes_per_row` x max_slots), over the
  `ssd_decode` kernel's self time a step inside whole `jit_decode_block`
  executions, in %.
- `paged_attention`: the live requests' keys and values in the attention
  layers, each request's tokens rounded up to whole pages, over the
  `paged_attention` kernel's self time a step.
- `held_experts`: the held routed experts' int8 bytes x the share of (layer,
  held expert) pairs some row chose (the `moe_experts` journal events, b over
  a) over the self time a step of the `int8_matmul` calls on the expert
  stack: those whose result leads with the held experts' count (a
  projection's leads with 1).
- `proj_matmul`: the int8 matrices outside the experts (`in_proj` and
  `out_proj` of every Mamba layer, the attention layers' four projections,
  every layer's shared MLP: `costs_ssd_gqa.proj_matmul_bytes`) over the self
  time a step of the `int8_matmul` calls whose result leads with 1: the dense
  dequant-matmul, which walks a column count with no 128-multiple divisor in
  single lane tiles (`in_proj` as one 16,768-column matrix read 19%).
- `step`: the whole step (`costs_ssd_gqa.decode_step_bytes`) over
  `step_device_ms`.
- `ssd_mix`: self time, inside the marked window, of the decode block's ops
  written under `ssd_mix` (the Mamba-2 layer, the operator whole: its in- and
  out-projection, the conv, the rows read and written, the `ssd_decode`
  kernel and the gated norm; a name written AROUND the scope leaves that book
  its parts, `localai_tpu/observe/scopes.py`) over the self time of all the
  decode block's ops, mean over chips, in %: `conv_gqa_roofline`'s reading
  under another word.

None where the program has no such kernel or scope or journals no routing (a
parent that cannot run the cell), without a capture, or without a whole
decode block.
"""
from benchmark.harness import costs_ssd_gqa as costs
from benchmark.harness import trace_reduce as TRD
from benchmark.harness import xplane_meta as X
from benchmark.reducers import capture as CAP
from benchmark.reducers import journal_ratio
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.reducers.kda_gqa_roofline import paged_tokens
from benchmark.reducers.scope_share import kind_of
from benchmark.reducers.step_device_ms import read as step_ms

SSD_MIX = "ssd_mix"


def ssd_mix_share(planes):
    """The decode block's ops with `ssd_mix` anywhere in their name over all
    its ops, self time in the marked window, mean over chips, in %; None
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
            if SSD_MIX in op.tf_op.rsplit(":", 1)[0].split(";")[0].split("/"):
                mine += t
        if mine and total:
            shares.append(100.0 * mine / total)
    return sum(shares) / len(shares) if shares else None


def read(ctx, metric):
    if metric == "ssd_mix":
        planes = X.load(ctx)
        return None if planes is None else ssd_mix_share(planes)
    if ctx.get("peaks") is None or not ctx.get("trace"):
        return None
    share = journal_ratio.read(ctx, ["moe_experts", "b"], ["moe_experts", "a"])
    cap = CAP.load(ctx)
    if cap is None:
        return None
    cfg = ctx["config"]
    rows = float(ctx["engine_cfg"].max_slots)
    if metric == "step":
        ms = step_ms(ctx)
        if share is None or not ms:
            return None
        step = ms / 1000.0
        need = costs.decode_step_bytes(
            cfg, rows, paged_tokens(ctx), cfg["bytes_per_weight"],
            cfg["bytes_per_kv"], share / 100.0)
    elif metric == "ssd_state":
        step = kernel_step_s(cap, "ssd_decode")
        need = rows * costs.ssd_kernel_bytes_per_row(cfg)
    elif metric == "paged_attention":
        step = kernel_step_s(cap, "paged_attention")
        need = paged_tokens(ctx) * costs.kv_bytes_per_token(
            cfg, cfg["bytes_per_kv"])
    elif metric == "proj_matmul":
        step = kernel_step_s(cap, "int8_matmul", lead=1)
        need = costs.proj_matmul_bytes(cfg, cfg["bytes_per_weight"])
    elif metric == "held_experts":
        if share is None:
            return None
        step = kernel_step_s(cap, "int8_matmul", lead=cfg["num_local_experts"])
        need = costs.held_expert_bytes(cfg, cfg["bytes_per_weight"],
                                       share / 100.0)
    else:
        raise ValueError(metric)
    if not step:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
