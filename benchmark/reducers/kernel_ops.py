"""Metrics of the named Pallas kernels, from the op events of the capture.

`metric`:
- `time_share`: self time of the ops of every named kernel over the device's
  busy time, both over the whole capture, mean over chips, in %.
- `paged_attention_roofline`: the bytes of live keys and values one decode
  step has to read (`costs.kv_bytes_per_token` x the live tokens of
  `decode_roofline.live_tokens`, per chip) at the chip's peak HBM bytes/s,
  over the measured self time of `paged_attention` per decode step, in %.
- `quant_matmul_roofline`: the same for the dequant matmul kernels
  (`int8_matmul`, `int4_matmul`, `int8_unembed`). The matrices that go
  through them are every projection of every layer (q, k, v, o, gate, up,
  down) and the output head, each read once a step: `costs.weight_bytes`.
  Their scales and the activations are left out, so the share errs low.

Time per step: the kernel's self time inside the executions of
`jit_decode_block` that the capture holds whole, over those executions'
steps; a block's steps come from the engine's `dispatch/decode_block`
annotation. None without a capture, without named kernels in it (the parent
of PR 24), or without a whole decode block.
"""
from benchmark.harness import costs
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import capture as CAP
from benchmark.reducers.decode_roofline import live_tokens

QUANT = ("int8_matmul", "int4_matmul", "int8_unembed")


def time_share(cap):
    shares = []
    for p in TRD.device_planes(cap["planes"]):
        ops = p["lines"].get(TRD.OPS_LINE, [])
        busy = TRD.union_ns(ops)[0]
        kernels = CAP.kernel_self_ns(ops)
        if busy and kernels:
            shares.append(100.0 * sum(kernels.values()) / busy)
    return sum(shares) / len(shares) if shares else None


def per_step_s(cap, kernels):
    """Seconds of the given kernels' self time per decode step, mean over
    chips."""
    n = CAP.block_steps(cap)
    if not n:
        return None
    out = []
    for p in TRD.device_planes(cap["planes"]):
        runs = CAP.whole_runs_of(p, CAP.DECODE_BLOCK)
        if not runs:
            continue
        inside = [e for e in p["lines"].get(TRD.OPS_LINE, [])
                  if any(a <= e[1] < b for a, b in runs)]
        t = CAP.kernel_self_ns(inside)
        t = sum(t.get(k, 0.0) for k in kernels)
        if t:
            out.append(t / 1e9 / (len(runs) * n))
    return sum(out) / len(out) if out else None


def read(ctx, metric):
    cap = CAP.load(ctx)
    if cap is None:
        return None
    if metric == "time_share":
        return time_share(cap)
    if ctx.get("peaks") is None:
        return None
    cfg, chips = ctx["config"], ctx["cell"]["chips"]
    if metric == "paged_attention_roofline":
        step = per_step_s(cap, ("paged_attention",))
        need = live_tokens(ctx) * costs.kv_bytes_per_token(
            cfg, cfg["bytes_per_kv"], chips)
    elif metric == "quant_matmul_roofline":
        step = per_step_s(cap, QUANT)
        need = costs.weight_bytes(cfg, cfg["bytes_per_weight"], chips)
    else:
        raise ValueError(metric)
    if not step:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
