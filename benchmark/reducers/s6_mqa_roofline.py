"""HBM roofline shares of an S6 + NoPE multi-query hybrid's decode step (the
AI21-Jamba2 cell), and the S6 operator's share of it. Bytes from
`benchmark/harness/costs_s6_mqa.py`, counted as what MOVES (every compiled row
of the state read and written: `s6_decode` walks idle slots too; the live
requests' tokens, not their pages; every matrix once); times from the
capture, as `hybrid_roofline.kernel_step_s` takes them. Every count errs low,
so no share can pass 100% by its bytes.

`metric`:
- `scan_state`: the float32 state matrices of every compiled batch row read
  and written once a step (`costs_s6_mqa.s6_state_bytes_per_row` x
  max_slots; the kernel's row operands are left out) over the `s6_decode`
  kernel's self time a step inside whole `jit_decode_block` executions, in %.
- `paged_attention`: the live requests' keys and values in the attention
  layers (`costs_s6_mqa.kv_bytes_per_token` x prompt + streamed tokens of
  every request that holds a slot, NOT rounded up to pages) over the
  `paged_attention` kernel's self time a step.
- `proj_matmul`: the int8 matrices (`costs_s6_mqa.proj_matmul_bytes`) over
  the self time a step of the `int8_matmul` calls.
- `step`: the whole step (`costs_s6_mqa.decode_step_bytes`) over
  `step_device_ms`.
- `s6_mix`: self time, inside the marked window, of the decode block's ops
  written under `s6_mix` (the Mamba-1 layer, the operator whole: its four
  matmuls, the conv, the inner norms, the rows read and written, the
  `s6_decode` kernel and the gate; a name written AROUND the scope leaves
  that book its parts, `localai_tpu/observe/scopes.py`) over the self time of
  all the decode block's ops, mean over chips, in %: `scope_share`'s
  account (`kind_of`, self times in the marked window) under a word that is
  no leaf.

None where the program has no such kernel or scope (a parent that cannot run
the cell), without a capture, or without a whole decode block.
"""
from benchmark.harness import costs_s6_mqa as costs
from benchmark.harness import trace_reduce as TRD
from benchmark.harness import xplane_meta as X
from benchmark.reducers import capture as CAP
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.reducers.scope_share import kind_of
from benchmark.reducers.step_device_ms import read as step_ms

S6_MIX = "s6_mix"


def s6_mix_share(planes):
    """The decode block's ops with `s6_mix` in their name's path over all
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
            if S6_MIX in op.tf_op.rsplit(":", 1)[0].split(";")[0].split("/"):
                mine += t
        if mine and total:
            shares.append(100.0 * mine / total)
    return sum(shares) / len(shares) if shares else None


def live_tokens(ctx) -> float:
    """Mean over the traced window of the tokens the live requests hold:
    each request's prompt + tokens streamed so far, as they are (no rounding
    to pages). A request that has no token yet waits in the queue and holds
    none; tokens of blocks not yet delivered are left out: the count errs
    low."""
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
            got = sum(1 for c in r["chunks"] if c <= t)
            if got:
                total += r["prompt_tokens"] + got
    return total / len(grid)


def read(ctx, metric):
    if metric == "s6_mix":
        planes = X.load(ctx)
        return None if planes is None else s6_mix_share(planes)
    if ctx.get("peaks") is None or not ctx.get("trace"):
        return None
    cap = CAP.load(ctx)
    if cap is None:
        return None
    cfg = ctx["config"]
    rows = float(ctx["engine_cfg"].max_slots)
    if metric == "step":
        ms = step_ms(ctx)
        if not ms or not kernel_step_s(cap, "s6_decode"):
            return None  # a program without the kernel is not this model's
        step = ms / 1000.0
        need = costs.decode_step_bytes(
            cfg, rows, live_tokens(ctx), cfg["bytes_per_weight"],
            cfg["bytes_per_kv"])
    elif metric == "scan_state":
        step = kernel_step_s(cap, "s6_decode")
        need = rows * costs.s6_state_bytes_per_row(cfg)
    elif metric == "paged_attention":
        step = kernel_step_s(cap, "paged_attention")
        need = live_tokens(ctx) * costs.kv_bytes_per_token(
            cfg, cfg["bytes_per_kv"])
    elif metric == "proj_matmul":
        step = kernel_step_s(cap, "int8_matmul")
        need = costs.proj_matmul_bytes(cfg, cfg["bytes_per_weight"])
    else:
        raise ValueError(metric)
    if not step:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
