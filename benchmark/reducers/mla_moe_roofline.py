"""HBM roofline shares of a latent-attention MoE decoder's decode step (the
GLM-4.7-Flash cell), and its latent pool's block write. Bytes from
`benchmark/harness/costs_mla_moe.py`; times from the capture, as
`hybrid_roofline.kernel_step_s` takes them.

The latent rows a step reads come from the program's own account, the
`latent_rows` journal events (one a dispatched decode block: a = rows its
live slots held at dispatch x its steps, b = the pool's rows x its steps),
and not from the generator's stamps (`decode_roofline.live_tokens`), which
also count the prompts of the clients that wait in the queue: rows a step =
sum a / (sum b / pool rows), the pool's rows from the engine's configuration.

`metric`:
- `latent_attention`: those rows x `costs_mla_moe.latent_bytes_per_token`
  over the `latent_paged_attention` kernel's self time a step inside whole
  `jit_decode_block` executions, in %.
- `held_experts`: the held routed experts' int8 bytes x the share of (layer,
  held expert) pairs some row chose (the `moe_experts` journal events, b over
  a) over the self time a step of the `int8_matmul` calls on the expert stack
  (those whose result leads with the held experts' count).
- `proj_matmul`: the int8 matrices outside the experts and the head
  (`costs_mla_moe.proj_matmul_bytes`: the four attention projections of every
  layer, the shared experts, the dense MLP) over the self time a step of the
  `int8_matmul` calls whose result leads with 1.
- `step`: the whole step (`costs_mla_moe.decode_step_bytes`) over
  `step_device_ms`.
- `latent_write`: self time, inside the marked window, of the decode block's
  ops written under `latent_write` (the staged write of the block's window
  into the latent pool, `localai_tpu/observe/scopes.py`) over the self time
  of all the decode block's ops, mean over chips, in %.

None where the program journals no `latent_rows` or no routing, has no such
kernel or scope (a parent that cannot run the cell), without a capture, or
without a whole decode block.
"""
from benchmark.harness import costs_mla_moe as costs
from benchmark.harness import trace_reduce as TRD
from benchmark.harness import xplane_meta as X
from benchmark.reducers import capture as CAP
from benchmark.reducers import journal_ratio
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.reducers.scope_share import kind_of
from benchmark.reducers.step_device_ms import read as step_ms

LATENT_WRITE = "latent_write"


def word_share(planes, word: str):
    """The decode block's ops with `word` a segment of their name over all
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
            if word in op.tf_op.rsplit(":", 1)[0].split(";")[0].split("/"):
                mine += t
        if mine and total:
            shares.append(100.0 * mine / total)
    return sum(shares) / len(shares) if shares else None


def latent_rows(ctx):
    """Mean latent rows a decode step's page walks read, over the window's
    dispatched blocks; None where the program journals none."""
    a = journal_ratio.total(ctx["journal"], "latent_rows", "a")
    b = journal_ratio.total(ctx["journal"], "latent_rows", "b")
    ecfg = ctx["engine_cfg"]
    pool = float(ecfg.kv_pages) * float(ecfg.kv_page_size)
    if a is None or not b or not pool:
        return None
    return a * pool / b


def read(ctx, metric):
    if metric == "latent_write":
        planes = X.load(ctx)
        return None if planes is None else word_share(planes, LATENT_WRITE)
    if ctx.get("peaks") is None or not ctx.get("trace"):
        return None
    rows = latent_rows(ctx)
    share = journal_ratio.read(ctx, ["moe_experts", "b"], ["moe_experts", "a"])
    cap = CAP.load(ctx)
    if cap is None:
        return None
    cfg = ctx["config"]
    if metric == "step":
        ms = step_ms(ctx)
        if share is None or rows is None or not ms:
            return None
        step = ms / 1000.0
        need = costs.decode_step_bytes(
            cfg, rows, cfg["bytes_per_weight"], cfg["bytes_per_kv"],
            share / 100.0)
    elif metric == "latent_attention":
        if rows is None:
            return None
        step = kernel_step_s(cap, "latent_paged_attention")
        need = rows * costs.latent_bytes_per_token(cfg, cfg["bytes_per_kv"])
    elif metric == "proj_matmul":
        step = kernel_step_s(cap, "int8_matmul", lead=1)
        need = costs.proj_matmul_bytes(cfg, cfg["bytes_per_weight"])
    elif metric == "held_experts":
        if share is None:
            return None
        step = kernel_step_s(cap, "int8_matmul", lead=cfg["n_routed_experts"])
        need = costs.held_expert_bytes(cfg, cfg["bytes_per_weight"],
                                       share / 100.0)
    else:
        raise ValueError(metric)
    if not step:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step
