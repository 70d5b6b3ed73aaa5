"""Bytes a decode step of an SSD + NoPE-GQA hybrid with an expert share
(Granite-4.0-H: Mamba-2 layers with a per-slot float32 state beside a few GQA
layers over an ordinary paged K/V pool; every layer sparse experts, of which a
chip holds a share, beside a shared MLP; a tied bfloat16 head) has to read and
write, from shapes alone. Kept with the benchmark, beside `costs.py`,
`costs_moe.py`, `costs_hybrid.py`, `costs_kda_gqa.py` and
`costs_conv_gqa.py`, for the same reason: no PR that claims a gain can change
the yardstick.

`arch` is the configuration file: the published config.json's keys
(`layer_types`, `mamba_*`, `intermediate_size` the width of one expert,
`shared_intermediate_size`, ...), with `num_local_experts` as HELD here and
`published` the whole model's. What a step touches, as this program's
kernels are built:

- every matrix outside the experts once, whatever the batch: `in_proj`
  [D, 2 d_inner + 2 G N + H] (the program holds its three column blocks as
  three leaves: the same bytes) and `out_proj` [d_inner, D] of every Mamba
  layer, the four projections of every attention layer, the shared MLP of
  every layer (int8 at `bytes_per_weight`), the routers, the conv's taps and
  bias and the gated norm's weight (bfloat16), and the head, which is the
  embedding (tied: bfloat16 as held, 2 bytes a weight whatever
  `bytes_per_weight` says);
- of a layer's HELD experts (three matrices each) a step HAS to read only
  those that some row of the batch chose, so the held experts' bytes are
  weighted by the measured share of (layer, held expert) pairs that were
  active (`costs_moe`'s rule: the stacked kernel reads every held expert, so
  the count errs low and a share computed from it cannot pass 100%);
- per compiled batch row the whole recurrent state of every Mamba layer,
  read AND written (float32 [H, P, N]; `ssd_decode` updates every row, live
  or not), its operands (dt x [H, P] in, y [H, P] out, the decay's rows
  [H, N], B and C [G, N], float32) and the conv's held inputs, read and
  written (bfloat16, `mamba_d_conv` - 1 rows of d_inner + 2 G N);
- per live request the keys and values of its pages in every attention
  layer, read once, in whole pages (`paged_attention` moves a page a DMA).

Norms, scales, the decay's vectors, the activations and the embedding rows
gathered are left out (under 0.1% at these shapes): the count errs low.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def layers(arch: dict) -> dict:
    kinds = arch["layer_types"]
    ssd = sum(1 for k in kinds if k == "mamba")
    return {"ssd": ssd, "gqa": len(kinds) - ssd, "moe": len(kinds)}


def d_inner(arch: dict) -> int:
    return arch["mamba_n_heads"] * arch["mamba_d_head"]


def conv_dim(arch: dict) -> int:
    return d_inner(arch) + 2 * arch["mamba_n_groups"] * arch["mamba_d_state"]


def ssd_layer_params(arch: dict) -> dict:
    """One Mamba layer's: {"int8": in_proj + out_proj, "small": the taps and
    their bias, the gated norm's weight, dt_bias, A_log, D}."""
    D, H = arch["hidden_size"], arch["mamba_n_heads"]
    di, cd = d_inner(arch), conv_dim(arch)
    return {"int8": D * (di + cd + H) + di * D,
            "small": (arch["mamba_d_conv"] + 1) * cd + di + 3 * H}


def gqa_layer_params(arch: dict) -> int:
    """One attention layer's W_q, W_k, W_v, W_o, all int8."""
    D, H, K, hd = (arch["hidden_size"], arch["num_attention_heads"],
                   arch["num_key_value_heads"], arch["head_dim"])
    return 2 * D * H * hd + 2 * D * K * hd


def expert_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["intermediate_size"]


def shared_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["shared_intermediate_size"]


def router_params(arch: dict) -> int:
    return arch["hidden_size"] * arch["published"]["num_local_experts"]


def held_params(arch: dict) -> dict:
    """Parameters this chip holds, by the rows of PERF.md's table."""
    n = layers(arch)
    ssd = ssd_layer_params(arch)
    return {
        "ssd_mixers": n["ssd"] * (ssd["int8"] + ssd["small"]),
        "gqa_attention": n["gqa"] * gqa_layer_params(arch),
        "shared_router": n["moe"] * (shared_params(arch) + router_params(arch)),
        "experts_held": n["moe"] * arch["num_local_experts"] * expert_params(arch),
        "embedding": arch["vocab_size"] * arch["hidden_size"],  # also the head
    }


def param_count(arch: dict) -> int:
    """Every parameter of the PUBLISHED model (what its card calls its
    size): all the experts, plus the norms; the tied matrix once."""
    h = held_params(arch)
    n = layers(arch)
    D = arch["hidden_size"]
    experts = n["moe"] * arch["published"]["num_local_experts"] * expert_params(arch)
    norms = (2 * n["moe"] + 1) * D
    return (h["ssd_mixers"] + h["gqa_attention"] + h["shared_router"] + experts
            + h["embedding"] + norms)


def active_params(arch: dict) -> int:
    """Parameters of the published model one token passes through (its
    card's "A9B"): every mixer, the routers and shared MLPs, top-k experts a
    layer, and the tied matrix twice, as the embedding it is read from and as
    the head (how the card arrives at 9B)."""
    h = held_params(arch)
    n = layers(arch)
    return (h["ssd_mixers"] + h["gqa_attention"] + h["shared_router"]
            + n["moe"] * arch["num_experts_per_tok"] * expert_params(arch)
            + 2 * h["embedding"])


def held_expert_bytes(arch: dict, bytes_per_weight: float,
                      active_share: float = 1.0) -> float:
    """What a step has to read of the held experts: those some row chose."""
    return held_params(arch)["experts_held"] * bytes_per_weight * active_share


def proj_matmul_bytes(arch: dict, bytes_per_weight: float) -> float:
    """The int8 matrices outside the experts, each read once a step by the
    dense dequant-matmul: `in_proj` and `out_proj` of every Mamba layer, the
    four projections of every attention layer, the shared MLP of every
    layer. Their scales are left out: the count errs low."""
    n = layers(arch)
    return bytes_per_weight * (
        n["ssd"] * ssd_layer_params(arch)["int8"]
        + n["gqa"] * gqa_layer_params(arch) + n["moe"] * shared_params(arch))


def weight_bytes(arch: dict, bytes_per_weight: float,
                 active_share: float = 1.0) -> float:
    """Matrix bytes one decode step has to read."""
    n = layers(arch)
    h = held_params(arch)
    small = n["ssd"] * ssd_layer_params(arch)["small"] + n["moe"] * router_params(arch)
    int8 = h["ssd_mixers"] + h["gqa_attention"] + h["shared_router"] - small
    return (int8 * bytes_per_weight + small * BF16 + h["embedding"] * BF16
            + held_expert_bytes(arch, bytes_per_weight, active_share))


def state_bytes_per_row(arch: dict) -> int:
    """One slot's recurrent state over all Mamba layers, as held: the
    float32 [H, P, N] matrices and the conv's last inputs in bfloat16."""
    H, P, N = arch["mamba_n_heads"], arch["mamba_d_head"], arch["mamba_d_state"]
    conv = (arch["mamba_d_conv"] - 1) * conv_dim(arch)
    return layers(arch)["ssd"] * (H * P * N * F32 + conv * BF16)


def ssd_kernel_bytes_per_row(arch: dict) -> int:
    """What the SSD decode kernel moves for one slot a step over all Mamba
    layers: the float32 state read and written, and its operands: dt x in and
    y out [H, P], the decay's rows [H, N], B and C [G, N]."""
    H, P, N, G = (arch["mamba_n_heads"], arch["mamba_d_head"],
                  arch["mamba_d_state"], arch["mamba_n_groups"])
    return layers(arch)["ssd"] * F32 * (
        2 * H * P * N + 2 * H * P + H * N + 2 * G * N)


def kv_bytes_per_token(arch: dict, bytes_per_kv: float) -> float:
    """One token's keys and values over the attention layers."""
    return (layers(arch)["gqa"] * 2 * arch["num_key_value_heads"]
            * arch["head_dim"] * bytes_per_kv)


def decode_step_bytes(arch: dict, rows: float, paged_tokens: float,
                      bytes_per_weight: float, bytes_per_kv: float,
                      active_share: float = 1.0) -> float:
    """`rows`: the compiled batch rows; `paged_tokens`: the live requests'
    tokens, each request's rounded up to whole pages."""
    conv = (2 * layers(arch)["ssd"] * (arch["mamba_d_conv"] - 1)
            * conv_dim(arch) * BF16)
    return (weight_bytes(arch, bytes_per_weight, active_share)
            + rows * (ssd_kernel_bytes_per_row(arch) + conv)
            + paged_tokens * kv_bytes_per_token(arch, bytes_per_kv))
