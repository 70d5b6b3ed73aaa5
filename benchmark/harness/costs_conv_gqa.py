"""Bytes a decode step of a conv + GQA hybrid with whole experts (LFM2-MoE:
gated short convolutions with a two-row per-slot state beside a few GQA
layers of 64-wide heads over an ordinary paged K/V pool; a dense prefix, then
sparse experts held whole; a tied bfloat16 head) has to read and write, from
shapes alone. Kept with the benchmark, beside `costs.py`, `costs_moe.py`,
`costs_hybrid.py` and `costs_kda_gqa.py`, for the same reason: no PR that
claims a gain can change the yardstick.

`arch` is the configuration file: the published config.json's keys
(`layer_types`, `conv_L_cache`, `num_dense_layers`, `num_experts`,
`moe_intermediate_size`, ...), nothing reduced. What a step touches:

- every matrix outside the experts once, whatever the batch: W_in [D, 3D]
  and W_out [D, D] of every conv layer, the four projections of every
  attention layer, the dense prefix's SwiGLUs (int8 at `bytes_per_weight`),
  the routers and the conv taps (bfloat16), and the head, which is the
  embedding (tied: bfloat16 as held, 2 bytes a weight whatever
  `bytes_per_weight` says);
- of a layer's E experts (three matrices each) a step HAS to read only those
  that some row of the batch chose, so the expert bytes are weighted by the
  measured share of (layer, expert) pairs that were active (`costs_moe`'s
  rule: the stacked kernel reads every expert, so the count errs low and a
  share computed from it cannot pass 100%);
- per compiled batch row the conv's held inputs of every conv layer, read
  and written (bfloat16, `conv_L_cache` - 1 rows of D);
- per live request the keys and values of its pages in every attention layer,
  read once, in whole pages (`paged_attention` moves a page a DMA); a token's
  8 heads of 64 are stored as 4 rows of 128 lanes, the same bytes.

Norms, scales, the expert bias, the activations and the embedding rows
gathered are left out (under 0.1% at these shapes): the count errs low.
"""

from __future__ import annotations

BF16 = 2


def layers(arch: dict) -> dict:
    kinds = arch["layer_types"]
    conv = sum(1 for k in kinds if k == "conv")
    dense = int(arch["num_dense_layers"])
    return {"conv": conv, "gqa": len(kinds) - conv, "dense": dense,
            "moe": len(kinds) - dense}


def head_dim(arch: dict) -> int:
    return arch["hidden_size"] // arch["num_attention_heads"]


def conv_layer_params(arch: dict) -> dict:
    """One conv layer's operator: {"int8": W_in + W_out, "small": the taps}."""
    D = arch["hidden_size"]
    return {"int8": 4 * D * D, "small": arch["conv_L_cache"] * D}


def gqa_layer_params(arch: dict) -> int:
    """One attention layer's W_q, W_k, W_v, W_o, all int8."""
    D, H, K = (arch["hidden_size"], arch["num_attention_heads"],
               arch["num_key_value_heads"])
    return 2 * D * H * head_dim(arch) + 2 * D * K * head_dim(arch)


def expert_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def held_params(arch: dict) -> dict:
    """Parameters the chip holds (the whole model), by PERF.md's rows."""
    n = layers(arch)
    D = arch["hidden_size"]
    conv = conv_layer_params(arch)
    return {
        "conv": n["conv"] * (conv["int8"] + conv["small"]),
        "attention": n["gqa"] * gqa_layer_params(arch),
        "dense_mlp": n["dense"] * 3 * D * arch["intermediate_size"],
        "experts": n["moe"] * arch["num_experts"] * expert_params(arch),
        "routers": n["moe"] * D * arch["num_experts"],
        "embedding": arch["vocab_size"] * D,  # also the head
    }


def param_count(arch: dict) -> int:
    """Every parameter of the model (what its card calls its size): the
    matrices above once (the tied head is the embedding), the norms (two a
    layer, the last one, q and k of every attention layer) and the experts'
    bias."""
    n = layers(arch)
    D = arch["hidden_size"]
    norms = (2 * len(arch["layer_types"]) + 1) * D + n["gqa"] * 2 * head_dim(arch)
    return (sum(held_params(arch).values()) + norms
            + n["moe"] * arch["num_experts"])


def active_params(arch: dict) -> int:
    """Parameters one token passes through (the card's "A1.5B"): every
    operator, the dense prefix, the routers, top-k experts a MoE layer, the
    embedding once (row in, head out)."""
    n = layers(arch)
    h = held_params(arch)
    return (h["conv"] + h["attention"] + h["dense_mlp"] + h["routers"]
            + n["moe"] * arch["num_experts_per_tok"] * expert_params(arch)
            + h["embedding"])


def expert_bytes(arch: dict, bytes_per_weight: float,
                 active_share: float = 1.0) -> float:
    """What a step has to read of the expert stacks: the (layer, expert)
    pairs some row chose."""
    return held_params(arch)["experts"] * bytes_per_weight * active_share


def weight_bytes(arch: dict, bytes_per_weight: float,
                 active_share: float = 1.0) -> float:
    """Matrix bytes one decode step has to read."""
    n = layers(arch)
    h = held_params(arch)
    small = n["conv"] * conv_layer_params(arch)["small"] + h["routers"]
    int8 = h["conv"] + h["attention"] + h["dense_mlp"] - (small - h["routers"])
    return (int8 * bytes_per_weight + (small + h["embedding"]) * BF16
            + expert_bytes(arch, bytes_per_weight, active_share))


def conv_bytes_per_row(arch: dict) -> int:
    """One slot's conv rows over all conv layers, as held (bfloat16)."""
    return (layers(arch)["conv"] * (arch["conv_L_cache"] - 1)
            * arch["hidden_size"] * BF16)


def kv_bytes_per_token(arch: dict, bytes_per_kv: float) -> float:
    """One token's keys and values over the attention layers, as stored."""
    return (layers(arch)["gqa"] * 2 * arch["num_key_value_heads"]
            * head_dim(arch) * bytes_per_kv)


def decode_step_bytes(arch: dict, rows: float, paged_tokens: float,
                      bytes_per_weight: float, bytes_per_kv: float,
                      active_share: float = 1.0) -> float:
    """`rows`: the compiled batch rows; `paged_tokens`: the live requests'
    tokens, each request's rounded up to whole pages; `active_share` in
    [0, 1]: the share of (layer, expert) pairs some row chose."""
    return (weight_bytes(arch, bytes_per_weight, active_share)
            + 2 * rows * conv_bytes_per_row(arch)
            + paged_tokens * kv_bytes_per_token(arch, bytes_per_kv))
