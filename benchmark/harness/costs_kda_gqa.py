"""Bytes a decode step of a KDA + gated-GQA hybrid with an expert share
(Solar-Open2: periods of one softmax GQA layer over an ordinary K/V pool and
three KDA layers with a per-slot recurrent state; every layer sparse experts
with a shared one, of which a chip holds a share; a pipeline stage's layers
and a share of the vocabulary) has to read and write, from shapes alone. Kept
with the benchmark, beside `costs.py`, `costs_moe.py` and `costs_hybrid.py`,
for the same reason: no PR that claims a gain can change the yardstick.

`arch` is the configuration file: the published config.json's keys, with
`num_hidden_layers`, `n_routed_experts` and `vocab_size` as they are HELD
here (the stage's layers, the held experts, the head's rows) and `published`
the whole model's. `gqa_layers` is the published list; the layers of it below
`num_hidden_layers` exist here. What a step touches, as this program's
kernels are built:

- every matrix once, whatever the batch: the attention of every layer (int8
  at `bytes_per_weight`; KDA's small leaves and the routers at 2 bytes), the
  shared expert and the router of every layer, the head's rows, and EVERY
  held routed expert (the stacked expert kernel reads each held expert,
  chosen or not);
- per compiled batch row the whole recurrent state of every KDA layer, read
  and written (float32; `kda_decode` updates every row, live or not), and
  the conv's last inputs;
- per live request the keys and values of its pages in every GQA layer, read
  once, in whole pages (`paged_attention` moves a page a DMA).

Norms, scales, the decay's vectors, the correction bias, the activations and
the embedding rows gathered are left out (under 0.1% at these shapes), so
the count errs low and a share computed from it errs low with it.
"""

from __future__ import annotations

# One KDA layer's attention parameters, {"int8": ..., "small": ...}: the same
# layer as Kimi-Linear's, read from the same `linear_attn_config` keys.
from benchmark.harness.costs_hybrid import BF16, F32, kda_layer_params


def _lin(arch: dict) -> dict:
    return arch["linear_attn_config"]


def gqa_layer_params(arch: dict) -> int:
    """One softmax layer's: W_q, W_k, W_v, W_o and the gate W_g, all int8."""
    D, H = arch["hidden_size"], arch["num_attention_heads"]
    K, hd = arch["num_key_value_heads"], arch["head_dim"]
    return 3 * D * H * hd + 2 * D * K * hd


def expert_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def layers(arch: dict, published: bool = False) -> dict:
    L = (arch["published"] if published else arch)["num_hidden_layers"]
    gqa = sum(1 for l in arch["gqa_layers"] if l < L)
    return {"gqa": gqa, "kda": L - gqa, "moe": L}


def held_params(arch: dict) -> dict:
    """Parameters this chip holds, by the rows of PERF.md's table."""
    n = layers(arch)
    D, V = arch["hidden_size"], arch["vocab_size"]
    kda = kda_layer_params(arch)
    return {
        "kda_attention": n["kda"] * (kda["int8"] + kda["small"]),
        "gqa_attention": n["gqa"] * gqa_layer_params(arch),
        "shared_router": n["moe"] * (
            arch["n_shared_experts"] * expert_params(arch)
            + D * arch["published"]["n_routed_experts"]),
        "experts_held": n["moe"] * arch["n_routed_experts"] * expert_params(arch),
        "head": V * D,
        "embedding": V * D,
    }


def param_count(arch: dict) -> int:
    """Every parameter of the PUBLISHED model (what its card calls its
    size): all its layers, experts and vocabulary rows, plus the norms."""
    pub = arch["published"]
    n = layers(arch, published=True)
    D = arch["hidden_size"]
    kda = kda_layer_params(arch)
    experts = n["moe"] * (pub["n_routed_experts"]
                          + arch["n_shared_experts"]) * expert_params(arch)
    routers = n["moe"] * D * pub["n_routed_experts"]
    norms = (2 * pub["num_hidden_layers"] + 1) * D
    return (n["kda"] * (kda["int8"] + kda["small"])
            + n["gqa"] * gqa_layer_params(arch) + experts + routers
            + 2 * pub["vocab_size"] * D + norms)


def active_params(arch: dict) -> int:
    """Parameters of the published model one token passes through (its
    card's "A15B"): every attention, the routers, top-k + shared experts,
    the embedding row's matrix and the head."""
    pub = arch["published"]
    n = layers(arch, published=True)
    D = arch["hidden_size"]
    kda = kda_layer_params(arch)
    per_tok = arch["num_experts_per_tok"] + arch["n_shared_experts"]
    return (n["kda"] * (kda["int8"] + kda["small"])
            + n["gqa"] * gqa_layer_params(arch)
            + n["moe"] * (per_tok * expert_params(arch)
                          + D * pub["n_routed_experts"])
            + 2 * pub["vocab_size"] * D)


def weight_bytes(arch: dict, bytes_per_weight: float) -> float:
    """Matrix bytes one decode step reads: every held matrix once."""
    n = layers(arch)
    h = held_params(arch)
    kda = kda_layer_params(arch)
    routers = n["moe"] * arch["hidden_size"] * arch["published"]["n_routed_experts"]
    small = n["kda"] * kda["small"] + routers
    int8 = (h["kda_attention"] + h["gqa_attention"] + h["shared_router"]
            + h["experts_held"] + h["head"] - small)
    return int8 * bytes_per_weight + small * BF16


def held_expert_bytes(arch: dict, bytes_per_weight: float) -> float:
    """What the stacked expert kernel reads a step: every held expert."""
    return held_params(arch)["experts_held"] * bytes_per_weight


def state_bytes_per_row(arch: dict) -> int:
    """One slot's recurrent state over all KDA layers, as held: the float32
    [heads, dk, dv] matrices and the conv's last inputs in bfloat16."""
    lin = _lin(arch)
    H, d = lin["num_heads"], lin["head_dim"]
    conv = (lin["short_conv_kernel_size"] - 1) * 3 * H * d
    return layers(arch)["kda"] * (H * d * d * F32 + conv * BF16)


def kda_matrix_bytes_per_row(arch: dict) -> int:
    """What the KDA decode kernel moves for one slot a step: the float32
    state matrices of every KDA layer, read and written."""
    lin = _lin(arch)
    return 2 * layers(arch)["kda"] * lin["num_heads"] * lin["head_dim"] ** 2 * F32


def kv_bytes_per_token(arch: dict, bytes_per_kv: float) -> float:
    """One token's keys and values over the GQA layers."""
    return (layers(arch)["gqa"] * 2 * arch["num_key_value_heads"]
            * arch["head_dim"] * bytes_per_kv)


def decode_step_bytes(arch: dict, rows: float, paged_tokens: float,
                      bytes_per_weight: float, bytes_per_kv: float) -> float:
    """`rows`: the compiled batch rows; `paged_tokens`: the live requests'
    tokens, each request's rounded up to whole pages."""
    return (weight_bytes(arch, bytes_per_weight)
            + 2 * rows * state_bytes_per_row(arch)
            + paged_tokens * kv_bytes_per_token(arch, bytes_per_kv))
