"""Bytes a decode step of a hybrid linear-attention decoder (Kimi-Linear:
KDA layers with a per-slot recurrent state, MLA layers with latent cache
rows, one dense MLP then sparse experts with a shared one, of which a chip
holds a share) has to read and write, from shapes alone. Kept with the
benchmark, beside `costs.py` and `costs_moe.py`, for the same reason: no PR
that claims a gain can change the yardstick.

`arch` is the configuration file: the published config.json's keys, with
`num_experts` the experts HELD here and `published.num_experts` all of them,
and `assumed.latent_row_values` the values a latent cache row holds in
device memory. What a step touches:

- every matrix once, whatever the batch: the attention of every layer (its
  int8 matrices at `bytes_per_weight`, the small bfloat16 ones at 2 bytes),
  the shared expert and the router of every MoE layer, the dense MLP, the
  output head; of the held routed experts those some row of the batch chose
  (the measured active share);
- per live request the whole recurrent state of every KDA layer, read and
  written (float32), and the conv's last inputs, read and written;
- per live token one latent row of every MLA layer, read once.

Norms, scales, the decay's vectors, the correction bias, the activations and
the embedding rows gathered are left out (under 0.1% at these shapes), so
the count errs low and a share computed from it errs low with it.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def _lin(arch: dict) -> dict:
    return arch["linear_attn_config"]


def kda_layer_params(arch: dict) -> dict:
    """One KDA layer's attention parameters: {"int8": the four projections,
    "small": low-rank pairs, W_beta, conv taps, decay vectors, head norm}."""
    D = arch["hidden_size"]
    HK = _lin(arch)["num_heads"] * _lin(arch)["head_dim"]
    r = _lin(arch)["head_dim"]  # assumed.kda_gate_rank: the head width
    conv = _lin(arch)["short_conv_kernel_size"] * 3 * HK
    small = (2 * (D * r + r * HK) + D * _lin(arch)["num_heads"] + conv
             + HK + _lin(arch)["num_heads"] + _lin(arch)["head_dim"])
    return {"int8": 4 * D * HK, "small": small}


def mla_layer_params(arch: dict) -> dict:
    """One MLA layer's: {"int8": W_q, W_kva, W_o; "small": W_kvb, kv norm}."""
    D, H = arch["hidden_size"], arch["num_attention_heads"]
    r, n = arch["kv_lora_rank"], arch["qk_nope_head_dim"]
    rot, v = arch["qk_rope_head_dim"], arch["v_head_dim"]
    return {"int8": D * H * (n + rot) + D * (r + rot) + H * v * D,
            "small": H * (n + v) * r + r}


def expert_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def layers(arch: dict) -> dict:
    lin = _lin(arch)
    L, kd = arch["num_hidden_layers"], arch["first_k_dense_replace"]
    return {"kda": len(lin["kda_layers"]), "mla": len(lin["full_attn_layers"]),
            "dense": kd, "moe": L - kd}


def held_params(arch: dict) -> dict:
    """Parameters this chip holds, by the rows of PERF.md's table."""
    n = layers(arch)
    D, V = arch["hidden_size"], arch["vocab_size"]
    kda, mla = kda_layer_params(arch), mla_layer_params(arch)
    return {
        "kda_attention": n["kda"] * (kda["int8"] + kda["small"]),
        "mla_attention": n["mla"] * (mla["int8"] + mla["small"]),
        "shared_router_dense": (
            n["moe"] * (arch["num_shared_experts"] * expert_params(arch)
                        + D * arch["published"]["num_experts"])
            + n["dense"] * 3 * D * arch["intermediate_size"]),
        "experts_held": n["moe"] * arch["num_experts"] * expert_params(arch),
        "head": V * D,
        "embedding": V * D,
    }


def param_count(arch: dict) -> int:
    """Every parameter of the PUBLISHED model (what its card calls its
    size): all `published.num_experts` experts a layer, plus the norms."""
    h = held_params(arch)
    n = layers(arch)
    all_experts = (n["moe"] * arch["published"]["num_experts"]
                   * expert_params(arch))
    norms = (2 * arch["num_hidden_layers"] + 1) * arch["hidden_size"]
    return sum(h.values()) - h["experts_held"] + all_experts + norms


def weight_bytes(arch: dict, bytes_per_weight: float,
                 active_share: float = 1.0) -> float:
    """Matrix bytes one decode step has to read; `active_share` in [0, 1] is
    the share of (layer, held expert) pairs some row chose."""
    n = layers(arch)
    D = arch["hidden_size"]
    kda, mla = kda_layer_params(arch), mla_layer_params(arch)
    int8 = (n["kda"] * kda["int8"] + n["mla"] * mla["int8"]
            + n["moe"] * arch["num_shared_experts"] * expert_params(arch)
            + n["dense"] * 3 * D * arch["intermediate_size"]
            + arch["vocab_size"] * D
            + held_params(arch)["experts_held"] * active_share)
    small = (n["kda"] * kda["small"] + n["mla"] * mla["small"]
             + n["moe"] * D * arch["published"]["num_experts"])
    return int8 * bytes_per_weight + small * BF16


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


def latent_bytes_per_token(arch: dict, bytes_per_kv: float) -> float:
    """One token's latent rows over the MLA layers, as the kernel reads
    them (rows padded to `assumed.latent_row_values`)."""
    return (layers(arch)["mla"] * arch["assumed"]["latent_row_values"]
            * bytes_per_kv)


def decode_step_bytes(arch: dict, live_rows: float, live_tokens: float,
                      bytes_per_weight: float, bytes_per_kv: float,
                      active_share: float = 1.0) -> float:
    return (weight_bytes(arch, bytes_per_weight, active_share)
            + 2 * live_rows * state_bytes_per_row(arch)
            + live_tokens * latent_bytes_per_token(arch, bytes_per_kv))
