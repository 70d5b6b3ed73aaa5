"""Bytes a decode step of a latent-attention decoder over sparse experts
(GLM-4.7-Flash: MLA with a q-lora bottleneck in every layer, one dense MLP
then sigmoid-routed experts beside a shared one, of which a chip holds a
share) has to read, from shapes alone. Kept with the benchmark, beside
`costs.py`, `costs_moe.py` and `costs_hybrid.py`, for the same reason: no PR
that claims a gain can change the yardstick.

`arch` is the configuration file: the published config.json's keys, with
`n_routed_experts` the experts HELD here and `published.n_routed_experts`
all of them, and `assumed.latent_row_values` the values a latent cache row
holds in device memory. What a step touches:

- every matrix once, whatever the batch: every layer's attention (its four
  int8 projections at `bytes_per_weight`, W_kb and W_vb at 2 bytes), the
  shared expert and the router of every MoE layer, the dense MLP, the output
  head; of the held routed experts those some row of the batch chose (the
  measured active share);
- per live token one latent row of every layer, read once.

Norms, scales, the correction bias, the activations, the block's window and
the embedding rows gathered are left out (under 0.1% at these shapes), so
the count errs low and a share computed from it errs low with it.
"""

from __future__ import annotations

BF16 = 2


def layers(arch: dict) -> dict:
    L, kd = arch["num_hidden_layers"], arch["first_k_dense_replace"]
    return {"mla": L, "dense": kd, "moe": L - kd}


def mla_layer_params(arch: dict) -> dict:
    """One layer's attention: {"int8": W_qa, W_qb (or W_q), W_kva, W_o;
    "small": W_kb, W_vb and the two inner norms}."""
    D, H = arch["hidden_size"], arch["num_attention_heads"]
    r, n = arch["kv_lora_rank"], arch["qk_nope_head_dim"]
    rot, v = arch["qk_rope_head_dim"], arch["v_head_dim"]
    ql = arch.get("q_lora_rank") or 0
    q = D * ql + ql * H * (n + rot) if ql else D * H * (n + rot)
    return {"int8": q + D * (r + rot) + H * v * D,
            "small": H * (n + v) * r + r + ql}


def expert_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def held_params(arch: dict) -> dict:
    """Parameters this chip holds, by the rows of PERF.md's table."""
    n = layers(arch)
    D, V = arch["hidden_size"], arch["vocab_size"]
    mla = mla_layer_params(arch)
    return {
        "mla_attention": n["mla"] * (mla["int8"] + mla["small"]),
        "shared_experts": n["moe"] * arch["n_shared_experts"] * expert_params(arch),
        "routers": n["moe"] * D * arch["published"]["n_routed_experts"],
        "dense_mlp": n["dense"] * 3 * D * arch["intermediate_size"],
        "experts_held": n["moe"] * arch["n_routed_experts"] * expert_params(arch),
        "head": V * D,
        "embedding": V * D,
    }


def param_count(arch: dict) -> int:
    """Every parameter of the PUBLISHED language model (what its card calls
    its size, without the MTP block): all `published.n_routed_experts`
    experts a layer, plus the layers' two norms and the final one."""
    h = held_params(arch)
    all_experts = (layers(arch)["moe"] * arch["published"]["n_routed_experts"]
                   * expert_params(arch))
    norms = (2 * arch["num_hidden_layers"] + 1) * arch["hidden_size"]
    return sum(h.values()) - h["experts_held"] + all_experts + norms


def active_params(arch: dict) -> int:
    """Parameters one token's forward pass multiplies by: everything but the
    embedding table and the experts it was not routed to."""
    h = held_params(arch)
    picked = (layers(arch)["moe"] * arch["num_experts_per_tok"]
              * expert_params(arch))
    return (h["mla_attention"] + h["shared_experts"] + h["routers"]
            + h["dense_mlp"] + h["head"] + picked)


def proj_matmul_bytes(arch: dict, bytes_per_weight: float) -> float:
    """The int8 matrices outside the routed experts and the head: what the
    decode block's `int8_matmul` calls whose result leads with 1 walk (every
    layer's four attention projections, every MoE layer's shared expert, the
    dense MLP); scales left out."""
    h = held_params(arch)
    n = layers(arch)
    return (n["mla"] * mla_layer_params(arch)["int8"] + h["shared_experts"]
            + h["dense_mlp"]) * bytes_per_weight


def held_expert_bytes(arch: dict, bytes_per_weight: float,
                      active_share: float = 1.0) -> float:
    return held_params(arch)["experts_held"] * bytes_per_weight * active_share


def weight_bytes(arch: dict, bytes_per_weight: float,
                 active_share: float = 1.0) -> float:
    """Matrix bytes one decode step has to read; `active_share` in [0, 1] is
    the share of (layer, held expert) pairs some row chose."""
    h = held_params(arch)
    small = (layers(arch)["mla"] * mla_layer_params(arch)["small"]
             + h["routers"])
    return (proj_matmul_bytes(arch, bytes_per_weight)
            + h["head"] * bytes_per_weight
            + held_expert_bytes(arch, bytes_per_weight, active_share)
            + small * BF16)


def latent_bytes_per_token(arch: dict, bytes_per_kv: float) -> float:
    """One token's latent rows over the layers, as the kernel reads them
    (rows padded to `assumed.latent_row_values`): 47 x 640 x 2 = 60,160."""
    return (layers(arch)["mla"] * arch["assumed"]["latent_row_values"]
            * bytes_per_kv)


def decode_step_bytes(arch: dict, live_tokens: float, bytes_per_weight: float,
                      bytes_per_kv: float, active_share: float = 1.0) -> float:
    return (weight_bytes(arch, bytes_per_weight, active_share)
            + live_tokens * latent_bytes_per_token(arch, bytes_per_kv))
