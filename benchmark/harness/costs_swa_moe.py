"""Bytes a decode step of a decoder of window and full attention layers over
sparse experts (Laguna-XS.2: 1 full GQA layer to 3 sliding-window ones with
their own head count, one dense MLP then sigmoid-routed experts beside a
shared one, of which a chip holds a share) has to read, from shapes alone.
Kept with the benchmark, beside `costs.py`, `costs_moe.py`, `costs_hybrid.py`
and `costs_mla_moe.py`, for the same reason: no PR that claims a gain can
change the yardstick.

`arch` is the configuration file: the published config.json's keys, with
`num_experts` the experts HELD here and `published.num_experts` all of them.
What a step touches:

- every matrix once, whatever the batch: every layer's attention (W_q, W_k,
  W_v, W_o at `bytes_per_weight`, the per-head gate at 2 bytes), the shared
  expert and the router of every expert layer, the dense MLP, the output
  head; of the held routed experts those some row of the batch chose (the
  measured active share);
- per live slot, in each WINDOW layer, the rows its ring holds, at most
  `sliding_window`, whatever the context: K and V of every KV head;
- per live token one row of K and V in each FULL layer.

Norms, scales, the selection bias, the activations, the block's own rows and
the embedding rows gathered are left out (under 0.1% at these shapes), so the
count errs low and a share computed from it errs low with it.
"""

from __future__ import annotations

BF16 = 2


def layers(arch: dict) -> dict:
    kinds = arch["layer_types"]
    mlp = arch["mlp_layer_types"]
    return {"full": kinds.count("full_attention"),
            "window": kinds.count("sliding_attention"),
            "dense": mlp.count("dense"), "moe": mlp.count("sparse")}


def heads(arch: dict) -> dict:
    """Query heads by kind of layer: one count a kind."""
    by = {}
    for kind, h in zip(arch["layer_types"],
                       arch["num_attention_heads_per_layer"]):
        by.setdefault(kind, h)
    return {"full": by["full_attention"], "window": by["sliding_attention"]}


def attn_layer_params(arch: dict, kind: str) -> dict:
    """One attention layer of `kind` ("full" | "window"): {"int8": W_q, W_k,
    W_v, W_o; "small": the per-head gate}."""
    D, d = arch["hidden_size"], arch["head_dim"]
    H, K = heads(arch)[kind], arch["num_key_value_heads"]
    return {"int8": 2 * D * H * d + 2 * D * K * d, "small": D * H}


def expert_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def held_params(arch: dict) -> dict:
    """Parameters this chip holds, by the rows of PERF.md's table."""
    n = layers(arch)
    D, V = arch["hidden_size"], arch["vocab_size"]
    full, win = attn_layer_params(arch, "full"), attn_layer_params(arch, "window")
    return {
        "full_attention": n["full"] * (full["int8"] + full["small"]),
        "window_attention": n["window"] * (win["int8"] + win["small"]),
        "shared_experts": n["moe"] * 3 * D * arch["shared_expert_intermediate_size"],
        "routers": n["moe"] * D * arch["published"]["num_experts"],
        "dense_mlp": n["dense"] * 3 * D * arch["intermediate_size"],
        "experts_held": n["moe"] * arch["num_experts"] * expert_params(arch),
        "head": V * D,
        "embedding": V * D,
    }


def param_count(arch: dict) -> int:
    """Every parameter of the PUBLISHED model (what its card calls its size):
    all `published.num_experts` experts a layer, plus the layers' two norms
    and the final one."""
    h = held_params(arch)
    all_experts = (layers(arch)["moe"] * arch["published"]["num_experts"]
                   * expert_params(arch))
    norms = (2 * arch["num_hidden_layers"] + 1) * arch["hidden_size"]
    return sum(h.values()) - h["experts_held"] + all_experts + norms


def proj_matmul_bytes(arch: dict, bytes_per_weight: float) -> float:
    """The int8 matrices outside the routed experts and the head: what the
    decode block's `int8_matmul` calls whose result leads with 1 walk (the
    four attention projections of every layer, every expert layer's shared
    expert, the dense MLP); scales left out."""
    n, h = layers(arch), held_params(arch)
    attn = (n["full"] * attn_layer_params(arch, "full")["int8"]
            + n["window"] * attn_layer_params(arch, "window")["int8"])
    return (attn + h["shared_experts"] + h["dense_mlp"]) * bytes_per_weight


def held_expert_bytes(arch: dict, bytes_per_weight: float,
                      active_share: float = 1.0) -> float:
    return held_params(arch)["experts_held"] * bytes_per_weight * active_share


def weight_bytes(arch: dict, bytes_per_weight: float,
                 active_share: float = 1.0) -> float:
    """Matrix bytes one decode step has to read; `active_share` in [0, 1] is
    the share of (layer, held expert) pairs some row chose."""
    n, h = layers(arch), held_params(arch)
    small = (n["full"] * attn_layer_params(arch, "full")["small"]
             + n["window"] * attn_layer_params(arch, "window")["small"]
             + h["routers"])
    return (proj_matmul_bytes(arch, bytes_per_weight)
            + h["head"] * bytes_per_weight
            + held_expert_bytes(arch, bytes_per_weight, active_share)
            + small * BF16)


def kv_row_bytes(arch: dict, bytes_per_kv: float) -> float:
    """One position's K and V in ONE layer: 8 x 128 x 2 x 2 = 4,096."""
    return (2 * arch["num_key_value_heads"] * arch["head_dim"] * bytes_per_kv)


def window_bytes(arch: dict, window_rows: float, bytes_per_kv: float) -> float:
    """`window_rows`: rows ONE window layer's reader walks a step, summed
    over the live slots (each at most `sliding_window`)."""
    return window_rows * kv_row_bytes(arch, bytes_per_kv) * layers(arch)["window"]


def paged_bytes(arch: dict, live_tokens: float, bytes_per_kv: float) -> float:
    """`live_tokens`: rows the live slots hold in the paged pool."""
    return live_tokens * kv_row_bytes(arch, bytes_per_kv) * layers(arch)["full"]


def decode_step_bytes(arch: dict, window_rows: float, live_tokens: float,
                      bytes_per_weight: float, bytes_per_kv: float,
                      active_share: float = 1.0) -> float:
    return (weight_bytes(arch, bytes_per_weight, active_share)
            + window_bytes(arch, window_rows, bytes_per_kv)
            + paged_bytes(arch, live_tokens, bytes_per_kv))
