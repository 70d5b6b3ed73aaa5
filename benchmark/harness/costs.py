"""Bytes a decode step has to read, from shapes alone. Kept with the
benchmark so that no PR which claims a gain can change the yardstick.

A decode step of a dense GQA decoder reads every projection matrix and the
output head once, whatever the batch, and the keys and values of every live
token once. Activations, scales and the embedding rows gathered are left out
(under 0.1% at these shapes), so the count errs low and the share it gives
errs low with it: it can never flatter a kernel.
"""

from __future__ import annotations


def weight_bytes(arch: dict, bytes_per_weight: float, chips: int = 1) -> float:
    D, F = arch["hidden_size"], arch["intermediate_size"]
    H, K, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                arch["head_dim"])
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    total = arch["num_hidden_layers"] * per_layer + arch["vocab_size"] * D
    return total * bytes_per_weight / chips


def kv_bytes_per_token(arch: dict, bytes_per_value: float, chips: int = 1) -> float:
    return (2 * arch["num_key_value_heads"] * arch["head_dim"] * bytes_per_value
            * arch["num_hidden_layers"] / chips)


def decode_step_bytes(arch: dict, live_tokens: float, bytes_per_weight: float,
                      bytes_per_kv: float, chips: int = 1) -> float:
    """Per chip: its share of the weights plus its share of the live cache."""
    return (weight_bytes(arch, bytes_per_weight, chips)
            + live_tokens * kv_bytes_per_token(arch, bytes_per_kv, chips))
