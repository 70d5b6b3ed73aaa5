"""Bytes a decode step of a sparse mixture-of-experts decoder has to read,
from shapes alone. Kept with the benchmark, beside `costs.py`, for the same
reason: no PR that claims a gain can change the yardstick.

Every layer reads its four attention projections once, whatever the batch;
the output head is read once; of the layer's E experts (three matrices each,
`intermediate_size` wide: the published config's `intermediate_size` IS the
expert's width in this family, there is no dense MLP) a step HAS to read only
those that at least one row of the batch chose, so the expert bytes are
weighted by the measured share of experts that were active. The router, the
scales, the norms, the activations and the embedding rows gathered are left
out (under 0.2% at these shapes), so the count errs low and a share computed
from it errs low with it: it can never flatter a kernel, nor pass 100%.
"""

from __future__ import annotations

from benchmark.harness.costs import kv_bytes_per_token


def attention_params(arch: dict) -> int:
    D, H, K, hd = (arch["hidden_size"], arch["num_attention_heads"],
                   arch["num_key_value_heads"], arch["head_dim"])
    return arch["num_hidden_layers"] * (D * H * hd + 2 * D * K * hd + H * hd * D)


def expert_params(arch: dict) -> int:
    return (arch["num_hidden_layers"] * arch["num_experts"]
            * 3 * arch["hidden_size"] * arch["intermediate_size"])


def head_params(arch: dict) -> int:
    return arch["vocab_size"] * arch["hidden_size"]


def param_count(arch: dict) -> int:
    """Every parameter of the model (what its card calls its size): the
    matrices above plus the embedding, the routers and the norms. Not what a
    step reads; `benchmark/tests/test_moe.py` holds it to the published 6.9 B."""
    D, L = arch["hidden_size"], arch["num_hidden_layers"]
    kv = arch["num_key_value_heads"] * arch["head_dim"]
    q = arch["num_attention_heads"] * arch["head_dim"]
    norms = L * (2 * D + q + kv) + D
    return (attention_params(arch) + expert_params(arch) + head_params(arch)
            + arch["vocab_size"] * D + L * D * arch["num_experts"] + norms)


def weight_bytes(arch: dict, bytes_per_weight: float,
                 active_share: float = 1.0) -> float:
    """Matrix bytes one decode step has to read; `active_share` in [0, 1] is
    the share of (layer, expert) pairs some row chose."""
    return bytes_per_weight * (attention_params(arch) + head_params(arch)
                               + expert_params(arch) * active_share)


def decode_step_bytes(arch: dict, live_tokens: float, bytes_per_weight: float,
                      bytes_per_kv: float, active_share: float = 1.0) -> float:
    return (weight_bytes(arch, bytes_per_weight, active_share)
            + live_tokens * kv_bytes_per_token(arch, bytes_per_kv))
