"""Plain reference: a decoder of rotated latent attention (MLA) in every
layer over sigmoid-routed experts beside a shared one (GLM-4.7-Flash,
`zai-org/GLM-4.7-Flash`, `glm4_moe_lite`; the DeepSeek-V3 block) in
straightforward `jax.numpy`, float32, matmul precision "highest". No cache,
no latent row array, no absorbed weights, no kernel, no sort, and none of the
program's forward code. Pre-norm residual blocks, x [T, D], H heads:

MLA layer (every layer), position t:

    c_q = rms_norm(a W_qa)                 a = rms_norm(x); the q bottleneck
    q_h = (c_q W_qb)_h = [q_n (n) | q_r (rot)]        (no bottleneck: a W_q)
    [c_kv (r) | k_r (rot)] = a W_kva       c = rms_norm(c_kv)
    k_h = [W_kb,h c | RoPE_t(k_r)]         v_h = W_vb,h c     (explicit, per head)
    s = ([q_n | RoPE_t(q_r)] . k_h) / sqrt(n + rot), causal softmax in float32
    x = x + concat_h(sum p v_h) W_o

RoPE rotates ALL rot dims, half-split pairs (i, i + rot/2), base theta, no
scaling; k_r is one row shared by the heads. `rope=False` is the NoPE form.

MLP: the first `first_k_dense` layers a dense SwiGLU; the others
s = sigmoid(m W_r) over ALL E experts, the top k of s + b picked,
w_e = scaling s_e / (sum of the picks' s + 1e-20), and

    x = x + sum over picked e HELD HERE of w_e E_e(m)  +  E_shared(m)

which is the Kimi-Linear reference's expert layer (`kda_mla_moe.experts`,
`dense_mlp`, `head`: the same router family, shared here as the program
shares `llama._deepseek_route`). "Held here" is the deployment's share
(`cfg.expert_share` = (index, of), None = all): nothing stands in for the
other experts, and the weights are normalised over all k picks.

`kv_round="fp8"` rounds what the cache holds one step below what the
configuration states: a token's latent row [c | RoPE(k_r)] to an 8-bit float
(4 exponent bits, 3 mantissa bits; `lax.reduce_precision`, which the TPU
compiler cannot drop as it drops a cast pair).

It reads the served model's parameter arrays as DATA (`layers` and
`dense_layers` stacks, `[in, out]` matrices, int8 as {"q", "s"}), one layer
at a time, so the check fits at 2,017 tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import F32, _mm, _rms_norm, _rope, _weight
from benchmark.reference.kda_mla_moe import dense_mlp, experts, head
from benchmark.reference.moe_qknorm import _at


def _lin(x, w, dt, weight_round=""):
    return _mm(x, _weight(w, weight_round), dt).astype(dt)


def _round_fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rot", "theta", "rope", "eps", "compute",
    "weight_round", "kv_round"))
def mla_attention(h, lw, *, heads, rank, nope, rot, theta, rope=True, eps,
                  compute="float32", weight_round="", kv_round=""):
    """x + MLA(x) of one layer over the whole sequence, with explicit
    per-head keys and values. h: [T, D]."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    pos = jnp.arange(T)
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    if "wq_a" in lw:
        c_q = _rms_norm(_lin(a, lw["wq_a"], dt, weight_round),
                        lw["q_norm_a"], eps).astype(dt)
        q = _lin(c_q, lw["wq_b"], dt, weight_round)
    else:
        q = _lin(a, lw["wq"], dt, weight_round)
    q = q.reshape(T, heads, nope + rot)
    ckv = _lin(a, lw["wkv_a"], dt, weight_round)
    c = _rms_norm(ckv[:, :rank], lw["kv_norm"], eps).astype(dt)
    q_r, k_r = q[..., nope:], ckv[:, None, rank:rank + rot]
    if rope:
        q_r = _rope(q_r.astype(F32), pos, theta).astype(dt)
        k_r = _rope(k_r.astype(F32), pos, theta).astype(dt)
    if kv_round == "fp8":
        c, k_r = _round_fp8(c), _round_fp8(k_r)
    elif kv_round:
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    k_n = jnp.einsum("tr,hnr->thn", c, lw["w_kb"].astype(dt),
                     preferred_element_type=F32).astype(dt)
    v = jnp.einsum("tr,hvr->thv", c, lw["w_vb"].astype(dt),
                   preferred_element_type=F32).astype(dt)
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (T, heads, rot))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=F32)
    s = s / jnp.sqrt(F32(nope + rot))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=F32)
    y = _mm(o.reshape(T, -1).astype(dt), _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


_MLA = ("attn_norm", "wq_a", "q_norm_a", "wq_b", "wq", "wkv_a", "kv_norm",
        "w_kb", "w_vb", "wo")
_DENSE = ("mlp_norm", "w_gate", "w_up", "w_down")
_MOE = ("mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down")


def arch_of(cfg) -> dict:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    share = get("expert_share")
    E = int(get("num_experts"))
    return {
        "layers": int(get("num_layers")), "eps": float(get("rms_eps")),
        "heads": int(get("num_heads")), "rank": int(get("kv_lora_rank")),
        "nope": int(get("qk_nope_head_dim")),
        "rot": int(get("qk_rope_head_dim")),
        "theta": float(get("rope_theta")), "rope": bool(get("mla_rope")),
        "dense": int(get("first_k_dense")),
        "top_k": int(get("num_experts_per_token")),
        "scaling": float(get("routed_scaling_factor")),
        "lo": 0 if share is None else int(share[0]) * (E // int(share[1])),
    }


def forward(params, cfg, ids, rows, *, compute="float32", weight_round="",
            kv_round="", pad_to: int = 128, hidden_after=None) -> np.ndarray:
    """Teacher-forced full forward over `ids`; log-probabilities
    [len(rows), V] at the positions in `rows`. Right-padded to a multiple of
    `pad_to` (causal attention: padding cannot reach an earlier position).
    `hidden_after` as in `dense_gqa.forward`."""
    a = arch_of(cfg)
    T = -(-len(ids) // pad_to) * pad_to
    toks = np.zeros((T,), np.int32)
    toks[: len(ids)] = ids
    dt = jnp.dtype(compute)
    kw = dict(eps=a["eps"], compute=compute, weight_round=weight_round)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        for li in range(a["layers"]):
            stack = "dense_layers" if li < a["dense"] else "layers"
            at = li if li < a["dense"] else li - a["dense"]
            lw = {k: _at(params[stack][k], at) for k in _MLA
                  if k in params[stack]}
            h = mla_attention(h, lw, heads=a["heads"], rank=a["rank"],
                              nope=a["nope"], rot=a["rot"], theta=a["theta"],
                              rope=a["rope"], kv_round=kv_round, **kw)
            if li < a["dense"]:
                h = dense_mlp(h, {k: _at(params[stack][k], at) for k in _DENSE},
                              **kw)
            else:
                h = experts(h, {k: _at(params[stack][k], at) for k in _MOE},
                            top_k=a["top_k"], scaling=a["scaling"], lo=a["lo"],
                            **kw)
            if hidden_after is not None:
                hidden_after.append(np.asarray(h[jnp.asarray(rows)].astype(F32)))
        V = (params["lm_head"]["q"] if isinstance(params["lm_head"], dict)
             else params["lm_head"]).shape[0]
        blocks = next(b for b in (16, 8, 4, 2, 1) if V % b == 0 and V // b >= 64)
        out = head(h[jnp.asarray(rows)], params["final_norm"], params["lm_head"],
                   blocks=blocks, **kw)
        return np.asarray(out)
