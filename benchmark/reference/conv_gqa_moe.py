"""Plain reference: the LFM2-MoE decoder (HF `LiquidAI/LFM2-8B-A1B`,
`model_type: lfm2_moe`) in straightforward `jax.numpy`, float32, matmul
precision "highest". No cache, no conv-state array, no kernel, no sort, and
none of the program's forward code. Pre-norm residual blocks, x [T, D];
every layer is `x += op(norm(x)); x += ff(norm(x))`:

Gated short convolution (the layers `cfg.layer_kinds` calls "conv"), with
a = rms_norm(x) and W_in [D, 3D] split in three D-wide parts in that order:

    [b | c | z] = a W_in                                   no bias
    u_t = b_t * z_t
    v_t = sum_{i=0..L-1} w_i * u_{t-L+1+i}                 depthwise, causal,
                                 L = conv_L_cache taps w [L, D], u = 0 before
                                 the sequence's start, tap L-1 on the current
                                 token; no activation anywhere in the operator
    x = x + (c_t * v_t) W_out

Attention ("gqa"), H query heads over K key/value heads of width hd:

    q = a Wq [T, H, hd];  k = a Wk, v = a Wv [T, K, hd]    no biases
    q = rms_norm_head(q),  k = rms_norm_head(k)            weights [hd]
    q, k = rope(q), rope(k)             all hd dims, half-split, theta 1e6
    o_h = causal softmax(q_h k_{h // (H/K)} / sqrt(hd)) v_{h // (H/K)}
    x = x + o Wo

Feed-forward: the first `first_k_dense` layers a dense SwiGLU; the others
s = sigmoid(m Wr) over ALL E experts in float32, the top k of s + bias
picked, g = s[picked] / (sum(s[picked]) + 1e-6) * routed_scaling_factor, and

    x = x + sum over picked e of g_e E_e(m)                no shared expert

The experts run as a plain loop, one dequantised at a time, so the float32
copy of one expert is all that stands beside the served model. After the last
layer the published `embedding_norm` and the TIED head: logits = h E^T with
the embedding E itself (bfloat16 as held; `weight_round` leaves it alone, it
is no int8 leaf).

Assumed, because the catalog's keys do not say (each also in the
configuration file's `assumed`): the embedding is tied; `head_dim` =
hidden_size / num_attention_heads; the renormalisation adds 1e-6; the split
order b, c, z; the conv's state is its last L-1 inputs u.

`kv_round="fp8"` rounds what the caches hold one step below what the
configuration states: the K/V rows AND the conv's held inputs (the u of the
L-1 tokens before the current one; the current token's u never passes through
a row) to an 8-bit float (4 exponent bits, 3 mantissa bits), both by
`lax.reduce_precision` and not by a cast pair, which the TPU compiler is free
to drop (PERF.md section 6, PRs 31 and 34).

It reads the served model's parameter arrays as DATA (stacks over layers,
`[in, out]` matrices, int8 as {"q", "s"}); the helpers shared with the other
references (norm, rope, matmul in a compute type, weight rounding, the dense
SwiGLU, the blocked head) are those files'.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import F32, _mm, _rms_norm, _rope, _weight
from benchmark.reference.kda_mla_moe import _lin, _swiglu, dense_mlp, head
from benchmark.reference.moe_qknorm import _at


def _fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


@functools.partial(jax.jit, static_argnames=(
    "eps", "compute", "weight_round", "kv_round"))
def conv_operator(h, lw, *, eps, compute="float32", weight_round="",
                  kv_round=""):
    """x + gated short convolution(x) of one layer over the whole sequence."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    b, c, z = jnp.split(_lin(a, lw["w_in"], dt, weight_round), 3, axis=-1)
    u = (b.astype(F32) * z.astype(F32)).astype(dt).astype(F32)
    if kv_round == "fp8":
        held = _fp8(u)  # what a row one precision lower would hand back
    elif kv_round:
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    else:
        held = u
    w = lw["conv_w"].astype(F32)  # [L, D], tap L-1 on the current token
    L = w.shape[0]
    past = jnp.concatenate([jnp.zeros((L - 1, u.shape[1]), F32), held], 0)
    v = u * w[L - 1] + sum(past[i:i + T] * w[i] for i in range(L - 1))
    y = (c.astype(F32) * v).astype(dt)
    return (h.astype(F32) + _mm(y, _weight(lw["wo"], weight_round), dt)
            ).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "compute", "weight_round",
    "kv_round"))
def attention(h, lw, *, heads, kv_heads, theta, eps, compute="float32",
              weight_round="", kv_round=""):
    """x + GQA(x) of one layer: per-head q/k norms, then the rotation."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    pos = jnp.arange(T)
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    q = _lin(a, lw["wq"], dt, weight_round).reshape(T, heads, -1)
    k = _lin(a, lw["wk"], dt, weight_round).reshape(T, kv_heads, -1)
    v = _lin(a, lw["wv"], dt, weight_round).reshape(T, kv_heads, -1)
    q = _rms_norm(q, lw["q_norm"], eps).astype(dt)
    k = _rms_norm(k, lw["k_norm"], eps).astype(dt)
    q = _rope(q.astype(F32), pos, theta).astype(dt)
    k = _rope(k.astype(F32), pos, theta).astype(dt)
    if kv_round == "fp8":  # rows held with 4 exponent and 3 mantissa bits
        k, v = _fp8(k), _fp8(v)
    elif kv_round:
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=F32)
    s = s / jnp.sqrt(F32(q.shape[-1]))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=F32)
    y = _mm(o.reshape(T, -1).astype(dt), _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


def route(m, router, bias, *, top_k, scaling, norm_eps=1e-6):
    """(g [T, k] float32, e [T, k]): sigmoid scores over all experts, the
    top k of score + bias, their scores renormalised with `norm_eps`."""
    s = jax.nn.sigmoid(_mm(m.astype(F32), router.astype(F32), F32))
    _, e = jax.lax.top_k(s + bias.astype(F32), top_k)
    g = jnp.take_along_axis(s, e, axis=-1)
    return g / (jnp.sum(g, -1, keepdims=True) + norm_eps) * scaling, e


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "eps", "compute", "weight_round"))
def experts(h, lw, *, top_k, scaling, eps, compute="float32",
            weight_round=""):
    """x + the routed sum. lw's expert leaves are [E, ...]."""
    dt = jnp.dtype(compute)
    m = _rms_norm(h, lw["mlp_norm"], eps).astype(dt)
    g, e = route(m, lw["router"], lw["router_bias"], top_k=top_k,
                 scaling=scaling)
    E = lw["router"].shape[-1]

    def one(i, acc):
        mine = jnp.sum(g * (e == i), axis=-1)  # [T]: 0 where not picked
        y = _swiglu(m, _at(lw["w_gate"], i), _at(lw["w_up"], i),
                    _at(lw["w_down"], i), dt, weight_round)
        return acc + mine[:, None] * y

    out = jax.lax.fori_loop(0, E, one, jnp.zeros(h.shape, F32))
    return (h.astype(F32) + out).astype(dt)


_CONV = ("w_in", "conv_w", "wo")
_GQA = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_DENSE = ("mlp_norm", "w_gate", "w_up", "w_down")
_MOE = ("mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down")


def arch_of(cfg) -> dict:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    return {
        "kinds": tuple(get("layer_kinds")), "eps": float(get("rms_eps")),
        "heads": int(get("num_heads")), "kv_heads": int(get("num_kv_heads")),
        "theta": float(get("rope_theta")), "dense": int(get("first_k_dense")),
        "top_k": int(get("num_experts_per_token")),
        "scaling": float(get("routed_scaling_factor")),
    }


def forward(params, cfg, ids, rows, *, compute="float32", weight_round="",
            kv_round="", pad_to: int = 128, hidden_after=None) -> np.ndarray:
    """Teacher-forced full forward over `ids`; log-probabilities
    [len(rows), V] at the positions in `rows`. Right-padded to a multiple of
    `pad_to` (causal attention and a causal convolution: padding cannot
    reach an earlier position). `hidden_after` as in `dense_gqa.forward`."""
    a = arch_of(cfg)
    T = -(-len(ids) // pad_to) * pad_to
    toks = np.zeros((T,), np.int32)
    toks[: len(ids)] = ids
    dt = jnp.dtype(compute)
    kw = dict(eps=a["eps"], compute=compute, weight_round=weight_round)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        nc = ng = 0
        for li, kind in enumerate(a["kinds"]):
            stack = "dense_layers" if li < a["dense"] else "layers"
            at = li if li < a["dense"] else li - a["dense"]
            norm = {"attn_norm": _at(params[stack]["attn_norm"], at)}
            if kind == "conv":
                lw = {k: _at(params["conv_layers"][k], nc) for k in _CONV}
                h = conv_operator(h, {**lw, **norm}, kv_round=kv_round, **kw)
                nc += 1
            else:
                lw = {k: _at(params["gqa_layers"][k], ng) for k in _GQA}
                h = attention(h, {**lw, **norm}, heads=a["heads"],
                              kv_heads=a["kv_heads"], theta=a["theta"],
                              kv_round=kv_round, **kw)
                ng += 1
            if li < a["dense"]:
                h = dense_mlp(h, {k: _at(params[stack][k], at) for k in _DENSE},
                              **kw)
            else:
                h = experts(h, {k: _at(params[stack][k], at) for k in _MOE},
                            top_k=a["top_k"], scaling=a["scaling"], **kw)
            if hidden_after is not None:
                hidden_after.append(np.asarray(h[jnp.asarray(rows)].astype(F32)))
        V = params["embed"].shape[0]
        blocks = next(b for b in (16, 8, 4, 2, 1) if V % b == 0 and V // b >= 64)
        out = head(h[jnp.asarray(rows)], params["final_norm"], params["embed"],
                   blocks=blocks, eps=a["eps"], compute=compute)
        return np.asarray(out)
