"""Plain reference: a decoder of full-attention and sliding-window GQA layers
with head counts that differ by kind, over sigmoid-routed experts beside a
shared one (Laguna-XS.2, `poolside/Laguna-XS.2`, `laguna`) in straightforward
`jax.numpy`, float32, matmul precision "highest". No cache, no ring, no
kernel, no sort, and none of the program's forward code. Pre-norm residual
blocks, x [T, D], K KV heads of d lanes in every layer:

Attention layer l, H_l query heads (one count a kind), position t:

    a = rms_norm(x)    q = a Wq_l [H_l, d]    k = a Wk, v = a Wv [K, d]
    full layer:   the first `rot` lanes of each head of q and k rotated in
                  half-split pairs (i with i + rot/2) by YaRN's frequencies
                  over `rot` dims, cos and sin times the attention factor;
                  the other lanes pass through
    window layer: all d lanes rotated at the local base, unscaled
    s = q . k / sqrt(d) over all d lanes, causal; a window layer attends
    position j from i iff 0 <= i - j < window; softmax in float32
    g = sigmoid(a Wg_l) [H_l]: head h's output times g_h
    x = x + concat_h(g_h sum p v) Wo_l

YaRN over dim = rot: extrap_i = theta^(-2i/rot), interp_i = extrap_i / factor,
corr(b) = rot ln(orig / (2 pi b)) / (2 ln theta), low = floor(corr(beta_fast)),
high = ceil(corr(beta_slow)) (clipped to [0, rot - 1]), ramp_i = clip((i - low)
/ (high - low), 0, 1), inv_i = interp_i ramp_i + extrap_i (1 - ramp_i)
(`yarn_inv`; HF `_compute_yarn_parameters`).

MLP: the first `first_k_dense` layers a dense SwiGLU; the others the
Kimi-Linear reference's expert layer (`kda_mla_moe.experts`, `dense_mlp`,
`head`: the same router family: s = sigmoid(m W_r) over ALL E experts, the
top k of s + b, w_e = scaling s_e / (sum of the picks' s + 1e-20), the
weight on the expert's OUTPUT, plus the shared expert). "Held here" is the
deployment's share (`cfg.expert_share`): nothing stands in for the other
experts, and the weights are normalised over all k picks.

`window=0` takes the window OUT (every layer attends the whole prefix): the
control that a reader which forgets the window, or a ring that keeps a stale
row, cannot pass. `kv_round="fp8"` rounds what the caches hold (k rotated,
and v, of both kinds of layer) to an 8-bit float (4 exponent, 3 mantissa
bits; `lax.reduce_precision`, which the TPU compiler cannot drop as it drops
a cast pair).

It reads the served model's parameter arrays as DATA (`gqa_layers` and
`swa_layers` the two attention stacks, `layers` and `dense_layers` the norms
and MLPs, `[in, out]` matrices, int8 as {"q", "s"}), one layer at a time,
and scores a block of query rows at a time, so the check fits at 2,017
tokens beside the served model.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import F32, _mm, _rms_norm, _weight
from benchmark.reference.kda_mla_moe import dense_mlp, experts, head
from benchmark.reference.moe_qknorm import _at

Q_BLOCK = 256  # query rows scored at once: [H, 256, T] float32


def _lin(x, w, dt, weight_round=""):
    return _mm(x, _weight(w, weight_round), dt).astype(dt)


def _round_fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def yarn_inv(rot: int, theta: float, factor: float, orig: int,
             beta_fast: float, beta_slow: float) -> np.ndarray:
    """[rot / 2] float64: YaRN's inverse frequencies over `rot` dims."""
    i = np.arange(rot // 2, dtype=np.float64)
    extrap = theta ** (-2.0 * i / rot)
    interp = extrap / factor

    def corr(b):
        return rot * math.log(orig / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), rot - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp)


def rotate(x, pos, inv, amp: float = 1.0):
    """x [T, heads, d]: the leading 2 len(inv) lanes of each head rotated in
    half-split pairs at `pos`, cos and sin times `amp`; the rest passed."""
    rot = 2 * inv.shape[0]
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = amp * jnp.cos(ang)[:, None, :], amp * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "inv", "amp", "window", "eps", "compute",
    "weight_round", "kv_round"))
def attention(h, lw, *, heads, kv_heads, inv, amp, window, eps,
              compute="float32", weight_round="", kv_round=""):
    """x + gated attention(x) of one layer over the whole sequence. h: [T, D],
    T a multiple of Q_BLOCK or less than it; `inv` the layer's frequencies as
    a tuple; window 0 = the whole prefix."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    pos = jnp.arange(T)
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    q = _lin(a, lw["wq"], dt, weight_round)
    d = q.shape[-1] // heads
    q = q.reshape(T, heads, d)
    k = _lin(a, lw["wk"], dt, weight_round).reshape(T, kv_heads, d)
    v = _lin(a, lw["wv"], dt, weight_round).reshape(T, kv_heads, d)
    inv = np.asarray(inv, np.float64)
    q = rotate(q.astype(F32), pos, inv, amp).astype(dt)
    k = rotate(k.astype(F32), pos, inv, amp).astype(dt)
    if kv_round == "fp8":
        k, v = _round_fp8(k), _round_fp8(v)
    elif kv_round:
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    g = heads // kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    bq = min(Q_BLOCK, T)

    def block(i):  # query rows i·bq .. i·bq + bq - 1 against every key
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 0)
        at = i * bq + jnp.arange(bq)
        s = jnp.einsum("qhd,khd->hqk", qb, k, preferred_element_type=F32)
        s = s / jnp.sqrt(F32(d))
        dist = at[:, None] - pos[None, :]
        ok = dist >= 0
        if window:
            ok = ok & (dist < window)
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=F32)

    o = jax.lax.map(block, jnp.arange(T // bq)).reshape(T, heads, d)
    gate = jax.nn.sigmoid(_mm(a, lw["wg_head"], dt))  # [T, H], never rounded
    o = (o * gate[:, :, None]).reshape(T, heads * d).astype(dt)
    y = _mm(o, _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


_ATTN = ("wq", "wk", "wv", "wo", "wg_head")
_DENSE = ("mlp_norm", "w_gate", "w_up", "w_down")
_MOE = ("mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down")


def arch_of(cfg) -> dict:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    share = get("expert_share")
    E = int(get("num_experts"))
    d = int(get("head_dim"))
    rot = int(d * float(get("partial_rotary")))
    m = get("rope_attn_factor")
    factor = float(get("rope_scaling_factor"))
    full_inv = yarn_inv(
        rot, float(get("rope_theta")), factor,
        int(get("rope_original_max_position")), float(get("rope_beta_fast")),
        float(get("rope_beta_slow")))
    local = float(get("rope_local_theta"))
    return {
        "kinds": tuple(get("layer_kinds")), "eps": float(get("rms_eps")),
        "heads": {"gqa": int(get("num_heads")), "swa": int(get("swa_heads"))},
        "kv_heads": int(get("num_kv_heads")),
        "inv": {"gqa": tuple(full_inv.tolist()),
                "swa": tuple((local ** (-2.0 * np.arange(d // 2) / d)).tolist())},
        "amp": {"gqa": float(m) if m is not None else 0.1 * math.log(factor) + 1,
                "swa": 1.0},
        "window": int(get("sliding_window")),
        "dense": int(get("first_k_dense")),
        "top_k": int(get("num_experts_per_token")),
        "scaling": float(get("routed_scaling_factor")),
        "lo": 0 if share is None else int(share[0]) * (E // int(share[1])),
    }


def forward(params, cfg, ids, rows, *, compute="float32", weight_round="",
            kv_round="", pad_to: int = 128, hidden_after=None,
            window=None) -> np.ndarray:
    """Teacher-forced full forward over `ids`; log-probabilities
    [len(rows), V] at the positions in `rows`. Right-padded to a multiple of
    `pad_to` (and of Q_BLOCK past it; causal attention: padding cannot reach
    an earlier position). `hidden_after` as in `dense_gqa.forward`; `window`
    overrides the configuration's (0: none, the control)."""
    a = arch_of(cfg)
    W = a["window"] if window is None else int(window)
    T = -(-len(ids) // pad_to) * pad_to
    if T > Q_BLOCK:
        T = -(-T // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((T,), np.int32)
    toks[: len(ids)] = ids
    dt = jnp.dtype(compute)
    kw = dict(eps=a["eps"], compute=compute, weight_round=weight_round)
    seen = {"gqa": 0, "swa": 0}
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        for li, kind in enumerate(a["kinds"]):
            stack = "dense_layers" if li < a["dense"] else "layers"
            at = li if li < a["dense"] else li - a["dense"]
            lw = {k: _at(params[f"{kind}_layers"][k], seen[kind]) for k in _ATTN}
            lw["attn_norm"] = _at(params[stack]["attn_norm"], at)
            seen[kind] += 1
            h = attention(h, lw, heads=a["heads"][kind], kv_heads=a["kv_heads"],
                          inv=a["inv"][kind], amp=a["amp"][kind],
                          window=W if kind == "swa" else 0, kv_round=kv_round,
                          **kw)
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
