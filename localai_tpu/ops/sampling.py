"""Batched, per-slot parameterized token sampling.

The reference's sampler lives inside llama.cpp (params parsed at
backend/cpp/llama-cpp/grpc-server.cpp:118 parse_options: temperature, top_k,
top_p, min_p, repeat/presence/frequency penalties, seed, logit bias). Here the
whole chain is one jitted function over the decode batch: every slot carries
its own sampling parameters as array entries, so one compiled program serves
heterogeneous requests (no recompile per request — that is the continuous-
batching contract).

Grammar-constrained decoding plugs in through `logit_bias`: the engine writes
-inf outside the grammar-allowed token set (reference equivalent: GBNF
sampling inside llama.cpp, pkg/functions grammar generation).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from localai_tpu.observe.scopes import scope

NEG_INF = -1e30


class SamplingParams(NamedTuple):
    """Per-slot sampling parameters; every field has shape [B]."""

    temperature: jnp.ndarray  # f32; <= 0 means greedy
    top_k: jnp.ndarray  # i32; 0 disables
    top_p: jnp.ndarray  # f32; >= 1 disables
    min_p: jnp.ndarray  # f32; 0 disables
    repeat_penalty: jnp.ndarray  # f32; 1.0 disables (llama.cpp semantics)
    presence_penalty: jnp.ndarray  # f32; 0 disables
    frequency_penalty: jnp.ndarray  # f32; 0 disables

    @staticmethod
    def make(
        batch: int,
        temperature=0.0,
        top_k=0,
        top_p=1.0,
        min_p=0.0,
        repeat_penalty=1.0,
        presence_penalty=0.0,
        frequency_penalty=0.0,
    ) -> "SamplingParams":
        full = lambda v, dt: jnp.full((batch,), v, dtype=dt)
        return SamplingParams(
            temperature=full(temperature, jnp.float32),
            top_k=full(top_k, jnp.int32),
            top_p=full(top_p, jnp.float32),
            min_p=full(min_p, jnp.float32),
            repeat_penalty=full(repeat_penalty, jnp.float32),
            presence_penalty=full(presence_penalty, jnp.float32),
            frequency_penalty=full(frequency_penalty, jnp.float32),
        )


@scope("sample")
def apply_penalties(
    logits: jnp.ndarray,  # [B, V] f32
    counts: jnp.ndarray,  # [B, V] i32 — occurrences of each token so far (prompt+generated)
    params: SamplingParams,
) -> jnp.ndarray:
    seen = counts > 0
    rp = params.repeat_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    logits = jnp.where(seen, penalized, logits)
    logits = logits - params.presence_penalty[:, None] * seen.astype(jnp.float32)
    logits = logits - params.frequency_penalty[:, None] * counts.astype(jnp.float32)
    return logits


def _filter_sorted(sorted_logits: jnp.ndarray, params: SamplingParams) -> jnp.ndarray:
    """Apply top-k, then top-p, then min-p on descending-sorted logits [B, K].

    Chain semantics match llama.cpp: each stage renormalizes over the
    candidate set left by the previous stage (top-p mass is measured over the
    post-top-k distribution, min-p against the surviving max-probability).
    K may be a partial candidate set (see `sample`); top_k larger than K is
    clamped to K.
    """
    B, V = sorted_logits.shape
    ranks = jnp.arange(V)[None, :]

    k = jnp.where(params.top_k <= 0, V, jnp.minimum(params.top_k, V))[:, None]
    keep = ranks < k

    # Renormalized softmax over the top-k survivors (masked-out rows get 0).
    probs = jax.nn.softmax(jnp.where(keep, sorted_logits, NEG_INF), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens until the cumulative mass *before* this token reaches top_p
    # (always keeps the first token).
    keep_p = (cum - probs) < params.top_p[:, None]
    keep = jnp.logical_and(keep, keep_p)

    # min-p over the post-top-p survivors, renormalized.
    probs = jax.nn.softmax(jnp.where(keep, sorted_logits, NEG_INF), axis=-1)
    keep_mp = probs >= params.min_p[:, None] * probs[:, :1]
    keep = jnp.logical_and(keep, keep_mp)

    keep = keep.at[:, 0].set(True)  # never mask everything
    return jnp.where(keep, sorted_logits, NEG_INF)


@scope("sample")
def sample(
    logits: jnp.ndarray,  # [B, V] any float dtype
    rng: jnp.ndarray,  # [B] batch of PRNG keys (jax.random.key dtype)
    params: SamplingParams,
    counts: jnp.ndarray | None = None,  # [B, V] i32
    logit_bias: jnp.ndarray | None = None,  # [B, V] f32 (grammar masks, user bias)
    num_candidates: int = 64,
) -> jnp.ndarray:
    """Sample one token per slot. Returns [B] int32.

    TPU note: a full-vocab sort is a multi-ms operation at V=128k, so the
    filter chain runs over a partial top-`num_candidates` candidate set
    (exact when V <= num_candidates, e.g. every test arch). Consequences on
    a real vocab: `top_k` is clamped to num_candidates (llama.cpp default is
    40), and top-p mass is measured over the renormalized top-candidate head
    — the tail mass beyond 64 candidates is negligible for any top_p < 1.
    Slots with no filters active sample the exact full distribution via
    `jax.random.categorical` (Gumbel argmax — no sort at all).
    """
    logits = logits.astype(jnp.float32)
    if counts is not None:
        logits = apply_penalties(logits, counts, params)
    if logit_bias is not None:
        logits = logits + logit_bias

    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(params.temperature, 1e-6)[:, None]

    # llama.cpp chain order: top-k/top-p/min-p filter on unscaled logits,
    # temperature last — so the kept support is temperature-independent.
    K = min(num_candidates, logits.shape[-1])
    sorted_logits, sorted_idx = jax.lax.top_k(logits, K)
    filtered = _filter_sorted(sorted_logits, params)
    filtered = jnp.where(filtered <= NEG_INF, NEG_INF, filtered / temp)

    def draw(key, row):
        return jax.random.categorical(key, row)

    pos = jax.vmap(draw)(rng, filtered)
    cand_tok = jnp.take_along_axis(sorted_idx, pos[:, None], axis=-1)[:, 0].astype(jnp.int32)

    # Exact full-distribution draw for unfiltered slots.
    free_tok = jax.vmap(draw)(rng, logits / temp).astype(jnp.int32)

    needs_filter = (params.top_k > 0) | (params.top_p < 1.0) | (params.min_p > 0.0)
    sampled_tok = jnp.where(needs_filter, cand_tok, free_tok)
    return jnp.where(params.temperature <= 0.0, greedy_tok, sampled_tok)


@scope("sample")
def sample_simple(
    logits: jnp.ndarray,  # [B, V]
    rng: jnp.ndarray,  # [B] PRNG keys
    params: SamplingParams,
    counts: jnp.ndarray | None = None,
    logit_bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Greedy + exact unfiltered categorical only — no top-k/top-p/min-p.

    The engine dispatches this variant when no active slot has filters
    enabled; it avoids the partial-sort entirely (one Gumbel argmax pass).
    """
    logits = logits.astype(jnp.float32)
    if counts is not None:
        logits = apply_penalties(logits, counts, params)
    if logit_bias is not None:
        logits = logits + logit_bias
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    free_tok = jax.vmap(jax.random.categorical)(rng, logits / temp).astype(jnp.int32)
    return jnp.where(params.temperature <= 0.0, greedy_tok, free_tok)


@scope("sample")
def sample_greedy(
    logits: jnp.ndarray,  # [B, V]
    params: SamplingParams,
    counts: jnp.ndarray | None = None,
    logit_bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Pure argmax (with penalties/bias) — the cheapest per-step sampler."""
    logits = logits.astype(jnp.float32)
    if counts is not None:
        logits = apply_penalties(logits, counts, params)
    if logit_bias is not None:
        logits = logits + logit_bias
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@scope("sample")
def update_counts(counts: jnp.ndarray, tokens: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """counts[b, tokens[b]] += 1 for active slots. All shapes static."""
    B = counts.shape[0]
    inc = active.astype(counts.dtype)
    return counts.at[jnp.arange(B), tokens].add(inc)


@scope("sample")
def deterministic_accept(
    pl: jnp.ndarray,  # [B, V] target processed log-probs (processed_logprobs)
    x: jnp.ndarray,  # [B] int32 draft token under test
):
    """Speculative accept inputs for a DETERMINISTIC draft source (prompt
    lookup, ISSUE 12): the proposal distribution q is a point mass at x, so
    the canonical test accept-w.p.-min(1, p(x)/q(x)) reduces to p(x), and
    the rejection draw normalize(max(p - q, 0)) reduces to p with x zeroed,
    renormalized. Returns (log_ratio [B] = log p(x), residual_logprobs
    [B, V]); greedy (one-hot p) degenerates to exact argmax agreement —
    reject unless x IS the argmax, then resample lands on the argmax.
    """
    B, V = pl.shape
    idx = jnp.arange(B)
    log_ratio = pl[idx, x]
    res = jnp.where(jnp.arange(V)[None, :] == x[:, None], 0.0, jnp.exp(pl))
    mass = res.sum(axis=-1, keepdims=True)
    res_log = jnp.where(
        mass > 1e-9,
        jnp.log(res / jnp.maximum(mass, 1e-9) + 1e-38),
        pl,  # residual mass ~0: the draft matched p's entire support
    )
    return log_ratio, res_log


@scope("sample")
def processed_logprobs(
    logits: jnp.ndarray,  # [B, V] any float dtype
    params: SamplingParams,
    counts: jnp.ndarray | None = None,  # [B, V] i32
    logit_bias: jnp.ndarray | None = None,  # [B, V] f32
    num_candidates: int = 64,
) -> jnp.ndarray:
    """Full post-chain sampling distribution as log-probs [B, V] f32.

    Exactly the distribution `sample` draws from — penalties, bias, the
    top-k/top-p/min-p chain over the partial candidate set, temperature, and
    the temperature==0 greedy degenerate (one-hot). Speculative decoding's
    stochastic verify (accept w.p. min(1, p/q), resample from max(p-q, 0))
    needs the *distributions* of both models, and using one shared
    implementation for p and q is what makes the acceptance test exact.
    """
    logits = logits.astype(jnp.float32)
    if counts is not None:
        logits = apply_penalties(logits, counts, params)
    if logit_bias is not None:
        logits = logits + logit_bias
    B, V = logits.shape

    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    has_filter = (
        (params.top_k > 0) | (params.top_p < 1.0) | (params.min_p > 0.0)
    )[:, None]

    K = min(num_candidates, V)
    sorted_logits, sorted_idx = jax.lax.top_k(logits, K)
    filtered = _filter_sorted(sorted_logits, params)
    scattered = jnp.full((B, V), NEG_INF, jnp.float32)
    scattered = scattered.at[jnp.arange(B)[:, None], sorted_idx].set(filtered)

    eff = jnp.where(has_filter, scattered, logits) / temp
    # temperature == 0 → degenerate one-hot on the argmax (greedy)
    greedy_tok = jnp.argmax(logits, axis=-1)
    onehot = jnp.where(
        jnp.arange(V)[None, :] == greedy_tok[:, None], 0.0, NEG_INF
    )
    eff = jnp.where((params.temperature <= 0.0)[:, None], onehot, eff)
    return jax.nn.log_softmax(eff, axis=-1)
