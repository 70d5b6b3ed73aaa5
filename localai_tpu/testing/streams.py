"""Comparing one request's token stream between two engines whose arithmetic
is sharded differently (tp=1 against tp=2; ROADMAP D0, closed by PR 28).

Two reduction orders cannot promise the same tokens for ever. On the tiny
bf16 model the two engines' log-probabilities of one context differ by up to
2.8e-3 (measured, PR 28), and random weights put neighbouring candidates
closer than that: where two candidates sit 4e-4 apart the argmax flips, and
so does a top-k sample (`ops/sampling.sample` draws its noise by RANK, so a
swap of two ranks hands the winning draw to the other token). What sharded
arithmetic can promise, and `assert_same_until_near_tie` asserts:

- while the streams agree the context is the same, so every log-probability
  both engines report for a step agrees within `LOGPROB_TOL`. A wrong head
  shard, a dropped all-reduce, an adapter delta applied on one shard only or
  a pool row read from the wrong page moves them by 1e-1 and more (the
  adapter alone moves them by 0.14), at the first step it touches;
- the streams are identical up to the first step at which the two tokens
  were within `LOGPROB_TOL` of each other for the reference engine, a tie
  no reduction order decides. A different token anywhere else fails: a
  seed or rng fault, a sampler fed other parameters, a stale cache row.
"""

from __future__ import annotations

from localai_tpu.engine import GenRequest

# About twice the worst difference measured between tp=1 and tp=2 on one
# context (2.8e-3 with an adapter, 2.2e-3 without; tiny preset, bf16), a
# tenth of what the smallest real fault tried moves (see the docstring).
LOGPROB_TOL = 5e-3
TOP = 20  # Engine.LOGPROB_TOPK: as many candidates as an engine reports


def stream(eng, prompt, **kw) -> list[tuple[int, dict[int, float]]]:
    """[(token id, {candidate id: logprob})] of one request, step by step."""
    h = eng.submit(GenRequest(prompt_ids=list(prompt), ignore_eos=True,
                              logprobs=TOP, **kw))
    out = []
    for ev in h:
        assert ev.kind != "error", ev.error
        if ev.kind == "token":
            out.append((ev.token_id,
                        {ev.token_id: ev.logprob, **dict(ev.top_logprobs)}))
    return out


def assert_same_until_near_tie(want, got, tol: float = LOGPROB_TOL) -> int:
    """`want` (the reference engine's `stream`) against `got`, as the module
    docstring states it. Returns the number of steps that were identical."""
    assert len(want) == len(got)
    for t, ((tok_w, lp_w), (tok_g, lp_g)) in enumerate(zip(want, got)):
        both = lp_w.keys() & lp_g.keys()
        assert len(both) >= TOP // 2, (t, sorted(lp_w), sorted(lp_g))
        worst = max(abs(lp_w[k] - lp_g[k]) for k in both)
        assert worst <= tol, f"step {t}: logprobs differ by {worst}"
        if tok_w != tok_g:
            assert tok_g in lp_w and abs(lp_w[tok_w] - lp_w[tok_g]) <= tol, (
                f"step {t}: {tok_w} against {tok_g}, no near-tie: {lp_w}")
            return t
    return len(want)
