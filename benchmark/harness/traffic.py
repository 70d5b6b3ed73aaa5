"""One general, seeded traffic generator. No JAX: the load generator's child
process imports this file.

A traffic mix is a JSON file of parameters (`benchmark/traffic/<mix>.json`);
a later PR adds a mix by adding such a file, never code. The generator turns
(mix, clients, seed) into the ordered queue of requests that the clients of a
closed loop draw from, each with a prompt and an output length.

Every seed gets THE SAME multiset of prompt lengths and output lengths: they
are the mid-quantiles of the mix's distributions, and the seed only decides
their order, their pairing and the prompt text. So two seeds differ as two
days of the same traffic differ, and not in how much work they hold (the
run-to-run spread would otherwise be the seed's, not the system's).

What it takes today is what the listed cells use: a closed loop and uniform
lengths. An open loop, other distributions, bursts and shared prefixes come
with the cell that first needs and proves them (PERF.md, Open questions).
"""

from __future__ import annotations

import random

ALPHABET = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 ,.;"


def quantiles(dist: dict, n: int) -> list[int]:
    """n mid-quantiles ((i + 0.5) / n) of an integer length distribution."""
    if dist["dist"] != "uniform":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = int(dist["min"]), int(dist["max"])
    return [int(min(hi, max(lo, round(lo + (i + 0.5) / n * (hi - lo)))))
            for i in range(n)]


def _text(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(ALPHABET, k=n))


def _requests(mix: dict, n: int, rng: random.Random, overhead: int) -> list[dict]:
    """n requests: stratified lengths, shuffled and paired by the rng.
    `prompt_tokens` in a mix counts the user's content; the chat template's
    `overhead` tokens come on top (the engine sees content + overhead)."""
    plens = quantiles(mix["prompt_tokens"], n)
    olens = quantiles(mix["output_tokens"], n)
    rng.shuffle(plens)
    rng.shuffle(olens)
    return [{"prompt": _text(rng, max(1, p)),
             "prompt_tokens": max(1, p) + overhead,
             "max_tokens": o} for p, o in zip(plens, olens)]


def schedule(mix: dict, load: dict, seed: int, overhead: int = 0) -> dict:
    """The whole run's requests: an ordered queue that `load["clients"]`
    workers draw from, long enough never to run dry. Each stratum of the
    queue is the same multiset of lengths in a fresh order."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    rng = random.Random(seed)
    clients = int(load["clients"])
    reqs: list[dict] = []
    while len(reqs) < max(4 * clients, int(mix["queue"])):
        reqs += _requests(mix, int(mix["stratum"]), rng, overhead)
    return {"loop": "closed", "ramp_s": float(mix["ramp_s"]),
            "clients": clients, "requests": reqs}


def prompt_lengths(mix: dict, overhead: int = 0, n: int = 1024) -> list[int]:
    """The mix's prompt lengths as the engine sees them (n quantiles, the
    chat template's overhead included): what the warm-up has to cover, and
    in which shares."""
    return [p + overhead for p in quantiles(mix["prompt_tokens"], n)]
