import json
import os

import pytest

from benchmark.harness import traffic as TR

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def strip(sched):
    return [(r["prompt"], r["max_tokens"]) for r in sched["requests"]]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule_other_seed_other_order(name):
    m, load = mix(name), {"clients": 40}
    a = TR.schedule(m, load, 2**31 + 7, overhead=51)
    b = TR.schedule(m, load, 2**31 + 7, overhead=51)
    c = TR.schedule(m, load, 12345, overhead=51)
    assert strip(a) == strip(b)
    assert strip(a) != strip(c)
    # the seed changes the order and the text, never the amount of work
    key = lambda s: sorted(r["max_tokens"] for r in s["requests"])  # noqa: E731
    assert key(a) == key(c)
    assert sorted(r["prompt_tokens"] for r in a["requests"]) == \
        sorted(r["prompt_tokens"] for r in c["requests"])


@pytest.mark.parametrize("name", MIXES)
def test_the_queue_never_runs_dry_and_every_stratum_holds_the_same_work(name):
    m = mix(name)
    s = TR.schedule(m, {"clients": 40}, 3, overhead=51)
    assert s["loop"] == "closed" and s["clients"] == 40
    assert s["ramp_s"] == m["ramp_s"]
    assert len(s["requests"]) >= max(160, m["queue"])
    k = m["stratum"]
    first = sorted(r["max_tokens"] for r in s["requests"][:k])
    for i in range(k, len(s["requests"]) - k + 1, k):
        assert sorted(r["max_tokens"] for r in s["requests"][i:i + k]) == first
    lo, hi = m["prompt_tokens"]["min"], m["prompt_tokens"]["max"]
    assert all(lo + 51 <= r["prompt_tokens"] <= hi + 51 for r in s["requests"])
    assert all(len(r["prompt"]) + 51 == r["prompt_tokens"] for r in s["requests"])


def test_quantiles_cover_the_distribution():
    u = TR.quantiles({"dist": "uniform", "min": 64, "max": 256}, 64)
    assert min(u) >= 64 and max(u) <= 256 and u == sorted(u)
    assert u[0] == 66 and u[-1] == 254 and abs(sum(u) / 64 - 160) < 1
    assert TR.prompt_lengths({"prompt_tokens": {"dist": "uniform", "min": 10,
                                                "max": 10}}, 5, n=3) == [15] * 3


def test_what_the_generator_does_not_know_it_refuses():
    with pytest.raises(ValueError):
        TR.quantiles({"dist": "zipf", "min": 1, "max": 2}, 4)
    with pytest.raises(ValueError):
        TR.schedule({**mix(MIXES[0]), "loop": "open"}, {"clients": 2}, 1)
