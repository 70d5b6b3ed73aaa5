import json

import pytest

from benchmark.harness import stamps as ST


def write(tmp_path, head, reqs):
    p = tmp_path / "stamps.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps(head) + "\n")
        for r in reqs:
            f.write(json.dumps(r) + "\n")
    return ST.load(str(p))


def req(i, send, chunks, max_tokens=None, **kw):
    n = len(chunks)
    return {"i": i, "send": send, "end": (chunks[-1] if chunks else send) + 0.01,
            "status": 200, "done": True, "max_tokens": max_tokens or n,
            "completion_tokens": n, "prompt_tokens": 10, "chunks": chunks, **kw}


def test_percentile_interpolates():
    assert ST.percentile([1.0], 95) == 1.0
    assert ST.percentile([0.0, 10.0], 50) == 5.0
    assert ST.percentile(list(range(101)), 95) == 95.0
    with pytest.raises(ValueError):
        ST.percentile([], 50)


def test_closed_loop_window_arithmetic(tmp_path):
    head = {"t0": 100.0, "seconds": 10.0, "loop": "closed", "ramp_s": 2.0}
    reqs = [
        # sent in the ramp, ends inside: judged, and its 2 window tokens count
        req(0, 99.0, [99.5, 100.5, 101.5]),
        # sent and finished inside: 4 tokens 1 s apart
        req(1, 101.2, [102.0, 103.0, 104.0, 105.0]),
        # sent inside, still streaming at the window's end: cut by the
        # generator, its 1 window token counts, its pace does not
        {**req(2, 109.0, [109.5, 111.0]), "done": False, "cut": True, "max_tokens": 9},
        # refused: failed, never "incorrect", no tokens
        {"i": 3, "send": 105.0, "end": 105.1, "status": 429, "done": False,
         "max_tokens": 5, "prompt_tokens": 10, "chunks": [], "error": "queue full"},
        # began and ended in the ramp: not judged at all
        req(4, 98.0, [98.5, 99.0]),
        # ended inside with one token: no pace to take
        req(5, 103.0, [103.5]),
    ]
    head = write(tmp_path, head, reqs)
    assert [r["i"] for r in ST.measured(head)] == [0, 1, 2, 3, 5]
    assert [r["i"] for r in ST.failed(head)] == [3]
    assert ST.tokens_in_window(head) == 2 + 4 + 1 + 1
    e = ST.end_to_end(head)
    assert e["out_tokens_per_s"] == pytest.approx(0.8)
    # pace over the requests that finished inside with two tokens or more:
    # request 0 (1,000 ms a token) and request 1 (1,000 ms)
    assert e["tpot_ms_p95"] == pytest.approx(1000.0)
    assert set(e) == {"out_tokens_per_s", "tpot_ms_p95"}
    assert ST.streams_consistent(head) == []


def test_stream_faults_are_about_streams_not_load(tmp_path):
    head = {"t0": 0.0, "seconds": 10.0, "loop": "closed", "ramp_s": 0.0}
    good = req(0, 1.0, [2.0, 3.0])
    extra = req(1, 1.0, [2.0, 3.0, 4.0], max_tokens=2)
    extra["completion_tokens"] = 2
    cut = {**req(2, 8.0, [9.0]), "done": False, "cut": True, "max_tokens": 9}
    no_done = {**req(3, 1.0, [2.0]), "done": False}
    head = write(tmp_path, head, [good, extra, cut, no_done])
    faults = ST.streams_consistent(head)
    assert len(faults) == 2 and faults[0].startswith("1:") and faults[1].startswith("3:")
    # a request cut by the generator at the window's end is not a failure
    assert [r["i"] for r in ST.failed(head)] == [3]
