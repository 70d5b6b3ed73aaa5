"""What the model test modules share (not collected): the tiny engine each
builds, the record of a streamed request, its distance from the module's
plain reference, and the module's one long-lived engine.

A model keeps a module of its own (`test_olmoe.py`, `test_kimi_linear.py`,
`test_solar_open2.py`, `test_lfm2.py`): under `--dist loadfile` one module is
one worker's, and the four together would be the run's longest.
"""

import pytest

from benchmark.harness import check as C
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig


def _engine(cfg, params, **kw):
    kw = {"max_slots": 2, "max_seq": 256, "block_sizes": (8, 1),
          "kv_pages": 40, "kv_page_size": 16, "trace_journal_events": 2048,
          **kw}
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**kw))
    eng.start()
    return eng


def _collect(handle, n):
    rec = {"ids": [], "lp": [], "top": []}
    for ev in handle:
        assert ev.kind != "error", ev.error
        if ev.kind == "token":
            rec["ids"].append(int(ev.token_id))
            rec["lp"].append(float(ev.logprob))
            rec["top"].append({int(i): float(v)
                               for i, v in (ev.top_logprobs or [])})
    assert len(rec["ids"]) == n
    return rec


def _err_against(forward, params, cfg, prompt, rec):
    """`benchmark.harness.check.compare` of a record against the reference
    `forward` teacher-forced over the record's ids."""
    return C.compare(rec, C.reference_logprobs(
        forward, params, cfg, prompt, rec["ids"], pad_to=16))


def served_engine(seeded, cfg):
    """The module-scoped `served` fixture: one long-lived engine on int8
    matrices as the cell's (both sides read them as data), and its
    parameters. `seeded` is the module's own `_seeded`."""

    @pytest.fixture(scope="module")
    def served():
        params = seeded(quantize="int8")
        eng = _engine(cfg, params)
        yield eng, params
        eng.stop()

    return served
