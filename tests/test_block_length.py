"""The length of the throughput decode block (ISSUE 43, ROADMAP S6a).

`EngineConfig.block_sizes` is (16, 4, 1): a request is live to the end of
the block its budget ends in, so the block's length is what a budget-ended
request throws away and how long a parked request waits for its `done`.
Under test: (a) the default engine dispatches, compiles and warms nothing
longer than 16 steps and a request loses less than one such block; (b) a
request's tokens are a function of its seed and its steps, not of where
blocks end: the 64-step sizes and the default give the same streams, greedy
and sampled, dense and paged, plain and hybrid; (c) `_pick_block_size` on
the default sizes, as a table.
"""

from __future__ import annotations

import types

import jax
import numpy as np
import pytest

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params

TOP = 16
OLD = (64, 16, 4, 1)
# The admission's token is no decode row: budget b owes b - 1 rows.
BUDGETS = (1, 2, 5, 17, 18, 70, 100, 131)


@pytest.fixture(scope="module")
def archs():
    out = {}
    for name in ("tiny", "tiny-lfm2"):
        cfg = get_arch(name)
        out[name] = (cfg, init_params(cfg, jax.random.key(0)))
    return out


def _mk(archs, arch, mode, **kw):
    cfg, params = archs[arch]
    kw = {"max_slots": 2, "max_seq": 256, "kv_page_size": 16,
          "kv_pages": 64 if mode == "paged" else 0,
          "trace_journal_events": 4096, **kw}
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**kw))
    eng.start()
    return eng


def _ids(handle):
    events = list(handle)
    assert events[-1].kind == "done", events[-1]
    return [int(e.token_id) for e in events if e.kind == "token"]


# ---- (a) the default engine knows no block longer than 16 -------------------- #


def test_the_default_sizes():
    assert EngineConfig().block_sizes == (TOP, 4, 1)
    assert EngineConfig().pipeline_depth == 3


@pytest.fixture(scope="module")
def default_paged(archs):
    """Each budget alone through a default one-slot paged engine: the
    `decode_block` events (a = n) and the row gauges a request."""
    eng = _mk(archs, "tiny", "paged", max_slots=1)
    try:
        eng.warmup(prompt_len=4)
        warmed = set(eng._block_cache)
        runs = {}
        for b in BUDGETS:
            before = eng.metrics()
            mark = len(eng.journal.snapshot())
            got = _ids(eng.submit(GenRequest(
                prompt_ids=[1, 5, 9, 3], max_new_tokens=b, temperature=0.0,
                ignore_eos=True)))
            assert len(got) == b
            after = eng.metrics()
            blocks = [int(e["a"]) for e in eng.journal.snapshot()[mark:]
                      if e["event"] == "decode_block"]
            runs[b] = dict(blocks=blocks, rows={
                k: after[k] - before[k] for k in (
                    "decode_rows_dispatched", "decode_rows_posted",
                    "decode_rows_overshoot")})
        return dict(warmed=warmed, built=set(eng._block_cache), runs=runs)
    finally:
        eng.stop()


def _block_steps(keys):
    """The n of every decode-block program among `_block_cache`'s keys
    (`_get_block`: (variant, n, with_lp, with_dfa, kv_win, with_lora))."""
    return {k[1] for k in keys
            if len(k) == 6 and k[0] in ("greedy", "simple", "filtered",
                                        "grammar")}


def test_warm_up_builds_no_program_longer_than_the_top_size(default_paged):
    assert _block_steps(default_paged["warmed"]) == {TOP, 4, 1}
    # serving the budgets compiled no further size either
    assert _block_steps(default_paged["built"]) == {TOP, 4, 1}


@pytest.mark.parametrize("budget", BUDGETS)
def test_a_request_schedules_less_than_a_block_past_its_budget(default_paged,
                                                               budget):
    run = default_paged["runs"][budget]
    assert all(n <= TOP for n in run["blocks"]), run["blocks"]
    owed = budget - 1
    assert sum(run["blocks"]) < owed + TOP
    assert run["rows"]["decode_rows_posted"] == owed
    assert run["rows"]["decode_rows_overshoot"] == sum(run["blocks"]) - owed
    # whole throughput blocks, then ONE tail block: the smallest that covers
    whole, rest = divmod(owed, TOP)
    tail = [n for n in (1, 4, TOP) if n >= rest][:1] if rest else []
    assert run["blocks"] == [TOP] * whole + tail


# ---- (b) tokens do not depend on where blocks end ---------------------------- #


def _requests():
    """Greedy and seeded sampled requests (plain temperature, and the
    filtered chain), budgets that end mid-block under both size sets."""
    out = []
    for i, (budget, kw) in enumerate((
            (70, dict(temperature=0.0)),
            (37, dict(temperature=0.0)),
            (83, dict(temperature=0.9, seed=1234)),
            (50, dict(temperature=0.7, top_k=20, top_p=0.9, seed=77)),
    )):
        out.append(GenRequest(prompt_ids=[1 + i, 5, 9, 3 + i],
                              max_new_tokens=budget, ignore_eos=True, **kw))
    return out


@pytest.mark.parametrize("arch,mode", [
    ("tiny", "dense"), ("tiny", "paged"), ("tiny-lfm2", "paged")])
def test_tokens_are_the_same_under_64_step_and_16_step_blocks(archs, arch,
                                                              mode):
    streams = {}
    for sizes in (OLD, (TOP, 4, 1)):
        eng = _mk(archs, arch, mode, block_sizes=sizes)
        try:
            handles = [eng.submit(r) for r in _requests()]
            streams[sizes] = [_ids(h) for h in handles]
            top = max(int(e["a"]) for e in eng.journal.snapshot()
                      if e["event"] == "decode_block")
        finally:
            eng.stop()
        assert top == sizes[0]  # the size under test was the one dispatched
    old, new = streams[OLD], streams[(TOP, 4, 1)]
    assert [len(s) for s in new] == [r.max_new_tokens for r in _requests()]
    for i, (a, b) in enumerate(zip(old, new)):
        assert a == b, f"request {i}"


# ---- (c) the tail rule on the default sizes ---------------------------------- #


def _pick(remaining, max_seq=4096, sizes=None):
    """`Engine._pick_block_size` over slots with `remaining` tokens owed."""
    ecfg = EngineConfig(max_slots=len(remaining), max_seq=max_seq,
                        **({"block_sizes": sizes} if sizes else {}))
    slots = [None if r is None else types.SimpleNamespace(
        request=types.SimpleNamespace(max_new_tokens=r + 3),
        scheduled=3, prompt_len=10) for r in remaining]
    fake = types.SimpleNamespace(
        ecfg=ecfg, slots=slots,
        h_active=np.array([r is not None for r in remaining]))
    return Engine._pick_block_size(fake)


@pytest.mark.parametrize("remaining,want", (
    [((r,), TOP) for r in (16, 17, 64, 500)]
    + [((r,), TOP) for r in range(5, 16)]
    + [((r,), 4) for r in (2, 3, 4)]
    + [((1,), 1)]
    # the largest budget over the live slots decides; a free slot has none
    + [((1, 3, None), 4), ((2, 40, 1), TOP), ((None, 1), 1)]))
def test_pick_block_size_on_the_default_sizes(remaining, want):
    assert _pick(remaining) == want


def test_the_context_s_end_bounds_the_budget_too():
    # 10 prompt + 3 scheduled of max_seq 16: 3 rows left, whatever is owed
    assert _pick((500,), max_seq=16) == 4
    # sizes set from code keep the same rule
    assert _pick((500,), sizes=OLD) == 64
    assert _pick((20,), sizes=OLD) == 64
    assert _pick((9,), sizes=OLD) == 16


# ---- (d) the text of an answer is not decoded again for every token ----------- #
# Four times the blocks showed the loop's cost a TOKEN (PERF.md section 6,
# PR 43): `_post_token` decoded the whole answer for each. `Engine._decoded`
# keeps the text of a settled prefix; it has to equal the whole decode always.


class _SpaceLeading:
    """SentencePiece's habit: a piece starts with a space, and a decode
    drops the space its FIRST piece starts with; " ." loses its space (HF's
    clean-up), so a cut between the two changes the text."""

    WORDS = (" the", " cat", "s", ".", " sat", ",", " on", "ing", " .", " a")

    def decode(self, ids):
        text = "".join(self.WORDS[i % len(self.WORDS)] for i in ids)
        text = text.replace(" .", ".")
        return text[1:] if text.startswith(" ") else text


def _byte_ids(rng, n):
    """UTF-8 of mixed one- to four-byte characters, a byte a token, with
    ids that decode to nothing sprinkled in (the byte tokenizer's >= 256)."""
    chars = "aé€😀 zࠀ"
    data = "".join(chars[int(rng.integers(len(chars)))] for _ in range(n))
    out = []
    for b in data.encode("utf-8"):
        out.append(int(b))
        if rng.random() < 0.2:
            out.append(300)
    return out


@pytest.mark.parametrize("kind", ("bytes", "broken-utf8", "space-leading"))
def test_the_settled_prefix_decodes_to_the_whole_text(kind):
    rng = np.random.default_rng(7)
    tok = _SpaceLeading() if kind == "space-leading" else ByteTokenizer(512)
    if kind == "bytes":
        ids = _byte_ids(rng, 300)
    elif kind == "broken-utf8":  # lead and continuation bytes in any order
        ids = [int(x) for x in rng.choice(
            [0x61, 0xC3, 0xA9, 0xE2, 0x82, 0xAC, 0xF0, 0x9F, 0x98, 300], 600)]
    else:
        ids = [int(x) for x in rng.integers(0, 10, 600)]
    fake = types.SimpleNamespace(tokenizer=tok,
                                 _DECODE_TAIL=Engine._DECODE_TAIL)
    slot = types.SimpleNamespace(generated=[], dec_n=0, dec_text="")
    longest = 0
    for t in ids:
        slot.generated.append(t)
        assert Engine._decoded(fake, slot) == tok.decode(slot.generated)
        assert slot.dec_text == tok.decode(slot.generated[:slot.dec_n])
        longest = max(longest, len(slot.generated) - slot.dec_n)
    # the prefix did settle: what is decoded a token stays a bounded tail
    # (a stream of stray UTF-8 bytes offers a cut behind a replacement
    # character four times in ten, and waits 16 tokens each time)
    bound = (16 if kind == "broken-utf8" else 8) * Engine._DECODE_TAIL
    assert slot.dec_n > len(ids) - bound
    assert longest <= bound
