"""The plain reference against the program's own forward pass (models/llama.py)
at the `tiny` width on the CPU: they must agree to float32 rounding when the
program computes in float32-held bf16 weights, and the reference fed
int4-rounded weights must NOT agree."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check as C
from benchmark.reference import dense_gqa as REF


@pytest.fixture(scope="module")
def tiny():
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    cfg = get_arch("tiny")
    return cfg, init_params(cfg, jax.random.key(3))


def program_logprobs(cfg, params, ids):
    from localai_tpu.models.llama import prefill

    toks = jnp.asarray([ids], jnp.int32)
    logits, _k, _v = prefill(cfg, params, toks, jnp.asarray([len(ids)], jnp.int32))
    return np.asarray(jax.nn.log_softmax(logits[0].astype(jnp.float32)))


@pytest.mark.parametrize("n", [5, 33, 100])
def test_reference_agrees_with_the_program_forward(tiny, n):
    cfg, params = tiny
    ids = C.sample_prompts(n, cfg.vocab_size, [n])[0]
    want = program_logprobs(cfg, params, ids)
    got = REF.forward(params, cfg, ids, [n - 1])[0]
    # the program runs bf16 activations; the reference float32
    assert np.max(np.abs(got - want)) < 0.02
    top = np.argsort(-got)[:5]
    assert int(np.argmax(want)) in top


def test_reference_rows_do_not_see_the_padding(tiny):
    cfg, params = tiny
    ids = C.sample_prompts(1, cfg.vocab_size, [40])[0]
    a = REF.forward(params, cfg, ids, [10, 39], pad_to=64)
    b = REF.forward(params, cfg, ids, [10, 39], pad_to=128)
    c = REF.forward(params, cfg, ids[:11], [10], pad_to=64)
    np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(a[0], c[0], atol=1e-5)


def test_lower_precision_is_caught(tiny):
    cfg, params = tiny
    ids = C.sample_prompts(2, cfg.vocab_size, [64])[0]
    rows = list(range(40, 64))
    f32 = REF.forward(params, cfg, ids, rows)
    bf16 = REF.forward(params, cfg, ids, rows, compute="bfloat16")
    int4 = REF.forward(params, cfg, ids, rows, weight_round="int4")
    fp8 = REF.forward(params, cfg, ids, rows, kv_round="fp8")
    chosen = np.argmax(f32, axis=-1)
    err = lambda x: float(np.max(np.abs(  # noqa: E731
        x[np.arange(len(rows)), chosen] - f32[np.arange(len(rows)), chosen])))
    assert err(bf16) < 0.01
    assert err(int4) > 3 * err(bf16)
    assert err(fp8) > err(bf16)


def test_compare_and_verdict():
    V = 50
    ref = np.log(np.full((2, V), 1.0 / V))
    ref[0, 7] += 1.0
    ref[1, 9] += 1.0
    good = {"ids": [7, 9], "lp": [float(ref[0, 7]) + 0.01, float(ref[1, 9])],
            "top": [{7: float(ref[0, 7]) + 0.01}, {9: float(ref[1, 9])}]}
    e = C.compare(good, ref)
    assert e["chosen"] == pytest.approx(0.01) and e["argmax_gap"] == 0.0
    assert C.verdict([e], 0.02) and not C.verdict([e], 0.005)
    # an argmax flip: chose id 3 where the reference prefers 7 by 1.0
    flip = {"ids": [3, 9], "lp": [float(ref[0, 3]), float(ref[1, 9])],
            "top": [{3: float(ref[0, 3]), 7: float(ref[0, 7])}, {9: float(ref[1, 9])}]}
    e = C.compare(flip, ref)
    assert e["argmax_gap"] == pytest.approx(1.0) and not C.verdict([e], 0.05)
    # the reference's best id missing from the system's list is a miss
    blind = {"ids": [3, 9], "lp": [float(ref[0, 3]), float(ref[1, 9])],
             "top": [{3: float(ref[0, 3])}, {9: float(ref[1, 9])}]}
    assert C.compare(blind, ref)["best_missing"] == 1
