"""The paged-attention kernel reads its layer's pages straight out of the
pool that is still stacked over layers (stack + layer index, ops/stacked.py;
ISSUE 27): bit-identical to the call on the sliced layer, under the model's
layer scan, with two stacks, sharded over tp.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops.paged_flash import (
    paged_decode_partials,
    paged_decode_partials_mq,
)
from paged_cases import PAGE, _hier_of, _table

_STACK_L = 3


def _stacked_case(variant):
    """(pools [L, P, page, K, D], table, limits, kwargs) for one variant."""
    B, K, D, MP, P = 2, 2, 32, 4, 10
    kk, kv = jax.random.split(jax.random.key(40))
    k5 = jax.random.normal(kk, (_STACK_L, P, PAGE, K, D))
    v5 = jax.random.normal(kv, (_STACK_L, P, PAGE, K, D))
    table = _table(B, MP, P, seed=11)
    limits = jnp.array([3 * PAGE + 5, 2 * PAGE], jnp.int32)
    kw = {}
    if variant == "hier":
        table = _hier_of(table, 2)
    elif variant == "fp8_scale":
        kw["kv_scale"] = jnp.asarray([[2.0, 0.5], [1.5, 3.0]], jnp.float32)
        k5 = (k5 / kw["kv_scale"][0][:, None]).astype(jnp.float8_e4m3fn)
        v5 = (v5 / kw["kv_scale"][1][:, None]).astype(jnp.float8_e4m3fn)
    elif variant == "sliding":
        kw.update(window=PAGE + 3, sliding=jnp.asarray(True))
    elif variant == "sink_window":
        kw.update(sink=PAGE // 2, swin=PAGE + 5)
    return k5, v5, table, limits, kw


def _stacked_wrapper(name):
    from localai_tpu.ops.paged_flash import paged_prefill_partials_mq

    B, T, H, D = 2, 6, 4, 32
    if name == "decode":
        return paged_decode_partials, jax.random.normal(
            jax.random.key(41), (B, H, D)), {}
    q = jax.random.normal(jax.random.key(42), (B, T, H, D))
    if name == "mq":
        return paged_decode_partials_mq, q, {}
    # three tiles of two tokens: every tile re-reads the same stack
    return paged_prefill_partials_mq, q, {"max_qrows": 4}


@functools.cache
def _stacked_and_sliced(wrapper, variant):
    """(pools, one compiled call) of a (wrapper, variant): the call takes the
    pools and a layer and gives the wrapper's partials on the stack at that
    layer and on the layer sliced out. The layer is an argument, so the
    cases of a group share one compile of the two interpreted kernels."""
    from localai_tpu.ops.stacked import StackedLayer

    fn, q, extra = _stacked_wrapper(wrapper)
    k5, v5, table, limits, kw = _stacked_case(variant)
    if q.ndim == 4:
        kw["q_pos"] = limits[:, None] + jnp.arange(q.shape[1])[None, :]

    def both(k5, v5, li):
        kp = StackedLayer(k5, li)
        assert kp.shape == k5.shape[1:] and kp.dtype == k5.dtype and kp.ndim == 4
        return (fn(q, kp, StackedLayer(v5, li), table, limits, interpret=True,
                   **extra, **kw),
                fn(q, k5[li], v5[li], table, limits, interpret=True,
                   **extra, **kw))

    return (k5, v5), jax.jit(both)


@pytest.mark.parametrize("variant", ["flat", "hier", "fp8_scale", "sliding",
                                     "sink_window"])
@pytest.mark.parametrize("layer", [0, _STACK_L // 2, _STACK_L - 1])
@pytest.mark.parametrize("wrapper", ["decode", "mq", "prefill"])
def test_stacked_pool_bit_identical_to_sliced(wrapper, layer, variant):
    pools, both = _stacked_and_sliced(wrapper, variant)
    got, want = both(*pools, jnp.int32(layer))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _scan_partials(cfg, params, h, pools, table, limits, q):
    """paged_partials(impl=pallas) under llama._scan_layers with the pools
    marked to ride stacked: per-layer acc, the layer numbers the body got."""
    from localai_tpu.models import llama
    from localai_tpu.ops.attention import paged_partials
    from localai_tpu.ops.stacked import StackedLayer

    seen = []

    def layer(h, xs):
        lp, li, kc, vc, plain = xs
        assert isinstance(kc, StackedLayer) and kc.layer is vc.layer
        seen.append((kc.stack.shape, vc.stack.shape, plain.shape))
        acc, _, _ = paged_partials(q, kc, vc, table, limits, impl="pallas")
        return h, (acc, li, kc.layer, plain)

    extras = llama._paged_pool(llama.KVCache(*pools)) + (
        jnp.arange(pools[0].shape[0], dtype=jnp.float32),)
    _, out = llama._scan_layers(cfg, params, h, layer, extras)
    return out, seen


def test_scan_stack_hands_the_pool_on_with_a_traced_index():
    """A marked pool reaches the body unsliced with the scan's counter, a
    plain extra beside it sliced as ever; the kernel under the scan equals
    the per-layer calls on slices bit for bit."""
    import types

    k5, v5, table, limits, _ = _stacked_case("flat")
    q = jax.random.normal(jax.random.key(43), (2, 4, 32))
    cfg = types.SimpleNamespace(num_layers=_STACK_L, first_k_dense=0)
    params = {"layers": {"w": jnp.zeros((_STACK_L, 1))}}
    run = jax.jit(lambda k, v: _scan_partials(
        cfg, params, jnp.zeros(()), (k, v), table, limits, q)[0])
    acc, li, lk, plain = run(k5, v5)
    assert li.tolist() == lk.tolist() == plain.tolist() == [0, 1, 2]
    for l in range(_STACK_L):
        want = paged_decode_partials(q, k5[l], v5[l], table, limits,
                                     interpret=True)[0]
        np.testing.assert_array_equal(np.asarray(acc[l]), np.asarray(want))


def test_two_stack_model_gets_the_global_layer_and_no_cut_of_the_pool():
    """first_k_dense > 0 (DeepSeek layout): both stacks' scans read the
    WHOLE pool at the model's layer number — no `[:kd]` / `[kd:]` cut, which
    for a stacked pool would be a copy of most of it once a step."""
    import types

    k5, v5, table, limits, _ = _stacked_case("flat")
    q = jax.random.normal(jax.random.key(44), (2, 4, 32))
    cfg = types.SimpleNamespace(num_layers=_STACK_L, first_k_dense=1)
    params = {"dense_layers": {"w": jnp.zeros((1, 1))},
              "layers": {"w": jnp.zeros((_STACK_L - 1, 1))}}
    shapes = []

    def fn(k, v):
        out, seen = _scan_partials(cfg, params, jnp.zeros(()), (k, v), table,
                                   limits, q)
        shapes.extend(seen)
        return out

    jaxpr = jax.make_jaxpr(fn)(k5, v5)
    # one trace a stack; each saw all L layers of both pools, one row of the rest
    assert shapes == [(k5.shape, v5.shape, ())] * 2
    assert not [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "slice"]
    acc, li, lk, plain = jax.jit(fn)(k5, v5)
    assert li.tolist() == lk.tolist() == plain.tolist() == [0, 1, 2]
    for l in range(_STACK_L):
        want = paged_decode_partials(q, k5[l], v5[l], table, limits,
                                     interpret=True)[0]
        np.testing.assert_array_equal(np.asarray(acc[l]), np.asarray(want))


@pytest.mark.multichip
def test_stacked_pool_sharded_tp2(multichip):
    """tp=2 shard_map with the pool still stacked: the layer axis stays
    whole on every shard, the index is replicated; all three dispatchers
    equal the sharded call on the sliced layer bit for bit."""
    if multichip is True:
        return  # verdict delivered by the subprocess re-run
    from localai_tpu.ops import attention as A
    from localai_tpu.ops.stacked import StackedLayer
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = build_mesh(MeshPlan(tp=2))
    k5, v5, table, limits, _ = _stacked_case("flat")
    kvs = jnp.asarray([[2.0, 0.5], [1.5, 3.0]], jnp.float32)
    layer = _STACK_L - 1
    with mesh:
        for name, fn in (("decode", A.paged_partials),
                         ("mq", A.paged_partials_mq),
                         ("prefill", A.paged_prefill_partials)):
            _, q, _ = _stacked_wrapper(name)
            kw = {"kv_scale": kvs, "impl": "pallas", "mesh": mesh}
            if q.ndim == 4:
                kw["q_pos"] = limits[:, None] + jnp.arange(q.shape[1])[None, :]
            stacked, sliced = jax.jit(lambda q, k, v, i, fn=fn, kw=kw: (
                fn(q, StackedLayer(k, i), StackedLayer(v, i), table, limits, **kw),
                fn(q, k[layer], v[layer], table, limits, **kw),
            ))(q, k5, v5, jnp.int32(layer))
            for g, w in zip(stacked, sliced):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
