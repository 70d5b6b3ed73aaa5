"""A decode block's window written into the page pool in place (ISSUE 44).

`ops/pool_write.pool_write` copies the rows `llama.write_block_to_pool`
resolves through the page table into the donated pool by DMA, where a token's
row of the pool is narrower than the native tile; everywhere else XLA's
scatter stays. Here: the kernel (interpret mode, the code that compiles for
the chip) against the scatter bit for bit; the rule and its two counters; a
tp = 2 engine against tp = 1; and the four-chip cell's decode block compiled
for a described TPU v5e, which is what shows the pool copies gone. The one
file that describes the topology also keeps the other kernels' compiles for
the described chip: the swap gather and `ops/ssd.ssd_decode`'s read-out.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import llama
from localai_tpu.ops import attention as A
from localai_tpu.ops.pool_write import in_place_rows, pool_write
from localai_tpu.ops.stacked import SiteCounts

L, PAGE, MP, D = 3, 32, 4, 128
SCRATCH = 24  # the page idle slots' table entries name; 24 live pages before it


def _bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _case(K, n, dtype, hier, seed=0):
    """Six slots of what a block's write meets: a run inside a page, a
    straddle, a start on a page's first row, an idle slot (SCRATCH entries,
    its position ratcheted to the last row: every row clamps), a live slot
    whose last rows pass the table (the clamp), a run that ends on a page's
    last row."""
    rng = np.random.default_rng(seed)
    B = 6
    pool = llama.KVCache(*(
        jnp.asarray(rng.standard_normal((L, SCRATCH + 1, PAGE, K, D)),
                    jnp.float32).astype(dtype) for _ in range(2)))
    win = [jnp.asarray(rng.standard_normal((L, B, n, K, D)) * 3, jnp.bfloat16)
           for _ in range(2)]
    flat = rng.permutation(SCRATCH)[:B * MP].reshape(B, MP).astype(np.int32)
    flat[3] = SCRATCH
    start = np.array([5, PAGE - n // 2, 2 * PAGE, MP * PAGE - 1,
                      MP * PAGE - 1 - n // 2, 2 * PAGE - n], np.int32)
    if hier:  # the same columns through a two-level table, 2 columns a span
        table = (jnp.arange(B * 2, dtype=jnp.int32).reshape(B, 2),
                 jnp.asarray(flat.reshape(B * 2, 2)))
    else:
        table = jnp.asarray(flat)
    scale = None
    if jnp.dtype(dtype).itemsize == 1:  # a scaled fp8 pool
        scale = jnp.asarray(rng.uniform(0.5, 2.0, (2, K)), jnp.float32)
    row = np.minimum(start[:, None] + np.arange(n)[None], MP * PAGE - 1)
    return pool, table, win, jnp.asarray(start), scale, (
        flat[np.arange(B)[:, None], row // PAGE], row % PAGE)


@pytest.mark.parametrize("hier", [False, True], ids=["flat", "hier"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
@pytest.mark.parametrize("n", [16, 4, 1])
@pytest.mark.parametrize("K", [2, 4])
def test_kernel_writes_the_scatters_rows(K, n, dtype, hier):
    """`write_block_to_pool` under the Pallas reader (the kernel) and under
    the XLA walk (the scatter): the same pool, bit for bit. Rows that clamp
    to one address hold one of the rows sent there (the scatter's order is
    as unspecified as a DMA's)."""
    pool, table, win, start, scale, (pid, off) = _case(K, n, dtype, hier)

    def write(impl):
        sites = SiteCounts()
        with sites.tracing("write"):
            out = jax.jit(lambda p, t: llama.write_block_to_pool(
                p, t, *win, start, kv_scale=scale, paged_impl=impl))(pool, table)
        return out, sites.by_program["write"]

    got, tally = write("pallas")
    want, tally_x = write("xla")
    ruled = in_place_rows(pool.k.shape, dtype)
    assert ruled is not (K == 2 and dtype != "bfloat16")  # half a word a token
    assert (tally["pool_write_inplace"], tally["pool_write_scatter"]) == (
        (2, 0) if ruled else (0, 2))
    assert (tally_x["pool_write_inplace"], tally_x["pool_write_scatter"]) == (0, 2)
    if not ruled:  # no chip could run it there; the kernel's code still can
        scales = (None, None) if scale is None else scale
        got = llama.KVCache(*(pool_write(
            p, llama._pool_store(w, p.dtype, sc), jnp.asarray(pid),
            jnp.asarray(off), interpret=True)
            for p, w, sc in zip(pool[:2], win, scales)))
    sent = {}
    for b in range(pid.shape[0]):
        for r in range(n):
            sent.setdefault((int(pid[b, r]), int(off[b, r])), []).append((b, r))
    shared = {at: rows for at, rows in sent.items() if len(rows) > 1}
    assert bool(shared) == (n > 1)  # the idle slot, and the slot at the clamp
    for which, rows, sc in zip("kv", win, (None, None) if scale is None else scale):
        g, w, before = (_bits(getattr(p, which)) for p in (got, want, pool))
        stored = _bits(llama._pool_store(rows, pool.k.dtype, sc))
        same = np.ones(g.shape[1:3], bool)
        for (p, o), from_rows in shared.items():
            same[p, o] = False
            assert any(np.array_equal(g[:, p, o], stored[:, b, r])
                       for b, r in from_rows), (p, o)
        np.testing.assert_array_equal(g[:, same], w[:, same])
        for p, o in sent:
            same[p, o] = False
        np.testing.assert_array_equal(  # and every other row as it was
            g[:, same], before[:, same])


def test_pool_write_refuses_a_window_of_another_shape():
    pool = jnp.zeros((2, 4, 8, 2, 128), jnp.bfloat16)
    idx = jnp.zeros((3, 4), jnp.int32)
    with pytest.raises(ValueError, match="pool_write"):
        pool_write(pool, jnp.zeros((2, 3, 4, 4, 128), jnp.bfloat16), idx, idx,
                   interpret=True)
    with pytest.raises(ValueError, match="pool_write"):
        pool_write(pool, jnp.zeros((2, 3, 4, 2, 128), jnp.float32), idx, idx,
                   interpret=True)


# rows of D a token, dtype -> does the block write take the kernel
_RULE = [
    (2, "bfloat16", 128, True), (4, "bfloat16", 128, True),
    (8, "bfloat16", 128, False), (16, "bfloat16", 128, False),
    (4, "float8_e4m3fn", 128, True), (8, "float8_e4m3fn", 128, False),
    (1, "float32", 128, True), (2, "float32", 256, True),
    (8, "float32", 128, False),
    # what Mosaic refuses as a DMA's slice (compiled for a described v5e):
    (1, "bfloat16", 640, False),  # the latent pool's row: half a sublane word
    (2, "float8_e4m3fn", 128, False),  # likewise
    (6, "bfloat16", 128, False), (3, "bfloat16", 128, False),  # tiled by 8, by 4
    (2, "bfloat16", 64, False), (4, "bfloat16", 96, False),  # part of a lane tile
]


@pytest.mark.parametrize("rows,dtype,width,inplace", _RULE,
                         ids=[f"{r}x{w}-{d}" for r, d, w, _ in _RULE])
def test_rule_is_on_the_pools_row(rows, dtype, width, inplace):
    assert in_place_rows((32, 257, 128, rows, width), dtype) is inplace


@pytest.mark.parametrize("K,tp,impl,inplace", [
    (8, 1, "pallas", False), (4, 1, "pallas", True), (2, 1, "pallas", True),
    (2, 1, "xla", False), (4, 1, "auto", False),  # off the TPU auto is the XLA walk
    (8, 2, "pallas", True), (16, 2, "pallas", False), (8, 2, "xla", False),
], ids=lambda v: str(v))
def test_write_window_counts_what_it_chose(K, tp, impl, inplace):
    """The rule where it is applied: one chip's rows of the pool and the
    paged reader decide, and the choice is counted per traced program."""
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = build_mesh(MeshPlan(tp=tp), jax.devices()[:tp]) if tp > 1 else None
    pool = jax.ShapeDtypeStruct((2, 5, 16, K, 128), jnp.bfloat16)
    win = jax.ShapeDtypeStruct((2, 3, 4, K, 128), jnp.bfloat16)
    idx = jax.ShapeDtypeStruct((3, 4), jnp.int32)
    sites = SiteCounts()
    with sites.tracing("decode_block"):
        jaxpr = jax.make_jaxpr(lambda p, w, a, b: A.write_window(
            p, w, a, b, impl=impl, mesh=mesh))(pool, win, idx, idx)
    tally = sites.by_program["decode_block"]
    assert (tally["pool_write_inplace"], tally["pool_write_scatter"]) == (
        (1, 0) if inplace else (0, 1))
    text = str(jaxpr)
    assert ("pallas_call" in text) is inplace
    assert ("scatter" in text) is not inplace
    assert ("shard_map" in text) is (inplace and tp > 1)
    assert sites.totals()["pool_write_inplace"] == int(inplace)


@pytest.mark.multichip
def test_head_sharded_write_matches_the_scatter(multichip):
    """Under a tp = 2 mesh the kernel runs inside shard_map on each chip's
    two of four heads: the pool it returns is the scatter's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    pool, table, win, start, _, _ = _case(4, 16, "bfloat16", False, seed=3)
    mesh = build_mesh(MeshPlan(tp=2), jax.devices()[:2])
    def put(a):
        return jax.device_put(
            a, NamedSharding(mesh, P(None, None, None, "tp", None)))

    sharded = llama.KVCache(put(pool.k), put(pool.v))
    sites = SiteCounts()
    with sites.tracing("write"):
        got = jax.jit(lambda p: llama.write_block_to_pool(
            p, table, put(win[0]), put(win[1]), start, paged_impl="pallas",
            mesh=mesh))(sharded)
    assert sites.by_program["write"]["pool_write_inplace"] == 2
    assert got.k.sharding.spec == P(None, None, None, "tp", None)
    want = llama.write_block_to_pool(pool, table, *win, start)
    rows = np.ones((SCRATCH + 1, PAGE), bool)
    rows[SCRATCH, PAGE - 1] = rows[int(table[4, MP - 1]), PAGE - 1] = False
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(_bits(g)[:, rows], _bits(w)[:, rows])


@pytest.mark.multichip
def test_tp2_engine_writes_in_place_and_matches_tp1(multichip):
    """A paged engine of four KV heads over two chips (two rows of D a
    token a chip) writes its blocks in place and streams the tokens the
    one-chip engine under the XLA walk and scatter streams."""
    from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
    from localai_tpu.models import get_arch
    from localai_tpu.parallel.mesh import MeshPlan

    cfg = dataclasses.replace(get_arch("tiny"), num_kv_heads=4, head_dim=128)
    params = llama.init_params(cfg, jax.random.key(0))
    prompt = [(i * 13) % 251 + 2 for i in range(44)]

    def run(tp, impl):
        eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                     mesh_plan=MeshPlan(tp=tp) if tp > 1 else None,
                     engine_cfg=EngineConfig(
                         max_slots=2, max_seq=128, min_prefill_bucket=16,
                         kv_pages=10, kv_page_size=32, paged_kernel=impl,
                         block_sizes=(4, 1), prefix_admit_async_compile=False))
        eng.start()
        try:
            ids = [ev.token_id for ev in eng.submit(GenRequest(
                prompt_ids=prompt, ignore_eos=True, max_new_tokens=10))
                if ev.kind == "token"]
            return ids, dict(eng.quant_sites.by_program), eng.metrics()
        finally:
            eng.stop()

    ids2, by_program, metrics = run(2, "pallas")
    block = by_program["decode_block"]
    assert block["pool_write_inplace"] == 2 * block["traces"] > 0
    assert block["pool_write_scatter"] == 0
    assert metrics["pool_write_inplace_sites"] == block["pool_write_inplace"]
    assert metrics["pool_write_scatter_sites"] == 0
    ids1, by_program, metrics = run(1, "xla")
    block = by_program["decode_block"]
    assert (block["pool_write_inplace"], block["pool_write_scatter"]) == (
        0, 2 * block["traces"])
    assert metrics["pool_write_scatter_sites"] == block["pool_write_scatter"]
    assert metrics["pool_write_inplace_sites"] == 0
    assert ids2 == ids1 and len(ids1) == 10


# --------------------------------------------------------------------------- #
# Compiled for a described TPU v5e (no chip; the `on-chip-measurement`
# guide's third rehearsal, kept as a test). The topology is described inside
# a fixture: only the worker that runs this file loads libtpu.
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def topo():
    from tools import cell_program

    try:
        return cell_program.describe("v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


# The four-chip cell cut to 8 layers: the shallowest depth at which the
# scatter of ISSUE 44's parent still relaid the pool exactly as at 32 (the
# layer axis on the tile's 8 sublanes: `{4,0,3,2,1:T(8,128)(2,1)}`; 4 layers
# relaid it to `T(4,128)`; 1, 2, 6 and 12 layers not at all).
CELL, DEPTH = "mistral-7b-bf16-tp4", 8


def test_four_chip_decode_block_holds_no_copy_of_the_pool(topo):
    from tools import cell_program

    prog = cell_program.decode_block(cell_program.cell_yaml(CELL), topo,
                                     layers=DEPTH)
    assert prog.pool_local == (DEPTH, 257, 128, 2, 128)
    text = prog.compile_text()
    assert cell_program.pool_copies(text, prog.pool_local) == []
    kernels = cell_program.kernels(text)
    assert kernels.get("pool_write") == 2 and kernels.get("paged_attention") == 1
    assert (prog.sites["pool_write_inplace"],
            prog.sites["pool_write_scatter"]) == (2, 0)
    # in place: the pools a chip holds are the program's donated parameters
    assert text.count("output_to_operand_aliasing") >= 2
    assert jax.default_backend() == "cpu"  # the patch did not outlive the compile


def test_pool_copies_finds_a_relaid_pool():
    """The reader of the compiled text, on the two lines ISSUE 44's parent
    held a pool (one in, one back) and on lines that are no pool."""
    from tools import cell_program

    text = "\n".join((
        "  %copy.62 = bf16[32,257,128,2,128]{4,0,3,2,1:T(8,128)(2,1)} "
        "copy(%param.15), sharding={devices=[1,1,1,4,1]<=[4]}",
        "  %copy.65 = bf16[32,257,128,2,128]{4,3,2,1,0:T(2,128)(2,1)} "
        "copy(%fusion.2), backend_config={}",
        "  %copy.280 = bf16[257,128,32,2,128]{4,1,3,0,2:T(8,128)(2,1)} "
        "copy(%param_0.858)",
        "  %copy.81 = bf16[32,4096,1024]{1,2,0:T(8,128)(2,1)} copy(%p.3)",
        "  %fusion.2 = bf16[32,257,128,2,128]{4,0,3,2,1} fusion(%copy.62)",
    ))
    found = cell_program.pool_copies(text, (32, 257, 128, 2, 128))
    assert [line.split(" = ")[0] for line in found] == [
        "%copy.62", "%copy.65", "%copy.280"]


@pytest.mark.parametrize("shape,n,dtype", [
    ((32, 257, 128, 2, 128), 4, "bfloat16"),  # the cell's shorter blocks
    ((32, 257, 128, 2, 128), 1, "bfloat16"),
    ((32, 257, 128, 4, 128), 16, "bfloat16"),  # tp = 2
    ((32, 257, 128, 4, 128), 16, "float8_e4m3fn"),
    ((6, 257, 128, 4, 128), 16, "bfloat16"),  # lfm2-8b-a1b's packed pool
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_mosaic_takes_the_kernel(topo, shape, n, dtype):
    """The kernel alone, compiled by Mosaic at the shapes the rule sends it:
    in place (the result aliases the pool) and no copy of the pool."""
    from jax.sharding import SingleDeviceSharding

    from tools import cell_program

    one = SingleDeviceSharding(topo.devices[0])
    B = 32

    def sds(s, d):
        return jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one)

    assert in_place_rows(shape, dtype)
    with cell_program.as_on_tpu():
        text = jax.jit(pool_write, donate_argnums=(0,)).trace(
            sds(shape, dtype), sds((shape[0], B, n, *shape[3:]), dtype),
            sds((B, n), "int32"), sds((B, n), "int32"),
        ).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert cell_program.kernels(text) == {"pool_write": 1}
    assert cell_program.pool_copies(text, shape) == []
    assert "output_to_operand_aliasing" in text


@pytest.mark.parametrize("shape,groups", [
    ((4, 32, 128, 64, 128), 1),  # granite-4.0-h-small's state, 4 layers of it
    ((3, 3, 16, 8, 128), 2),  # a head block of one tile of read-outs
    ((3, 4, 8, 16, 32), 1),  # tiny-granite-h: a state under one lane tile
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_mosaic_takes_the_ssd_read_out(topo, shape, groups):
    """`ops/ssd.ssd_decode` (kept here, in the one file that describes the
    topology): Mosaic takes the float32 dot at HIGHEST that reads the state
    out (ISSUE 52) at the published shape and under one lane tile alike, the
    stacked state aliased, nothing held beside it."""
    from jax.sharding import SingleDeviceSharding

    from localai_tpu.ops import ssd
    from tools import cell_program

    one = SingleDeviceSharding(topo.devices[0])
    _, B, H, P, N = shape

    def sds(*s, d="float32"):
        return jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one)

    with cell_program.as_on_tpu():
        compiled = jax.jit(ssd.ssd_decode, donate_argnums=(0,)).trace(
            sds(*shape), sds(d="int32"), sds(B, H, P), sds(B, H), sds(H),
            sds(B, groups, N), sds(B, groups, N), sds(H),
        ).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert cell_program.kernels(text) == {"ssd_decode": 1}
    assert "output_to_operand_aliasing" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("k,v", [
    ((47, 897, 128, 1, 640), (47, 897, 128, 1, 0)),  # GLM-4.7-Flash's latent pool
    ((32, 257, 128, 8, 128), (32, 257, 128, 8, 128)),  # mistral-7b's
], ids=["latent", "gqa"])
def test_swap_gather_holds_no_copy_of_the_pool(topo, k, v):
    """The swap path's gather of 8 pages: XLA's own gather cut a pool of
    640-value rows into five pool-sized slices (5.9 GB of temporaries, which
    failed to load beside the pool on the chip); a page at a time holds the
    pages it returns and no more."""
    from jax.sharding import SingleDeviceSharding

    from localai_tpu.engine.engine import _gather_pages
    from tools import cell_program

    one = SingleDeviceSharding(topo.devices[0])

    def sds(s, d):
        return jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one)

    with cell_program.as_on_tpu():
        prog = jax.jit(_gather_pages).trace(
            sds(k, "bfloat16"), sds(v, "bfloat16"), sds((8,), "int32"),
        ).lower(lowering_platforms=("tpu",)).compile()
    image = 2 * 8 * (np.prod(k) + np.prod(v)) // k[1]
    mem = prog.memory_analysis()
    assert mem.output_size_in_bytes >= image
    assert mem.temp_size_in_bytes < image
