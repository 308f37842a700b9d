"""Does Mosaic accept the kernels? — without a chip.

The installed libtpu compiles for a TPU that is not there: a v5e topology
description stands in for the devices, and ``jit(f).lower(<shapes placed on
a topology device>).compile()`` runs the real Mosaic + XLA:TPU pipeline
under ``JAX_PLATFORMS=cpu``. Interpret-mode tests check what a kernel
computes; only this checks that it lowers — unsupported shape casts, block
shapes Pallas refuses, SMEM and VMEM budgets (PR 21 found five such
refusals, none visible from a CPU run).

Shapes: Mistral-7B's head geometry (32/8 heads, head_dim 128, 128-token
pages, 4096 window) at an 8k-token tick — 1024 blocks of 8 tokens, 8 row
slots, a 128-page table — the size at which a per-block copy of the page
table overflowed the v5e's 1 MiB of SMEM.

The second half runs the tp wrappers under ``jax.shard_map`` with the
kernels in interpret mode on two virtual devices: on CPU the dispatchers
normally pick the gather references, which hid a ``pallas_call`` that the
installed ``shard_map`` rejected at trace time.

Between the two: the compiled decode PROGRAM (``step_paged_decode_ragged``,
donation and all) is read for anything that moves the page pool — the
copies, slices and relayouts that were two thirds of a decode step until
PR 25 carried the pool in place (PERF.md §6) — and both serving programs
of a latent model for a layer scan's slice of a stacked WEIGHT that does
not fit fast memory and is copied through HBM instead (``weight_moves``;
a fifth of a decode step at 128 heads until PR 36).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, SingleDeviceSharding

from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.ops import flash_attention as fa
from quoracle_tpu.ops import paged_attention as pa

CFG = get_model_config("mistral-7b")
H, KV, HD, WINDOW = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim, \
    CFG.sliding_window
PAGE, N_PAGES = 128, 257
LAYERS = 2      # the kernel takes the pool whole, as stored: [L, n_pages,
                # page, KV·hd], and is told which layer to read


@pytest.fixture(scope="module")
def on_v5e():
    """ShapeDtypeStruct factory placing arrays on one v5e topology device."""
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def compiles(fn, *args) -> None:
    jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("tq,nb,rows", [(8, 1024, 8), (1, 8, 8)],
                         ids=["tq8-8k-tick", "tq1-decode"])
def test_ragged_kernel_compiles(on_v5e, tq, nb, rows, quant):
    """The block kernel at Mistral's widths with the scratch of its block
    walk (ISSUE 38): two blocks of ``walk_pages`` pages a stream and a
    DMA semaphore a slot in scoped VMEM; the prefetched tables in SMEM
    are the ones it always had."""
    S = on_v5e
    pool = S((LAYERS, N_PAGES, PAGE, KV * HD),
             jnp.int8 if quant else jnp.bfloat16)
    args = [S((nb * tq, H, HD), jnp.bfloat16), pool, pool,
            S((rows, 128), jnp.int32), S((4, nb), jnp.int32),
            S((), jnp.int32)]
    if quant:
        args += [S((LAYERS, N_PAGES, KV, PAGE), jnp.float32)] * 2

    def fn(q, k, v, tables, meta, layer, ks=None, vs=None):
        return pa.ragged_attend(q, k, v, tables, meta, layer, tq=tq,
                                sliding_window=WINDOW, k_scale=ks,
                                v_scale=vs)
    compiles(fn, *args)


@pytest.mark.parametrize("rows", [8, 64], ids=["r8", "r64"])
@pytest.mark.parametrize("h,kv,hd,quant", [
    (32, 8, 128, False), (32, 8, 128, True), (16, 2, 128, False),
    (16, 2, 128, True), (32, 8, 64, False),     # packed: no int8 pool
], ids=["h32-kv8-bf16", "h32-kv8-int8", "h16-kv2-bf16", "h16-kv2-int8",
        "h32-kv8-hd64-packed-bf16"])
def test_shared_walk_kernel_compiles(on_v5e, h, kv, hd, quant, rows):
    """The decode call with a shared-walk table (ISSUE 32) at Mistral's,
    Qwen's and LFM2's head shapes (the last two heads to a lane tile), a
    table 128 wide, the batcher's 8 row slots and the largest row bucket:
    the walk table in SMEM beside the page tables; the gathered queries,
    the walk's softmax state and every row's parked state in scoped VMEM
    beside the two blocks of pages a stream, ``walk_pages`` each (ISSUE
    38; at 64 rows of 8 kv heads the parked state alone is 6 MiB)."""
    S = on_v5e
    pool = S((LAYERS, N_PAGES, PAGE, kv * hd),
             jnp.int8 if quant else jnp.bfloat16)
    args = [S((rows, h, hd), jnp.bfloat16), pool, pool,
            S((rows, 128), jnp.int32), S((4, rows), jnp.int32),
            S((), jnp.int32), S((2 + pa.SHARED_ROWS, rows), jnp.int32)]
    if quant:
        args += [S((LAYERS, N_PAGES, kv, PAGE), jnp.float32)] * 2

    def fn(q, k, v, tables, meta, layer, shared, ks=None, vs=None):
        return pa.ragged_attend(q, k, v, tables, meta, layer, tq=1,
                                k_scale=ks, v_scale=vs, shared=shared)
    compiles(fn, *args)


# the decode call of every configuration the dense block kernel serves:
# (query heads, kv heads, head_dim, window, whether the call has the
# shared-walk table, B, the parent's kernel in bytes of code: AOT, PR 45)
DECODE_CALLS = {
    "qwen2.5-3b": (16, 2, 128, None, True, 8, 130048),
    "mistral-7b-l16": (32, 8, 128, 4096, True, 2, 76800),
    "lfm2-24b-a2b-l9": (32, 8, 64, None, True, 4, 264192),
    "laguna-s-2.1-full": (48, 8, 128, None, True, 2, 229376),
    "laguna-s-2.1-window": (72, 8, 128, 512, False, 2, 89600),
    "mellum2-full": (32, 4, 128, None, True, 4, 166400),
    "mellum2-window": (32, 4, 128, 1024, False, 4, 66048),
}


@pytest.mark.parametrize("geometry", DECODE_CALLS.values(), ids=DECODE_CALLS)
def test_decode_call_compiles_with_its_walks_started_ahead(on_v5e, geometry):
    """The decode call at each dense configuration's widths (ISSUE 45):
    the two scalars that carry a walk started ahead from one grid program
    to the next lie in SMEM beside the prefetched tables, the search for
    the next program with a walk is a scalar loop Mosaic accepts, and the
    kernel grew by the copies' descriptors, not by a second walk body: it
    stays within 32 KiB of the parent's (a walk body is 40–200)."""
    h, kv, hd, window, table, block, parent_bytes = geometry
    S = on_v5e
    hd_p, pack = pa._lane_geometry(kv, hd)
    assert pa.walk_pages(PAGE * (kv // pack) * hd_p * 2) == block
    pool = S((LAYERS, N_PAGES, PAGE, kv * hd), jnp.bfloat16)
    args = [S((8, h, hd), jnp.bfloat16), pool, pool, S((8, 128), jnp.int32),
            S((4, 8), jnp.int32), S((), jnp.int32)]
    if table:
        args.append(S((2 + pa.SHARED_ROWS, 8), jnp.int32))

    def fn(q, k, v, tables, meta, layer, shared=None):
        return pa.ragged_attend(q, k, v, tables, meta, layer, tq=1,
                                sliding_window=window, shared=shared)
    compiled = jax.jit(fn).lower(*args).compile()
    assert len(_custom_calls(compiled.as_text())) == 1
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert parent_bytes < code < parent_bytes + (32 << 10), code


# the two dense configurations the benchmark serves: (H, KV, window)
DENSE = {"mistral-h32-kv8": (32, 8, WINDOW), "qwen-h16-kv2": (16, 2, None)}


def _tile_args(S, h, kv, tb, width, rows, quant):
    """Shapes of the chunk forward's attention call at a warm key
    (token budget ``tb``, table ``width``, ``rows`` slots): the tile
    kernel's, with the tile table the engine would hand it."""
    tq = 8
    tile = pa.ragged_tile(h, HD, tq)
    pool = S((LAYERS, N_PAGES, PAGE, kv * HD),
             jnp.int8 if quant else jnp.bfloat16)
    args = [S((tb, h, HD), jnp.bfloat16), pool, pool,
            S((rows, width), jnp.int32), S((4, tb // tq), jnp.int32),
            S((), jnp.int32),
            S((6, pa.ragged_tile_slots(tb // tq, rows, tq, tile)),
              jnp.int32)]
    if quant:
        args += [S((LAYERS, N_PAGES, kv, PAGE), jnp.float32)] * 2
    return tq, tile, args


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("tb,width", [(16384, 64), (32768, 128)],
                         ids=["16k-w64", "32k-w128"])
@pytest.mark.parametrize("geometry", DENSE.values(), ids=DENSE)
def test_ragged_tile_kernel_compiles(on_v5e, geometry, tb, width, quant):
    """The tile kernel (ISSUE 30) at both dense configurations' widths
    and the largest warm keys, 8 row slots: the tile table in SMEM beside
    the page tables, and a 128-token tile's queries, softmax state and
    output in scoped VMEM beside the double-buffered pages — what Mosaic
    refuses, it refuses here."""
    h, kv, window = geometry
    tq, tile, args = _tile_args(on_v5e, h, kv, tb, width, 8, quant)
    assert tile == pa.RAGGED_TILE

    def fn(q, k, v, tables, meta, layer, tiles, ks=None, vs=None):
        return pa.ragged_attend(q, k, v, tables, meta, layer, tq=tq,
                                sliding_window=window, k_scale=ks,
                                v_scale=vs, tiles=tiles, tile=tile)
    compiles(fn, *args)


def test_compiled_ragged_tile_kernel_carries_the_pinned_name(on_v5e):
    """The tile kernel is ``%ragged_attend.<n>`` in a trace too: the
    benchmark's ``^%ragged_attend`` patterns read it as they read the
    block kernel."""
    h, kv, window = DENSE["mistral-h32-kv8"]
    tq, tile, args = _tile_args(on_v5e, h, kv, 4096, 32, 8, False)
    text = jax.jit(functools.partial(
        pa.ragged_attend, tq=tq, sliding_window=window, tile=tile)).lower(
        *args[:6], tiles=args[6]).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == 1
    # (alone in its program the call is the ROOT instruction)
    assert re.match(r"\s*(ROOT )?%ragged_attend(\.\d+)? = ", call[0])
    assert "/ragged_attend/pallas_call" in call[0]


def test_compiled_ragged_kernel_carries_its_pinned_name(on_v5e):
    """A profiler trace shows the kernel as ``%ragged_attend.<n>``, under
    the ``pallas_call``'s explicit ``name`` (ISSUE 24): the benchmark's
    metric files match on it. The pools reach it as they are stored: no
    re-layout (scope ``kv_layout``) is left in the program."""
    S = on_v5e
    pool = S((LAYERS, N_PAGES, PAGE, KV * HD), jnp.bfloat16)
    text = jax.jit(functools.partial(
        pa.ragged_attend, tq=1, sliding_window=WINDOW)).lower(
        S((8, H, HD), jnp.bfloat16), pool, pool, S((8, 128), jnp.int32),
        S((4, 8), jnp.int32), S((), jnp.int32)).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == 1
    assert re.match(r"\s*%ragged_attend(\.\d+)? = ", call[0])
    assert "/ragged_attend/pallas_call" in call[0]
    assert "kv_layout" not in text


def test_ragged_kernel_compiles_at_every_catalog_geometry(on_v5e):
    """The dispatcher routes every paged model to this kernel: all head
    geometries in the catalog must lower, prefill and decode."""
    S = on_v5e
    geometries = {(c.n_heads, c.n_kv_heads, c.head_dim, c.sliding_window)
                  for c in map(get_model_config,
                               ("llama-3-8b", "mistral-7b", "gemma-7b",
                                "llama-1b", "mistral-1b", "gemma-1b"))}
    for h, kv, hd, window in sorted(geometries, key=str):
        # each at its own block of pages: both blocks of K and V within
        # an eighth of the 16 MiB of scoped VMEM (a page of a MiB, gemma-7b's,
        # is walked alone: 4 MiB)
        block = pa.decode_walk_pages(PAGE, kv, hd, 2)
        assert 1 <= block <= 8 and (block == 1 or
                                    4 * block * PAGE * kv * hd * 2 <= 2 << 20)
        pool = S((LAYERS, N_PAGES, PAGE, kv * hd), jnp.bfloat16)
        for tq, nb in ((8, 64), (1, 8)):
            compiles(functools.partial(pa.ragged_attend, tq=tq,
                                       sliding_window=window),
                     S((nb * tq, h, hd), jnp.bfloat16), pool, pool,
                     S((8, 64), jnp.int32), S((4, nb), jnp.int32),
                     S((), jnp.int32))


@pytest.mark.parametrize("name,n_kv,hd,itemsize,page_bytes,block", [
    ("qwen2.5-3b", 2, 128, 2, 64 << 10, 8),
    ("mistral-7b-l16", 8, 128, 2, 256 << 10, 2),
    ("lfm2-24b-a2b-l9", 8, 64, 2, 128 << 10, 4),    # 4 packed heads of 128
    ("mistral-7b-l16-int8", 8, 128, 1, 128 << 10, 4),
    ("mistral-7b-tp4-shard", 2, 128, 2, 64 << 10, 8),
    ("gemma-7b", 16, 256, 2, 1 << 20, 1),           # a page an iteration
    ("tiny-f32", 2, 16, 4, 128 << 10, 4),           # 16 lanes padded to 128
], ids=lambda v: v if isinstance(v, str) else None)
def test_walk_block_follows_the_pages_bytes(name, n_kv, hd, itemsize,
                                            page_bytes, block):
    """B of the block walk is a function of what the kernel sees — the
    bytes of a page in one stream, as it lays the heads out — at the three
    benchmark widths and around them: a block in flight of half a MiB a
    stream, at most 8 pages."""
    hd_p, pack = pa._lane_geometry(n_kv, hd)
    assert PAGE * (n_kv // pack) * hd_p * itemsize == page_bytes
    assert pa.walk_pages(page_bytes) == block
    assert pa.decode_walk_pages(PAGE, n_kv, hd, itemsize) == block
    assert block == 1 or block * page_bytes == 512 << 10


def test_walk_steps_count_what_a_hand_walked_table_gives():
    """``attn_walk_steps`` is counted from the tables as the other tick
    arguments are: per decode step a row's own walk turns once a block of
    the pages BEHIND its shared ones (the last block partial), and a
    group's shared walk once a block of its common pages in every step
    one of its members runs. Walked by hand here, loop for loop."""
    page, block = 128, 4
    #       resident tokens, decode forwards, leading pages a walk covers
    rows = [(1000, 3, 0), (9 * page - 1, 2, 6), (6 * page + 5, 4, 6),
            (17 * page + 60, 5, 0)]
    ctx, fwd, skip = (np.asarray(c, np.int64) for c in zip(*rows))
    shared = np.zeros((2 + pa.SHARED_ROWS, 4), np.int32)
    shared[2:] = np.arange(4)
    shared[0, [1, 2]], shared[1, 1] = 6, 1
    shared[2:, 1] = [1, 2] + [1] * 6
    private = walks = 0
    for step in range(1, fwd.max() + 1):
        for r in np.flatnonzero(fwd >= step):
            n = -(-(ctx[r] + step) // page) - skip[r]    # the kernel's n
            private += len(range(0, n, block))           # its turns
        if (fwd[[1, 2]] >= step).any():
            walks += len(range(0, 6, block))
    steps = np.arange(1, fwd.max() + 1)
    seen = ctx[:, None] + steps
    decode = np.stack([seen, seen - 1, steps <= fwd[:, None]])
    assert pa.ragged_walk_steps(decode, page, block,
                                skip=skip[:, None]) == private
    assert pa.shared_walk_steps(shared, fwd, block) == walks
    # row 0: 8 pages, 2 turns a step; row 3: 18 pages, 5 turns a step,
    # the last of 2 pages; the group's 6 common pages: 2 turns, 4 steps
    assert private == 3 * 2 + (1 + 1) + 4 * 1 + 5 * 5 and walks == 2 * 4
    # a page an iteration: the tile kernel's count, and the pages streamed
    assert pa.ragged_walk_steps(decode, page, 1, skip=skip[:, None]) * page \
        == pa.ragged_tile_walk(decode, page, skip=skip[:, None])[0]
    # a window's first page is the row's own: nothing is skipped, and a
    # walk is the 4 or 5 pages the window touches (1 turn or 2)
    window = 4 * page
    by_hand = sum(len(range((ctx[r] + step - window) // page,
                            -(-(ctx[r] + step) // page), block))
                  for r in range(4) for step in range(1, fwd[r] + 1))
    assert pa.ragged_walk_steps(decode, page, block, window) == by_hand
    assert int(fwd.sum()) < by_hand < 2 * int(fwd.sum())


@pytest.mark.parametrize("b,t,s", [(3, 512, 1024), (1, 1024, 32768 + 1024)],
                         ids=["B3", "32k-window"])
def test_flash_kernel_compiles(on_v5e, b, t, s):
    """B > 1 (the (1, tq) position block) and a cache as long as
    Mistral-7B's context (whole-S K/V blocks needed 32 MiB of VMEM)."""
    S = on_v5e
    compiles(functools.partial(fa.flash_attend, sliding_window=WINDOW),
             S((b, t, H, HD), jnp.bfloat16), S((b, s, KV, HD), jnp.bfloat16),
             S((b, s, KV, HD), jnp.bfloat16), S((b, t), jnp.int32),
             S((b,), jnp.int32))


# --- the compiled decode program: the pool stays where it is -----------------


_ARRAY = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
# what may hold an array of the pool's size without moving it: the entry's
# donated parameter, the loops' carries, a view, the kernel's HBM operand
_STAYS = {"parameter", "bitcast", "get-tuple-element", "custom-call"}


def pool_moves(hlo: str, layer_elems: int) -> list:
    """The instructions of an optimized HLO module that MOVE an array as
    large as one layer's K (or V) pool: everything outside a fused
    computation that produces or consumes such an array and is neither in
    ``_STAYS`` nor a fusion that scatters rows into it in place. A
    ``copy``, ``reshape``, ``dynamic-slice``, ``dynamic-update-slice`` —
    or anything else XLA might think of — lands here; a free reshape is
    printed as ``bitcast`` and does not. A pool-sized array is told from a
    weight by its element count, a multiple of ``layer_elems``."""
    elems, body_of, root_op, insts, comp = {}, {}, {}, [], None
    for ln in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{\s*$", ln)
        if head:
            comp = head.group(1)
            continue
        m = _ARRAY.match(ln)
        if not m:
            continue
        name, _, dims, op = m.groups()
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        elems[name] = n
        calls = re.search(r"calls=%([\w.\-]+)", ln)
        if op == "fusion" and calls:
            body_of[name] = calls.group(1)
        if "ROOT " in ln[:ln.index("=")]:
            root_op[comp] = op
        operands = re.findall(r"%([\w.\-]+)", ln[m.end():].split(
            "), ")[0])
        insts.append((comp, name, op, operands))
    fused = set(body_of.values())

    def pool_sized(n):
        return n >= layer_elems and n % layer_elems == 0
    out = []
    for comp, name, op, operands in insts:
        if comp in fused or op in _STAYS:
            continue
        if not (pool_sized(elems[name])
                or any(pool_sized(elems.get(o, 0)) for o in operands)):
            continue
        if op == "fusion" and root_op.get(body_of[name]) == "scatter" \
                and pool_sized(elems[name]):
            continue                    # rows written into the pool in place
        out.append((op, name, elems[name]))
    return out


def test_pool_moves_finds_what_the_parent_program_did():
    """The reader itself, on lines of the kind PR 24's program held (one
    layer's pool is 4 x 128 x 1024 elements here)."""
    hlo = """
%fused_computation.1 (p0: bf16[8,128,1024]) -> bf16[4,128,1024] {
  %p0 = bf16[8,128,1024]{2,1,0} parameter(0)
  ROOT %dynamic-slice.1 = bf16[4,128,1024]{2,1,0} dynamic-slice(%p0), dynamic_slice_sizes={4,128,1024}
}

%fused_computation.2 (p1: bf16[1024,1024], p2: s32[8], p3: bf16[8,1024]) -> bf16[1024,1024] {
  %p1 = bf16[1024,1024]{1,0} parameter(0)
  %p2 = s32[8]{0} parameter(1)
  %p3 = bf16[8,1024]{1,0} parameter(2)
  ROOT %scatter.1 = bf16[1024,1024]{1,0} scatter(%p1, %p2, %p3), update_window_dims={1}
}

ENTRY %main (a: bf16[2,4,128,1024]) -> bf16[2,4,128,1024] {
  %a = bf16[2,4,128,1024]{3,2,1,0} parameter(0)
  %copy.91 = bf16[2,4,128,1024]{3,2,1,0} copy(%a)
  %bitcast.1 = bf16[1024,1024]{1,0} bitcast(%copy.91)
  %fusion.7 = bf16[4,128,1024]{2,1,0} fusion(%bitcast.1), kind=kLoop, calls=%fused_computation.1
  %reshape.477 = bf16[4,128,8,128]{3,2,1,0} reshape(%fusion.7)
  %w = bf16[300,1024]{1,0} constant(0)
  %copy.2 = bf16[300,1024]{1,0} copy(%w)
  %i = s32[8]{0} constant(0)
  %u = bf16[8,1024]{1,0} constant(0)
  %fusion.9 = bf16[1024,1024]{1,0} fusion(%bitcast.1, %i, %u), kind=kCustom, calls=%fused_computation.2
  ROOT %bitcast.2 = bf16[2,4,128,1024]{3,2,1,0} bitcast(%fusion.9)
}
"""
    assert [(op, name) for op, name, _ in pool_moves(hlo, 4 * 128 * 1024)] \
        == [("copy", "copy.91"), ("fusion", "fusion.7"),
            ("reshape", "reshape.477")]


def _decode_program(on_v5e, monkeypatch, cfg, max_seq, rows=8, width=4):
    """``step_paged_decode_ragged`` of an engine at ``cfg``, compiled for
    the v5e with donation as served: (optimized HLO, memory analysis, one
    layer's pool in elements). The engine is built on shapes alone; its
    dispatcher is told it is on the TPU, so the program holds the Mosaic
    kernel and not the gather reference."""
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    S = on_v5e
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=max_seq)
    st = eng.sessions
    lanes = cfg.n_kv_heads * cfg.head_dim
    pool = S((cfg.n_layers, st.n_pages, st.page, lanes), eng.pool_dtype)
    R, i32, f32 = rows, jnp.int32, jnp.float32
    compiled = eng._step_paged_decode_ragged.lower(
        params, pool, pool, None, None, S((R, width), i32),
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32),
        S((R,), i32), S((R, cfg.vocab_size), f32), S((2,), jnp.uint32),
        S((R,), f32), S((R,), f32), S((R,), jnp.bool_), S((R,), i32),
        None, None, max_new=32).compile()
    return (compiled.as_text(), compiled.memory_analysis(),
            st.n_pages * st.page * lanes)


def _narrow(name, n_kv_heads, **kw):
    """Real pool tiling (head_dim 128, 128-token pages) under a model
    narrow enough for tier-1's clock: every weight, stacked over the 3
    layers, is smaller than one layer's pool (8.6 MB: 33 pages of 8
    kv-heads, or 129 of 2), and none has a multiple of its rows. A pool
    much smaller than these 26 MB the compiler would prefetch into fast
    memory whole, which no serving pool fits."""
    from quoracle_tpu.models.config import ModelConfig
    kw.setdefault("n_heads", 8)
    return ModelConfig(name=name, vocab_size=512, dim=256, n_layers=3,
                       n_kv_heads=n_kv_heads, ffn_dim=512, head_dim=128,
                       **kw)


@pytest.mark.parametrize("cfg,max_seq,width", [
    (_narrow("narrow-kv8-window", 8, sliding_window=4096), 128, 4),
    (_narrow("narrow-kv2-bias", 2, attn_bias=True, tie_embeddings=True),
     512, 4),
    # the shared walk (ISSUE 32) at the two dense configurations' head
    # shapes, a table 128 wide: Mistral's without its window, under which
    # the kernel holds no walk
    (_narrow("narrow-h32-kv8-w128", 8, n_heads=32), 128, 128),
    (_narrow("narrow-h16-kv2-w128", 2, n_heads=16), 512, 128),
], ids=lambda c: getattr(c, "name", None))
def test_decode_program_leaves_the_pool_where_it_is(on_v5e, monkeypatch,
                                                    cfg, max_seq, width):
    """The optimized HLO of the decode program holds no operation that
    moves a layer's pool or more, its temporaries stay under one layer's
    pool, and both pools are donated into their outputs. This is what
    keeps the copies from coming back unnoticed (PERF.md §6, PR 25). The
    program takes the tick's shared-walk table, [2 + SHARED_ROWS, R]
    int32, and its one kernel a layer body reads it."""
    hlo, mem, layer_elems = _decode_program(on_v5e, monkeypatch, cfg,
                                            max_seq=max_seq, width=width)
    assert layer_elems == (max_seq // 4 + 1) * 128 * cfg.n_kv_heads * 128
    assert hlo.count("tpu_custom_call") == 1     # one kernel a layer body
    call = next(ln for ln in hlo.splitlines() if "tpu_custom_call" in ln)
    assert "%ragged_attend" in call
    assert f"s32[{2 + pa.SHARED_ROWS},8]" in call
    assert pool_moves(hlo, layer_elems) == []
    pool_bytes = 2 * cfg.n_layers * layer_elems * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < layer_elems * 2
    # with its walks started ahead (ISSUE 45) the program still copies no
    # layer's weight through HBM, and holds ONE walk body a kernel: 1.22 to
    # 1.45 MB of code on the parent, 5.6 to 7.7 KB more now (AOT, PR 45)
    assert weight_moves(hlo, 1 << 20) == []
    assert mem.generated_code_size_in_bytes < 1_500_000


def test_prefill_program_holds_the_tile_kernel(on_v5e, monkeypatch):
    """``step_paged_ragged`` as the engine serves it (tile table and
    all, pools donated), compiled for the v5e: its one kernel a layer
    body is ``%ragged_attend`` fed the tile table, [6, slots] int32, and
    both pools are donated into their outputs."""
    from quoracle_tpu.models.generate import RAGGED_TQ, GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    cfg = _narrow("narrow-kv2-prefill", 2)
    S = on_v5e
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=512)
    st = eng.sessions
    lanes = cfg.n_kv_heads * cfg.head_dim
    pool = S((cfg.n_layers, st.n_pages, st.page, lanes), eng.pool_dtype)
    tb, rows, i32 = 2048, 8, jnp.int32
    slots = pa.ragged_tile_slots(tb // RAGGED_TQ, rows, RAGGED_TQ,
                                 eng._ragged_tile)
    assert (eng._ragged_tile, slots) == (128, 2048 // 128 + 8 + 1)
    compiled = eng._step_paged_ragged.lower(
        params, pool, pool, None, None, S((tb,), i32), S((tb,), i32),
        S((rows, 4), i32), S((4, tb // RAGGED_TQ), i32), S((6, slots), i32),
        S((tb,), i32), S((rows,), i32), tq=RAGGED_TQ,
        tile=eng._ragged_tile).compile()
    hlo = compiled.as_text()
    call = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == 1 and "%ragged_attend" in call[0]
    assert f"s32[6,{slots}]" in hlo
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * cfg.n_layers * st.n_pages * st.page * lanes * 2


@pytest.mark.slow
def test_decode_program_leaves_the_pool_where_it_is_at_mistral_widths(
        on_v5e, monkeypatch):
    """The same reading at the benchmark's ``mistral-7b-l16`` (run by hand
    before chip time: ``-m slow``, half a minute). There the head's and
    the projections' weights are larger than a layer's pool, so the
    temporaries are held under one WHOLE pool instead — a second pool, or
    a copy of one, cannot hide."""
    import dataclasses
    cfg = dataclasses.replace(CFG, name="mistral-7b-l16", n_layers=16)
    hlo, mem, layer_elems = _decode_program(on_v5e, monkeypatch, cfg,
                                            max_seq=8192, width=64)
    assert layer_elems == 257 * 128 * 1024
    moves = pool_moves(hlo, layer_elems)
    print("pool moves:", moves, "temp bytes:", mem.temp_size_in_bytes,
          "alias bytes:", mem.alias_size_in_bytes)
    assert moves == []
    assert mem.temp_size_in_bytes < cfg.n_layers * layer_elems * 2


@pytest.mark.slow
@pytest.mark.parametrize("name,max_seq,pool_pages", [
    ("mistral-7b-l16", 8192, 257), ("qwen2.5-3b", 32768, 457)])
def test_dense_decode_programs_at_benchmark_widths(on_v5e, monkeypatch, name,
                                                   max_seq, pool_pages):
    """The decode program of the two dense configurations the benchmark
    serves, as their files state them, with every walk of its one kernel a
    layer started ahead (ISSUE 45; `-m slow`, half a minute each, by hand
    before chip time): the pools stay where they are and no layer's slice
    of a stacked weight is copied through HBM (PR 36's reading stands)."""
    from benchmark import configs
    from benchmark.families import dense
    cfg = get_model_config(dense.register(configs.load_config(name)))
    hlo, mem, layer_elems = _decode_program(on_v5e, monkeypatch, cfg,
                                            max_seq=max_seq, width=128)
    assert layer_elems == pool_pages * 128 * cfg.n_kv_heads * 128
    assert hlo.count("tpu_custom_call") == 1
    print(name, "code bytes:", mem.generated_code_size_in_bytes,
          "temp bytes:", mem.temp_size_in_bytes)
    assert pool_moves(hlo, layer_elems) == []
    assert weight_moves(hlo, 1 << 20) == []


# --- a layer's slice of a stacked weight, inside a layer scan (ISSUE 36) -----

_CALLED = re.compile(r"(\w+)=\{?((?:%[\w.\-]+(?:, )?)+)\}?")
_RESULT = re.compile(
    r"\s*(ROOT )?%(\S+) = (\w+)\[([\d,]*)\](\S*) ([\w\-]+)\((?:%([\w.\-]+))?")
_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4}


def weight_moves(hlo: str, min_bytes: int) -> list:
    """The instructions INSIDE a ``while`` body of an optimized HLO module
    (or in what a body calls: an inner loop, a branch) whose result is a
    ``dynamic-slice`` or a ``copy`` — bare, or as the root of a fusion,
    behind bitcasts — of at least ``min_bytes`` of an array the loop
    carries, and does NOT lie in fast memory (no ``S(1)`` in its layout):
    [(opcode, name, bytes)]. A layer scan's slice of a stacked weight is
    such a result, and so is a re-laid copy of one. One that fits fast
    memory is the read of that weight; one that does not is written back
    to HBM and read again by its consumer, a copy that no arithmetic asks
    for (PERF.md §6, PR 36: 224 MiB of ``wo`` in every expert layer of
    every decode step at 128 heads). The entry computation is not looked
    at: what it copies, it copies once a call."""
    comps, comp = {}, None
    for ln in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{\s*$", ln)
        if head:
            comp = comps[head.group(1)] = {"insts": {}, "root": None,
                                           "calls": [], "fuses": {},
                                           "bodies": []}
            continue
        if comp is None or " = " not in ln:
            continue
        # a loop's or a branch's result may be a tuple, which _RESULT does
        # not match: what a line calls is read off the line itself
        m = _RESULT.match(ln)
        for key, names in _CALLED.findall(ln):
            called = re.findall(r"%([\w.\-]+)", names)
            if key == "calls" and " fusion(" in ln:
                if m:                   # a fused body is no computation
                    comp["fuses"][m.group(2)] = called[0]   # of the loop's
            elif key == "body":
                comp["bodies"] += called
            else:
                comp["calls"] += called
        if not m:
            continue
        root, name, dtype, dims, layout, op, first = m.groups()
        n = _WIDTH.get(dtype, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        comp["insts"][name] = (op, n, layout, first)
        if root:
            comp["root"] = name
    todo = [b for c in comps.values() for b in c["bodies"]]
    in_loop = set(todo)
    while todo:
        c = comps[todo.pop()]
        for n in c["calls"] + c["bodies"]:
            if n in comps and n not in in_loop:
                in_loop.add(n)
                todo.append(n)

    def maker(c, name):
        """The opcode that makes ``name``'s bytes: a fusion's is its
        root's, behind bitcasts."""
        op, body = c["insts"][name][0], comps.get(c["fuses"].get(name))
        if op != "fusion" or body is None:
            return op
        r = body["root"]
        while r in body["insts"] and body["insts"][r][0] == "bitcast":
            r = body["insts"][r][3]
        return body["insts"][r][0] if r in body["insts"] else op

    def moved(cn, name):
        """Does ``name`` hold bytes the loop CARRIES (an element of the
        body's parameter: a stacked weight, a pool), moved by slices and
        copies alone? What the body computed and then copied is an
        activation, not looked at."""
        c, moves = comps[cn], False
        while name in c["insts"]:
            op = maker(c, name)
            if op in ("get-tuple-element", "parameter"):
                return moves
            if op not in ("bitcast", "dynamic-slice", "copy"):
                return False
            moves = moves or op != "bitcast"
            name = c["insts"][name][3]
        return False
    return [(comps[cn]["insts"][name][0], name, n)
            for cn in sorted(in_loop)
            for name, (op, n, layout, _) in comps[cn]["insts"].items()
            if n >= min_bytes and "S(1)" not in layout and op != "bitcast"
            and moved(cn, name)]


def test_weight_moves_tells_a_copy_through_hbm_from_a_read():
    """The reader itself, on the parent's lines (PR 33's decode program at
    `deepseek-v3.2-ep16-l5`, layouts as the compiler printed them): the
    layer scan's slice of `wo`, 224 MiB, has no `S(1)`: it is the
    `constant_dynamic-slice_fusion.13` that was the largest device
    operation of the cell; `wq_b`'s, 72 MiB, lies in fast memory. The
    entry's copy of a whole stacked weight is once a call, not a layer's;
    a slice inside another fusion is nothing of its own; an inner loop's
    body counts; a slice re-laid into HBM counts (the chunk forward's
    `copy.163` of `wq_b`), a copy of what the body computed does not; a
    small slice does not."""
    hlo = """
%fused_computation.276 (param_0.3675: bf16[4,16384,7168], param_1.4086: s32[]) -> bf16[1,16384,7168] {
  %param_0.3675 = bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.4086 = s32[]{:T(128)} parameter(1)
  %constant.4341 = s32[]{:T(128)} constant(0)
  ROOT %dynamic_slice.350 = bf16[1,16384,7168]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.3675, %param_1.4086, %constant.4341, %constant.4341), dynamic_slice_sizes={1,16384,7168}
}

%fused_computation.284 (param_0.1: bf16[4,1536,24576], param_1.1: s32[]) -> bf16[1,1536,24576] {
  %param_0.1 = bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)} parameter(0)
  %param_1.1 = s32[]{:T(128)} parameter(1)
  %constant.1 = s32[]{:T(128)} constant(0)
  ROOT %dynamic_slice.348 = bf16[1,1536,24576]{1,2,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.1, %param_1.1, %constant.1, %constant.1), dynamic_slice_sizes={1,1536,24576}
}

%fused_computation.624 (param_0.2: bf16[4,16384,7168], param_1.2: s32[]) -> bf16[16384,7168] {
  %param_0.2 = bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = s32[]{:T(128)} parameter(1)
  %constant.2 = s32[]{:T(128)} constant(0)
  %dynamic_slice.514 = bf16[1,16384,7168]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.2, %param_1.2, %constant.2, %constant.2), dynamic_slice_sizes={1,16384,7168}
  ROOT %bitcast.9 = bf16[16384,7168]{1,0:T(8,128)(2,1)} bitcast(%dynamic_slice.514)
}

%fused_computation.700 (param_0.3: bf16[8,16384], param_1.3: bf16[4,16384,7168], param_2.3: s32[]) -> bf16[8,7168] {
  %param_0.3 = bf16[8,16384]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.3 = bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)} parameter(1)
  %param_2.3 = s32[]{:T(128)} parameter(2)
  %constant.3 = s32[]{:T(128)} constant(0)
  %dynamic_slice.7 = bf16[1,16384,7168]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_1.3, %param_2.3, %constant.3, %constant.3), dynamic_slice_sizes={1,16384,7168}
  %bitcast.7 = bf16[16384,7168]{1,0:T(8,128)(2,1)} bitcast(%dynamic_slice.7)
  ROOT %convolution.7 = bf16[8,7168]{1,0:T(8,128)(2,1)} convolution(%param_0.3, %bitcast.7), dim_labels=bf_io->bf
}

%chunk_body (c: (s32[], bf16[4,16384,7168])) -> (s32[], bf16[4,16384,7168]) {
  %c = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%c), index=0
  %w.1 = bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)} get-tuple-element(%c), index=1
  %dynamic-slice_bitcast_fusion.11 = bf16[16384,7168]{1,0:T(8,128)(2,1)} fusion(%w.1, %i.1), kind=kLoop, calls=%fused_computation.624
  ROOT %t.1 = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}) tuple(%i.1, %w.1)
}

%chunk_cond (c: (s32[], bf16[4,16384,7168])) -> pred[] {
  %c = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %p = pred[]{:T(512)} constant(false)
}

%layer_body (c: (s32[], bf16[4,16384,7168], bf16[4,1536,24576], bf16[8,16384])) -> (s32[], bf16[4,16384,7168], bf16[4,1536,24576], bf16[8,16384]) {
  %c = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}, bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)}, bf16[8,16384]{1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%c), index=0
  %wo = bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)} get-tuple-element(%c), index=1
  %wq = bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)} get-tuple-element(%c), index=2
  %o = bf16[8,16384]{1,0:T(8,128)(2,1)} get-tuple-element(%c), index=3
  %constant_dynamic-slice_fusion.15 = bf16[1,1536,24576]{1,2,0:T(8,128)(2,1)S(1)} fusion(%wq, %i), kind=kLoop, calls=%fused_computation.284
  %constant_dynamic-slice_fusion.13 = bf16[1,16384,7168]{2,1,0:T(8,128)(2,1)} fusion(%wo, %i), kind=kLoop, calls=%fused_computation.276
  %bitcast.13 = bf16[16384,7168]{1,0:T(8,128)(2,1)} bitcast(%constant_dynamic-slice_fusion.13)
  %fusion.700 = bf16[8,7168]{1,0:T(8,128)(2,1)} fusion(%o, %wo, %i), kind=kOutput, calls=%fused_computation.700
  %dynamic-slice.3 = s32[1]{0:T(128)} dynamic-slice(%i, %i), dynamic_slice_sizes={1}
  %copy.163 = bf16[1,1536,24576]{2,1,0:T(8,128)(2,1)} copy(%constant_dynamic-slice_fusion.15)
  %add.1 = bf16[8,16384]{1,0:T(8,128)(2,1)} add(%o, %o)
  %copy.168 = bf16[8,16384]{0,1:T(8,128)(2,1)} copy(%add.1)
  %t.2 = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}) tuple(%i, %wo)
  %while.2 = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}) while(%t.2), condition=%chunk_cond, body=%chunk_body
  ROOT %t = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}, bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)}, bf16[8,16384]{1,0:T(8,128)(2,1)}) tuple(%i, %wo, %wq, %o)
}

%layer_cond (c: (s32[], bf16[4,16384,7168], bf16[4,1536,24576], bf16[8,16384])) -> pred[] {
  %c = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}, bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)}, bf16[8,16384]{1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %p = pred[]{:T(512)} constant(false)
}

ENTRY %main (wo: bf16[4,16384,7168], wq: bf16[4,1536,24576], o: bf16[8,16384]) -> bf16[8,16384] {
  %wo = bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)} parameter(0)
  %wq = bf16[4,1536,24576]{2,1,0:T(8,128)(2,1)} parameter(1)
  %o = bf16[8,16384]{1,0:T(8,128)(2,1)} parameter(2)
  %copy.154 = bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)} copy(%wq)
  %z = s32[]{:T(128)} constant(0)
  %t = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}, bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)}, bf16[8,16384]{1,0:T(8,128)(2,1)}) tuple(%z, %wo, %copy.154, %o)
  %while.1 = (s32[]{:T(128)}, bf16[4,16384,7168]{2,1,0:T(8,128)(2,1)}, bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)}, bf16[8,16384]{1,0:T(8,128)(2,1)}) while(%t), condition=%layer_cond, body=%layer_body
  ROOT %r = bf16[8,16384]{1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=3
}
"""
    assert weight_moves(hlo, 64 << 20) == [
        ("fusion", "dynamic-slice_bitcast_fusion.11", 234881024),
        ("fusion", "constant_dynamic-slice_fusion.13", 234881024),
        ("copy", "copy.163", 75497472)]
    assert weight_moves(hlo, 128 << 20) == weight_moves(hlo, 64 << 20)[:2]
    # with no threshold to speak of: still nothing that lies in fast
    # memory, and nothing the body made itself
    assert [n for _, n, _ in weight_moves(hlo, 1)] == [
        "dynamic-slice_bitcast_fusion.11",
        "constant_dynamic-slice_fusion.13", "dynamic-slice.3", "copy.163"]


# --- the chunk forward over its live blocks (ISSUE 49) ----------------------


def _prefill_program(S, monkeypatch, cfg, max_seq, tb, width, rows=8,
                     live_block=None):
    """``step_paged_ragged`` of an engine at ``cfg`` as it is served (tile
    table, pools donated), compiled for the v5e at a tick of ``tb`` slots
    and a table ``width`` pages wide: (optimized HLO, memory analysis).
    ``live_block``: the forward's block in place of its own (a block no
    bucket holds twice gives the whole-bucket form)."""
    from quoracle_tpu.models import transformer as tr
    from quoracle_tpu.models.generate import RAGGED_TQ, GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    if live_block is not None:
        monkeypatch.setattr(tr, "LIVE_BLOCK", live_block)
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: tr.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=max_seq)
    st = eng.sessions
    pool = S((cfg.n_layers, st.n_pages, st.page,
              cfg.n_kv_heads * cfg.head_dim), eng.pool_dtype)
    i32 = jnp.int32
    slots = pa.ragged_tile_slots(tb // RAGGED_TQ, rows, RAGGED_TQ,
                                 eng._ragged_tile)
    compiled = eng._step_paged_ragged.lower(
        params, pool, pool, None, None, S((tb,), i32), S((tb,), i32),
        S((rows, width), i32), S((4, tb // RAGGED_TQ), i32),
        S((6, slots), i32), S((tb,), i32), S((rows,), i32), tq=RAGGED_TQ,
        tile=eng._ragged_tile).compile()
    return compiled.as_text(), compiled.memory_analysis()


def _whiles(hlo: str) -> int:
    return len(re.findall(r" while\(", hlo))


@pytest.mark.parametrize("cfg,max_seq", [
    (_narrow("narrow-kv8-window-live", 8, sliding_window=4096), 128),
    (_narrow("narrow-kv2-bias-live", 2, attn_bias=True,
             tie_embeddings=True), 512),
], ids=lambda c: getattr(c, "name", None))
def test_prefill_program_reads_its_weights_inside_the_live_blocks(
        on_v5e, monkeypatch, cfg, max_seq):
    """A tick of two blocks and more runs a layer's per-token work in two
    loops over its live blocks, inside the layer scan: the compiled
    program reads the layer's weights there where they lie (no slice or
    copy of a stacked weight goes through HBM) and holds one kernel a
    layer body."""
    from quoracle_tpu.models.transformer import LIVE_BLOCK
    tb = 4 * LIVE_BLOCK
    hlo, _ = _prefill_program(on_v5e, monkeypatch, cfg, max_seq, tb, 4)
    whole, _ = _prefill_program(on_v5e, monkeypatch, cfg, max_seq, tb, 4,
                                live_block=tb)
    assert _whiles(hlo) == _whiles(whole) + 2
    assert hlo.count("tpu_custom_call") == 1
    assert weight_moves(hlo, 1 << 20) == []
    # a tick of one block is the whole-bucket form: the same program
    one, _ = _prefill_program(on_v5e, monkeypatch, cfg, max_seq,
                              LIVE_BLOCK, 4)
    assert _whiles(one) == _whiles(whole)


@pytest.mark.slow
@pytest.mark.parametrize("tb,width", [(8192, 32), (16384, 64)],
                         ids=["tb8192-w32", "tb16384-w64"])
@pytest.mark.parametrize("name,max_seq", [
    ("mistral-7b-l16", 8192), ("qwen2.5-3b", 32768)])
def test_dense_prefill_programs_at_benchmark_widths(on_v5e, monkeypatch,
                                                    name, max_seq, tb,
                                                    width):
    """The prefill programs of the two dense configurations the benchmark
    serves at the `cold-prompts` cells' widest keys (`-m slow`, a minute
    each, by hand before chip time). The live blocks' loops read the MLP's
    matrices (117 / 45 MB each a layer: four fifths of a layer's weights)
    inside their matmuls, where they lie; what is left to move is the
    re-layout of a layer's q/k/v projections where fast memory has no room
    to keep them across the blocks' loop, never more than their own size
    (Mistral: wq's 33.5 MB at 16,384 slots, all three, 50.3 MB, at 8,192;
    the whole-bucket form, which is the parent's program, re-lays them too
    and at 8,192 slots sends wq through HBM as well; Qwen: nothing); and
    the temporaries do not exceed the whole-bucket form's."""
    from benchmark import configs
    from benchmark.families import dense
    cfg = get_model_config(dense.register(configs.load_config(name)))
    hlo, mem = _prefill_program(on_v5e, monkeypatch, cfg, max_seq, tb,
                                width)
    whole_hlo, whole = _prefill_program(on_v5e, monkeypatch, cfg, max_seq,
                                        tb, width, live_block=tb)
    moves = weight_moves(hlo, 1 << 20)
    print(name, tb, "temp bytes:", mem.temp_size_in_bytes, "whole form:",
          whole.temp_size_in_bytes, "code bytes:",
          mem.generated_code_size_in_bytes, "moves:", moves,
          "whole form's:", weight_moves(whole_hlo, 1 << 20))
    assert weight_moves(hlo, 40 << 20) == []
    qkv_bytes = 2 * cfg.dim * cfg.head_dim * (cfg.n_heads
                                              + 2 * cfg.n_kv_heads)
    assert {op for op, _, _ in moves} <= {"copy"}
    assert sum(n for _, _, n in moves) <= qkv_bytes
    assert hlo.count("tpu_custom_call") == 1
    assert mem.temp_size_in_bytes <= whole.temp_size_in_bytes


# --- what the decode program runs on every step, and what only on request ---


def on_every_path(hlo: str, opcode: str) -> tuple[list, int]:
    """(the ``opcode`` instructions of an optimized HLO module that run
    WHENEVER the program does, how many the module holds in all): those
    in the entry computation and in every computation it reaches through
    a call, a fusion, a loop's body or condition — anything but the
    branch of a ``conditional``, which runs only when its predicate says
    so."""
    comps, comp = {}, None
    for ln in hlo.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\{\s*$", ln)
        if head:
            comp = comps[head.group(2)] = {"entry": bool(head.group(1)),
                                           "ops": [], "calls": []}
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*?\s([\w\-]+)\(", ln)
        if comp is None or not m:
            continue
        if m.group(2) == opcode:
            comp["ops"].append(m.group(1))
        for key, names in _CALLED.findall(ln):
            if key not in ("branch_computations", "true_computation",
                           "false_computation"):
                comp["calls"] += re.findall(r"%([\w.\-]+)", names)
    todo = [n for n, c in comps.items() if c["entry"]]
    seen = set(todo)
    while todo:
        for n in comps[todo.pop()]["calls"]:
            if n in comps and n not in seen:
                seen.add(n)
                todo.append(n)
    return (sorted(op for n in seen for op in comps[n]["ops"]),
            sum(len(c["ops"]) for c in comps.values()))


def test_on_every_path_tells_a_branch_from_a_loop_body():
    """The reader itself: a sort in a loop's body runs on every step, one
    in a conditional's branch (or in what the branch calls) does not."""
    hlo = """
%cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

%inner (q: f32[8,512]) -> f32[8,512] {
  %q = f32[8,512]{1,0} parameter(0)
  ROOT %sort.3 = f32[8,512]{1,0} sort(%q), dimensions={1}, to_apply=%cmp
}

%asked (p: (f32[8,512])) -> f32[8,512] {
  %p = (f32[8,512]{1,0}) parameter(0)
  %x = f32[8,512]{1,0} get-tuple-element(%p), index=0
  %sort.2 = f32[8,512]{1,0} sort(%x), dimensions={1}, to_apply=%cmp
  ROOT %call.1 = f32[8,512]{1,0} call(%sort.2), to_apply=%inner
}

%not_asked (p: (f32[8,512])) -> f32[8,512] {
  %p = (f32[8,512]{1,0}) parameter(0)
  ROOT %x = f32[8,512]{1,0} get-tuple-element(%p), index=0
}

%body (c: (f32[8,512], pred[])) -> (f32[8,512], pred[]) {
  %c = (f32[8,512]{1,0}, pred[]) parameter(0)
  %l = f32[8,512]{1,0} get-tuple-element(%c), index=0
  %any = pred[] get-tuple-element(%c), index=1
  %t = (f32[8,512]{1,0}) tuple(%l)
  %sort.1 = f32[8,512]{1,0} sort(%l), dimensions={1}, to_apply=%cmp
  %conditional.1 = f32[8,512]{1,0} conditional(%any, %t, %t), branch_computations={%not_asked, %asked}
  ROOT %r = (f32[8,512]{1,0}, pred[]) tuple(%conditional.1, %any)
}

%cond (c: (f32[8,512], pred[])) -> pred[] {
  %c = (f32[8,512]{1,0}, pred[]) parameter(0)
  ROOT %any = pred[] get-tuple-element(%c), index=1
}

ENTRY %main (a: (f32[8,512], pred[])) -> (f32[8,512], pred[]) {
  %a = (f32[8,512]{1,0}, pred[]) parameter(0)
  ROOT %while.1 = (f32[8,512]{1,0}, pred[]) while(%a), condition=%cond, body=%body
}
"""
    assert on_every_path(hlo, "sort") == (["sort.1"], 3)
    assert on_every_path(hlo, "conditional") == (["conditional.1"], 1)


def test_decode_program_sorts_the_vocabulary_only_in_a_branch(on_v5e,
                                                              monkeypatch):
    """The nucleus of ``sample_tokens`` is one branch of a conditional in
    the compiled program, the first draw's and the loop body's alike: the
    compiler did not turn it into a select that computes both sides, and
    no other sort of the vocabulary has come into a step (PERF.md §6,
    PR 28: 8 x 151,936 logits sorted every step were 15% of Qwen's)."""
    hlo, _, _ = _decode_program(
        on_v5e, monkeypatch,
        _narrow("narrow-kv2-bias", 2, attn_bias=True, tie_embeddings=True),
        max_seq=512)
    always, in_all = on_every_path(hlo, "sort")
    assert always == [] and in_all == 2       # first token, loop body
    always, in_all = on_every_path(hlo, "conditional")
    assert len(always) == in_all == 2
    assert "decode_loop/while/body/sample/top_p/cond/branch_1_fun/" in hlo


# --- the latent kernel and a latent / expert model's decode program ---------

@pytest.mark.parametrize("tq,nb", [(8, 1024), (1, 8)],
                         ids=["tq8-8k-tick", "tq1-decode"])
def test_latent_ragged_kernel_compiles(on_v5e, tq, nb):
    """A.X-K1's latent geometry: 64 query heads against ONE stored row of
    640 lanes (512 latent, 64 rotary, 64 of pad) whose first 512 lanes are
    the value; an 8k-token tick and a decode step. The custom call carries
    the pinned name that the benchmark's ``^%ragged_attend`` matches."""
    S = on_v5e
    pool = S((LAYERS, N_PAGES, PAGE, 640), jnp.bfloat16)
    text = jax.jit(functools.partial(
        pa.ragged_attend_latent, tq=tq, v_lanes=512, scale=0.13)).lower(
        S((nb * tq, 64, 640), jnp.bfloat16), pool, S((8, 128), jnp.int32),
        S((4, nb), jnp.int32), S((), jnp.int32)).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == 1
    assert re.match(r"\s*%ragged_attend_latent(\.\d+)? = ", call[0])


def test_latent_decode_program_leaves_the_pool_where_it_is(on_v5e,
                                                           monkeypatch):
    """A latent, routed-expert model's decode program on the v5e: two
    layer stacks (one dense layer, two expert layers) and the decode loop
    carry ONE latent pool in place: two kernels (one a stack's body),
    nothing that moves a layer's pool, the pool donated into its output."""
    from quoracle_tpu.models.config import LatentConfig, ModelConfig, MoEConfig
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    cfg = ModelConfig(
        name="narrow-latent-moe", vocab_size=512, dim=256, n_layers=3,
        n_heads=8, n_kv_heads=8, ffn_dim=512,
        rope_scaling=("yarn", 32.0, 32.0, 1.0, 4096, 1.0, 1.0),
        latent=LatentConfig(q_rank=128, kv_rank=512, nope_dim=128,
                            rope_dim=64, v_dim=128),
        moe=MoEConfig(n_routed=32, n_held=4, per_token=4, expert_dim=256,
                      n_group=4, topk_group=2, routed_scale=2.5,
                      first_dense=1))
    S = on_v5e
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024)
    st = eng.sessions
    assert cfg.kv_pools == (640,)
    pool = S((cfg.n_layers, st.n_pages, st.page, 640), eng.pool_dtype)
    layer_elems = st.n_pages * st.page * 640
    R, i32, f32 = 8, jnp.int32, jnp.float32
    compiled = eng._step_paged_decode_ragged.lower(
        params, pool, None, None, None, S((R, 8), i32),
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32),
        S((R,), i32), S((R, cfg.vocab_size), f32), S((2,), jnp.uint32),
        S((R,), f32), S((R,), f32), S((R,), jnp.bool_), S((R,), i32),
        None, None, max_new=32).compile()
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    assert hlo.count("tpu_custom_call") == 2
    assert pool_moves(hlo, layer_elems) == []
    assert mem.alias_size_in_bytes >= cfg.n_layers * layer_elems * 2
    assert mem.temp_size_in_bytes < layer_elems * 2


# --- a learned selection inside the latent attention (ISSUE 31) -------------

@pytest.mark.parametrize("tq,nb", [(8, 128), (1, 8)],
                         ids=["tq8-1k-chunk", "tq1-decode"])
def test_selection_kernels_compile_at_v32_widths(on_v5e, tq, nb):
    """DeepSeek-V3.2's geometry against a 128-page table (16k positions):
    the scoring kernel (64 heads of 128 against ONE index key a token)
    and the latent kernel with a per-query selection at 128 heads, which
    needs more than Mosaic's default 16 MiB of scoped VMEM. One custom
    call each; only the attention's name matches ``^%ragged_attend``."""
    S = on_v5e
    tables, meta = S((8, 128), jnp.int32), S((4, nb), jnp.int32)
    scores = jax.jit(functools.partial(pa.index_scores, tq=tq)).lower(
        S((nb * tq, 64, 128), jnp.bfloat16), S((nb * tq, 64), jnp.float32),
        S((LAYERS, N_PAGES, PAGE, 128), jnp.bfloat16), tables, meta,
        S((), jnp.int32)).compile().as_text()
    attn = jax.jit(functools.partial(
        pa.ragged_attend_latent, tq=tq, v_lanes=512, scale=0.13)).lower(
        S((nb * tq, 128, 640), jnp.bfloat16),
        S((LAYERS, N_PAGES, PAGE, 640), jnp.bfloat16), tables, meta,
        S((), jnp.int32),
        select=S((nb * tq, 128 * PAGE), jnp.int32)).compile().as_text()
    for text, name in ((scores, "index_scores"),
                       (attn, "ragged_attend_latent")):
        call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
        assert len(call) == 1
        assert re.match(rf"\s*%{name}(\.\d+)? = ", call[0])
    assert not re.match(r"\s*%ragged_attend", [
        ln for ln in scores.splitlines() if "tpu_custom_call" in ln][0])


def test_selecting_decode_program_leaves_both_pools_where_they_are(
        on_v5e, monkeypatch):
    """A model with an indexer on the v5e: the decode program's two layer
    stacks and its loop carry TWO pools of unequal width in place (latent
    rows, index keys): four kernels (a scoring and an attention call a
    stack's body), nothing that moves a layer of either pool, both
    donated into their outputs."""
    from quoracle_tpu.models.config import (
        IndexerConfig, LatentConfig, ModelConfig, MoEConfig,
    )
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    cfg = ModelConfig(
        name="narrow-sparse-latent-moe", vocab_size=512, dim=256,
        n_layers=3, n_heads=8, n_kv_heads=8, ffn_dim=512,
        rope_scaling=("yarn", 40.0, 32.0, 1.0, 4096, 1.0, 1.0),
        latent=LatentConfig(q_rank=128, kv_rank=512, nope_dim=128,
                            rope_dim=64, v_dim=128),
        moe=MoEConfig(n_routed=32, n_held=4, per_token=4, expert_dim=256,
                      n_group=4, topk_group=2, routed_scale=2.5,
                      first_dense=1, router_bias=True),
        indexer=IndexerConfig(n_heads=8, head_dim=128, topk=256,
                              rope_dim=64))
    S = on_v5e
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=1024)
    st = eng.sessions
    assert cfg.kv_pools == (640, 128)
    pools = [S((cfg.n_layers, st.n_pages, st.page, w), eng.pool_dtype)
             for w in cfg.kv_pools]
    tokens = st.n_pages * st.page
    R, i32, f32 = 8, jnp.int32, jnp.float32
    compiled = eng._step_paged_decode_ragged.lower(
        params, *pools, None, None, S((R, 8), i32),
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32),
        S((R,), i32), S((R, cfg.vocab_size), f32), S((2,), jnp.uint32),
        S((R,), f32), S((R,), f32), S((R,), jnp.bool_), S((R,), i32),
        None, None, max_new=32).compile()
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    assert hlo.count("tpu_custom_call") == 4
    assert len(re.findall(r"%index_scores(\.\d+)? = ", hlo)) == 2
    assert pool_moves(hlo, tokens * 640) == []
    assert pool_moves(hlo, tokens * 128) == []
    assert mem.alias_size_in_bytes >= cfg.n_layers * tokens * (640 + 128) * 2
    assert mem.temp_size_in_bytes < tokens * 640 * 2


# --- the latent decode walk: a group's pages once (ISSUE 40) ----------------

def _custom_calls(text: str) -> list:
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln]


@pytest.mark.parametrize("rows", [8, 64], ids=["r8", "r64"])
@pytest.mark.parametrize("heads,selected", [(128, True), (64, False)],
                         ids=["deepseek-v3.2", "ax-k1"])
def test_latent_decode_walk_compiles(on_v5e, heads, selected, rows):
    """The decode call with a shared-walk table at both latent
    configurations' widths (128 heads under a selection over 16k positions;
    64 heads without), tables 128 wide: the group's walk at every size of
    ``LATENT_WALK_SIZES``, the members' gathered queries and selections,
    the parked state of ``rows`` rows and a block of four pages in flight
    fit the scoped VMEM the call asks for, the four prefetched tables the
    1 MiB of SMEM; the custom call keeps the pinned name."""
    S = on_v5e
    kw = {"select": S((rows, 128 * PAGE), jnp.int32)} if selected else {}
    text = jax.jit(functools.partial(
        pa.ragged_attend_latent, tq=1, v_lanes=512, scale=0.13)).lower(
        S((rows, heads, 640), jnp.bfloat16),
        S((LAYERS, N_PAGES, PAGE, 640), jnp.bfloat16),
        S((rows, 128), jnp.int32), S((4, rows), jnp.int32), S((), jnp.int32),
        shared=S((2 + pa.SHARED_ROWS, rows), jnp.int32),
        **kw).compile().as_text()
    call = _custom_calls(text)
    assert len(call) == 1
    assert re.match(r"\s*%ragged_attend_latent(\.\d+)? = ", call[0])


@pytest.mark.parametrize("rows", [8, 64], ids=["r8", "r64"])
def test_index_scores_decode_walk_compiles(on_v5e, rows):
    """DeepSeek-V3.2's scoring call in the decode program, with the table:
    64 heads of 128 a member, eight pages of index keys a turn, every row's
    scores of the shared pages parked in VMEM; the name `^%ragged_attend`
    does not match."""
    S = on_v5e
    text = jax.jit(functools.partial(pa.index_scores, tq=1)).lower(
        S((rows, 64, 128), jnp.bfloat16), S((rows, 64), jnp.float32),
        S((LAYERS, N_PAGES, PAGE, 128), jnp.bfloat16),
        S((rows, 128), jnp.int32), S((4, rows), jnp.int32), S((), jnp.int32),
        shared=S((2 + pa.SHARED_ROWS, rows), jnp.int32)).compile().as_text()
    call = _custom_calls(text)
    assert len(call) == 1
    assert re.match(r"\s*%index_scores(\.\d+)? = ", call[0])


def mosaic_bodies(lowered) -> list:
    """The Mosaic modules of a lowering's kernels as text WITHOUT source
    locations (the serialized body in ``backend_config`` holds them: an
    edit that moves the file's lines changes the raw text, not this)."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    out = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered.as_text()):
        with jax_mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            out.append(ir.Module.parse(base64.b64decode(body)).operation
                       .get_asm(enable_debug_info=False))
    return out


# sha256 of the DECODE program's latent kernels at the two latent cells'
# widths (and of the scoring kernel's chunk-forward call), read off the
# parent commit (79fda53) with this same reader: the chunk forward's latent
# kernel was rewritten to walk a block of pages a turn (ISSUE 44) and
# leaves these as they were. Since ISSUE 45 the dense block kernel starts
# every walk's first block ahead, through ``_start_block`` and
# ``_walk_blocks``, which these kernels call too: the chunk forward's
# latent kernel (both forms) and the TILE kernel at the two dense widths,
# read off ITS parent (1a20234), are pinned beside them.
UNTOUCHED_KERNELS = {
    "latent-decode-selected": "142631240b228f79",
    "latent-decode": "ca973008759f4348",
    "index-decode": "bf86b017ce0c2348",
    "index-scores": "af1c4f58cd154a0e",
    "latent-chunk-selected": "783aaeaa3a619770",
    "latent-chunk": "a06f4ba4eb680402",
    "tile-mistral-h32-kv8": "8eb5c6256b4fb10e",
    "tile-qwen-h16-kv2": "7f4639fe9e10da16",
}


@pytest.mark.parametrize("kernel", sorted(UNTOUCHED_KERNELS))
def test_the_kernels_beside_the_chunk_walk_are_the_text_they_were(on_v5e,
                                                                  kernel):
    """The decode calls (tq = 1, with the shared-walk table: the latent
    walk with and without a selection, the scoring walk) and the scoring
    kernel's chunk-forward call (tq = 8) lower to the Mosaic text they had
    before the chunk forward's latent kernel got the block walk; that
    kernel and the tile kernel to the text they had before the dense
    decode call's walks were started ahead."""
    import hashlib
    S = on_v5e
    if kernel.startswith("tile-"):
        h, kv, window = DENSE[kernel[5:]]
        tq, tile, args = _tile_args(S, h, kv, 16384, 64, 8, False)
        lowered = jax.jit(lambda q, k, v, tables, meta, layer, tiles:
                          pa.ragged_attend(q, k, v, tables, meta, layer,
                                           tq=tq, sliding_window=window,
                                           tiles=tiles, tile=tile)
                          ).lower(*args)
    elif kernel.startswith("latent-chunk"):
        nb = 128
        heads, kw = (128, {"select": S((nb * 8, 128 * PAGE), jnp.int32)}) \
            if kernel.endswith("selected") else (64, {})
        lowered = jax.jit(functools.partial(
            pa.ragged_attend_latent, tq=8, v_lanes=512, scale=0.13)).lower(
            S((nb * 8, heads, 640), jnp.bfloat16),
            S((5, 512, PAGE, 640), jnp.bfloat16), S((8, 128), jnp.int32),
            S((4, nb), jnp.int32), S((), jnp.int32), **kw)
    elif kernel == "index-scores":
        nb = 128
        lowered = jax.jit(functools.partial(pa.index_scores, tq=8)).lower(
            S((nb * 8, 64, 128), jnp.bfloat16), S((nb * 8, 64), jnp.float32),
            S((5, 512, PAGE, 128), jnp.bfloat16), S((8, 128), jnp.int32),
            S((4, nb), jnp.int32), S((), jnp.int32))
    else:
        rows = 8
        tables, meta = S((rows, 128), jnp.int32), S((4, rows), jnp.int32)
        shared = S((2 + pa.SHARED_ROWS, rows), jnp.int32)
        if kernel == "index-decode":
            lowered = jax.jit(functools.partial(
                pa.index_scores, tq=1)).lower(
                S((rows, 64, 128), jnp.bfloat16), S((rows, 64), jnp.float32),
                S((5, 512, PAGE, 128), jnp.bfloat16), tables, meta,
                S((), jnp.int32), shared=shared)
        else:
            heads, kw = (128, {"select": S((rows, 128 * PAGE), jnp.int32)}) \
                if kernel == "latent-decode-selected" else (64, {})
            lowered = jax.jit(functools.partial(
                pa.ragged_attend_latent, tq=1, v_lanes=512,
                scale=0.13)).lower(
                S((rows, heads, 640), jnp.bfloat16),
                S((5, 512, PAGE, 640), jnp.bfloat16), tables, meta,
                S((), jnp.int32), shared=shared, **kw)
    (body,) = mosaic_bodies(lowered)
    assert hashlib.sha256(body.encode()).hexdigest()[:16] \
        == UNTOUCHED_KERNELS[kernel]


# --- the latent chunk forward's block walk (ISSUE 44) ------------------------

@pytest.mark.parametrize("heads,selected,code_max,vmem_max", [
    # the parent's page-a-turn bodies were 557,056 and 285,696 bytes (AOT,
    # PR 44); a prefill program holds two instances, and the PR keeps the
    # growth under 0.5 MB a program
    (128, True, 820_000, 32 << 20),
    (64, False, 460_000, None),
], ids=["deepseek-v3.2", "ax-k1"])
def test_latent_chunk_walk_compiles_within_its_code_and_vmem(
        on_v5e, heads, selected, code_max, vmem_max):
    """The chunk forward's latent call (tq = 8, no table) at both latent
    configurations' widths, 128 blocks against tables 128 wide: four pages
    a turn as one run of 512 keys, the block's (m, l, acc) in scratch.
    ONE body a program: its code stays within a quarter of a MB of the
    page-a-turn body's, and it asks for 32 MiB of scoped VMEM at 1,024
    score rows under a selection over 16k positions and for no more than
    the default 16 MiB at 512."""
    S = on_v5e
    nb = 128
    kw = {"select": S((nb * 8, 128 * PAGE), jnp.int32)} if selected else {}
    lowered = jax.jit(functools.partial(
        pa.ragged_attend_latent, tq=8, v_lanes=512, scale=0.13)).lower(
        S((nb * 8, heads, 640), jnp.bfloat16),
        S((5, 512, PAGE, 640), jnp.bfloat16), S((8, 128), jnp.int32),
        S((4, nb), jnp.int32), S((), jnp.int32), **kw)
    assert pa.latent_walk_pages(PAGE) == 4
    scoped = re.findall(r'scoped_memory_configs\\22: \[\{[^}]*'
                        r'\\22size\\22: (\d+)', lowered.as_text())
    assert [int(x) for x in scoped] == ([vmem_max] if vmem_max else [])
    compiled = lowered.compile()
    call = _custom_calls(compiled.as_text())
    assert len(call) == 1
    assert re.match(r"\s*%ragged_attend_latent(\.\d+)? = ", call[0])
    assert compiled.memory_analysis().generated_code_size_in_bytes \
        < code_max


# --- a latent model's output projection reads wo[layer] where it lies -------

def _latent_programs(S, monkeypatch, cfg, tb, width, rows=8, max_seq=1024):
    """Both serving programs of a latent, routed-expert model (with or
    without an indexer), compiled for the v5e with donation as served:
    [(optimized HLO, memory analysis)] of the chunk forward (``tb`` flat
    tokens) and the decode loop."""
    from quoracle_tpu.models.generate import RAGGED_TQ, GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=max_seq)
    st = eng.sessions
    pools = [S((cfg.n_layers, st.n_pages, st.page, w), eng.pool_dtype)
             for w in cfg.kv_pools] + [None] * (2 - len(cfg.kv_pools))
    R, i32, f32 = rows, jnp.int32, jnp.float32
    chunk = eng._step_paged_ragged.lower(
        params, *pools, None, None, S((tb,), i32), S((tb,), i32),
        S((R, width), i32), S((4, tb // RAGGED_TQ), i32), None,
        S((tb,), i32), S((R,), i32), tq=RAGGED_TQ, tile=0).compile()
    decode = eng._step_paged_decode_ragged.lower(
        params, *pools, None, None, S((R, width), i32),
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32),
        S((R,), i32), S((R, cfg.vocab_size), f32), S((2,), jnp.uint32),
        S((R,), f32), S((R,), f32), S((R,), jnp.bool_), S((R,), i32),
        None, None, max_new=32).compile()
    return [(c.as_text(), c.memory_analysis()) for c in (chunk, decode)]


WO_LAYER_BYTES = 128 * 128 * 7168 * 2     # 224 MiB: no fast memory holds it


@pytest.mark.parametrize("indexer", [False, True],
                         ids=["latent", "sparse-latent"])
def test_latent_programs_read_a_layer_of_wo_where_it_lies(on_v5e,
                                                          monkeypatch,
                                                          indexer):
    """DeepSeek-V3.2's output projection — 128 heads of 128 into 7,168:
    a layer of `wo` is 224 MiB, which fast memory cannot hold — under a
    model whose every other weight is small: neither serving program
    makes a layer's slice of the stacked weight (3 expert layers) a
    result of its own in HBM. `_latent_attn_out` contracts `wo` over one
    flat dimension, so the scan's slice is part of the matmul's fusion,
    which takes the stacked array and the layer number; contracted over
    (heads, v) apart, the slice was its own fusion in front of it, 224
    MiB written and read again in every layer of every decode step
    (PERF.md §6, PR 36). Temporaries stay under one such slice."""
    from quoracle_tpu.models.config import (
        IndexerConfig, LatentConfig, ModelConfig, MoEConfig,
    )
    cfg = ModelConfig(
        name="narrow-but-wo" + ("-indexed" if indexer else ""),
        vocab_size=512, dim=7168, n_layers=4, n_heads=128, n_kv_heads=128,
        ffn_dim=256, rope_scaling=("yarn", 40.0, 32.0, 1.0, 4096, 1.0, 1.0),
        latent=LatentConfig(q_rank=128, kv_rank=128, nope_dim=128,
                            rope_dim=64, v_dim=128),
        moe=MoEConfig(n_routed=16, n_held=2, per_token=2, expert_dim=128,
                      n_group=4, topk_group=2, routed_scale=2.5,
                      first_dense=1, router_bias=indexer),
        indexer=IndexerConfig(n_heads=8, head_dim=128, topk=256,
                              rope_dim=64) if indexer else None)
    programs = _latent_programs(on_v5e, monkeypatch, cfg, tb=256, width=8)
    for hlo, mem in programs:
        assert f"bf16[3,{128 * 128},7168]" in hlo   # the stack, an operand
        assert weight_moves(hlo, 64 << 20) == []
        assert mem.temp_size_in_bytes < WO_LAYER_BYTES


@pytest.mark.slow
@pytest.mark.parametrize("name,wo_bytes,decode_temp_max,chunk_moves", [
    # parent (PR 33): decode 953,849,344 with the slice of `wo` in it
    ("deepseek-v3.2-ep16-l5", WO_LAYER_BYTES, 730_000_000,
     # the chunk forward re-lays its layer of `wq_b` (contraction minor):
     # 72 MiB, the parent's too; ROADMAP S13's item, not this reader's
     [("copy", 1536 * 24576 * 2)]),
    ("ax-k1-ep16-l7", WO_LAYER_BYTES // 2, None, []),
])
def test_latent_programs_at_benchmark_widths(on_v5e, monkeypatch, name,
                                             wo_bytes, decode_temp_max,
                                             chunk_moves):
    """The benchmark's two latent configurations at their published
    widths (`-m slow`: a minute each; run by hand before chip time), 8
    rows, tables 128 wide, a 512-token chunk: the decode program moves
    no weight slice of 64 MiB or more through HBM, the chunk forward
    none of a layer of `wo`; prints each program's temporaries."""
    from benchmark import configs
    from benchmark.families import latent_moe, sparse_latent_moe
    raw = configs.load_config(name)
    family = {"latent_moe": latent_moe,
              "sparse_latent_moe": sparse_latent_moe}[raw["family"]]
    cfg = get_model_config(family.register(raw))
    assert cfg.n_heads * cfg.latent.v_dim * cfg.dim * 2 == wo_bytes
    (chunk, cmem), (decode, dmem) = _latent_programs(
        on_v5e, monkeypatch, cfg, tb=512, width=128,
        max_seq=min(cfg.context_window, 16384))
    print(name, "temp_size_in_bytes: decode", dmem.temp_size_in_bytes,
          "chunk", cmem.temp_size_in_bytes,
          "moves: decode", weight_moves(decode, 16 << 20),
          "chunk", weight_moves(chunk, 16 << 20))
    assert weight_moves(decode, 64 << 20) == []
    assert [(op, n) for op, _, n in weight_moves(chunk, 64 << 20)] \
        == chunk_moves
    if decode_temp_max:
        assert dmem.temp_size_in_bytes <= decode_temp_max


# --- conv layers' state beside the paged K/V (ISSUE 33) ----------------------

def _hybrid_programs(S, monkeypatch, cfg, tb=256, width=8, rows=8):
    """Both serving programs of a model with conv layers, compiled for the
    v5e with donation as served: [(optimized HLO, memory analysis)] of the
    chunk forward and the decode loop, and the engine's store."""
    from quoracle_tpu.models.generate import RAGGED_TQ, GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(),
                         max_seq=min(cfg.context_window, 131072))
    st = eng.sessions
    kv = S((cfg.n_attn_layers, st.n_pages, st.page, cfg.kv_pools[0]),
           eng.pool_dtype)
    state = S((cfg.n_conv_layers * st.n_pages, cfg.state_lanes),
              eng.pool_dtype)
    R, i32, f32 = rows, jnp.int32, jnp.float32
    slots = pa.ragged_tile_slots(tb // RAGGED_TQ, R, RAGGED_TQ,
                                 eng._ragged_tile)
    n_rec = tb // st.page + 2 * R
    chunk = eng._step_paged_ragged.lower(
        params, kv, kv, None, None, S((tb,), i32), S((tb,), i32),
        S((R, width), i32), S((4, tb // RAGGED_TQ), i32),
        S((6, slots), i32), S((tb,), i32), S((R,), i32), state,
        (S((R,), i32), S((tb, cfg.conv_cache - 1), i32), S((n_rec,), i32),
         S((n_rec,), i32)), tq=RAGGED_TQ, tile=eng._ragged_tile).compile()
    decode = eng._step_paged_decode_ragged.lower(
        params, kv, kv, None, None, S((R, width), i32),
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32), S((R,), i32),
        S((R, cfg.vocab_size), f32), S((2,), jnp.uint32), S((R,), f32),
        S((R,), f32), S((R,), jnp.bool_), S((R,), i32), None, None, state,
        max_new=32).compile()
    return [(c.as_text(), c.memory_analysis()) for c in (chunk, decode)], st


def _narrow_hybrid(periods):
    """LFM2's layer pattern and head geometry (heads of 64: two kv heads
    a lane tile, 512 lanes a token) under narrow weights, with thousands
    of pages as the benchmark has: a state pool of a few megabytes the
    compiler would prefetch into fast memory whole, which no serving pool
    fits."""
    from quoracle_tpu.models.config import ModelConfig, MoEConfig
    return ModelConfig(
        name=f"narrow-shortconv-moe-{periods}", vocab_size=512, dim=512,
        n_layers=1 + 4 * periods, n_heads=32, n_kv_heads=8, head_dim=64,
        ffn_dim=512, rope_theta=1e6, tie_embeddings=True, qk_norm=True,
        layer_types=("conv",) + ("attention", "conv", "conv", "conv")
        * periods,
        moe=MoEConfig(n_routed=16, n_held=16, per_token=4, expert_dim=128,
                      n_shared=0, first_dense=1, router_bias=True,
                      gate_eps=1e-6), context_window=16384)


def test_hybrid_programs_carry_pools_and_state_in_place(on_v5e, monkeypatch):
    """A model with conv layers on the v5e: both programs carry the two
    K/V pools (attention layers only) AND the state pool through the
    segment scans — and the decode loop — in place: one attention kernel
    (the period's one attention layer; heads of 64 packed two a lane
    tile, so no re-layout of the pool) and one kernel for each expert
    layer's blocks, nothing that moves a layer of either pool, all three
    donated into their outputs. And the scan over whole
    periods holds: at two periods (the benchmark's cut) and at three the
    programs have the same kernels and the same fusions, so program size
    and compile time do not follow the depth."""
    counts = []
    for periods in (2, 3):
        cfg = _narrow_hybrid(periods)
        programs, st = _hybrid_programs(on_v5e, monkeypatch, cfg)
        kv_elems = st.n_pages * st.page * 512
        state_elems = st.n_pages * cfg.state_lanes
        assert cfg.n_conv_layers == 1 + 3 * periods and st.n_pages > 2048
        for hlo, mem in programs:
            calls = [ln for ln in hlo.splitlines()
                     if "tpu_custom_call" in ln]
            # the period's attention layer, and its four expert layers'
            # blocks in one kernel each (ops/grouped_experts.py)
            assert sum("%ragged_attend" in c for c in calls) == 1
            assert sum("%routed_experts_ffn" in c for c in calls) == 4
            assert len(calls) == 5
            assert "kv_layout" not in hlo
            assert pool_moves(hlo, kv_elems) == []
            # what consumes the state pool and makes a few rows of it is
            # a row's record being read, not the pool being moved
            assert [m for m in pool_moves(hlo, state_elems)
                    if not (m[0] == "fusion" and m[2] < state_elems)] == []
            assert mem.alias_size_in_bytes >= 2 * (
                2 * cfg.n_attn_layers * kv_elems
                + cfg.n_conv_layers * state_elems)
        counts.append([len(re.findall(r" fusion\(", hlo))
                       for hlo, _ in programs])
    assert counts[0] == counts[1]


@pytest.mark.slow
def test_hybrid_programs_at_lfm2_widths(on_v5e, monkeypatch):
    """The benchmark's `lfm2-24b-a2b-l9` at its published widths (`-m
    slow`: a minute of every core, which the suite's timed tests cannot
    spare; run by hand before chip time): both programs compile with pools and state in place, and
    arguments plus temporaries stay under 13 GiB of the chip's 16."""
    from benchmark import configs
    from benchmark.families import shortconv_moe
    from quoracle_tpu.models.config import get_model_config
    cfg = get_model_config(shortconv_moe.register(
        configs.load_config("lfm2-24b-a2b-l9")))
    programs, st = _hybrid_programs(on_v5e, monkeypatch, cfg, tb=2048,
                                    width=32)
    assert st.n_pages == 4097 and cfg.state_lanes == 4096
    for hlo, mem in programs:
        assert hlo.count("tpu_custom_call") == 5
        assert pool_moves(hlo, st.n_pages * st.page * 512) == []
        assert [m for m in pool_moves(hlo, st.n_pages * 4096)
                if not (m[0] == "fusion" and m[2] < st.n_pages * 4096)] == []
        assert mem.alias_size_in_bytes >= 2 * st.n_pages * (
            2 * 2 * st.page * 512 + 7 * 4096)
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < 13 * 2 ** 30)


# --- window and full attention layers mixed (ISSUE 39) ----------------------

@pytest.mark.parametrize("walk", ["tq8-blocks", "tq8-tiles", "tq1-decode"])
@pytest.mark.parametrize("h,window", [(48, None), (72, 512)],
                         ids=["full-g6", "sliding-g9"])
def test_ragged_kernels_compile_at_groups_of_six_and_nine(on_v5e, h, window,
                                                          walk):
    """Laguna's two kinds of attention layer: 6 and 9 query heads to a kv
    head, neither a multiple of the 8-row sublane tile every accepted
    configuration's group is (4, 8) or divides (2) — the block kernel at a
    16,384-token tick, the tile kernel with the tile 72 heads leave room
    for (32 tokens: 288 and 192 score rows a kv head), and the decode call
    (one query a row: 6 and 9 score rows a kv head), with the shared walk
    in the full layers and the window's first page in the sliding ones."""
    S = on_v5e
    tq = 1 if walk == "tq1-decode" else 8
    tb = 8 if tq == 1 else 16384
    pool = S((LAYERS, N_PAGES, PAGE, 8 * HD), jnp.bfloat16)
    args = [S((tb, h, HD), jnp.bfloat16), pool, pool,
            S((8, 128), jnp.int32), S((4, tb // tq), jnp.int32),
            S((), jnp.int32)]
    kw = {}
    tile = pa.ragged_tile(72, HD, 8)
    assert tile == 32
    if walk == "tq8-tiles":
        args.append(S((6, pa.ragged_tile_slots(tb // tq, 8, tq, tile)),
                      jnp.int32))
        kw = dict(tile=tile)
    elif tq == 1 and window is None:
        args.append(S((2 + pa.SHARED_ROWS, 8), jnp.int32))

    def fn(q, k, v, tables, meta, layer, plan=None):
        name = "tiles" if walk == "tq8-tiles" else "shared"
        return pa.ragged_attend(q, k, v, tables, meta, layer, tq=tq,
                                sliding_window=window, **{name: plan}, **kw)
    compiles(fn, *args)


def _window_programs(S, monkeypatch, cfg, tb=256, width=8, rows=8,
                     max_seq=16384):
    """``_hybrid_programs`` for a model with a window group beside the
    full one: pools, tables and write slots are PAIRS."""
    from quoracle_tpu.models.generate import GenerateEngine, RAGGED_TQ
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(), max_seq=max_seq)
    st = eng.sessions
    pools = tuple(S((layers, n, st.page, cfg.kv_pools[0]), eng.pool_dtype)
                  for (_, layers), n in zip(
                      cfg.kv_groups, (st.n_pages, st.window.n_pages)))
    R, i32, f32 = rows, jnp.int32, jnp.float32
    slots = pa.ragged_tile_slots(tb // RAGGED_TQ, R, RAGGED_TQ,
                                 eng._ragged_tile)
    chunk = eng._step_paged_ragged.lower(
        params, pools, pools, None, None, S((tb,), i32), S((tb,), i32),
        (S((R, width), i32),) * 2, S((4, tb // RAGGED_TQ), i32),
        S((6, slots), i32), (S((tb,), i32),) * 2, S((R,), i32), None, None,
        tq=RAGGED_TQ, tile=eng._ragged_tile).compile()
    decode = eng._step_paged_decode_ragged.lower(
        params, pools, pools, None, None, (S((R, width), i32),) * 2,
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32), S((R,), i32),
        S((R, cfg.vocab_size), f32), S((2,), jnp.uint32), S((R,), f32),
        S((R,), f32), S((R,), jnp.bool_), S((R,), i32), None, None, None,
        max_new=32).compile()
    return [(c.as_text(), c.memory_analysis()) for c in (chunk, decode)], st


def _narrow_window_moe(periods):
    """Laguna's layer pattern, head geometry (8 kv heads of 128, 6 and 9
    query heads to each) and window under narrow weights."""
    from quoracle_tpu.models.config import AttnKind, ModelConfig, MoEConfig
    return ModelConfig(
        name=f"narrow-window-moe-{periods}", vocab_size=512, dim=256,
        n_layers=1 + 4 * periods, n_heads=48, n_kv_heads=8, head_dim=128,
        ffn_dim=512, norm_eps=1e-6,
        layer_types=("full_attention",) + (
            ("sliding_attention",) * 3 + ("full_attention",)) * periods,
        attn_kinds=(("full_attention", AttnKind(
            48, None, 500000.0, ("yarn", 128.0, 32.0, 1.0, 8192, 1.0, 0.0),
            64)), ("sliding_attention", AttnKind(72, 512, 10000.0))),
        attn_gate=True,
        moe=MoEConfig(n_routed=64, n_held=8, per_token=10, expert_dim=128,
                      n_shared=1, routed_scale=2.5, first_dense=1),
        context_window=16384)


def test_window_programs_carry_both_groups_in_place(on_v5e, monkeypatch):
    """A model with a window group beside the full one on the v5e: both
    programs carry BOTH groups' K/V pools through the segment scans — and
    the decode loop — in place, donated into their outputs: an attention
    kernel a layer of the period (three sliding, one full) and the leading
    layer's, a grouped-experts kernel an expert layer, nothing that moves a
    layer of any pool. The scan over whole periods holds: two periods and
    three give the same kernels and fusions."""
    counts = []
    for periods in (2, 3):
        cfg = _narrow_window_moe(periods)
        assert cfg.kv_groups == ((None, 1 + periods), (512, 3 * periods))
        programs, st = _window_programs(on_v5e, monkeypatch, cfg)
        elems = [n * st.page * 1024 for n in (st.n_pages, st.window.n_pages)]
        assert elems[0] != elems[1]
        for hlo, mem in programs:
            calls = [ln for ln in hlo.splitlines()
                     if "tpu_custom_call" in ln]
            assert sum("%ragged_attend" in c for c in calls) == 5
            assert sum("%routed_experts_ffn" in c for c in calls) == 4
            assert len(calls) == 9 and "kv_layout" not in hlo
            for e in elems:
                assert pool_moves(hlo, e) == []
            assert mem.alias_size_in_bytes >= 2 * 2 * (
                (1 + periods) * elems[0] + 3 * periods * elems[1])
        counts.append([len(re.findall(r" fusion\(", hlo))
                       for hlo, _ in programs])
    assert counts[0] == counts[1]


@pytest.mark.slow
def test_window_programs_at_laguna_widths(on_v5e, monkeypatch):
    """The benchmark's `laguna-s-2.1-ep8-l13` at its published widths (`-m
    slow`: a minute; run by hand before chip time): the chunk forward at
    the first agent's 16,384-token tick and the decode program compile,
    both groups' pools in place, no layer's weight copied through HBM in
    the decode loop, and arguments plus temporaries under 13 GiB of the
    chip's 16 (a long tick's experts go MOE_TICK tokens at a time)."""
    from benchmark import configs
    from benchmark.families import window_moe
    cfg = get_model_config(window_moe.register(
        configs.load_config("laguna-s-2.1-ep8-l13")))
    programs, st = _window_programs(on_v5e, monkeypatch, cfg, tb=16384,
                                    width=128, max_seq=131072)
    assert (st.n_pages, st.window.n_pages) == (513, 229)
    for hlo, mem in programs:
        assert hlo.count("tpu_custom_call") == 9
        for n in (513, 229):
            assert pool_moves(hlo, n * st.page * 1024) == []
        assert mem.alias_size_in_bytes >= 2 * st.page * 1024 * 2 * (
            4 * 513 + 9 * 229)
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < 13 * 2 ** 30)
    assert weight_moves(programs[1][0], 1 << 20) == []


# --- every layer's experts behind a softmax router (ISSUE 41) -----------------

@pytest.mark.parametrize("blk,nb", [(16, 72), (256, 192)],
                         ids=["decode-16", "prefill-256"])
def test_grouped_experts_compile_at_mellum_widths(on_v5e, blk, nb):
    """``grouped_ffn`` at a model 2,304 wide and experts 896 wide — the
    first ``dim`` that is no multiple of 512, the first width no slice of
    ``WIDTH_SLICES`` but the narrowest divides — at a decode step's block
    height and a long tick's, with the slice ``width_slice`` keeps for it;
    the accepted widths keep theirs (512 lanes: ``lfm2``'s 1,536 and
    ``laguna``'s 1,024 lower the programs they had)."""
    from quoracle_tpu.ops import grouped_experts as ge
    assert ge.width_slice(1536) == 512 and ge.width_slice(1024) == 512
    assert ge.width_slice(896, 2304) == 896 and ge.width_slice(2048) == 512
    # ... and a model too wide for an expert whole keeps the slices
    assert ge.width_slice(896, 7168) == 128 and ge.width_slice(128) == 128
    S, bf = on_v5e, jnp.bfloat16
    D, F, E, L = 2304, 896, 64, 3
    compiled = ge.grouped_ffn.lower(
        S((nb, blk, D), bf), S((L, E, D, F), bf), S((L, E, D, F), bf),
        S((L, E, F, D), bf), S((), jnp.int32), S((nb,), jnp.int32),
        S((), jnp.int32), act=jax.nn.silu).compile()
    assert "routed_experts_ffn" in compiled.as_text()


def _narrow_softmax_moe(periods):
    """Mellum's layer pattern (sliding x 3 then full, no leading layer),
    head geometry (4 kv heads of 128, 8 query heads to each), window and
    router under narrow weights."""
    from quoracle_tpu.models.config import AttnKind, ModelConfig, MoEConfig
    return ModelConfig(
        name=f"narrow-softmax-moe-{periods}", vocab_size=512, dim=256,
        n_layers=4 * periods, n_heads=32, n_kv_heads=4, head_dim=128,
        ffn_dim=512, norm_eps=1e-6,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",))
        * periods,
        attn_kinds=(("full_attention", AttnKind(
            32, None, 500000.0, ("yarn", 16.0, 32.0, 1.0, 8192, 1.0, 0.0))),
            ("sliding_attention", AttnKind(32, 1024, 500000.0))),
        moe=MoEConfig(n_routed=64, n_held=64, per_token=8, expert_dim=128,
                      n_shared=0, first_dense=0, score="softmax"),
        context_window=16384)


def test_softmax_moe_programs_carry_both_groups_in_place(on_v5e,
                                                         monkeypatch):
    """The Mellum form on the v5e: no leading segment, the period's full
    layer last. Both programs carry both groups' pools in place through
    the one scan, with an attention kernel and a grouped-experts kernel a
    layer of the period and nothing else; two periods and three give the
    same kernels and fusions."""
    counts = []
    for periods in (2, 3):
        cfg = _narrow_softmax_moe(periods)
        assert cfg.kv_groups == ((None, periods), (1024, 3 * periods))
        assert cfg.layer_plan[0] == ((), 0) and cfg.layer_plan[2] == ((), 0)
        programs, st = _window_programs(on_v5e, monkeypatch, cfg)
        elems = [n * st.page * 512 for n in (st.n_pages, st.window.n_pages)]
        for hlo, mem in programs:
            calls = [ln for ln in hlo.splitlines()
                     if "tpu_custom_call" in ln]
            assert sum("%ragged_attend" in c for c in calls) == 4
            assert sum("%routed_experts_ffn" in c for c in calls) == 4
            assert len(calls) == 8 and "kv_layout" not in hlo
            for e in elems:
                assert pool_moves(hlo, e) == []
            assert mem.alias_size_in_bytes >= 2 * 2 * (
                periods * elems[0] + 3 * periods * elems[1])
        # a layer's expert stack (64 x 3 matrices, 12 MiB here) is read at
        # [layer, expert] by the kernel, never copied through HBM
        assert weight_moves(programs[1][0], 1 << 20) == []
        counts.append([len(re.findall(r" fusion\(", hlo))
                       for hlo, _ in programs])
    assert counts[0] == counts[1]


@pytest.mark.slow
def test_softmax_moe_programs_at_mellum_widths(on_v5e, monkeypatch):
    """The benchmark's `mellum2-12b-a2.5b-l12` at its published widths (`-m
    slow`: a minute; run by hand before chip time): the chunk forward at
    the 4,096-token tick and the decode program compile, both groups'
    pools in place, NO copy of a layer's expert stack (64 x 12.4 MB a
    matrix triple) or of any other layer's weight through HBM in the
    decode loop, and arguments plus temporaries under 14 GiB of the
    chip's 16."""
    from benchmark import configs
    from benchmark.families import window_moe_softmax
    cfg = get_model_config(window_moe_softmax.register(
        configs.load_config("mellum2-12b-a2.5b-l12")))
    programs, st = _window_programs(on_v5e, monkeypatch, cfg, tb=4096,
                                    width=128, max_seq=131072)
    assert (st.n_pages, st.window.n_pages) == (1367, 457)
    for hlo, mem in programs:
        assert hlo.count("tpu_custom_call") == 8
        for n in (1367, 457):
            assert pool_moves(hlo, n * st.page * 512) == []
        assert mem.alias_size_in_bytes >= 2 * st.page * 512 * 2 * (
            3 * 1367 + 9 * 457)
        print("mellum AOT: arguments", mem.argument_size_in_bytes,
              "temporaries", mem.temp_size_in_bytes)
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < 14 * 2 ** 30)
    assert weight_moves(programs[1][0], 1 << 20) == []


# --- Mamba-2 layers beside attention and ungated experts (ISSUE 47) -----------

def test_ssm_scan_compiles_at_nemotron_widths(on_v5e):
    """The scan kernel at the published widths (64 heads of 64 x 128 in 8
    groups, chunks of 128) over the 40 chunks a 4,096-token tick of 8 rows
    lays out: a grid program is one chunk of one group's 8 heads, the
    group's state [512, 128] float32 in VMEM scratch."""
    from quoracle_tpu.ops import ssm_scan as sc
    S, bf, f = on_v5e, jnp.bfloat16, jnp.float32
    NC, Q, H, P, G, N, R = 40, 128, 64, 64, 8, 128, 8
    compiled = jax.jit(sc.ssm_scan).lower(
        S((NC, Q, H, P), bf), S((NC, Q, H), f), S((H,), f),
        S((NC, Q, G, N), bf), S((NC, Q, G, N), bf), S((R, H, P, N), f),
        S((NC,), jnp.int32), S((NC,), jnp.int32)).compile()
    assert "%ssm_scan" in compiled.as_text()


@pytest.mark.parametrize("blk,nb", [(16, 72), (256, 80)],
                         ids=["decode-16", "prefill-256"])
def test_ungated_grouped_experts_compile_at_nemotron_widths(on_v5e, blk, nb):
    """``grouped_ffn`` with no gate matrix at a model 2,688 wide and
    experts 1,856 wide: 1,856 is no multiple of 128 lanes, so both
    matrices lie ``[F, D]`` and the width is cut by ROWS, 928 a grid
    program (two matrices of 5 MB twice over)."""
    from quoracle_tpu.ops import grouped_experts as ge
    assert ge.rows_slice(1856, 2688) == 928 and ge.rows_slice(32, 64) == 32
    S, bf = on_v5e, jnp.bfloat16
    D, F, E, L = 2688, 1856, 64, 2
    compiled = ge.grouped_ffn.lower(
        S((nb, blk, D), bf), None, S((L, E, F, D), bf), S((L, E, F, D), bf),
        S((), jnp.int32), S((nb,), jnp.int32), S((), jnp.int32),
        act=tr_relu2()).compile()
    assert "routed_experts_ffn" in compiled.as_text()


def tr_relu2():
    from quoracle_tpu.models.transformer import _ACTIVATIONS
    return _ACTIVATIONS["relu2"]


def _mamba_programs(S, monkeypatch, cfg, tb, width, rows=8):
    """Both serving programs of a model with ssm layers, compiled for the
    v5e with donation as served."""
    from quoracle_tpu.models.generate import RAGGED_TQ, GenerateEngine
    from quoracle_tpu.models.tokenizer import ByteTokenizer
    from quoracle_tpu.models.transformer import init_params
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    eng = GenerateEngine(cfg, params, ByteTokenizer(),
                         max_seq=min(cfg.context_window, 131072))
    st = eng.sessions
    m, R, i32, f32 = cfg.ssm, rows, jnp.int32, jnp.float32
    kv = S((cfg.n_attn_layers, st.n_pages, st.page, cfg.kv_pools[0]),
           eng.pool_dtype)
    n_rec = cfg.n_ssm_layers * st.records.n_ids
    state = (S((n_rec, m.d_inner, m.state_dim), f32),
             S((n_rec, (m.conv_kernel - 1) * m.conv_dim), eng.pool_dtype))
    slots = pa.ragged_tile_slots(tb // RAGGED_TQ, R, RAGGED_TQ,
                                 eng._ragged_tile)
    nc = tb // m.chunk + R
    tick = (S((R,), i32), S((R,), i32), S((tb, m.conv_kernel - 1), i32),
            S((R,), i32), S((nc * m.chunk,), i32), S((tb,), i32),
            S((nc,), i32), S((nc,), i32)) + (S((R,), i32),) * 4 \
        + (S((1,), i32),)
    chunk = eng._step_paged_ragged.lower(
        params, kv, kv, None, None, S((tb,), i32), S((tb,), i32),
        S((R, width), i32), S((4, tb // RAGGED_TQ), i32),
        S((6, slots), i32), S((tb,), i32), S((R,), i32), state, tick,
        tq=RAGGED_TQ, tile=eng._ragged_tile).compile()
    decode = eng._step_paged_decode_ragged.lower(
        params, kv, kv, None, None, S((R, width), i32),
        S((2 + pa.SHARED_ROWS, R), i32), S((R,), i32), S((R,), i32),
        S((R, cfg.vocab_size), f32), S((2,), jnp.uint32), S((R,), f32),
        S((R,), f32), S((R,), jnp.bool_), S((R,), i32), None, None, state,
        S((R,), i32), max_new=32).compile()
    return [(c.as_text(), c.memory_analysis()) for c in (chunk, decode)], eng


def records_stay(hlo: str, mem, pool_elems: int, temps: bool = True) -> bool:
    """The ssm record pool (``pool_elems`` float32 values) is carried in
    place: donated into the program's result, no gather of it that copies
    it whole (`mini-gather-slice`: what the compiler made of ``pool[ids]``
    for a few megabyte-sized rows), and (``temps``: where the program's
    activations are smaller than the pool) no temporaries as large as it
    — a row's record is read by a dynamic slice and written by a dynamic
    update, which ``pool_moves`` cannot tell from a move by name."""
    return ("mini-gather" not in hlo
            and (not temps or mem.temp_size_in_bytes < 4 * pool_elems)
            and mem.alias_size_in_bytes >= 4 * pool_elems)


def _narrow_mamba(periods):
    """Nemotron-H's layer pattern (`MEMEM*E`), head geometry (2 kv heads of
    128 under 16 query heads each; 64 Mamba heads of 64 in 8 groups, 128
    state values) and record size under a narrow residual stream."""
    from quoracle_tpu.models.config import ModelConfig, MoEConfig, SSMConfig
    pat = "MEMEM*E" * periods
    return ModelConfig(
        name=f"narrow-mamba-{periods}", vocab_size=512, dim=256,
        n_layers=len(pat), n_heads=32, n_kv_heads=2, head_dim=128,
        ffn_dim=128, activation="relu2", rope=False, state_records=24,
        layer_types=tuple({"M": "ssm", "*": "attention", "E": None}[c]
                          for c in pat),
        ff_types=tuple("experts" if c == "E" else None for c in pat),
        ssm=SSMConfig(n_heads=64, head_dim=64, n_groups=8, state_dim=128),
        moe=MoEConfig(n_routed=16, n_held=8, per_token=6, expert_dim=128,
                      n_shared=1, shared_dim=256, gated=False,
                      routed_scale=2.5, router_bias=True, first_dense=0))


def test_mamba_programs_carry_pools_and_records_in_place(on_v5e,
                                                         monkeypatch):
    """A model with ssm layers on the v5e: both programs carry the K/V
    pools AND the two record pools through the segment scans — and the
    decode loop — in place; a row's record is read by a dynamic slice and
    written by a dynamic update (the compiler's gather of such rows first
    copied the pool whole: `mini-gather-slice`); the kernels are the
    attention's, the experts' and the scan's — in the decode step, whose
    scan holds every repeat of the period, the decode kernel's; and the
    scan over whole periods holds at two periods and at three."""
    counts = []
    for periods in (2, 3):
        cfg = _narrow_mamba(periods)
        programs, eng = _mamba_programs(on_v5e, monkeypatch, cfg, 256, 8)
        st = eng.sessions
        assert eng._ragged_tile == 64     # 16 query heads a kv head
        rec_elems = cfg.n_ssm_layers * st.records.n_ids * 4096 * 128
        for i, (hlo, mem) in enumerate(programs):
            calls = [ln for ln in hlo.splitlines()
                     if "tpu_custom_call" in ln]
            # the chunk forward scans the period; the decode step holds
            # every repeat of it (no loop inside the decode loop)
            n = 1 if i == 0 else periods
            assert sum("%ragged_attend" in c for c in calls) == n
            assert sum("%routed_experts_ffn" in c for c in calls) == 3 * n
            # the chunk forward's scan, the decode step's fused kernel
            assert sum("%ssm_scan" in c for c in calls) == (3 if i == 0
                                                            else 0)
            assert sum("%ssm_decode" in c for c in calls) == (0 if i == 0
                                                              else 3 * n)
            assert pool_moves(hlo, st.n_pages * st.page * 256) == []
            assert records_stay(hlo, mem, rec_elems)
        counts.append([len(re.findall(r" fusion\(", hlo))
                       for hlo, _ in programs])
    # the chunk forward's size follows the period, the decode program's
    # the depth
    assert counts[0][0] == counts[1][0]
    assert 1.3 < counts[1][1] / counts[0][1] < 1.6


@pytest.mark.slow
def test_mamba_programs_at_nemotron_widths(on_v5e, monkeypatch):
    """The benchmark's `nemotron-3-nano-30b-a3b-ep2-l14` at its published
    widths (`-m slow`: a minute; run by hand before chip time): the chunk
    forward at the 4,096-token tick and the decode program compile, pools
    and records in place, no stack of expert weights copied into a padded
    layout (1.2 GiB each before the up matrices lay [F, D]), and
    arguments plus temporaries under 13 GiB of the chip's 16."""
    from benchmark import configs
    from benchmark.families import mamba_moe
    cfg = get_model_config(mamba_moe.register(
        configs.load_config("nemotron-3-nano-30b-a3b-ep2-l14")))
    programs, eng = _mamba_programs(on_v5e, monkeypatch, cfg, 4096, 32)
    st = eng.sessions
    assert (st.n_pages, st.records.n_ids) == (8193, 48)
    for hlo, mem in programs:
        assert pool_moves(hlo, st.n_pages * st.page * 256) == []
        # (the 4,096-token tick's activations outweigh the pool)
        assert records_stay(hlo, mem, 6 * 48 * 4096 * 128,
                            temps="decode" in hlo[:60])
        print("nemotron AOT: arguments", mem.argument_size_in_bytes,
              "temporaries", mem.temp_size_in_bytes)
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < 13 * 2 ** 30)


# --- tp wrappers: shard_map around a pallas_call ----------------------------


@pytest.fixture(scope="module")
def tp_case(eight_devices):
    mesh = Mesh(np.array(eight_devices[:2]).reshape(1, 2), ("dp", "tp"))
    rng = np.random.default_rng(0)
    h, kv, hd, page, n_pages = 4, 2, 32, 8, 9

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return {
        "mesh": mesh, "h": h, "kv": kv, "hd": hd,
        "kp": arr(n_pages, page, kv, hd), "vp": arr(n_pages, page, kv, hd),
        # the same pages as layer 1 of a stored 2-layer pool
        "stored": lambda a: jnp.stack([jnp.zeros_like(a), a]).reshape(
            2, n_pages, page, kv * hd),
        "tables": jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32),
        "arr": arr,
    }


@pytest.mark.parametrize("tile", [0, 16], ids=["blocks", "tiles"])
def test_ragged_tp_wrapper_runs_the_kernel_under_shard_map(tp_case, tile):
    """Per tp shard under ``shard_map``: one program a block, and (the
    chunk forward's call) a tile, whose table replicates like the block
    table beside it."""
    c = tp_case
    q = c["arr"](32, c["h"], c["hd"])
    meta = np.asarray([[28, 28, 28, 9], [4, 12, 20, 8], [8, 8, 8, 1],
                       [0, 0, 0, 1]], np.int32)
    args = (q, c["stored"](c["kp"]), c["stored"](c["vp"]), c["tables"],
            jnp.asarray(meta), jnp.asarray(1, jnp.int32))
    tiles = jnp.asarray(pa.ragged_tiles(meta, 8, tile)) if tile else None
    ref = pa.ragged_attend_ref(*args, tq=8)
    out = jax.jit(lambda *a, tiles: pa.ragged_attend_auto(
        *a, tq=8, interpret=True, shard=(c["mesh"], "tp"), tile=tile,
        tiles=tiles))(*args, tiles=tiles)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_shared_walk_replicates_under_shard_map(tp_case):
    """The decode call per tp shard: the shared-walk table replicates like
    the page tables, and each shard walks the common pages once for its
    own heads of both rows."""
    c = tp_case
    n = pa.SHARED_MIN_PAGES
    assert n + 2 <= 8                       # the case's pool: pages 1..8
    tables = np.zeros((2, n + 1), np.int32)
    tables[:, :n] = np.arange(1, n + 1)
    tables[:, n] = (n + 1, n + 2)
    lens = np.asarray([n * 8 + 3, n * 8 + 7], np.int32)
    shared = pa.shared_walks(tables, lens, 8)
    assert shared[:2].tolist() == [[n, n], [1, 0]]
    meta = np.stack([lens + 1, lens, [1, 1], [0, 1]]).astype(np.int32)
    args = (c["arr"](2, c["h"], c["hd"]), c["stored"](c["kp"]),
            c["stored"](c["vp"]), jnp.asarray(tables), jnp.asarray(meta),
            jnp.asarray(1, jnp.int32))
    ref = pa.ragged_attend_ref(*args, tq=1)
    out = jax.jit(lambda *a, shared: pa.ragged_attend_auto(
        *a, tq=1, interpret=True, shard=(c["mesh"], "tp"),
        shared=shared))(*args, shared=jnp.asarray(shared))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_dispatcher_runs_the_kernel_under_shard_map(tp_case):
    """A mesh engine's long prefill chunk: GSPMD cannot partition a Mosaic
    kernel, so attend_auto lays it over the mesh itself."""
    from quoracle_tpu.ops.attention import attend
    c = tp_case
    q = c["arr"](2, 256, c["h"], c["hd"])
    k, v = c["arr"](2, 384, c["kv"], c["hd"]), c["arr"](2, 384, c["kv"],
                                                        c["hd"])
    q_pos = jnp.broadcast_to(128 + jnp.arange(256, dtype=jnp.int32), (2, 256))
    kv_len = jnp.asarray([384, 300], jnp.int32)
    ref = attend(q, k, v, q_pos, kv_len, sliding_window=100)
    out = jax.jit(lambda *a: fa.attend_auto(
        *a, sliding_window=100, interpret=True,
        shard=(c["mesh"], "tp", None)))(q, k, v, q_pos, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)
