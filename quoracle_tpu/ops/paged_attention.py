"""Ragged paged attention (PAPERS.md: Ragged Paged Attention,
arxiv 2604.15464 — pattern only, the kernels are written here for the
engine's page-pool layout): ``ragged_attend`` serves a token-major
flattened batch of mixed prefill+decode rows in ONE launch per layer —
a program per 8-token block, or per TILE of up to 128 of a row's tokens
that read its pages once between them (the chunk forward's call) —
``ragged_attend_latent`` the same contract over a latent (MLA) pool,
with a per-query selection where the model has an indexer, whose scores
``index_scores`` streams from a pool of its own; in the decode program
both take the dense kernel's shared-walk table and read a group's common
pages once: see the section comments below and ARCHITECTURE.md §10.

The paged KV session cache (models/generate.py SessionStore) keeps every
resident conversation as a PAGE LIST into one device pool. The gather
programs index each batch row's pages into a contiguous working cache
([B, maxp·page, ...] materialized in HBM) and attend over the PADDED
length. Here attention reads the pool directly: the Pallas kernel walks
each row's page table and streams only ceil(kv_len/page) pages through
VMEM (HBM DMA, copies running ahead) — work is RAGGED, proportional to each
row's real length, not the batch max — and since the forward scatters a
chunk's KV to its pages BEFORE attention, every key a query can see is
already there: no tail buffer, no dense intra-chunk piece, no partials
to merge (SURVEY §7 hard part 2).

Each kernel has an XLA gather reference (``*_ref``): the tests' oracle
and, off the TPU, the serving kernel (``*_auto`` dispatches).

No reference counterpart: the reference never executes attention
(SURVEY.md §2.8 — all inference was remote HTTPS).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _on_tpu() -> bool:
    """Whether the dispatchers below pick the Pallas kernels (else the
    XLA gather references). One seam: an AOT compile for a described TPU
    runs under the CPU backend and steers it from the test."""
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Unified RAGGED kernel (ISSUE 8): mixed prefill+decode in ONE launch
# ---------------------------------------------------------------------------
#
# Token-major flattened batch: the caller lays every row's query tokens out
# contiguously in one [Tp, H, hd] array, each row's segment padded to a
# multiple of ``tq`` tokens so a tq-token BLOCK never spans two rows. The
# grid is (Tp // tq,): one program per block, so device work is
# proportional to the tick's real tokens (rounded per row to tq), never to
# a [B, T_max] rectangle. Scalar-prefetched metadata: one page table per
# ROW (``row_tables [R, maxp]``) and four ints per block, block-minor so
# the SMEM copy pads 4 → 8 sublanes instead of 3 → 128 lanes:
#
#   block_meta[:, i] = (kv_len, qpos0, nq, row)
#     kv_len  row's valid KV tokens in its pages INCLUDING this chunk's
#             queries (the layer scatters chunk KV to pages BEFORE the
#             attention call — intra-chunk causality is pure masking);
#     qpos0   buffer position of the block's first query
#             (= kv_len_row - q_len_row + block_offset_in_row);
#     nq      valid queries in this block (0 = inert padding block);
#     row     index of the owning row's table in ``row_tables``.
#
# Because every key the block can see — resident prefix, earlier chunk
# tokens, its own tokens — already sits in the pages, there is no
# tail/chunk partial to merge: the kernel streams only the row's real
# ceil(visible/page) pages through VMEM (copies running ahead, kv heads
# flattened into lanes so every Mosaic memref slice stays (8, 128)-tiled
# for ANY head count; KV = 14 is not sublane-tileable) and normalizes the
# online-softmax accumulator in-kernel. T=1 decode rows, T=chunk
# continuation rows, T=suffix prefill rows and T=K speculative-verify
# rows are just blocks with different (qpos0, nq) — one program shape
# serves the whole mixed tick.
#
# The pools arrive WHOLE and in their stored layout, [L, n_pages, page,
# KV·hd] (generate.py ``_ensure_pool``), with the layer as one more
# prefetched scalar: the kernel's DMA source is ``pool.at[layer, pid]``, so
# no slice, reshape or copy of a layer's pool stands between the store and
# the kernel (PERF.md §6, PR 25).


def ragged_attend_ref(
    q: jax.Array,            # [NB·tq, H, hd] token-major flattened queries
    k_pool: jax.Array,       # [L, n_pages, page, KV·hd] — the stored pool
    v_pool: jax.Array,
    row_tables: jax.Array,   # [R, maxp] int32 — one page table per row
    block_meta: jax.Array,   # [4, NB] int32: kv_len, qpos0, nq, row
    layer,                   # int32 scalar: which layer's pages to read
    tq: int,
    sliding_window: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,   # [L, n_pages, KV, page] f32
    v_scale: Optional[jax.Array] = None,   # (int8 pools, ISSUE 13)
) -> jax.Array:
    """XLA gather reference for the unified ragged kernel (CPU serving
    path + the kernel's numerical oracle). Same contract: normalized
    output [NB·tq, H, hd] f32. With ``k_scale``/``v_scale`` the pools
    are int8 and the gathered pages dequantize per (token, kv-head)
    before the scores — the dequantize-then-attend twin of the
    kernel's in-loop dequant."""
    kv_len, qpos0, nq, row = (block_meta[j][:, None, None]   # [NB,1,1]
                              for j in range(4))
    block_tables = row_tables[row[:, 0, 0]]                  # [NB, maxp]
    NB, maxp = block_tables.shape
    _, H, hd = q.shape
    _, n_pages, page, lanes = k_pool.shape
    KV = lanes // hd
    G = H // KV
    qb = (q.astype(jnp.float32) * hd ** -0.5).reshape(NB, tq, KV, G, hd)
    k = k_pool[layer, block_tables].reshape(NB, maxp * page, KV, hd)
    v = v_pool[layer, block_tables].reshape(NB, maxp * page, KV, hd)
    if k_scale is not None:
        from quoracle_tpu.models.quant import gather_scales
        k = k.astype(jnp.float32) \
            * gather_scales(k_scale[layer], block_tables)[..., None]
        v = v.astype(jnp.float32) \
            * gather_scales(v_scale[layer], block_tables)[..., None]
    scores = jnp.einsum("btkgd,bskd->bkgts", qb, k.astype(jnp.float32))
    t_idx = jnp.arange(tq, dtype=jnp.int32)[None, :, None]
    s_idx = jnp.arange(maxp * page, dtype=jnp.int32)[None, None, :]
    qpos = qpos0 + t_idx                           # [NB,tq,1]
    mask = (s_idx < kv_len) & (s_idx <= qpos) & (t_idx < nq)
    if sliding_window is not None:
        mask = mask & (qpos - s_idx < sliding_window)
    mask = mask[:, None, None, :, :]               # [NB,1,1,tq,S]
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(jnp.broadcast_to(mask, scores.shape),
                  jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1)                        # [NB,KV,G,tq]
    acc = jnp.einsum("bkgts,bskd->bkgtd", p, v.astype(jnp.float32))
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(NB * tq, H, hd)
    return out


def _attend_page(q, k, v, ks, vs, valid, m, l, acc):
    """One page of one kv head's online softmax in the tile kernel (the
    block kernel updates the same state once a BLOCK of pages,
    ``_attend_block``): ``q`` [rows, hd] scaled float32 queries (query-major,
    the head's G query heads a token), ``valid`` [rows, page]; ``k``/``v``
    give the page's [page, hd] float32 blocks and ``ks``/``vs`` an int8
    page's [1, page] scales (else None), each a function called where its
    value is used: one made early would live across the softmax. ``valid``
    None: every row sees the whole page (a shared walk's pages). Returns
    the updated (m, l, acc)."""
    scores = jax.lax.dot_general(                        # [rows, page]
        q, k(), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if ks is not None:
        scores = scores * ks()                           # dequant K
    if valid is not None:
        scores = jnp.where(valid, scores, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
    p = jnp.exp(scores - m_new)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    if vs is not None:
        p = p * vs()                                     # dequant V
    pv = jax.lax.dot_general(                            # [rows, hd]
        p, v(), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc * corr + pv


def _ragged_kernel(tables_ref, meta_ref, layer_ref, *refs, page: int,
                   n_kv: int, hd: int, tq: int, scale: float, window: int,
                   quant: bool, block: int, shared: bool = False):
    """One tq-token block of the flattened batch: stream the owning row's
    VISIBLE pages through VMEM (kv heads flattened into the lane dim) and
    write the NORMALIZED attention output for the block. With the chunk KV
    already scattered into the pages there is no second partial to merge,
    so the online-softmax accumulator normalizes in-kernel.

    The walk advances ``block`` PAGES a loop iteration (``_walk_blocks``,
    section "The BLOCK walk" below): the scratch of a stream holds two
    blocks, ``[2·block, page, KV·hd]`` with a DMA semaphore a slot, and an
    iteration starts the next block's copies before it attends its own —
    every page's scores first, then ONE update of the float32 softmax
    state for the block (``_attend_block``; its score tiles wait in the
    last scratch, ``[KV, block, rows, page]``). Same operands, same
    products, float32 throughout; ``block`` = 1 is the walk of one page
    an iteration and one state update a page.

    Scalar-prefetched (SMEM): tables_ref [R, maxp] one page table per ROW,
    meta_ref [4, NB] per-block (kv_len, qpos0, nq, row), layer_ref [1] the
    layer whose pages to stream — the pools stay in HBM WHOLE,
    [L, n_pages, page, KV·hd], and a page's DMA source is
    ``pool.at[layer, pid]``. A per-block copy of the table, or a [NB, 3]
    meta (SMEM pads the minor dim to 128 lanes), overflows the v5e's
    1 MiB of SMEM at an 8k-token tick.

    ``quant`` (int8 pools, ISSUE 13): each page's fp32 scale block
    ``[KV, page]`` rides the SAME stream of copies, slot for slot, and the
    dequant happens inside the streaming loop with zero lane transposes:
    K's per-token scale multiplies the score columns
    (``q·(k·s) = (q·k)·s``) and V's multiplies the probability columns
    (``(p·s)·v = p·(v·s)``), both as a ``[1, page]`` lane broadcast.

    ``shared`` (the decode program, tq = 1, block i = row i): one more
    prefetched table, ``shared_ref [2 + SHARED_ROWS, R]`` (``shared_walks``),
    q a second time whole in HBM, and the shared walk's scratch; see the
    section "The SHARED walk" below.

    A walk's FIRST block is started by the walk before it (section "The
    walk started ahead"): ``ahead_ref`` (SMEM scratch, [2]) carries from
    one grid program to the next which half of the scratch the next walk
    begins in and which program's first walk is in flight."""
    if shared:
        shared_ref, q_ref, q_hbm, k_hbm, v_hbm, *refs = refs
    else:
        q_ref, k_hbm, v_hbm, *refs = refs
    if quant:
        ks_hbm, vs_hbm, out_ref, k_scr, v_scr, ks_scr, vs_scr, sems, \
            ahead_ref, *walk_scr = refs
        streams = ((k_hbm, k_scr), (v_hbm, v_scr),
                   (ks_hbm, ks_scr), (vs_hbm, vs_scr))
    else:
        out_ref, k_scr, v_scr, sems, ahead_ref, *walk_scr = refs
        streams = ((k_hbm, k_scr), (v_hbm, v_scr))
    s_scr = walk_scr[-1]                 # a block's score tiles
    i = pl.program_id(0)
    last = meta_ref.shape[1] - 1
    kv_len = meta_ref[0, i]
    qpos0 = meta_ref[1, i]
    nq = meta_ref[2, i]
    layer = layer_ref[0]
    H = q_ref.shape[2]
    G = H // n_kv
    # a shared walk's pages are a row's FIRST: under a window, whose first
    # page differs by row, nothing is shared, whatever the table
    sharing = shared and window < 0

    def page_dmas(row, j, slot):
        pid = tables_ref[row, j]
        return [pltpu.make_async_copy(hbm.at[layer, pid], scr.at[slot],
                                      sems.at[slot, s])
                for s, (hbm, scr) in enumerate(streams)]

    # Every walk of the call is known to every program: the tables are
    # scalars in SMEM, program j's as readable from program i as its own.
    def own_walk(j):
        """(row, first page, pages) of program j's walk of ITS row's
        pages: those its last query sees, behind the pages a shared walk
        covers for it; a program with no query (padding; a row that is
        done) walks nothing."""
        kv_len, qpos0, nq, row = (meta_ref[a, j] for a in range(4))
        # last visible key + 1: nothing past the block's last query is
        kv_hi = jnp.minimum(kv_len, qpos0 + nq)
        if window >= 0:
            p_lo = jnp.maximum(qpos0 + 1 - window, 0) // page
        elif shared:
            p_lo = shared_ref[0, j]
        else:
            p_lo = jnp.int32(0)
        n = jnp.where(nq > 0,
                      jnp.maximum((kv_hi + page - 1) // page - p_lo, 0), 0)
        return row, p_lo, n

    def shared_pages(j):
        """(members, pages) of the shared walk program j makes: 0 pages
        where it leads no group, or none of its group has a query."""
        members = [shared_ref[2 + k, j] for k in range(SHARED_ROWS)]
        live = meta_ref[2, members[0]]
        for r in members[1:]:
            live = jnp.maximum(live, meta_ref[2, r])
        return members, jnp.where((shared_ref[1, j] > 0) & (live > 0),
                                  shared_ref[0, j], 0)

    def first_of(own, common):
        """(row, first page, pages) of the walk a program makes FIRST:
        its group's ``common`` pages where it walks them, else its own."""
        row, p_lo, n = own
        if not sharing:
            return own
        return (row, jnp.where(common > 0, 0, p_lo),
                jnp.where(common > 0, common, n))

    def first_walk(j):
        return first_of(own_walk(j), shared_pages(j)[1] if sharing else 0)

    def walk_dmas(walk, after):
        """``dmas(j, slot)`` of a walk of this program that starts the
        first block of the walk ``after`` it behind its own last block
        (``_walk_blocks``' ``n_after``): page j of the walk, and from the
        end of its whole blocks on the pages of ``after``."""
        row, p_lo, n = walk
        end = (n + block - 1) // block * block

        def dmas(j, slot):
            own = j < end
            return page_dmas(jnp.where(own, row, after[0]),
                             jnp.where(own, p_lo, after[1] - end) + j, slot)

        return dmas

    def page_blocks(half, kv):
        """What ``_attend_block`` reads of a block's i-th page, in scratch
        slot ``half + i``, for one kv head: (k, v, k's scales, v's
        scales), each a function of i."""
        lanes = slice(kv * hd, (kv + 1) * hd)
        return (lambda i: k_scr[half + i, :, lanes].astype(jnp.float32),
                lambda i: v_scr[half + i, :, lanes].astype(jnp.float32),
                (lambda i: ks_scr[half + i, kv:kv + 1, :]) if quant
                else None,
                (lambda i: vs_scr[half + i, kv:kv + 1, :]) if quant
                else None)

    row, p_lo, n = own = own_walk(i)
    members, common = shared_pages(i) if sharing else (None, 0)
    walks = (common > 0) | (n > 0)
    # what runs after this program's walks: the first walk of the next
    # program that has one (section "The walk started ahead"; a program
    # that walks nothing starts nothing, and does not look)
    nxt, *after = jax.lax.while_loop(
        lambda c: walks & (c[3] == 0) & (c[0] < last),
        lambda c: (c[0] + 1, *first_walk(c[0] + 1)),
        (i, jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    # ... and which half of the scratch each of its own begins in. The
    # first program with a walk starts cold, as every program used to.
    ahead = (i > 0) & (ahead_ref[1] == i)
    half_sh = jnp.where(ahead, ahead_ref[0], 0)
    half_own = jax.lax.rem(half_sh + (common + block - 1) // block, 2)
    half_after = jax.lax.rem(half_own + (n + block - 1) // block, 2)

    first_row, first_lo, first_n = first_of(own, common)
    _start_block(jnp.where(walks & ~ahead, first_n, 0), block, 0,
                 lambda j, slot: page_dmas(first_row, first_lo + j, slot),
                 half_sh)
    # (SMEM scratch holds whatever the last call left: the first program
    # reads none of it, and leaves "none started" where it starts none)
    ahead_ref[0] = jnp.where(walks, half_after, ahead_ref[0])
    ahead_ref[1] = jnp.where(walks & (after[2] > 0), nxt,
                             jnp.where(walks | (i == 0), -1, ahead_ref[1]))

    carried = None
    if sharing:
        m_st, l_st, acc_st = walk_scr[5:8]       # the rows' parked state

        @pl.when(common > 0)
        def _():
            # behind its last block the leader's own walk is next, or
            # where it has none the next program's
            then = tuple(jnp.where(n > 0, a, b) for a, b in zip(own, after))
            _shared_walk(members, common,
                         walk_dmas((row, 0, common), then), page_blocks,
                         q_hbm, walk_scr[:-1], s_scr, n_kv=n_kv, G=G,
                         scale=scale, block=block, half0=half_sh,
                         n_after=then[2])

        # pages a shared walk covers for this row: its own walk starts
        # behind them, from the state the walk left for it
        carried = (p_lo > 0) & (nq > 0)

    dmas = walk_dmas(own, after)

    q = q_ref[0].astype(jnp.float32) * scale             # [tq, H, hd]

    # per-score-row query index (tq·G rows, query-major like the prefill
    # kernel) → buffer position and validity shared by every kv head.
    # Built at its final shape: Mosaic has no [tq, G] → [tq·G, 1] cast.
    t_of_row = jax.lax.broadcasted_iota(jnp.int32, (tq * G, 1), 0) // G
    qpos = qpos0 + t_of_row                              # [tq·G, 1]
    q_ok = t_of_row < nq

    def attend(first, half, left, wait, carry):
        def valid(i):
            s_idx = (p_lo + first + i) * page + jax.lax.broadcasted_iota(
                jnp.int32, (1, page), 1)                 # [1, page]
            ok = (s_idx < kv_len) & (s_idx <= qpos) & q_ok
            if window >= 0:
                ok = ok & (qpos - s_idx < window)
            return ok

        heads = [(q[:, kv * G:(kv + 1) * G].reshape(tq * G, hd),
                  *page_blocks(half, kv)) for kv in range(n_kv)]
        return _attend_block(left, wait, heads, valid, s_scr, carry)

    def fresh():
        return (jnp.full((tq * G, 1), NEG_INF, jnp.float32),
                jnp.zeros((tq * G, 1), jnp.float32),
                jnp.zeros((tq * G, hd), jnp.float32))

    def init(kv):
        if carried is None:
            return fresh()
        # m and l are parked one value a row in all 128 lanes and come
        # back through a lane reduction, as the loop's own row maxima and
        # sums do: a column loaded as such would be re-laid every page
        # (+10% a call at Qwen's widths, +19% at Mistral's, on the chip)
        at = i * n_kv + kv
        parked = (jnp.max(m_st[at], axis=1, keepdims=True),
                  jnp.max(l_st[at], axis=1, keepdims=True), acc_st[at])
        return tuple(jnp.where(carried, st, new)
                     for st, new in zip(parked, fresh()))

    final = _walk_blocks(n, block, dmas, attend,
                         tuple(init(kv) for kv in range(n_kv)),
                         half0=half_own, n_after=after[2])
    for kv in range(n_kv):
        _, l, acc = final[kv]
        norm = acc / jnp.where(l > 0, l, 1.0)
        out_ref[0, :, kv * G:(kv + 1) * G] = norm.reshape(tq, G, hd)


@functools.partial(jax.jit, static_argnames=("tq", "sliding_window",
                                             "interpret", "tile",
                                             "walk_block"))
def ragged_attend(
    q: jax.Array,            # [NB·tq, H, hd] token-major flattened queries
    k_pool: jax.Array,       # [L, n_pages, page, KV·hd] — the stored pool
    v_pool: jax.Array,
    row_tables: jax.Array,   # [R, maxp] int32
    block_meta: jax.Array,   # [4, NB] int32: kv_len, qpos0, nq, row
    layer,                   # int32 scalar: which layer's pages to stream
    tq: int,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,   # [L, n_pages, KV, page] f32
    v_scale: Optional[jax.Array] = None,
    tiles: Optional[jax.Array] = None,     # [6, NT] int32 (ragged_tiles)
    tile: int = 0,                         # tokens a tile holds at most
    shared: Optional[jax.Array] = None,    # [2 + SHARED_ROWS, R] int32
    walk_block: Optional[int] = None,      # tests: pages a loop iteration
) -> jax.Array:
    """Pallas unified ragged attention (same contract as ragged_attend_ref;
    tests/test_ragged_attention.py asserts numerical agreement). Grid is
    (NB,) — sized by the tick's real tokens / tq, never by batch × max —
    or, with the block table grouped into ``tiles`` of up to ``tile``
    tokens, (NT,): one walk of a row's pages per tile (the tile kernel,
    section above) where a block a program walks them per tq queries (the
    decode program's call, tq = 1).
    The pools are passed whole, as stored, and stay in HBM: the kernel
    indexes ``layer`` itself, so nothing of a pool's size is sliced,
    reshaped or copied on the way in. With ``k_scale``/``v_scale`` the
    kernel streams each int8 page's scale block alongside its payload and
    dequantizes in-loop. With ``shared`` (``shared_walks`` of the tables;
    the decode program's call, tq = 1 and block i row i's) rows whose
    tables begin alike have their common pages walked once between them
    (section "The SHARED walk"): the same output, fewer page reads. The
    block kernel's walk carries ``walk_pages`` of the page's bytes a loop
    iteration (section "The BLOCK walk"), and each of its walks starts the
    first block of the walk that runs after it (section "The walk started
    ahead"); ``walk_block`` is the tests' way to the walk of one page an
    iteration, which nothing else asks for."""
    Tp, H, hd = q.shape
    NB = block_meta.shape[1]
    _, n_pages, page, lanes = k_pool.shape
    KV = lanes // hd
    quant = k_scale is not None
    layer = jnp.asarray(layer, jnp.int32).reshape(())
    pools = [k_pool, v_pool]
    if quant:
        pools += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    hd_p, pack = _lane_geometry(KV, hd)
    if pack > 1:
        # heads narrower than a lane tile (LFM2: 64): ``pack`` kv heads
        # that lie side by side in the stored row are read as ONE head of
        # 128 lanes, and a query keeps its own head's lanes and zeros in
        # its neighbours' (``_pack_queries``), so its scores are its own
        # head's and the pool is streamed as it lies, unpadded. The
        # kernel multiplies zeros in place of moving the pool.
        q = _pack_queries(q, KV, pack)
        KV //= pack
    elif hd_p != hd:
        # tiny test models only (every production head_dim is a lane
        # multiple or packs, and never comes here): the one layer the
        # call reads, its head_dim zero-padded to the lane width, read as
        # layer 0
        q = jnp.pad(q, [(0, 0), (0, 0), (0, hd_p - hd)])
        one = [jax.lax.dynamic_index_in_dim(p, layer, 0, keepdims=True)
               for p in pools]
        with jax.named_scope("kv_layout"):
            pools = [jnp.pad(p.reshape(1, n_pages, page, KV, hd),
                             [(0, 0)] * 4 + [(0, hd_p - hd)]
                             ).reshape(1, n_pages, page, KV * hd_p)
                     for p in one[:2]] + one[2:]
        layer = jnp.zeros((), jnp.int32)
    window = -1 if sliding_window is None else int(sliding_window)
    # the tile kernel walks a page an iteration (its multiplies bind it)
    block = 1 if tiles is not None else walk_block or walk_pages(
        page * KV * hd_p * k_pool.dtype.itemsize)
    scratch = [pltpu.VMEM((2 * block, page, KV * hd_p), k_pool.dtype),
               pltpu.VMEM((2 * block, page, KV * hd_p), v_pool.dtype)]
    if quant:
        scratch += [pltpu.VMEM((2 * block, KV, page), jnp.float32)] * 2
    if tiles is not None:
        assert shared is None, "a tile's queries are one row's"
        state = (KV, tile * (H // KV))   # kv-head-major, query-major rows
        out = pl.pallas_call(
            functools.partial(
                _ragged_tile_kernel, page=page, n_kv=KV, hd=hd_p, tq=tq,
                tile=tile, scale=hd ** -0.5, quant=quant, window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,             # tables, tiles, layer
                grid=(tiles.shape[1],),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)  # q, the pools
                          for _ in range(1 + len(pools))],
                out_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                scratch_shapes=[
                    pltpu.VMEM((tile, H, hd_p), q.dtype),
                    pltpu.VMEM((tile, H, hd_p), jnp.float32),
                    pltpu.VMEM(state + (hd_p,), jnp.float32),
                    pltpu.VMEM(state + (1,), jnp.float32),
                    pltpu.VMEM(state + (1,), jnp.float32),
                    pltpu.VMEM(state + (hd_p,), jnp.float32),
                    *scratch,
                    pltpu.SemaphoreType.DMA((2, len(pools))),
                    pltpu.SemaphoreType.DMA((1,))],
            ),
            out_shape=[jax.ShapeDtypeStruct((Tp, H, hd_p), jnp.float32)],
            interpret=interpret,
            name="ragged_attend",                  # pinned, as below
        )(row_tables.astype(jnp.int32), tiles.astype(jnp.int32),
          layer.reshape(1), q, *pools)[0]
        return _own_lanes(out, KV, pack, hd)
    qb = q.reshape(NB, tq, H, hd_p)
    kernel = functools.partial(
        _ragged_kernel, page=page, n_kv=KV, hd=hd_p, tq=tq,
        scale=hd ** -0.5, quant=quant, window=window, block=block,
        shared=shared is not None)
    prefetch = [row_tables.astype(jnp.int32), block_meta.astype(jnp.int32),
                layer.reshape(1)]
    more_in, more_scr = [], []
    if shared is not None:
        R, G = row_tables.shape[0], H // KV
        assert tq == 1 and NB == R and shared.shape == (2 + SHARED_ROWS, R)
        prefetch.append(shared.astype(jnp.int32))
        more_in = [qb]                             # whole, for the gather
        walk = (KV, SHARED_ROWS * G)               # member-major score rows
        more_scr = [pltpu.VMEM((SHARED_ROWS, tq, H, hd_p), q.dtype),
                    pltpu.VMEM(walk + (hd_p,), jnp.float32),
                    pltpu.VMEM(walk + (1,), jnp.float32),
                    pltpu.VMEM(walk + (1,), jnp.float32),
                    pltpu.VMEM(walk + (hd_p,), jnp.float32),
                    pltpu.VMEM((R * KV, G, 128), jnp.float32),
                    pltpu.VMEM((R * KV, G, 128), jnp.float32),
                    pltpu.VMEM((R * KV, G, hd_p), jnp.float32),
                    pltpu.SemaphoreType.DMA((1,))]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),     # tables, meta, layer …
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((1, tq, H, hd_p), lambda i, *_: (i, 0, 0, 0)),
                *[pl.BlockSpec(memory_space=pl.ANY)       # pools stay in HBM
                  for _ in more_in + pools],
            ],
            out_specs=[
                pl.BlockSpec((1, tq, H, hd_p), lambda i, *_: (i, 0, 0, 0)),
            ],
            scratch_shapes=[*scratch,
                            pltpu.SemaphoreType.DMA((2 * block, len(pools))),
                            pltpu.SMEM((2,), jnp.int32),   # started ahead
                            *more_scr,
                            pltpu.VMEM((KV, block, max(
                                tq, SHARED_ROWS if shared is not None else 0)
                                * (H // KV), page), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((NB, tq, H, hd_p), jnp.float32),
        ],
        interpret=interpret,
        # pinned: the trace shows the kernel as `%ragged_attend.<n>`, and
        # the benchmark's metric files match on that name
        name="ragged_attend",
    )(*prefetch, qb, *more_in, *pools)[0]
    return _own_lanes(out.reshape(NB * tq, H, hd_p), KV, pack, hd)


def _lane_geometry(n_kv: int, head_dim: int) -> tuple:
    """(lanes a head takes in the kernel, kv heads read as one): a head
    narrower than a lane tile packs with its neighbours where the heads
    divide evenly, else it is padded to the tile."""
    hd_p = max(128, -(-head_dim // 128) * 128)
    pack = hd_p // head_dim
    if hd_p % head_dim or n_kv % pack:
        pack = 1
    return hd_p, pack


# ---------------------------------------------------------------------------
# The BLOCK walk (ISSUE 38): several pages in flight, one loop turn a block
# ---------------------------------------------------------------------------
#
# Two things made a page of a decode walk cost its bytes + 0.30–0.36 µs on
# the chip whatever its size (34% of the bandwidth at 2 kv heads, 62% at 8;
# PERF.md §6, PR 38, has the split). A walk that starts page j + 1 while it
# attends page j has one page's arithmetic to hide a DMA's start-to-arrival
# latency behind; and with the copies far enough ahead the walk is bound by
# the online softmax's own chain, run once a page for 4–64 score rows: a
# row maximum and a row sum (lane reductions), exp(m − m_new) and a rescale
# of acc a kv head a page, each waiting for the one before.
#
# The block kernel's walks (a row's own pages, and a group's shared ones)
# therefore move a BLOCK of B pages a loop iteration. A stream's scratch
# is two blocks, [2·B, page, KV·hd], with a DMA semaphore a slot; iteration
# b starts block b + 1's copies — up to B a stream, all in flight at once —
# into the half block b − 1 left, then attends block b (``_attend_block``):
#
#   1. every page's score tile, q·kᵀ (masked where the walk masks), is
#      written to scratch, a running ELEMENTWISE maximum beside it;
#   2. one row maximum for the block, one m_new, one exp(m − m_new);
#   3. every page's p = exp(s − m_new) is summed elementwise and multiplied
#      into the block's p·v;
#   4. one row sum, one rescale of l and acc.
#
# The B score tiles of a block share a maximum and a rescale, as a page's
# 128 columns always did: the online softmax with a tile of B·page keys
# (boom_attention_tricks §9–11, ``pages_per_compute_block``). Operands do
# not change — float32 q, pages upcast exactly, float32 state — so this is
# no lower precision; it reorders float32 additions and takes exp against
# the block's maximum, not the page's (tests/test_ragged_attention.py holds
# B against B = 1 and the dense oracle within the file's float32 limit).
# A walk's last block is partial: pages past the walk's end are neither
# copied nor attended (every loop is bounded by the pages left, none over
# a masked page of stale scratch), so a walk of fewer than B pages costs
# what it did (less, since ISSUE 45, the wait for its first block: the
# next section says what a grid program costs now). The loops' bodies are
# a page each — the kernel holds two matmuls a kv head as it always did,
# not B of them.
#
# B is a function of what the kernel can see (``walk_pages``): the bytes of
# a page in one stream, so that a block in flight is half a MiB a stream —
# what covers the latency at the bandwidth; on the chip larger blocks read
# level or worse (PERF.md §6) — and the two blocks of K and V stay within
# 2 MiB of the 16 MiB of scoped VMEM.

# ---------------------------------------------------------------------------
# The walk started ahead (ISSUE 45): no walk waits for its first block cold
# ---------------------------------------------------------------------------
#
# Inside a walk block b + 1 is in flight while block b is attended, but a
# walk's FIRST block used to be started by the walk itself and waited for
# with nothing to hide it behind: once a grid program, twice in a leader's
# (its shared walk, then its own). The decode call is 8 programs of walks of
# 1–3 blocks, so most blocks of a call were first blocks.
#
# In the block kernel (``_ragged_kernel``) every walk's first block is
# started by the walk that runs BEFORE it, as that walk's "next block": the
# block behind a walk's last is the first block of what runs next — in a
# leader's program its own walk behind its shared one, else the first walk
# of the next program that walks anything (its shared walk if it leads a
# live group, else its own; programs with no query are passed over). The
# copies start where a next block's always did, before the last block is
# attended and into the half of the two-block scratch it does not occupy:
# the same ``_start_block`` of the same loop turn, with a row, a first page
# and a count chosen by scalars (``walk_dmas``, ``_walk_blocks``'
# ``n_after``), so the loop holds no branch more than it did. Every
# program can work out every other's walks: tables, block meta and the
# shared-walk table are scalars in SMEM. Two more scalars, in SMEM scratch
# that outlives a grid step (the grid's one dimension is sequential),
# carry what a program cannot know from the tables alone: which half the
# next walk begins in (the parity of the blocks attended so far), and
# which program's first walk is in flight. The first program of a call
# with a walk finds none in flight and starts its own cold, as every
# program used to; the last starts nothing, so a call ends with no copy in
# flight. The same copies into the same scratch and the same products in
# the same order, only started earlier: a row's output is bit-equal to the
# parent's (tests/test_ragged_attention.py holds a row in a full call
# against the same row alone in its call, where its walks start cold).
#
# What a grid program costs now (PERF.md §6, PR 45; the decode call alone on
# the chip, 8 row slots, parent → this): a walk started ahead is 0.4–0.5 µs
# cheaper at Qwen's widths and 0.7–1.0 at Mistral's, lfm2's, laguna's and
# mellum2's — the copy's latency and the first pages' bytes. A call of 8
# rows of 1–3 pages is 12.8 → 9.7 µs (Qwen), 20.8 → 14.2 (Mistral), 16.9 →
# 12.0 (lfm2): 1.2–1.8 µs a program; the agent cells' call (14 shared pages
# + tails of 2–10) 28.0 → 24.7. What is left of PR 38's "2.8 µs a grid
# program" is not a wait: a block's own arithmetic (scores, one softmax
# update, values: a chain with nothing to overlap in a walk of one block),
# the copies' descriptors (2 a page, issued from the scalar core) and the
# grid step. Walks of 90–100 pages read level (234.7 → 236.7 µs for 763
# pages at Qwen's widths).

_WALK_BLOCK_BYTES = 512 << 10    # a block in flight, a stream, at most
_WALK_BLOCK_PAGES = 8            # ... and in pages (the score scratch)


def walk_pages(page_bytes: int) -> int:
    """Pages a loop iteration of the block kernel's walks carries (B of
    the section above) for pages of ``page_bytes`` in one stream (page ·
    KV·hd · itemsize, as the kernel lays a head out): the largest power of
    two that keeps a block within ``_WALK_BLOCK_BYTES``, at most
    ``_WALK_BLOCK_PAGES`` — 8 at 64 KiB a page (qwen2.5-3b), 4 at 128 KiB
    (lfm2-24b-a2b-l9, heads packed), 2 at 256 KiB (mistral-7b-l16)."""
    block = _WALK_BLOCK_PAGES
    while block > 1 and block * page_bytes > _WALK_BLOCK_BYTES:
        block //= 2
    return block


def decode_walk_pages(page: int, n_kv: int, head_dim: int,
                      itemsize: int) -> int:
    """``walk_pages`` for a pool of this geometry, as ``ragged_attend``
    reckons it (host side: the engine's count of loop iterations)."""
    hd_p, pack = _lane_geometry(n_kv, head_dim)
    return walk_pages(page * (n_kv // pack) * hd_p * itemsize)


def _start_block(n, block: int, b, dmas, half0=None) -> None:
    """Start the copies of block ``b`` of a walk of ``n`` pages: pages
    b·block .. into the scratch half of b's parity, a slot a page; none
    past the walk's end. ``half0`` (0 or 1): the half the walk's block 0
    lies in where that is not the first (a walk STARTED AHEAD, section
    "The walk started ahead")."""
    first = b * block
    half = jax.lax.rem(b if half0 is None else b + half0, 2) * block

    def start(i):
        for d in dmas(first + i, half + i):
            d.start()

    _each(jnp.minimum(block, n - first), start)


def _walk_blocks(n, block: int, dmas, attend, carry, half0=None,
                 n_after=None):
    """The walk of ``n`` pages (a traced count) ``block`` pages a loop
    iteration, block 0's copies already started (``_start_block``: the
    caller starts them as early as it can). ``dmas(j, slot)`` are page j's
    copies into scratch slot ``slot``; ``attend(first, half, left, wait,
    carry)`` attends one block — pages ``first`` .., in slots ``half`` ..,
    ``left`` of them (1..block), ``wait(i)`` the wait for its i-th page's
    copies — and returns the carry. Returns the last carry. A caller whose
    walks are started ahead gives ``half0``, the half block 0 lies in, and
    ``n_after``, the pages of the first block of whatever walk runs NEXT:
    to this walk they are pages ``turns·block ..``, the block behind its
    last, which ``dmas`` maps to the next walk's, and their copies start
    where a next block's always do — before the last block is attended,
    into the half it does not occupy."""
    turns = (n + block - 1) // block

    def turn(b, carry):
        if n_after is None:
            _start_block(n, block, b + 1, dmas)
        else:
            _start_block(jnp.where(b + 1 == turns, turns * block + n_after,
                                   n), block, b + 1, dmas, half0)
        first = b * block
        half = jax.lax.rem(b if half0 is None else b + half0, 2) * block

        def wait(i):
            for d in dmas(first + i, half + i):
                d.wait()

        return attend(first, half, jnp.minimum(block, n - first), wait,
                      carry)

    return jax.lax.fori_loop(0, turns, turn, carry)


_MASKED = NEG_INF / 2        # a stored score under this was masked


def _attend_block(left, wait, heads, valid, s_scr, state):
    """One block of a walk for every kv head (section comment): ``left``
    pages (traced, 1..B); ``wait(i)`` waits for the copies of the block's
    i-th page; ``heads`` a kv head each (q [rows, hd] scaled float32
    queries, then k, v and an int8 page's k and v scales or None, each a
    function of i: the page's [page, hd] float32 blocks, [1, page]
    scales); ``valid(i)`` the page's [rows, page] mask, or ``valid`` None
    where every row sees every page whole (a shared walk); ``s_scr``
    [n_kv, B, >= rows, page] the block's score tiles; ``state`` a kv head
    each (m, l, acc). Returns the updated state."""
    rows, hd = heads[0][0].shape
    page = s_scr.shape[-1]

    def scores(i, tops):
        wait(i)
        ok = None if valid is None else valid(i)
        out = []
        for kv, (q, k, _, ks, _) in enumerate(heads):
            s = jax.lax.dot_general(                     # [rows, page]
                q, k(i), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if ks is not None:
                s = s * ks(i)                            # dequant K
            if ok is not None:
                s = jnp.where(ok, s, NEG_INF)
            s_scr[kv, i, 0:rows, :] = s
            out.append(jnp.maximum(tops[kv], s))
        return tuple(out)

    tops = jax.lax.fori_loop(
        0, left, scores,
        tuple(jnp.full((rows, page), NEG_INF, jnp.float32) for _ in heads))
    m_new = [jnp.maximum(m, jnp.max(top, axis=1, keepdims=True))
             for (m, _, _), top in zip(state, tops)]

    def values(i, sums):
        out = []
        for kv, (_, _, v, _, vs) in enumerate(heads):
            s = s_scr[kv, i, 0:rows, :]
            p = jnp.exp(s - m_new[kv])
            if valid is not None:
                p = jnp.where(s > _MASKED, p, 0.0)
            lt, pv = sums[kv]
            lt = lt + p
            if vs is not None:
                p = p * vs(i)                            # dequant V
            out.append((lt, pv + jax.lax.dot_general(    # [rows, hd]
                p, v(i), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)))
        return tuple(out)

    sums = jax.lax.fori_loop(
        0, left, values,
        tuple((jnp.zeros((rows, page), jnp.float32),
               jnp.zeros((rows, hd), jnp.float32)) for _ in heads))
    out = []
    for (m, l, acc), top, (lt, pv) in zip(state, m_new, sums):
        corr = jnp.exp(m - top)
        out.append((top, l * corr + jnp.sum(lt, axis=1, keepdims=True),
                    acc * corr + pv))
    return tuple(out)


def _pack_queries(q: jax.Array, n_kv: int, pack: int) -> jax.Array:
    """[T, H, hd] -> [T, H, pack·hd]: query head h of kv head g keeps its
    values in lanes ``(g % pack)·hd ..`` of the packed head ``g // pack``
    and holds zeros elsewhere."""
    T, H, hd = q.shape
    G = H // n_kv
    q = q.reshape(T, n_kv // pack, pack, G, 1, hd)
    own = jnp.eye(pack, dtype=q.dtype)[None, None, :, None, :, None]
    return (q * own).reshape(T, H, pack * hd)


def _own_lanes(out: jax.Array, n_kv: int, pack: int, hd: int) -> jax.Array:
    """The kernel's [T, H, hd_p] output as [T, H, hd]: a packed head's
    output holds all ``pack`` heads' values side by side, and a query
    head takes its own kv head's (``n_kv`` counts the PACKED heads);
    unpacked, the lanes before the zero padding."""
    if pack == 1:
        return out[..., :hd]
    T, H, _ = out.shape
    G = H // (n_kv * pack)
    out = out.reshape(T, n_kv, pack, G, pack, hd)
    return jnp.stack([out[:, :, a, :, a] for a in range(pack)],
                     axis=2).reshape(T, H, hd)


# ---------------------------------------------------------------------------
# The query TILE (ISSUE 30): a row's pages are read once per tile
# ---------------------------------------------------------------------------
#
# One program per tq-token block re-reads the row's whole visible context
# for every 8 queries: a cold prompt of n tokens streams n² / 16 resident
# tokens a layer and multiplies with 8·G-row left operands. The TILE kernel
# keeps the flat layout (segments padded to tq, the same [Tp, H, hd] arrays,
# the same program keys) and changes only which queries share a walk: a
# grid program serves a tile of up to ``tile`` consecutive query tokens of
# ONE row, brings each visible page into VMEM once, and attends it for all
# of the tile's queries at once (up to tile·G score rows a kv head), their
# online-softmax state in VMEM scratch between pages. A tile starts
# wherever its row's segment puts it (a multiple of tq, not of ``tile``),
# so q and the output stay in HBM and move by the tile's own start, tq
# tokens a copy.
#
# The host derives the tile table from the block table (``ragged_tiles``):
#
#   tiles[:, i] = (kv_len, qpos0, nq, row, tok0, span)
#     kv_len, qpos0, row   as in block_meta, for the tile's first query;
#     nq      valid queries of the tile (1..tile; 0 = writes zeros only);
#     tok0    flat index of the tile's first token;
#     span    flat tokens the tile owns and writes (nq rounded up to tq;
#             for nq = 0 a run of padding tokens, written as zeros).
#
# A tile's cost follows its nq, which the kernel reads: it attends at the
# first HEIGHT of tq, 4·tq, … , ``tile`` tokens that holds its queries —
# a decode row of a mixed tick at tq, a block's cost; a short tool result
# at 32; a prompt's tiles at ``tile``. Same operands, same page order,
# same float32 online softmax per query row as the block kernel.
#
# The decode program keeps the block kernel at tq = 1: one token a row has
# no walk to share, and what the tile pays for sharing — q and the output
# moved by hand, the softmax state through VMEM scratch every page — read
# 10% (Qwen's widths) to 19% (Mistral's) more a call on the chip at
# tile = tq = 1, bit-equal outputs (PERF.md §6, PR 30).

RAGGED_TILE = 128            # query tokens a grid program serves at most
_TILE_VMEM = 12 << 20        # the tile's scratch, of 16 MiB scoped VMEM


_TILE_GROUP_ROWS = 1024      # query rows a kv head's scores take at most


def ragged_tile(n_heads: int, head_dim: int, tq: int,
                q_per_kv: int = 1) -> int:
    """Tokens of a query tile for this head geometry: ``RAGGED_TILE``,
    halved until the tile's scratch fits ``_TILE_VMEM`` — per token its
    queries as they arrive (2 bytes), scaled to float32, the accumulator
    and the output (4 each) at H·hd, and two softmax columns that pad to
    128 lanes — and until a kv head's score block, ``tile · q_per_kv``
    query rows against a block of keys in float32 on the kernel's stack,
    has at most ``_TILE_GROUP_ROWS`` rows (16 query heads a kv head at a
    tile of 128 took 18.45 MB of the 16 MiB; the widest group before, 8
    at 128, is the bound)."""
    hd_p = -(-head_dim // 128) * 128
    per_token = n_heads * (hd_p * 14 + 2 * 128 * 4)
    tile = RAGGED_TILE
    while tile > tq and (tile * per_token > _TILE_VMEM
                         or tile * q_per_kv > _TILE_GROUP_ROWS):
        tile //= 2
    return max(tile, tq)


def ragged_tile_slots(n_blocks: int, rows: int, tq: int, tile: int) -> int:
    """Static length of the tile table for a flat layout of ``n_blocks``
    tq-token blocks and ``rows`` row slots: each row's last tile may be
    short, and so may the last padding tile."""
    return min(n_blocks, n_blocks // (tile // tq) + rows + 1)


def ragged_tiles(block_meta, tq: int, tile: int, slots: int = 0):
    """The tile table [6, max(slots, tiles)] (numpy, host side) of a block
    table [4, NB]: consecutive live blocks of one row group into tiles of
    ``tile`` tokens, consecutive inert blocks into padding tiles. Unused
    slots are all zero: a program that does nothing."""
    kv_len, qpos0, nq, row = np.asarray(block_meta)
    nb = nq.shape[0]
    idx = np.arange(nb)
    run = np.where(nq > 0, row, -1)            # inert blocks: one run
    new = np.r_[True, run[1:] != run[:-1]]
    in_run = idx - np.maximum.accumulate(np.where(new, idx, 0))
    first = np.flatnonzero(in_run % (tile // tq) == 0)
    n_blk = np.diff(np.r_[first, nb])
    tiles = np.zeros((6, max(slots, first.size)), np.int32)
    tiles[:, :first.size] = (kv_len[first], qpos0[first],
                             np.add.reduceat(nq, first), row[first],
                             first * tq, n_blk * tq)
    return tiles


def _tile_pages(tiles, page: int, sliding_window, skip) -> tuple:
    """(pages each tile's program walks, which tiles are live)."""
    kv_len, qpos0, nq = np.asarray(tiles, np.int64)[:3]
    live = nq > 0
    hi = -(-np.minimum(kv_len, qpos0 + nq) // page)
    lo = skip if sliding_window is None else \
        np.maximum(qpos0 + 1 - sliding_window, 0) // page
    return np.maximum(hi - lo, 0) * live, live


def ragged_tile_walk(tiles, page: int, sliding_window=None,
                     skip=0) -> tuple:
    """(resident tokens the kernel's programs bring into VMEM, programs
    that walk pages) for a tile table: Σ over live tiles of visible pages
    × page. With the block table as its own tile table (``tile`` = tq) it
    prices the walk of one program per block. ``skip``: leading pages a
    tile's own walk leaves to a shared one (a count, or one a tile)."""
    pages, live = _tile_pages(tiles, page, sliding_window, skip)
    return int(pages.sum()) * page, int(live.sum())


def ragged_walk_steps(tiles, page: int, block: int = 1,
                      sliding_window=None, skip=0) -> int:
    """Loop iterations the programs of a tile table make walking their
    pages ``block`` pages an iteration (``walk_pages``, or for a latent
    pool ``latent_walk_pages``; the tile kernel one), each walk's last
    block partial."""
    pages, _ = _tile_pages(tiles, page, sliding_window, skip)
    return int((-(-pages // block)).sum())


def decode_walks(steps, page: int, sliding_window=None, skip=0,
                 shared=None) -> tuple:
    """(walks of at least one page the block kernel's programs make over a
    decode loop, those whose first block an EARLIER walk of the same call
    started: all but a call's first; section "The walk started ahead").
    ``steps`` [3, rows, steps]: the one-token tiles of the loop, (kv_len,
    qpos0, nq) a row a step; ``skip`` as in ``ragged_tile_walk``; with
    ``shared`` a group's walk is one more in every step that one of its
    rows runs."""
    pages, live = _tile_pages(steps, page, sliding_window, skip)
    calls = (pages > 0).sum(axis=0)              # a row's own walk, a step
    if shared is not None and sliding_window is None:
        shared = np.asarray(shared)[:, :live.shape[0]]
        for r in np.flatnonzero((shared[1] > 0) & (shared[0] > 0)):
            calls = calls + live[shared[2:, r]].any(axis=0)
    return int(calls.sum()), int(np.maximum(calls - 1, 0).sum())


def _each(n, fn) -> None:
    """``fn(i)`` for 0 <= i < n: unrolled where n is a Python int, else a
    loop on the device."""
    if isinstance(n, int):
        for i in range(n):
            fn(i)
    else:
        jax.lax.fori_loop(0, n, lambda i, c: (fn(i), c)[1], 0)


def _ragged_tile_kernel(tables_ref, tiles_ref, layer_ref, q_hbm, k_hbm,
                        v_hbm, *refs, page: int, n_kv: int, hd: int,
                        tq: int, tile: int, scale: float, window: int,
                        quant: bool):
    """One TILE of up to ``tile`` query tokens of one row (see the section
    comment): q and the output stay in HBM and move tq tokens a copy from
    and to the tile's own start; the row's visible pages stream through
    VMEM double-buffered as in the block kernel, ONCE for the whole tile,
    and each updates the tile's (m, l, acc) in VMEM scratch.
    Scalar-prefetched: tables_ref [R, maxp], tiles_ref [6, NT] (kv_len,
    qpos0, nq, row, tok0, span), layer_ref [1].

    Scratch, all [.., tile, ..] tall: q_scr the queries as they arrive,
    qf_scr the same scaled to float32 and laid kv-head-major, query-major
    rows ([n_kv, tile·G, hd]: a kv head's left operand is a row slice),
    m/l/acc_scr the softmax state in that layout, o_scr the normalized
    output token-major for the copies out."""
    if quant:
        (ks_hbm, vs_hbm, out_hbm, q_scr, o_scr, qf_scr, m_scr, l_scr,
         acc_scr, k_scr, v_scr, ks_scr, vs_scr, sems, io_sem) = refs
        streams = ((k_hbm, k_scr), (v_hbm, v_scr),
                   (ks_hbm, ks_scr), (vs_hbm, vs_scr))
    else:
        (out_hbm, q_scr, o_scr, qf_scr, m_scr, l_scr, acc_scr, k_scr,
         v_scr, sems, io_sem) = refs
        streams = ((k_hbm, k_scr), (v_hbm, v_scr))
    i = pl.program_id(0)
    kv_len, qpos0, nq, row, tok0, span = (tiles_ref[j, i] for j in range(6))
    layer = layer_ref[0]
    G = q_scr.shape[1] // n_kv

    # q and the output move one tq-token granule a copy: granule g of
    # the tile's span of the flat array <-> granule g of the scratch
    def q_in(g):
        return pltpu.make_async_copy(q_hbm.at[pl.ds(tok0 + g * tq, tq)],
                                     q_scr.at[pl.ds(g * tq, tq)],
                                     io_sem.at[0])

    def o_out(g, src=None):
        return pltpu.make_async_copy(
            o_scr.at[pl.ds((g if src is None else src) * tq, tq)],
            out_hbm.at[pl.ds(tok0 + g * tq, tq)], io_sem.at[0])

    def start(n, copy):
        """Start ``n`` granule copies; returns the wait for them."""
        _each(n, lambda g: copy(g).start())
        return lambda: _each(n, lambda g: copy(g).wait())

    @pl.when((nq == 0) & (span > 0))
    def _():
        # padding of the flat layout: zeros, as an inert block writes
        o_scr[0:tq] = jnp.zeros((tq,) + o_scr.shape[1:], o_scr.dtype)
        start(span // tq, lambda g: o_out(g, src=0))()

    # last visible key + 1: nothing past the tile's last query is visible;
    # first page: what the tile's FIRST query's window still reaches
    kv_hi = jnp.minimum(kv_len, qpos0 + nq)
    if window >= 0:
        p_lo = jnp.maximum(qpos0 + 1 - window, 0) // page
    else:
        p_lo = jnp.int32(0)
    n = jnp.maximum((kv_hi + page - 1) // page - p_lo, 0)

    def dmas(j, slot):
        pid = tables_ref[row, p_lo + j]
        return [pltpu.make_async_copy(hbm.at[layer, pid], scr.at[slot],
                                      sems.at[slot, s])
                for s, (hbm, scr) in enumerate(streams)]

    def attend(height: int) -> None:
        """The tile at ``height`` tokens (>= nq): M = height·G score rows
        a kv head, rows 0..M of the state scratch."""
        M = height * G
        n_g = 1 if height == tq else span // tq          # granules to move
        wait_q = start(n_g, q_in)

        @pl.when(n > 0)
        def _():
            for d in dmas(0, 0):
                d.start()

        wait_q()

        def granule_rows(g):
            r0 = g * (tq * G)
            return pl.ds(r0 if isinstance(g, int)
                         else pl.multiple_of(r0, tq * G), tq * G)

        def load_q(g):
            qg = q_scr[pl.ds(g * tq, tq)].astype(jnp.float32) * scale
            for kv in range(n_kv):
                qf_scr[kv, granule_rows(g), :] = \
                    qg[:, kv * G:(kv + 1) * G].reshape(tq * G, hd)

        _each(n_g, load_q)
        for kv in range(n_kv):
            m_scr[kv, 0:M, :] = jnp.full((M, 1), NEG_INF, jnp.float32)
            l_scr[kv, 0:M, :] = jnp.zeros((M, 1), jnp.float32)
            acc_scr[kv, 0:M, :] = jnp.zeros((M, hd), jnp.float32)

        # per-score-row query index → buffer position and validity, as in
        # the block kernel; rows past the last granule hold stale queries
        # and are masked like any row past nq
        t_of_row = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) // G
        qpos = qpos0 + t_of_row                          # [M, 1]
        q_ok = t_of_row < nq

        def walk(j):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n)
            def _():
                for d in dmas(j + 1, jax.lax.rem(j + 1, 2)):
                    d.start()

            for d in dmas(j, slot):
                d.wait()
            s_idx = (p_lo + j) * page + jax.lax.broadcasted_iota(
                jnp.int32, (1, page), 1)                 # [1, page]
            valid = (s_idx < kv_len) & (s_idx <= qpos) & q_ok
            if window >= 0:
                valid = valid & (qpos - s_idx < window)
            for kv in range(n_kv):
                lanes = slice(kv * hd, (kv + 1) * hd)
                m, l, acc = _attend_page(
                    qf_scr[kv, 0:M, :],
                    lambda: k_scr[slot, :, lanes].astype(jnp.float32),
                    lambda: v_scr[slot, :, lanes].astype(jnp.float32),
                    (lambda: ks_scr[slot, kv:kv + 1, :]) if quant else None,
                    (lambda: vs_scr[slot, kv:kv + 1, :]) if quant else None,
                    valid, m_scr[kv, 0:M, :], l_scr[kv, 0:M, :],
                    acc_scr[kv, 0:M, :])
                m_scr[kv, 0:M, :] = m
                l_scr[kv, 0:M, :] = l
                acc_scr[kv, 0:M, :] = acc

        _each(n, walk)

        def store(g):
            rows = granule_rows(g)
            for kv in range(n_kv):
                l = l_scr[kv, rows, :]
                norm = acc_scr[kv, rows, :] / jnp.where(l > 0, l, 1.0)
                o_scr[pl.ds(g * tq, tq), kv * G:(kv + 1) * G] = \
                    norm.reshape(tq, G, hd)

        _each(n_g, store)
        start(n_g, o_out)()

    heights = [tq]
    while heights[-1] * 4 < tile:
        heights.append(heights[-1] * 4)
    if heights[-1] < tile:
        heights.append(tile)
    for below, height in zip([0] + heights, heights):
        @pl.when((nq > below) & (nq <= height))
        def _(height=height):
            attend(height)


# ---------------------------------------------------------------------------
# The SHARED walk (ISSUE 32): common leading pages are read once a step
# ---------------------------------------------------------------------------
#
# The decode program's call is one program a row (tq = 1), each walking ITS
# row's table. Rows that adopted one prompt from the prefix cache hold the
# same page ids at the head of their tables, so 8 agents on a 10k-token
# prompt brought the same 80 pages into VMEM 8 times a layer a step. The
# host finds such rows from the tick's tables alone (``shared_walks``):
#
#   shared[0, r]      pages of r's table that a shared walk covers (0: none)
#   shared[1, r]      1 where r LEADS a walk: the lowest row of its group,
#                     so the grid reaches it before the others
#   shared[2 + k, r]  the rows of the walk r leads, SHARED_ROWS of them
#                     (short groups repeat the leader, which costs a
#                     duplicate score row and writes the same state twice)
#
# The leader's program gathers its group's queries from HBM, streams the
# common pages through the kernel's own scratch ONCE, a block of pages a
# loop iteration as every walk of the kernel goes (section "The BLOCK
# walk"), and multiplies each against all of them (SHARED_ROWS·G score
# rows a kv head), every query row with its own float32 online-softmax
# state, updated once a block; it leaves each member's (m, l, acc) in
# VMEM scratch that outlives the program. Every row's program — the
# leader's too — then walks only the pages behind the shared ones and
# starts from that state instead of (−inf, 0, 0): the same pages in the same
# order for every query, the state merely parked between two programs (a
# block's maximum is taken where the two walks cut the pages, so a row's
# output is its unshared walk's to float32 rounding). Every shared page
# is full and wholly visible for every member (``shared_walks`` caps the
# count at the rows' whole pages before the loop), so the walk masks
# nothing.
#
# A row in no group reads shared[0, r] = 0 and runs the program it always
# ran; a tick with no group runs no walk. One compiled kernel either way.

SHARED_ROWS = 8              # rows one shared walk serves at most
# Common pages below which a walk does not pay: what it adds (the gathered
# queries' DMA, a pipeline start of its own, the parked state) is 2–3 µs a
# call on the chip, and a PAIR of rows gets that back between 4 common
# pages (20.1 µs a call for 19.2 unshared at Qwen's widths, 30.6 for 29.7 at
# Mistral's) and 8 (21.3 for 22.7; 34.7 for 37.1): PERF.md §6, PR 32.
SHARED_MIN_PAGES = 6


def shared_walks(tables, pool_lens, page: int, sliding_window=None):
    """The decode program's shared-walk table [2 + SHARED_ROWS, R] (numpy,
    host side; layout in the section comment) from the tick's row tables
    [R, maxp] and the rows' resident tokens [R] alone. Rows whose tables
    BEGIN with the same page ids form a group of 2..SHARED_ROWS rows; its
    walk covers their common leading pages, at most the fewest WHOLE pages
    any of them holds now — the decode loop writes behind those. Groups are
    runs of the rows sorted by table (neighbours share the most), cut where
    the pages saved, Σ (rows − 1) × common, are most. A row slot that holds
    nothing (a zero table) joins no group; under a sliding window nothing
    is shared."""
    tables = np.asarray(tables)
    full = np.asarray(pool_lens) // page
    R = full.shape[0]
    out = np.zeros((2 + SHARED_ROWS, R), np.int32)
    out[2:] = np.arange(R)
    if sliding_window is not None:
        return out
    rows = np.flatnonzero(full >= SHARED_MIN_PAGES)
    if rows.size < 2:
        return out
    rows = rows[np.lexsort(tables[rows].T[::-1])]
    t, f = tables[rows], full[rows]
    differ = t[1:] != t[:-1]
    lcp = np.where(differ.any(axis=1), differ.argmax(axis=1), t.shape[1])
    lcp = np.minimum(lcp, np.minimum(f[1:], f[:-1])).tolist()
    if max(lcp) < SHARED_MIN_PAGES:
        return out
    # best[j]: most page reads saved among the first j sorted rows;
    # cut[j]: the group that ends there, (rows, common pages), if one does
    n = rows.size
    best, cut = [0] * (n + 1), [None] * (n + 1)
    for j in range(2, n + 1):
        best[j] = best[j - 1]
        common = lcp[j - 2]
        for size in range(2, min(SHARED_ROWS, j) + 1):
            common = min(common, lcp[j - size])
            if common < SHARED_MIN_PAGES:
                break
            saved = best[j - size] + (size - 1) * common
            if saved > best[j]:
                best[j], cut[j] = saved, (size, common)
    j = n
    while j > 0:
        if cut[j] is None:
            j -= 1
            continue
        size, common = cut[j]
        members = np.sort(rows[j - size:j])
        out[0, members] = common
        out[1, members[0]] = 1
        out[2:2 + size, members[0]] = members
        j -= size
    return out


def shared_walk_tokens(shared, forwards, page: int) -> tuple:
    """(resident tokens the rows of a tick needed from shared pages, tokens
    the shared walks brought into VMEM for them) over a decode loop in
    which row r ran ``forwards[r]`` steps: a walk runs in every step that
    one of its rows does."""
    shared = np.asarray(shared)
    forwards = np.asarray(forwards, np.int64)
    needed = int((shared[0, :forwards.shape[0]] * forwards).sum()) * page
    walked = sum(common * steps
                 for common, steps in _shared_runs(shared, forwards)) * page
    return needed, walked


def _shared_runs(shared, forwards) -> list:
    """[(common pages, decode steps it ran in)] a shared walk of the loop:
    a walk runs in every step that one of its rows does."""
    return [(int(shared[0, r]), int(forwards[shared[2:, r]].max()))
            for r in np.flatnonzero(shared[1, :forwards.shape[0]])]


def shared_walk_steps(shared, forwards, block: int) -> int:
    """Loop iterations the shared walks of a decode loop made, ``block``
    pages an iteration (``forwards`` as in ``shared_walk_tokens``)."""
    return sum(-(-common // block) * steps for common, steps in
               _shared_runs(np.asarray(shared),
                            np.asarray(forwards, np.int64)))


def _shared_walk(members, n_pages, dmas, page_blocks, q_hbm, scratch, s_scr,
                 *, n_kv: int, G: int, scale: float, block: int, half0,
                 n_after):
    """The walk a leader's program makes for its group (section comment):
    ``members`` SHARED_ROWS row indices (scalars), ``n_pages`` > 0 common
    pages walked ``block`` a loop iteration as every walk of the kernel is
    (``_walk_blocks``), ``dmas(j, slot)`` the copies of page j,
    ``page_blocks(half, kv)`` what ``_attend_block`` reads of a block's
    pages in the kernel's own scratch, ``s_scr`` its score tiles.
    The walk's first block is in flight when it begins, in half ``half0``
    of the scratch, and behind its last it starts ``n_after`` pages of the
    walk that runs next (``dmas`` knows them: ``_walk_blocks``).
    ``scratch``, as ``ragged_attend``
    lists it: qg_scr [SHARED_ROWS, 1, H, hd] the members' queries as they
    arrive, qf_scr [n_kv, SHARED_ROWS·G, hd] the same scaled to float32,
    member-major rows a kv head; (m, l, acc) in that layout while the walk
    runs; the same PARKED by (row, kv head), [R·n_kv, G, ·], where each
    member's program finds it (m and l in every lane of 128); the
    queries' DMA semaphore."""
    qg_scr, qf_scr, *state, q_sem = scratch
    state, parked = state[:3], state[3:]
    M = len(members) * G

    def q_in(k):
        return pltpu.make_async_copy(q_hbm.at[members[k]], qg_scr.at[k],
                                     q_sem.at[0])

    for k in range(len(members)):
        q_in(k).start()
    for k in range(len(members)):
        q_in(k).wait()
    for k in range(len(members)):
        qk = qg_scr[k].astype(jnp.float32) * scale       # [1, H, hd]
        for kv in range(n_kv):
            qf_scr[kv, k * G:(k + 1) * G, :] = \
                qk[:, kv * G:(kv + 1) * G].reshape(G, qk.shape[-1])
    m_scr, l_scr, acc_scr = state
    for kv in range(n_kv):
        m_scr[kv] = jnp.full((M, 1), NEG_INF, jnp.float32)
        l_scr[kv] = jnp.zeros((M, 1), jnp.float32)
        acc_scr[kv] = jnp.zeros(acc_scr.shape[1:], jnp.float32)

    def attend(first, half, left, wait, carry):
        heads = [(qf_scr[kv], *page_blocks(half, kv)) for kv in range(n_kv)]
        new = _attend_block(
            left, wait, heads, None, s_scr,
            tuple((m_scr[kv], l_scr[kv], acc_scr[kv]) for kv in range(n_kv)))
        for kv in range(n_kv):
            m_scr[kv], l_scr[kv], acc_scr[kv] = new[kv]
        return carry

    _walk_blocks(n_pages, block, dmas, attend, 0, half0, n_after)
    for k, r in enumerate(members):
        for kv in range(n_kv):
            for scr, st in zip(state, parked):
                rows = scr[kv, k * G:(k + 1) * G, :]
                st[r * n_kv + kv] = jnp.broadcast_to(rows, st.shape[1:])


def ragged_attend_auto(
    q: jax.Array,            # [NB·tq, H, hd]
    k_pool: jax.Array,       # [L, n_pages, page, KV·hd] — the stored pool
    v_pool: jax.Array,
    row_tables: jax.Array,
    block_meta: jax.Array,
    layer,                   # int32 scalar
    tq: int,
    sliding_window: Optional[int] = None,
    interpret: Optional[bool] = None,
    shard: Optional[tuple] = None,   # (mesh, tp_axis)
    k_scale: Optional[jax.Array] = None,   # [L, n_pages, KV, page] f32 —
    v_scale: Optional[jax.Array] = None,   # int8 pools (ISSUE 13)
    tiles: Optional[jax.Array] = None,     # [6, NT]: the tile kernel's
    tile: int = 0,                         # schedule (ragged_tiles)
    shared: Optional[jax.Array] = None,    # [2 + SHARED_ROWS, R]: the
                                           # decode call's (shared_walks)
) -> jax.Array:
    """Unified ragged attention dispatcher: Pallas kernel on TPU (or under
    ``interpret``), XLA gather reference elsewhere (CPU tier-1 — same
    numerics, no paging win). With ``shard``, runs per-tp-shard under
    shard_map: every head attends independently (whole GQA groups per
    shard — callers gate on divisibility), tables/metadata replicate, no
    collective; the pools' KV·hd lanes split into tp runs of whole
    kv-heads, and int8 scale pools shard on their KV axis beside them.
    ``k_scale``/``v_scale`` mark int8 pools and route to the in-kernel
    dequant / dequantizing reference. ``tiles`` and ``shared`` are
    schedules of the kernel's walk (replicated under ``shard``); the
    reference, which gathers, has no use for either."""
    if shard is not None:
        from jax.sharding import PartitionSpec as P
        mesh, tp_ax = shard
        head = P(None, tp_ax, None)              # [Tp, H, hd]
        kv = P(None, None, None, tp_ax)          # [L, n_pages, page, KV·hd]
        ins = [head, kv, kv, P(None, None), P(None, None), P()]
        args = [q, k_pool, v_pool, row_tables, block_meta,
                jnp.asarray(layer, jnp.int32)]
        if k_scale is not None:
            ins += [P(None, None, tp_ax, None)] * 2   # [L, n_pages, KV, page]
            args += [k_scale, v_scale]
        # the walk's schedule, whichever the call has: replicated
        plan = {"tiles": tiles, "shared": shared}
        plan = {k: v for k, v in plan.items() if v is not None}
        ins += [P(None, None)] * len(plan)
        args += list(plan.values())

        def inner(qq, kp, vp, rt, bm, ly, *rest):
            ks, vs = rest[:2] if k_scale is not None else (None, None)
            return ragged_attend_auto(
                qq, kp, vp, rt, bm, ly, tq=tq,
                sliding_window=sliding_window, interpret=interpret,
                k_scale=ks, v_scale=vs, tile=tile,
                **dict(zip(plan, rest[len(rest) - len(plan):])))
        # check_vma off: a pallas_call's outputs carry no varying-axes
        # annotation for the checker to verify
        return jax.shard_map(inner, mesh=mesh, in_specs=tuple(ins),
                             out_specs=head, check_vma=False)(*args)
    if _on_tpu() or interpret:
        return ragged_attend(q, k_pool, v_pool, row_tables, block_meta,
                             layer, tq=tq, sliding_window=sliding_window,
                             interpret=bool(interpret),
                             k_scale=k_scale, v_scale=v_scale,
                             tiles=tiles, tile=tile, shared=shared)
    return ragged_attend_ref(q, k_pool, v_pool, row_tables, block_meta,
                             layer, tq=tq, sliding_window=sliding_window,
                             k_scale=k_scale, v_scale=v_scale)


# ---------------------------------------------------------------------------
# LATENT ragged kernel: one shared key per token whose head is the value
# ---------------------------------------------------------------------------
#
# Latent attention (MLA) in its FOLDED form: the key up-projection is folded
# into the query and the value up-projection into the output, so every one
# of the H query heads attends to the SAME stored row per token,
# ``[c_kv | k_rope | 0-pad]`` (``lanes`` wide, models/config.LatentConfig),
# and the value is the row's first ``v_lanes`` lanes (``c_kv`` again). It is
# multi-query attention with one kv head, a key wider than the value, and
# ONE pool: the kernel streams a page once and uses it for both products.
# Same flat token-major contract, page tables, block meta and in-kernel
# normalisation as ``ragged_attend``; no window, no int8 pages. Both of its
# calls — the chunk forward's (tq = 8, below) and the decode program's
# (section "The latent DECODE walk") — move a block of pages a loop turn
# and attend it as one run of keys.


def ragged_attend_latent_ref(
    q: jax.Array,            # [NB·tq, H, lanes] folded queries
    pool: jax.Array,         # [L, n_pages, page, lanes] — the latent pool
    row_tables: jax.Array,   # [R, maxp] int32
    block_meta: jax.Array,   # [4, NB] int32: kv_len, qpos0, nq, row
    layer,                   # int32 scalar
    tq: int,
    v_lanes: int,
    scale: float,
    select: Optional[jax.Array] = None,   # [NB·tq, maxp·page] int32
) -> jax.Array:
    """XLA gather reference for the latent kernel (CPU serving path + the
    kernel's oracle): normalized output [NB·tq, H, v_lanes] f32. With
    ``select`` a query attends only where its row of it is nonzero (and
    the causal mask allows)."""
    kv_len, qpos0, nq, row = (block_meta[j][:, None, None]
                              for j in range(4))
    block_tables = row_tables[row[:, 0, 0]]                  # [NB, maxp]
    NB, maxp = block_tables.shape
    _, H, lanes = q.shape
    page = pool.shape[2]
    qb = q.astype(jnp.float32).reshape(NB, tq, H, lanes)
    k = pool[layer, block_tables].reshape(
        NB, maxp * page, lanes).astype(jnp.float32)
    scores = jnp.einsum("bthc,bsc->bhts", qb, k) * scale
    t_idx = jnp.arange(tq, dtype=jnp.int32)[None, :, None]
    s_idx = jnp.arange(maxp * page, dtype=jnp.int32)[None, None, :]
    mask = ((s_idx < kv_len) & (s_idx <= qpos0 + t_idx)
            & (t_idx < nq))
    if select is not None:
        mask = mask & (select.reshape(NB, tq, maxp * page) != 0)
    mask = mask[:, None]                                     # [NB,1,tq,S]
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1)                                  # [NB,H,tq]
    acc = jnp.einsum("bhts,bsc->bhtc", p, k[..., :v_lanes])
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.transpose(0, 2, 1, 3).reshape(NB * tq, H, v_lanes)


def _ragged_latent_kernel(tables_ref, meta_ref, layer_ref, q_ref, kv_hbm,
                          *refs, page: int, tq: int, v_lanes: int,
                          scale: float, selected: bool, block: int):
    """One tq-token block of the chunk forward: the owning row's visible
    latent pages ``block`` a loop turn through ``_walk_blocks`` (the next
    block's copies in flight, ONE DMA a page), each block attended as one
    run of ``block·page`` keys (``_latent_block``, section "The latent
    DECODE walk": the block's tq queries are a group whose every page is
    common), and the normalized output written in latent space. The page
    is the key at its full width and the value at its first ``v_lanes``
    lanes. Products take the operands in their stored type with float32
    accumulation; softmax is float32.

    ``selected``: one more input, the block's rows of the selection
    ``[1, tq, S]`` int32 (S a whole number of blocks), whole in VMEM; a
    query attends a key only where its row is nonzero. The walk is the
    causal one — every visible page is streamed and multiplied, the mask
    decides what the softmax sees — so it costs the dense walk's time.

    Scratch: kv_scr [2·block·page, lanes], two blocks of pages end to end,
    and a DMA semaphore a page; (m, l, acc)_scr the block's softmax state,
    query-major [tq·H, ·] (l a sum a lane, as the walks carry it): a
    carry that size is the register file, and lives here."""
    if selected:
        sel_ref, out_ref, kv_scr, sems, m_scr, l_scr, acc_scr = refs
    else:
        out_ref, kv_scr, sems, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(0)
    kv_len = meta_ref[0, i]
    qpos0 = meta_ref[1, i]
    nq = meta_ref[2, i]
    row = meta_ref[3, i]
    layer = layer_ref[0]
    keys = block * page
    # a block with no query walks nothing and writes zeros
    n = jnp.where(
        nq > 0, (jnp.minimum(kv_len, qpos0 + nq) + page - 1) // page, 0)
    H, lanes = q_ref.shape[2], q_ref.shape[3]

    def dmas(j, slot):
        return [pltpu.make_async_copy(
            kv_hbm.at[layer, tables_ref[row, j]],
            kv_scr.at[pl.ds(pl.multiple_of(slot * page, page), page)],
            sems.at[slot])]

    @pl.when(i == 0)
    def _():
        # a walk's last block may be partial: the slots behind it are
        # masked, never copied into, and must hold numbers
        kv_scr[...] = jnp.zeros(kv_scr.shape, kv_scr.dtype)

    _start_block(n, block, 0, dmas)
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    q = q_ref[0].reshape(tq * H, lanes)                  # query-major rows
    t = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def attend(first, half, left, wait, carry):
        _each(left, wait)
        # what the block's tq queries see of its keys, built ONCE at
        # [tq, keys], then a query's row for each of its H score rows
        s_idx = first * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1)
        seen = (s_idx < kv_len) & (s_idx <= qpos0 + t) & (t < nq)
        if selected:
            seen = seen & (sel_ref[0, :, pl.ds(
                pl.multiple_of(first * page, keys), keys)] != 0)
        valid = jnp.concatenate(
            [jnp.broadcast_to(seen[k:k + 1], (H, keys))
             for k in range(tq)], axis=0)
        m_scr[...], l_scr[...], acc_scr[...] = _latent_block(
            q, kv_scr[pl.ds(pl.multiple_of(half * page, keys), keys)],
            valid, m_scr[...], l_scr[...], acc_scr[...], scale=scale,
            v_lanes=v_lanes)
        return carry

    _walk_blocks(n, block, dmas, attend, 0)
    l = jnp.sum(l_scr[...], axis=1, keepdims=True)
    norm = acc_scr[...] / jnp.where(l > 0, l, 1.0)
    out_ref[0] = norm.reshape(tq, H, v_lanes).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tq", "v_lanes", "scale",
                                             "interpret", "walk_block"))
def ragged_attend_latent(
    q: jax.Array,            # [NB·tq, H, lanes] folded queries
    pool: jax.Array,         # [L, n_pages, page, lanes] — the latent pool
    row_tables: jax.Array,   # [R, maxp] int32
    block_meta: jax.Array,   # [4, NB] int32: kv_len, qpos0, nq, row
    layer,                   # int32 scalar: which layer's pages to stream
    tq: int,
    v_lanes: int,
    scale: float,
    interpret: bool = False,
    select: Optional[jax.Array] = None,   # [NB·tq, maxp·page] int32
    shared: Optional[jax.Array] = None,   # [2 + SHARED_ROWS, R] int32
    walk_block: Optional[int] = None,     # tests: pages a loop iteration
) -> jax.Array:
    """Pallas latent ragged attention (contract of
    ``ragged_attend_latent_ref``; output in the queries' type). The pool is
    passed whole, as stored, and stays in HBM; ``lanes`` and ``v_lanes``
    are multiples of 128 (config.LatentConfig.lanes pads the stored row).
    With ``shared`` (``shared_walks`` of the tables; the decode program's
    call, tq = 1 and block i row i's) the call is the DECODE walk of the
    section below: rows whose tables begin alike have their common pages
    streamed and multiplied once between them."""
    if shared is not None:
        return _latent_decode_walk(q, pool, row_tables, block_meta, layer,
                                   shared, tq=tq, v_lanes=v_lanes,
                                   scale=scale, interpret=interpret,
                                   select=select, walk_block=walk_block)
    Tp, H, lanes = q.shape
    NB = block_meta.shape[1]
    page = pool.shape[2]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    block = walk_block or latent_walk_pages(page)
    keys, rows, itemsize = block * page, tq * H, pool.dtype.itemsize
    kernel = functools.partial(_ragged_latent_kernel, page=page, tq=tq,
                               v_lanes=v_lanes, scale=scale,
                               selected=select is not None, block=block)
    # what the scratch, the blocks in flight, the pipelined q and output
    # blocks and a block's score tiles take of VMEM (the default scope is
    # 16 MiB, which 128 heads of 8 queries pass), as the decode walk does
    need = (2 * keys * lanes * itemsize
            + 2 * rows * (lanes + v_lanes) * itemsize
            + rows * (v_lanes + 2 * 128) * 4 + 3 * rows * keys * 4)
    more_specs, more = [], []
    if select is not None:
        # a walk's blocks begin at whole blocks of pages and the selection
        # is read a block at a time: it ends a whole block on
        S = -(-select.shape[1] // keys) * keys
        more_specs = [pl.BlockSpec((1, tq, S), lambda i, *_: (i, 0, 0))]
        more = [jnp.pad(select.astype(jnp.int32),
                        ((0, 0), (0, S - select.shape[1]))
                        ).reshape(NB, tq, S)]
        need += 2 * max(tq, 8) * S * 4           # a block pads to 8 sublanes
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                 # tables, meta, layer
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((1, tq, H, lanes), lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
                *more_specs,
            ],
            out_specs=[
                pl.BlockSpec((1, tq, H, v_lanes),
                             lambda i, *_: (i, 0, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((2 * keys, lanes), pool.dtype),
                            pltpu.SemaphoreType.DMA((2 * block,)),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, v_lanes), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((NB, tq, H, v_lanes), q.dtype)],
        interpret=interpret,
        # pinned: the trace shows `%ragged_attend_latent.<n>`, which the
        # benchmark's `^%ragged_attend` patterns match
        name="ragged_attend_latent",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, need + (8 << 20))
            if need > (12 << 20) else None),
    )(row_tables.astype(jnp.int32), block_meta.astype(jnp.int32), layer,
      q.astype(pool.dtype).reshape(NB, tq, H, lanes), pool, *more)[0]
    return out.reshape(NB * tq, H, v_lanes)


def ragged_attend_latent_auto(q, pool, row_tables, block_meta, layer, *,
                              tq: int, v_lanes: int, scale: float,
                              interpret: Optional[bool] = None,
                              select: Optional[jax.Array] = None,
                              shared: Optional[jax.Array] = None):
    """Latent attention dispatcher: the Pallas kernel on TPU (or under
    ``interpret``), the XLA gather reference elsewhere. A pool whose lanes
    are no multiple of 128 (tiny test models) takes the reference.
    ``shared`` is a schedule of the decode call's walk (``shared_walks``):
    the reference, which gathers, has no use for it."""
    aligned = pool.shape[-1] % 128 == 0 and v_lanes % 128 == 0
    if (_on_tpu() or interpret) and aligned:
        return ragged_attend_latent(q, pool, row_tables, block_meta, layer,
                                    tq=tq, v_lanes=v_lanes, scale=scale,
                                    interpret=bool(interpret), select=select,
                                    shared=shared)
    return ragged_attend_latent_ref(q, pool, row_tables, block_meta, layer,
                                    tq=tq, v_lanes=v_lanes, scale=scale,
                                    select=select)


# ---------------------------------------------------------------------------
# The latent DECODE walk (ISSUE 40): a group's pages once, a block a turn
# ---------------------------------------------------------------------------
#
# The decode program's call of the latent kernel is one program a one-token
# row, H score rows each (64 at A.X-K1, 128 at DeepSeek-V3.2), and rows that
# adopted one prompt walked its pages one after another, a page a turn: 5
# rows × 80 pages of 160 KiB a layer a step, 0.66 µs a page a row for 0.2 of
# bytes. With ``shared`` (the table of ``shared_walks``, section "The SHARED
# walk": the same contract, the same host half) the call is this kernel:
#
#   the group  the lowest row of a group streams the common pages ONCE and
#              multiplies each against all of the group's queries at once, a
#              left operand of members × H rows, every score row with its
#              own float32 online-softmax state; it parks each member's
#              (m, l, acc) in VMEM scratch that outlives the program, and
#              every row's program walks only the pages behind the shared
#              ones, from that state. A member is H score rows × ``lanes``
#              of multiplies a page, and a walk's cost follows its rows
#              (0.64 / 1.0 / 1.9 µs a page at 2 / 5 / 8 members of 128
#              heads), so a short group does not repeat its leader up to
#              SHARED_ROWS as the dense walk does: the walk is compiled at
#              ``LATENT_WALK_SIZES`` members and a group takes the first
#              that holds it (a table's tail repeats the leader: a group's
#              size is the members that differ from it).
#   a block    both walks move ``latent_walk_pages`` pages a loop turn
#              through ``_walk_blocks`` (the next block's copies in flight)
#              and attend a block as ONE run of keys: one q·kᵀ, one row
#              maximum, one exp, one p·v and one rescale of the [rows,
#              v_lanes] accumulator a block, where the walk of a page a
#              turn paid each a page (0.66 → 0.35 µs a page a row at 128
#              heads, 0.55 → 0.29 at 64). Operands as before — the stored
#              type with float32 accumulation, a float32 softmax — so a
#              row's output is its old walk's to the rounding of where the
#              maxima are taken. A walk's last block may be partial: its
#              tail is masked, never copied into (the scratch is zeroed
#              once a call so that it holds numbers).
#   select     a shared page is NOT wholly visible to every member: each
#              member's row of the selection is gathered beside its query
#              and masks its H score rows, as in the row's own walk.
#
# A row in no group reads shared[0, r] = 0 and walks all of its pages; a
# zero table is the walk with nothing shared. PERF.md §6, PR 40, has the
# readings.
#
# The chunk forward's call (tq = 8, no table: ``_ragged_latent_kernel``)
# walks the same way since ISSUE 44. Its block of 8 queries IS such a group
# — 8 members whose every page is common, tq·H score rows (512 at A.X-K1,
# 1,024 at DeepSeek-V3.2) — and it walked a page a turn with the old
# arithmetic: a [1,024, 128] score tile scaled, masked twice, one row
# maximum, one lane reduction of l and one rescale of the [1,024, 512]
# float32 accumulator a PAGE, each waiting on the last (PERF.md §6, PR 44,
# has the reading against the MXU's time for the page's multiplies). It now
# moves ``latent_walk_pages`` pages a turn through ``_walk_blocks`` and
# attends them as one run of keys through ``_latent_block``, its (m, l, acc)
# in VMEM scratch as a group's; a block's visibility is built once at
# [tq, keys] (kv_len, the causal mask, nq, the queries' rows of the
# selection) and a query's row of it broadcast to its H score rows.

# Members a group's walk is compiled at: a group takes the first that
# holds it, so an odd one multiplies one member's rows for nothing (0.2 µs
# a page at 128 heads). Every size compiled in cost 3.4 MB of kernel code a
# call against 0.17 before, 13 MiB of HBM and 11 s of a warm start at
# DeepSeek-V3.2's widths (PERF.md §6, PR 40); the scoring kernel, whose
# cost hardly follows its rows, takes two.
LATENT_WALK_SIZES = (2, 4, 6, 8)
INDEX_WALK_SIZES = (4, 8)
# Keys a block of the latent walks scores at once. The float32 score tile
# of a full group is members × H × keys × 4 bytes (2 MiB at 1,024 rows):
# 512 keys read 20% under 256 on the chip, 1,024 read 1.9–4.6× OVER.
_LATENT_WALK_KEYS = 512


def latent_walk_pages(page: int) -> int:
    """Pages a loop turn of the latent decode walks carries (4 at pages of
    128 tokens); the engine's count of loop turns reads it too."""
    return max(1, _LATENT_WALK_KEYS // page)


def _group_walks(shared_ref, meta_ref, i, sizes: tuple, walk) -> None:
    """Run ``walk(members)`` where row i LEADS a group of the shared-walk
    table with a live member, ``members`` the group's rows padded to the
    first of ``sizes`` that holds them (a table's tail repeats the leader:
    a group's size is the members that differ from it)."""
    members = [shared_ref[2 + k, i] for k in range(SHARED_ROWS)]
    live, size = meta_ref[2, members[0]], jnp.int32(1)
    for r in members[1:]:
        live = jnp.maximum(live, meta_ref[2, r])
        size = size + (r != members[0]).astype(jnp.int32)
    leads = (shared_ref[1, i] > 0) & (live > 0)
    for below, held in zip((0,) + sizes, sizes):
        @pl.when(leads & (size > below) & (size <= held))
        def _(held=held):
            walk(members[:held])


def _latent_block(q, kv, valid, m, l, acc, *, scale: float, v_lanes: int):
    """One block of a latent walk, its pages end to end as ONE run of keys:
    ``q`` [rows, lanes] in the stored type, ``kv`` [keys, lanes] (the key
    at full width, the value its first ``v_lanes`` lanes), ``valid`` [rows
    or 1, keys]. One row maximum, one exp and one rescale of acc a block;
    ``l`` stays a sum a LANE, [rows, 128], until the walk ends (a lane
    reduction a block less). Returns the updated (m, l, acc)."""
    rows, keys = q.shape[0], kv.shape[0]
    valid = jnp.broadcast_to(valid, (rows, keys))
    scores = jax.lax.dot_general(                        # [rows, keys]
        q, kv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid, scores, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + sum(p[:, at:at + 128] for at in range(0, keys, 128))
    pv = jax.lax.dot_general(                            # [rows, v_lanes]
        p.astype(kv.dtype), kv[:, :v_lanes],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l_new, acc * corr + pv


def _latent_decode_kernel(tables_ref, meta_ref, layer_ref, shared_ref,
                          q_ref, q_hbm, kv_hbm, *refs, page: int,
                          v_lanes: int, scale: float, selected: bool,
                          block: int, sizes: tuple):
    """Row i's program of the latent decode walk (section comment): the
    group's walk first where i leads one, then i's own pages behind the
    shared ones. Scalar-prefetched: tables_ref [R, maxp], meta_ref [4, R],
    layer_ref [1], shared_ref [2 + SHARED_ROWS, R]. q arrives twice, the
    row's block in VMEM and whole in HBM for the group's gather; so does
    the selection where the model has one.

    Scratch: kv_scr [2·block·page, lanes], two blocks of pages end to end,
    and a DMA semaphore a page (both walks); qg_scr [members·H, lanes] the
    group's queries, member-major; (m, l, acc)_scr the group's state in
    that layout while its walk runs; (m, l, acc)_st the same PARKED by row,
    [R, H, ·] (m in every lane of 128: a column loaded as such would be
    re-laid every block; l a sum a lane, as the walks carry it); the
    gather's semaphore; selg_scr [members, 1, maxp·page] the members' rows
    of the selection."""
    if selected:
        (sel_ref, sel_hbm, out_ref, kv_scr, sems, qg_scr, m_scr, l_scr,
         acc_scr, m_st, l_st, acc_st, g_sem, selg_scr) = refs
    else:
        (out_ref, kv_scr, sems, qg_scr, m_scr, l_scr, acc_scr, m_st, l_st,
         acc_st, g_sem) = refs
    i = pl.program_id(0)
    kv_len = meta_ref[0, i]
    qpos0 = meta_ref[1, i]
    nq = meta_ref[2, i]
    row = meta_ref[3, i]
    layer = layer_ref[0]
    H, lanes = q_ref.shape[2], q_ref.shape[3]
    keys = block * page
    p_lo = shared_ref[0, i]

    @pl.when(i == 0)
    def _():
        # a walk's last block may be partial: the slots behind it are
        # masked, never copied into, and must hold numbers
        kv_scr[...] = jnp.zeros(kv_scr.shape, kv_scr.dtype)

    def page_dmas(j, slot):
        return [pltpu.make_async_copy(
            kv_hbm.at[layer, tables_ref[row, j]],
            kv_scr.at[pl.ds(pl.multiple_of(slot * page, page), page)],
            sems.at[slot])]

    def block_of(half):
        return kv_scr[pl.ds(pl.multiple_of(half * page, keys), keys)]

    def kept(sel, at, j):
        """[1, keys]: where row ``at`` of ``sel`` keeps the keys of the
        block that begins with page j."""
        return sel[at, :, pl.ds(pl.multiple_of(j * page, page), keys)] != 0

    def group_walk(group):
        """The common pages once for ``group`` (a static count of member
        rows; a table's tail repeats the leader)."""
        rows = len(group) * H

        def gather(k):
            out = [pltpu.make_async_copy(q_hbm.at[group[k], 0],
                                         qg_scr.at[pl.ds(k * H, H)],
                                         g_sem.at[0])]
            if selected:
                out.append(pltpu.make_async_copy(
                    sel_hbm.at[group[k]], selg_scr.at[k], g_sem.at[0]))
            return out

        for k in range(len(group)):
            for d in gather(k):
                d.start()
        _start_block(p_lo, block, 0, page_dmas)
        m_scr[0:rows] = jnp.full((rows, 1), NEG_INF, jnp.float32)
        l_scr[0:rows] = jnp.zeros((rows, 128), jnp.float32)
        acc_scr[0:rows] = jnp.zeros((rows, v_lanes), jnp.float32)
        for k in range(len(group)):
            for d in gather(k):
                d.wait()

        def attend(first, half, left, wait, carry):
            _each(left, wait)
            # every shared page is whole and visible to every member, but
            # for what a member's selection drops; the last block's tail
            s_idx = first * page + jax.lax.broadcasted_iota(
                jnp.int32, (1, keys), 1)
            valid = s_idx < p_lo * page
            if selected:
                valid = jnp.concatenate(
                    [jnp.broadcast_to(valid & kept(selg_scr, k, first),
                                      (H, keys))
                     for k in range(len(group))], axis=0)
            m_scr[0:rows], l_scr[0:rows], acc_scr[0:rows] = _latent_block(
                qg_scr[0:rows], block_of(half), valid, m_scr[0:rows],
                l_scr[0:rows], acc_scr[0:rows], scale=scale,
                v_lanes=v_lanes)
            return carry

        _walk_blocks(p_lo, block, page_dmas, attend, 0)
        for k, r in enumerate(group):
            at = slice(k * H, (k + 1) * H)
            m_st[r] = jnp.broadcast_to(m_scr[at], m_st.shape[1:])
            l_st[r] = l_scr[at]
            acc_st[r] = acc_scr[at]

    _group_walks(shared_ref, meta_ref, i, sizes, group_walk)

    # the row's own walk: the pages behind the shared ones, from the state
    # the group's walk parked; a row that is done walks nothing
    kv_hi = jnp.minimum(kv_len, qpos0 + nq)
    n = jnp.where(nq > 0,
                  jnp.maximum((kv_hi + page - 1) // page - p_lo, 0), 0)

    def dmas(j, slot):
        return page_dmas(p_lo + j, slot)

    _start_block(n, block, 0, dmas)
    q = q_ref[0].reshape(H, lanes)
    carried = (p_lo > 0) & (nq > 0)
    init = tuple(
        jnp.where(carried, st, new) for st, new in zip(
            (jnp.max(m_st[i], axis=1, keepdims=True), l_st[i], acc_st[i]),
            (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 128), jnp.float32),
             jnp.zeros((H, v_lanes), jnp.float32))))

    def attend(first, half, left, wait, carry):
        _each(left, wait)
        s_idx = (p_lo + first) * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1)
        valid = (s_idx < kv_len) & (s_idx <= qpos0) & (nq > 0)
        if selected:
            valid = valid & kept(sel_ref, 0, p_lo + first)
        return _latent_block(q, block_of(half), valid, *carry, scale=scale,
                             v_lanes=v_lanes)

    _, l, acc = _walk_blocks(n, block, dmas, attend, init)
    l = jnp.sum(l, axis=1, keepdims=True)
    norm = acc / jnp.where(l > 0, l, 1.0)
    out_ref[0] = norm.reshape(1, H, v_lanes).astype(out_ref.dtype)


def _latent_decode_walk(q, pool, row_tables, block_meta, layer, shared, *,
                        tq: int, v_lanes: int, scale: float,
                        interpret: bool, select, walk_block):
    """``ragged_attend_latent`` with a shared-walk table: the pallas_call
    of ``_latent_decode_kernel`` (traced inside the caller's jit)."""
    R, H, lanes = q.shape
    page = pool.shape[2]
    assert tq == 1 and block_meta.shape[1] == R \
        and shared.shape == (2 + SHARED_ROWS, R), (tq, shared.shape, R)
    sizes = LATENT_WALK_SIZES
    assert sizes[-1] == SHARED_ROWS, sizes
    block = walk_block or latent_walk_pages(page)
    kernel = functools.partial(
        _latent_decode_kernel, page=page, v_lanes=v_lanes, scale=scale,
        selected=select is not None, block=block, sizes=sizes)
    qb = q.astype(pool.dtype).reshape(R, 1, H, lanes)
    wide = SHARED_ROWS * H
    more_specs, more, more_scr = [], [], []
    # what the scratch and the blocks in flight take of VMEM: the default
    # scope is 16 MiB, which 128 heads of parked state and eight members'
    # selections at 16k positions pass
    need = (2 * block * page * lanes * pool.dtype.itemsize
            + wide * lanes * pool.dtype.itemsize
            + (wide + R * H) * (v_lanes + 2 * 128) * 4
            + 3 * wide * block * page * 4 + 4 * H * (lanes + v_lanes) * 4)
    if select is not None:
        # a walk's last block may begin at the table's last page: the
        # selection is read a block at a time, so it ends a block's tail on
        S = select.shape[1] + (block - 1) * page
        sel = jnp.pad(select.astype(jnp.int32),
                      ((0, 0), (0, (block - 1) * page))).reshape(R, 1, S)
        more_specs = [pl.BlockSpec((1, 1, S), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)]
        more = [sel, sel]
        more_scr = [pltpu.VMEM((SHARED_ROWS, 1, S), jnp.int32)]
        need += (SHARED_ROWS + 2) * 8 * S * 4    # a row pads to 8 sublanes
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,          # tables, meta, layer, shared
            grid=(R,),
            in_specs=[
                pl.BlockSpec((1, 1, H, lanes), lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # q, for the gather
                pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
                *more_specs,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, H, v_lanes),
                             lambda i, *_: (i, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2 * block * page, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2 * block,)),
                pltpu.VMEM((wide, lanes), pool.dtype),
                pltpu.VMEM((wide, 1), jnp.float32),
                pltpu.VMEM((wide, 128), jnp.float32),
                pltpu.VMEM((wide, v_lanes), jnp.float32),
                pltpu.VMEM((R, H, 128), jnp.float32),
                pltpu.VMEM((R, H, 128), jnp.float32),
                pltpu.VMEM((R, H, v_lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((1,)),
                *more_scr],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, 1, H, v_lanes), q.dtype)],
        interpret=interpret,
        # pinned, as the walk without a table: `%ragged_attend_latent.<n>`
        name="ragged_attend_latent",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, need + (8 << 20))
            if select is not None or need > (12 << 20) else None),
    )(row_tables.astype(jnp.int32), block_meta.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), shared.astype(jnp.int32),
      qb, qb, pool, *more)[0]
    return out.reshape(R, H, v_lanes)


# ---------------------------------------------------------------------------
# INDEX scores: the cheap attention that selects a query's keys
# ---------------------------------------------------------------------------
#
# A model with an indexer (models/config.IndexerConfig) keeps ONE index key
# a token in a pool of its own, ``[L, n_pages, page, head_dim]`` under the
# latent pool's page ids. A query's score of a visible position is
# ``Σ_j w_j · ReLU(q_j · k)`` over the indexer's heads: the kernel below
# walks a block's row exactly as the attention kernels do (same tables,
# same block meta, one DMA a page of index keys — a fifth of a latent
# page's bytes) and writes the float32 scores of the block's queries
# against every position it walked, ``[NB·tq, maxp·page]``. Positions it
# did not walk (past the block's last query) hold whatever the buffer
# held: the caller masks by visibility before it selects. The decode
# program's call (tq = 1, with the shared-walk table) is
# ``_index_decode_kernel``: eight pages a turn scored as one run of keys,
# a group's common pages once for all of its members.


def index_scores_ref(
    q: jax.Array,            # [NB·tq, Hi, di] the indexer's queries
    w: jax.Array,            # [NB·tq, Hi] float32 head weights
    pool: jax.Array,         # [L, n_pages, page, di] — the index-key pool
    row_tables: jax.Array,   # [R, maxp] int32
    block_meta: jax.Array,   # [4, NB] int32: kv_len, qpos0, nq, row
    layer,                   # int32 scalar
    tq: int,
) -> jax.Array:
    """XLA gather reference for ``index_scores`` (CPU serving path + the
    kernel's oracle): [NB·tq, maxp·page] float32, every position of the
    row's table scored (the kernel leaves those past the causal walk
    unwritten)."""
    tables = row_tables[block_meta[3]]                       # [NB, maxp]
    NB, maxp = tables.shape
    _, Hi, di = q.shape
    page = pool.shape[2]
    k = pool[layer, tables].reshape(NB, maxp * page, di)
    dots = jnp.einsum("bthd,bsd->bths",
                      q.astype(pool.dtype).reshape(NB, tq, Hi, di), k,
                      preferred_element_type=jnp.float32)
    sc = jnp.einsum("bths,bth->bts", jnp.maximum(dots, 0.0),
                    w.astype(jnp.float32).reshape(NB, tq, Hi))
    return sc.reshape(NB * tq, maxp * page)


def _index_scores_kernel(tables_ref, meta_ref, layer_ref, q_ref, w_ref,
                         k_hbm, out_ref, k_scr, sems, *, page: int,
                         tq: int):
    """One tq-token block's index scores over its row's visible pages."""
    i = pl.program_id(0)
    kv_len = meta_ref[0, i]
    qpos0 = meta_ref[1, i]
    nq = meta_ref[2, i]
    row = meta_ref[3, i]
    layer = layer_ref[0]
    n = (jnp.minimum(kv_len, qpos0 + nq) + page - 1) // page
    Hi, di = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0].reshape(tq * Hi, di)                    # query-major rows
    w = w_ref[0]                                         # [tq·Hi, 1]

    def dma(j, slot):
        return pltpu.make_async_copy(
            k_hbm.at[layer, tables_ref[row, j]], k_scr.at[slot],
            sems.at[slot])

    @pl.when(n > 0)
    def _():
        dma(0, 0).start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n)
        def _():
            dma(j + 1, jax.lax.rem(j + 1, 2)).start()

        dma(j, slot).wait()
        dots = jax.lax.dot_general(                      # [tq·Hi, page]
            q, k_scr[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        heads = jnp.maximum(dots, 0.0) * w
        out_ref[0, :, pl.ds(pl.multiple_of(j * page, page), page)] = \
            jnp.concatenate(
                [heads[t * Hi:(t + 1) * Hi].sum(axis=0, keepdims=True)
                 for t in range(tq)], axis=0)            # [tq, page]
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _index_decode_kernel(tables_ref, meta_ref, layer_ref, shared_ref, q_ref,
                         w_ref, q_hbm, k_hbm, out_ref, k_scr, sems, qg_scr,
                         wg_scr, sc_st, g_sem, *, page: int, block: int,
                         sizes: tuple):
    """Row i's index scores in the decode program, with the shared walk of
    the latent decode kernel (section "The latent DECODE walk": the same
    table, the same groups): where i leads a group, the common pages of
    index keys are streamed once and scored against every member's heads
    at once, each member's row of scores PARKED in ``sc_st [R, 1, S]``;
    every row then copies its parked scores out and scores the pages
    behind them. A score is a sum of independent products — no state
    crosses a page — so a row's scores are those of its walk alone.
    Both walks carry ``block`` pages a turn (``walk_pages`` of a page of
    index keys), scored as one run of keys. ``w_ref`` is every row's head
    weights, whole in VMEM ([R, Hi, 1]: a column a row cannot be copied
    out of HBM by itself)."""
    i = pl.program_id(0)
    kv_len = meta_ref[0, i]
    qpos0 = meta_ref[1, i]
    nq = meta_ref[2, i]
    row = meta_ref[3, i]
    layer = layer_ref[0]
    Hi = q_ref.shape[2]
    keys = block * page
    p_lo = shared_ref[0, i]

    def page_dmas(j, slot):
        return [pltpu.make_async_copy(
            k_hbm.at[layer, tables_ref[row, j]],
            k_scr.at[pl.ds(pl.multiple_of(slot * page, page), page)],
            sems.at[slot])]

    def score(q, w, half, left, wait, stores):
        """A block's scores for ``len(stores)`` members: ``stores[k](b,
        [1, page])`` takes member k's scores of the block's page b."""
        _each(left, wait)
        dots = jax.lax.dot_general(                      # [rows, keys]
            q, k_scr[pl.ds(pl.multiple_of(half * page, keys), keys)],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        heads = jnp.maximum(dots, 0.0) * w
        for k, store in enumerate(stores):
            mine = heads[k * Hi:(k + 1) * Hi].sum(axis=0, keepdims=True)
            for b in range(block):
                @pl.when(b < left)
                def _(b=b, mine=mine, store=store):
                    store(b, mine[:, b * page:(b + 1) * page])

    def group_walk(group):
        rows = len(group) * Hi

        def gather(k):
            return pltpu.make_async_copy(q_hbm.at[group[k], 0],
                                         qg_scr.at[pl.ds(k * Hi, Hi)],
                                         g_sem.at[0])

        for k in range(len(group)):
            gather(k).start()
        _start_block(p_lo, block, 0, page_dmas)
        for k, r in enumerate(group):
            wg_scr[k * Hi:(k + 1) * Hi] = w_ref[r]
        for k in range(len(group)):
            gather(k).wait()

        def attend(first, half, left, wait, carry):
            def park(r):
                def store(b, sc):
                    sc_st[r, :, pl.ds(pl.multiple_of(
                        (first + b) * page, page), page)] = sc
                return store

            score(qg_scr[0:rows], wg_scr[0:rows], half, left, wait,
                  [park(r) for r in group])
            return carry

        _walk_blocks(p_lo, block, page_dmas, attend, 0)

    _group_walks(shared_ref, meta_ref, i, sizes, group_walk)

    n = jnp.where(nq > 0, jnp.maximum(
        (jnp.minimum(kv_len, qpos0 + nq) + page - 1) // page - p_lo, 0), 0)

    def dmas(j, slot):
        return page_dmas(p_lo + j, slot)

    _start_block(n, block, 0, dmas)

    def out_page(j):
        return (0, slice(None), pl.ds(pl.multiple_of(j * page, page), page))

    def adopt(j):
        out_ref[out_page(j)] = sc_st[i, :, pl.ds(
            pl.multiple_of(j * page, page), page)]

    _each(jnp.where(nq > 0, p_lo, 0), adopt)
    q = q_ref[0].reshape(Hi, q_ref.shape[3])
    w = w_ref[i]                                         # [Hi, 1]

    def attend(first, half, left, wait, carry):
        def store(b, sc):
            out_ref[out_page(p_lo + first + b)] = sc

        score(q, w, half, left, wait, [store])
        return carry

    _walk_blocks(n, block, dmas, attend, 0)


def _index_decode_walk(q, w, pool, row_tables, block_meta, layer, shared, *,
                       tq: int, interpret: bool, walk_block):
    """``index_scores`` with a shared-walk table: the pallas_call of
    ``_index_decode_kernel`` (traced inside the caller's jit)."""
    R, Hi, di = q.shape
    page = pool.shape[2]
    assert tq == 1 and block_meta.shape[1] == R \
        and shared.shape == (2 + SHARED_ROWS, R), (tq, shared.shape, R)
    S = row_tables.shape[1] * page
    block = walk_block or walk_pages(page * di * pool.dtype.itemsize)
    wide = SHARED_ROWS * Hi
    qb = q.astype(pool.dtype).reshape(R, 1, Hi, di)
    wb = w.astype(jnp.float32).reshape(R, Hi, 1)
    out = pl.pallas_call(
        functools.partial(_index_decode_kernel, page=page, block=block,
                          sizes=INDEX_WALK_SIZES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,      # tables, meta, layer, shared
            grid=(R,),
            in_specs=[
                pl.BlockSpec((1, 1, Hi, di), lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec((R, Hi, 1), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # q, for the gather
                pl.BlockSpec(memory_space=pl.ANY),   # pool stays in HBM
            ],
            out_specs=[pl.BlockSpec((1, 1, S), lambda i, *_: (i, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((2 * block * page, di), pool.dtype),
                pltpu.SemaphoreType.DMA((2 * block,)),
                pltpu.VMEM((wide, di), pool.dtype),
                pltpu.VMEM((wide, 1), jnp.float32),
                pltpu.VMEM((R, 1, S), jnp.float32),
                pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, 1, S), jnp.float32)],
        interpret=interpret,
        name="index_scores",               # pinned, as below
    )(row_tables.astype(jnp.int32), block_meta.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), shared.astype(jnp.int32),
      qb, wb, qb, pool)[0]
    return out.reshape(R, S)


@functools.partial(jax.jit, static_argnames=("tq", "interpret",
                                             "walk_block"))
def index_scores(q, w, pool, row_tables, block_meta, layer, tq: int,
                 interpret: bool = False,
                 shared: Optional[jax.Array] = None,
                 walk_block: Optional[int] = None) -> jax.Array:
    """Pallas index scores (contract of ``index_scores_ref`` on the
    positions a block can see). The pool stays in HBM, whole. With
    ``shared`` (``shared_walks`` of the tables; the decode program's call,
    tq = 1 and block i row i's) rows of a group have their common pages
    scored in one walk (``_index_decode_kernel``): the same scores."""
    if shared is not None:
        return _index_decode_walk(q, w, pool, row_tables, block_meta, layer,
                                  shared, tq=tq, interpret=interpret,
                                  walk_block=walk_block)
    Tp, Hi, di = q.shape
    NB = block_meta.shape[1]
    page = pool.shape[2]
    S = row_tables.shape[1] * page
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, page=page, tq=tq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                 # tables, meta, layer
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((1, tq, Hi, di), lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec((1, tq * Hi, 1), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
            ],
            out_specs=[pl.BlockSpec((1, tq, S), lambda i, *_: (i, 0, 0))],
            scratch_shapes=[pltpu.VMEM((2, page, di), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((NB, tq, S), jnp.float32)],
        interpret=interpret,
        # a name `^%ragged_attend` does not match: the attention kernel
        # stays the one such call a layer (the benchmark's step mark)
        name="index_scores",
    )(row_tables.astype(jnp.int32), block_meta.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(pool.dtype).reshape(NB, tq, Hi, di),
      w.astype(jnp.float32).reshape(NB, tq * Hi, 1), pool)[0]
    return out.reshape(NB * tq, S)


def index_scores_auto(q, w, pool, row_tables, block_meta, layer, *,
                      tq: int, interpret: Optional[bool] = None,
                      shared: Optional[jax.Array] = None):
    """Index-score dispatcher: the Pallas kernel on TPU (or under
    ``interpret``) where the key is whole lanes wide, else the reference
    (which gathers, and has no use for ``shared``)."""
    if (_on_tpu() or interpret) and pool.shape[-1] % 128 == 0:
        return index_scores(q, w, pool, row_tables, block_meta, layer,
                            tq=tq, interpret=bool(interpret), shared=shared)
    return index_scores_ref(q, w, pool, row_tables, block_meta, layer,
                            tq=tq)
