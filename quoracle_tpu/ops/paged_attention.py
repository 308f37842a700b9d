"""Ragged paged attention (PAPERS.md: Ragged Paged Attention,
arxiv 2604.15464 — pattern only, the kernels are written here for the
engine's page-pool layout). Two generations live in this file: the
PR-3/4 split kernels (paged decode piece ⊕ tail, paged prefill piece ⊕
dense chunk, merged by online-softmax partials) and the PR-8 UNIFIED
kernel (``ragged_attend``) that serves a token-major flattened batch of
mixed prefill+decode rows in ONE launch with no partials to merge — see
the "Unified RAGGED kernel" section below and ARCHITECTURE.md §10.

The paged KV session cache (models/generate.py SessionStore) keeps every
resident conversation as a PAGE LIST into one device pool. Until this op,
decode still gathered each batch row's pages into a contiguous working
cache ([B, maxp·page, ...] materialized in HBM) and attended over the
PADDED length. Here decode reads the pool directly:

  * the Pallas kernel walks each row's page table and streams only
    ceil(kv_len/page) pages through VMEM (double-buffered HBM DMA) — work
    is RAGGED, proportional to each row's real length, not the batch max;
  * newly generated tokens land in a small contiguous TAIL buffer
    ([B, max_new, ...]) whose attention is a dense partial;
  * the two pieces merge by online-softmax statistics (m, l, acc) — the
    same recipe ops/flash_attention.py uses across KV blocks.

So the decode loop's memory high-water drops from pool + working cache to
pool + tail, and a 32k-token session batch no longer materializes a second
copy of itself per call (SURVEY §7 hard part 2; NOTES_r03 gap 2).

Partial convention: (acc [.., hd] f32 UNNORMALIZED, m rowmax, l denom);
empty sets give (0, NEG_INF, 0) — NEG_INF is finite so merging an empty
partial is exact (exp(NEG_INF - NEG_INF) = 1 scales l = 0).

No reference counterpart: the reference never executes attention
(SURVEY.md §2.8 — all inference was remote HTTPS).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _on_tpu() -> bool:
    """Whether the dispatchers below pick the Pallas kernels (else the
    XLA gather references). One seam: an AOT compile for a described TPU
    runs under the CPU backend and steers it from the test."""
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Partials: dense pieces + merge (plain XLA)
# ---------------------------------------------------------------------------

def _partials_from_scores(scores: jax.Array, mask: jax.Array,
                          v: jax.Array) -> tuple:
    """scores [B, KV, G, S], mask broadcastable to it, v [B, KV, S, hd] →
    (acc [B, KV, G, hd], m [B, KV, G], l [B, KV, G]) f32 partials."""
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.where(jnp.broadcast_to(mask, scores.shape),
                  jnp.exp(scores - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bksd->bkgd", p, v)
    return acc, m, l


def _partials_from_scores_t(scores: jax.Array, mask: jax.Array,
                            v: jax.Array) -> tuple:
    """Multi-query variant: scores [B, KV, G, T, S], mask broadcastable to
    it, v [B, S, KV, hd] → partials reshaped to query-major layout
    (acc [B, T, H, hd], m [B, T, H], l [B, T, H]) f32. Shares the partial
    convention documented at the top of the file with
    _partials_from_scores — keep them in lockstep."""
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.where(jnp.broadcast_to(mask, scores.shape),
                  jnp.exp(scores - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgts,bskd->bkgtd", p, v.astype(jnp.float32))
    B, KV, G, T, hd = acc.shape
    acc = acc.transpose(0, 3, 1, 2, 4).reshape(B, T, KV * G, hd)
    return (acc, m.transpose(0, 3, 1, 2).reshape(B, T, KV * G),
            l.transpose(0, 3, 1, 2).reshape(B, T, KV * G))


def merge_partials(p1: tuple, p2: tuple) -> jax.Array:
    """Combine two online-softmax partials → normalized output (f32)."""
    a1, m1, l1 = p1
    a2, m2, l2 = p2
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    l = l1 * c1 + l2 * c2
    acc = a1 * c1[..., None] + a2 * c2[..., None]
    return acc / jnp.where(l > 0, l, 1.0)[..., None]


def _grouped(q: jax.Array, n_kv: int) -> jax.Array:
    """[B, H, hd] → [B, KV, G, hd] (GQA grouping, no repetition)."""
    b, h, hd = q.shape
    return q.reshape(b, n_kv, h // n_kv, hd)


def tail_attend_partials(
    q: jax.Array,          # [B, H, hd]
    tail_k: jax.Array,     # [B, Tmax, KV, hd]
    tail_v: jax.Array,     # [B, Tmax, KV, hd]
    tail_len,              # scalar or [B] int32: valid tail entries
    tail_pos0: jax.Array,  # [B] int32 absolute position of tail index 0
    q_pos: jax.Array,      # [B] int32
    sliding_window: Optional[int] = None,
) -> tuple:
    """Dense partials of the decode queries against the tail buffer."""
    B, H, hd = q.shape
    KV = tail_k.shape[2]
    scale = hd ** -0.5
    qg = _grouped(q.astype(jnp.float32) * scale, KV)     # [B, KV, G, hd]
    k = tail_k.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B, KV, T, hd]
    v = tail_v.astype(jnp.float32).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, k)
    idx = jnp.arange(tail_k.shape[1], dtype=jnp.int32)[None, :]   # [1, T]
    tl = jnp.broadcast_to(jnp.asarray(tail_len, jnp.int32),
                          (B,))[:, None]
    kv_pos = tail_pos0.astype(jnp.int32)[:, None] + idx
    mask = (idx < tl) & (kv_pos <= q_pos.astype(jnp.int32)[:, None])
    if sliding_window is not None:
        mask &= q_pos.astype(jnp.int32)[:, None] - kv_pos < sliding_window
    mask = mask[:, None, None, :]                         # [B, 1, 1, T]
    acc, m, l = _partials_from_scores(scores, mask, v)
    return (acc.reshape(B, H, hd), m.reshape(B, H), l.reshape(B, H))


# ---------------------------------------------------------------------------
# Paged piece: XLA reference (gathers pages — CPU tests / fallback)
# ---------------------------------------------------------------------------

def paged_attend_ref(
    q: jax.Array,          # [B, H, hd]
    k_pages: jax.Array,    # [n_pages, page, KV, hd]
    v_pages: jax.Array,
    tables: jax.Array,     # [B, maxp] int32
    kv_lens: jax.Array,    # [B] int32 valid POOL tokens per row
    kv_off: jax.Array,     # [B] int32 absolute position of pool index 0
    q_pos: jax.Array,      # [B] int32
    sliding_window: Optional[int] = None,
) -> tuple:
    """Partials of q against the paged pool, via a page gather. Used off-TPU
    and as the numerical oracle for the kernel."""
    B, H, hd = q.shape
    n_pages, page, KV, _ = k_pages.shape
    maxp = tables.shape[1]
    k = k_pages[tables].reshape(B, maxp * page, KV, hd)
    v = v_pages[tables].reshape(B, maxp * page, KV, hd)
    scale = hd ** -0.5
    qg = _grouped(q.astype(jnp.float32) * scale, KV)
    kT = k.astype(jnp.float32).transpose(0, 2, 1, 3)      # [B, KV, S, hd]
    vT = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, kT)
    idx = jnp.arange(maxp * page, dtype=jnp.int32)[None, :]
    kv_pos = idx + kv_off.astype(jnp.int32)[:, None]
    mask = (idx < kv_lens.astype(jnp.int32)[:, None]) \
        & (kv_pos <= q_pos.astype(jnp.int32)[:, None])
    if sliding_window is not None:
        mask &= q_pos.astype(jnp.int32)[:, None] - kv_pos < sliding_window
    mask = mask[:, None, None, :]
    acc, m, l = _partials_from_scores(scores, mask, vT)
    return (acc.reshape(B, H, hd), m.reshape(B, H), l.reshape(B, H))


# ---------------------------------------------------------------------------
# Paged piece: Pallas kernel (TPU)
# ---------------------------------------------------------------------------

def _paged_kernel(tables_ref, meta_ref, q_ref, k_hbm, v_hbm,
                  acc_ref, stats_ref, k_scr, v_scr, sems, *,
                  page: int, n_kv: int, hd: int, scale: float):
    """One batch row: stream this row's pages through VMEM double-buffered.

    Refs: tables_ref [B, maxp] / meta_ref [B, 4] (SMEM, scalar-prefetched;
    meta = kv_len, kv_off, q_pos, qlo where qlo = q_pos - window, or
    INT32_MIN); q_ref [1, H, hd] VMEM; k_hbm/v_hbm stay in HBM (ANY) as
    [n_pages, page, KV·hd] — the kv-head axis is FLATTENED into the lane
    dimension so every memref slice keeps Mosaic's (8, 128) tiling happy
    for any head count (KV = 14 broke the [page, KV, hd] layout), and
    per-head math uses static 128-aligned lane slices. The kernel DMAs
    page blocks on demand: VMEM holds 2 pages, not the row's history.
    """
    b = pl.program_id(0)
    kv_len = meta_ref[b, 0]
    kv_off = meta_ref[b, 1]
    q_pos = meta_ref[b, 2]
    qlo = meta_ref[b, 3]
    n = (kv_len + page - 1) // page                      # pages this row

    q = q_ref[0].astype(jnp.float32) * scale             # [H, hd]
    H = q.shape[0]
    G = H // n_kv

    def start_dma(j, slot):
        pid = tables_ref[b, j]
        pltpu.make_async_copy(k_hbm.at[pid], k_scr.at[slot],
                              sems.at[slot, 0]).start()
        pltpu.make_async_copy(v_hbm.at[pid], v_scr.at[slot],
                              sems.at[slot, 1]).start()

    def wait_dma(j, slot):
        pid = tables_ref[b, j]
        pltpu.make_async_copy(k_hbm.at[pid], k_scr.at[slot],
                              sems.at[slot, 0]).wait()
        pltpu.make_async_copy(v_hbm.at[pid], v_scr.at[slot],
                              sems.at[slot, 1]).wait()

    @pl.when(n > 0)
    def _():
        start_dma(0, 0)

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n)
        def _():
            start_dma(j + 1, jax.lax.rem(j + 1, 2))

        wait_dma(j, slot)
        k_blk = k_scr[slot].astype(jnp.float32)          # [page, KV·hd]
        v_blk = v_scr[slot].astype(jnp.float32)
        # per-kv-head static lane slices (hd is a 128 multiple)
        scores = jnp.concatenate([
            jax.lax.dot_general(                         # [G, page]
                q[kv * G:(kv + 1) * G],
                k_blk[:, kv * hd:(kv + 1) * hd],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for kv in range(n_kv)], axis=0)              # [H, page]
        idx = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        pos = idx + kv_off
        mask = (idx < kv_len) & (pos <= q_pos) & (pos > qlo)
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)  # [H, page]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.concatenate([
            jax.lax.dot_general(                         # [G, hd]
                p[kv * G:(kv + 1) * G],
                v_blk[:, kv * hd:(kv + 1) * hd],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for kv in range(n_kv)], axis=0)              # [H, hd]
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n, body, (m0, l0, acc0))
    acc_ref[0] = acc
    # (m, l) share one [2, H] stats block — TPU block shapes require the
    # trailing dims to tile or equal the array's, which a bare [1, H] block
    # can't satisfy for small H.
    stats_ref[0, 0] = m[:, 0]
    stats_ref[0, 1] = l[:, 0]


def _lane_flat_pools(k_pages: jax.Array, v_pages: jax.Array,
                     hd_p: int) -> tuple[jax.Array, jax.Array]:
    """One layer's ``[n_pages, page, KV, hd]`` view of the pool as the
    SPLIT kernels (paged_attend, paged_prefill_attend) take it: head_dim
    padded to the lane width and kv-heads flattened into the lane dim,
    [n_pages, page, KV·hd_p] — every Mosaic memref slice stays
    (8, 128)-tiled for ANY head count (KV = 14 is not sublane-tileable).
    Scope ``kv_layout``: on the TPU this merge of the two minor dims of
    a tiled array is no bitcast but a relayout copy of the layer's whole
    K and V pool per call (PERF.md §5, PR 24) — which is why the pool is
    STORED lane-flat (generate.py ``_ensure_pool``) and the unified
    kernel, the serving path, never comes here: ``ragged_attend`` reads
    the stored pool as it is. Only the split kernels, which no TPU cell
    runs, still pay it for their 4-D view."""
    with jax.named_scope("kv_layout"):
        n_pages, page, KV, hd = k_pages.shape
        if hd_p != hd:
            padkv = [(0, 0), (0, 0), (0, 0), (0, hd_p - hd)]
            k_pages = jnp.pad(k_pages, padkv)
            v_pages = jnp.pad(v_pages, padkv)
        return (k_pages.reshape(n_pages, page, KV * hd_p),
                v_pages.reshape(n_pages, page, KV * hd_p))


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret"))
def paged_attend(
    q: jax.Array,          # [B, H, hd]
    k_pages: jax.Array,    # [n_pages, page, KV, hd]
    v_pages: jax.Array,
    tables: jax.Array,     # [B, maxp] int32
    kv_lens: jax.Array,    # [B] int32
    kv_off: jax.Array,     # [B] int32
    q_pos: jax.Array,      # [B] int32
    sliding_window: Optional[int] = None,
    interpret: bool = False,
) -> tuple:
    """Pallas partials of q against the paged pool (same contract as
    paged_attend_ref; tests assert numerical agreement)."""
    B, H, hd = q.shape
    n_pages, page, KV, _ = k_pages.shape
    # lane alignment: pad head_dim to 128. Production models (config.py
    # catalog) all have hd = 128, so the pool pad below is a no-op there;
    # tiny test models pay a copy, which only interpret/validation runs see.
    hd_p = max(128, ((hd + 127) // 128) * 128)
    if hd_p != hd:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, hd_p - hd)])
    kf, vf = _lane_flat_pools(k_pages, v_pages, hd_p)
    window = sliding_window
    qlo = (q_pos.astype(jnp.int32) - jnp.int32(window) if window is not None
           else jnp.full_like(q_pos, jnp.iinfo(jnp.int32).min))
    meta = jnp.stack([kv_lens.astype(jnp.int32),
                      kv_off.astype(jnp.int32),
                      q_pos.astype(jnp.int32),
                      qlo.astype(jnp.int32)], axis=1)     # [B, 4]
    scale = hd ** -0.5

    kernel = functools.partial(_paged_kernel, page=page, n_kv=KV, hd=hd_p,
                               scale=scale)
    acc, stats = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                        # tables, meta
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, hd_p), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),     # k pool in HBM
                pl.BlockSpec(memory_space=pl.ANY),     # v pool in HBM
            ],
            out_specs=[
                pl.BlockSpec((1, H, hd_p), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, 2, H), lambda b, *_: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, page, KV * hd_p), k_pages.dtype),
                pltpu.VMEM((2, page, KV * hd_p), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, hd_p), jnp.float32),
            jax.ShapeDtypeStruct((B, 2, H), jnp.float32),
        ],
        interpret=interpret,
        # pinned: the trace shows the kernel as `%paged_attend.<n>`, and
        # the benchmark's metric files match on that name
        name="paged_attend",
    )(tables.astype(jnp.int32), meta, q, kf, vf)
    return acc[..., :hd], stats[:, 0], stats[:, 1]


def chunk_attend_partials(
    q: jax.Array,          # [B, T, H, hd] (prefill chunk queries)
    k: jax.Array,          # [B, T, KV, hd] (the chunk's own KV)
    v: jax.Array,
    chunk_lens: jax.Array,  # [B] int32 valid chunk tokens per row
    sliding_window: Optional[int] = None,
) -> tuple:
    """Dense causal partials of the chunk against ITSELF (the paged-prefill
    counterpart of tail_attend_partials). Both sides share the row's
    absolute offset (kv_off + prefix), so causality reduces to s <= t and
    the window to t - s < W — no absolute positions needed. fp32, O(T²)
    scores: the direct-prefill gate caps the chunk size (resumed rounds
    splice most of the prompt; long FRESH prefills are dense already and
    never gather, so they stay on the standard path)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    scale = hd ** -0.5
    qg = (q.astype(jnp.float32) * scale).reshape(B, T, KV, H // KV, hd)
    kT = k.astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, kT)       # [B,KV,G,T,S]
    t_idx = jnp.arange(T, dtype=jnp.int32)
    causal = t_idx[:, None] >= t_idx[None, :]              # [T, S]
    valid = t_idx[None, :] < chunk_lens.astype(jnp.int32)[:, None]  # [B, S]
    mask = causal[None, :, :] & valid[:, None, :]
    if sliding_window is not None:
        mask &= (t_idx[:, None] - t_idx[None, :]
                 < sliding_window)[None, :, :]
    mask = mask[:, None, None, :, :]                       # [B,1,1,T,S]
    return _partials_from_scores_t(scores, mask, v)


def paged_prefill_attend_ref(
    q: jax.Array,          # [B, T, H, hd] (chunk queries)
    k_pages: jax.Array,    # [n_pages, page, KV, hd]
    v_pages: jax.Array,
    tables: jax.Array,     # [B, maxp] int32
    kv_lens: jax.Array,    # [B] int32 resident PREFIX tokens per row
    sliding_window: Optional[int] = None,
) -> tuple:
    """Partials of the whole chunk against the resident pool prefix, via a
    page gather (CPU tests / fallback oracle for the kernel). Every pool
    token precedes every chunk token (the chunk starts at buffer index
    kv_lens), so causality is just s < kv_len; the window uses the shared
    offset: q_abs - s_abs = kv_len + t - s."""
    B, T, H, hd = q.shape
    n_pages, page, KV, _ = k_pages.shape
    maxp = tables.shape[1]
    k = k_pages[tables].reshape(B, maxp * page, KV, hd)
    v = v_pages[tables].reshape(B, maxp * page, KV, hd)
    scale = hd ** -0.5
    qg = (q.astype(jnp.float32) * scale).reshape(B, T, KV, H // KV, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32))
    s_idx = jnp.arange(maxp * page, dtype=jnp.int32)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    kl = kv_lens.astype(jnp.int32)[:, None, None]          # [B,1,1]
    mask = jnp.broadcast_to(s_idx[None, None, :] < kl,
                            (B, T, maxp * page))
    if sliding_window is not None:
        dist = (kl + t_idx[None, :, None]) - s_idx[None, None, :]
        mask &= dist < sliding_window
    mask = mask[:, None, None, :, :]                       # [B,1,1,T,S]
    return _partials_from_scores_t(scores, mask, v)


def _paged_prefill_kernel(tables_ref, meta_ref, q_ref, k_hbm, v_hbm,
                          acc_ref, stats_ref, k_scr, v_scr, sems, *,
                          page: int, n_kv: int, hd: int, t_blk: int,
                          scale: float, window: int):
    """One (batch row, T-block): stream the row's PREFIX pages through VMEM
    double-buffered (same DMA/layout recipe as _paged_kernel — kv heads
    flattened into the lane dim) and accumulate online-softmax partials
    for every query in the block at once — ONE launch per layer per
    chunk, not per token: the launch overhead that makes the decode
    kernel lose at small batch amortizes over the whole chunk here."""
    b = pl.program_id(0)
    tb = pl.program_id(1)
    kv_len = meta_ref[b, 0]
    n = (kv_len + page - 1) // page

    q = q_ref[0].astype(jnp.float32) * scale             # [Tb, H, hd]
    Tb = q.shape[0]
    H = q.shape[1]
    G = H // n_kv

    def start_dma(j, slot):
        pid = tables_ref[b, j]
        pltpu.make_async_copy(k_hbm.at[pid], k_scr.at[slot],
                              sems.at[slot, 0]).start()
        pltpu.make_async_copy(v_hbm.at[pid], v_scr.at[slot],
                              sems.at[slot, 1]).start()

    def wait_dma(j, slot):
        pid = tables_ref[b, j]
        pltpu.make_async_copy(k_hbm.at[pid], k_scr.at[slot],
                              sems.at[slot, 0]).wait()
        pltpu.make_async_copy(v_hbm.at[pid], v_scr.at[slot],
                              sems.at[slot, 1]).wait()

    @pl.when(n > 0)
    def _():
        start_dma(0, 0)

    # Window validity shared by every kv head: q_abs - s_abs = kv_len + t - s
    # (the row's absolute offset cancels on both sides). Built at its
    # final shape: Mosaic has no [Tb, G] → [Tb·G, 1] cast.
    t_of_row = tb * t_blk + jax.lax.broadcasted_iota(
        jnp.int32, (Tb * G, 1), 0) // G

    def body(j, carry):
        # carry: per-kv-head tuples of (m [Tb·G,1], l [Tb·G,1], acc [Tb·G,hd])
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n)
        def _():
            start_dma(j + 1, jax.lax.rem(j + 1, 2))

        wait_dma(j, slot)
        k_blk = k_scr[slot].astype(jnp.float32)          # [page, KV·hd]
        v_blk = v_scr[slot].astype(jnp.float32)
        s_idx = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)                     # [1, page]
        valid = s_idx < kv_len
        if window >= 0:
            valid = valid & (kv_len + t_of_row - s_idx < window)
        out = []
        for kv in range(n_kv):
            m, l, acc = carry[kv]
            scores = jax.lax.dot_general(                # [Tb·G, page]
                q[:, kv * G:(kv + 1) * G].reshape(Tb * G, hd),
                k_blk[:, kv * hd:(kv + 1) * hd],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            scores = jnp.where(valid, scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(                    # [Tb·G, hd]
                p, v_blk[:, kv * hd:(kv + 1) * hd],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc * corr + pv))
        return tuple(out)

    init = tuple((jnp.full((Tb * G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((Tb * G, 1), jnp.float32),
                  jnp.zeros((Tb * G, hd), jnp.float32))
                 for _ in range(n_kv))
    final = jax.lax.fori_loop(0, n, body, init)
    for kv in range(n_kv):
        m, l, acc = final[kv]
        acc_ref[0, :, kv * G:(kv + 1) * G] = acc.reshape(Tb, G, hd)
        stats_ref[0, :, 0, kv * G:(kv + 1) * G] = m.reshape(Tb, G)
        stats_ref[0, :, 1, kv * G:(kv + 1) * G] = l.reshape(Tb, G)


def _prefill_t_blk(row_elems: int) -> int:
    """Queries per block of the paged-prefill kernel for a query row of
    ``row_elems`` = H·hd elements. The kernel holds about 36 bytes per
    (query, element) — double-buffered q and outputs plus the f32
    accumulators — so 64 × 4096 (Mistral-7B, Gemma-7B) takes ~9.5 MiB of
    the v5e's 16 MiB scoped VMEM and 128 × 4096 is refused at 18.8 MiB
    (AOT compile, tests/test_kernels_compile_tpu.py)."""
    t = 128
    while t > 8 and t * row_elems > (1 << 18):
        t //= 2
    return t


@functools.partial(jax.jit, static_argnames=("sliding_window", "interpret",
                                             "t_blk"))
def paged_prefill_attend(
    q: jax.Array,          # [B, T, H, hd] (chunk queries)
    k_pages: jax.Array,    # [n_pages, page, KV, hd]
    v_pages: jax.Array,
    tables: jax.Array,     # [B, maxp] int32
    kv_lens: jax.Array,    # [B] int32 resident prefix tokens
    sliding_window: Optional[int] = None,
    interpret: bool = False,
    t_blk: Optional[int] = None,
) -> tuple:
    """Pallas partials of a whole prefill chunk against the paged pool
    (same contract as paged_prefill_attend_ref; tests assert agreement).
    Grid is (B, T/t_blk): each launch streams the row's prefix pages once
    for t_blk queries — launch cost amortizes over the chunk. ``t_blk``
    defaults to the largest power of two that keeps the block's q, f32
    accumulators and outputs inside the 16 MiB scoped VMEM."""
    B, T, H, hd = q.shape
    n_pages, page, KV, _ = k_pages.shape
    hd_p = max(128, ((hd + 127) // 128) * 128)
    if t_blk is None:
        t_blk = _prefill_t_blk(H * hd_p)
    if hd_p != hd:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, 0), (0, hd_p - hd)])
    t_blk = min(t_blk, T)
    if T % t_blk:
        pad_t = t_blk - T % t_blk
        q = jnp.pad(q, [(0, 0), (0, pad_t), (0, 0), (0, 0)])
    Tp = q.shape[1]
    kf, vf = _lane_flat_pools(k_pages, v_pages, hd_p)
    meta = kv_lens.astype(jnp.int32)[:, None]            # [B, 1]
    scale = hd ** -0.5
    kernel = functools.partial(
        _paged_prefill_kernel, page=page, n_kv=KV, hd=hd_p, t_blk=t_blk,
        scale=scale,
        window=-1 if sliding_window is None else int(sliding_window))
    acc, stats = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                        # tables, meta
            grid=(B, Tp // t_blk),
            in_specs=[
                pl.BlockSpec((1, t_blk, H, hd_p),
                             lambda b, tb, *_: (b, tb, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, t_blk, H, hd_p),
                             lambda b, tb, *_: (b, tb, 0, 0)),
                pl.BlockSpec((1, t_blk, 2, H),
                             lambda b, tb, *_: (b, tb, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, page, KV * hd_p), k_pages.dtype),
                pltpu.VMEM((2, page, KV * hd_p), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, H, hd_p), jnp.float32),
            jax.ShapeDtypeStruct((B, Tp, 2, H), jnp.float32),
        ],
        interpret=interpret,
        # pinned: the trace shows the kernel as `%paged_prefill_attend.<n>`, and
        # the benchmark's metric files match on that name
        name="paged_prefill_attend",
    )(tables.astype(jnp.int32), meta, q, kf, vf)
    return (acc[:, :T, :, :hd], stats[:, :T, 0], stats[:, :T, 1])


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
# ceil(visible/page) pages through VMEM (double-buffered, kv heads
# flattened into lanes exactly like _paged_kernel) and normalizes the
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


def _ragged_kernel(tables_ref, meta_ref, layer_ref, q_ref, k_hbm, v_hbm,
                   *refs, page: int, n_kv: int, hd: int, tq: int,
                   scale: float, window: int, quant: bool):
    """One tq-token block of the flattened batch: stream the owning row's
    VISIBLE pages through VMEM double-buffered (same DMA/layout recipe as
    _paged_kernel — kv heads flattened into the lane dim) and write the
    NORMALIZED attention output for the block. With the chunk KV already
    scattered into the pages there is no second partial to merge, so the
    online-softmax accumulator normalizes in-kernel.

    Scalar-prefetched (SMEM): tables_ref [R, maxp] one page table per ROW,
    meta_ref [4, NB] per-block (kv_len, qpos0, nq, row), layer_ref [1] the
    layer whose pages to stream — the pools stay in HBM WHOLE,
    [L, n_pages, page, KV·hd], and a page's DMA source is
    ``pool.at[layer, pid]``. A per-block copy of the table, or a [NB, 3]
    meta (SMEM pads the minor dim to 128 lanes), overflows the v5e's
    1 MiB of SMEM at an 8k-token tick.

    ``quant`` (int8 pools, ISSUE 13): each page's fp32 scale block
    ``[KV, page]`` rides the SAME double-buffered DMA stream, and the
    dequant happens inside the streaming loop with zero lane transposes:
    K's per-token scale multiplies the score columns
    (``q·(k·s) = (q·k)·s``) and V's multiplies the probability columns
    (``(p·s)·v = p·(v·s)``), both as a ``[1, page]`` lane broadcast."""
    if quant:
        ks_hbm, vs_hbm, out_ref, k_scr, v_scr, ks_scr, vs_scr, sems = refs
        streams = ((k_hbm, k_scr), (v_hbm, v_scr),
                   (ks_hbm, ks_scr), (vs_hbm, vs_scr))
    else:
        out_ref, k_scr, v_scr, sems = refs
        streams = ((k_hbm, k_scr), (v_hbm, v_scr))
    i = pl.program_id(0)
    kv_len = meta_ref[0, i]
    qpos0 = meta_ref[1, i]
    nq = meta_ref[2, i]
    row = meta_ref[3, i]
    layer = layer_ref[0]
    # last visible key + 1: nothing past the block's last query is visible
    kv_hi = jnp.minimum(kv_len, qpos0 + nq)
    if window >= 0:
        p_lo = jnp.maximum(qpos0 + 1 - window, 0) // page
    else:
        p_lo = jnp.int32(0)
    n = jnp.maximum((kv_hi + page - 1) // page - p_lo, 0)

    q = q_ref[0].astype(jnp.float32) * scale             # [tq, H, hd]
    H = q.shape[1]
    G = H // n_kv

    def dmas(j, slot):
        pid = tables_ref[row, p_lo + j]
        return [pltpu.make_async_copy(hbm.at[layer, pid], scr.at[slot],
                                      sems.at[slot, s])
                for s, (hbm, scr) in enumerate(streams)]

    @pl.when(n > 0)
    def _():
        for d in dmas(0, 0):
            d.start()

    # per-score-row query index (tq·G rows, query-major like the prefill
    # kernel) → buffer position and validity shared by every kv head.
    # Built at its final shape: Mosaic has no [tq, G] → [tq·G, 1] cast.
    t_of_row = jax.lax.broadcasted_iota(jnp.int32, (tq * G, 1), 0) // G
    qpos = qpos0 + t_of_row                              # [tq·G, 1]
    q_ok = t_of_row < nq

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n)
        def _():
            for d in dmas(j + 1, jax.lax.rem(j + 1, 2)):
                d.start()

        for d in dmas(j, slot):
            d.wait()
        k_blk = k_scr[slot].astype(jnp.float32)          # [page, KV·hd]
        v_blk = v_scr[slot].astype(jnp.float32)
        if quant:
            ks_blk = ks_scr[slot]                        # [KV, page] f32
            vs_blk = vs_scr[slot]
        s_idx = (p_lo + j) * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)                     # [1, page]
        valid = (s_idx < kv_len) & (s_idx <= qpos) & q_ok
        if window >= 0:
            valid = valid & (qpos - s_idx < window)
        out = []
        for kv in range(n_kv):
            m, l, acc = carry[kv]
            scores = jax.lax.dot_general(                # [tq·G, page]
                q[:, kv * G:(kv + 1) * G].reshape(tq * G, hd),
                k_blk[:, kv * hd:(kv + 1) * hd],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if quant:
                scores = scores * ks_blk[kv:kv + 1, :]   # dequant K
            scores = jnp.where(valid, scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * vs_blk[kv:kv + 1, :]             # dequant V
            pv = jax.lax.dot_general(                    # [tq·G, hd]
                p, v_blk[:, kv * hd:(kv + 1) * hd],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc * corr + pv))
        return tuple(out)

    init = tuple((jnp.full((tq * G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((tq * G, 1), jnp.float32),
                  jnp.zeros((tq * G, hd), jnp.float32))
                 for _ in range(n_kv))
    final = jax.lax.fori_loop(0, n, body, init)
    for kv in range(n_kv):
        _, l, acc = final[kv]
        norm = acc / jnp.where(l > 0, l, 1.0)
        out_ref[0, :, kv * G:(kv + 1) * G] = norm.reshape(tq, G, hd)


@functools.partial(jax.jit, static_argnames=("tq", "sliding_window",
                                             "interpret"))
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
) -> jax.Array:
    """Pallas unified ragged attention (same contract as ragged_attend_ref;
    tests/test_ragged_attention.py asserts numerical agreement). Grid is
    (NB,) — sized by the tick's real tokens / tq, never by batch × max.
    The pools are passed whole, as stored, and stay in HBM: the kernel
    indexes ``layer`` itself, so nothing of a pool's size is sliced,
    reshaped or copied on the way in. With ``k_scale``/``v_scale`` the
    kernel streams each int8 page's scale block alongside its payload and
    dequantizes in-loop."""
    Tp, H, hd = q.shape
    NB = block_meta.shape[1]
    _, n_pages, page, lanes = k_pool.shape
    KV = lanes // hd
    quant = k_scale is not None
    layer = jnp.asarray(layer, jnp.int32).reshape(())
    pools = [k_pool, v_pool]
    if quant:
        pools += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    hd_p = max(128, ((hd + 127) // 128) * 128)
    if hd_p != hd:
        # tiny test models only (every production head_dim is a lane
        # multiple and never comes here): the one layer the call reads,
        # its head_dim zero-padded to the lane width, read as layer 0
        q = jnp.pad(q, [(0, 0), (0, 0), (0, hd_p - hd)])
        one = [jax.lax.dynamic_index_in_dim(p, layer, 0, keepdims=False)
               for p in pools]
        kf, vf = _lane_flat_pools(*(p.reshape(n_pages, page, KV, hd)
                                    for p in one[:2]), hd_p)
        pools = [p[None] for p in (kf, vf, *one[2:])]
        layer = jnp.zeros((), jnp.int32)
    qb = q.reshape(NB, tq, H, hd_p)
    kernel = functools.partial(
        _ragged_kernel, page=page, n_kv=KV, hd=hd_p, tq=tq,
        scale=hd ** -0.5, quant=quant,
        window=-1 if sliding_window is None else int(sliding_window))
    scratch = [pltpu.VMEM((2, page, KV * hd_p), k_pool.dtype),
               pltpu.VMEM((2, page, KV * hd_p), v_pool.dtype)]
    if quant:
        scratch += [pltpu.VMEM((2, KV, page), jnp.float32)] * 2
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                 # tables, meta, layer
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((1, tq, H, hd_p), lambda i, *_: (i, 0, 0, 0)),
                *[pl.BlockSpec(memory_space=pl.ANY)       # pools stay in HBM
                  for _ in pools],
            ],
            out_specs=[
                pl.BlockSpec((1, tq, H, hd_p), lambda i, *_: (i, 0, 0, 0)),
            ],
            scratch_shapes=[*scratch,
                            pltpu.SemaphoreType.DMA((2, len(pools)))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((NB, tq, H, hd_p), jnp.float32),
        ],
        interpret=interpret,
        # pinned: the trace shows the kernel as `%ragged_attend.<n>`, and
        # the benchmark's metric files match on that name
        name="ragged_attend",
    )(row_tables.astype(jnp.int32), block_meta.astype(jnp.int32),
      layer.reshape(1), qb, *pools)[0]
    return out.reshape(NB * tq, H, hd_p)[..., :hd]


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
) -> jax.Array:
    """Unified ragged attention dispatcher: Pallas kernel on TPU (or under
    ``interpret``), XLA gather reference elsewhere (CPU tier-1 — same
    numerics, no paging win). With ``shard``, runs per-tp-shard under
    shard_map: every head attends independently (whole GQA groups per
    shard — callers gate on divisibility), tables/metadata replicate, no
    collective; the pools' KV·hd lanes split into tp runs of whole
    kv-heads, and int8 scale pools shard on their KV axis beside them.
    ``k_scale``/``v_scale`` mark int8 pools and route to the in-kernel
    dequant / dequantizing reference."""
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

        def inner(qq, kp, vp, rt, bm, ly, ks=None, vs=None):
            return ragged_attend_auto(
                qq, kp, vp, rt, bm, ly, tq=tq,
                sliding_window=sliding_window, interpret=interpret,
                k_scale=ks, v_scale=vs)
        # check_vma off: a pallas_call's outputs carry no varying-axes
        # annotation for the checker to verify
        return jax.shard_map(inner, mesh=mesh, in_specs=tuple(ins),
                             out_specs=head, check_vma=False)(*args)
    if _on_tpu() or interpret:
        return ragged_attend(q, k_pool, v_pool, row_tables, block_meta,
                             layer, tq=tq, sliding_window=sliding_window,
                             interpret=bool(interpret),
                             k_scale=k_scale, v_scale=v_scale)
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
# normalisation as ``ragged_attend``; no window, no int8 pages.


def ragged_attend_latent_ref(
    q: jax.Array,            # [NB·tq, H, lanes] folded queries
    pool: jax.Array,         # [L, n_pages, page, lanes] — the latent pool
    row_tables: jax.Array,   # [R, maxp] int32
    block_meta: jax.Array,   # [4, NB] int32: kv_len, qpos0, nq, row
    layer,                   # int32 scalar
    tq: int,
    v_lanes: int,
    scale: float,
) -> jax.Array:
    """XLA gather reference for the latent kernel (CPU serving path + the
    kernel's oracle): normalized output [NB·tq, H, v_lanes] f32."""
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
            & (t_idx < nq))[:, None]                         # [NB,1,tq,S]
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1)                                  # [NB,H,tq]
    acc = jnp.einsum("bhts,bsc->bhtc", p, k[..., :v_lanes])
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.transpose(0, 2, 1, 3).reshape(NB * tq, H, v_lanes)


def _ragged_latent_kernel(tables_ref, meta_ref, layer_ref, q_ref, kv_hbm,
                          out_ref, kv_scr, sems, *, page: int, tq: int,
                          v_lanes: int, scale: float):
    """One tq-token block: stream the owning row's visible latent pages
    through VMEM double-buffered, ONE DMA a page, and write the normalized
    output in latent space. The page is the key at its full width and the
    value at its first ``v_lanes`` lanes. Products take the operands in
    their stored type with float32 accumulation; softmax is float32."""
    i = pl.program_id(0)
    kv_len = meta_ref[0, i]
    qpos0 = meta_ref[1, i]
    nq = meta_ref[2, i]
    row = meta_ref[3, i]
    layer = layer_ref[0]
    kv_hi = jnp.minimum(kv_len, qpos0 + nq)
    n = (kv_hi + page - 1) // page
    H, lanes = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0].reshape(tq * H, lanes)                  # query-major rows

    def dma(j, slot):
        return pltpu.make_async_copy(
            kv_hbm.at[layer, tables_ref[row, j]], kv_scr.at[slot],
            sems.at[slot])

    @pl.when(n > 0)
    def _():
        dma(0, 0).start()

    t_of_row = jax.lax.broadcasted_iota(jnp.int32, (tq * H, 1), 0) // H
    qpos = qpos0 + t_of_row                              # [tq·H, 1]
    q_ok = t_of_row < nq

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n)
        def _():
            dma(j + 1, jax.lax.rem(j + 1, 2)).start()

        dma(j, slot).wait()
        kv = kv_scr[slot]                                # [page, lanes]
        s_idx = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)
        valid = (s_idx < kv_len) & (s_idx <= qpos) & q_ok
        scores = jax.lax.dot_general(                    # [tq·H, page]
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(                        # [tq·H, v_lanes]
            p.astype(kv.dtype), kv[:, :v_lanes],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    init = (jnp.full((tq * H, 1), NEG_INF, jnp.float32),
            jnp.zeros((tq * H, 1), jnp.float32),
            jnp.zeros((tq * H, v_lanes), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n, body, init)
    norm = acc / jnp.where(l > 0, l, 1.0)
    out_ref[0] = norm.reshape(tq, H, v_lanes).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tq", "v_lanes", "scale",
                                             "interpret"))
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
) -> jax.Array:
    """Pallas latent ragged attention (contract of
    ``ragged_attend_latent_ref``; output in the queries' type). The pool is
    passed whole, as stored, and stays in HBM; ``lanes`` and ``v_lanes``
    are multiples of 128 (config.LatentConfig.lanes pads the stored row)."""
    Tp, H, lanes = q.shape
    NB = block_meta.shape[1]
    page = pool.shape[2]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    kernel = functools.partial(_ragged_latent_kernel, page=page, tq=tq,
                               v_lanes=v_lanes, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                 # tables, meta, layer
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((1, tq, H, lanes), lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # pool stays in HBM
            ],
            out_specs=[
                pl.BlockSpec((1, tq, H, v_lanes),
                             lambda i, *_: (i, 0, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((2, page, lanes), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((NB, tq, H, v_lanes), q.dtype)],
        interpret=interpret,
        # pinned: the trace shows `%ragged_attend_latent.<n>`, which the
        # benchmark's `^%ragged_attend` patterns match
        name="ragged_attend_latent",
    )(row_tables.astype(jnp.int32), block_meta.astype(jnp.int32), layer,
      q.astype(pool.dtype).reshape(NB, tq, H, lanes), pool)[0]
    return out.reshape(NB * tq, H, v_lanes)


def ragged_attend_latent_auto(q, pool, row_tables, block_meta, layer, *,
                              tq: int, v_lanes: int, scale: float,
                              interpret: Optional[bool] = None):
    """Latent attention dispatcher: the Pallas kernel on TPU (or under
    ``interpret``), the XLA gather reference elsewhere. A pool whose lanes
    are no multiple of 128 (tiny test models) takes the reference."""
    aligned = pool.shape[-1] % 128 == 0 and v_lanes % 128 == 0
    if (_on_tpu() or interpret) and aligned:
        return ragged_attend_latent(q, pool, row_tables, block_meta, layer,
                                    tq=tq, v_lanes=v_lanes, scale=scale,
                                    interpret=bool(interpret))
    return ragged_attend_latent_ref(q, pool, row_tables, block_meta, layer,
                                    tq=tq, v_lanes=v_lanes, scale=scale)


def _tp_shard_map(inner, shard, q_rank4: bool):
    """Wrap a paged-attention piece in shard_map over the tp axis: every
    head attends independently (GQA groups stay whole per shard — callers
    gate on H % tp == KV % tp == 0), so each tp shard runs the
    single-device kernel on its local heads with NO collective; dp shards
    the batch. This is how mesh engines keep the ragged kernels instead
    of falling back to gather (VERDICT r4 item 3)."""
    from jax.sharding import PartitionSpec as P
    mesh, tp_ax, dp_ax = shard
    head = P(dp_ax, None, tp_ax, None)       # [B, T|1, H, hd] (and tails)
    kv = P(None, None, tp_ax, None)          # [n_pages, page, KV, hd]
    row = P(dp_ax)
    tbl = P(dp_ax, None)
    if q_rank4:   # decode: q [B,1,H,hd]; prefill merge: q [B,T,H,hd]
        ins = (head, kv, kv, tbl, row, row, head, head, P(), row)
    else:
        ins = (head, head, head, kv, kv, tbl, row, row)
    # check_vma off: see ragged_attend_auto
    return jax.shard_map(inner, mesh=mesh, in_specs=ins, out_specs=head,
                         check_vma=False)


def paged_prefill_merge(
    q: jax.Array,          # [B, T, H, hd]
    chunk_k: jax.Array,    # [B, T, KV, hd]
    chunk_v: jax.Array,
    k_pages: jax.Array,    # [n_pages, page, KV, hd]
    v_pages: jax.Array,
    tables: jax.Array,
    prefix_lens: jax.Array,   # [B] resident pool tokens
    chunk_lens: jax.Array,    # [B] valid chunk tokens
    sliding_window: Optional[int] = None,
    interpret: Optional[bool] = None,
    shard: Optional[tuple] = None,   # (mesh, tp_axis, dp_axis|None)
) -> jax.Array:
    """Full paged-prefill attention = pool-prefix piece ⊕ intra-chunk
    causal piece → [B, T, H, hd] in q.dtype. Pallas kernel on TPU, gather
    reference elsewhere (CPU tests — same numerics, no paging win). With
    ``shard``, runs per-tp-shard under shard_map (heads independent)."""
    if shard is not None:
        inner = functools.partial(paged_prefill_merge,
                                  sliding_window=sliding_window,
                                  interpret=interpret, shard=None)
        return _tp_shard_map(inner, shard, q_rank4=False)(
            q, chunk_k, chunk_v, k_pages, v_pages, tables, prefix_lens,
            chunk_lens)
    if _on_tpu() or interpret:
        pooled = paged_prefill_attend(q, k_pages, v_pages, tables,
                                      prefix_lens, sliding_window,
                                      interpret=bool(interpret))
    else:
        pooled = paged_prefill_attend_ref(q, k_pages, v_pages, tables,
                                          prefix_lens, sliding_window)
    chunk = chunk_attend_partials(q, chunk_k, chunk_v, chunk_lens,
                                  sliding_window)
    return merge_partials(pooled, chunk).astype(q.dtype)


def paged_decode_attend(
    q: jax.Array,          # [B, 1, H, hd] (decode step)
    k_pages: jax.Array,    # [n_pages, page, KV, hd]
    v_pages: jax.Array,
    tables: jax.Array,
    pool_lens: jax.Array,  # [B] valid pool tokens (fixed through decode)
    kv_off: jax.Array,     # [B] absolute position of pool index 0
    tail_k: jax.Array,     # [B, Tmax, KV, hd]
    tail_v: jax.Array,
    tail_len,              # scalar/[B] valid tail entries (incl. current)
    q_pos: jax.Array,      # [B] absolute query position
    sliding_window: Optional[int] = None,
    interpret: Optional[bool] = None,
    shard: Optional[tuple] = None,   # (mesh, tp_axis, dp_axis|None)
) -> jax.Array:
    """Full decode attention = paged pool piece ⊕ tail piece → [B, 1, H, hd]
    in q.dtype. Picks the Pallas kernel on TPU (or under ``interpret``),
    the gather reference elsewhere (CPU tests — same numerics, no paging
    win). With ``shard``, runs per-tp-shard under shard_map (heads
    independent)."""
    if shard is not None:
        inner = functools.partial(paged_decode_attend,
                                  sliding_window=sliding_window,
                                  interpret=interpret, shard=None)
        return _tp_shard_map(inner, shard, q_rank4=True)(
            q, k_pages, v_pages, tables, pool_lens, kv_off, tail_k, tail_v,
            jnp.asarray(tail_len), q_pos)
    B, _, H, hd = q.shape
    q1 = q[:, 0]
    if _on_tpu() or interpret:
        pooled = paged_attend(q1, k_pages, v_pages, tables, pool_lens,
                              kv_off, q_pos, sliding_window,
                              interpret=bool(interpret))
    else:
        pooled = paged_attend_ref(q1, k_pages, v_pages, tables, pool_lens,
                                  kv_off, q_pos, sliding_window)
    tail_pos0 = kv_off.astype(jnp.int32) + pool_lens.astype(jnp.int32)
    tail = tail_attend_partials(q1, tail_k, tail_v, tail_len, tail_pos0,
                                q_pos, sliding_window)
    out = merge_partials(pooled, tail)                   # [B, H, hd] f32
    return out[:, None].astype(q.dtype)
