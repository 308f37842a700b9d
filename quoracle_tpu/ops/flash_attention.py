"""Pallas flash attention (blockwise online-softmax) for TPU prefill.

The XLA `attend` path materializes [B, H, T, S] scores in HBM; this kernel
streams KV blocks through VMEM with running (max, denom, acc) statistics so
the memory high-water is O(TQ x TK) per core — the standard flash recipe
mapped to the TPU constraints of /opt/skills/guides/pallas_guide.md (grid
over (batch, head, q-block), MXU contractions with
preferred_element_type=f32, VPU mask/softmax chain, lane dim 128).

Semantics match ops/attention.attend exactly (same masking: validity by
kv_len, causality by absolute position, optional sliding window) and the
tests assert numerical agreement. Off-TPU the kernel runs in interpreter
mode — correct but slow — so production callers gate on platform
(attend_auto below).

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

from quoracle_tpu.ops.attention import attend

DEFAULT_TQ = 128
NEG_INF = -1e30


def _flash_kernel(kv_meta_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float,
                  sliding_window: Optional[int]):
    """One (batch, head, q-block, kv-block) program. The kv axis is the
    innermost grid dimension, so Pallas streams one [tk, hd] K/V block at
    a time (a whole-S block needs 32 MiB of VMEM at Mistral-7B's 32k
    window) and the running (max, denom, acc) live in VMEM scratch across
    it: zeroed at the first kv block, normalized into o_ref at the last.

    Block shapes (leading singleton dims dropped by indexing):
      q_ref [1, 1, TQ, hd]   k_ref/v_ref [1, 1, tk, hd]
      qpos_ref [1, 1, TQ] (VMEM) kv_meta_ref [B, 2] (SMEM: kv_len, pos offset)
      o_ref [1, 1, TQ, hd]
    """
    ki = pl.program_id(3)
    kv_len = kv_meta_ref[pl.program_id(0), 0]             # this batch row
    kv_off = kv_meta_ref[pl.program_id(0), 1]             # abs pos of idx 0
    tk = k_ref.shape[2]

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki * tk < kv_len)            # blocks past the row's KV: skip
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [TQ, hd]
        tq = q.shape[0]
        k_blk = k_ref[0, 0].astype(jnp.float32)           # [tk, hd]
        v_blk = v_ref[0, 0].astype(jnp.float32)
        scores = jax.lax.dot_general(                     # [TQ, tk] on MXU
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kv_idx = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        kv_pos = kv_idx + kv_off
        qp = qpos_ref[0, 0].astype(jnp.int32)[:, None]    # [TQ, 1]
        mask = (kv_idx < kv_len) & (kv_pos <= qp)
        if sliding_window is not None:
            mask &= qp - kv_pos < sliding_window
        scores = jnp.where(mask, scores, NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        # NEG_INF is finite, so a fully-masked block would give
        # exp(NEG_INF - NEG_INF) = 1 per position; re-mask p so masked
        # positions contribute 0 and fully-masked rows keep l == 0.
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)  # [TQ, tk]
        correction = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * correction \
            + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        l = l_scr[...]
        # fully-masked rows (query padding) produce l == 0 → emit zeros
        out = jnp.where(l > 0, acc_scr[...] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int,
            value: float = 0.0) -> jax.Array:
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=value)


@functools.partial(jax.jit, static_argnames=("sliding_window", "tq", "tk",
                                             "interpret"))
def flash_attend(
    q: jax.Array,            # [B, T, n_heads, hd]
    k: jax.Array,            # [B, S, n_kv, hd]
    v: jax.Array,            # [B, S, n_kv, hd]
    q_positions: jax.Array,  # [B, T] int32
    kv_len: jax.Array,       # [B] int32
    sliding_window: Optional[int] = None,
    kv_pos_offset: Optional[jax.Array] = None,   # [B] int32
    tq: int = DEFAULT_TQ,
    tk: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for attend() with flash memory behavior. GQA is handled by
    head-index mapping (kv never materializes repeated). ``tk`` (the kv
    block streamed per grid step) defaults to the largest of 512/256/128
    that divides S rounded up to 128, so the cache is padded by < 128."""
    b, t, n_heads, hd = q.shape
    n_kv = k.shape[2]
    q_per_kv = n_heads // n_kv
    scale = hd ** -0.5

    # Lane/tile alignment: hd → 128-multiple, T → tq-multiple, S → tk-mult.
    hd_p = max(128, ((hd + 127) // 128) * 128)
    if tk is None:
        s_128 = -(-k.shape[1] // 128) * 128
        tk = next(n for n in (512, 256, 128) if s_128 % n == 0)
    q2 = _pad_to(_pad_to(q, 3, hd_p), 1, tq)
    k2 = _pad_to(_pad_to(k, 3, hd_p), 1, tk)
    v2 = _pad_to(_pad_to(v, 3, hd_p), 1, tk)
    # padded queries get position -1: masked against every kv index.
    # [B, 1, T]: a (1, tq) block of a [B, T] array is refused for B > 1
    # (second-minor block dim must be 8-divisible or the whole axis).
    qpos2 = _pad_to(q_positions.astype(jnp.int32), 1, tq, value=-1)[:, None]
    t_p, s_p = q2.shape[1], k2.shape[1]

    q2 = q2.transpose(0, 2, 1, 3)        # [B, H, T, hd]
    k2 = k2.transpose(0, 2, 1, 3)        # [B, KVH, S, hd]
    v2 = v2.transpose(0, 2, 1, 3)

    kv_block = pl.BlockSpec(
        (1, 1, tk, hd_p),
        lambda bb, h, qi, ki, kvl, _q=q_per_kv: (bb, h // _q, ki, 0))
    kernel = functools.partial(_flash_kernel, scale=scale,
                               sliding_window=sliding_window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,            # kv_len rides SMEM
            grid=(b, n_heads, t_p // tq, s_p // tk),
            in_specs=[
                pl.BlockSpec((1, 1, tq), lambda bb, h, qi, ki, kvl:
                             (bb, 0, qi)),
                pl.BlockSpec((1, 1, tq, hd_p),
                             lambda bb, h, qi, ki, kvl: (bb, h, qi, 0)),
                kv_block,
                kv_block,
            ],
            out_specs=pl.BlockSpec((1, 1, tq, hd_p),
                                   lambda bb, h, qi, ki, kvl:
                                   (bb, h, qi, 0)),
            scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, hd_p), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_heads, t_p, hd_p), q.dtype),
        interpret=interpret,
        name="flash_attend",    # pinned: the kernel's name in a trace
    )(jnp.stack([kv_len.astype(jnp.int32),
                 (jnp.zeros_like(kv_len, jnp.int32)
                  if kv_pos_offset is None
                  else kv_pos_offset.astype(jnp.int32))], axis=1),
      qpos2, q2, k2, v2)

    return out.transpose(0, 2, 1, 3)[:, :t, :, :hd]


def attend_auto(q, k, v, q_positions, kv_len,
                sliding_window: Optional[int] = None,
                kv_pos_offset: Optional[jax.Array] = None,
                min_flash_len: int = 256,
                interpret: Optional[bool] = None,
                shard: Optional[tuple] = None) -> jax.Array:
    """Pick the attention path: flash on TPU (or under ``interpret``) for
    long prefill chunks, dense XLA otherwise (decode steps and CPU tests).
    Same semantics as attend().

    ``shard`` = (mesh, tp_axis | None, dp_axis | None), which every mesh
    engine passes: GSPMD cannot partition a Mosaic kernel (lowering raises
    "cannot be automatically partitioned"), so the kernel runs per shard
    under shard_map — heads on tp (independent; None = replicated), rows
    on dp. The dense path needs none: XLA partitions its einsums."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if not ((on_tpu or interpret) and q.shape[1] >= min_flash_len):
        return attend(q, k, v, q_positions, kv_len,
                      sliding_window=sliding_window,
                      kv_pos_offset=kv_pos_offset)

    def flash(q, k, v, q_positions, kv_len, kv_pos_offset):
        return flash_attend(q, k, v, q_positions, kv_len,
                            sliding_window=sliding_window,
                            kv_pos_offset=kv_pos_offset,
                            interpret=bool(interpret))
    if shard is not None:
        from jax.sharding import PartitionSpec as P
        mesh, tp_ax, dp_ax = shard
        head = P(dp_ax, None, tp_ax, None)       # [B, T|S, H|KV, hd]
        row = P(dp_ax)
        # check_vma off: a pallas_call's outputs carry no varying-axes
        # annotation for the checker to verify
        flash = jax.shard_map(
            flash, mesh=mesh, out_specs=head, check_vma=False,
            in_specs=(head, head, head, P(dp_ax, None), row, row))
    if kv_pos_offset is None:
        kv_pos_offset = jnp.zeros_like(kv_len)
    return flash(q, k, v, q_positions, kv_len, kv_pos_offset)
