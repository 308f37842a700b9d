"""The routed experts' gated feed-forward over expert-aligned blocks as ONE
kernel a layer (``grouped_ffn``), for a model whose layer reaches tens of
small experts a decode step (LFM2: 64 experts of 18.9 MB, 4 a token, so 8
rows reach about 21 of them in each of 8 layers).

The loop over blocks in ``transformer._routed_experts`` computes the same
thing with some 18 device operations a block: at 170 blocks a step that
is 3,000 operations a step, which the chip runs well enough but which a
profiler session cannot carry (a million events in five traced seconds
take it four minutes to hand over; PERF.md §6, PR 33). Here a grid
program per (block, slice of the expert's width) reads that block's
expert straight from the stacked weights — its number comes from a
scalar-prefetched table, so a step streams the experts its rows reached
and no others — and the three matmuls and the activation between them
happen in VMEM: one operation a layer.

The loop stays the oracle (tests/test_shortconv_moe.py) and what the CPU
and the latent models run.

No reference counterpart: the reference never executes a model
(SURVEY.md §2.8 — all inference was remote HTTPS).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lanes of an expert's width one grid program takes: three weight slices
# of [D, 512] / [512, D], twice each for the pipeline, are 12 MiB at
# D = 2048.
WIDTH_SLICES = (512, 384, 256, 128)
# What an expert's three matrices WHOLE may take of the kernel's 48 MiB,
# twice each for the pipeline (24.8 MB at 2,304 x 896), beside the block,
# its float32 sum and the float32 activations between the matmuls.
WHOLE_BYTES = 32 << 20


def width_slice(expert_dim: int, dim: int = 0, itemsize: int = 2) -> int:
    """The widest of ``WIDTH_SLICES`` that divides the expert's width,
    else the width whole (toy sizes) — and the width whole, too, where
    only the NARROWEST slice divides it and the expert fits (896 = 7 x 128
    at a model 2,304 wide): a 128-lane slice of ``[D, F]`` is D runs of
    256 bytes, seven grid programs a block (PERF.md section 6, PR 41, has
    both readings)."""
    s = next((s for s in WIDTH_SLICES if expert_dim % s == 0), expert_dim)
    if s == WIDTH_SLICES[-1] < expert_dim \
            and 2 * 3 * dim * expert_dim * itemsize <= WHOLE_BYTES:
        return expert_dim
    return s


def rows_slice(expert_dim: int, dim: int, itemsize: int = 2) -> int:
    """Rows of an UNGATED expert's two ``[F, D]`` matrices one grid
    program takes: the width whole where both fit ``WHOLE_BYTES`` twice
    over, else the largest divisor of it that is a multiple of 16 (a
    bfloat16 tile's rows) and does (928 of 1,856 at a model 2,688 wide)."""
    for n in range(1, expert_dim + 1):
        tf = expert_dim // n
        if expert_dim % n == 0 and (n == 1 or tf % 16 == 0) \
                and 2 * 2 * dim * tf * itemsize <= WHOLE_BYTES:
            return tf
    return expert_dim


def _ffn_kernel(expert_ref, meta_ref, x_ref, *refs, act: Callable,
                n_slices: int):
    # refs: the gate matrix's slice where the body is gated, then up,
    # down, the result and the float32 sum
    wg_ref = refs[0] if len(refs) == 5 else None
    wu_ref, wd_ref, out_ref, acc_ref = refs[-4:]
    b, f = pl.program_id(0), pl.program_id(1)

    @pl.when(b < meta_ref[0])               # a block that exists
    def _():
        @pl.when(f == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]                                        # [blk, D]
        if wg_ref is not None:
            g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
            a = (act(g) * u).astype(x.dtype)                  # [blk, tf]
        else:
            # the up matrix's slice lies [tf, D]: x W_uᵀ
            a = act(jax.lax.dot_general(
                x, wu_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)).astype(x.dtype)
        acc_ref[...] += jnp.dot(a, wd_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(f == n_slices - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def grouped_ffn(
    x: jax.Array,          # [NB, blk, D]: each block's tokens, gathered
    wg,                    # [L, E, D, F]  the experts' stacked weights,
    wu: jax.Array,         # [L, E, D, F]  whole: they stay in HBM and a
    wd: jax.Array,         # [L, E, F, D]  program reads its slice; ``wg``
                           # None: the experts are not gated, and ``wu``
                           # lies TRANSPOSED, [L, E, F, D]
    layer,                 # int32 scalar: which of the L layers
    block_expert: jax.Array,   # [NB] int32: each block's expert
    n_blocks,              # int32 scalar: blocks that exist (the first)
    act: Callable,
    interpret: bool = False,
) -> jax.Array:
    """``act(x_b W_g[e_b]) ⊙ (x_b W_u[e_b])) W_d[e_b]`` — or, with ``wg``
    None (a static choice of the body: two matmuls, no gate),
    ``act(x_b W_u[e_b]ᵀ) W_d[e_b]``, which is Nemotron-H's ``relu2``
    expert, both matrices ``[F, D]`` (a width of 1,856 is no multiple of
    128 lanes: as the minor dimension of ``[D, F]`` it made the compiler
    copy every stack into a padded layout, 1.2 GiB each; as rows it
    slices by 16) — for every block
    ``b < n_blocks``, [NB, blk, D] in ``x``'s type; the blocks behind
    them are left unwritten (no program of theirs moves or multiplies
    anything: their index maps stay on the last block that exists, so the
    pipeline has nothing to fetch). float32 between the matmuls and in the
    sum over the width's slices, rounded once at the end."""
    NB, blk, D = x.shape
    if wg is None:
        F = wu.shape[-2]
        tf = rows_slice(F, D, wu.dtype.itemsize)
    else:
        F = wu.shape[-1]
        tf = width_slice(F, D, wu.dtype.itemsize)
    n_slices = F // tf
    meta = jnp.stack([jnp.asarray(n_blocks, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])

    def block(b, meta):
        return jnp.maximum(jnp.minimum(b, meta[0] - 1), 0)

    def slice_(b, f, meta):
        return jnp.where(b < meta[0], f, n_slices - 1)

    def x_map(b, f, expert, meta):
        return block(b, meta), 0, 0

    def up_map(b, f, expert, meta):
        return meta[1], expert[block(b, meta)], 0, slice_(b, f, meta)

    def down_map(b, f, expert, meta):
        return meta[1], expert[block(b, meta)], slice_(b, f, meta), 0

    return pl.pallas_call(
        functools.partial(_ffn_kernel, act=act, n_slices=n_slices),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                  # block_expert, meta
            grid=(NB, n_slices),
            in_specs=[
                pl.BlockSpec((None, blk, D), x_map),
                *([pl.BlockSpec((None, None, tf, D), down_map)]
                  if wg is None else
                  [pl.BlockSpec((None, None, D, tf), up_map)] * 2),
                pl.BlockSpec((None, None, tf, D), down_map),
            ],
            out_specs=pl.BlockSpec((None, blk, D), x_map),
            scratch_shapes=[pltpu.VMEM((blk, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((NB, blk, D), x.dtype),
        # a block's result is summed over the width's slices in order, and
        # the blocks that do not exist rest on the last one's buffers
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="routed_experts_ffn",
    )(block_expert.astype(jnp.int32), meta, x,
      *(() if wg is None else (wg,)), wu, wd)
