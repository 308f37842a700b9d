"""The recurrent-matrix mixer's scan over a tick's chunks (Mamba-2's SSD
form; config.SSMConfig): ``ssm_scan`` is the kernel the chunk forward runs
on the TPU, and ``ssm_decode`` a decode step's convolution, one-token
recurrence and gated norm as ONE kernel (a score of small device
operations a layer otherwise, which a profiler session cannot carry at 190
steps a second; PERF.md §6, PR 47). ``ssm_scan_ref`` and
``ssm_decode_ref`` are the same sums in plain XLA, on the same arguments
and layouts — what the CPU runs and what the kernels are tested against
(tests/test_mamba_moe.py).

The recurrence, a head ``h`` of group ``g`` (``S`` its state ``[P, N]``,
float32): ``S ← exp(Δ_t A_h) S + Δ_t x_t ⊗ B_t``; ``y_t = S C_t``. Over a
CHUNK of ``Q`` tokens it is three matrix products (``l_t = Σ_{s≤t} Δ_s
A_h``, the chunk's running log-decay):

  within    ``y_t += Σ_{s≤t} exp(l_t − l_s) (C_t·B_s) Δ_s x_s``
  from S    ``y_t += exp(l_t) S C_t``
  to S      ``S ← exp(l_Q) S + Σ_s exp(l_Q − l_s) Δ_s x_s ⊗ B_s``

so a tick's tokens go through it ``Q`` at a time, the chunks of one row in
order and rows apart: the caller lays each row's tokens out from a chunk's
first slot (``transformer.SsmTick``: the SCAN layout, a row's last chunk
padded with tokens of ``Δ = 0``, which leave the state as it is), names
each chunk's row and says which chunks start a row — those take the row's
initial state ``s0[row]`` in place of the state carried from the chunk
before. The state after EVERY chunk comes back (a row's end state and a
snapshot at a chunk boundary are both reads of it).

The kernel takes ``x`` and gives ``y`` TRANSPOSED, ``[.., H·P, Q]``: a
head's rows are then a run of sublanes (64 at the published width), every
product is a plain or a transposed-right matmul, and the state lies as it
is stored, ``[H·P, N]`` with the 128 state values a head's channel holds
along the lanes. A grid program is one chunk of one group's heads (which
share ``B`` and ``C``, hence ``C·Bᵀ``); the chunks run in order with the
group's state in VMEM scratch.

No reference counterpart: the reference never executes a model
(SURVEY.md §2.8 — all inference was remote HTTPS).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _log_decay(dt: jax.Array, A: jax.Array) -> jax.Array:
    """``l``: the running sum of ``Δ A`` along a chunk. dt [NC, Q, H]."""
    return jnp.cumsum(dt * A, axis=1)


def ssm_scan_ref(x, dt, A, B, C, s0, chunk_row, chunk_first):
    """The scan in plain XLA. ``x [NC, Q, H, P]``; ``dt [NC, Q, H]``
    float32 (Δ, 0 at padding); ``A [H]`` float32 (negative); ``B``, ``C
    [NC, Q, G, N]``; ``s0 [R, H, P, N]`` float32; ``chunk_row [NC]``,
    ``chunk_first [NC]`` int32. Returns (``y [NC, Q, H, P]`` float32,
    ``states [NC, H, P, N]`` float32: the state after each chunk)."""
    NC, Q, H, P = x.shape
    G, N = B.shape[2:]
    Hg = H // G
    f32 = jnp.float32
    la = _log_decay(dt, A)                                    # [NC, Q, H]
    xdt = (x.astype(f32) * dt[..., None]).reshape(NC, Q, G, Hg, P)
    Bf, Cf = B.astype(f32), C.astype(f32)
    lg = la.reshape(NC, Q, G, Hg)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]
    L = jnp.where(causal, jnp.exp(jnp.where(
        causal, lg[:, :, None] - lg[:, None], 0.0)), 0.0)     # [c,t,s,g,k]
    cb = jnp.einsum("ctgn,csgn->ctsg", Cf, Bf)
    y = jnp.einsum("ctsg,ctsgk,csgkp->ctgkp", cb, L, xdt)

    def step(S, c):
        l, xd, b, cc, row, first = c
        S = jnp.where(first > 0, s0[row].reshape(G, Hg, P, N), S)
        y_in = jnp.einsum("tgn,gkpn->tgkp", cc, S) * jnp.exp(l)[..., None]
        w = jnp.exp(l[-1] - l)                                # [Q, G, Hg]
        S = jnp.exp(l[-1])[..., None, None] * S + jnp.einsum(
            "tgkp,tgn->gkpn", xd * w[..., None], b)
        return S, (y_in, S)

    _, (y_in, states) = jax.lax.scan(
        step, jnp.zeros((G, Hg, P, N), f32),
        (lg, xdt, Bf, Cf, chunk_row, chunk_first))
    return ((y + y_in).reshape(NC, Q, H, P),
            states.reshape(NC, H, P, N))


def _scan_kernel(row_ref, first_ref, n_ref, *refs, heads: int, P: int):
    # a chunk behind the last that holds a token: nothing moves (its index
    # maps stay on the last one that does) and nothing is multiplied
    c = pl.program_id(1)

    @pl.when(c < n_ref[0])
    def _():
        _scan_chunk(c, first_ref, *refs, heads=heads, P=P)


def _scan_chunk(c, first_ref, x_ref, b_ref, c_ref, lr_ref, lc_ref, wr_ref,
                dq_ref, s0_ref, y_ref, st_ref, s_ref, *, heads: int, P: int):
    @pl.when(first_ref[c] > 0)
    def _():
        s_ref[...] = s0_ref[...]

    f32 = jnp.float32
    Q = x_ref.shape[-1]
    B, C = b_ref[...], c_ref[...]                             # [Q, N]
    # (B Cᵀ)[s, t]: the group's heads share it
    bc = jax.lax.dot_general(B, C, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)
    Bf, Cf = B.astype(f32), C.astype(f32)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    for k in range(heads):
        rows = slice(k * P, (k + 1) * P)
        lr = lr_ref[k:k + 1, :]                               # [1, Q]: l_t
        lc = lc_ref[k]                                        # [Q, 1]: l_s
        xT = x_ref[rows, :]                                   # [P, Q] Δ·x
        # Mᵀ[s, t] = (C_t·B_s) exp(l_t − l_s) for s ≤ t
        mT = jnp.where(s_idx <= t_idx,
                       bc * jnp.exp(jnp.where(s_idx <= t_idx, lr - lc, 0.0)),
                       0.0)
        y = jnp.dot(xT, mT.astype(xT.dtype), preferred_element_type=f32)
        S = s_ref[rows, :]                                    # [P, N]
        y += jax.lax.dot_general(S, Cf, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32) * jnp.exp(lr)
        y_ref[rows, :] = y.astype(y_ref.dtype)
        # exp(l_Q) S + Σ_s exp(l_Q − l_s) Δ_s x_s ⊗ B_s; both decays come
        # in as rows (a [1, 1] value does not broadcast both ways here)
        S = dq_ref[k:k + 1, :] * S + jnp.dot(
            xT.astype(f32) * wr_ref[k:k + 1, :], Bf,
            preferred_element_type=f32)
        s_ref[rows, :] = S
        st_ref[rows, :] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(x, dt, A, B, C, s0, chunk_row, chunk_first, n_chunks=None,
             interpret: bool = False):
    """``ssm_scan_ref`` as one kernel (module docstring): the same
    arguments and results, ``y`` in ``x``'s type. ``n_chunks`` (int32
    [1]; None: all): the chunks that hold a token, the first of the
    layout; the grid programs of the others do nothing, and their part of
    ``y`` and of the states is left unwritten."""
    NC, Q, H, P = x.shape
    G, N = B.shape[2:]
    Hg = H // G
    la = _log_decay(dt, A)                                    # [NC, Q, H]
    lr = la.transpose(0, 2, 1)                                # [NC, H, Q]
    wr = jnp.exp(lr[..., -1:] - lr)              # exp(l_Q − l_t), a row
    dq = jnp.broadcast_to(jnp.exp(lr[..., -1:]), (NC, H, N))  # exp(l_Q)
    xT = (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype).reshape(
        NC, Q, H * P).transpose(0, 2, 1)                      # [NC, H·P, Q]
    R = s0.shape[0]
    if n_chunks is None:
        n_chunks = jnp.full((1,), NC, jnp.int32)

    def live(c, n):
        return jnp.maximum(jnp.minimum(c, n[0] - 1), 0)

    def chunk(g, c, row, first, n):
        return live(c, n), g, 0

    def group(g, c, row, first, n):
        return live(c, n), 0, g

    yT, states = pl.pallas_call(
        functools.partial(_scan_kernel, heads=Hg, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # chunk_row, chunk_first, n_chunks
            grid=(G, NC),
            in_specs=[
                pl.BlockSpec((None, Hg * P, Q), chunk),
                pl.BlockSpec((None, Q, N), group),
                pl.BlockSpec((None, Q, N), group),
                pl.BlockSpec((None, Hg, Q), chunk),
                pl.BlockSpec((None, Hg, Q, 1),
                             lambda g, c, row, first, n: (live(c, n), g, 0,
                                                          0)),
                pl.BlockSpec((None, Hg, Q), chunk),
                pl.BlockSpec((None, Hg, N), chunk),
                # a row's chunks follow each other: the block's index holds
                # still over them, and the pipeline fetches it once
                pl.BlockSpec((None, Hg * P, N),
                             lambda g, c, row, first, n: (row[live(c, n)],
                                                          g, 0)),
            ],
            out_specs=[pl.BlockSpec((None, Hg * P, Q), chunk),
                       pl.BlockSpec((None, Hg * P, N), chunk)],
            scratch_shapes=[pltpu.VMEM((Hg * P, N), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((NC, H * P, Q), x.dtype),
                   jax.ShapeDtypeStruct((NC, H * P, N), jnp.float32)],
        # a group's chunks in order (its state rides the scratch); groups
        # apart
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(chunk_row.astype(jnp.int32), chunk_first.astype(jnp.int32),
      n_chunks.astype(jnp.int32), xT,
      B.reshape(NC, Q, G * N), C.reshape(NC, Q, G * N), lr, lr[..., None],
      wr, dq, s0.reshape(R, H * P, N))
    return (yT.transpose(0, 2, 1).reshape(NC, Q, H, P),
            states.reshape(NC, H, P, N))


def ssm_scan_auto(x, dt, A, B, C, s0, chunk_row, chunk_first, n_chunks=None,
                  interpret=None):
    """The kernel on the TPU and where a test asks for it
    (``interpret``), the XLA form elsewhere."""
    from quoracle_tpu.ops.paged_attention import _on_tpu
    if interpret or _on_tpu():
        y, states = ssm_scan(x, dt, A, B, C, s0, chunk_row, chunk_first,
                             n_chunks, interpret=bool(interpret))
        return y.astype(jnp.float32), states
    return ssm_scan_ref(x, dt, A, B, C, s0, chunk_row, chunk_first)


def ssm_decode_ref(z, xbc, prev, w_conv, b_conv, delta, a_d, norm_w, st,
                   layer, live, *, G: int, N: int, K: int, eps: float):
    """``ssm_decode`` in plain XLA: the same arguments, layouts and
    results (the state transposed, ``[layers, R, N, H·P]``). What the CPU
    runs and what the kernel is tested against."""
    f32 = jnp.float32
    R, DI = z.shape
    CD = xbc.shape[1]
    w = w_conv.astype(f32)
    past = jax.lax.dynamic_index_in_dim(prev, layer, 0, False).reshape(
        R, K - 1, CD)                                  # the oldest first
    acc = w[K - 1] * xbc + b_conv.astype(f32) \
        + jnp.einsum("kc,rkc->rc", w[:K - 1], past)
    conv = acc * jax.nn.sigmoid(acc)                   # silu

    def lanes(v):         # B or C [R, G·N] -> [R, N, H·P], a group's
        return jnp.repeat(v.reshape(R, G, N).transpose(0, 2, 1), DI // G,
                          axis=2)                      # channels share it

    x = conv[:, :DI]
    S = jax.lax.dynamic_index_in_dim(st, layer, 0, False)    # [R, N, H·P]
    S_new = jnp.exp(delta * a_d[0])[:, None] * S \
        + lanes(conv[:, DI:DI + G * N]) * (delta * x)[:, None]
    y = jnp.sum(S_new * lanes(conv[:, DI + G * N:]), axis=1) + a_d[1] * x
    y = (y * (z * jax.nn.sigmoid(z))).reshape(R, G, DI // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    st = jax.lax.dynamic_update_index_in_dim(
        st, jnp.where((live > 0)[:, None, None], S_new, S), layer, 0)
    return y.reshape(R, DI) * norm_w.astype(f32), st


def _decode_kernel(c_ref, live_ref, z_ref, x_ref, b_ref, cc_ref, *refs,
                   K: int, eps: float):
    # refs: the K - 1 earlier inputs of x, of B and of C (oldest first),
    # the taps and the bias of each, Δ, the decay, D and the norm's weight
    # a lane, the state in, then y and the state out
    prev = [refs[i * (K - 1):(i + 1) * (K - 1)] for i in range(3)]
    taps = refs[3 * (K - 1):3 * (K - 1) + 3]
    bias = refs[3 * (K - 1) + 3:3 * (K - 1) + 6]
    dl_ref, ad_ref, nw_ref, st_in, y_ref, st_out = refs[3 * K + 3:]
    r = pl.program_id(1)
    row = pl.ds(r, 1)
    f32 = jnp.float32

    def conv(cur, prevs, w_ref, bias_ref):
        w = w_ref[...].astype(f32)        # the weights come as stored
        acc = w[K - 1:K] * cur[row, :] + bias_ref[...].astype(f32)
        for j, p in enumerate(prevs):
            acc = acc + w[j:j + 1] * p[row, :]
        return acc * jax.nn.sigmoid(acc)                      # silu

    x = conv(x_ref, prev[0], taps[0], bias[0])                # [1, Hg·P]
    b = conv(b_ref, prev[1], taps[1], bias[1])                # [1, N]
    c = conv(cc_ref, prev[2], taps[2], bias[2])
    n = b.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def column(v):            # [1, N] along the lanes -> [N, 1]
        return jnp.sum(jnp.where(eye, v, 0.0), axis=1, keepdims=True)

    S = st_in[...]                                            # [N, Hg·P]
    dl = dl_ref[row, :]                                       # Δ a lane
    S_new = jnp.exp(dl * ad_ref[0:1, :]) * S + column(b) * (dl * x)
    y = jnp.sum(S_new * column(c), axis=0, keepdims=True) \
        + ad_ref[1:2, :] * x
    z = z_ref[row, :]
    y = y * (z * jax.nn.sigmoid(z))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=1, keepdims=True) + eps) \
        * nw_ref[...].astype(f32)
    y_ref[row, :] = y.astype(y_ref.dtype)
    st_out[...] = jnp.where(live_ref[r] > 0, S_new, S)


@functools.partial(jax.jit, static_argnames=("G", "N", "K", "eps",
                                             "interpret"))
def ssm_decode(z, xbc, prev, w_conv, b_conv, delta, a_d, norm_w, st, layer,
               live, *, G: int, N: int, K: int, eps: float,
               interpret: bool = False):
    """One decode step of one Mamba layer behind its input projection, as
    ONE kernel: the convolution's taps over the token and the row's last
    ``K - 1`` inputs, silu, the recurrence on the row's state, ``D x``,
    the gate and the groups' RMSNorm. Everything lies along the lanes, the
    state TRANSPOSED: ``st [layers, R, N, H·P]`` float32 (the decode
    loop's own buffer, updated in place at ``[layer]`` where ``live`` is
    1); ``z [R, d_inner]``, ``xbc [R, conv_dim]`` and ``prev [layers, R,
    (K - 1) · conv_dim]`` (the oldest input first) float32; ``w_conv [K,
    conv_dim]``, ``b_conv [1, conv_dim]`` and ``norm_w [1, d_inner]`` as
    stored; ``delta [R, d_inner]`` (Δ a head, repeated over its channels)
    and ``a_d [2, d_inner]`` (``A`` and ``D`` a head, likewise) float32.
    A grid program is
    one row of one group (whose B and C turn from lanes to a column by a
    masked sum). Returns (``y [R, d_inner]`` float32, normed and weighted;
    ``st``)."""
    R, DI = z.shape
    CD = xbc.shape[1]
    W = DI // G                                   # a group's channels
    xb, bb, cb = 0, DI // N, (DI + G * N) // N    # lane blocks of x, B, C

    def rows(width, first):
        return pl.BlockSpec((R, width), lambda g, r, c, lv: (0, first + g))

    def earlier(width, first, per):
        return [pl.BlockSpec((None, R, width),
                             lambda g, r, c, lv, j=j: (c[0], 0, j * per
                                                       + first + g))
                for j in range(K - 1)]

    def taps(height, width, first):
        return pl.BlockSpec((height, width),
                            lambda g, r, c, lv: (0, first + g))

    state = pl.BlockSpec((None, None, N, W),
                         lambda g, r, c, lv: (c[0], r, 0, g))
    y, st = pl.pallas_call(
        functools.partial(_decode_kernel, K=K, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                       # layer, live
            grid=(G, R),
            in_specs=[rows(W, 0), rows(W, xb), rows(N, bb), rows(N, cb),
                      *earlier(W, xb, CD // W), *earlier(N, bb, CD // N),
                      *earlier(N, cb, CD // N),
                      taps(K, W, xb), taps(K, N, bb), taps(K, N, cb),
                      taps(1, W, xb), taps(1, N, bb), taps(1, N, cb),
                      rows(W, 0), taps(2, W, 0), taps(1, W, 0), state],
            out_specs=[rows(W, 0), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, DI), jnp.float32),
                   jax.ShapeDtypeStruct(st.shape, st.dtype)],
        # the state, the last operand, is updated in place; a group's rows
        # run in order (y's block holds them all)
        input_output_aliases={2 + 4 + 3 * (K - 1) + 6 + 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      z, xbc, xbc, xbc, *([prev] * (3 * (K - 1))), w_conv, w_conv, w_conv,
      b_conv, b_conv, b_conv, delta, a_d, norm_w, st)
    return y, st


def ssm_decode_auto(*args, interpret=None, **sizes):
    """The kernel on the TPU and where a test asks for it
    (``interpret``), the XLA form elsewhere."""
    from quoracle_tpu.ops.paged_attention import _on_tpu
    if interpret or _on_tpu():
        return ssm_decode(*args, interpret=bool(interpret), **sizes)
    return ssm_decode_ref(*args, **sizes)
