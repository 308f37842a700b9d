"""Decoder-only transformer forward pass, pure JAX.

One traced function serves every family in the catalog (Llama/Mistral/Gemma
quirks are ModelConfig data — see models/config.py). Design choices are
TPU-first, not a translation of anything in the reference (which runs no model
math locally, SURVEY.md §2.8):

  * layers are STACKED on a leading axis and iterated with ``lax.scan`` —
    one compiled layer body regardless of depth (fast compiles, XLA-friendly);
  * params live in bf16; layernorm/softmax math in fp32;
  * KV cache is a position-ordered padded buffer updated in-place via
    ``lax.dynamic_update_slice_in_dim``; attention masks by integer lengths,
    so the whole step is shape-static under jit;
  * the same forward serves prefill (T = chunk) and decode (T = 1).

Every forward names its device work with ``jax.named_scope`` (ISSUE 24),
identically: ``embed``, ``layers`` around the scan and inside the layer
body ``qkv``, ``rope``, ``kv_write``, ``attn`` (within it ``kv_layout``:
a re-layout of the pool for the kernel, which only a test model whose
head_dim is below the lane width still needs, ops/paged_attention.py),
``attn_out``, ``mlp``; then ``final_norm`` and, in project_logits,
``head``. The decode loops (models/generate.py) add ``decode_loop`` around
the while loop, ``sample`` (``grammar_mask``, ``top_p``) and ``row_state``.
The names reach the profiler as the operation's ``tf_op`` path, which the
benchmark's scope metrics read (benchmark/scopes.json). What a scan
itself emits (slices of the stacked weights) carries ``layers`` and no
sub-scope, what a decode loop itself emits ``decode_loop`` and no
sub-scope: those remainders, with ``kv_write`` and ``kv_layout``, are
where a move of the KV pool would show (two thirds of a decode step
until PR 25; PERF.md §6). Scopes are metadata only — the computed values
are bit-identical with and without them.

The paged session pool is stored ONCE, ``[L, n_pages, page, KV·hd]``
(kv-heads flattened into the lane dimension — the layout the ragged
kernel streams; generate.py ``_ensure_pool``). ``forward_hidden_ragged``,
the serving path, carries it through the layer scan in place and hands
it to the kernel whole; the gather programs (generate.py) index its
pages into the dense cache ``forward_hidden`` attends over.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from quoracle_tpu.models.config import ModelConfig, require_plain
from quoracle_tpu.models.quant import (
    dequant_weight, is_quantized, kv_quant,
)
from quoracle_tpu.ops.attention import attend


class KVCache(NamedTuple):
    """Per-model KV buffer. k/v: [L, B, S, n_kv, head_dim]; lens: [B]."""

    k: jax.Array
    v: jax.Array
    lens: jax.Array  # int32 valid length per row

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> KVCache:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        lens=jnp.zeros((batch,), jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("shape", "fan_in", "dtype",
                                             "sharding"))
def _normal_leaf(key, shape, fan_in, dtype, sharding):
    """One normal/sqrt(fan_in) weight as ONE program: the float32 draw
    fuses into the cast, so only the ``dtype`` result is ever allocated,
    and under ``sharding`` each device computes just its own shard."""
    x = (jax.random.normal(key, shape, jnp.float32)
         * (fan_in ** -0.5)).astype(dtype)
    if sharding is not None:
        x = jax.lax.with_sharding_constraint(x, sharding)
    return x


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                mesh=None) -> dict:
    """Random-init params pytree (normal/sqrt(dim)) — tests, bench and the
    chip smoke. Real checkpoints load through models/loader.py (same
    structure, weights from safetensors).

    With ``mesh`` every weight is created in its parallel/mesh.param_specs
    sharding, so no device ever holds a whole full-width tensor (w_gate of
    mistral-7b is 3.8 GB in bf16, 7.5 GB as the float32 draw). Values do
    not depend on the mesh (partitionable threefry)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    L, D, F = cfg.n_layers, cfg.dim, cfg.ffn_dim
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if not cfg.plain:
        assert mesh is None, "latent/expert/hybrid models are not sharded yet"
        init = _init_params_stacks if cfg.latent is not None \
            else _init_params_pattern
        return init(cfg, k_embed, k_layers, k_head, dtype)
    if mesh is not None:
        from jax.sharding import NamedSharding
        from quoracle_tpu.parallel.mesh import param_specs
        specs = param_specs(cfg)

    def normal(key, shape, fan_in, *path):
        sharding = None
        if mesh is not None:
            spec = specs
            for name in path:
                spec = spec[name]
            sharding = NamedSharding(mesh, spec)
        return _normal_leaf(key, shape, fan_in, dtype, sharding)

    lk = jax.random.split(k_layers, 7)
    params = {
        "embed": normal(k_embed, (cfg.vocab_size, D), D, "embed"),
        "layers": {
            "attn_norm": jnp.ones((L, D), dtype),
            "wq": normal(lk[0], (L, D, H * HD), D, "layers", "wq"),
            "wk": normal(lk[1], (L, D, KV * HD), D, "layers", "wk"),
            "wv": normal(lk[2], (L, D, KV * HD), D, "layers", "wv"),
            "wo": normal(lk[3], (L, H * HD, D), H * HD, "layers", "wo"),
            "mlp_norm": jnp.ones((L, D), dtype),
            "w_gate": normal(lk[4], (L, D, F), D, "layers", "w_gate"),
            "w_up": normal(lk[5], (L, D, F), D, "layers", "w_up"),
            "w_down": normal(lk[6], (L, F, D), F, "layers", "w_down"),
        },
        "final_norm": jnp.ones((D,), dtype),
    }
    if cfg.attn_bias:
        params["layers"]["bq"] = jnp.zeros((L, H * HD), dtype)
        params["layers"]["bk"] = jnp.zeros((L, KV * HD), dtype)
        params["layers"]["bv"] = jnp.zeros((L, KV * HD), dtype)
    if cfg.rmsnorm_plus_one:
        # Gemma norm weights are a delta around 1; zero-init matches identity.
        params["layers"]["attn_norm"] = jnp.zeros((L, D), dtype)
        params["layers"]["mlp_norm"] = jnp.zeros((L, D), dtype)
        params["final_norm"] = jnp.zeros((D,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(k_head, (D, cfg.vocab_size), D,
                                   "lm_head")
    if cfg.vision is not None:
        from quoracle_tpu.models.vision import init_vision_params
        assert cfg.vision.out_dim == cfg.dim, \
            "vision projector must target the decoder dim"
        params["vision"] = init_vision_params(
            cfg.vision, jax.random.fold_in(k_head, 7), dtype)
    return params


# Leaves of a latent, routed-expert model's layer stacks, in the order that
# numbers their keys (``_init_params_stacks``): (name, shape, fan-in).
def _stack_leaves(cfg: ModelConfig, experts: bool) -> list:
    D, H, la = cfg.dim, cfg.n_heads, cfg.latent
    attn = [("wq_a", (D, la.q_rank), D),
            ("wq_b", (la.q_rank, H * la.qk_dim), la.q_rank),
            ("wkv_a", (D, la.kv_rank + la.rope_dim), D),
            ("wkv_b", (la.kv_rank, H * (la.nope_dim + la.v_dim)),
             la.kv_rank),
            ("wo", (H * la.v_dim, D), H * la.v_dim)]
    if cfg.indexer is not None:
        # the indexer's leaves come after the attention's
        ix = cfg.indexer
        attn += [("wi_q", (la.q_rank, ix.n_heads * ix.head_dim), la.q_rank),
                 ("wi_k", (D, ix.head_dim), D),
                 ("wi_w", (D, ix.n_heads), D)]
    if not experts:
        F = cfg.ffn_dim
        return attn + [("w_gate", (D, F), D), ("w_up", (D, F), D),
                       ("w_down", (F, D), F)]
    m = cfg.moe
    Fe, Fs = m.expert_dim, m.expert_dim * m.n_shared
    if m.router_bias:
        # float32, 0.01 × normal (a "fan-in" of 1e4): a checkpoint's is
        # what balanced its router's load, a seed's only exercises the path
        attn += [("router_bias", (m.n_routed,), 10_000)]
    return attn + [("router", (D, m.n_routed), D),
                   ("we_gate", (D, Fe), D), ("we_up", (D, Fe), D),
                   ("we_down", (Fe, D), Fe),
                   ("ws_gate", (D, Fs), D), ("ws_up", (D, Fs), D),
                   ("ws_down", (Fs, D), Fs)]


@functools.partial(jax.jit, static_argnames=("n", "shape", "fan_in",
                                             "dtype"))
def _expert_leaf(key, first, n, shape, fan_in, dtype):
    """[L, n, ...]: expert ``first + e`` of every layer drawn from
    ``fold_in(key, first + e)``, so an expert's weights do not depend on
    which share of the experts holds it."""
    def one(e):
        return (jax.random.normal(jax.random.fold_in(key, e), shape,
                                  jnp.float32) * (fan_in ** -0.5)
                ).astype(dtype)
    return jax.vmap(one, out_axes=1)(first + jnp.arange(n))


def _init_params_stacks(cfg: ModelConfig, k_embed, k_layers, k_head,
                        dtype) -> dict:
    """Random init of a latent, routed-expert model: two layer stacks, the
    leading dense layers under ``dense_layers`` and the expert layers under
    ``layers``. THE RULE
    (the benchmark's reference draws the same bits from it): a leaf is
    normal/sqrt(fan-in) rounded to ``dtype``, drawn at its stacked shape
    ``[layers of the stack, ...]`` from ``fold_in(fold_in(k_layers,
    stack), i)`` with stack 0 = ``dense_layers``, 1 = ``layers`` and ``i``
    the leaf's place in ``_stack_leaves``; a routed-expert leaf
    ``[L, n_held, ...]`` draws expert ``e`` (its global number) at
    ``[L, ...]`` from ``fold_in(that key, e)``; norms are one; embed and
    lm_head as for every model."""
    D = cfg.dim
    params = {
        "embed": _normal_leaf(k_embed, (cfg.vocab_size, D), D, dtype, None),
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal_leaf(k_head, (D, cfg.vocab_size), D,
                                         dtype, None)
    n_dense = cfg.n_dense_layers
    for stack, name, n, experts in ((0, "dense_layers", n_dense, False),
                                    (1, "layers", cfg.n_layers - n_dense,
                                     True)):
        if n == 0:
            continue
        ks = jax.random.fold_in(k_layers, stack)
        leaves = {"attn_norm": jnp.ones((n, D), dtype),
                  "mlp_norm": jnp.ones((n, D), dtype),
                  "q_norm": jnp.ones((n, cfg.latent.q_rank), dtype),
                  "kv_norm": jnp.ones((n, cfg.latent.kv_rank), dtype)}
        if cfg.indexer is not None:
            leaves["ik_norm_w"] = jnp.ones((n, cfg.indexer.head_dim), dtype)
            leaves["ik_norm_b"] = jnp.zeros((n, cfg.indexer.head_dim), dtype)
        for i, (leaf, shape, fan_in) in enumerate(
                _stack_leaves(cfg, experts)):
            k = jax.random.fold_in(ks, i)
            if leaf == "router_bias":
                leaves[leaf] = _normal_leaf(k, (n, *shape), fan_in,
                                            jnp.float32, None)
            elif leaf.startswith("we_"):
                leaves[leaf] = _expert_leaf(
                    k, cfg.moe.held_start, cfg.moe.n_held, (n, *shape),
                    fan_in, dtype)
            else:
                leaves[leaf] = _normal_leaf(k, (n, *shape), fan_in, dtype,
                                            None)
        params[name] = leaves
    return params


# Leaves of one layer of a PATTERN model (per-head attention or short conv,
# dense feed-forward or experts), in the order that numbers their keys
# (``_init_params_pattern``): (name, shape, fan-in).
def _pattern_leaves(cfg: ModelConfig, mixer, ff) -> list:
    D, KV, HD = cfg.dim, cfg.n_kv_heads, cfg.head_dim
    if mixer is None:
        leaves = []
    elif mixer == "conv":
        K = cfg.conv_cache
        leaves = [("w_in", (D, 3 * D), D), ("w_conv", (K, D), K),
                  ("w_out", (D, D), D)]
    elif mixer == "ssm":
        m = cfg.ssm
        K, H = m.conv_kernel, m.n_heads
        # a_log, dt_bias and d_skip are float32 and drawn by rules of
        # their own (``_ssm_leaf``); the "fan-in" names the rule
        # ``W_in`` as its three column blocks, [z | xBC | dt]: 10,304
        # columns in one are no multiple of 128 lanes
        leaves = [("w_z", (D, m.d_inner), D), ("w_xbc", (D, m.conv_dim), D),
                  ("w_dt", (D, H), D), ("w_conv", (K, m.conv_dim), K),
                  ("b_conv", (m.conv_dim,), K), ("a_log", (H,), "a_log"),
                  ("dt_bias", (H,), "dt_bias"), ("d_skip", (H,), "ones"),
                  ("w_out", (m.d_inner, D), m.d_inner)]
    else:
        H = cfg.attn_kind(mixer).n_heads
        leaves = [("wq", (D, H * HD), D), ("wk", (D, KV * HD), D),
                  ("wv", (D, KV * HD), D), ("wo", (H * HD, D), H * HD)]
        if cfg.attn_gate:
            leaves += [("wg", (D, H), D)]
    if ff is None:
        return leaves
    if ff == "dense":
        F = cfg.ffn_dim
        return leaves + [("w_gate", (D, F), D), ("w_up", (D, F), D),
                         ("w_down", (F, D), F)]
    m = cfg.moe
    Fe, Fs = m.expert_dim, m.shared_width
    if m.router_bias:
        leaves += [("router_bias", (m.n_routed,), 10_000)]
    leaves += [("router", (D, m.n_routed), D)]
    if not m.gated:
        # an ungated body has no gate matrix, and its up matrix lies
        # TRANSPOSED, ``[F, D]`` as the down matrix does (the width is
        # then rows, ops/grouped_experts.py)
        leaves += [("we_up", (Fe, D), D), ("we_down", (Fe, D), Fe)]
        if m.n_shared:
            leaves += [("ws_up", (Fs, D), D), ("ws_down", (Fs, D), Fs)]
        return leaves
    leaves += [("we_gate", (D, Fe), D), ("we_up", (D, Fe), D),
               ("we_down", (Fe, D), Fe)]
    if m.n_shared:
        leaves += [("ws_gate", (D, Fs), D), ("ws_up", (D, Fs), D),
                   ("ws_down", (Fs, D), Fs)]
    return leaves


@functools.partial(jax.jit, static_argnames=("shape", "rule", "lo", "hi",
                                             "floor"))
def _ssm_leaf(key, shape, rule, lo=0.0, hi=0.0, floor=0.0):
    """A Mamba-2 head's float32 scalars (``_init_params_pattern`` states
    the rules): ``a_log`` = log of a uniform draw in [1, 16]; ``dt_bias``
    = the inverse softplus of a log-uniform draw in [``lo``, ``hi``]
    floored at ``floor``; ``ones``."""
    if rule == "ones":
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if rule == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    dt = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo))
                             + math.log(lo)), floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_params_pattern(cfg: ModelConfig, k_embed, k_layers, k_head,
                         dtype) -> dict:
    """Random init of a model whose layers differ in kind
    (``cfg.layer_plan``): ``params["segments"]`` holds, for each of the
    plan's three segments (lead, period, tail), a tuple over the segment's
    layer positions of that position's leaves, stacked over the segment's
    repeats. THE RULE (the benchmark's reference draws the same bits from
    it): the leaf numbered ``i`` in ``_pattern_leaves`` of position ``q``
    of segment ``s`` is normal/sqrt(fan-in) rounded to ``dtype``, drawn at
    its stacked shape ``[repeats, ...]`` from ``fold_in(fold_in(fold_in(
    k_layers, s), q), i)``; a routed-expert leaf ``[repeats, n_held, ...]``
    draws expert ``e`` (its global number) at ``[repeats, ...]`` from
    ``fold_in(that key, e)``; ``router_bias`` is float32, 0.01 × normal;
    norms are one (a layer has one for each part it has: ``attn_norm``
    before a mixer, ``mlp_norm`` before a feed-forward part); embed and
    lm_head as for every model. An UNGATED expert body (``MoEConfig.gated``
    false) has two leaves, ``we_up`` and ``we_down`` (``ws_up``,
    ``ws_down``), both drawn at ``[F, D]``: the up matrix lies transposed.
    A Mamba-2 mixer's leaves (``"ssm"``): ``W_in`` as its three column
    blocks ``w_z``, ``w_xbc``, ``w_dt`` (fan-in D), ``w_conv`` (fan-in:
    the taps), ``b_conv`` (normal/sqrt(taps)
    too) and ``w_out`` by the rule above; its float32 scalars a head from
    the same numbered keys, drawn uniform ``u`` in [0, 1) at ``[repeats,
    heads]``: ``a_log = log(1 + 15 u)`` (so ``A = −exp(a_log)`` lies in
    [−16, −1]), ``dt_bias = softplus⁻¹(max(exp(u · log(dt_max / dt_min) +
    log dt_min), dt_floor))`` with softplus⁻¹(x) = x + log(−expm1(−x))
    (``SSMConfig.dt_min/dt_max/dt_floor``: 0.001, 0.1, 1e-4 as
    published), ``d_skip`` ones; the gated norm's weight ``ssm_norm``
    ones — a step's decay ``exp(Δ A)`` is then 0.2–0.999 at Δ = the drawn
    value: the state is neither dead nor frozen."""
    D = cfg.dim
    params = {
        "embed": _normal_leaf(k_embed, (cfg.vocab_size, D), D, dtype, None),
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal_leaf(k_head, (D, cfg.vocab_size), D,
                                         dtype, None)
    segments = []
    for s, (kinds, n) in enumerate(cfg.layer_plan):
        positions = []
        for q, (mixer, ff) in enumerate(kinds if n else ()):
            kq = jax.random.fold_in(jax.random.fold_in(k_layers, s), q)
            leaves = {}
            if mixer is not None:
                leaves["attn_norm"] = jnp.ones((n, D), dtype)
            if ff is not None:
                leaves["mlp_norm"] = jnp.ones((n, D), dtype)
            if cfg.is_attention(mixer) and cfg.qk_norm:
                leaves["q_norm"] = jnp.ones((n, cfg.head_dim), dtype)
                leaves["k_norm"] = jnp.ones((n, cfg.head_dim), dtype)
            if mixer == "ssm":
                leaves["ssm_norm"] = jnp.ones((n, cfg.ssm.d_inner), dtype)
            for i, (leaf, shape, fan_in) in enumerate(
                    _pattern_leaves(cfg, mixer, ff)):
                k = jax.random.fold_in(kq, i)
                if isinstance(fan_in, str):
                    m = cfg.ssm
                    leaves[leaf] = _ssm_leaf(k, (n, *shape), fan_in,
                                             m.dt_min, m.dt_max, m.dt_floor)
                elif leaf == "router_bias":
                    leaves[leaf] = _normal_leaf(k, (n, *shape), fan_in,
                                                jnp.float32, None)
                elif leaf.startswith("we_"):
                    leaves[leaf] = _expert_leaf(
                        k, cfg.moe.held_start, cfg.moe.n_held, (n, *shape),
                        fan_in, dtype)
                else:
                    leaves[leaf] = _normal_leaf(k, (n, *shape), fan_in,
                                                dtype, None)
            positions.append(leaves)
        segments.append(tuple(positions))
    params["segments"] = tuple(segments)
    return params


def rmsnorm(x: jax.Array, w: jax.Array, eps: float, plus_one: bool) -> jax.Array:
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    wf = w.astype(jnp.float32)
    if plus_one:
        wf = 1.0 + wf
    return (normed * wf).astype(x.dtype)


def _scale_rope_freqs(freqs: jax.Array, scaling: Optional[tuple]) -> jax.Array:
    """Apply HF-style rope_scaling to inverse frequencies.

    ("linear", factor): freqs / factor.
    ("llama3", factor, low_ff, high_ff, orig_max): long wavelengths divided
    by factor, short kept, smooth ramp between — matching the llama-3.1
    frequency-scaling scheme every 3.1/3.2 checkpoint ships in config.json.
    """
    if scaling is None:
        return freqs
    kind = scaling[0]
    if kind == "linear":
        return freqs / scaling[1]
    if kind == "llama3":
        _, factor, low_ff, high_ff, orig_max = scaling
        wavelen = 2.0 * jnp.pi / freqs
        low_wl = orig_max / low_ff
        high_wl = orig_max / high_ff
        smooth = (orig_max / wavelen - low_ff) / (high_ff - low_ff)
        interp = (1.0 - smooth) * freqs / factor + smooth * freqs
        out = jnp.where(wavelen > low_wl, freqs / factor,
                        jnp.where(wavelen < high_wl, freqs, interp))
        return out
    raise ValueError(f"unsupported rope scaling {kind!r}")


def yarn_correction_range(beta_fast: float, beta_slow: float, dim: int,
                          theta: float, orig_max: int) -> tuple:
    """The rotary dimensions between which YaRN blends: where a frequency
    turns ``beta`` times over ``orig_max`` positions,
    dim·ln(orig_max / (2π·beta)) / (2·ln theta), floored for beta_fast and
    ceiled for beta_slow, inside [0, dim/2 - 1] (dim/2 as published)."""
    def at(beta):
        return dim * math.log(orig_max / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(at(beta_fast)), 0),
            min(math.ceil(at(beta_slow)), dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attn_softmax_scale(cfg: ModelConfig) -> float:
    """What a latent model's scores are multiplied by: 1/sqrt(key width),
    and under YaRN the square of ``yarn_mscale(factor, mscale_all_dim)``."""
    scale = cfg.latent.qk_dim ** -0.5
    sc = cfg.rope_scaling
    if sc is not None and sc[0] == "yarn":
        scale *= yarn_mscale(sc[1], sc[6]) ** 2
    return scale


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling: Optional[tuple] = None,
         rotary_dim: Optional[int] = None) -> jax.Array:
    """Rotary embedding. x: [B, T, heads, hd]; positions: [B, T]. With
    ``rotary_dim`` below hd only each head's first ``rotary_dim`` values
    rotate (pairs (i, i + rotary_dim/2), frequencies and a YaRN blend
    reckoned over ``rotary_dim``); the rest pass through."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta, scaling),
             x[..., rotary_dim:]], axis=-1)
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    yarn = scaling is not None and scaling[0] == "yarn"
    if yarn:
        # NTK-by-parts (YaRN): frequency i keeps its value where it turns
        # more than beta_fast times over the original context, is divided
        # by ``factor`` where it turns less than beta_slow times, and is
        # blended linearly between the two dimensions those counts give
        _, factor, beta_fast, beta_slow, orig_max = scaling[:5]
        low, high = yarn_correction_range(beta_fast, beta_slow, hd, theta,
                                          orig_max)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
    else:
        freqs = _scale_rope_freqs(freqs, scaling)
    angles = positions.astype(jnp.float32)[:, :, None, None] * freqs  # [B,T,1,half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn:
        # cos/sin carry mscale / mscale_all_dim (1 where the two agree)
        ms = yarn_mscale(scaling[1], scaling[5]) \
            / yarn_mscale(scaling[1], scaling[6])
        cos, sin = cos * ms, sin * ms
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


# "relu2", the square of relu, is the activation of Nemotron-H's UNGATED
# feed-forward bodies (``MoEConfig.gated`` false: ``relu2(x W_u) W_d``)
_ACTIVATIONS = {"silu": jax.nn.silu,
                "gelu": functools.partial(jax.nn.gelu, approximate=True),
                "relu2": _relu2}


def _activation(x: jax.Array, kind: str) -> jax.Array:
    if kind not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {kind!r}")
    return _ACTIVATIONS[kind](x)


def _embed_lookup(params: dict, tokens: jax.Array) -> jax.Array:
    """Embedding gather, int8-aware: a quantized embed gathers the int8
    rows plus their per-row scales and dequantizes only the looked-up
    rows (never the whole [V, D] table)."""
    e = params["embed"]
    if is_quantized(e):
        q = e["q8"][tokens].astype(jnp.float32)
        s = e["scale_r"][tokens]
        # activations run at the UNQUANTIZED leaves' dtype (norms stay
        # dense) — bf16 serving, fp32 parity tests
        return (q * s[..., None]).astype(params["final_norm"].dtype)
    return e[tokens]


def _embed(params: dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    """Token embeddings as the stack takes them (scope ``embed``)."""
    with jax.named_scope("embed"):
        x = _embed_lookup(params, tokens)   # gather: [B, T, D]
        if cfg.scale_embeddings:
            x = (x.astype(jnp.float32) * (cfg.dim ** 0.5)).astype(x.dtype)
        return x


@jax.named_scope("mlp")
def _mlp(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """The shared MLP block: rmsnorm → gate·up → down, weights
    dequantized on the fly when quantized (models/quant.py). One
    implementation so the four forward variants can never drift."""
    h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps, cfg.rmsnorm_plus_one)
    gate = _activation(
        jnp.einsum("btd,df->btf", h, dequant_weight(p["w_gate"], h.dtype)),
        cfg.activation)
    up = jnp.einsum("btd,df->btf", h, dequant_weight(p["w_up"], h.dtype))
    return x + jnp.einsum("btf,fd->btd", gate * up,
                          dequant_weight(p["w_down"], h.dtype))


def _qkv(x: jax.Array, p: dict, cfg: ModelConfig, B: int, T: int,
         positions: jax.Array, kind=None
         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The shared attention-input block: rmsnorm → q/k/v projections
    (+ optional bias) reshaped to head layout, weights dequantized on
    the fly when quantized, with ``cfg.qk_norm`` an RMSNorm over each
    head's values of q and k (scope ``qkv`` ⊃ ``qk_norm``), then rotary
    embedding of q and k at ``positions`` (scope ``rope``). ``kind``
    (config.AttnKind; None: the model's one kind) gives the layer's query
    heads and rotary."""
    kind = kind or cfg.attn_kind()
    with jax.named_scope("qkv"):
        h = rmsnorm(x, p["attn_norm"], cfg.norm_eps, cfg.rmsnorm_plus_one)
        q = jnp.einsum("btd,dh->bth", h, dequant_weight(p["wq"], h.dtype))
        k = jnp.einsum("btd,dh->bth", h, dequant_weight(p["wk"], h.dtype))
        v = jnp.einsum("btd,dh->bth", h, dequant_weight(p["wv"], h.dtype))
        if cfg.attn_bias:               # Qwen2-style QKV biases
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(B, T, kind.n_heads, cfg.head_dim)
        k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = rmsnorm(q, p["q_norm"], cfg.norm_eps,
                            cfg.rmsnorm_plus_one)
                k = rmsnorm(k, p["k_norm"], cfg.norm_eps,
                            cfg.rmsnorm_plus_one)
    if not cfg.rope:        # no positional embedding at all (Nemotron-H)
        return q, k, v
    with jax.named_scope("rope"):
        q = rope(q, positions, kind.rope_theta, kind.rope_scaling,
                 kind.rotary_dim)
        k = rope(k, positions, kind.rope_theta, kind.rope_scaling,
                 kind.rotary_dim)
    return q, k, v


@jax.named_scope("attn_gate")
def _attn_gate(x: jax.Array, attn: jax.Array, p: dict,
               cfg: ModelConfig) -> jax.Array:
    """``cfg.attn_gate``: head n's output times sigmoid((norm(x) W_g)_n),
    the layer's normed input through ``wg`` [dim, heads], in float32.
    attn: [1, T, H, hd] float32."""
    h = rmsnorm(x, p["attn_norm"], cfg.norm_eps, cfg.rmsnorm_plus_one)
    g = jnp.einsum("btd,dh->bth", h, p["wg"],
                   preferred_element_type=jnp.float32)
    return attn * jax.nn.sigmoid(g)[..., None]


@jax.named_scope("attn_out")
def _attn_out(x: jax.Array, attn: jax.Array, p: dict,
              cfg: ModelConfig) -> jax.Array:
    """Residual + output projection of the attended heads (as many as
    ``attn`` has: a layer's kind decides), each times its gate first where
    the model has one (scope ``attn_out`` ⊃ ``attn_gate``)."""
    if cfg.attn_gate:
        attn = _attn_gate(x, attn, p, cfg).astype(x.dtype)
    wo = dequant_weight(p["wo"], x.dtype).reshape(
        attn.shape[2], cfg.head_dim, cfg.dim)
    return x + jnp.einsum("bthd,hdD->btD", attn, wo)


def _latent_qkv(x: jax.Array, p: dict, cfg: ModelConfig,
                positions: jax.Array) -> tuple:
    """Latent attention's inputs in the FOLDED form, for a flat tick
    ``x [1, Tp, D]``: the row a token stores, ``[c_kv | k_rope | 0]``
    (``[Tp, lanes]``), and the queries every head puts against such rows,
    ``[q_nope·W_kb^K | q_rope | 0]`` (``[Tp, H, lanes]``): the key
    up-projection rides the query, so a cached latent is read as it lies
    (scope ``qkv`` ⊃ ``latent_proj``, then ``rope``). Also returns what
    an indexer projects from: the normed input ``h`` and the normed query
    latent ``c_q`` (``[1, Tp, ·]``)."""
    la, H = cfg.latent, cfg.n_heads
    Tp = x.shape[1]
    with jax.named_scope("qkv"), jax.named_scope("latent_proj"):
        h = rmsnorm(x, p["attn_norm"], cfg.norm_eps, cfg.rmsnorm_plus_one)
        cq = rmsnorm(jnp.einsum("btd,dr->btr", h, p["wq_a"]), p["q_norm"],
                     cfg.norm_eps, False)
        q = jnp.einsum("btr,rh->bth", cq, p["wq_b"]).reshape(
            1, Tp, H, la.qk_dim)
        ckv = jnp.einsum("btd,dr->btr", h, p["wkv_a"])
        c_kv = rmsnorm(ckv[..., :la.kv_rank], p["kv_norm"], cfg.norm_eps,
                       False)
        # fold: score_i = (q_nope_i W_kb,i^K^T)·c_kv + q_rope_i·k_rope
        w_k = p["wkv_b"].reshape(la.kv_rank, H,
                                 la.nope_dim + la.v_dim)[..., :la.nope_dim]
        q_lat = jnp.einsum("bthn,chn->bthc", q[..., :la.nope_dim], w_k)
    with jax.named_scope("rope"):
        q_rope = rope(q[..., la.nope_dim:], positions, cfg.rope_theta,
                      cfg.rope_scaling)
        k_rope = rope(ckv[:, :, None, la.kv_rank:], positions,
                      cfg.rope_theta, cfg.rope_scaling)[:, :, 0]
    pad = la.lanes - la.kv_rank - la.rope_dim
    row = jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((1, Tp, pad), x.dtype)], axis=-1)[0]
    q_full = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((1, Tp, H, pad), x.dtype)], axis=-1)[0]
    return q_full, row, h, cq


@jax.named_scope("index_proj")
def _index_inputs(h: jax.Array, cq: jax.Array, p: dict, cfg: ModelConfig,
                  positions: jax.Array) -> tuple:
    """The indexer's inputs for a flat tick (``h`` the attention's normed
    input, ``cq`` its normed query latent, both ``[1, Tp, ·]``): queries
    ``[Tp, Hi, di]`` from the query latent, ONE key a token ``[Tp, di]``
    (LayerNorm: mean subtracted, eps 1e-6), rotary on the first
    ``rope_dim`` values of each (the model's frequencies), and the float32
    head weights ``[Tp, Hi]`` with the published constants
    ``Hi^-1/2 · di^-1/2`` in them."""
    ix = cfg.indexer
    Tp = h.shape[1]
    q = jnp.einsum("btr,rh->bth", cq, p["wi_q"]).reshape(
        1, Tp, ix.n_heads, ix.head_dim)
    k = jnp.einsum("btd,dk->btk", h, p["wi_k"]).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + 1e-6)
    k = (k * p["ik_norm_w"].astype(jnp.float32)
         + p["ik_norm_b"].astype(jnp.float32)).astype(h.dtype)[:, :, None]

    def rotate(x):
        return jnp.concatenate(
            [rope(x[..., :ix.rope_dim], positions, cfg.rope_theta,
                  cfg.rope_scaling), x[..., ix.rope_dim:]], axis=-1)

    w = jnp.einsum("btd,dh->bth", h, p["wi_w"],
                   preferred_element_type=jnp.float32) \
        * (ix.n_heads ** -0.5 * ix.head_dim ** -0.5)
    return rotate(q)[0], rotate(k)[0, :, 0], w[0]


@jax.named_scope("index_select")
def select_keys(scores: jax.Array, block_meta: jax.Array, tq: int,
                topk: int) -> jax.Array:
    """The learned selection, exact: ``scores [T, S]`` float32 (a flat
    tick's queries against every position of their rows' tables) →
    int32 ``[T, S]``, 1 where the query attends: its visible positions
    (``block_meta``: ``s <= qpos``, ``s < kv_len``) when there are at most
    ``topk`` of them, else the ``topk`` of largest score, ties to the
    lower position.

    No sort: the ``topk``-th largest score is found digit by digit on the
    scores' bits in an order-preserving unsigned form (two bits a pass,
    each pass one fused compare-and-count over the scores), and among the
    positions that tie with it the cut is found the same way on the
    position's bits. 24 passes at 16k positions; every pass streams what
    the scoring kernel wrote and nothing of the size is made."""
    T, S = scores.shape
    kv_len, qpos0, nq, _ = (jnp.repeat(block_meta[j], tq) for j in range(4))
    t_in = jnp.arange(T, dtype=jnp.int32) % tq
    s_idx = jnp.arange(S, dtype=jnp.int32)[None]
    visible = ((s_idx <= (qpos0 + t_in)[:, None]) & (s_idx < kv_len[:, None])
               & (t_in < nq)[:, None])
    # float32 → uint32 of the same order (-0.0 as +0.0); unseen → 0
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32), jnp.uint32)
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    u = jnp.where(visible, u, jnp.uint32(0))

    def climb(n_bits, dtype, holds):
        """The largest value whose ``holds(candidates [T, 3]) -> bool``
        is true, two bits a pass; ``holds`` is monotone (true up to it)."""
        v = jnp.zeros((T,), dtype)
        for shift in range(n_bits + n_bits % 2 - 2, -1, -2):
            c = v[:, None] | (jnp.arange(1, 4, dtype=dtype) << shift)
            v = v | (holds(c).sum(-1).astype(dtype) << shift)
        return v

    # the topk-th largest: the largest v with at least topk scores >= v
    thr = climb(32, jnp.uint32, lambda c: (
        u[:, None, :] >= c[:, :, None]).sum(-1) >= topk)[:, None]
    above = u > thr
    ties = (u == thr) & visible
    need = (topk - above.sum(-1))[:, None]
    # ... and of its ties the first `need`: the largest position p with
    # fewer than `need` ties below it is the last one taken
    cut = climb(max(S - 1, 1).bit_length(), jnp.int32, lambda c: (
        ties[:, None, :] & (s_idx[None] < c[:, :, None])).sum(-1) < need)
    return (visible & (above | (ties & (s_idx <= cut[:, None])))
            ).astype(jnp.int32)


@jax.named_scope("attn_out")
def _latent_attn_out(x: jax.Array, attn: jax.Array, p: dict,
                     cfg: ModelConfig) -> jax.Array:
    """Residual + the value up-projection folded into the output:
    ``o_i = (p_i·c_kv) W_kb,i^V``, then ``W_o``. attn: [Tp, H, kv_rank]."""
    la, H = cfg.latent, cfg.n_heads
    with jax.named_scope("latent_proj"):
        w_v = p["wkv_b"].reshape(la.kv_rank, H,
                                 la.nope_dim + la.v_dim)[..., la.nope_dim:]
        o = jnp.einsum("thc,chv->thv", attn.astype(x.dtype), w_v)
    # flat over H·v: the layer scan's slice of wo fuses INTO this matmul
    return x + jnp.einsum("tk,kD->tD", o.reshape(len(o), -1), p["wo"])[None]


def moe_select(logits: jax.Array, m,
               bias: Optional[jax.Array] = None
               ) -> tuple[jax.Array, jax.Array]:
    """Router logits [T, n_routed] float32 → (experts [T, k] int32, gates
    [T, k] float32): scores by ``m.score`` (sigmoid, an expert at a time,
    or softmax over all ``n_routed``); the experts fall into ``n_group``
    groups, a group scores the sum of its two largest, the ``topk_group``
    best groups stay; the ``k`` largest scores inside them are selected;
    gates are the selected scores over their sum plus ``gate_eps``
    (``norm_topk``) times ``routed_scale``. Ties go to the lower index.
    With ``bias`` (float32 [n_routed], the ``noaux_tc`` correction) groups
    and experts are chosen by score + bias; the gates are the bare scores
    of the chosen."""
    T, E = logits.shape
    if m.score == "softmax":
        s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
    pick = s if bias is None else s + bias
    if m.n_group > 1:
        g = pick.reshape(T, m.n_group, E // m.n_group)
        group = jax.lax.top_k(g, 2)[0].sum(-1)               # [T, n_group]
        keep = jax.lax.top_k(group, m.topk_group)[1]         # [T, topk]
        mask = jnp.zeros((T, m.n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        pick = jnp.where(mask[:, :, None], g,
                         -1.0 if bias is None else -jnp.inf).reshape(T, E)
    gates, idx = jax.lax.top_k(pick, m.per_token)
    if bias is not None:
        gates = jnp.take_along_axis(s, idx, axis=-1)
    if m.norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + m.gate_eps)
    return idx.astype(jnp.int32), gates * m.routed_scale


def _gated(h: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
           act: str) -> jax.Array:
    return (_activation(h @ wg, act) * (h @ wu)) @ wd


def _ffn_body(h: jax.Array, w: tuple, act: str) -> jax.Array:
    """An expert's body from its matrices: three, the gated form; two
    (``MoEConfig.gated`` false), ``act(h W_uᵀ) W_d``, both ``[F, D]``."""
    if len(w) == 3:
        return _gated(h, *w, act)
    return _activation(jnp.einsum("td,fd->tf", h, w[0]), act) @ w[1]


def _expert_leaves(m) -> tuple:
    """The names of a routed expert's stacked matrices, in the order an
    expert's body takes them."""
    return ("we_gate", "we_up", "we_down") if m.gated \
        else ("we_up", "we_down")


# Rows of one block of the grouped matmul: a block holds assignments to ONE
# expert (an expert's assignments are padded up to whole blocks).
MOE_BLOCK = 256
# Tokens of a tick that go through the grouped experts at a time: the layout
# holds a row for EVERY assignment (any routing is exact), 10 a token at
# 3,072 wide twice over (in and out) — 3 GiB at a 16,384-token tick whole,
# under 1 GiB at 4,096, the longest tick any accepted cell has.
MOE_TICK = 4096


def _routed_experts(h: jax.Array, idx: jax.Array, gates: jax.Array,
                    w: tuple, layer, m, act: str,
                    valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The HELD experts' part of the routed sum, exact for every routing:
    ``Σ_{e selected and held} g_e FFN_e(h)`` for each valid token; what an
    absent expert would add is left out. h [T, D]; idx/gates [T, k];
    ``w`` the stacked expert weights ``[L, n_held, ...]`` whole, read at
    ``[layer, e]``. Returns ([T, D] float32, int32 [4]: assignments,
    assignments to held experts, held experts with a token, 1).

    Grouped by expert without a capacity: the token-expert assignments to
    held experts are sorted by expert, each expert's run is cut into
    blocks of ``MOE_BLOCK`` rows, and a loop over the blocks THAT EXIST
    gathers a block's tokens, multiplies them by that one expert's three
    matrices and adds the gated result to its tokens' rows. Work and
    weight traffic follow the real routing (a decode step reads only the
    experts its rows reached); the worst case, every token choosing held
    experts, only makes the loop longer."""
    T, D = h.shape
    k, E = m.per_token, m.n_held
    A = T * k
    blk = min(MOE_BLOCK, -(-T // 8) * 8)
    local = idx - m.held_start
    held = (local >= 0) & (local < E) & valid[:, None]
    local = jnp.where(held, local, E).reshape(A)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    counts = (local[:, None] == jnp.arange(E, dtype=jnp.int32)).sum(
        0, dtype=jnp.int32)                                  # [E]
    starts = jnp.cumsum(counts) - counts
    n_blk = (counts + blk - 1) // blk
    blk_end = jnp.cumsum(n_blk)
    flat_gates = gates.reshape(A)

    def body(b, out):
        e = jnp.sum(blk_end <= b).astype(jnp.int32)          # b's expert
        pos = starts[e] + (b - (blk_end[e] - n_blk[e])) * blk \
            + jnp.arange(blk, dtype=jnp.int32)
        ok = pos < starts[e] + counts[e]
        a = order[jnp.minimum(pos, A - 1)]
        tok = a // k
        y = _ffn_body(h[tok], tuple(m_[layer, e] for m_ in w), act)
        y = y.astype(jnp.float32) * jnp.where(ok, flat_gates[a], 0.0)[:, None]
        # a block's tokens ascend (stable sort), the dropped rows last
        return out.at[jnp.where(ok, tok, T)].add(y, mode="drop",
                                                 indices_are_sorted=True)

    out = jax.lax.fori_loop(0, blk_end[-1], body,
                            jnp.zeros((T, D), jnp.float32))
    stats = jnp.stack([valid.sum(dtype=jnp.int32) * k, counts.sum(),
                       (counts > 0).sum(dtype=jnp.int32),
                       valid.any().astype(jnp.int32)])
    return out, stats


def _routed_experts_grouped(h: jax.Array, idx: jax.Array, gates: jax.Array,
                            w: tuple, layer, m, act: str, valid: jax.Array,
                            interpret: bool = False
                            ) -> tuple[jax.Array, jax.Array]:
    """``_routed_experts`` with the blocks' work in ONE kernel
    (ops/grouped_experts.py): the same grouping — an expert's assignments
    in their own order, cut into blocks of one expert's rows — laid out
    for all blocks at once and without a sort: an assignment's row is its
    expert's first block plus its place among that expert's assignments (a
    running count down a one-hot table). Then a gather of the blocks'
    tokens, the kernel over the blocks THAT EXIST (a scalar table names
    each block's expert, so the weights read are those the routing
    reached), and each token's sum over its own assignments' rows, gated,
    in float32. A score of device operations a layer where the loop runs
    eighteen a block. Returns ([T, D] float32, int32 [3, n_held]: each
    held expert's assignments, 1 where it has any, and the blocks of
    ``moe_block_rows(T)`` rows the kernel ran for it — what of
    ``_routed_experts``' four counts differs from layer to layer, and what
    the layout cost; the caller sums them and adds the tick's own,
    ``moe_counts``)."""
    from quoracle_tpu.ops.grouped_experts import grouped_ffn
    T, D = h.shape
    k, E = m.per_token, m.n_held
    A = T * k
    blk = moe_block_rows(T)
    NB = min(E, A) + A // blk            # Σ ceil(c_e / blk) is at most this
    local = idx - m.held_start
    held = (local >= 0) & (local < E) & valid[:, None]
    hot = (jnp.where(held, local, E).reshape(A, 1)
           == jnp.arange(E, dtype=jnp.int32)).astype(jnp.int32)   # [A, E]
    ahead = jnp.cumsum(hot, axis=0) - hot    # of its expert's, before it
    counts = hot.sum(0)                                           # [E]
    n_blk = (counts + blk - 1) // blk
    blk_end = jnp.cumsum(n_blk)
    first = blk_end - n_blk                  # an expert's first block
    e_b = jnp.minimum((blk_end[None, :] <= jnp.arange(
        NB, dtype=jnp.int32)[:, None]).sum(1, dtype=jnp.int32), E - 1)
    row = jnp.where(held.reshape(A),
                    (hot * (first * blk + ahead)).sum(1), NB * blk)
    tok = jnp.zeros((NB * blk,), jnp.int32).at[row].set(
        jnp.arange(A, dtype=jnp.int32) // k, mode="drop",
        unique_indices=True)                 # rows no one has: token 0
    y = grouped_ffn(h[tok].reshape(NB, blk, D), *(None,) * (3 - len(w)), *w,
                    layer, e_b, blk_end[-1], act=_ACTIVATIONS[act],
                    interpret=interpret)
    mine = y.reshape(NB * blk, D)[jnp.minimum(row, NB * blk - 1)]
    out = jnp.where(held[..., None], gates[..., None]
                    * mine.reshape(T, k, D).astype(jnp.float32), 0.0).sum(1)
    return out, jnp.stack([counts, (counts > 0).astype(jnp.int32), n_blk])


def moe_block_rows(n_tokens: int) -> int:
    """Rows of a block of the grouped kernel in a forward of ``n_tokens``
    (a long tick's pieces of ``MOE_TICK`` have ``MOE_BLOCK``)."""
    return min(MOE_BLOCK, -(-n_tokens // 16) * 16)


# What a forward's expert layers report: ``_routed_experts``' four counts
# and, where the grouped kernel ran them, two more (``moe_counts``).
MOE_STATS, MOE_STATS_GROUPED = 4, 6


def experts_grouped(cfg: ModelConfig, interpret) -> bool:
    """Whether ``cfg``'s expert layers run as one kernel a layer
    (``_routed_experts_grouped``): the pattern forward's, on the TPU and
    where a test asks for the kernels; the loop over blocks elsewhere, and
    in the latent models, which keep their accepted programs."""
    from quoracle_tpu.ops.paged_attention import _on_tpu
    return cfg.latent is None and (bool(interpret) or _on_tpu())


def moe_stats_len(cfg: ModelConfig, interpret) -> int:
    """How many counts ``forward_hidden_ragged`` returns for ``cfg``."""
    return MOE_STATS_GROUPED if experts_grouped(cfg, interpret) \
        else MOE_STATS


def moe_counts(per_expert: jax.Array, valid: jax.Array, m,
               n_layers: int) -> jax.Array:
    """``_routed_experts``' int32 [4] summed over ``n_layers`` expert
    layers of one forward, from the sum of ``_routed_experts_grouped``'s
    [3, n_held] over them and the tick's valid tokens, and behind them
    what the grouped layout cost: the blocks the kernel ran and the rows
    they hold (a block is one expert's, so 64 experts with a token each
    are 64 blocks of ``moe_block_rows`` rows whatever the tokens)."""
    blocks = per_expert[2].sum()
    return jnp.stack([
        valid.sum(dtype=jnp.int32) * (m.per_token * n_layers),
        per_expert[0].sum(), per_expert[1].sum(),
        valid.any().astype(jnp.int32) * n_layers,
        blocks, blocks * moe_block_rows(valid.shape[0])])


@jax.named_scope("mlp")
def _moe(x: jax.Array, p: dict, experts: tuple, layer, cfg: ModelConfig,
         valid: jax.Array, grouped: bool = False,
         interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """The expert layer of a flat tick ``x [1, T, D]``: router over ALL
    ``n_routed`` experts in float32 (scope ``router``), the held experts'
    part of the routed sum (``routed_experts``), the shared expert where
    the model has one (``shared_expert``), residual. Returns (x, the counts
    of ``_routed_experts``, or of ``_routed_experts_grouped``).
    ``grouped``: the blocks in one kernel a layer
    (``_routed_experts_grouped``) in place of the loop over them; the
    pattern forward asks for it on the TPU — the latent models keep the
    loop, and with it their accepted programs."""
    m = cfg.moe
    h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps, cfg.rmsnorm_plus_one)[0]
    with jax.named_scope("router"):
        logits = jnp.dot(h.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        idx, gates = moe_select(logits, m, p.get("router_bias"))
    with jax.named_scope("routed_experts"):
        T = h.shape[0]
        if grouped and T > MOE_TICK and T % MOE_TICK == 0:
            # a long tick's tokens MOE_TICK at a time: the grouped layout
            # is sized for every assignment falling on a held expert
            def part(c):
                return _routed_experts_grouped(
                    *c[:3], experts, layer, m, cfg.activation, c[3],
                    interpret)
            routed, stats = jax.lax.map(part, tuple(
                a.reshape(T // MOE_TICK, MOE_TICK, *a.shape[1:])
                for a in (h, idx, gates, valid)))
            routed = routed.reshape(T, -1)
            stats = jnp.stack([stats[:, 0].sum(0), stats[:, 1].max(0),
                               stats[:, 2].sum(0)])
        elif grouped:
            routed, stats = _routed_experts_grouped(
                h, idx, gates, experts, layer, m, cfg.activation, valid,
                interpret)
        else:
            routed, stats = _routed_experts(h, idx, gates, experts, layer,
                                            m, cfg.activation, valid)
    if m.n_shared:
        with jax.named_scope("shared_expert"):
            shared = _ffn_body(h, tuple(p[k] for k in (
                "ws_gate", "ws_up", "ws_down") if k in p), cfg.activation)
        routed = routed + shared.astype(jnp.float32)
    return x + routed.astype(x.dtype)[None], stats


@jax.named_scope("final_norm")
def _final_norm(x: jax.Array, params: dict, cfg: ModelConfig) -> jax.Array:
    return rmsnorm(x, params["final_norm"], cfg.norm_eps,
                   cfg.rmsnorm_plus_one)


def forward_hidden(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,       # [B, T] int32
    positions: jax.Array,    # [B, T] int32 absolute positions
    cache: KVCache,
    write_offset: jax.Array,  # [B] int32: where this chunk's kv entries land
    kv_lens: jax.Array,       # [B] int32 valid kv count AFTER this chunk
    kv_pos_offset: Optional[jax.Array] = None,  # [B] int32: absolute position
                                                # of kv buffer index 0
    ring: Optional[tuple] = None,   # (mesh, seq_axis, batch_axis, head_axis):
                                    # sequence-parallel prefill — attention
                                    # runs as ring_attend over the chunk
                                    # itself (fresh full-prompt prefill only)
    input_embeds: Optional[jax.Array] = None,   # [B, T, D] overrides the
                                    # embedding lookup (VLM soft tokens).
                                    # Callers pass these FULLY PREPARED —
                                    # scale_embeddings is NOT re-applied
                                    # (image features splice in unscaled,
                                    # matching standard VLM semantics)
    shard: Optional[tuple] = None,  # (mesh, tp_axis|None, dp_axis|None) of
                                    # a mesh engine: the flash kernel runs
                                    # per shard (ops/flash_attention.py)
) -> tuple[jax.Array, KVCache]:
    """Run the stack over a token chunk, updating the cache; returns final
    hidden states [B, T, D] (pre-head) — see project_logits.

    The kv buffer is position-ordered (a token at absolute position p lives at
    buffer index p), so right-padded prompt rows simply leave garbage beyond
    ``kv_lens[b]`` which the attention validity mask ignores; decode later
    overwrites index ``lens[b]`` with the real next token.

    The caller advances ``cache.lens`` — keeping length bookkeeping out of
    the traced body lets the same trace serve speculative / chunked prefill.
    """
    require_plain(cfg, "forward_hidden (the dense-cache forward)")
    B, T = tokens.shape
    if input_embeds is not None:
        x = input_embeds                # prepared by the caller (VLM)
    else:
        x = _embed(params, cfg, tokens)

    # Offsets are per-row; rows share one buffer write position only when all
    # offsets are equal. We write per-row with a vmap'd dynamic slice.
    def write_row(buf_l, new_l, off):
        # buf_l: [S, n_kv, hd]; new_l: [T, n_kv, hd]
        return jax.lax.dynamic_update_slice_in_dim(buf_l, new_l, off, axis=0)

    def layer_body(x, scanned):
        p, k_buf, v_buf = scanned  # p: one layer's params; bufs: [B, S, kv, hd]
        q, k, v = _qkv(x, p, cfg, B, T, positions)

        with jax.named_scope("kv_write"):
            k_buf = jax.vmap(write_row)(k_buf, k, write_offset)
            v_buf = jax.vmap(write_row)(v_buf, v, write_offset)

        with jax.named_scope("attn"):
            if ring is not None:
                # Sequence-parallel prefill: the chunk IS the whole
                # (fresh) prompt, so attention is chunk-vs-chunk — K/V
                # shards rotate the ring while each device keeps its Q
                # shard (SURVEY §5 long-context; ops/ring_attention.py).
                from quoracle_tpu.ops.ring_attention import ring_attend
                mesh_, seq_ax, batch_ax, head_ax = ring
                attn = ring_attend(mesh_, q, k, v, kv_len=kv_lens,
                                   axis_name=seq_ax,
                                   sliding_window=cfg.sliding_window,
                                   batch_axis=batch_ax, head_axis=head_ax)
            else:
                # attend_auto: pallas flash kernel for long prefill
                # chunks on TPU, dense fused XLA otherwise (decode
                # steps, CPU tests).
                from quoracle_tpu.ops.flash_attention import attend_auto
                attn = attend_auto(q, k_buf, v_buf, positions,
                                   kv_len=kv_lens,
                                   sliding_window=cfg.sliding_window,
                                   kv_pos_offset=kv_pos_offset,
                                   shard=shard)
        x = _attn_out(x, attn, p, cfg)
        x = _mlp(x, p, cfg)
        return x, (k_buf, v_buf)

    with jax.named_scope("layers"):
        x, (new_k, new_v) = jax.lax.scan(
            layer_body, x, (params["layers"], cache.k, cache.v))
    return (_final_norm(x, params, cfg),
            KVCache(k=new_k, v=new_v, lens=cache.lens))


# Slots of a block of the dense chunk forward's per-token work: a tick of
# two such blocks or more runs norms, projections, rope and MLP over the
# blocks that hold a token, not over its bucket
# (generate.RAGGED_TOKEN_BUCKETS; PERF.md §6, PR 49 has both candidates'
# readings).
LIVE_BLOCK = 1024


def live_token_slots(Tp: int, filled: int, sharded: bool = False) -> int:
    """The slots of a bucket of ``Tp`` whose per-token work the dense chunk
    forward runs when the tick's rows fill the first ``filled``: the blocks
    of ``LIVE_BLOCK`` that hold one, in a tick of two blocks or more that
    is not built under a mesh; else the bucket."""
    if Tp < 2 * LIVE_BLOCK or sharded:
        return Tp
    return min(Tp, -(-filled // LIVE_BLOCK) * LIVE_BLOCK)


def forward_hidden_ragged(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,       # [1, Tp] int32 token-major FLATTENED batch
    positions: jax.Array,    # [1, Tp] int32 absolute positions per token
    k_pool: jax.Array,       # [L, n_pages, page, KV·hd] — the pool AS
    v_pool: jax.Array,       # STORED (donated by jit), updated in place
    row_tables: jax.Array,   # [R, maxp] int32 — one page table per row
    block_meta: jax.Array,   # [4, NB] int32: kv_len, qpos0, nq, row
    flat_dst: jax.Array,     # [Tp] int32 token slot per flattened token in
                             # ONE layer's pages (page·128 + offset, from
                             # the owning row's DST page table); any value
                             # >= n_pages·page is the drop sentinel
    tq: int,
    interpret: Optional[bool] = None,
    shard: Optional[tuple] = None,   # (mesh, tp_axis)
    k_scale: Optional[jax.Array] = None,   # [L, n_pages, KV, page] f32
    v_scale: Optional[jax.Array] = None,   # per-(token, kv-head) scales
    tiles: Optional[jax.Array] = None,     # [6, NT] int32: the blocks in
    tile: int = 0,                         # tiles of <= ``tile`` tokens
    shared: Optional[jax.Array] = None,    # [2 + SHARED_ROWS, R] int32
    conv: Optional["ConvTick"] = None,     # a model with conv layers: its
                                           # state pool and the tick's use
    ssm: Optional["SsmTick"] = None,       # a model with ssm layers: its
                                           # record pools and the tick's use
) -> tuple:
    """UNIFIED ragged forward (ISSUE 8): one launch per layer over a
    token-major flattened batch of rows with arbitrary query lengths —
    T=1 decode rows, T=chunk continuations, T=suffix prefills and T=K
    speculative-verify rows all in one grid. Each layer scatters the
    chunk's KV into the rows' pages FIRST, then attention streams each
    block's real pages (ops/paged_attention.ragged_attend_auto) — the
    [B, maxp·page] working cache, the dense intra-chunk piece, and the
    decode tail buffer all cease to exist. Returns
    (hidden [1, Tp, D], k_pool, v_pool, k_scale, v_scale, moe_stats) with
    the chunk KV written (the scales None as they came, on unquantized
    pools; ``moe_stats`` None but for a model with expert layers, see
    ``_forward_hidden_ragged_stacks``, which serves latent attention and
    expert layers under this same contract, as
    ``_forward_hidden_ragged_pattern`` serves a model whose layers differ
    in kind; with ``conv``, what a model with conv layers hands in, the
    tuple has a seventh member, the state pool updated; with ``ssm``, the
    pair of record pools).

    The pools never move (PR 25): they ride the layer scan as a CARRY,
    whole and in their stored lane-flat layout, each layer writes its Tp
    fresh rows into the pool viewed as [L·n_pages·page, KV·hd] (merging
    major dimensions of 128-row pages is a bitcast) at
    ``layer·n_tok + flat_dst``, and the kernel is handed the whole pool
    and the layer index. Nothing the size of a layer's pool is sliced,
    stacked, reshaped or copied in a step — scanned as ``xs``/``ys``
    they were, four times over (PERF.md §6).

    With ``k_scale``/``v_scale`` (ISSUE 13) the pools are INT8: each
    layer quantizes the chunk's fresh KV per (token, kv-head)
    (models/quant.kv_quant), scatters int8 payloads into the pages and
    fp32 scales into the page-structured scale pools — carried and
    indexed by layer the same way — and the attention dequantizes inside
    the kernel's streaming loop.

    With ``tiles`` (ops/paged_attention.ragged_tiles of ``block_meta``)
    the dense kernel walks a row's pages once per tile of its queries
    and not once per block; with ``shared`` (``shared_walks`` of
    ``row_tables``; the decode step, one token a row) it walks the pages
    that rows have in common once for all of them: schedules, not layouts
    — nothing else here reads either."""
    if cfg.latent is not None:
        assert shard is None and k_scale is None and tiles is None, \
            "latent/expert models: no tp shards, no int8 pages, no tiles"
        return _forward_hidden_ragged_stacks(
            params, cfg, tokens, positions, k_pool, v_pool, row_tables,
            block_meta, flat_dst, tq, interpret, shared)
    if not cfg.plain:
        assert shard is None and k_scale is None, \
            "expert/hybrid models: no tp shards, no int8 pages"
        return _forward_hidden_ragged_pattern(
            params, cfg, tokens, positions, k_pool, v_pool, row_tables,
            block_meta, flat_dst, tq, interpret, tiles, tile, shared, conv,
            ssm)
    from quoracle_tpu.ops.paged_attention import ragged_attend_auto
    B, Tp = tokens.shape       # B == 1: the flat layout is the batch
    L, n_pages, page, lanes = k_pool.shape
    n_tok = n_pages * page
    quant = k_scale is not None
    x = _embed(params, cfg, tokens)
    # A dropped write must stay dropped in EVERY layer: the per-layer
    # sentinel n_tok is the next layer's first slot once the layer offset
    # is added, so the drop is decided here, before it.
    keep = flat_dst < n_tok
    if quant:
        pid = jnp.where(keep, flat_dst // page, n_pages)  # OOB page = drop
        off = flat_dst % page

    # A tick of two blocks of LIVE_BLOCK slots or more runs a layer's
    # per-token work over its LIVE blocks alone (the real segments lie from
    # slot 0, the padding is one run behind them: generate._run_unified),
    # a loop with a traced trip count; a shorter tick, and a program built
    # under a mesh, keeps the whole-Tp form (``live_token_slots`` is the
    # rule, shared with the host's counter: a tick has blocks where one
    # filled slot does not make its whole bucket live).
    blocked = live_token_slots(Tp, 1, shard is not None) < Tp
    layers = params["layers"]
    if blocked:
        assert Tp % LIVE_BLOCK == 0, (Tp, LIVE_BLOCK)
        last = jnp.max(jnp.where(block_meta[2] > 0, jnp.arange(
            1, block_meta.shape[1] + 1, dtype=jnp.int32), 0))
        n_live = (last * tq + LIVE_BLOCK - 1) // LIVE_BLOCK
        zeros = tuple(jnp.zeros((B, Tp, n, cfg.head_dim), x.dtype)
                      for n in (cfg.attn_kind().n_heads, cfg.n_kv_heads,
                                cfg.n_kv_heads))
        # The MLP's matrices are sliced from their stacks INSIDE a block's
        # turn, where each matmul reads its slice as it lies: as the scan's
        # slices they would be copied through HBM once a layer for the
        # loop to read (PR 36). The barrier ties the layer's index to the
        # block's, or the compiler hoists the slices out of the loop all
        # the same. The attention's projections and the norms stay the
        # scan's slices: they fit fast memory.
        mlp_stacks = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
        layers = {k: w for k, w in layers.items() if k not in mlp_stacks}

        def mlp_weights(layer, start):
            at, _ = jax.lax.optimization_barrier((layer, start))
            return jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
                w, at, keepdims=False), mlp_stacks)

        def live_blocks(body, init):
            """``body(start, carry)`` for the first slot of each live
            block, in order."""
            return jax.lax.fori_loop(
                0, n_live, lambda i, c: body(i * LIVE_BLOCK, c), init)

        def block_of(a, start):
            return jax.lax.dynamic_slice_in_dim(a, start, LIVE_BLOCK, axis=1)

        def put_block(a, blk, start):
            return jax.lax.dynamic_update_slice_in_dim(a, blk, start, axis=1)

    def qkv(x, p):
        """``_qkv`` of the tick: of its live blocks, where it has several
        (a dead block's q, k and v are zeros: its writes drop, its tiles
        write zeros)."""
        if not blocked:
            return _qkv(x, p, cfg, B, Tp, positions)

        def body(start, qkv_all):
            blk = _qkv(block_of(x, start), p, cfg, B, LIVE_BLOCK,
                       block_of(positions, start))
            return tuple(put_block(a, b, start)
                         for a, b in zip(qkv_all, blk))
        return live_blocks(body, zeros)

    def out_mlp(x, attn, p, layer):
        """The layer behind its attention, ``_attn_out`` and ``_mlp``, the
        same way: a dead block's hidden state stays what it was (nothing
        reads it: a row's logits come from its last real token)."""
        if not blocked:
            return _mlp(_attn_out(x, attn, p, cfg), p, cfg)

        def body(start, x):
            blk = _attn_out(block_of(x, start), block_of(attn, start), p,
                            cfg)
            return put_block(
                x, _mlp(blk, {**p, **mlp_weights(layer, start)}, cfg), start)
        return live_blocks(body, x)

    def layer_body(carry, scanned):
        x, kp, vp, ks, vs = carry     # the pools, whole: [L, n_pages, ...]
        p, layer = scanned
        q, k, v = qkv(x, p)
        # KV → pages BEFORE attention (padding/overflow slots drop):
        # intra-chunk visibility is then pure causal masking inside the
        # one kernel — no dense second piece.
        with jax.named_scope("kv_write"):
            dst = jnp.where(keep, layer * n_tok + flat_dst, L * n_tok)
            k_new, v_new = k[0], v[0]                     # [Tp, KV, hd]
            if quant:
                k_new, ks_new = kv_quant(k_new)           # int8 / [Tp, KV]
                v_new, vs_new = kv_quant(v_new)
                # token t's scales → [layer, page, :, offset] of the
                # [L, n_pages, KV, page] scale pool
                ks = ks.at[layer, pid, :, off].set(ks_new, mode="drop")
                vs = vs.at[layer, pid, :, off].set(vs_new, mode="drop")
            kp = kp.reshape(L * n_tok, lanes).at[dst].set(
                k_new.reshape(Tp, lanes).astype(kp.dtype),
                mode="drop").reshape(kp.shape)
            vp = vp.reshape(L * n_tok, lanes).at[dst].set(
                v_new.reshape(Tp, lanes).astype(vp.dtype),
                mode="drop").reshape(vp.shape)
        with jax.named_scope("attn"):
            attn = ragged_attend_auto(
                q[0], kp, vp, row_tables, block_meta, layer, tq=tq,
                sliding_window=cfg.sliding_window, interpret=interpret,
                shard=shard, k_scale=ks, v_scale=vs, tiles=tiles,
                tile=tile, shared=shared)[None]             # [1,Tp,H,hd]
        x = out_mlp(x, attn.astype(x.dtype), p, layer)
        return (x, kp, vp, ks, vs), None

    with jax.named_scope("layers"):
        # unquantized engines carry the scale slots as empty pytrees
        (x, k_pool, v_pool, k_scale, v_scale), _ = jax.lax.scan(
            layer_body, (x, k_pool, v_pool, k_scale, v_scale),
            (layers, jnp.arange(L, dtype=jnp.int32)))
    return (_final_norm(x, params, cfg), k_pool, v_pool, k_scale, v_scale,
            None)


# Query tokens a model with an indexer attends at a time: a tick's index
# scores are [tokens, table width · page] float32 (64 MiB at 1,024 tokens
# against 16k positions; a 16k-token tick whole would be 1 GiB, and its
# folded queries 2.7 GB at 128 heads), so a longer tick runs its attention
# — projections of the queries, scores, selection, kernel, output fold —
# chunk by chunk inside the layer.
INDEX_CHUNK = 1024


def _forward_hidden_ragged_stacks(params, cfg, tokens, positions, k_pool,
                                  v_pool, row_tables, block_meta, flat_dst,
                                  tq, interpret, shared=None) -> tuple:
    """``forward_hidden_ragged`` for a model with latent attention and
    expert layers: the same contract, with TWO layer stacks — the leading
    dense layers (``params["dense_layers"]``) and then the expert layers
    (``params["layers"]``), one ``lax.scan`` each — both carrying the
    pool in place as the dense model's one scan does. The
    pool is ONE array ``[L, n_pages, page, latent.lanes]`` (``v_pool`` is
    None): a token's row is its compressed latent and rotary key, written
    before attention and read in place by the folded kernel
    (ops/paged_attention.ragged_attend_latent). Returns the dense
    function's tuple; its last member is the expert layers' int32 [4]
    (assignments, of them to held experts, held experts reached summed
    over layers, expert layers run).

    With an indexer (``cfg.indexer``) ``v_pool`` is the index-key pool
    ``[L, n_pages, page, indexer.head_dim]``, carried and written beside
    the latent pool under the same slots, and a layer's attention runs
    over each query's SELECTION (scope ``indexer`` ⊃ ``index_proj``,
    ``index_scores``, ``index_select``): every query scores its visible
    positions against their index keys (ops/paged_attention.index_scores),
    keeps its ``topk`` best (``select_keys``, exact), and the latent
    kernel walks the row's pages with that per-query mask — the causal
    walk's cost, the published arithmetic. Ticks longer than
    ``INDEX_CHUNK`` tokens attend chunk by chunk.

    ``shared`` (the decode step, one token a row): the latent kernel walks
    the pages that rows have in common once for all of them, as the dense
    kernel does; a chunk forward's call (tq > 1) has no such walk."""
    from quoracle_tpu.ops.paged_attention import (
        index_scores_auto, ragged_attend_latent_auto,
    )
    if tq != 1:
        shared = None
    L, n_pages, page, _ = k_pool.shape
    n_tok = n_pages * page
    Tp = tokens.shape[1]
    x = _embed(params, cfg, tokens)
    keep = flat_dst < n_tok
    scale = attn_softmax_scale(cfg)
    n_dense = cfg.n_dense_layers
    experts = tuple(params["layers"][k]
                    for k in ("we_gate", "we_up", "we_down"))

    def write(pool, rows, layer, keep, flat_dst):
        dst = jnp.where(keep, layer * n_tok + flat_dst, L * n_tok)
        return pool.reshape(L * n_tok, pool.shape[-1]).at[dst].set(
            rows.astype(pool.dtype), mode="drop").reshape(pool.shape)

    def latent_attention(x, kp, p, layer):
        q, row, _, _ = _latent_qkv(x, p, cfg, positions)
        with jax.named_scope("kv_write"):
            kp = write(kp, row, layer, keep, flat_dst)
        with jax.named_scope("attn"):
            attn = ragged_attend_latent_auto(
                q, kp, row_tables, block_meta, layer, tq=tq,
                v_lanes=cfg.latent.kv_rank, scale=scale,
                interpret=interpret, shared=shared)
        return _latent_attn_out(x, attn, p, cfg), kp

    def selected_attention(x, pools, p, layer, positions, keep, flat_dst,
                           block_meta):
        """``latent_attention`` over each query's selection, for the
        tokens handed in (the tick, or a chunk of it with its blocks)."""
        kp, ip = pools
        q, row, h, cq = _latent_qkv(x, p, cfg, positions)
        with jax.named_scope("indexer"):
            qi, ki, w = _index_inputs(h, cq, p, cfg, positions)
        with jax.named_scope("kv_write"):
            kp = write(kp, row, layer, keep, flat_dst)
            ip = write(ip, ki, layer, keep, flat_dst)
        with jax.named_scope("indexer"):
            with jax.named_scope("index_scores"):
                scores = index_scores_auto(qi, w, ip, row_tables,
                                           block_meta, layer, tq=tq,
                                           interpret=interpret,
                                           shared=shared)
            select = select_keys(scores, block_meta, tq, cfg.indexer.topk)
        with jax.named_scope("attn"):
            attn = ragged_attend_latent_auto(
                q, kp, row_tables, block_meta, layer, tq=tq,
                v_lanes=cfg.latent.kv_rank, scale=scale,
                interpret=interpret, select=select, shared=shared)
        return _latent_attn_out(x, attn, p, cfg), (kp, ip)

    def chunked_attention(x, pools, p, layer):
        C = INDEX_CHUNK
        if Tp <= C:
            return selected_attention(x, pools, p, layer, positions, keep,
                                      flat_dst, block_meta)
        assert Tp % C == 0 and C % tq == 0, (Tp, C, tq)
        n = Tp // C
        # a chunk's queries see the chunks before them, written by the
        # steps before theirs; a block never spans two chunks (C % tq)
        chunks = (x.reshape(n, 1, C, -1), positions.reshape(n, 1, C),
                  keep.reshape(n, C), flat_dst.reshape(n, C),
                  block_meta.reshape(4, n, C // tq).transpose(1, 0, 2))

        def step(pools, chunk):
            y, pools = selected_attention(chunk[0], pools, p, layer,
                                          *chunk[1:])
            return pools, y

        pools, y = jax.lax.scan(step, pools, chunks)
        return y.reshape(x.shape), pools

    # what the scans carry as "the pool": the latent pool, or the pair
    attention = latent_attention
    if cfg.indexer is not None:
        attention, k_pool = chunked_attention, (k_pool, v_pool)

    def dense_body(carry, scanned):
        x, kp = carry
        p, layer = scanned
        x, kp = attention(x, kp, p, layer)
        return (_mlp(x, p, cfg), kp), None

    def expert_body(carry, scanned):
        x, kp, stats = carry
        p, layer = scanned
        x, kp = attention(x, kp, p, layer)
        x, st = _moe(x, p, experts, layer - n_dense, cfg, keep)
        return (x, kp, stats + st), None

    with jax.named_scope("layers"):
        if n_dense:
            (x, k_pool), _ = jax.lax.scan(
                dense_body, (x, k_pool),
                (params["dense_layers"],
                 jnp.arange(n_dense, dtype=jnp.int32)))
        # the routed experts' weights stay out of the scanned slices: a
        # block reads one expert's matrices at [layer, e]
        rest = {k: v for k, v in params["layers"].items()
                if not k.startswith("we_")}
        (x, k_pool, stats), _ = jax.lax.scan(
            expert_body, (x, k_pool, jnp.zeros((4,), jnp.int32)),
            (rest, jnp.arange(n_dense, L, dtype=jnp.int32)))
    if cfg.indexer is not None:
        k_pool, v_pool = k_pool
    return _final_norm(x, params, cfg), k_pool, v_pool, None, None, stats


class ConvTick(NamedTuple):
    """What a tick needs of the conv layers' state (generate.py
    ``_ensure_pool``: one record a page a conv layer, the state at the end
    of the page's tokens) — the pool, updated in place as the K/V pools
    are, and where this tick's rows read and write it. A forward reads
    every conv layer's records ONCE, before its layers, and writes them
    once, behind them (``_forward_hidden_ragged_pattern``):

    ``src [R]``        the page whose record each row's chunk starts from
                       (the page of its last resident token); negative: the
                       row starts a sequence, from zeros
    ``past [Tp, K-1]`` where the token ``j + 1`` places before each flat
                       token in ITS row lies (generate.py ``conv_past``): a
                       flat
                       position, or ``Tp + row · (K-1) + i`` for entry ``i``
                       of the row's record (a chunk's first ``conv_cache -
                       1`` tokens take predecessors from the record, never
                       from the flat layout's neighbours). None: one token
                       a row, flat token ``r`` row ``r``'s (a decode step):
                       every predecessor is the record's
    ``rec_src [NR]``   flat tokens after which the state is recorded: a
                       row's last, and every token that ends a page (None
                       with ``past``: every row's one token)
    ``rec_dst [NR]``   the page each is recorded under; ``n_pages`` or more
                       drops the write (unused slots, rows that are done)"""

    pool: jax.Array    # [n_conv_layers · n_pages, (conv_cache - 1) · dim]:
                       # flat over (layer, page) as STORED — n_pages is no
                       # multiple of a tile's rows, so merging the two
                       # dimensions in the program would copy the pool
    src: jax.Array
    past: Optional[jax.Array]
    rec_src: Optional[jax.Array]
    rec_dst: jax.Array


@jax.named_scope("conv")
def _short_conv(x: jax.Array, p: dict, cfg: ModelConfig, prev: jax.Array,
                tick: ConvTick) -> tuple[jax.Array, jax.Array]:
    """The gated short convolution of a flat tick ``x [1, Tp, D]`` (LFM2):
    ``[B ‖ C ‖ x̃] = norm(x) W_in``; ``z = B ⊙ x̃``; a depthwise causal
    convolution of ``conv_cache`` taps over ``z`` along each ROW's tokens,
    the taps before a chunk's first token read from the row's record
    ``prev [R, (K-1)·D]`` (scope ``conv_in``, ``conv_taps``); ``y = C ⊙
    conv``; residual + ``y W_out`` (``conv_out``). ``z`` is rounded to the
    activation type before the taps, so a value read back from a record
    is the value a longer chunk would have had in hand. Returns (x, the
    state after the tick's recorded tokens ``[NR, (K-1)·D]``: their last
    ``conv_cache - 1`` values of ``z``, the oldest first)."""
    K, D = cfg.conv_cache, cfg.dim
    with jax.named_scope("conv_in"):
        u = rmsnorm(x, p["attn_norm"], cfg.norm_eps, cfg.rmsnorm_plus_one)
        bcx = jnp.einsum("btd,df->btf", u, p["w_in"])[0]        # [Tp, 3D]
        z = bcx[:, :D] * bcx[:, 2 * D:]
    with jax.named_scope("conv_taps"):
        # back[j-1][t] = z of the token j places before t in t's row
        if tick.past is None:
            back = [prev[:, (K - 1 - j) * D:(K - j) * D].astype(z.dtype)
                    for j in range(1, K)]
        else:
            past = jnp.concatenate([z, prev.reshape(-1, D).astype(
                z.dtype)])[tick.past]
            back = [past[:, j - 1] for j in range(1, K)]
        w = p["w_conv"].astype(jnp.float32)                     # [K, D]
        c = w[K - 1] * z.astype(jnp.float32) + sum(
            w[K - 1 - j] * back[j - 1].astype(jnp.float32)
            for j in range(1, K))
        y = (bcx[:, D:2 * D].astype(jnp.float32) * c).astype(x.dtype)
        last = jnp.concatenate(
            [back[j - 1] for j in range(K - 2, 0, -1)] + [z], axis=-1)
        if tick.rec_src is not None:
            last = last[tick.rec_src]
    with jax.named_scope("conv_out"):
        x = x + jnp.einsum("td,dD->tD", y, p["w_out"])[None]
    return x, last


class SsmTick(NamedTuple):
    """What a tick needs of the ssm layers' state (generate.py
    ``_ensure_pool``: a pool of RECORDS beside the pages, one record a
    session and one a snapshot the prefix cache keeps) — the two pools,
    updated in place as the K/V pools are, and where this tick's rows read
    and write them. A row reads ONE record (``src``) and writes one
    (``dst``); they are the same record where a session goes on from its
    own end, and differ where it starts from a snapshot (adoption IS this
    copy). Every layer reads and writes its own part of the records.

    ``src [R]``   the record each row's state starts from; negative: zeros
                  (a sequence's start)
    ``dst [R]``   the record each row's state at its end is written to;
                  ``n_records`` or more drops the write (unused slots)

    A DECODE STEP (one token a row, flat token ``r`` row ``r``'s; ``past``
    is None) works on the loop's own buffers: ``decode_ragged`` reads its
    rows' records ONCE before the loop into buffers that lie as the
    step's one kernel takes them (``ops/ssm_scan.ssm_decode``: the
    convolution, the recurrence and the norm) — ``ssm [n_ssm_layers, R,
    N, H·P]``, the state TRANSPOSED, and ``conv [n_ssm_layers, R, ·]``
    float32 —, a step updates row ``r`` of layer ``c`` there where
    ``dst[r]`` is 1 (0: a row that is done, whose state stands), and one
    write behind the loop puts them back: a record read and a record
    written a row a layer a step either way, as two device operations a
    layer and not four a row. A chunk forward works on the pools and also
    says how its tokens lie:

    ``past [Tp, K-1]``  as ``ConvTick.past``, for the convolution's taps
    ``last [R]``        each row's last flat token (its conv inputs end
                        the row's new record)
    ``scan_idx [NC·Q]`` the flat token in each slot of the SCAN layout
                        (ops/ssm_scan.py: a row's tokens from a chunk's
                        first slot, ``Q`` to a chunk); ``Tp``: padding
    ``scan_pos [Tp]``   each flat token's slot there
    ``chunk_row``, ``chunk_first [NC]``  each chunk's row, and 1 where it
                        starts its row
    ``row_end [R]``     the chunk that holds each row's last token
    ``snap_chunk``, ``snap_tok``, ``snap_dst [R]``  a SNAPSHOT a row at
                        most: the chunk after which and the flat token
                        behind which the state is also written to record
                        ``snap_dst`` (``n_records`` or more: none) — the
                        chunk's end is the token's place, since a row that
                        takes one starts on a page boundary
    ``n_chunks [1]``    the chunks that hold a token (the layout's first):
                        the scan kernel skips the rest"""

    ssm: jax.Array     # [n_ssm_layers · n_records, H·P, N] float32
    conv: jax.Array    # [n_ssm_layers · n_records, (K-1) · conv_dim]
    src: jax.Array
    dst: jax.Array
    past: Optional[jax.Array] = None
    last: Optional[jax.Array] = None
    scan_idx: Optional[jax.Array] = None
    scan_pos: Optional[jax.Array] = None
    chunk_row: Optional[jax.Array] = None
    chunk_first: Optional[jax.Array] = None
    row_end: Optional[jax.Array] = None
    snap_chunk: Optional[jax.Array] = None
    snap_tok: Optional[jax.Array] = None
    snap_dst: Optional[jax.Array] = None
    n_chunks: Optional[jax.Array] = None   # [1]: the chunks that hold a token


def take_rows(pool: jax.Array, ids: jax.Array) -> jax.Array:
    """``pool[ids]`` for a few rows of megabytes each, as one dynamic
    slice a row: the compiler's gather of such rows first copies the pool
    whole in two halves (590 MB a Mamba layer a decode step at the
    benchmark's widths, read off the compiled program), a dynamic slice
    reads the row."""
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(pool, ids[r], 1, 0)
                            for r in range(ids.shape[0])])


def put_rows(pool: jax.Array, ids: jax.Array, rows: jax.Array) -> jax.Array:
    """``pool.at[ids].set(rows)``, a dynamic update a row, in place."""
    for r in range(ids.shape[0]):
        pool = jax.lax.dynamic_update_slice_in_dim(
            pool, rows[r:r + 1].astype(pool.dtype), ids[r], 0)
    return pool


@jax.named_scope("ssm")
def _ssm(x: jax.Array, p: dict, cfg: ModelConfig, tick: SsmTick, c,
         interpret) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The Mamba-2 mixer of a flat tick ``x [1, Tp, D]``, the ``c``-th ssm
    layer of the model (config.SSMConfig has the sizes): ``[z | xBC | dt]
    = norm(x) W_in`` (scope ``ssm_in``); ``xBC ← silu(conv(xBC) + b)``, a
    causal depthwise convolution along each ROW's tokens whose taps before
    a chunk's first token read from the row's record (``ssm_conv``); the
    heads' recurrence ``S ← exp(Δ A) S + Δ x ⊗ B``, ``y = S C + D x`` with
    ``Δ = softplus(dt + dt_bias)`` and ``A = −exp(a_log)`` — a chunk
    forward through the scan kernel in the scan layout (``ssm_scan``; a
    decode step's convolution, recurrence and norm are one kernel under
    that scope, ``ops/ssm_scan.ssm_decode``); ``y ⊙ silu(z)`` through an
    RMSNorm over each group's values (``ssm_norm``); residual + ``y
    W_out`` (``ssm_out``); and the rows' new records written under
    ``tick.dst`` and a snapshot's under ``tick.snap_dst``, in place
    (``state_write``; a write that is dropped goes to the layer's record
    0, the pool's scratch). The conv inputs are rounded to the activation type
    before the taps, so a value read back from a record is the value a
    longer chunk would have had in hand. Returns (x, the two pools)."""
    from quoracle_tpu.ops.ssm_scan import ssm_decode_auto, ssm_scan_auto
    m = cfg.ssm
    H, P, G, N, K = m.n_heads, m.head_dim, m.n_groups, m.state_dim, \
        m.conv_kernel
    Tp, CD = x.shape[1], m.conv_dim
    R = tick.dst.shape[0]
    f32 = jnp.float32
    with jax.named_scope("ssm_in"):
        u = rmsnorm(x, p["attn_norm"], cfg.norm_eps, cfg.rmsnorm_plus_one)
        z, xbc, dt = (jnp.einsum("btd,df->btf", u, p[k])[0]
                      for k in ("w_z", "w_xbc", "w_dt"))
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])   # [Tp, H]
    if tick.past is None:
        # a decode step, on the decode loop's buffers: the state
        # TRANSPOSED, [n_ssm, R, N, H·P], the conv inputs float32
        with jax.named_scope("ssm_scan"):
            xb = xbc.astype(f32)        # rounded to the activation type
            a_d = jnp.repeat(jnp.stack([-jnp.exp(p["a_log"]), p["d_skip"]]),
                             P, axis=1)                       # [2, d_inner]
            y, ssm_pool = ssm_decode_auto(
                z.astype(f32), xb, tick.conv, p["w_conv"],
                p["b_conv"][None], jnp.repeat(dt, P, axis=1), a_d,
                p["ssm_norm"][None], tick.ssm, c, tick.dst,
                G=G, N=N, K=K, eps=cfg.norm_eps, interpret=interpret)
        with jax.named_scope("state_write"):
            prev = jax.lax.dynamic_index_in_dim(tick.conv, c, 0, False)
            conv_pool = jax.lax.dynamic_update_index_in_dim(
                tick.conv, jnp.where((tick.dst > 0)[:, None], jnp.concatenate(
                    [prev[:, CD:], xb], axis=-1), prev), c, 0)
        with jax.named_scope("ssm_out"):
            x = x + jnp.einsum("td,dD->tD", y.astype(x.dtype),
                               p["w_out"])[None]
        return x, ssm_pool, conv_pool
    NR = tick.ssm.shape[0] // cfg.n_ssm_layers
    rec = c * NR + jnp.maximum(tick.src, 0)
    with jax.named_scope("ssm_conv"):
        prev = jnp.where((tick.src >= 0)[:, None],
                         take_rows(tick.conv, rec), 0)
        # back[j-1][t] = xBC of the token j places before t in t's row
        past = jnp.concatenate([xbc, prev.reshape(-1, CD).astype(
            xbc.dtype)])[tick.past]
        back = [past[:, j - 1] for j in range(1, K)]
        w = p["w_conv"].astype(f32)                           # [K, CD]
        conv = jax.nn.silu(
            w[K - 1] * xbc.astype(f32) + sum(
                w[K - 1 - j] * back[j - 1].astype(f32) for j in range(1, K))
            + p["b_conv"].astype(f32)).astype(x.dtype)
        new_conv = jnp.concatenate(
            [back[j - 1] for j in range(K - 2, 0, -1)] + [xbc], axis=-1)
    A = -jnp.exp(p["a_log"])
    with jax.named_scope("ssm_scan"):
        s0 = jnp.where((tick.src >= 0)[:, None, None],
                       take_rows(tick.ssm, rec), 0.0).reshape(R, H, P, N)
        Q = m.chunk
        NC = tick.chunk_row.shape[0]
        # the scan layout: a zero row behind the flat tokens is the
        # padding (Δ = 0 leaves the state as it is)
        lay = jnp.concatenate([conv, jnp.zeros((1, CD), conv.dtype)]
                              )[tick.scan_idx].reshape(NC, Q, CD)
        dts = jnp.concatenate([dt, jnp.zeros((1, H), f32)]
                              )[tick.scan_idx].reshape(NC, Q, H)
        ys, states = ssm_scan_auto(
            lay[..., :m.d_inner].reshape(NC, Q, H, P), dts, A,
            lay[..., m.d_inner:m.d_inner + G * N].reshape(NC, Q, G, N),
            lay[..., m.d_inner + G * N:].reshape(NC, Q, G, N), s0,
            tick.chunk_row, tick.chunk_first, tick.n_chunks, interpret)
        y = ys.reshape(NC * Q, H, P)[tick.scan_pos] \
            + p["d_skip"][:, None] * conv[:, :m.d_inner].reshape(
                Tp, H, P).astype(f32)
        states = states.reshape(NC, H * P, N)
        ends = take_rows(states, tick.row_end)
    with jax.named_scope("ssm_norm"):
        y = (y.reshape(Tp, G, -1) * jax.nn.silu(z.astype(f32)).reshape(
            Tp, G, -1))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.norm_eps)
        y = (y.reshape(Tp, m.d_inner)
             * p["ssm_norm"].astype(f32)).astype(x.dtype)
    with jax.named_scope("ssm_out"):
        x = x + jnp.einsum("td,dD->tD", y, p["w_out"])[None]
    with jax.named_scope("state_write"):
        def at(ids):
            return c * NR + jnp.where(ids < NR, ids, 0)

        ssm_pool = put_rows(tick.ssm, at(tick.dst), ends)
        conv_pool = put_rows(tick.conv, at(tick.dst), new_conv[tick.last])
        if tick.snap_dst is not None:
            ssm_pool = put_rows(ssm_pool, at(tick.snap_dst),
                                take_rows(states, tick.snap_chunk))
            conv_pool = put_rows(conv_pool, at(tick.snap_dst),
                                 new_conv[tick.snap_tok])
    return x, ssm_pool, conv_pool


def _forward_hidden_ragged_pattern(params, cfg, tokens, positions, k_pool,
                                   v_pool, row_tables, block_meta, flat_dst,
                                   tq, interpret, tiles, tile, shared,
                                   conv, ssm=None) -> tuple:
    """``forward_hidden_ragged`` for a model whose layers differ in KIND
    (``cfg.layer_plan``): per-head attention (of one kind or of several,
    ``cfg.attn_kinds``), a gated short convolution, a Mamba-2 mixer
    (``_ssm``, whose records ``ssm`` hands in: every ssm layer reads and
    writes its part of the two record pools in place, which ride the
    scans as the K/V pools do) or no mixer; a dense feed-forward, routed
    experts or none. The same contract and the same in-place pools, with
    these differences. The K/V pools hold the ATTENTION layers only,
    ``[n_attn_layers, n_pages, page, KV·hd]``, indexed by a layer's place
    among them. A model whose attention layers fall into several retention
    GROUPS (``cfg.kv_groups``: window and full layers mixed) hands in a
    TUPLE, one member a group, of each of ``k_pool``, ``v_pool``,
    ``row_tables`` and ``flat_dst`` — a group has its own pools
    ``[layers of the group, its n_pages, page, KV·hd]``, its own page ids
    and so its own tables and slots, all over the same positions (a window
    group's table holds page 0 where the session let a page behind the
    window go: the kernel's walk starts at the first page the window
    reaches and never reads those entries) — and gets the tuples of pools
    back; a layer reads and writes its group's, at its place among the
    group's layers, with its kind's window; ``shared`` is the full group's
    walk (a window's first page differs by row). And the conv layers'
    state lies beside them (``conv``, a ``ConvTick``): every conv layer's
    records are read from its pool once, before the layers, the layers'
    new records ride the scans in a buffer of the tick's size, and one
    write behind the layers puts them under their pages, in place (scopes
    ``conv_taps`` and ``state_write``, as ``_short_conv``'s). Each segment
    of the plan is one ``lax.scan`` over its repeats with the segment's
    layers unrolled in the body: the leading dense layers once, the period
    as often as it fits, the rest of a last period once — so program size
    follows the period, not the depth (but for the decode step of a model
    with ssm layers, whose scans hold every repeat). The routed experts' weights stay
    out of the scanned slices. A layer's scopes are the dense forward's;
    where the model names kinds of attention, a layer's are inside one
    named for its kind (``full_attention`` ⊃ ``qkv`` …). Returns the dense
    function's tuple with the expert layers' counts (``moe_counts``' six
    where the grouped kernel ran them, else ``_routed_experts``' four; None
    without experts) and, seventh, the state pool (None without conv
    layers; the pair of record pools with ssm layers)."""
    from quoracle_tpu.ops.paged_attention import ragged_attend_auto
    grouped = experts_grouped(cfg, interpret)
    n_groups = len(cfg.kv_groups)
    multi = n_groups > 1
    if not multi:
        k_pool, v_pool, row_tables, flat_dst = (
            (k_pool,), (v_pool,), (row_tables,), (flat_dst,))
    n_pages, page, lanes = k_pool[0].shape[1:]
    Tp = tokens.shape[1]
    x = _embed(params, cfg, tokens)
    # a group's sentinel is ITS pool's end; a token dropped in one group
    # (padding, a row that is done) is dropped in every group
    keeps = tuple(d < kp.shape[1] * page for d, kp in zip(flat_dst, k_pool))
    keep = keeps[0]
    n_conv = cfg.n_conv_layers
    assert (conv is not None) == (n_conv > 0)
    assert (ssm is not None) == (cfg.n_ssm_layers > 0)
    prev = recs = None
    if conv is not None:
        of_layer = jnp.arange(n_conv, dtype=jnp.int32)[:, None] \
            * (conv.pool.shape[0] // n_conv)
        with jax.named_scope("conv"), jax.named_scope("conv_taps"):
            prev = jnp.where((conv.src >= 0)[None, :, None], conv.pool[
                of_layer + jnp.maximum(conv.src, 0)], 0)  # [n_conv, R, ·]
        recs = jnp.zeros((n_conv, conv.rec_dst.shape[0],
                          conv.pool.shape[1]), conv.pool.dtype)

    def attention(x, kp, vp, p, a, kind, g):
        """One attention layer of ``kind``, the ``a``-th of group ``g``:
        ``kp``/``vp`` that group's pools."""
        A, n_tok = kp.shape[0], kp.shape[1] * page
        q, k, v = _qkv(x, p, cfg, 1, Tp, positions, kind)
        with jax.named_scope("kv_write"):
            dst = jnp.where(keeps[g], a * n_tok + flat_dst[g], A * n_tok)
            kp = kp.reshape(A * n_tok, lanes).at[dst].set(
                k[0].reshape(Tp, lanes).astype(kp.dtype),
                mode="drop").reshape(kp.shape)
            vp = vp.reshape(A * n_tok, lanes).at[dst].set(
                v[0].reshape(Tp, lanes).astype(vp.dtype),
                mode="drop").reshape(vp.shape)
        with jax.named_scope("attn"):
            attn = ragged_attend_auto(
                q[0], kp, vp, row_tables[g], block_meta, a, tq=tq,
                sliding_window=kind.window, interpret=interpret,
                tiles=tiles, tile=tile,
                shared=shared if kind.window is None else None)[None]
        if not cfg.attn_gate:
            attn = attn.astype(x.dtype)     # gated in float32, then cast
        return _attn_out(x, attn, p, cfg), kp, vp

    def segment(carry, stacked, kinds, n, a0, c0):
        """``n`` repeats of ``kinds``, whose first attention layer of
        group g is the ``a0[g]``-th of that group's and first conv (or
        ssm) layer the ``c0``-th of the model's."""
        group = [cfg.kv_group_of(m) if cfg.is_attention(m) else None
                 for m, _ in kinds]
        a_per = [group.count(g) for g in range(n_groups)]
        c_per = sum(m in ("conv", "ssm") for m, _ in kinds)
        experts = [tuple(p[k] for k in _expert_leaves(cfg.moe))
                   if ff == "experts" else None
                   for p, (_, ff) in zip(stacked, kinds)]
        rest = tuple({k: v for k, v in p.items() if not k.startswith("we_")}
                     for p in stacked)

        def body(carry, scanned):
            x, kps, vps, recs, stats, pools = carry
            ps, rep = scanned
            a = [a0[g] + rep * a_per[g] for g in range(n_groups)]
            c = c0 + rep * c_per
            kps, vps = list(kps), list(vps)
            for p, w, (mixer, ff), g in zip(ps, experts, kinds, group):
                if g is not None:
                    with jax.named_scope(mixer) if cfg.attn_kinds \
                            else contextlib.nullcontext():
                        x, kps[g], vps[g] = attention(
                            x, kps[g], vps[g], p, a[g],
                            cfg.attn_kind(mixer), g)
                    a[g] = a[g] + 1
                elif mixer == "conv":
                    x, last = _short_conv(x, p, cfg, prev[c], conv)
                    recs = jax.lax.dynamic_update_index_in_dim(
                        recs, last.astype(recs.dtype), c, 0)
                    c = c + 1
                elif mixer == "ssm":
                    x, *pools = _ssm(x, p, cfg, ssm._replace(
                        ssm=pools[0], conv=pools[1]), c, interpret)
                    c = c + 1
                if ff == "dense":
                    x = _mlp(x, p, cfg)
                elif ff == "experts":
                    x, st = _moe(x, p, w, rep, cfg, keep, grouped,
                                 bool(interpret))
                    stats = stats + st
            return (x, tuple(kps), tuple(vps), recs, stats,
                    pools if pools is None else tuple(pools)), None

        # a decode step of a model with ssm layers holds every repeat in
        # the scan's body: inside the decode loop the scan is a ``while``
        # in a ``while``, and its slice of every stacked leaf is a device
        # operation of its own every step (478 a step for 365 where seven
        # one-operator layers repeat twice; PERF.md §6, PR 47). A chunk
        # forward, one pass a tick, keeps the loop and a program of the
        # period's size; so do the models without such layers
        unroll = n if ssm is not None and ssm.past is None else 1
        carry, _ = jax.lax.scan(body, carry,
                                (rest, jnp.arange(n, dtype=jnp.int32)),
                                unroll=unroll)
        return carry, [a0[g] + n * a_per[g] for g in range(n_groups)], \
            c0 + n * c_per

    stats = None
    if cfg.moe is not None:
        stats = jnp.zeros((3, cfg.moe.n_held) if grouped else (MOE_STATS,),
                          jnp.int32)
    carry = (x, tuple(k_pool), tuple(v_pool), recs, stats,
             None if ssm is None else (ssm.ssm, ssm.conv))
    a0, c0 = [0] * n_groups, 0
    with jax.named_scope("layers"):
        for stacked, (kinds, n) in zip(params["segments"], cfg.layer_plan):
            if n:
                carry, a0, c0 = segment(carry, stacked, kinds, n, a0, c0)
    x, k_pool, v_pool, recs, stats, state = carry
    if not multi:
        k_pool, v_pool = k_pool[0], v_pool[0]
    if conv is not None:
        with jax.named_scope("conv"), jax.named_scope("state_write"):
            dst = jnp.where(conv.rec_dst < n_pages, of_layer + conv.rec_dst,
                            conv.pool.shape[0])
            state = conv.pool.at[dst.reshape(-1)].set(
                recs.reshape(-1, recs.shape[-1]), mode="drop")
    if grouped and stats is not None:
        stats = moe_counts(stats, keep, cfg.moe, cfg.n_expert_layers)
    return (_final_norm(x, params, cfg), k_pool, v_pool, None, None, stats,
            state)


def project_logits(params: dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Final hidden states [B, T, D] -> logits [B, T, vocab] fp32.

    Split from the stack so prefill can gather ONE position per row before
    projecting — at llama-3-8b scale a full [B, 8192, 128256] fp32 logits
    tensor is ~4 GB/row and would blow HBM for a value that's 99.99% discarded.
    """
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            head = dequant_weight(params["embed"], jnp.float32).T
        else:
            head = dequant_weight(params["lm_head"], jnp.float32)
        logits = jnp.einsum("btd,dv->btv", hidden.astype(jnp.float32),
                            head.astype(jnp.float32))
        if cfg.final_logit_softcap is not None:
            c = cfg.final_logit_softcap
            logits = c * jnp.tanh(logits / c)
        return logits


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, cache: KVCache, write_offset: jax.Array,
            kv_lens: jax.Array) -> tuple[jax.Array, KVCache]:
    """forward_hidden + full-sequence head projection. Convenience for
    tests/training; serving paths gather positions from forward_hidden first."""
    hidden, cache = forward_hidden(params, cfg, tokens, positions, cache,
                                   write_offset, kv_lens)
    return project_logits(params, cfg, hidden), cache


def param_count(params: dict) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
