"""The latent-attention, routed-expert decoder family (the DeepSeek-V2/V3
form that A.X-K1's `config.json` spells: `model_type` `axk1`): everything
the harness knows of this architecture, under the names `benchmark/README.md`
fixes (`register`, `Reference`, `stated_precision`, `decode_weight_bytes`,
`decode_step_mark`), and the counts its two kernels' roofline shares are
taken from (`routed_experts_floor_s`, `latent_attn_floor_s`).

`raw` is the configuration's file as `configs.load_config` gives it: the
published keys under their own names with the cut applied. Three keys are
cut (`reduced`; the published values stand beside them under
`reduced_from`): `num_hidden_layers`; `vocab_size` (a slice of the rows);
and `n_routed_experts`, which here counts the experts HELD: this chip's
share of an expert-parallel deployment, experts `held_experts_first` to
`held_experts_first + n_routed_experts`. The router keeps its published
width (`reduced_from.n_routed_experts`) and its `num_experts_per_tok`.

**The equations** (per layer, `x` the residual stream, every norm RMSNorm
with `rms_norm_eps` and a weight of ones):

* Latent attention. `h = norm(x)`. `c_q = norm(h W_qa)` (`q_lora_rank`);
  `q = c_q W_qb`, per head `qk_nope_head_dim ‖ qk_rope_head_dim`, rotary on
  the second part. `[c_kv ‖ k_r] = h W_kva` (`kv_lora_rank ‖
  qk_rope_head_dim`); `c_kv = norm(c_kv)`, `k_r = rotary(k_r)`, one `k_r`
  for all heads. `k_i = [c_kv W_kb,i^K ‖ k_r]`, `v_i = c_kv W_kb,i^V`
  (`v_head_dim`). Scores `q_i·k_i · (nope+rope)^-1/2 · m²`, `m =
  0.1·mscale_all_dim·ln(factor) + 1`, causal softmax, `x += concat_i(p_i
  v_i) W_o`. Rotary is YaRN on halves (pair `(i, i + d/2)`): per
  frequency, `inv_freq/factor` blended with `inv_freq` by the linear ramp
  between the dimensions that `beta_fast` and `beta_slow` give over
  `original_max_position_embeddings`; cos and sin scaled by
  `mscale/mscale_all_dim`. This reference computes attention UNFOLDED
  (per-head keys and values up-projected from the latents of the whole
  sequence), in blocks of queries; the program serves it folded.
* Feed-forward, the first `first_k_dense_replace` layers: `x += (silu(h
  W_g) ⊙ h W_u) W_d` at `intermediate_size`. The others: `s = sigmoid(h
  W_r)` over all published experts; the experts fall into `n_group` groups,
  a group's score is the sum of its two largest `s`, the `topk_group` best
  groups stay, the `num_experts_per_tok` largest `s` inside them are
  selected (ties to the lower index); `g = routed_scaling_factor · s_sel /
  Σ s_sel`; `x += Σ_{e selected AND held} g_e FFN_e(h) + FFN_shared(h)`,
  every FFN the gated form at `moe_intermediate_size`. What an absent expert
  would add is left out, here as in the program; the gates are normalised
  over all selected experts wherever they live. No correction bias
  (`topk_method` `"none"`; the config names no bias tensor).

**The reference** is float32 at matmul precision "highest", in plain
`jax.numpy`, the experts as a plain loop over the held ones (each over
every token, times its gate, zero where it was not selected). It imports
nothing of the program and takes nothing the program made (`register` alone
touches the program: its whole job is to hand the mapping over). Its
weights are drawn here from the seed by the rule the program's
initialisation STATES (`transformer._init_params_stacks`): `PRNGKey(seed)`
split three ways, embed / layers / head; a layer leaf is
normal/sqrt(fan-in) rounded to bfloat16, drawn at its stacked shape from
`fold_in(fold_in(k_layers, stack), i)`, stack 0 the leading dense layers
and 1 the expert layers, `i` the leaf's place in `leaves_of`; a routed
expert's leaf is drawn per expert, at `[layers, ...]`, from `fold_in(that
key, e)` with `e` the expert's number among all published ones. **Memory**:
the leaves stay bfloat16 as served, 9.0 GiB at `ax-k1-ep16-l7`; `run.py`
frees the server's memory before it builds the reference, and a layer is
widened to float32 one matrix (one expert) at a time.

**A resident token** holds `c_kv ‖ k_r`: `kv_lora_rank + qk_rope_head_dim`
values a layer (576: 1,152 bytes at bfloat16, 8,064 over 7 layers). The
TPU stores a minor dimension in tiles of 128 lanes, so the program's pool
row is 640 lanes wide with 64 of them zero, whether the program says so or
not; both sides state the STORED bytes, `7 × 640 × 2 = 8,960`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import DTYPE_BYTES

LANE = 128


# -- the mapping ------------------------------------------------------------

def shapes(raw: dict) -> dict:
    """The sizes this module computes with, from the published keys."""
    if raw["scoring_func"] != "sigmoid" or raw["topk_method"] != "none":
        raise ValueError("latent_moe: only sigmoid scores without a "
                         "correction bias are written down here")
    if raw["rope_scaling"]["type"] != "yarn" or raw["hidden_act"] != "silu":
        raise ValueError("latent_moe: YaRN and silu only")
    published = raw.get("reduced_from", {})
    rs = raw["rope_scaling"]
    return dict(
        L=int(raw["num_hidden_layers"]), D=int(raw["hidden_size"]),
        H=int(raw["num_attention_heads"]), V=int(raw["vocab_size"]),
        F=int(raw["intermediate_size"]), q_rank=int(raw["q_lora_rank"]),
        kv_rank=int(raw["kv_lora_rank"]),
        nope=int(raw["qk_nope_head_dim"]), rope=int(raw["qk_rope_head_dim"]),
        v=int(raw["v_head_dim"]), n_dense=int(raw["first_k_dense_replace"]),
        E=int(published.get("n_routed_experts", raw["n_routed_experts"])),
        held=int(raw["n_routed_experts"]),
        first=int(raw.get("held_experts_first", 0)),
        k=int(raw["num_experts_per_tok"]),
        Fe=int(raw["moe_intermediate_size"]),
        shared=int(raw["n_shared_experts"]), n_group=int(raw["n_group"]),
        topk_group=int(raw["topk_group"]),
        routed_scale=float(raw["routed_scaling_factor"]),
        norm_topk=bool(raw["norm_topk_prob"]),
        eps=float(raw["rms_norm_eps"]), theta=float(raw["rope_theta"]),
        yarn=(float(rs["factor"]), float(rs["beta_fast"]),
              float(rs["beta_slow"]),
              int(rs["original_max_position_embeddings"]),
              float(rs["mscale"]), float(rs["mscale_all_dim"])))


def register(raw: dict) -> str:
    """Register the configuration with the program; returns its spec."""
    from quoracle_tpu.models.config import (
        LatentConfig, ModelConfig, MoEConfig, register_model,
    )
    s = shapes(raw)
    register_model(ModelConfig(
        name=raw["name"], vocab_size=s["V"], dim=s["D"], n_layers=s["L"],
        n_heads=s["H"], n_kv_heads=int(raw["num_key_value_heads"]),
        ffn_dim=s["F"], head_dim=s["nope"] + s["rope"],
        rope_theta=s["theta"], norm_eps=s["eps"],
        activation=raw["hidden_act"],
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        attn_bias=bool(raw["attention_bias"]),
        rope_scaling=("yarn",) + s["yarn"],
        latent=LatentConfig(q_rank=s["q_rank"], kv_rank=s["kv_rank"],
                            nope_dim=s["nope"], rope_dim=s["rope"],
                            v_dim=s["v"]),
        moe=MoEConfig(n_routed=s["E"], n_held=s["held"], per_token=s["k"],
                      expert_dim=s["Fe"], n_shared=s["shared"],
                      n_group=s["n_group"], topk_group=s["topk_group"],
                      routed_scale=s["routed_scale"],
                      norm_topk=s["norm_topk"], first_dense=s["n_dense"],
                      held_start=s["first"]),
        context_window=int(raw["serving"]["context_window"]),
        output_limit=int(raw["serving"]["output_limit"]),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"])))
    return f"xla:{raw['name']}"


# -- bytes and operations, from the shapes ----------------------------------

def stored_lanes(raw: dict) -> int:
    """Lanes a resident token takes in a layer as STORED: `kv_lora_rank +
    qk_rope_head_dim` rounded up to the TPU's 128-lane tile."""
    s = shapes(raw)
    return -(-(s["kv_rank"] + s["rope"]) // LANE) * LANE


def stated_precision(raw: dict) -> dict:
    """{key of the engine's `quant_stats()`: what it has to read}: the
    bytes one resident token holds over all layers at the stated type, as
    stored (module docstring: 8,960 at `ax-k1-ep16-l7`, of which 8,064 are
    the 576 values a layer that the equations give it)."""
    return {"kv_bytes_per_token": shapes(raw)["L"] * stored_lanes(raw)
            * DTYPE_BYTES[raw["torch_dtype"]]}


def _attn_params(s: dict) -> int:
    return (s["D"] * s["q_rank"] + s["q_rank"]
            + s["q_rank"] * s["H"] * (s["nope"] + s["rope"])
            + s["D"] * (s["kv_rank"] + s["rope"]) + s["kv_rank"]
            + s["kv_rank"] * s["H"] * (s["nope"] + s["v"])
            + s["H"] * s["v"] * s["D"])


def decode_weight_bytes(raw: dict) -> int:
    """Bytes of weights EVERY decode step has to read: a LOWER bound for
    any step the cell can run. Counted: all that lies outside the routed
    experts (the latent projections and the output projection of every
    layer, the dense layers' MLP, each expert layer's router and shared
    expert, the norms, the output head). Of the routed experts: NOTHING. A
    row's `num_experts_per_tok` choices fall on all published experts and
    this chip holds a sixteenth of them, so a step with few rows can reach
    no held expert in a layer, and no single expert's bytes are unavoidable.
    What the steps of a run did read of them is
    `kernel.routed_experts_bw_share_pct`'s, from the program's counter.
    The embedding lookup reads rows, not the table, and is left out."""
    s = shapes(raw)
    dense = _attn_params(s) + 3 * s["D"] * s["F"] + 2 * s["D"]
    expert = (_attn_params(s) + s["D"] * s["E"]
              + 3 * s["D"] * s["Fe"] * s["shared"] + 2 * s["D"])
    total = (s["n_dense"] * dense + (s["L"] - s["n_dense"]) * expert
             + s["D"] + s["V"] * s["D"])
    return total * DTYPE_BYTES[raw["torch_dtype"]]


def decode_step_mark(raw: dict) -> dict:
    """The attention kernel's custom call (`ragged_attend_latent`), once a
    layer of either kind."""
    return {"op_pattern": "^%ragged_attend",
            "per_step": int(raw["num_hidden_layers"])}


def routed_expert_bytes(raw: dict) -> int:
    """Bytes of one routed expert's three matrices: what the grouped
    matmul has to read for each held expert a step reaches."""
    s = shapes(raw)
    return 3 * s["D"] * s["Fe"] * DTYPE_BYTES[raw["torch_dtype"]]


def routed_experts_floor_s(raw: dict, reached: float, peaks: dict) -> float:
    """The least time the grouped matmuls need for `reached` held experts
    with a token (summed over layers and steps): each has to be read."""
    return reached * routed_expert_bytes(raw) / peaks["hbm_bytes_per_s"]


def latent_attn_floor_s(raw: dict, kv_reads: float, pairs: float,
                        peaks: dict) -> float:
    """The least time the latent attention kernel needs for `kv_reads`
    resident tokens streamed (each row's context once a step) and `pairs`
    query-key pairs attended, summed over a tick's steps, in ALL layers:
    the larger of the stored latent bytes over the memory bandwidth and the
    folded form's operations over the peak. Folded, a pair costs every head
    one dot product over the stored row and one weighted sum over the
    latent: `2·H·(lanes + kv_lora_rank)` operations."""
    s = shapes(raw)
    lanes = stored_lanes(raw)
    byts = kv_reads * s["L"] * lanes * DTYPE_BYTES[raw["torch_dtype"]]
    flops = pairs * s["L"] * 2 * s["H"] * (lanes + s["kv_rank"])
    return max(byts / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


# -- the plain reference ----------------------------------------------------

Q_BLOCK = 512

# (name, shape, fan-in) of a stack's leaves, in the order that numbers
# their keys: attention first, then the stack's feed-forward
def leaves_of(s: dict, experts: bool) -> list:
    D, H = s["D"], s["H"]
    attn = [("wq_a", (D, s["q_rank"]), D),
            ("wq_b", (s["q_rank"], H * (s["nope"] + s["rope"])), s["q_rank"]),
            ("wkv_a", (D, s["kv_rank"] + s["rope"]), D),
            ("wkv_b", (s["kv_rank"], H * (s["nope"] + s["v"])), s["kv_rank"]),
            ("wo", (H * s["v"], D), H * s["v"])]
    if not experts:
        return attn + [("w_gate", (D, s["F"]), D), ("w_up", (D, s["F"]), D),
                       ("w_down", (s["F"], D), s["F"])]
    Fe, Fs = s["Fe"], s["Fe"] * s["shared"]
    return attn + [("router", (D, s["E"]), D),
                   ("we_gate", (D, Fe), D), ("we_up", (D, Fe), D),
                   ("we_down", (Fe, D), Fe),
                   ("ws_gate", (D, Fs), D), ("ws_up", (D, Fs), D),
                   ("ws_down", (Fs, D), Fs)]


@functools.partial(jax.jit, static_argnames=("shape", "fan_in"))
def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("n", "shape", "fan_in"))
def _normal_experts(key, first, n, shape, fan_in):
    """[layers, n, ...]: expert `first + e` drawn from `fold_in(key,
    first + e)` at `[layers, ...]`."""
    def one(e):
        return (jax.random.normal(jax.random.fold_in(key, e), shape,
                                  jnp.float32)
                * (fan_in ** -0.5)).astype(jnp.bfloat16)
    return jax.vmap(one, out_axes=1)(first + jnp.arange(n))


def make_weights(s: dict, seed: int) -> dict:
    """The model of `seed`: `embed`, `lm_head`, and per stack (`dense`,
    `experts`) its stacked bfloat16 leaves."""
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = {"embed": _normal(k_embed, (s["V"], s["D"]), s["D"]),
         "lm_head": _normal(k_head, (s["D"], s["V"]), s["D"])}
    for stack, name, n, experts in (
            (0, "dense", s["n_dense"], False),
            (1, "experts", s["L"] - s["n_dense"], True)):
        if n == 0:
            continue
        ks = jax.random.fold_in(k_layers, stack)
        leaves = {}
        for i, (leaf, shape, fan_in) in enumerate(leaves_of(s, experts)):
            k = jax.random.fold_in(ks, i)
            if leaf.startswith("we_"):
                leaves[leaf] = _normal_experts(k, s["first"], s["held"],
                                               (n, *shape), fan_in)
            else:
                leaves[leaf] = _normal(k, (n, *shape), fan_in)
        w[name] = leaves
    return w


def quantize_int8(w: dict) -> dict:
    """The control's weights: every matrix as symmetric int8 with one
    float32 scale per output channel (the embedding: per row), the step
    below the bfloat16 the configuration states. Leaves become (int8,
    scale) pairs; `_widen` multiplies them out in float32."""
    @functools.partial(jax.jit, static_argnames=("axis",), donate_argnums=0)
    def q(x, axis):
        x = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return (jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8),
                scale)

    def q_big(x, axis):
        # an expert leaf [layers, held, ...] a layer at a time: its
        # float32 copy whole would not fit beside the rest
        if x.ndim < 4:
            return q(x, axis)
        parts = [q(x[i], axis) for i in range(x.shape[0])]
        return (jnp.stack([p[0] for p in parts]),
                jnp.stack([p[1] for p in parts]))

    # leaf by leaf, each bfloat16 leaf given up as its int8 pair is made
    out = {"embed": q(w.pop("embed"), -1), "lm_head": q(w.pop("lm_head"), -2)}
    for name in ("dense", "experts"):
        if name in w:
            stack = w.pop(name)
            out[name] = {k: q_big(stack.pop(k), -2) for k in sorted(stack)}
    return out


def _widen(leaf):
    """A weight as float32: bfloat16 widened, or int8 times its scale."""
    if isinstance(leaf, tuple):
        return leaf[0].astype(jnp.float32) * leaf[1]
    return leaf.astype(jnp.float32)


def _at(leaf, *idx):
    """`leaf[idx]` of a stacked weight (or of its int8 pair), widened."""
    for i in idx:
        leaf = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            leaf)
    return _widen(leaf)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def yarn_inv_freq(dim: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, orig_max: int) -> np.ndarray:
    """The closed form: frequency i of `dim/2` is `theta^(-2i/dim)`, kept
    where it turns more than `beta_fast` times over `orig_max` positions,
    divided by `factor` where it turns less than `beta_slow` times, blended
    linearly in i between the two dimensions those counts give."""
    def dim_at(turns):
        return dim * math.log(orig_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_at(beta_fast)), 0)
    high = min(math.ceil(dim_at(beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (freq / factor * ramp + freq * (1 - ramp)).astype(np.float32)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, s):
    """x: [T, heads, d]; position t rotates pair (i, i + d/2) by t times
    YaRN's frequency i; cos and sin times mscale / mscale_all_dim."""
    T, _, d = x.shape
    factor, beta_fast, beta_slow, orig_max, mscale, mscale_all = s["yarn"]
    freqs = jnp.asarray(yarn_inv_freq(d, s["theta"], factor, beta_fast,
                                      beta_slow, orig_max))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freqs
    ms = _mscale(factor, mscale) / _mscale(factor, mscale_all)
    cos, sin = jnp.cos(ang) * ms, jnp.sin(ang) * ms
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(s, w, x, l):
    """Latent attention, unfolded: keys and values of every head from the
    latents of the whole sequence, queries in blocks of Q_BLOCK."""
    T, H = x.shape[0], s["H"]
    h = _rmsnorm(x, s["eps"])
    cq = _rmsnorm(h @ _at(w["wq_a"], l), s["eps"])
    q = (cq @ _at(w["wq_b"], l)).reshape(T, H, s["nope"] + s["rope"])
    q = jnp.concatenate([q[..., :s["nope"]],
                         _rope(q[..., s["nope"]:], s)], -1)
    ckv = h @ _at(w["wkv_a"], l)
    c = _rmsnorm(ckv[:, :s["kv_rank"]], s["eps"])
    k_r = _rope(ckv[:, None, s["kv_rank"]:], s)               # [T, 1, rope]
    kv = (c @ _at(w["wkv_b"], l)).reshape(T, H, s["nope"] + s["v"])
    k = jnp.concatenate([kv[..., :s["nope"]],
                         jnp.broadcast_to(k_r, (T, H, s["rope"]))], -1)
    v = kv[..., s["nope"]:]
    factor, mscale_all = s["yarn"][0], s["yarn"][5]
    scale = (s["nope"] + s["rope"]) ** -0.5 * _mscale(factor, mscale_all) ** 2
    kpos = jnp.arange(T)
    out = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        qpos = q0 + jnp.arange(qb.shape[0])
        sc = jnp.einsum("thd,shd->hts", qb, k) * scale
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, -1), v))
    a = jnp.concatenate(out, 0).reshape(T, H * s["v"])
    return x + a @ _at(w["wo"], l)


def _ffn(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def select(scores, s):
    """scores [T, E] (sigmoid) -> (experts [T, k], gates [T, k]); ties go
    to the lower index (a stable sort of the negated scores)."""
    T, E = scores.shape
    g = scores.reshape(T, s["n_group"], E // s["n_group"])
    two = -jnp.sort(-g, axis=-1, stable=True)[..., :2]
    group = two.sum(-1)                                       # [T, n_group]
    best = jnp.argsort(-group, axis=-1, stable=True)[:, :s["topk_group"]]
    stays = jnp.zeros((T, s["n_group"]), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    inside = jnp.where(stays[:, :, None], g, -1.0).reshape(T, E)
    idx = jnp.argsort(-inside, axis=-1, stable=True)[:, :s["k"]]
    sel = jnp.take_along_axis(scores, idx, axis=-1)
    if s["norm_topk"]:
        sel = sel / (sel.sum(-1, keepdims=True) + 1e-20)
    return idx, sel * s["routed_scale"]


def _dense_layer(s, w, x, l):
    x = _attention(s, w, x, l)
    h = _rmsnorm(x, s["eps"])
    return x + _ffn(h, _at(w["w_gate"], l), _at(w["w_up"], l),
                    _at(w["w_down"], l))


def _expert_layer(s, w, x, l):
    x = _attention(s, w, x, l)
    h = _rmsnorm(x, s["eps"])
    idx, gates = select(jax.nn.sigmoid(h @ _at(w["router"], l)), s)
    y = _ffn(h, _at(w["ws_gate"], l), _at(w["ws_up"], l),
             _at(w["ws_down"], l))

    def held(e, y):
        # expert first + e, over every token, times its gate there (zero
        # where it was not selected)
        g = jnp.where(idx == s["first"] + e, gates, 0.0).sum(-1)
        return y + g[:, None] * _ffn(h, _at(w["we_gate"], l, e),
                                     _at(w["we_up"], l, e),
                                     _at(w["we_down"], l, e))

    return x + jax.lax.fori_loop(0, s["held"], held, y)


class Reference:
    """`Reference(raw, seed)`, `raw` the configuration's file as loaded;
    `logits(tokens, rows)`: the float32 logits at the given positions of
    one sequence. One compiled layer of each kind serves every layer of
    that kind and every sequence padded to the same length."""

    def __init__(self, raw: dict, seed: int):
        s = self.s = shapes(raw)
        self.w = make_weights(s, seed)

        @jax.jit
        def dense(w, x, l):
            with jax.default_matmul_precision("highest"):
                return _dense_layer(s, w, x, l)

        @jax.jit
        def expert(w, x, l):
            with jax.default_matmul_precision("highest"):
                return _expert_layer(s, w, x, l)

        @jax.jit
        def head(w, x, rows):
            with jax.default_matmul_precision("highest"):
                return _rmsnorm(x[rows], s["eps"]) @ _widen(w)

        self._dense, self._expert, self._head = dense, expert, head

    def lower_to_int8(self) -> None:
        """Turn this reference into the control: the same model computed
        from int8 weights (the bfloat16 leaves are given up)."""
        self.w = quantize_int8(self.w)

    def logits(self, tokens: np.ndarray, rows: np.ndarray) -> np.ndarray:
        x = _widen(jax.tree.map(lambda a: a[jnp.asarray(tokens)],
                                self.w["embed"]))
        for l in range(self.s["n_dense"]):
            x = self._dense(self.w["dense"], x, l)
        for l in range(self.s["L"] - self.s["n_dense"]):
            x = self._expert(self.w["experts"], x, l)
        return np.asarray(self._head(self.w["lm_head"], x,
                                     jnp.asarray(rows)))
