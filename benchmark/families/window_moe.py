"""Window and full attention layers mixed, a gate a head, routed experts with
a shared one (the Laguna form; `model_type` `laguna`): everything the harness
knows of this architecture, under the names `benchmark/README.md` fixes
(`register`, `Reference`, `stated_precision`, `decode_weight_bytes`,
`decode_step_mark`), and the counts its kernels' roofline shares are taken
from (`routed_experts_floor_s`, `window_attn_floor_s`, `full_attn_floor_s`).

`raw` is the configuration's file as `configs.load_config` gives it: the
published keys under their own names with the cut applied. `num_experts` is
the experts HELD here (experts 0 .. of the published `reduced_from.
num_experts`, which stays the router's width), `vocab_size` the rows of the
embedding and the head held here, `num_hidden_layers` the depth, and the
per-layer lists (`layer_types`, `mlp_layer_types`, `gating_types`,
`num_attention_heads_per_layer`) their first `num_hidden_layers` entries.

**The equations** (`x` the residual stream at a token, `n(·)` an RMSNorm
with `rms_norm_eps` and weight one; layer ℓ of KIND `layer_types[ℓ]`, with
`H = num_attention_heads_per_layer[ℓ]` query heads, `KV =
num_key_value_heads`, `d = head_dim`). Every layer: `x += Attn(n(x))`, then
`x += FF(n(x))`.

* Attention (`h = n(x)`): `q = h W_q` (H heads of d), `k = h W_k`, `v = h
  W_v` (KV heads of d), no bias, no q/k norm. Rotary by kind
  (`rope_parameters[kind]`), on the first `r = partial_rotary_factor · d`
  values of each head of q and k, pairs `(i, i + r/2)`, the rest passed
  through. `rope_type` `default`: frequencies `theta^(-2i/r)`. `yarn`:
  those blended by parts over `r` (kept where a frequency turns more than
  `beta_fast` times over `original_max_position_embeddings`, divided by
  `factor` where fewer than `beta_slow`, linear in i between the two
  dimensions those give), and cos and sin MULTIPLIED by `attention_factor`
  — the `rope_parameters` convention: only the products of rotated values
  carry its square, and the softmax scale stays `1/sqrt(d)`. Causal
  softmax of `q·k / sqrt(d)` over keys `j <= i`, and in a
  `sliding_attention` layer `i - j < sliding_window`; query head n reads kv
  head `n // (H / KV)`. Gate (`gating` `per-head`): `g = sigmoid(h W_g)`,
  `W_g` `hidden_size × H`; head n's output times `g_n`; `Attn = concat(g_n
  · o_n) W_o`.
* Dense feed-forward (`mlp_layer_types[ℓ]` `dense`): `W_2(silu(W_1 u) ⊙ W_3
  u)` at `intermediate_size`.
* Experts (`sparse`; `u = n(x)`): `s = sigmoid(u W_r)` in float32 over all
  published experts; the `num_experts_per_tok` largest (ties to the lower
  index); gates `s_e / (Σ_chosen s + 1e-20)` (`norm_topk_prob`) times
  `moe_routed_scaling_factor`; `FF = Σ_{chosen e HELD here} gate_e · W_2e(
  silu(W_1e u) ⊙ W_3e u)` at `moe_intermediate_size`, plus the shared
  expert's gated MLP at `shared_expert_intermediate_size`, ungated. No
  groups, no correction bias, no soft cap (`moe_router_logit_softcapping`
  0).
* Embedding lookup with no scale, a last RMSNorm, logits over the held rows
  of an untied head.

**The reference** is float32 at matmul precision "highest", in plain
`jax.numpy` over the whole sequence: a full causal mask with the window AS a
mask, attention per head in blocks of queries, a loop over the held experts
(each over every token, times its gate, zero where it was not chosen). No
pages, no cache, no batching. It imports nothing of the program and takes
nothing the program made (`register` alone touches the program). Its
weights are drawn here from the seed by the rule the program's
initialisation STATES (`transformer._init_params_pattern`): `PRNGKey(seed)`
split three ways, embed / layers / head; the layers fall into three SEGMENTS
— 0: the leading dense-feed-forward layers, 1: the shortest period of the
layers after them (kind and feed-forward), stacked over as many repeats as
fit whole, 2: what is left of a last period — and leaf `i` (its place in
`leaves_of`) of position `q` of segment `s` is normal/sqrt(fan-in) rounded
to bfloat16, drawn at `[repeats, ...]` from `fold_in(fold_in(fold_in(
k_layers, s), q), i)`; a routed expert's leaf is drawn per expert at
`[repeats, ...]` from `fold_in(that key, e)`, `e` its published number.
**Memory**: the leaves stay bfloat16 as served (8.86 GiB at
`laguna-s-2.1-ep8-l13`); `run.py` frees the server's memory first, and a
layer is widened one matrix (one expert) at a time.

**What a session holds.** A resident token's K and V rows are `2 · KV · d`
values a layer (2,048: 4,096 bytes at bfloat16) in every kind. A
`full_attention` layer needs every token of the session, for ever: 4 × 4,096
= 16,384 bytes a token at the cut. A `sliding_attention` layer needs what a
window still reaches: 9 × 4,096 = 36,864 bytes a token, held for at most a
window and a page; the program lets the pages behind it go.

`Reference.lift_window` is the window control's switch
(`benchmark/control_window.py`): with it the sliding layers attend to the
whole context — what a program would compute whose window layers walked
pages that should have been let go, or took the full layers' mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import DTYPE_BYTES
from benchmark.families.latent_moe import (
    _at, _ffn, _normal, _normal_experts, _rmsnorm, _widen, yarn_inv_freq,
)
from benchmark.families.shortconv_moe import plan, quantize_int8
from benchmark.families.shortconv_moe import select as _select

FULL = "full_attention"
SLIDING = "sliding_attention"


# -- the mapping ------------------------------------------------------------

def _rotary(raw: dict, kind: str) -> dict:
    """A kind's rotary, from `rope_parameters[kind]`."""
    rp = raw["rope_parameters"][kind]
    r = int(round(int(raw["head_dim"]) * float(rp["partial_rotary_factor"])))
    out = dict(r=r, theta=float(rp["rope_theta"]), yarn=None)
    if rp["rope_type"] == "yarn":
        out["yarn"] = (float(rp["factor"]), float(rp["beta_fast"]),
                       float(rp["beta_slow"]),
                       int(rp["original_max_position_embeddings"]),
                       float(rp["attention_factor"]))
    elif rp["rope_type"] != "default":
        raise ValueError(f"window_moe: rope_type {rp['rope_type']!r}")
    return out


def shapes(raw: dict) -> dict:
    """The sizes this module computes with, from the published keys."""
    L = int(raw["num_hidden_layers"])
    types = list(raw["layer_types"])
    heads = [int(h) for h in raw["num_attention_heads_per_layer"]]
    dense = [t == "dense" for t in raw["mlp_layer_types"]]
    if not (len(types) == len(heads) == len(dense) == L
            == len(raw["gating_types"])) or set(types) - {FULL, SLIDING}:
        raise ValueError("window_moe: the per-layer lists and "
                         "num_hidden_layers disagree")
    if raw["gating"] != "per-head" or set(raw["gating_types"]) != {"per_head"}:
        raise ValueError("window_moe: a gate a head in every layer only")
    n_dense = sum(dense)
    if dense != [True] * n_dense + [False] * (L - n_dense) \
            or [i for i, d in enumerate(dense) if d] \
            != list(raw["mlp_only_layers"])[:n_dense]:
        raise ValueError("window_moe: dense layers lead")
    if raw["moe_router_logit_softcapping"] or raw["attention_bias"] \
            or raw["moe_apply_router_weight_on_input"] \
            or raw["decoder_sparse_step"] != 1:
        raise ValueError("window_moe: no soft cap, no bias, gates on the "
                         "experts' output, every layer after the dense "
                         "ones sparse")
    by_kind = {t: h for t, h in zip(types, heads)}
    if any(by_kind[t] != h for t, h in zip(types, heads)):
        raise ValueError("window_moe: one head count a kind")
    published = raw.get("reduced_from", {})
    return dict(
        L=L, types=types, heads=by_kind, D=int(raw["hidden_size"]),
        KV=int(raw["num_key_value_heads"]), hd=int(raw["head_dim"]),
        W=int(raw["sliding_window"]), F=int(raw["intermediate_size"]),
        n_dense=n_dense,
        E=int(published.get("num_experts", raw["num_experts"])),
        held=int(raw["num_experts"]), k=int(raw["num_experts_per_tok"]),
        Fe=int(raw["moe_intermediate_size"]),
        Fs=int(raw["shared_expert_intermediate_size"]),
        V=int(raw["vocab_size"]), norm_topk=bool(raw["norm_topk_prob"]),
        gate_eps=1e-20,
        routed_scale=float(raw["moe_routed_scaling_factor"]),
        eps=float(raw["rms_norm_eps"]),
        tied=bool(raw["tie_word_embeddings"]),
        rotary={t: _rotary(raw, t) for t in by_kind})


def register(raw: dict) -> str:
    """Register the configuration with the program; returns its spec."""
    from quoracle_tpu.models.config import (
        AttnKind, ModelConfig, MoEConfig, register_model,
    )
    s = shapes(raw)

    def kind(t):
        ro = s["rotary"][t]
        scaling = None
        if ro["yarn"] is not None:
            # the program's YaRN tuple ends (mscale, mscale_all_dim) and
            # multiplies cos and sin by their ratio of yarn_mscale: 1 and 0
            # make that 0.1 ln(factor) + 1, the published attention_factor
            factor, fast, slow, orig, att = ro["yarn"]
            if abs(0.1 * math.log(factor) + 1.0 - att) > 1e-12:
                raise ValueError("window_moe: attention_factor is not "
                                 "0.1 ln(factor) + 1")
            scaling = ("yarn", factor, fast, slow, orig, 1.0, 0.0)
        return AttnKind(
            n_heads=s["heads"][t], window=s["W"] if t == SLIDING else None,
            rope_theta=ro["theta"], rope_scaling=scaling,
            rotary_dim=ro["r"] if ro["r"] < s["hd"] else None)

    register_model(ModelConfig(
        name=raw["name"], vocab_size=s["V"], dim=s["D"], n_layers=s["L"],
        n_heads=int(raw["num_attention_heads"]), n_kv_heads=s["KV"],
        ffn_dim=s["F"], head_dim=s["hd"], norm_eps=s["eps"],
        tie_embeddings=s["tied"], layer_types=tuple(s["types"]),
        attn_kinds=tuple((t, kind(t)) for t in sorted(s["heads"])),
        attn_gate=True,
        moe=MoEConfig(n_routed=s["E"], n_held=s["held"], per_token=s["k"],
                      expert_dim=s["Fe"], n_shared=s["Fs"] // s["Fe"],
                      routed_scale=s["routed_scale"],
                      norm_topk=s["norm_topk"], first_dense=s["n_dense"]),
        context_window=int(raw["serving"]["context_window"]),
        output_limit=int(raw["serving"]["output_limit"]),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"])))
    return f"xla:{raw['name']}"


# -- bytes and operations, from the shapes ----------------------------------

def _attn_params(s: dict, kind: str) -> int:
    H = s["heads"][kind]
    return (2 * s["D"] * H * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]
            + s["D"] * H)


def _kv_row_bytes(raw: dict) -> int:
    """Bytes of one token's K and V rows in one layer."""
    s = shapes(raw)
    return 2 * s["KV"] * s["hd"] * DTYPE_BYTES[raw["torch_dtype"]]


def stated_precision(raw: dict) -> dict:
    """{key of the engine's `quant_stats()`: what it has to read}: the
    bytes a resident token holds over the full layers, which grow with a
    session, and over the sliding layers, held for at most a window and a
    page (module docstring: 16,384 and 36,864 at `laguna-s-2.1-ep8-l13`)."""
    s = shapes(raw)
    return {"kv_bytes_per_token": s["types"].count(FULL) * _kv_row_bytes(raw),
            "window_kv_bytes_per_token":
            s["types"].count(SLIDING) * _kv_row_bytes(raw)}


def routed_expert_bytes(raw: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    s = shapes(raw)
    return 3 * s["D"] * s["Fe"] * DTYPE_BYTES[raw["torch_dtype"]]


def decode_weight_bytes(raw: dict) -> int:
    """Bytes of weights EVERY decode step has to read: a LOWER bound for
    any step the cell can run. Counted: everything outside the routed
    experts — every layer's attention (q, k, v, o and the gate), the dense
    layers' feed-forward, each expert layer's router and shared expert, the
    head's held rows. Of the routed experts: NOTHING. A row's
    `num_experts_per_tok` choices fall on all published experts and this
    chip holds an eighth of them, so a step with few rows can reach no held
    expert in a layer, and no single expert's bytes are unavoidable
    (2,115,944,448 bytes at `laguna-s-2.1-ep8-l13`). What the steps of a
    run did read of the experts is `kernel.routed_experts_bw_share_pct`'s,
    from the program's counter. Norms are left out; the embedding lookup
    reads rows, not the table."""
    s = shapes(raw)
    n_expert = s["L"] - s["n_dense"]
    outside = (sum(_attn_params(s, t) for t in s["types"])
               + s["n_dense"] * 3 * s["D"] * s["F"]
               + n_expert * (s["D"] * s["E"] + 3 * s["D"] * s["Fs"])
               + s["V"] * s["D"])
    return outside * DTYPE_BYTES[raw["torch_dtype"]]


def decode_step_mark(raw: dict) -> dict:
    """The attention kernel's custom call, once a layer of either kind."""
    return {"op_pattern": "^%ragged_attend", "per_step": shapes(raw)["L"]}


def routed_experts_floor_s(raw: dict, reached: float, peaks: dict) -> float:
    """The least time the grouped matmuls need for `reached` experts with
    a token (summed over layers and steps): each has to be read."""
    return reached * routed_expert_bytes(raw) / peaks["hbm_bytes_per_s"]


def _attn_floor_s(raw: dict, kind: str, kv_streamed: float, pairs: float,
                  peaks: dict) -> float:
    """The least time the attention kernel needs in the layers of `kind`
    for one tick: the larger of its bytes' and its multiplies' time. The
    program says, for ONE layer of the kind, the resident tokens its walks
    brought in (`kv_streamed`: each costs its K and V rows) and the
    query-key pairs under the mask (`pairs`: each costs `4 · head_dim`
    operations a QUERY HEAD, q·k and p·v)."""
    s = shapes(raw)
    layers = s["types"].count(kind)
    moved = layers * kv_streamed * _kv_row_bytes(raw)
    flops = layers * pairs * 4 * s["hd"] * s["heads"][kind]
    return max(moved / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def window_attn_floor_s(raw: dict, kv_streamed: float, pairs: float,
                        peaks: dict) -> float:
    return _attn_floor_s(raw, SLIDING, kv_streamed, pairs, peaks)


def full_attn_floor_s(raw: dict, kv_streamed: float, pairs: float,
                      peaks: dict) -> float:
    return _attn_floor_s(raw, FULL, kv_streamed, pairs, peaks)


# -- the plain reference ----------------------------------------------------

Q_BLOCK = 512


# `plan(s)`, the three segments `[(kinds, repeats)]` with `kinds` a list of
# (layer type, has experts) — the leading dense layers once, the shortest
# period of the rest as often as it fits whole, the remainder once — is the
# conv hybrids' (`shortconv_moe.plan`): it reads `types` and `n_dense` alone.


# (name, shape, fan-in) of a layer's leaves, in the order that numbers
# their keys: the attention first, then the feed-forward
def leaves_of(s: dict, kind: str, experts: bool) -> list:
    D = s["D"]
    q, kv = s["heads"][kind] * s["hd"], s["KV"] * s["hd"]
    leaves = [("wq", (D, q), D), ("wk", (D, kv), D), ("wv", (D, kv), D),
              ("wo", (q, D), q), ("wg", (D, s["heads"][kind]), D)]
    if not experts:
        return leaves + [("w_gate", (D, s["F"]), D), ("w_up", (D, s["F"]), D),
                         ("w_down", (s["F"], D), s["F"])]
    return leaves + [("router", (D, s["E"]), D),
                     ("we_gate", (D, s["Fe"]), D), ("we_up", (D, s["Fe"]), D),
                     ("we_down", (s["Fe"], D), s["Fe"]),
                     ("ws_gate", (D, s["Fs"]), D), ("ws_up", (D, s["Fs"]), D),
                     ("ws_down", (s["Fs"], D), s["Fs"])]


def make_weights(s: dict, seed: int) -> dict:
    """The model of `seed`: `embed`, `lm_head` and `segments[s][q]`, the
    stacked leaves of position `q`."""
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = {"embed": _normal(k_embed, (s["V"], s["D"]), s["D"])}
    if not s["tied"]:
        w["lm_head"] = _normal(k_head, (s["D"], s["V"]), s["D"])
    w["segments"] = []
    for si, (kinds, n) in enumerate(plan(s)):
        positions = []
        for q, (kind, experts) in enumerate(kinds if n else []):
            kq = jax.random.fold_in(jax.random.fold_in(k_layers, si), q)
            leaves = {}
            for i, (leaf, shape, fan_in) in enumerate(
                    leaves_of(s, kind, experts)):
                k = jax.random.fold_in(kq, i)
                if leaf.startswith("we_"):
                    leaves[leaf] = _normal_experts(k, 0, s["held"],
                                                   (n, *shape), fan_in)
                else:
                    leaves[leaf] = _normal(k, (n, *shape), fan_in)
            positions.append(leaves)
        w["segments"].append(positions)
    return w


def inv_freq(ro: dict) -> np.ndarray:
    """A kind's `r/2` rotary frequencies (`_rotary`)."""
    if ro["yarn"] is None:
        return (ro["theta"] ** (-2 * np.arange(ro["r"] // 2, dtype=np.float64)
                                / ro["r"])).astype(np.float32)
    factor, fast, slow, orig, _ = ro["yarn"]
    return yarn_inv_freq(ro["r"], ro["theta"], factor, fast, slow, orig)


def _rope(x, ro):
    """x: [T, heads, d]; position t rotates pair (i, i + r/2) of the first
    r values by t times frequency i, cos and sin times the attention
    factor; the values behind r pass through."""
    T, r = x.shape[0], ro["r"]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq(ro))
    att = 1.0 if ro["yarn"] is None else ro["yarn"][4]
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], -1)


def _attention(s, w, x, rep, kind, lift_window):
    T, H, KV, hd = x.shape[0], s["heads"][kind], s["KV"], s["hd"]
    h = _rmsnorm(x, s["eps"])
    q = (h @ _at(w["wq"], rep)).reshape(T, H, hd)
    k = (h @ _at(w["wk"], rep)).reshape(T, KV, hd)
    v = (h @ _at(w["wv"], rep)).reshape(T, KV, hd)
    q, k = _rope(q, s["rotary"][kind]), _rope(k, s["rotary"][kind])
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    gate = jax.nn.sigmoid(h @ _at(w["wg"], rep))              # [T, H]
    kpos = jnp.arange(T)
    out = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        qpos = q0 + jnp.arange(qb.shape[0])
        seen = kpos[None, :] <= qpos[:, None]
        if kind == SLIDING and not lift_window:
            seen = seen & (qpos[:, None] - kpos[None, :] < s["W"])
        sc = jnp.einsum("thd,shd->hts", qb, k) * hd ** -0.5
        sc = jnp.where(seen, sc, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, -1), v))
    a = jnp.concatenate(out, 0) * gate[:, :, None]
    return x + a.reshape(T, H * hd) @ _at(w["wo"], rep)


def select(scores, s):
    """scores [T, E] (sigmoid) -> (experts [T, k], gates [T, k]): the k
    largest, ties to the lower index; gates the chosen scores over (their
    sum + 1e-20) times the scale (`shortconv_moe.select` with no bias)."""
    return _select(scores, None, s)


def _experts(s, w, x, rep):
    u = _rmsnorm(x, s["eps"])
    idx, gates = select(jax.nn.sigmoid(u @ _at(w["router"], rep)), s)

    def one(e, y):
        # held expert e (its published number: the held ones are the first)
        # over every token, times its gate there (zero where not chosen)
        ge = jnp.where(idx == e, gates, 0.0).sum(-1)
        return y + ge[:, None] * _ffn(u, _at(w["we_gate"], rep, e),
                                      _at(w["we_up"], rep, e),
                                      _at(w["we_down"], rep, e))

    routed = jax.lax.fori_loop(0, s["held"], one, jnp.zeros_like(x))
    return x + routed + _ffn(u, _at(w["ws_gate"], rep), _at(w["ws_up"], rep),
                             _at(w["ws_down"], rep))


def _layer(s, w, x, rep, kind, experts, lift_window):
    x = _attention(s, w, x, rep, kind, lift_window)
    if experts:
        return _experts(s, w, x, rep)
    u = _rmsnorm(x, s["eps"])
    return x + _ffn(u, _at(w["w_gate"], rep), _at(w["w_up"], rep),
                    _at(w["w_down"], rep))


class Reference:
    """`Reference(raw, seed)`, `raw` the configuration's file as loaded;
    `logits(tokens, rows)`: the float32 logits at the given positions of
    one sequence. One compiled layer of each kind serves every layer of
    that kind and every sequence padded to the same length."""

    def __init__(self, raw: dict, seed: int):
        s = self.s = shapes(raw)
        self.w = make_weights(s, seed)
        self.lift_window = False

        @functools.partial(jax.jit, static_argnums=(3, 4, 5))
        def layer(w, x, rep, kind, experts, lift_window):
            with jax.default_matmul_precision("highest"):
                return _layer(s, w, x, rep, kind, experts, lift_window)

        @jax.jit
        def head(w, x, rows):
            with jax.default_matmul_precision("highest"):
                w = _widen(w)
                return _rmsnorm(x[rows], s["eps"]) @ (w.T if s["tied"] else w)

        self._layer, self._head = layer, head

    def lower_to_int8(self) -> None:
        """Turn this reference into the control: the same model computed
        from int8 weights (the bfloat16 leaves are given up)."""
        self.w = quantize_int8(self.w)

    def logits(self, tokens: np.ndarray, rows: np.ndarray) -> np.ndarray:
        x = _widen(jax.tree.map(lambda a: a[jnp.asarray(tokens)],
                                self.w["embed"]))
        for positions, (kinds, n) in zip(self.w["segments"], plan(self.s)):
            for rep in range(n):
                for w, (kind, experts) in zip(positions, kinds):
                    x = self._layer(w, x, rep, kind, experts,
                                    bool(self.lift_window))
        head = self.w["embed" if self.s["tied"] else "lm_head"]
        return np.asarray(self._head(head, x, jnp.asarray(rows)))
