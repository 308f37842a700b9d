"""Window and full attention layers mixed, routed experts in EVERY layer
behind a softmax router, and nothing else: no gate on the heads, no shared
expert, no dense layer (the Mellum 2 form; `model_type` `mellum`).
Everything the harness knows of this architecture, under the names
`benchmark/README.md` fixes (`register`, `Reference`, `stated_precision`,
`decode_weight_bytes`, `decode_step_mark`), and the counts its kernels'
roofline shares are taken from (`routed_experts_floor_s`,
`window_attn_floor_s`, `full_attn_floor_s`).

`raw` is the configuration's file as `configs.load_config` gives it: the
published keys under their own names with the cut applied:
`num_hidden_layers` the depth here, `layer_types` and `mlp_layer_types`
their first `num_hidden_layers` entries. Every expert of a layer is held
(`num_experts` is the published count and the router's width).

**The equations** (`x` the residual stream at a token, `n(·)` an RMSNorm
with `rms_norm_eps` and weight one; layer ℓ of KIND `layer_types[ℓ]`; `H =
num_attention_heads`, `KV = num_key_value_heads`, `d = head_dim`). Every
layer: `x += Attn(n(x))`, then `x += FF(n(x))`.

* Attention (`h = n(x)`): `q = h W_q` (H heads of d), `k = h W_k`, `v = h
  W_v` (KV heads of d), no bias, no q/k norm, no gate. Rotary by kind
  (`rope_parameters[kind]`) over the WHOLE head (the config has no
  `partial_rotary_factor`), pairs `(i, i + d/2)`. `rope_type` `default`:
  frequencies `theta^(-2i/d)`. `yarn`: those blended by parts (kept where a
  frequency turns more than `beta_fast` times over
  `original_max_position_embeddings`, divided by `factor` where fewer than
  `beta_slow`, linear in i between the two dimensions those give), and cos
  and sin MULTIPLIED by `attention_factor` — the `rope_parameters`
  convention: only the products of rotated values carry its square, and
  the softmax scale stays `1/sqrt(d)`. Causal softmax of `q·k / sqrt(d)`
  over keys `j <= i`, and in a `sliding_attention` layer `i - j <
  sliding_window`; query head n reads kv head `n // (H / KV)`. `Attn =
  concat(o_n) W_o`.
* Experts (`mlp_layer_types[ℓ]` `sparse`, every layer; `u = n(x)`): `s =
  softmax(u W_r)` in float32 over all `num_experts`; the
  `num_experts_per_tok` largest (ties to the lower index); gates `s_e /
  Σ_chosen s` (`norm_topk_prob`); `FF = Σ_chosen gate_e · W_2e(silu(W_1e u)
  ⊙ W_3e u)` at `moe_intermediate_size`. No shared expert, no groups, no
  correction bias, no scaling factor. `intermediate_size` names no layer.
* Embedding lookup with no scale, a last RMSNorm, logits over an untied
  head.

**The reference** is float32 at matmul precision "highest", in plain
`jax.numpy` over the whole sequence: a full causal mask with the window AS a
mask, attention per head in blocks of queries, a loop over the experts
(each over every token, times its gate, zero where it was not chosen). No
pages, no cache, no batching. It imports nothing of the program and takes
nothing the program made (`register` alone touches the program). Its
weights are drawn here from the seed by the rule the program's
initialisation STATES (`transformer._init_params_pattern`): `PRNGKey(seed)`
split three ways, embed / layers / head; the layers fall into three SEGMENTS
— 0: the leading dense-feed-forward layers (none here: the segment is
empty), 1: the shortest period of the layers (sliding × 3, full), stacked
over as many repeats as fit whole, 2: what is left of a last period — and
leaf `i` (its place in `leaves_of`) of position `q` of segment `s` is
normal/sqrt(fan-in) rounded to bfloat16, drawn at `[repeats, ...]` from
`fold_in(fold_in(fold_in(k_layers, s), q), i)`; a routed expert's leaf is
drawn per expert at `[repeats, ...]` from `fold_in(that key, e)`.
**Memory**: the leaves stay bfloat16 as served (10.18 GiB at
`mellum2-12b-a2.5b-l12`); `run.py` frees the server's memory first, and a
layer is widened one matrix (one expert) at a time.

**What a session holds.** A resident token's K and V rows are `2 · KV · d`
values a layer (1,024: 2,048 bytes at bfloat16) in every kind. A
`full_attention` layer needs every token of the session, for ever: 3 × 2,048
= 6,144 bytes a token at the cut. A `sliding_attention` layer needs what a
window still reaches: 9 × 2,048 = 18,432 bytes a token, held for at most a
window and a page; the program lets the pages behind it go.

Two switches for the controls, each what a broken program would compute:
`Reference.lift_window` (`benchmark/control_window.py`): the sliding layers
attend to the whole context; `Reference.sigmoid_router`
(`benchmark/control_router.py`): the router scores each expert by a sigmoid
of its own logit, as every other expert configuration of the benchmark
does, where this one takes the softmax over all of them — the same experts
are chosen (both are monotone in the logit), and the gates differ.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import DTYPE_BYTES
from benchmark.families.latent_moe import (
    _at, _ffn, _normal, _normal_experts, _rmsnorm, _widen,
)
from benchmark.families.shortconv_moe import plan, quantize_int8
from benchmark.families.window_moe import FULL, Q_BLOCK, SLIDING, _rope


# -- the mapping ------------------------------------------------------------

def _rotary(raw: dict, kind: str) -> dict:
    """A kind's rotary, from `rope_parameters[kind]`: the whole head."""
    rp = raw["rope_parameters"][kind]
    if "partial_rotary_factor" in rp:
        raise ValueError("window_moe_softmax: the rotary is over the whole "
                         "head")
    out = dict(r=int(raw["head_dim"]), theta=float(rp["rope_theta"]),
               yarn=None)
    if rp["rope_type"] == "yarn":
        out["yarn"] = (float(rp["factor"]), float(rp["beta_fast"]),
                       float(rp["beta_slow"]),
                       int(rp["original_max_position_embeddings"]),
                       float(rp["attention_factor"]))
    elif rp["rope_type"] != "default":
        raise ValueError(f"window_moe_softmax: rope_type {rp['rope_type']!r}")
    return out


# keys of the Laguna form (`window_moe.py`) that name a mechanism this
# family does not compute: a file that carries one is of another family
_OTHER_FORMS = ("gating", "gating_types", "mlp_only_layers",
                "shared_expert_intermediate_size",
                "num_attention_heads_per_layer", "moe_routed_scaling_factor",
                "moe_router_logit_softcapping", "n_group", "topk_group")


def shapes(raw: dict) -> dict:
    """The sizes this module computes with, from the published keys."""
    L = int(raw["num_hidden_layers"])
    types = list(raw["layer_types"])
    if len(types) != L or set(types) - {FULL, SLIDING}:
        raise ValueError("window_moe_softmax: layer_types and "
                         "num_hidden_layers disagree")
    if list(raw["mlp_layer_types"]) != ["sparse"] * L:
        raise ValueError("window_moe_softmax: every layer's feed-forward "
                         "is the experts'")
    has = [k for k in _OTHER_FORMS if raw.get(k)]
    if has or raw["attention_bias"] or raw["hidden_act"] != "silu" \
            or not raw["use_sliding_window"] or raw["max_window_layers"]:
        raise ValueError(f"window_moe_softmax: no gate, no shared expert, "
                         f"no dense layer, no bias, no scale, silu, "
                         f"layer_types decides the window {has}")
    return dict(
        L=L, types=types, H=int(raw["num_attention_heads"]),
        D=int(raw["hidden_size"]), KV=int(raw["num_key_value_heads"]),
        hd=int(raw["head_dim"]), W=int(raw["sliding_window"]), n_dense=0,
        E=int(raw["num_experts"]), k=int(raw["num_experts_per_tok"]),
        Fe=int(raw["moe_intermediate_size"]), V=int(raw["vocab_size"]),
        norm_topk=bool(raw["norm_topk_prob"]),
        eps=float(raw["rms_norm_eps"]),
        tied=bool(raw["tie_word_embeddings"]),
        rotary={t: _rotary(raw, t) for t in sorted(set(types))})


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
                raise ValueError("window_moe_softmax: attention_factor is "
                                 "not 0.1 ln(factor) + 1")
            scaling = ("yarn", factor, fast, slow, orig, 1.0, 0.0)
        return AttnKind(n_heads=s["H"],
                        window=s["W"] if t == SLIDING else None,
                        rope_theta=ro["theta"], rope_scaling=scaling)

    register_model(ModelConfig(
        name=raw["name"], vocab_size=s["V"], dim=s["D"], n_layers=s["L"],
        n_heads=s["H"], n_kv_heads=s["KV"],
        ffn_dim=int(raw["intermediate_size"]), head_dim=s["hd"],
        norm_eps=s["eps"], tie_embeddings=s["tied"],
        layer_types=tuple(s["types"]),
        attn_kinds=tuple((t, kind(t)) for t in sorted(s["rotary"])),
        moe=MoEConfig(n_routed=s["E"], n_held=s["E"], per_token=s["k"],
                      expert_dim=s["Fe"], n_shared=0,
                      norm_topk=s["norm_topk"], first_dense=0,
                      score="softmax"),
        context_window=int(raw["serving"]["context_window"]),
        output_limit=int(raw["serving"]["output_limit"]),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"])))
    return f"xla:{raw['name']}"


# -- bytes and operations, from the shapes ----------------------------------

def _kv_row_bytes(raw: dict) -> int:
    """Bytes of one token's K and V rows in one layer."""
    s = shapes(raw)
    return 2 * s["KV"] * s["hd"] * DTYPE_BYTES[raw["torch_dtype"]]


def stated_precision(raw: dict) -> dict:
    """{key of the engine's `quant_stats()`: what it has to read}: the
    bytes a resident token holds over the full layers, which grow with a
    session, and over the sliding layers, held for at most a window and a
    page (module docstring: 6,144 and 18,432 at `mellum2-12b-a2.5b-l12`)."""
    s = shapes(raw)
    return {"kv_bytes_per_token": s["types"].count(FULL) * _kv_row_bytes(raw),
            "window_kv_bytes_per_token":
            s["types"].count(SLIDING) * _kv_row_bytes(raw)}


def routed_expert_bytes(raw: dict) -> int:
    """Bytes of one routed expert's three matrices (12,386,304 at the
    published widths)."""
    s = shapes(raw)
    return 3 * s["D"] * s["Fe"] * DTYPE_BYTES[raw["torch_dtype"]]


def decode_weight_bytes(raw: dict) -> int:
    """Bytes of weights EVERY decode step has to read: a LOWER bound for
    any step the cell can run. Counted: everything outside the routed
    experts — every layer's attention (q, k, v, o) and router, the head —
    plus `num_experts_per_tok` experts a layer: every expert of a layer is
    held here, so the one row a step has at least reaches that many in
    each (2,155,216,896 bytes at `mellum2-12b-a2.5b-l12`: 966,131,712
    outside the experts and 1,189,085,184 in 8 experts a layer). What the
    steps of a run did read of the experts — 42 of a layer's 64 at 8 rows
    were their picks independent, some 17 under seeded weights, whose rows
    pick alike (PERF.md section 6, PR 41) — is
    `kernel.routed_experts_bw_share_pct`'s, from the program's counter.
    Norms are left out; the embedding lookup reads rows, not the table."""
    s = shapes(raw)
    attn = 2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]
    outside = s["L"] * (attn + s["D"] * s["E"]) + s["V"] * s["D"]
    return outside * DTYPE_BYTES[raw["torch_dtype"]] \
        + s["L"] * s["k"] * routed_expert_bytes(raw)


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
    brought in (`kv_streamed`: each costs its K and V rows, 2,048 bytes)
    and the query-key pairs under the mask (`pairs`: each costs `4 ·
    head_dim` operations a QUERY HEAD, q·k and p·v, 32 heads)."""
    s = shapes(raw)
    layers = s["types"].count(kind)
    moved = layers * kv_streamed * _kv_row_bytes(raw)
    flops = layers * pairs * 4 * s["hd"] * s["H"]
    return max(moved / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def window_attn_floor_s(raw: dict, kv_streamed: float, pairs: float,
                        peaks: dict) -> float:
    return _attn_floor_s(raw, SLIDING, kv_streamed, pairs, peaks)


def full_attn_floor_s(raw: dict, kv_streamed: float, pairs: float,
                      peaks: dict) -> float:
    return _attn_floor_s(raw, FULL, kv_streamed, pairs, peaks)


# -- the plain reference ----------------------------------------------------

# (name, shape, fan-in) of a layer's leaves, in the order that numbers
# their keys: the attention first, then the experts
def leaves_of(s: dict) -> list:
    D, q, kv = s["D"], s["H"] * s["hd"], s["KV"] * s["hd"]
    return [("wq", (D, q), D), ("wk", (D, kv), D), ("wv", (D, kv), D),
            ("wo", (q, D), q), ("router", (D, s["E"]), D),
            ("we_gate", (D, s["Fe"]), D), ("we_up", (D, s["Fe"]), D),
            ("we_down", (s["Fe"], D), s["Fe"])]


def make_weights(s: dict, seed: int) -> dict:
    """The model of `seed`: `embed`, `lm_head` and `segments[s][q]`, the
    stacked leaves of position `q` (segment 0, the leading dense layers',
    is empty)."""
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = {"embed": _normal(k_embed, (s["V"], s["D"]), s["D"])}
    if not s["tied"]:
        w["lm_head"] = _normal(k_head, (s["D"], s["V"]), s["D"])
    w["segments"] = []
    for si, (kinds, n) in enumerate(plan(s)):
        positions = []
        for q in range(len(kinds) if n else 0):
            kq = jax.random.fold_in(jax.random.fold_in(k_layers, si), q)
            leaves = {}
            for i, (leaf, shape, fan_in) in enumerate(leaves_of(s)):
                k = jax.random.fold_in(kq, i)
                if leaf.startswith("we_"):
                    leaves[leaf] = _normal_experts(k, 0, s["E"], (n, *shape),
                                                   fan_in)
                else:
                    leaves[leaf] = _normal(k, (n, *shape), fan_in)
            positions.append(leaves)
        w["segments"].append(positions)
    return w


def _attention(s, w, x, rep, kind, lift_window):
    T, H, KV, hd = x.shape[0], s["H"], s["KV"], s["hd"]
    h = _rmsnorm(x, s["eps"])
    q = (h @ _at(w["wq"], rep)).reshape(T, H, hd)
    k = (h @ _at(w["wk"], rep)).reshape(T, KV, hd)
    v = (h @ _at(w["wv"], rep)).reshape(T, KV, hd)
    q, k = _rope(q, s["rotary"][kind]), _rope(k, s["rotary"][kind])
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
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
    return x + jnp.concatenate(out, 0).reshape(T, H * hd) @ _at(w["wo"], rep)


def select(scores, s):
    """scores [T, E] -> (experts [T, k], gates [T, k]): the k largest, ties
    to the lower index (a stable sort of the negated scores); gates the
    chosen scores over their sum (`norm_topk_prob`)."""
    idx = jnp.argsort(-scores, axis=-1, stable=True)[:, :s["k"]]
    sel = jnp.take_along_axis(scores, idx, axis=-1)
    if s["norm_topk"]:
        sel = sel / sel.sum(-1, keepdims=True)
    return idx, sel


def _experts(s, w, x, rep, sigmoid_router):
    u = _rmsnorm(x, s["eps"])
    logits = u @ _at(w["router"], rep)
    idx, gates = select(jax.nn.sigmoid(logits) if sigmoid_router
                        else jax.nn.softmax(logits, axis=-1), s)

    def one(e, y):
        # expert e over every token, times its gate there (zero where it
        # was not chosen)
        ge = jnp.where(idx == e, gates, 0.0).sum(-1)
        return y + ge[:, None] * _ffn(u, _at(w["we_gate"], rep, e),
                                      _at(w["we_up"], rep, e),
                                      _at(w["we_down"], rep, e))

    return x + jax.lax.fori_loop(0, s["E"], one, jnp.zeros_like(x))


def _layer(s, w, x, rep, kind, lift_window, sigmoid_router):
    return _experts(s, w, _attention(s, w, x, rep, kind, lift_window), rep,
                    sigmoid_router)


class Reference:
    """`Reference(raw, seed)`, `raw` the configuration's file as loaded;
    `logits(tokens, rows)`: the float32 logits at the given positions of
    one sequence. One compiled layer of each kind serves every layer of
    that kind and every sequence padded to the same length."""

    def __init__(self, raw: dict, seed: int):
        s = self.s = shapes(raw)
        self.w = make_weights(s, seed)
        self.lift_window = False
        self.sigmoid_router = False

        @functools.partial(jax.jit, static_argnums=(3, 4, 5))
        def layer(w, x, rep, kind, lift_window, sigmoid_router):
            with jax.default_matmul_precision("highest"):
                return _layer(s, w, x, rep, kind, lift_window,
                              sigmoid_router)

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
                for w, (kind, _) in zip(positions, kinds):
                    x = self._layer(w, x, rep, kind, bool(self.lift_window),
                                    bool(self.sigmoid_router))
        head = self.w["embed" if self.s["tied"] else "lm_head"]
        return np.asarray(self._head(head, x, jnp.asarray(rows)))
