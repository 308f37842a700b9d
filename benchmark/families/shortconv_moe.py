"""The hybrid of gated short convolutions and per-head attention with many
small routed experts (the LFM2 form; `model_type` `lfm2_moe`): everything
the harness knows of this architecture, under the names `benchmark/README.md`
fixes (`register`, `Reference`, `stated_precision`, `decode_weight_bytes`,
`decode_step_mark`), and the counts its kernels' roofline shares are taken
from (`routed_experts_floor_s`, `short_conv_floor_s`).

`raw` is the configuration's file as `configs.load_config` gives it: the
published keys under their own names with the cut applied. Only the depth
is cut (`num_hidden_layers`, `num_dense_layers`, `layer_types`; the
published values stand beside them under `reduced_from`): every width,
every head, every expert and the whole vocabulary are held.

**The equations** (`h` the residual stream at a token, `n(·)` an RMSNorm,
`norm_eps`, weight one). Every layer: `h += Op(n(h))`, then `h += FF(n(h))`;
`Op` by `layer_types`, `FF` the dense one in the leading `num_dense_layers`
layers and the experts after.

* Short conv (`u = n(h)`, no bias: `conv_bias` false): `[B ‖ C ‖ x] = u
  W_in`, three vectors of `hidden_size`, in that order; `z = B ⊙ x`; `c_t =
  Σ_{j<K} w_j ⊙ z_{t-K+1+j}` with `K = conv_L_cache`: a depthwise causal
  convolution, one weight a channel a tap, the last tap on the current
  token, `z` zero before the sequence's start; `Op = (C ⊙ c) W_out`. No
  positions. What the next token needs of the past is the last `K - 1`
  values of `z`: that is the layer's STATE, `(K - 1) · hidden_size` values
  whatever the sequence's length.
* Attention: `q = u W_q` (`num_attention_heads` heads of `hidden_size /
  num_attention_heads`), `k = u W_k`, `v = u W_v` (`num_key_value_heads`);
  `q`, `k` through an RMSNorm over EACH head's values (one weight vector for
  all heads, one here), then rotary on halves (pair `(i, i + d/2)`,
  `rope_parameters.rope_theta`, no scaling); causal softmax of `q·k /
  sqrt(d)` over the whole context, a group of query heads a kv head; `Op =
  attn W_o`. No bias.
* Dense feed-forward: `W_2(silu(W_1 g) ⊙ W_3 g)` at `intermediate_size`.
* Experts (`g = n(h)`): `s = sigmoid(g W_r)` in float32 over all
  `num_experts`; the `num_experts_per_tok` experts of largest `s + b` are
  chosen (`b` the `expert_bias`, `use_expert_bias`: one float32 an expert;
  ties to the lower index); the gates are the BARE `s` of the chosen over
  (their sum + `gate_normaliser_eps`) (`norm_topk_prob`), times
  `routed_scaling_factor`; `FF = Σ_chosen gate_e · W_2e(silu(W_1e g) ⊙ W_3e
  g)` at `moe_intermediate_size`. No shared expert, no groups.
* Embedding lookup with no scale, a last RMSNorm, and the head is the
  embedding transposed (`tie_word_embeddings`, `assumed` in the file).

**The reference** is float32 at matmul precision "highest", in plain
`jax.numpy` over the whole sequence: shifts for the taps, a loop over the
experts (each over every token, times its gate, zero where it was not
chosen), attention per head in blocks of queries. No cache, no state, no
batching. It imports nothing of the program and takes nothing the program
made (`register` alone touches the program). Its weights are drawn here
from the seed by the rule the program's initialisation STATES
(`transformer._init_params_pattern`): `PRNGKey(seed)` split three ways,
embed / layers / head; the layers fall into three SEGMENTS — 0: the leading
dense layers, 1: the shortest period of the layers after them, stacked over
as many repeats as fit whole, 2: what is left of a last period — and leaf
`i` (its place in `leaves_of`) of position `q` of segment `s` is
normal/sqrt(fan-in) rounded to bfloat16, drawn at `[repeats, ...]` from
`fold_in(fold_in(fold_in(k_layers, s), q), i)`; a routed expert's leaf is
drawn per expert at `[repeats, ...]` from `fold_in(that key, e)`;
`expert_bias` is float32, 0.01 × normal. **Memory**: the leaves stay
bfloat16 as served, 9.64 GiB at `lfm2-24b-a2b-l9`; `run.py` frees the
server's memory first, and a layer is widened one matrix (one expert) at a
time.

**What a session holds.** In an attention layer a resident token holds its
K and V rows: `2 · num_key_value_heads · head` values (1,024: 2,048 bytes
at bfloat16, 4,096 over the cut's 2 attention layers). In a conv layer the
session holds ONE state whatever its length; the program keeps such a
record for every page of 128 tokens (the state at the page's end, which a
session adopting the page from the prefix cache starts from): `(K - 1) ·
hidden_size` values a conv layer, 57,344 bytes over the cut's 7.

`Reference.zero_state_every` is the state control's switch
(`benchmark/control_state.py`): with a page size there, every tap that
would reach back across a multiple of it reads zero — what a program would
compute that adopted cached pages without the state at their end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import DTYPE_BYTES
from benchmark.families.latent_moe import (
    _at, _ffn, _normal, _normal_experts, _rmsnorm, _widen,
)

ATTENTION = "full_attention"
CONV = "conv"


# -- the mapping ------------------------------------------------------------

def shapes(raw: dict) -> dict:
    """The sizes this module computes with, from the published keys."""
    if raw["conv_bias"] or raw["rope_parameters"]["rope_type"] != "default":
        raise ValueError("shortconv_moe: no conv bias and plain rotary "
                         "only are written down here")
    types = list(raw["layer_types"])
    if len(types) != raw["num_hidden_layers"] \
            or set(types) - {ATTENTION, CONV}:
        raise ValueError(f"shortconv_moe: layer_types {types}")
    H = int(raw["num_attention_heads"])
    return dict(
        L=len(types), types=types, D=int(raw["hidden_size"]), H=H,
        KV=int(raw["num_key_value_heads"]),
        hd=int(raw["hidden_size"]) // H, K=int(raw["conv_L_cache"]),
        F=int(raw["intermediate_size"]), n_dense=int(raw["num_dense_layers"]),
        E=int(raw["num_experts"]), k=int(raw["num_experts_per_tok"]),
        Fe=int(raw["moe_intermediate_size"]), V=int(raw["vocab_size"]),
        bias=bool(raw["use_expert_bias"]),
        norm_topk=bool(raw["norm_topk_prob"]),
        gate_eps=float(raw["gate_normaliser_eps"]),
        routed_scale=float(raw["routed_scaling_factor"]),
        eps=float(raw["norm_eps"]),
        theta=float(raw["rope_parameters"]["rope_theta"]),
        tied=bool(raw["tie_word_embeddings"]))


def register(raw: dict) -> str:
    """Register the configuration with the program; returns its spec."""
    from quoracle_tpu.models.config import (
        ModelConfig, MoEConfig, register_model,
    )
    s = shapes(raw)
    register_model(ModelConfig(
        name=raw["name"], vocab_size=s["V"], dim=s["D"], n_layers=s["L"],
        n_heads=s["H"], n_kv_heads=s["KV"], ffn_dim=s["F"],
        head_dim=s["hd"], rope_theta=s["theta"], norm_eps=s["eps"],
        tie_embeddings=s["tied"], qk_norm=True,
        layer_types=tuple("conv" if t == CONV else "attention"
                          for t in s["types"]),
        conv_cache=s["K"],
        moe=MoEConfig(n_routed=s["E"], n_held=s["E"], per_token=s["k"],
                      expert_dim=s["Fe"], n_shared=0,
                      routed_scale=s["routed_scale"],
                      norm_topk=s["norm_topk"], first_dense=s["n_dense"],
                      router_bias=s["bias"], gate_eps=s["gate_eps"]),
        context_window=int(raw["serving"]["context_window"]),
        output_limit=int(raw["serving"]["output_limit"]),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"])))
    return f"xla:{raw['name']}"


# -- bytes and operations, from the shapes ----------------------------------

def _conv_params(s: dict) -> int:
    return s["D"] * 3 * s["D"] + s["K"] * s["D"] + s["D"] * s["D"]


def _attn_params(s: dict) -> int:
    return (2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]
            + 2 * s["hd"])


def stated_precision(raw: dict) -> dict:
    """{key of the engine's `quant_stats()`: what it has to read}: the
    bytes a resident token holds over the attention layers, and the bytes
    of one state record over the conv layers, at the stated type (module
    docstring: 4,096 and 57,344 at `lfm2-24b-a2b-l9`)."""
    s = shapes(raw)
    b = DTYPE_BYTES[raw["torch_dtype"]]
    return {"kv_bytes_per_token":
            s["types"].count(ATTENTION) * 2 * s["KV"] * s["hd"] * b,
            "state_bytes_per_record":
            s["types"].count(CONV) * (s["K"] - 1) * s["D"] * b}


def routed_expert_bytes(raw: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    s = shapes(raw)
    return 3 * s["D"] * s["Fe"] * DTYPE_BYTES[raw["torch_dtype"]]


def decode_weight_bytes(raw: dict) -> int:
    """Bytes of weights EVERY decode step has to read: a LOWER bound for
    any step the cell can run. Counted: every conv and attention operator,
    the dense layers' feed-forward, each expert layer's router, the output
    head (the embedding, tied), and of the routed experts
    `num_experts_per_tok` a layer — all experts are held here, so the one
    row a step has at least reaches that many. 692,146,688 + 8 · 4 ·
    18,874,368 = 1,296,126,464 bytes at `lfm2-24b-a2b-l9`. What the steps
    of a run did read of the experts is `kernel.routed_experts_bw_share_pct`'s,
    from the program's counter. Norms and the router's bias are left out;
    the embedding lookup reads rows, not the table."""
    s = shapes(raw)
    n_conv = s["types"].count(CONV)
    n_expert = s["L"] - s["n_dense"]
    outside = (n_conv * _conv_params(s)
               + (s["L"] - n_conv) * _attn_params(s)
               + s["n_dense"] * 3 * s["D"] * s["F"]
               + n_expert * s["D"] * s["E"] + s["V"] * s["D"])
    return (outside * DTYPE_BYTES[raw["torch_dtype"]]
            + n_expert * s["k"] * routed_expert_bytes(raw))


def decode_step_mark(raw: dict) -> dict:
    """The attention kernel's custom call, once an attention layer."""
    return {"op_pattern": "^%ragged_attend",
            "per_step": shapes(raw)["types"].count(ATTENTION)}


def routed_experts_floor_s(raw: dict, reached: float, peaks: dict) -> float:
    """The least time the grouped matmuls need for `reached` experts with
    a token (summed over layers and steps): each has to be read."""
    return reached * routed_expert_bytes(raw) / peaks["hbm_bytes_per_s"]


# A v5e core's fast memory (VMEM). The compiler keeps operands of a loop
# there from one iteration to the next where they fit: the decode
# program's `while` holds whole stacked conv weights in it (the compiled
# program marks bf16[2,2048,6144] and bf16[2,2048,2048] operands `S(1)`;
# AOT, PR 33), so after a loop's first step only what cannot fit has to
# come from HBM again.
FAST_MEMORY_BYTES = 128 * 2 ** 20


def short_conv_floor_s(raw: dict, decode_steps: float, real_tokens: float,
                       peaks: dict) -> float:
    """The least time one tick's conv operators need, all conv layers
    together. A program call has to read their weights (234,967,040 bytes
    at `lfm2-24b-a2b-l9`) and to multiply each of its tokens by `W_in` and
    `W_out` (2 · 16,777,216 operations an operator; the taps are left
    out), and takes the larger of the two. A tick calls the chunk forward
    once, over `real_tokens`, and the decode program once, whose loop
    makes a step for every token the tick emits after the first
    (`decode_steps` counts the first, which the chunk forward's logits
    give): the loop's first step reads the weights whole, and each later
    step at least what of them the fast memory cannot hold
    (`FAST_MEMORY_BYTES`: 100,749,312 bytes here). Counting every step's
    weights whole read 94% over a whole trace and 102-105% over its
    decode steps alone (my chip runs, PR 33): the bytes were counted too
    high, not the time too low."""
    s = shapes(raw)
    n_conv = s["types"].count(CONV)
    weights = n_conv * _conv_params(s) * DTYPE_BYTES[raw["torch_dtype"]]
    read = weights / peaks["hbm_bytes_per_s"]
    again = max(weights - FAST_MEMORY_BYTES, 0) / peaks["hbm_bytes_per_s"]
    flops = 2 * n_conv * (3 * s["D"] * s["D"] + s["D"] * s["D"])
    steps = max(decode_steps - 1, 0)
    return (max(read, flops * real_tokens / peaks["bf16_flops_per_s"])
            + (read + (steps - 1) * again if steps else 0.0))


# -- the plain reference ----------------------------------------------------

Q_BLOCK = 512


def plan(s: dict) -> list:
    """The three segments `[(kinds, repeats)]`, `kinds` a list of (layer
    type, has experts): the leading dense layers once, the shortest period
    of the rest as often as it fits whole, the remainder once."""
    kinds = [(t, i >= s["n_dense"]) for i, t in enumerate(s["types"])]
    lead, rest = kinds[:s["n_dense"]], kinds[s["n_dense"]:]
    p = next((p for p in range(1, len(rest) + 1)
              if all(rest[i] == rest[i + p] for i in range(len(rest) - p))),
             0)
    n = len(rest) // p if p else 0
    return [(lead, 1 if lead else 0), (rest[:p], n),
            (rest[n * p:], 1 if rest[n * p:] else 0)]


# (name, shape, fan-in) of a layer's leaves, in the order that numbers
# their keys: the operator first, then the feed-forward
def leaves_of(s: dict, kind: str, experts: bool) -> list:
    D = s["D"]
    if kind == CONV:
        leaves = [("w_in", (D, 3 * D), D), ("w_conv", (s["K"], D), s["K"]),
                  ("w_out", (D, D), D)]
    else:
        q, kv = s["H"] * s["hd"], s["KV"] * s["hd"]
        leaves = [("wq", (D, q), D), ("wk", (D, kv), D), ("wv", (D, kv), D),
                  ("wo", (q, D), q)]
    if not experts:
        return leaves + [("w_gate", (D, s["F"]), D), ("w_up", (D, s["F"]), D),
                         ("w_down", (s["F"], D), s["F"])]
    if s["bias"]:
        leaves += [("router_bias", (s["E"],), 10_000)]
    return leaves + [("router", (D, s["E"]), D),
                     ("we_gate", (D, s["Fe"]), D), ("we_up", (D, s["Fe"]), D),
                     ("we_down", (s["Fe"], D), s["Fe"])]


@functools.partial(jax.jit, static_argnames=("shape", "fan_in"))
def _normal_f32(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)


def make_weights(s: dict, seed: int) -> dict:
    """The model of `seed`: `embed` (`lm_head` where the head is not
    tied) and `segments[s][q]`, the stacked leaves of position `q`."""
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
                if leaf == "router_bias":
                    leaves[leaf] = _normal_f32(k, (n, *shape), fan_in)
                elif leaf.startswith("we_"):
                    leaves[leaf] = _normal_experts(k, 0, s["E"], (n, *shape),
                                                   fan_in)
                else:
                    leaves[leaf] = _normal(k, (n, *shape), fan_in)
            positions.append(leaves)
        w["segments"].append(positions)
    return w


@functools.partial(jax.jit, static_argnames=("axis",), donate_argnums=0)
def _q8(x, axis):
    """Symmetric int8 with one float32 scale along `axis` (a matrix: per
    output channel; the embedding: per row)."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8),
            scale)


def quantize_int8(w: dict) -> dict:
    """The control's weights: every matrix as int8 with float32 scales,
    the step below the bfloat16 the configuration states; the router's
    float32 bias stays as it is. Leaf by leaf, each bfloat16 leaf given up
    as its pair is made (an expert leaf a repeat at a time: its float32
    copy whole would not fit beside the rest)."""
    def q(x):
        if x.ndim < 4:
            return _q8(x, -2)
        parts = [_q8(x[i], -2) for i in range(x.shape[0])]
        return (jnp.stack([p[0] for p in parts]),
                jnp.stack([p[1] for p in parts]))

    out = {"embed": _q8(w.pop("embed"), -1), "segments": []}
    if "lm_head" in w:
        out["lm_head"] = _q8(w.pop("lm_head"), -2)
    for positions in w.pop("segments"):
        out["segments"].append([
            {k: (p.pop(k) if k == "router_bias" else q(p.pop(k)))
             for k in sorted(p)} for p in positions])
    return out


def _rope(x, theta):
    """x: [T, heads, d]; position t rotates pair (i, i + d/2) by t times
    theta^(-2i/d)."""
    T, _, d = x.shape
    freqs = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(s, w, x, r):
    T, H, KV, hd = x.shape[0], s["H"], s["KV"], s["hd"]
    u = _rmsnorm(x, s["eps"])
    q = _rmsnorm((u @ _at(w["wq"], r)).reshape(T, H, hd), s["eps"])
    k = _rmsnorm((u @ _at(w["wk"], r)).reshape(T, KV, hd), s["eps"])
    v = (u @ _at(w["wv"], r)).reshape(T, KV, hd)
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    kpos = jnp.arange(T)
    out = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        qpos = q0 + jnp.arange(qb.shape[0])
        sc = jnp.einsum("thd,shd->hts", qb, k) * hd ** -0.5
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, -1), v))
    a = jnp.concatenate(out, 0).reshape(T, H * hd)
    return x + a @ _at(w["wo"], r)


def _short_conv(s, w, x, r, zero_every):
    T, D, K = x.shape[0], s["D"], s["K"]
    bcx = _rmsnorm(x, s["eps"]) @ _at(w["w_in"], r)
    z = bcx[:, :D] * bcx[:, 2 * D:]
    taps = _at(w["w_conv"], r)                                # [K, D]
    t = jnp.arange(T)
    c = taps[K - 1] * z
    for back in range(1, K):
        past = jnp.pad(z, ((back, 0), (0, 0)))[:T]            # z_{t-back}
        if zero_every:
            # the state control: nothing crosses a page boundary
            past = jnp.where(((t - back) // zero_every
                              == t // zero_every)[:, None], past, 0.0)
        c = c + taps[K - 1 - back] * past
    return x + (bcx[:, D:2 * D] * c) @ _at(w["w_out"], r)


def select(scores, bias, s):
    """scores [T, E] (sigmoid), bias [E] or None -> (experts [T, k], gates
    [T, k]): the k largest of score + bias, ties to the lower index (a
    stable sort of the negated values); gates the bare scores of the
    chosen."""
    pick = scores if bias is None else scores + bias
    idx = jnp.argsort(-pick, axis=-1, stable=True)[:, :s["k"]]
    sel = jnp.take_along_axis(scores, idx, axis=-1)
    if s["norm_topk"]:
        sel = sel / (sel.sum(-1, keepdims=True) + s["gate_eps"])
    return idx, sel * s["routed_scale"]


def _experts(s, w, x, r):
    g = _rmsnorm(x, s["eps"])
    bias = _at(w["router_bias"], r) if s["bias"] else None
    idx, gates = select(jax.nn.sigmoid(g @ _at(w["router"], r)), bias, s)

    def one(e, y):
        # expert e over every token, times its gate there (zero where it
        # was not chosen)
        ge = jnp.where(idx == e, gates, 0.0).sum(-1)
        return y + ge[:, None] * _ffn(g, _at(w["we_gate"], r, e),
                                      _at(w["we_up"], r, e),
                                      _at(w["we_down"], r, e))

    return x + jax.lax.fori_loop(0, s["E"], one, jnp.zeros_like(x))


def _layer(s, w, x, r, kind, experts, zero_every):
    x = _short_conv(s, w, x, r, zero_every) if kind == CONV \
        else _attention(s, w, x, r)
    if experts:
        return _experts(s, w, x, r)
    g = _rmsnorm(x, s["eps"])
    return x + _ffn(g, _at(w["w_gate"], r), _at(w["w_up"], r),
                    _at(w["w_down"], r))


class Reference:
    """`Reference(raw, seed)`, `raw` the configuration's file as loaded;
    `logits(tokens, rows)`: the float32 logits at the given positions of
    one sequence. One compiled layer of each kind serves every layer of
    that kind and every sequence padded to the same length."""

    def __init__(self, raw: dict, seed: int):
        s = self.s = shapes(raw)
        self.w = make_weights(s, seed)
        self.zero_state_every = 0

        @functools.partial(jax.jit, static_argnums=(3, 4, 5))
        def layer(w, x, r, kind, experts, zero_every):
            with jax.default_matmul_precision("highest"):
                return _layer(s, w, x, r, kind, experts, zero_every)

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
            for r in range(n):
                for w, (kind, experts) in zip(positions, kinds):
                    x = self._layer(w, x, r, kind, experts,
                                    int(self.zero_state_every))
        head = self.w["embed" if self.s["tied"] else "lm_head"]
        return np.asarray(self._head(head, x, jnp.asarray(rows)))
