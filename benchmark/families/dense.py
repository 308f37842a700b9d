"""The dense decoder family (Mistral, Qwen2: RMSNorm, RoPE, grouped-query
attention, gated MLP): everything the harness knows of this architecture.
A configuration's file names its family (`"family": "dense"`), `configs.family`
finds this module by that name, and the runner and the readers ask it, under
the names `benchmark/README.md` fixes, for

* `register(raw)`: the one mapping from the file's published `config.json`
  keys to the program's `ModelConfig`, registered at run time through the
  public constructor and `register_model` (`models/config.py` is not edited
  for a configuration the benchmark adds); returns the spec;
* `Reference(raw, seed)`: the plain reference, below;
* `stated_precision(raw)`: what the engine's own account of its precision
  (`quant_stats()`) has to read at the type the configuration states, key by
  key: the bytes of K and V one resident token holds;
* `decode_weight_bytes(raw)`: the bytes of weights one decode step must read;
* `decode_step_mark(raw)`: what marks a decode step in the device trace.

`raw` is the configuration's file as `configs.load_config` gives it: the
published keys under their own names, with the cut applied (`reduced` names
each changed key), what the builder had to set itself under `assumed`, and
how it is served (`chips`, `serve_args`).

**The plain reference**: the architecture's forward pass in straightforward
`jax.numpy`, float32 at matmul precision "highest", with no kernel, no
cache, no paging and no batching. Written from the published description of
the family: token embedding, then per layer
RMSNorm -> q/k/v projections (optional biases) -> rotary embedding on
halves -> grouped-query attention under a causal mask and, where the
configuration has one, a sliding window of W positions (a query sees itself
and the W-1 positions before it) -> output projection -> residual ->
RMSNorm -> gated MLP, silu(x W_gate) * (x W_up) W_down -> residual; final
RMSNorm; the output head, which is the embedding matrix where the
configuration ties them.

It imports nothing of the program and takes nothing the program made (this
module imports the program nowhere but inside `register`, whose whole job is
to hand the mapping over). The weights are drawn here, from the seed, by the
rule the program's random initialisation states (a normal draw over the
square root of the fan-in, rounded to bfloat16; norms one, biases zero; one
key per stacked leaf, split from `PRNGKey(seed)` as embed / layers / head and
the layer key seven ways in the order wq wk wv wo w_gate w_up w_down), so that
the same seed gives the same model on both sides without a byte passing
between them. The weights stay in bfloat16 as they are served and are widened
layer by layer: every product and sum below is float32.

Departure from the published models: none in the mathematics. The weights
are random and the biases of a random Qwen2 are zero (the program's
initialisation), so a dropped bias would not show here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import DTYPE_BYTES


# -- the mapping ------------------------------------------------------------

def model_kwargs(raw: dict) -> dict:
    """`ModelConfig(**model_kwargs(raw))`: one field per published key."""
    heads = int(raw["num_attention_heads"])
    window = raw.get("sliding_window")
    if raw.get("use_sliding_window") is False:
        window = None
    return dict(
        name=raw["name"],
        vocab_size=int(raw["vocab_size"]),
        dim=int(raw["hidden_size"]),
        n_layers=int(raw["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(raw["num_key_value_heads"]),
        ffn_dim=int(raw["intermediate_size"]),
        head_dim=int(raw.get("head_dim") or raw["hidden_size"] // heads),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        activation=raw["hidden_act"],
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        sliding_window=None if window is None else int(window),
        attn_bias=bool(raw.get("attention_bias", False)),
        context_window=int(raw["max_position_embeddings"]),
        output_limit=int(raw.get("serving", {}).get("output_limit", 4096)),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"]),
    )


def register(raw: dict) -> str:
    """Register the configuration with the program; returns its spec."""
    from quoracle_tpu.models.config import ModelConfig, register_model
    register_model(ModelConfig(**model_kwargs(raw)))
    return f"xla:{raw['name']}"


# -- bytes, from the shapes -------------------------------------------------

def stated_precision(raw: dict) -> dict:
    """{key of the engine's `quant_stats()`: what it has to read}. One key:
    the bytes of K and V that one resident token holds over all layers, at
    the type the configuration states (`torch_dtype`)."""
    k = model_kwargs(raw)
    return {"kv_bytes_per_token": (
        2 * k["n_layers"] * k["n_kv_heads"] * k["head_dim"]
        * DTYPE_BYTES[raw["torch_dtype"]])}


def decode_weight_bytes(raw: dict) -> int:
    """Bytes of weights one decode step has to read, from the shapes alone
    at the type the configuration states: every layer's projections and
    MLP, the norms, and the output head (the embedding matrix where it is
    tied). The embedding lookup reads rows, not the table, and is left out:
    a lower bound."""
    k = model_kwargs(raw)
    d, f, hd = k["dim"], k["ffn_dim"], k["head_dim"]
    q, kv = k["n_heads"] * hd, k["n_kv_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * f + 2 * d
    if k["attn_bias"]:
        layer += q + 2 * kv
    total = k["n_layers"] * layer + d + k["vocab_size"] * d
    return total * DTYPE_BYTES[raw["torch_dtype"]]


def decode_step_mark(raw: dict) -> dict:
    """The operation that marks a decode step in the device trace, and how
    often it runs in one step: the attention kernel's custom call, once a
    layer (`describe_trace` shows the names a trace holds)."""
    return {"op_pattern": "^%ragged_attend",
            "per_step": int(raw["num_hidden_layers"])}


# -- the plain reference ----------------------------------------------------

Q_BLOCK = 512


def make_weights(cfg: dict, seed: int) -> dict:
    """The model of `seed`, as stacked bfloat16 leaves."""
    L, D, F = cfg["n_layers"], cfg["dim"], cfg["ffn_dim"]
    H, KV, HD = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    V = cfg["vocab_size"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    lk = jax.random.split(k_layers, 7)

    @functools.partial(jax.jit, static_argnames=("shape", "fan_in"))
    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(jnp.bfloat16)

    w = {
        "embed": normal(k_embed, (V, D), D),
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "final_norm": jnp.ones((D,), jnp.float32),
        "wq": normal(lk[0], (L, D, H * HD), D),
        "wk": normal(lk[1], (L, D, KV * HD), D),
        "wv": normal(lk[2], (L, D, KV * HD), D),
        "wo": normal(lk[3], (L, H * HD, D), H * HD),
        "w_gate": normal(lk[4], (L, D, F), D),
        "w_up": normal(lk[5], (L, D, F), D),
        "w_down": normal(lk[6], (L, F, D), F),
    }
    if cfg["attn_bias"]:
        w["bq"] = jnp.zeros((L, H * HD), jnp.float32)
        w["bk"] = jnp.zeros((L, KV * HD), jnp.float32)
        w["bv"] = jnp.zeros((L, KV * HD), jnp.float32)
    if not cfg["tie_embeddings"]:
        w["lm_head"] = normal(k_head, (D, V), D)
    return w


MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")


def quantize_int8(w: dict) -> dict:
    """The control's weights: every matrix as symmetric int8 with one
    float32 scale per output channel (the embedding: per row), the step
    below the bfloat16 the configurations state. Leaves become
    (int8, scale) pairs; `_widen` multiplies them out in float32."""
    @functools.partial(jax.jit, static_argnames=("axis",), donate_argnums=0)
    def q(x, axis):
        x = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return (jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8),
                scale)
    out = dict(w)
    for name in MATRICES:
        if name in out:
            out[name] = q(out.pop(name), axis=-2)
    out["embed"] = q(out.pop("embed"), axis=-1)
    return out


def _widen(leaf):
    """A weight as float32: bfloat16 widened, or int8 times its scale."""
    if isinstance(leaf, tuple):
        return leaf[0].astype(jnp.float32) * leaf[1]
    return leaf.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, hd]; position t rotates pair (i, i + hd/2) by
    t * theta^(-2i/hd)."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """q: [T, KV, G, hd]; k, v: [T, KV, hd]; all positions, in blocks of
    Q_BLOCK queries against the whole sequence."""
    T, hd = q.shape[0], q.shape[-1]
    kpos = jnp.arange(T)
    out = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        qpos = q0 + jnp.arange(qb.shape[0])
        s = jnp.einsum("tkgd,skd->kgts", qb, k) * (hd ** -0.5)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("kgts,skd->tkgd", p, v))
    return jnp.concatenate(out, 0)


def _layer(cfg, w, x, l):
    H, KV, HD = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    T = x.shape[0]
    f32 = lambda name: _widen(jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
        w[name]))
    h = _rmsnorm(x, f32("attn_norm"), cfg["norm_eps"])
    q, k, v = h @ f32("wq"), h @ f32("wk"), h @ f32("wv")
    if cfg["attn_bias"]:
        q, k, v = q + f32("bq"), k + f32("bk"), v + f32("bv")
    q = _rope(q.reshape(T, H, HD), cfg["rope_theta"])
    k = _rope(k.reshape(T, KV, HD), cfg["rope_theta"])
    a = _attention(q.reshape(T, KV, H // KV, HD), k, v.reshape(T, KV, HD),
                   cfg["sliding_window"])
    x = x + a.reshape(T, H * HD) @ f32("wo")
    h = _rmsnorm(x, f32("mlp_norm"), cfg["norm_eps"])
    return x + (jax.nn.silu(h @ f32("w_gate")) * (h @ f32("w_up"))) \
        @ f32("w_down")


class Reference:
    """`Reference(raw, seed)`, `raw` the configuration's file as loaded;
    `logits(tokens, rows)`: the float32 logits at the given positions of
    one sequence. One compiled layer serves every layer and every sequence
    padded to the same length."""

    def __init__(self, raw: dict, seed: int):
        cfg = model_kwargs(raw)
        if cfg["activation"] != "silu":
            raise ValueError(f"reference: unknown activation "
                             f"{cfg['activation']!r}")
        self.cfg = {k: cfg[k] for k in (
            "n_layers", "dim", "ffn_dim", "n_heads", "n_kv_heads",
            "head_dim", "vocab_size", "rope_theta", "norm_eps",
            "sliding_window", "attn_bias", "tie_embeddings")}
        self.w = make_weights(self.cfg, seed)
        cfg_ = self.cfg

        @jax.jit
        def layer(w, x, l):
            with jax.default_matmul_precision("highest"):
                return _layer(cfg_, w, x, l)

        @jax.jit
        def head(w, x, rows):
            with jax.default_matmul_precision("highest"):
                h = _rmsnorm(x[rows], w["final_norm"], cfg_["norm_eps"])
                m = (_widen(w["embed"]).T if cfg_["tie_embeddings"]
                     else _widen(w["lm_head"]))
                return h @ m

        self._layer, self._head = layer, head

    def lower_to_int8(self) -> None:
        """Turn this reference into the control: the same model computed
        from int8 weights (the bfloat16 leaves are given up)."""
        self.w = quantize_int8(self.w)

    def logits(self, tokens: np.ndarray, rows: np.ndarray) -> np.ndarray:
        x = _widen(jax.tree.map(lambda a: a[jnp.asarray(tokens)],
                                self.w["embed"]))
        for l in range(self.cfg["n_layers"]):
            x = self._layer(self.w, x, l)
        return np.asarray(self._head(self.w, x, jnp.asarray(rows)))
