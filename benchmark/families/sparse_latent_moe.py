"""The latent-attention, routed-expert decoder with a LEARNED SPARSE
selection inside its attention and a corrected router (the DeepSeek-V3.2
form: `model_type` `deepseek_v32`): everything the harness knows of this
architecture, under the names `benchmark/README.md` fixes (`register`,
`Reference`, `stated_precision`, `decode_weight_bytes`, `decode_step_mark`),
and the counts its kernels' roofline shares are taken from
(`routed_experts_floor_s`, `index_scores_floor_s`, `sparse_attn_floor_s`).

It is `latent_moe.py`'s family with two additions, and everything that
module's docstring states of latent attention, YaRN, the dense and the
expert feed-forward, the held experts (`n_routed_experts` counts the
experts HELD, `reduced_from` has the router's width), the weights' rule
and the stored row holds here. That module refuses `topk_method` other
than `none` and knows no indexer; it may not be edited, so this one
imports the pieces of it that are the same arithmetic (`_rmsnorm`, `_rope`,
`_ffn`, the draws, the int8 lowering) and writes the rest.

**The additions** (`h` the attention's normed input, `c_q` its normed
query latent, `s <= t` the positions query `t` may see):

* The indexer. `q^I_t = c_q W_Iq`: `index_n_heads` heads of
  `index_head_dim`. `k^I_s = LayerNorm(h_s W_Ik)`: ONE key a token (mean
  subtracted, eps 1e-6, weight one, bias zero). Rotary, the model's own
  frequencies, on the FIRST `qk_rope_head_dim` values of each. `w_t = h_t
  W_Iw · heads^-1/2 · head_dim^-1/2`. Index score `I_ts = Σ_j w_tj ·
  ReLU(q^I_tj · k^I_s)`. `S_t` = the `min(t + 1, index_topk)` visible
  positions of largest `I_ts`, ties to the lower position. The softmax of
  query `t` runs over `S_t` only. Published: FP8 after a Hadamard rotation
  of `q^I` and `k^I`; the rotation is orthogonal and changes no product,
  so it is left out, and the configuration states bfloat16 (`assumed`).
* The router's correction bias (`topk_method` `noaux_tc`): groups and
  experts are CHOSEN by `s + b` (`b` one float32 a published expert; a
  group's score the sum of its two largest `s + b`); the gates are the
  bare `s` of the chosen, normalised, times `routed_scaling_factor`.

**The reference** is float32 at matmul precision "highest": unfolded
attention per head with the selection as a MASK (no gather), index scores
and their top-k in float32. Weights from the seed by the rule
`transformer._init_params_stacks` states; the indexer's leaves (`wi_q`,
`wi_k`, `wi_w`) and, in the expert stack, `router_bias` (float32, 0.01 ×
normal) come after the attention's five, before the feed-forward's.
**Memory**: a cell's contexts reach 14k tokens and the reference pads to
16,384 beside 9.07 GiB of bfloat16 leaves, so a layer never holds a
[heads, T, T] array: the selection is made in blocks of 128 queries (its
mask, [T, T] bool, is 256 MiB), attention runs 8 heads at a time over
blocks of 512 queries.

**A resident token** holds `c_kv ‖ k_r` (576 values, stored in 640 lanes)
and its index key (128): 1,536 stored bytes a layer at bfloat16, 7,680
over 5 layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import DTYPE_BYTES
from benchmark.families import latent_moe
from benchmark.families.latent_moe import (
    _at, _ffn, _mscale, _normal, _normal_experts, _rmsnorm, _rope,
    _widen,
)


# -- the mapping ------------------------------------------------------------

def _unbiased(raw: dict) -> dict:
    """`raw` as `latent_moe`'s functions take it (they refuse a router
    bias, which none of what is asked of them here depends on)."""
    return {**raw, "topk_method": "none"}


def shapes(raw: dict) -> dict:
    """The sizes this module computes with, from the published keys."""
    if raw["scoring_func"] != "sigmoid" or raw["topk_method"] != "noaux_tc":
        raise ValueError("sparse_latent_moe: sigmoid scores with the "
                         "noaux_tc correction bias only")
    s = latent_moe.shapes(_unbiased(raw))
    s.update(ix_heads=int(raw["index_n_heads"]),
             ix_dim=int(raw["index_head_dim"]),
             ix_topk=int(raw["index_topk"]))
    return s


def register(raw: dict) -> str:
    """Register the configuration with the program; returns its spec."""
    from quoracle_tpu.models.config import (
        IndexerConfig, LatentConfig, ModelConfig, MoEConfig, register_model,
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
                      held_start=s["first"], router_bias=True),
        indexer=IndexerConfig(n_heads=s["ix_heads"], head_dim=s["ix_dim"],
                              topk=s["ix_topk"], rope_dim=s["rope"]),
        context_window=int(raw["serving"]["context_window"]),
        output_limit=int(raw["serving"]["output_limit"]),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"])))
    return f"xla:{raw['name']}"


# -- bytes and operations, from the shapes ----------------------------------

def stated_precision(raw: dict) -> dict:
    """{key of the engine's `quant_stats()`: what it has to read}: the
    STORED bytes of one resident token over all layers at the stated type,
    the latent row's lanes and the index key's (7,680 at
    `deepseek-v3.2-ep16-l5`)."""
    s = shapes(raw)
    return {"kv_bytes_per_token": s["L"] * (
        latent_moe.stored_lanes(_unbiased(raw)) + s["ix_dim"])
        * DTYPE_BYTES[raw["torch_dtype"]]}


def _indexer_params(s: dict) -> int:
    return (s["q_rank"] * s["ix_heads"] * s["ix_dim"] + s["D"] * s["ix_dim"]
            + s["D"] * s["ix_heads"] + 2 * s["ix_dim"])


def decode_weight_bytes(raw: dict) -> int:
    """Bytes of weights EVERY decode step has to read, as
    `latent_moe.decode_weight_bytes` counts them (none of the routed
    experts; not the embedding), with each layer's indexer and each expert
    layer's correction bias."""
    s = shapes(raw)
    attn = latent_moe._attn_params(s) + _indexer_params(s)
    dense = attn + 3 * s["D"] * s["F"] + 2 * s["D"]
    expert = (attn + s["D"] * s["E"] + s["E"]
              + 3 * s["D"] * s["Fe"] * s["shared"] + 2 * s["D"])
    total = (s["n_dense"] * dense + (s["L"] - s["n_dense"]) * expert
             + s["D"] + s["V"] * s["D"])
    return total * DTYPE_BYTES[raw["torch_dtype"]]


def decode_step_mark(raw: dict) -> dict:
    """The attention kernel's custom call (`ragged_attend_latent`), once a
    layer of either kind; the scoring kernel is `index_scores`, which the
    pattern does not match."""
    return {"op_pattern": "^%ragged_attend",
            "per_step": int(raw["num_hidden_layers"])}


def routed_experts_floor_s(raw: dict, reached: float, peaks: dict) -> float:
    """As `latent_moe.routed_experts_floor_s`: each held expert a step
    reaches has to be read."""
    return latent_moe.routed_experts_floor_s(_unbiased(raw), reached, peaks)


def index_scores_floor_s(raw: dict, kv_reads: float, pairs: float,
                         peaks: dict) -> float:
    """The least time the scoring kernel needs for `kv_reads` index keys
    streamed (each row's context once a step) and `pairs` query-key pairs
    scored, in ALL layers: the larger of the keys' bytes over the memory
    bandwidth and `2 · heads · head_dim` operations a pair over the peak."""
    s = shapes(raw)
    byts = kv_reads * s["L"] * s["ix_dim"] * DTYPE_BYTES[raw["torch_dtype"]]
    flops = pairs * s["L"] * 2 * s["ix_heads"] * s["ix_dim"]
    return max(byts / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


def sparse_attn_floor_s(raw: dict, selected_pairs: float,
                        peaks: dict) -> float:
    """The least time the latent attention needs for `selected_pairs`
    query-key pairs INSIDE the selections, in ALL layers, each priced as
    `latent_moe.latent_attn_floor_s` prices a pair and a stored row: a
    selected row's bytes are read (once a query: no two queries are known
    to share a selection) and every head attends it. A kernel that walks
    all visible pages with a mask is measured against this same floor, so
    it reads as far from it as it is."""
    return latent_moe.latent_attn_floor_s(_unbiased(raw), selected_pairs,
                                          selected_pairs, peaks)


# -- the plain reference ----------------------------------------------------

SELECT_BLOCK = 128        # queries whose index scores are held at once
Q_BLOCK = 512             # queries attended at once ...
HEAD_BLOCK = 8            # ... by this many heads


def leaves_of(s: dict, experts: bool) -> list:
    """`latent_moe.leaves_of` with the indexer's leaves and, in the expert
    stack, the router's bias after the attention's five."""
    base = latent_moe.leaves_of(s, experts)
    D = s["D"]
    more = [("wi_q", (s["q_rank"], s["ix_heads"] * s["ix_dim"]),
             s["q_rank"]),
            ("wi_k", (D, s["ix_dim"]), D), ("wi_w", (D, s["ix_heads"]), D)]
    if experts:
        more.append(("router_bias", (s["E"],), 10_000))
    return base[:5] + more + base[5:]


@functools.partial(jax.jit, static_argnames=("shape", "fan_in"))
def _normal32(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)


def make_weights(s: dict, seed: int) -> dict:
    """The model of `seed`, as `latent_moe.make_weights` draws it, over
    this family's leaves; `router_bias` stays float32."""
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
            if leaf == "router_bias":
                leaves[leaf] = _normal32(k, (n, *shape), fan_in)
            elif leaf.startswith("we_"):
                leaves[leaf] = _normal_experts(k, s["first"], s["held"],
                                               (n, *shape), fan_in)
            else:
                leaves[leaf] = _normal(k, (n, *shape), fan_in)
        w[name] = leaves
    return w


def index_parts(s, w, h, cq, l):
    """The indexer's queries [T, heads, d], keys [T, d] and head weights
    [T, heads] of a whole sequence."""
    T = h.shape[0]
    q = (cq @ _at(w["wi_q"], l)).reshape(T, s["ix_heads"], s["ix_dim"])
    k = h @ _at(w["wi_k"], l)
    k = k - k.mean(-1, keepdims=True)
    k = (k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + 1e-6))[:, None]
    r = s["rope"]
    q = jnp.concatenate([_rope(q[..., :r], s), q[..., r:]], -1)
    k = jnp.concatenate([_rope(k[..., :r], s), k[..., r:]], -1)[:, 0]
    wt = (h @ _at(w["wi_w"], l)) * (s["ix_heads"] ** -0.5
                                    * s["ix_dim"] ** -0.5)
    return q, k, wt


def selection(s, q, k, wt):
    """[T, T] bool: where query t attends. Blocks of SELECT_BLOCK queries:
    the index scores of a block against every position in float32, the
    `index_topk` largest among the visible ones (`lax.top_k`: equal scores
    to the lower position), as a mask."""
    T = q.shape[0]
    K = min(s["ix_topk"], T)
    B = min(SELECT_BLOCK, T)
    pos = jnp.arange(T)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, B)
        wb = jax.lax.dynamic_slice_in_dim(wt, q0, B)
        sc = jnp.einsum("ths,th->ts", jax.nn.relu(
            jnp.einsum("thd,sd->ths", qb, k)), wb)
        visible = pos[None, :] <= (q0 + jnp.arange(B))[:, None]
        sc = jnp.where(visible, jnp.where(sc == 0, 0.0, sc), -jnp.inf)
        idx = jax.lax.top_k(sc, K)[1]
        chosen = jnp.zeros((B, T), bool).at[
            jnp.arange(B)[:, None], idx].set(True)
        return chosen & visible

    return jax.lax.map(block, jnp.arange(0, T, B)).reshape(T, T)


def _attention(s, w, x, l, select: bool):
    """Latent attention, unfolded, the softmax of each query over its
    selection (`select` False: over all it may see, the dense layer)."""
    T, H = x.shape[0], s["H"]
    h = _rmsnorm(x, s["eps"])
    cq = _rmsnorm(h @ _at(w["wq_a"], l), s["eps"])
    pos = jnp.arange(T)
    if select:
        mask = selection(s, *index_parts(s, w, h, cq, l))
    else:
        mask = pos[None, :] <= pos[:, None]
    ckv = h @ _at(w["wkv_a"], l)
    c = _rmsnorm(ckv[:, :s["kv_rank"]], s["eps"])
    k_r = _rope(ckv[:, None, s["kv_rank"]:], s)               # [T, 1, rope]
    factor, mscale_all = s["yarn"][0], s["yarn"][5]
    scale = (s["nope"] + s["rope"]) ** -0.5 * _mscale(factor, mscale_all) ** 2
    G, B = min(HEAD_BLOCK, H), min(Q_BLOCK, T)
    qk, kv = s["nope"] + s["rope"], s["nope"] + s["v"]
    wq = _at(w["wq_b"], l).reshape(-1, H, qk)
    wkv = _at(w["wkv_b"], l).reshape(-1, H, kv)

    def heads(g0):
        q = jnp.einsum("tr,rhd->thd", cq,
                       jax.lax.dynamic_slice_in_dim(wq, g0, G, 1))
        q = jnp.concatenate([q[..., :s["nope"]],
                             _rope(q[..., s["nope"]:], s)], -1)
        ckb = jnp.einsum("tr,rhd->thd", c,
                         jax.lax.dynamic_slice_in_dim(wkv, g0, G, 1))
        k = jnp.concatenate([ckb[..., :s["nope"]],
                             jnp.broadcast_to(k_r, (T, G, s["rope"]))], -1)
        v = ckb[..., s["nope"]:]

        def block(q0):
            sc = jnp.einsum("thd,shd->hts",
                            jax.lax.dynamic_slice_in_dim(q, q0, B), k) * scale
            sc = jnp.where(jax.lax.dynamic_slice_in_dim(mask, q0, B)[None],
                           sc, -jnp.inf)
            return jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, -1), v)

        return jax.lax.map(block, jnp.arange(0, T, B)).reshape(
            T, G, s["v"])

    a = jax.lax.map(heads, jnp.arange(0, H, G))               # [H/G,T,G,v]
    a = a.transpose(1, 0, 2, 3).reshape(T, H * s["v"])
    return x + a @ _at(w["wo"], l)


def select_experts(scores, bias, s):
    """scores [T, E] (sigmoid), bias [E] -> (experts [T, k], gates [T, k]):
    chosen by `scores + bias`, ties to the lower index (a stable sort of
    the negated values); gates from the bare scores."""
    T, E = scores.shape
    pick = scores + bias
    g = pick.reshape(T, s["n_group"], E // s["n_group"])
    group = (-jnp.sort(-g, axis=-1, stable=True)[..., :2]).sum(-1)
    best = jnp.argsort(-group, axis=-1, stable=True)[:, :s["topk_group"]]
    stays = jnp.zeros((T, s["n_group"]), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    inside = jnp.where(stays[:, :, None], g, -jnp.inf).reshape(T, E)
    idx = jnp.argsort(-inside, axis=-1, stable=True)[:, :s["k"]]
    sel = jnp.take_along_axis(scores, idx, axis=-1)
    if s["norm_topk"]:
        sel = sel / (sel.sum(-1, keepdims=True) + 1e-20)
    return idx, sel * s["routed_scale"]


def _dense_layer(s, w, x, l, select):
    x = _attention(s, w, x, l, select)
    h = _rmsnorm(x, s["eps"])
    return x + _ffn(h, _at(w["w_gate"], l), _at(w["w_up"], l),
                    _at(w["w_down"], l))


def _expert_layer(s, w, x, l, select):
    x = _attention(s, w, x, l, select)
    h = _rmsnorm(x, s["eps"])
    idx, gates = select_experts(
        jax.nn.sigmoid(h @ _at(w["router"], l)), _at(w["router_bias"], l), s)
    y = _ffn(h, _at(w["ws_gate"], l), _at(w["ws_up"], l),
             _at(w["ws_down"], l))

    def held(e, y):
        g = jnp.where(idx == s["first"] + e, gates, 0.0).sum(-1)
        return y + g[:, None] * _ffn(h, _at(w["we_gate"], l, e),
                                     _at(w["we_up"], l, e),
                                     _at(w["we_down"], l, e))

    return x + jax.lax.fori_loop(0, s["held"], held, y)


class Reference:
    """`Reference(raw, seed)`; `logits(tokens, rows)`: the float32 logits
    at the given positions of one sequence. `select = False` turns the
    learned selection off (every query attends all it may see): the
    builder's second control, which the cell's limits have to refuse."""

    def __init__(self, raw: dict, seed: int):
        s = self.s = shapes(raw)
        self.w = make_weights(s, seed)
        self.select = True

        def layer(fn):
            @functools.partial(jax.jit, static_argnames=("select",))
            def run(w, x, l, select):
                with jax.default_matmul_precision("highest"):
                    return fn(s, w, x, l, select)
            return run

        @jax.jit
        def head(w, x, rows):
            with jax.default_matmul_precision("highest"):
                return _rmsnorm(x[rows], s["eps"]) @ _widen(w)

        self._dense, self._expert = layer(_dense_layer), layer(_expert_layer)
        self._head = head

    def lower_to_int8(self) -> None:
        """Turn this reference into the control: the same model computed
        from int8 weights (the bfloat16 leaves are given up; the router's
        float32 bias is no matrix and stays)."""
        bias = self.w["experts"].pop("router_bias")
        self.w = latent_moe.quantize_int8(self.w)
        self.w["experts"]["router_bias"] = bias

    def logits(self, tokens: np.ndarray, rows: np.ndarray) -> np.ndarray:
        x = _widen(jax.tree.map(lambda a: a[jnp.asarray(tokens)],
                                self.w["embed"]))
        for l in range(self.s["n_dense"]):
            x = self._dense(self.w["dense"], x, l, select=self.select)
        for l in range(self.s["L"] - self.s["n_dense"]):
            x = self._expert(self.w["experts"], x, l, select=self.select)
        return np.asarray(self._head(self.w["lm_head"], x,
                                     jnp.asarray(rows)))
