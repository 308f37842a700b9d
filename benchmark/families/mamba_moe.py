"""The hybrid of Mamba-2 layers, attention with no positional embedding and
ungated routed experts, every layer ONE operator (the Nemotron-H form;
`model_type` `nemotron_h`): everything the harness knows of this
architecture, under the names `benchmark/README.md` fixes (`register`,
`Reference`, `stated_precision`, `decode_weight_bytes`, `decode_step_mark`),
and the counts its kernels' shares are taken from (`routed_experts_floor_s`,
`ssm_scan_floor_s`, `ssm_step_floor_s`).

`raw` is the configuration's file as `configs.load_config` gives it: the
published keys under their own names with the cut applied. The depth is cut
(`num_hidden_layers`, `hybrid_override_pattern`), the routed experts HELD
here are a share of the published ones (`n_routed_experts`: experts
`held_experts_first ..`, of the `reduced_from.n_routed_experts` the router
scores) and the vocabulary is a slice of the rows (`vocab_size`); the
published values stand under `reduced_from`. Every width is as published.

**The equations** (`x` the residual stream at a token, `n(·)` an RMSNorm,
`layer_norm_epsilon`, weight one). Layer ℓ is the kind
`hybrid_override_pattern[ℓ]` names and is ONE operator behind one norm:
`x += Op_ℓ(n(x))`. After the last layer one RMSNorm, then an untied head.

* `M`, Mamba-2 (H = `mamba_num_heads`, P = `mamba_head_dim`, d_inner = H·P
  — `expand` is read by nothing —, G = `n_groups`, N = `ssm_state_size`, K
  = `conv_kernel`; `u = n(x)`): `[z | xBC | dt] = u W_in`, widths d_inner |
  d_inner + 2·G·N | H, no bias; `xBC ← silu(Σ_{j<K} w_j ⊙ xBC_{t-K+1+j} +
  b)`: a causal depthwise convolution, one weight a channel a tap, the
  last tap on the current token, zero before the sequence's start
  (`use_conv_bias`); split `xBC → x̂ [H, P], B [G, N], C [G, N]`, head h
  reads group `h // (H/G)`; `Δ = softplus(dt + dt_bias)` a head, not
  clamped; `A = −exp(A_log)` a head. State `S_h ∈ R^{P×N}`, zero at the
  start: `S_h ← exp(Δ_h A_h) S_h + Δ_h · x̂_h ⊗ B_g`; `y_h = S_h C_g + D_h
  x̂_h`. Then `y ← y ⊙ silu(z)`, an RMSNorm over each of the G groups of
  d_inner/G values (one weight of d_inner, one here), `Op = y W_out`.
* `*`, attention: `q = u W_q` (`num_attention_heads` heads of `head_dim`),
  `k = u W_k`, `v = u W_v` (`num_key_value_heads`), no bias; a group of
  query heads a kv head; causal softmax of `q·k / sqrt(head_dim)` over all
  earlier keys; NO rotary and no other positional term (`rope_theta` and
  `partial_rotary_factor` are read by nothing); `Op = attn W_o`.
* `E`, experts: `s = sigmoid(u W_r)` in float32 over all published
  experts; the `num_experts_per_tok` of largest `s + b` are chosen (`b`
  one float32 an expert, where experts are CHOSEN only; `n_group` 1: no
  group step; ties to the lower index); gates the bare `s` of the chosen
  over (their sum + 1e-20) (`norm_topk_prob`) times
  `routed_scaling_factor`; `Op = Σ_chosen gate_e · relu(u W_1e)² W_2e` at
  `moe_intermediate_size` + the shared expert `relu(u W_1s)² W_2s` at
  `moe_shared_expert_intermediate_size`. No gate matrix (`mlp_hidden_act`
  `relu2`), no bias. Of the sum over the chosen, this share holds the
  experts it has; the others' part is another chip's.
* Embedding lookup with no scale; the head is untied.

**The reference** is float32 at matmul precision "highest", in plain
`jax.numpy` over the whole sequence: the Mamba layer as the recurrence
above, one token at a time (a `lax.scan` over positions — the program's
chunked kernel is what it checks), shifts for the taps, attention per head
in blocks of queries with a causal mask, a loop over the held experts. No
cache, no state pool, no pages, no batching. It imports nothing of the
program and takes nothing the program made (`register` alone touches the
program). Its weights are drawn here from the seed by the rule the
program's initialisation STATES (`transformer._init_params_pattern`):
`PRNGKey(seed)` split three ways, embed / layers / head; the layers fall
into three SEGMENTS — 0: leading layers with a dense feed-forward (none
here), 1: the shortest period of the rest, stacked over as many repeats as
fit whole, 2: what is left of a last period — and leaf `i` (its place in
`leaves_of`) of position `q` of segment `s` is normal/sqrt(fan-in) rounded
to bfloat16, drawn at `[repeats, ...]` from `fold_in(fold_in(fold_in(
k_layers, s), q), i)`; a routed expert's leaf per expert (its published
number) at `[repeats, ...]` from `fold_in(that key, e)` (an expert's two matrices, and the shared expert's,
both at `[F, D]`: the up matrix is stored transposed; `W_in` as its three
column blocks `w_z`, `w_xbc`, `w_dt`); the router's bias
float32, 0.01 × normal; a Mamba head's scalars float32 from a uniform `u`
in [0, 1) under the same numbered keys: `A_log = log(1 + 15 u)`, `dt_bias =
softplus⁻¹(max(exp(u · log(time_step_max / time_step_min) + log
time_step_min), time_step_floor))`, `D` one. **Memory**: the leaves stay
bfloat16 as served (8.54 GiB at `nemotron-3-nano-30b-a3b-ep2-l14`);
`run.py` frees the server's memory first, and a layer is widened one matrix
(one expert) at a time.

**What a session holds.** In an attention layer a resident token holds its
K and V rows: `2 · num_key_value_heads · head_dim` values (512: 1,024
bytes at bfloat16, 2,048 over the cut's 2 attention layers). In a Mamba
layer the session holds ONE record whatever its length: the state, H · P ·
N float32 (2 MiB), and the convolution's last K − 1 inputs, (K − 1) ·
(d_inner + 2·G·N) values at the stated type (36,864 bytes): 12,804,096
bytes over the cut's 6.

`Reference.zero_state_every` is the state control's switch
(`benchmark/control_state.py`): with a page size there, the state and the
taps are zero at every multiple of it — what a program would compute that
adopted cached pages without the record at their end.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.configs import DTYPE_BYTES
from benchmark.families.latent_moe import (
    _at, _normal, _normal_experts, _rmsnorm, _widen,
)
from benchmark.families.shortconv_moe import _normal_f32, _q8

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


# -- the mapping ------------------------------------------------------------

def shapes(raw: dict) -> dict:
    """The sizes this module computes with, from the published keys."""
    pattern = raw["hybrid_override_pattern"]
    if len(pattern) != raw["num_hidden_layers"] \
            or set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(f"mamba_moe: hybrid_override_pattern {pattern!r}")
    if raw["use_bias"] or raw["mlp_bias"] or raw["attention_bias"] \
            or not raw["use_conv_bias"] or raw["mlp_hidden_act"] != "relu2" \
            or raw["n_group"] != 1 or raw["tie_word_embeddings"]:
        raise ValueError("mamba_moe: only the published form is written "
                         "down here (no biases but the convolution's, "
                         "relu2, one router group, an untied head)")
    published = raw.get("reduced_from", {})
    H, P = int(raw["mamba_num_heads"]), int(raw["mamba_head_dim"])
    G, N = int(raw["n_groups"]), int(raw["ssm_state_size"])
    return dict(
        L=len(pattern), pattern=pattern, D=int(raw["hidden_size"]),
        H=H, P=P, G=G, N=N, K=int(raw["conv_kernel"]), DI=H * P,
        CD=H * P + 2 * G * N, Q=int(raw["chunk_size"]),
        Ha=int(raw["num_attention_heads"]),
        KV=int(raw["num_key_value_heads"]), hd=int(raw["head_dim"]),
        E=int(published.get("n_routed_experts", raw["n_routed_experts"])),
        held=int(raw["n_routed_experts"]),
        first=int(raw.get("held_experts_first", 0)),
        k=int(raw["num_experts_per_tok"]),
        Fe=int(raw["moe_intermediate_size"]),
        Fs=int(raw["moe_shared_expert_intermediate_size"])
        * int(raw["n_shared_experts"]),
        V=int(raw["vocab_size"]),
        norm_topk=bool(raw["norm_topk_prob"]), gate_eps=1e-20,
        routed_scale=float(raw["routed_scaling_factor"]),
        eps=float(raw["layer_norm_epsilon"]),
        dt=(float(raw["time_step_min"]), float(raw["time_step_max"]),
            float(raw["time_step_floor"])))


def register(raw: dict) -> str:
    """Register the configuration with the program; returns its spec."""
    from quoracle_tpu.models.config import (
        ModelConfig, MoEConfig, SSMConfig, register_model,
    )
    s = shapes(raw)
    mixer = {MAMBA: "ssm", ATTENTION: "attention", EXPERTS: None}
    register_model(ModelConfig(
        name=raw["name"], vocab_size=s["V"], dim=s["D"], n_layers=s["L"],
        n_heads=s["Ha"], n_kv_heads=s["KV"], ffn_dim=s["Fe"],
        head_dim=s["hd"], norm_eps=s["eps"], activation="relu2",
        tie_embeddings=False, rope=False,
        layer_types=tuple(mixer[c] for c in s["pattern"]),
        ff_types=tuple("experts" if c == EXPERTS else None
                       for c in s["pattern"]),
        ssm=SSMConfig(n_heads=s["H"], head_dim=s["P"], n_groups=s["G"],
                      state_dim=s["N"], conv_kernel=s["K"], chunk=s["Q"],
                      dt_min=s["dt"][0], dt_max=s["dt"][1],
                      dt_floor=s["dt"][2]),
        moe=MoEConfig(n_routed=s["E"], n_held=s["held"], per_token=s["k"],
                      expert_dim=s["Fe"], n_shared=1 if s["Fs"] else 0,
                      shared_dim=s["Fs"] or None, gated=False,
                      routed_scale=s["routed_scale"],
                      norm_topk=s["norm_topk"], first_dense=0,
                      held_start=s["first"], router_bias=True,
                      gate_eps=s["gate_eps"]),
        state_records=int(raw["serving"]["state_records"]),
        context_window=int(raw["serving"]["context_window"]),
        output_limit=int(raw["serving"]["output_limit"]),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"])))
    return f"xla:{raw['name']}"


# -- bytes and operations, from the shapes ----------------------------------

def _mamba_params(s: dict) -> int:
    """A Mamba-2 operator's matrices: in, the taps and their bias, out (the
    scalars a head and the norms are left out)."""
    return (s["D"] * (s["DI"] + s["CD"] + s["H"]) + (s["K"] + 1) * s["CD"]
            + s["DI"] * s["D"])


def _attn_params(s: dict) -> int:
    return 2 * s["D"] * s["Ha"] * s["hd"] + 2 * s["D"] * s["KV"] * s["hd"]


def record_bytes(raw: dict) -> int:
    """Bytes of one state record over the Mamba layers: the state in
    float32 and the convolution's inputs at the stated type."""
    s = shapes(raw)
    return s["pattern"].count(MAMBA) * (
        s["H"] * s["P"] * s["N"] * 4
        + (s["K"] - 1) * s["CD"] * DTYPE_BYTES[raw["torch_dtype"]])


def stated_precision(raw: dict) -> dict:
    """{key of the engine's `quant_stats()`: what it has to read}: the
    bytes a resident token holds over the attention layers, and the bytes
    of one state record over the Mamba layers (module docstring: 2,048 and
    12,804,096 at `nemotron-3-nano-30b-a3b-ep2-l14`)."""
    s = shapes(raw)
    return {"kv_bytes_per_token": s["pattern"].count(ATTENTION) * 2
            * s["KV"] * s["hd"] * DTYPE_BYTES[raw["torch_dtype"]],
            "state_bytes_per_record": record_bytes(raw)}


def routed_expert_bytes(raw: dict) -> int:
    """Bytes of one routed expert's two matrices (19,955,712 at the
    published widths)."""
    s = shapes(raw)
    return 2 * s["D"] * s["Fe"] * DTYPE_BYTES[raw["torch_dtype"]]


def decode_weight_bytes(raw: dict) -> int:
    """Bytes of weights EVERY decode step has to read: a LOWER bound for
    any step the cell can run. Counted: everything outside the routed
    experts — every Mamba and attention operator, each expert layer's
    router and shared expert, the output head (the embedding lookup reads
    rows, not the table) — and of the routed experts NOTHING: a row's
    `num_experts_per_tok` choices fall on all published experts and this
    share holds half of them, so a step whose rows chose only the other
    chip's experts reads none (at most `num_experts_per_tok` a layer a row
    otherwise). What the steps of a run did read of the experts is
    `kernel.routed_experts_bw_share_pct`'s, from the program's counter.
    Norms and scalars a head are left out."""
    s = shapes(raw)
    n_e = s["pattern"].count(EXPERTS)
    outside = (s["pattern"].count(MAMBA) * _mamba_params(s)
               + s["pattern"].count(ATTENTION) * _attn_params(s)
               + n_e * (s["D"] * s["E"] + 2 * s["D"] * s["Fs"])
               + s["V"] * s["D"])
    return outside * DTYPE_BYTES[raw["torch_dtype"]]


def decode_step_mark(raw: dict) -> dict:
    """The attention kernel's custom call, once an attention layer."""
    return {"op_pattern": "^%ragged_attend",
            "per_step": shapes(raw)["pattern"].count(ATTENTION)}


def routed_experts_floor_s(raw: dict, reached: float, peaks: dict) -> float:
    """The least time the grouped matmuls need for `reached` experts with
    a token (summed over layers and steps): each has to be read."""
    return reached * routed_expert_bytes(raw) / peaks["hbm_bytes_per_s"]


def ssm_scan_ops_bytes(raw: dict, chunks: float) -> tuple:
    """(operations, bytes) of the chunk scan kernel (`ssm_scan`) over
    `chunks` chunks of `chunk_size` tokens in ONE Mamba layer. Operations:
    a chunk of a group computes `B·Cᵀ` (2·Q·Q·N), and each of its heads
    the product within the chunk (2·Q·Q·P), the state's part of the output
    (2·Q·N·P) and the chunk's part of the state (2·Q·P·N). Bytes: a chunk
    reads Δ·x̂ and writes y (Q · d_inner values each at the stated type),
    reads B and C (2·Q·G·N), four float32 rows of decays a head (3·Q + N)
    and writes the state after it (H·P·N float32); a row's initial state,
    read once a row, is left out."""
    s = shapes(raw)
    Q, b = s["Q"], DTYPE_BYTES[raw["torch_dtype"]]
    ops = s["G"] * 2 * Q * Q * s["N"] + s["H"] * (
        2 * Q * Q * s["P"] + 2 * 2 * Q * s["P"] * s["N"])
    byts = (2 * Q * s["DI"] * b + 2 * Q * s["G"] * s["N"] * b
            + s["H"] * (3 * Q + s["N"]) * 4 + s["H"] * s["P"] * s["N"] * 4)
    return chunks * ops, chunks * byts


def ssm_scan_floor_s(raw: dict, chunks: float, peaks: dict) -> float:
    """The least time the scan kernel needs in one chunk forward whose
    rows' tokens fill `chunks` chunks (the tick's `ssm_scan_chunks`: a
    row's last chunk counts whole, the layout's unused chunks do not),
    every Mamba layer's call together: the larger of its operations at
    the matmul peak and its bytes at the memory's."""
    ops, byts = ssm_scan_ops_bytes(raw, chunks)
    return shapes(raw)["pattern"].count(MAMBA) * max(
        ops / peaks["bf16_flops_per_s"], byts / peaks["hbm_bytes_per_s"])


# A v5e core's fast memory (VMEM): the compiler keeps operands of the
# decode loop there from one step to the next where they fit, so after a
# loop's first step only what cannot fit has to come from HBM again (as
# `shortconv_moe.short_conv_floor_s` found for LFM2's conv weights).
FAST_MEMORY_BYTES = 128 * 2 ** 20


def ssm_step_floor_s(raw: dict, decode_steps: float, row_steps: float,
                     peaks: dict) -> float:
    """The least time the decode recurrence of ALL Mamba layers needs in
    one tick's decode program: its loop makes a step for every token the
    tick emits after the first (`decode_steps` counts the first, which the
    chunk forward's logits give), and the rows' forwards in it are
    `row_steps` in all. The loop's first step reads every Mamba
    operator's matrices whole (6 x 77.5 MB at
    `nemotron-3-nano-30b-a3b-ep2-l14`), each later step at least what of
    them the fast memory cannot hold; and a row's record (12,804,096
    bytes) is read and written once a forward."""
    s = shapes(raw)
    weights = s["pattern"].count(MAMBA) * _mamba_params(s) \
        * DTYPE_BYTES[raw["torch_dtype"]]
    steps = max(decode_steps - 1, 0)
    again = max(weights - FAST_MEMORY_BYTES, 0)
    read = (weights + (steps - 1) * again) if steps else 0.0
    return (read + row_steps * 2 * record_bytes(raw)) \
        / peaks["hbm_bytes_per_s"]


# -- the plain reference ----------------------------------------------------

Q_BLOCK = 512


def plan(s: dict) -> list:
    """The three segments `[(kinds, repeats)]`, `kinds` the pattern's
    letters: leading dense layers (none: every feed-forward part here is
    the experts') once, the shortest period of the rest as often as it
    fits whole, the remainder once."""
    rest = list(s["pattern"])
    p = next((p for p in range(1, len(rest) + 1)
              if all(rest[i] == rest[i + p] for i in range(len(rest) - p))),
             0)
    n = len(rest) // p if p else 0
    return [([], 0), (rest[:p], n), (rest[n * p:], 1 if rest[n * p:] else 0)]


# (name, shape, fan-in or rule) of a layer's leaves, in the order that
# numbers their keys
def leaves_of(s: dict, kind: str) -> list:
    D = s["D"]
    if kind == MAMBA:
        return [("w_z", (D, s["DI"]), D), ("w_xbc", (D, s["CD"]), D),
                ("w_dt", (D, s["H"]), D),
                ("w_conv", (s["K"], s["CD"]), s["K"]),
                ("b_conv", (s["CD"],), s["K"]), ("a_log", (s["H"],), "a_log"),
                ("dt_bias", (s["H"],), "dt_bias"), ("d_skip", (s["H"],), "one"),
                ("w_out", (s["DI"], D), s["DI"])]
    if kind == ATTENTION:
        q, kv = s["Ha"] * s["hd"], s["KV"] * s["hd"]
        return [("wq", (D, q), D), ("wk", (D, kv), D), ("wv", (D, kv), D),
                ("wo", (q, D), q)]
    leaves = [("router_bias", (s["E"],), 10_000), ("router", (D, s["E"]), D),
              ("we_up", (s["Fe"], D), D), ("we_down", (s["Fe"], D), s["Fe"])]
    if s["Fs"]:
        leaves += [("ws_up", (s["Fs"], D), D), ("ws_down", (s["Fs"], D),
                                                s["Fs"])]
    return leaves


@functools.partial(jax.jit, static_argnames=("shape", "rule", "dt"))
def _head_scalars(key, shape, rule, dt):
    if rule == "one":
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if rule == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    lo, hi, floor = dt
    step = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo))
                               + math.log(lo)), floor)
    return step + jnp.log(-jnp.expm1(-step))


def make_weights(s: dict, seed: int) -> dict:
    """The model of `seed`: `embed`, `lm_head` and `segments[s][q]`, the
    stacked leaves of position `q`."""
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = {"embed": _normal(k_embed, (s["V"], s["D"]), s["D"]),
         "lm_head": _normal(k_head, (s["D"], s["V"]), s["D"]),
         "segments": []}
    for si, (kinds, n) in enumerate(plan(s)):
        positions = []
        for q, kind in enumerate(kinds if n else []):
            kq = jax.random.fold_in(jax.random.fold_in(k_layers, si), q)
            leaves = {}
            for i, (leaf, shape, fan_in) in enumerate(leaves_of(s, kind)):
                k = jax.random.fold_in(kq, i)
                if isinstance(fan_in, str):
                    leaves[leaf] = _head_scalars(k, (n, *shape), fan_in,
                                                 s["dt"])
                elif leaf == "router_bias":
                    leaves[leaf] = _normal_f32(k, (n, *shape), fan_in)
                elif leaf.startswith("we_"):
                    leaves[leaf] = _normal_experts(
                        k, s["first"], s["held"], (n, *shape), fan_in)
                else:
                    leaves[leaf] = _normal(k, (n, *shape), fan_in)
            positions.append(leaves)
        w["segments"].append(positions)
    return w


FLOAT32_LEAVES = ("router_bias", "a_log", "dt_bias", "d_skip")


def quantize_int8(w: dict) -> dict:
    """The control's weights: every matrix as int8 with float32 scales,
    the step below the bfloat16 the configuration states; the float32
    leaves (the router's bias, a Mamba head's scalars) stay as they are.
    Leaf by leaf, each bfloat16 leaf given up as its pair is made (an
    expert leaf a repeat at a time)."""
    def q(x):
        if x.ndim < 4:
            return _q8(x, -2 if x.ndim > 2 else -1)
        parts = [_q8(x[i], -2) for i in range(x.shape[0])]
        return (jnp.stack([p[0] for p in parts]),
                jnp.stack([p[1] for p in parts]))

    out = {"embed": _q8(w.pop("embed"), -1),
           "lm_head": _q8(w.pop("lm_head"), -2), "segments": []}
    for positions in w.pop("segments"):
        out["segments"].append([
            {k: (p.pop(k) if k in FLOAT32_LEAVES else q(p.pop(k)))
             for k in sorted(p)} for p in positions])
    return out


def _attention(s, w, x, r):
    T, H, KV, hd = x.shape[0], s["Ha"], s["KV"], s["hd"]
    u = _rmsnorm(x, s["eps"])
    q = (u @ _at(w["wq"], r)).reshape(T, H, hd)
    k = (u @ _at(w["wk"], r)).reshape(T, KV, hd)
    v = (u @ _at(w["wv"], r)).reshape(T, KV, hd)
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


def _gate(y, z):
    """The gate of a Mamba layer's norm: `y ⊙ silu(z)`."""
    return y * jax.nn.silu(z)


def _mamba(s, w, x, r, zero_every, state_dtype=jnp.float32):
    """The recurrence, a token at a time. `state_dtype`: the type the
    state is ROUNDED to after every token (float32: as stated; a test's
    control keeps it in bfloat16)."""
    T, H, P, G, N, K = x.shape[0], s["H"], s["P"], s["G"], s["N"], s["K"]
    DI, CD = s["DI"], s["CD"]
    u = _rmsnorm(x, s["eps"])
    z, xbc, dt = (u @ _at(w[k], r) for k in ("w_z", "w_xbc", "w_dt"))
    taps = _at(w["w_conv"], r)                                # [K, CD]
    t = jnp.arange(T)
    c = taps[K - 1] * xbc + _at(w["b_conv"], r)
    for back in range(1, K):
        past = jnp.pad(xbc, ((back, 0), (0, 0)))[:T]          # xBC_{t-back}
        if zero_every:
            # the state control: nothing crosses a page boundary
            past = jnp.where(((t - back) // zero_every
                              == t // zero_every)[:, None], past, 0.0)
        c = c + taps[K - 1 - back] * past
    c = jax.nn.silu(c)
    xh = c[:, :DI].reshape(T, H, P)
    B = jnp.repeat(c[:, DI:DI + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(c[:, DI + G * N:].reshape(T, G, N), H // G, axis=1)
    delta = jax.nn.softplus(dt + _at(w["dt_bias"], r))        # [T, H]
    A = -jnp.exp(_at(w["a_log"], r))
    keep = jnp.ones((T,), jnp.float32) if not zero_every \
        else (t % zero_every != 0).astype(jnp.float32)

    def step(S, tok):
        xt, Bt, Ct, dl, kp = tok
        S = jnp.exp(dl * A)[:, None, None] * (S * kp) \
            + (dl[:, None] * xt)[:, :, None] * Bt[:, None, :]
        S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hpn,hn->hp", S, Ct)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xh, B, C, delta, keep))
    y = y + _at(w["d_skip"], r)[:, None] * xh
    y = _gate(y.reshape(T, DI), z).reshape(T, G, DI // G)
    y = _rmsnorm(y, s["eps"]).reshape(T, DI)
    return x + y @ _at(w["w_out"], r)


def select(scores, bias, s):
    """scores [T, E] (sigmoid), bias [E] -> (experts [T, k], gates [T,
    k]): the k largest of score + bias, ties to the lower index (a stable
    sort of the negated values); gates the bare scores of the chosen."""
    idx = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :s["k"]]
    sel = jnp.take_along_axis(scores, idx, axis=-1)
    if s["norm_topk"]:
        sel = sel / (sel.sum(-1, keepdims=True) + s["gate_eps"])
    return idx, sel * s["routed_scale"]


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _experts(s, w, x, r):
    act = _relu2
    g = _rmsnorm(x, s["eps"])
    idx, gates = select(jax.nn.sigmoid(g @ _at(w["router"], r)),
                        _at(w["router_bias"], r), s)

    def one(e, y):
        # held expert e (published number first + e) over every token,
        # times its gate there (zero where it was not chosen)
        ge = jnp.where(idx == s["first"] + e, gates, 0.0).sum(-1)
        return y + ge[:, None] * (act(g @ _at(w["we_up"], r, e).T)
                                  @ _at(w["we_down"], r, e))

    y = jax.lax.fori_loop(0, s["held"], one, jnp.zeros_like(x))
    if s["Fs"]:
        y = y + act(g @ _at(w["ws_up"], r).T) @ _at(w["ws_down"], r)
    return x + y


def _layer(s, w, x, r, kind, zero_every):
    if kind == MAMBA:
        return _mamba(s, w, x, r, zero_every)
    if kind == ATTENTION:
        return _attention(s, w, x, r)
    return _experts(s, w, x, r)


class Reference:
    """`Reference(raw, seed)`, `raw` the configuration's file as loaded;
    `logits(tokens, rows)`: the float32 logits at the given positions of
    one sequence. One compiled layer of each kind serves every layer of
    that kind and every sequence padded to the same length."""

    def __init__(self, raw: dict, seed: int):
        s = self.s = shapes(raw)
        self.w = make_weights(s, seed)
        self.zero_state_every = 0

        @functools.partial(jax.jit, static_argnums=(3, 4))
        def layer(w, x, r, kind, zero_every):
            with jax.default_matmul_precision("highest"):
                return _layer(s, w, x, r, kind, zero_every)

        @jax.jit
        def head(w, x, rows):
            with jax.default_matmul_precision("highest"):
                return _rmsnorm(x[rows], s["eps"]) @ _widen(w)

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
                for w, kind in zip(positions, kinds):
                    x = self._layer(w, x, r, kind,
                                    int(self.zero_state_every))
        return np.asarray(self._head(self.w["lm_head"], x,
                                     jnp.asarray(rows)))
