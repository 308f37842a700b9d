"""Model catalog: architecture configs + serving metadata.

Replaces the reference's LLMDB catalog (reference
lib/quoracle/models/llm_db_model_loader.ex) — context windows, output limits and
pricing lived in an external hex package there; here the catalog is the single
in-tree registry of models the TPU runtime can serve, keyed by the same
``provider:model`` spec format the reference uses (reference
lib/quoracle/models/local_model_helper.ex:13-19 is the precedent for an in-tree
provider bypass; ours is the ``xla:`` provider).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# Minimum room a consensus round must leave for the response (reference
# per_model_query.ex:17-18 — 4096 output floor). Effective per-model floor is
# min(OUTPUT_FLOOR, output_limit); shared by TPUBackend.query and
# TokenManager.dynamic_max_tokens so both layers agree on when a history
# "fits".
OUTPUT_FLOOR = 4096


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Latent attention (MLA, the DeepSeek-V2 form): queries through a
    low-rank bottleneck, keys and values up-projected from ONE compressed
    latent per token. A resident token holds ``kv_rank`` latent values and
    ``rope_dim`` rotary key values a layer, shared by every head; the
    per-head keys and values are never stored (the serving path folds the
    up-projections into the query and the output)."""

    q_rank: int          # q_lora_rank
    kv_rank: int         # kv_lora_rank
    nope_dim: int        # qk_nope_head_dim
    rope_dim: int        # qk_rope_head_dim
    v_dim: int           # v_head_dim

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def lanes(self) -> int:
        """Lanes a resident token takes in a layer's page as STORED: the
        latent and the rotary key side by side, rounded up to the TPU's
        128-lane tile (a minor dimension of 576 is padded to 640 in HBM
        whether or not the program says so; the pad lanes hold zeros)."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class IndexerConfig:
    """Learned sparse attention (the DeepSeek-V3.2 form) beside latent
    attention: every query scores all of its visible keys through a second,
    cheap attention — ``n_heads`` heads of ``head_dim`` from the query
    latent against ONE index key a token, weighted per head and summed
    after a ReLU — and the latent attention's softmax then runs over the
    ``topk`` best-scoring positions only. A resident token holds its index
    key beside its latent row, in a pool of its own under the same page
    ids (``ModelConfig.kv_pools``)."""

    n_heads: int         # index_n_heads
    head_dim: int        # index_head_dim: the index key's width
    topk: int            # index_topk: keys a query attends to at most
    rope_dim: int        # leading values of each query and key that rotate


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts, with or without a shared expert (the DeepSeek-V3
    form; ``n_shared`` 0: LFM2's, Mellum's): the router scores ALL
    ``n_routed`` experts; this process HOLDS experts ``held_start ..
    held_start + n_held`` (its share of an expert-parallel deployment; the
    whole set when ``n_held == n_routed``) and computes only their part of
    a token's result plus the shared expert. An expert's body is gated,
    ``act(x W_g) ⊙ (x W_u)`` then ``W_d`` (three matrices), or with
    ``gated`` false plain, ``act(x W_u) W_d`` (two; Nemotron-H: ``relu2``);
    the shared expert is ``n_shared`` experts of ``expert_dim`` side by
    side, or ONE of a width of its own (``shared_dim``)."""

    n_routed: int            # the router's width, as published
    n_held: int              # experts held here
    per_token: int           # num_experts_per_tok
    expert_dim: int          # moe_intermediate_size
    n_shared: int = 1        # shared experts, expert_dim wide each
                             # unless ``shared_dim`` gives the width
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    norm_topk: bool = True
    first_dense: int = 0     # leading layers with the dense MLP
    held_start: int = 0
    # ``noaux_tc``: one float32 a routed expert is added to its score where
    # groups and experts are CHOSEN; the gates stay the bare scores
    router_bias: bool = False
    # what the selected scores' sum takes before the gates are divided by
    # it (``norm_topk``): 1e-20 as DeepSeek-V3 publishes it, 1e-6 at LFM2
    gate_eps: float = 1e-20
    # what turns a router's logits into scores: "sigmoid", an expert at a
    # time (DeepSeek-V3, LFM2, Laguna), or "softmax" over all ``n_routed``
    # (Mellum: softmax, then the top k, then ``norm_topk``)
    score: str = "sigmoid"
    # False: no gate matrix, ``act(x W_u) W_d`` (routed and shared alike)
    gated: bool = True
    # the shared expert's width where it is not ``n_shared · expert_dim``
    shared_dim: Optional[int] = None

    @property
    def shared_width(self) -> int:
        """Width of the shared expert's hidden layer (0: the model has
        none)."""
        if not self.n_shared:
            return 0
        return self.shared_dim or self.expert_dim * self.n_shared

    @property
    def n_matrices(self) -> int:
        return 3 if self.gated else 2

    def __post_init__(self):
        assert self.n_routed % self.n_group == 0
        assert self.score in ("sigmoid", "softmax"), self.score
        assert 0 <= self.held_start \
            and self.held_start + self.n_held <= self.n_routed


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """A recurrent-matrix mixer (Mamba-2, the Nemotron-H form): ``n_heads``
    heads of ``head_dim`` channels, each with a STATE matrix ``[head_dim,
    state_dim]`` in float32 that a token decays by ``exp(Δ A)`` and adds
    ``Δ x ⊗ B`` to; ``B`` and ``C`` (``state_dim`` values each) are shared
    by the ``n_heads / n_groups`` heads of a group; a causal depthwise
    convolution of ``conv_kernel`` taps runs over ``[x | B | C]`` first.
    What a session holds of such a layer is one record whatever its
    length: the state matrices and the last ``conv_kernel - 1`` conv
    inputs (``ModelConfig.state_record``). ``chunk``: tokens the chunk
    forward's scan takes at a time (ops/ssm_scan.py), the engine's page."""

    n_heads: int         # mamba_num_heads
    head_dim: int        # mamba_head_dim
    n_groups: int        # n_groups (of B and C; NOT the router's n_group)
    state_dim: int       # ssm_state_size
    conv_kernel: int = 4
    chunk: int = 128
    # a seeded model's ``dt_bias`` is drawn between these (initialisation
    # only: time_step_min, time_step_max, time_step_floor; Δ itself is
    # not clamped)
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    def __post_init__(self):
        assert self.n_heads % self.n_groups == 0 and self.conv_kernel >= 2

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C side by side."""
        return self.d_inner + 2 * self.n_groups * self.state_dim

    @property
    def in_dim(self) -> int:
        """Outputs of the input projection: ``[z | xBC | dt]``."""
        return self.d_inner + self.conv_dim + self.n_heads


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """One KIND of per-head attention layer. A model whose attention layers
    differ (Laguna: a full-attention layer, then three with a window of 512
    and more query heads) names its kinds in ``ModelConfig.attn_kinds`` and
    a layer's kind in ``layer_types``; every other model has the one kind
    its own fields spell (``ModelConfig.attn_kind``). K and V are the same
    ``n_kv_heads`` of ``head_dim`` in every kind: kinds share a page's
    layout, and differ in what a session must KEEP (``kv_groups``)."""

    n_heads: int                       # query heads
    window: Optional[int] = None       # keys a query reaches back; None: all
    rope_theta: float = 10000.0
    rope_scaling: Optional[tuple] = None    # as ModelConfig.rope_scaling
    # leading values of each head that rotate (pairs (i, i + rotary_dim/2));
    # the rest pass through. None: the whole head
    rotary_dim: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture + serving config for one decoder-only model.

    Covers the dense Llama/Mistral/Gemma/Qwen families (RMSNorm, RoPE,
    GQA/MQA, gated MLP), latent attention with routed experts and an
    optional learned key selection (the DeepSeek-V2/V3/V3.2 form), and
    hybrids whose layers differ in KIND (LFM2: gated short convolutions
    among per-head attention layers, a dense feed-forward first and routed
    experts after; Laguna: window and full attention layers with their own
    head counts and rotary; Nemotron-H: layers that are ONE operator each,
    a Mamba-2 mixer, an attention with no positional embedding or an
    expert layer, ``layer_types`` beside ``ff_types``). Per-family quirks
    are data, not subclasses; which forward serves a configuration follows
    from that data alone (``plain``, ``latent``, ``layer_plan``), and what
    a session holds beside its pages from ``state_record``.
    """

    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_dim: int
    head_dim: Optional[int] = None  # defaults to dim // n_heads
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    # "silu" (llama/mistral), "gelu" (gemma), "relu2" (relu squared)
    activation: str = "silu"
    tie_embeddings: bool = False
    # Gemma multiplies token embeddings by sqrt(dim) (data, not code, per-family).
    scale_embeddings: bool = False
    # Gemma's RMSNorm computes (1 + w) * normed(x).
    rmsnorm_plus_one: bool = False
    # Sliding-window attention size (Mistral); None = full causal.
    sliding_window: Optional[int] = None
    # Optional logit soft-capping (Gemma-2 style); None = off.
    final_logit_softcap: Optional[float] = None
    # QKV projection biases (Qwen2-style).
    attn_bias: bool = False
    # RoPE frequency scaling, hashable: ("linear", factor) or
    # ("llama3", factor, low_freq_factor, high_freq_factor, original_max_pos).
    # ("yarn", factor, beta_fast, beta_slow, original_max_pos, mscale,
    # mscale_all_dim): NTK-by-parts frequencies, and the attention scale
    # takes mscale squared (transformer.attn_softmax_scale).
    # None = unscaled. (Kept a tuple so ModelConfig stays hashable for jit.)
    rope_scaling: Optional[tuple] = None
    # Latent attention and routed experts (frozen sub-records, so the
    # config stays hashable). Latent attention comes with experts; experts
    # also come beside per-head attention. Such a model is served on the
    # ragged paged path only (``require_plain`` at every other path's
    # entry).
    latent: Optional[LatentConfig] = None
    moe: Optional[MoEConfig] = None
    # Learned top-k selection inside the latent attention (latent models
    # only): a second pool holds each token's index key.
    indexer: Optional[IndexerConfig] = None
    # The token mixer of each layer, "attention", "conv", "ssm", the name of
    # one of ``attn_kinds``, or None: the layer has no mixer (None for the
    # tuple: attention everywhere). A "conv" layer is LFM2's gated short
    # convolution: what a session holds for it is the last ``conv_cache -
    # 1`` conv inputs, a fixed block whatever the session's length
    # (``state_lanes``), not per-token rows. An "ssm" layer is ``ssm``'s
    # recurrent-matrix mixer: a record of megabytes a session, in a pool
    # of records of its own (generate.py ``_ensure_pool``).
    layer_types: Optional[tuple] = None
    # The feed-forward part of each layer, "dense", "experts" or None: the
    # layer has none (None for the tuple: every layer has one, dense in
    # the leading ``moe.first_dense`` layers and experts after). A layer
    # with one part has one norm.
    ff_types: Optional[tuple] = None
    ssm: Optional[SSMConfig] = None
    # False: attention applies no positional embedding at all
    rope: bool = True
    # Records the pool of an "ssm" model holds (generate.py
    # ``SessionStore.records``): a live one a session, the snapshots the
    # prefix cache keeps, one a sessionless row of a tick
    state_records: int = 64
    # ((name, AttnKind), ...): the kinds of attention layer of a model that
    # has several, by the name ``layer_types`` gives a layer (a tuple of
    # pairs, so the config stays hashable). None: one kind, from the
    # fields above (n_heads, sliding_window, rope_theta, rope_scaling).
    attn_kinds: Optional[tuple] = None
    # a gate a head on the attention's output, before the output
    # projection: sigmoid of the layer's normed input through ``wg``
    # [dim, heads] ("Gated Attention for LLMs", the head-wise form)
    attn_gate: bool = False
    conv_cache: int = 3              # conv_L_cache: taps of the convolution
    # RMSNorm over each head's values of q and k, before the rotary (one
    # weight vector for all heads)
    qk_norm: bool = False

    # --- serving metadata (what the reference pulled from LLMDB) ---
    context_window: int = 8192
    output_limit: int = 4096
    # Cost per 1M tokens (USD) for budget accounting parity with the
    # reference's cost pipeline; on-TPU serving is "free" but agents still
    # budget, so these are nominal accounting rates.
    input_cost_per_mtok: float = 0.05
    output_cost_per_mtok: float = 0.15
    eos_token_id: int = 2
    bos_token_id: int = 1
    # Additional stop ids beyond eos_token_id — llama-3-instruct style
    # checkpoints end chat turns with <|eot_id|> while config.eos lists
    # several ids; decode stops on ANY of {eos_token_id} | stop_token_ids.
    stop_token_ids: tuple = ()
    # HF checkpoint directory for real weights (models/loader.py); None =
    # random-init (tests/bench). The directory's tokenizer files are used too.
    checkpoint_path: Optional[str] = None
    # Recommended tensor-parallel width on a v5e-8 sub-mesh (must divide
    # n_kv_heads so KV shards carry whole GQA groups — parallel/mesh.py).
    # The pool-sizing math (parallel/mesh.py pool_sizing) turns this + the
    # param count into the explicit HBM budget VERDICT r4 item 4 asks for.
    recommended_tp: int = 1
    # VLM member (BASELINE config 5): an in-tree ViT tower whose projected
    # patches splice into the prompt at ``image_token_id`` placeholders
    # (models/vision.py). None = text-only model. VisionConfig is a frozen
    # dataclass, so ModelConfig stays hashable for jit.
    vision: Optional["VisionConfig"] = None          # noqa: F821
    image_token_id: Optional[int] = None

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        assert self.n_heads % self.n_kv_heads == 0, "GQA requires n_heads % n_kv_heads == 0"
        assert self.latent is None or self.moe is not None, \
            "latent attention is served with routed experts only"
        if self.moe is not None:
            assert 0 <= self.moe.first_dense < self.n_layers
        assert self.indexer is None or self.latent is not None, \
            "the indexer selects keys for latent attention only"
        if self.attn_kinds is not None:
            assert self.latent is None and self.layer_types is not None \
                and "attention" not in self.layer_types, \
                "a model with kinds of attention names each layer's"
            assert all(k.n_heads % self.n_kv_heads == 0
                       for _, k in self.attn_kinds)
        if self.layer_types is not None:
            assert len(self.layer_types) == self.n_layers \
                and set(self.layer_types) <= {
                    "conv", "ssm", None,
                    *(dict(self.attn_kinds) if self.attn_kinds
                      else ("attention",))}, self.layer_types
            assert self.latent is None or not (
                {"conv", "ssm", None} & set(self.layer_types)), \
                "conv and ssm layers stand among per-head attention layers"
            assert self.conv_cache >= 2
            assert ("ssm" in self.layer_types) <= (self.ssm is not None)
            assert not ("ssm" in self.layer_types
                        and "conv" in self.layer_types), \
                "one manager of recurrent state a model"
        if self.ff_types is not None:
            assert self.latent is None and len(self.ff_types) \
                == self.n_layers and set(self.ff_types) <= {
                    "dense", "experts", None}, self.ff_types
            assert ("experts" in self.ff_types) <= (self.moe is not None)
            assert all(m is not None or f is not None
                       for m, f in zip(self.mixers, self.ff_types)), \
                "a layer is a mixer, a feed-forward part or both"
        else:
            assert None not in self.mixers
        assert not self.qk_norm or self.latent is None

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def plain(self) -> bool:
        """Dense decoder with rotary per-head K and V and a gated MLP in
        every layer: every path serves it. A model with latent attention,
        routed experts, conv or ssm layers, layers of one part, attention
        with no positional embedding, a q/k norm, kinds of attention
        layer or a gate on its heads runs on the ragged paged path
        alone."""
        return (self.latent is None and self.moe is None
                and self.n_conv_layers == 0 and not self.qk_norm
                and self.attn_kinds is None and not self.attn_gate
                and self.n_ssm_layers == 0 and self.ff_types is None
                and self.rope)

    @property
    def mixers(self) -> tuple:
        return self.layer_types or ("attention",) * self.n_layers

    @property
    def ffs(self) -> tuple:
        """Each layer's feed-forward part: ``ff_types``, or the rule of a
        model that does not name them."""
        if self.ff_types is not None:
            return self.ff_types
        n_dense = self.n_layers if self.moe is None else self.moe.first_dense
        return tuple("dense" if i < n_dense else "experts"
                     for i in range(self.n_layers))

    @staticmethod
    def is_attention(mixer) -> bool:
        """Whether a layer of type ``mixer`` holds per-token rows."""
        return mixer not in ("conv", "ssm", None)

    def attn_kind(self, mixer: str = "attention") -> AttnKind:
        """The attention a layer of type ``mixer`` runs: the kind of that
        name, or the model's one kind, spelled by its own fields."""
        if self.attn_kinds is not None:
            return dict(self.attn_kinds)[mixer]
        return AttnKind(self.n_heads, self.sliding_window, self.rope_theta,
                        self.rope_scaling)

    @property
    def max_heads(self) -> int:
        """The most query heads an attention layer has."""
        return max((k.n_heads for _, k in self.attn_kinds or ()),
                   default=self.n_heads)

    @property
    def n_attn_layers(self) -> int:
        """Layers that hold per-token rows in pages (``kv_pools``)."""
        return sum(map(self.is_attention, self.mixers))

    @property
    def kv_groups(self) -> tuple:
        """The attention layers by what a session must KEEP of them, one
        ``(window, layers)`` a retention GROUP: a group's layers hold a
        token's rows for as long as a query can still reach them — for
        ever (``window`` None) or for ``window`` positions. Every group has
        its own pools and its own page ids (generate.py ``_ensure_pool``,
        ``SessionStore``): a session's pages behind a window go back to
        that group's free list while the full group keeps every token. One
        group for every model but one with kinds of attention; there the
        group that keeps everything comes first, then windows ascending."""
        if self.attn_kinds is None:
            return ((self.sliding_window, self.n_attn_layers),)
        count: dict = {}
        for m in self.mixers:
            if self.is_attention(m):
                w = self.attn_kind(m).window
                count[w] = count.get(w, 0) + 1
        return tuple(sorted(count.items(),
                            key=lambda g: (g[0] is not None, g[0] or 0)))

    def kv_group_of(self, mixer: str) -> int:
        """The retention group (its place in ``kv_groups``) of a layer."""
        w = self.attn_kind(mixer).window
        return [g[0] for g in self.kv_groups].index(w)

    @property
    def n_conv_layers(self) -> int:
        """Layers that hold a fixed block of state (``state_lanes``)."""
        return self.mixers.count("conv")

    @property
    def state_lanes(self) -> int:
        """What a session holds in ONE conv layer, whatever its length:
        the last ``conv_cache - 1`` conv inputs of ``dim`` values, side by
        side. One such record a conv layer rides every page of the pool
        (generate.py ``_ensure_pool``): the state at the end of the page's
        tokens."""
        return (self.conv_cache - 1) * self.dim if self.n_conv_layers else 0

    @property
    def n_ssm_layers(self) -> int:
        """Layers that hold a state matrix a head (``ssm``)."""
        return self.mixers.count("ssm")

    @property
    def state_record(self) -> tuple:
        """THE statement of what one state record holds in ONE recurrent
        layer, ``((lanes, type), ...)`` a part, ``type`` None where the
        part is kept in the pool's own type. A conv layer: its last
        ``conv_cache - 1`` conv inputs. An ssm layer: the heads' state
        matrices in float32, and the last ``conv_kernel - 1`` inputs of
        its convolution. Pool shapes (generate.py ``_ensure_pool``),
        ``state_bytes_per_record`` and with it ``quant_stats`` and a
        family's ``stated_precision`` read this."""
        if self.n_ssm_layers:
            m = self.ssm
            return ((m.n_heads * m.head_dim * m.state_dim, "float32"),
                    ((m.conv_kernel - 1) * m.conv_dim, None))
        if self.n_conv_layers:
            return ((self.state_lanes, None),)
        return ()

    def state_bytes_per_record(self, dtype_bytes: int = 2) -> int:
        """Bytes of one state record over all conv or ssm layers."""
        return (self.n_conv_layers + self.n_ssm_layers) * sum(
            lanes * (dtype_bytes if t is None else 4)
            for lanes, t in self.state_record)

    @property
    def layer_plan(self) -> tuple:
        """The layers as the pattern forward runs them: three segments
        ``(kinds, repeats)`` — the leading dense-feed-forward layers once,
        the shortest PERIOD of the layers after them as often as it fits
        whole (one ``lax.scan``: program size does not follow the depth),
        and what is left of a last period once. ``kinds`` is a tuple of
        (mixer, feed-forward) pairs, what a layer IS: "attention" (or a
        kind's name) | "conv" | "ssm" | None and "dense" | "experts" |
        None."""
        n_dense = self.n_dense_layers
        kinds = tuple(zip(self.mixers, self.ffs))
        lead, rest = kinds[:n_dense], kinds[n_dense:]
        p = next((p for p in range(1, len(rest) + 1)
                  if all(rest[i] == rest[i + p]
                         for i in range(len(rest) - p))), 0)
        n = len(rest) // p if p else 0
        return ((lead, 1 if lead else 0), (rest[:p], n),
                (rest[n * p:], 1 if rest[n * p:] else 0))

    @property
    def kv_pools(self) -> tuple:
        """THE statement of what a resident token holds in one layer: the
        lane count of each stored pool. Per-head K and V: two pools of
        ``n_kv_heads·head_dim`` lanes; a latent cache: ONE pool of
        ``latent.lanes``, and with an indexer a second, narrower one of
        ``indexer.head_dim`` for the token's index key. Pool shapes
        (generate.py ``_ensure_pool``), the byte rates below,
        ``kv_signature``, ``quant_stats`` and ``pool_sizing`` all read
        this, beside ``kv_groups``: which layers share a set of pools."""
        if self.indexer is not None:
            return (self.latent.lanes, self.indexer.head_dim)
        if self.latent is not None:
            return (self.latent.lanes,)
        return (self.n_kv_heads * self.head_dim,) * 2

    @property
    def n_dense_layers(self) -> int:
        """Leading layers whose feed-forward part is the dense one."""
        return next((i for i, f in enumerate(self.ffs) if f != "dense"),
                    self.n_layers)

    @property
    def n_expert_layers(self) -> int:
        return self.ffs.count("experts")

    def _attn_params(self, mixer: str = "attention") -> int:
        if self.latent is not None:
            la, H = self.latent, self.n_heads
            n = (self.dim * la.q_rank + la.q_rank
                 + la.q_rank * H * la.qk_dim
                 + self.dim * (la.kv_rank + la.rope_dim) + la.kv_rank
                 + la.kv_rank * H * (la.nope_dim + la.v_dim)
                 + H * la.v_dim * self.dim)
            if self.indexer is not None:
                # query, key and head-weight projections, the key's
                # LayerNorm (weight and bias)
                ix = self.indexer
                n += (la.q_rank * ix.n_heads * ix.head_dim
                      + self.dim * ix.head_dim + self.dim * ix.n_heads
                      + 2 * ix.head_dim)
            return n
        if not self.is_attention(mixer):
            return 0
        hd, H = self.head_dim, self.attn_kind(mixer).n_heads
        q = self.dim * H * hd + (H * hd if self.attn_bias else 0)
        kv = 2 * (self.dim * self.n_kv_heads * hd
                  + (self.n_kv_heads * hd if self.attn_bias else 0))
        gate = self.dim * H if self.attn_gate else 0
        return q + kv + H * hd * self.dim + gate

    def _conv_params(self) -> int:
        """A short-conv operator: in (to B, C, x), the taps, out."""
        return (self.dim * 3 * self.dim + self.conv_cache * self.dim
                + self.dim * self.dim)

    def _ssm_params(self) -> int:
        """A Mamba-2 operator: in (to z, xBC, dt), the taps and their
        bias, ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm's
        weight, out."""
        m = self.ssm
        return (self.dim * m.in_dim + (m.conv_kernel + 1) * m.conv_dim
                + 3 * m.n_heads + m.d_inner + m.d_inner * self.dim)

    def _layer_params(self, ff: Optional[str], mixer="attention") -> int:
        """One layer's parameters: its mixer ``mixer`` (None: none), its
        feed-forward part ``ff`` ("dense": the gated MLP, "experts": the
        held routed experts beside the router and the shared one, None:
        none), and a norm for each part it has."""
        norms = ((mixer is not None) + (ff is not None)) * self.dim
        mlp = 0
        if ff == "dense":
            mlp = 3 * self.dim * self.ffn_dim      # gate + up + down
        elif ff == "experts":
            m = self.moe
            mlp = (self.dim * m.n_routed
                   + (m.n_routed if m.router_bias else 0)
                   + m.n_matrices * self.dim
                   * (m.expert_dim * m.n_held + m.shared_width))
        op = (self._conv_params() if mixer == "conv"
              else self._ssm_params() if mixer == "ssm"
              else self._attn_params(mixer)
              + (2 * self.head_dim if self.qk_norm else 0) if mixer else 0)
        return op + mlp + norms

    @property
    def n_params(self) -> int:
        """Exact decoder parameter count HELD here (embeddings + per-layer
        attn/mlp/norms + final norm + untied head; an expert model's held
        experts) — the input to the HBM budget."""
        embed = self.vocab_size * self.dim
        head = 0 if self.tie_embeddings else self.vocab_size * self.dim
        total = embed + self.dim + head + sum(
            self._layer_params(f, m)
            for m, f in zip(self.mixers, self.ffs))
        if self.vision is not None:
            # ViT tower + projector come out of the same HBM budget
            # (models/vision.py init_vision_params structure)
            v = self.vision
            v_layer = (2 * v.dim                    # ln1 + ln2
                       + v.dim * 3 * v.dim          # wqkv
                       + v.dim * v.dim              # wo
                       + 2 * v.dim * v.ffn_dim)     # w_up + w_down
            total += (v.patch_dim * v.dim           # patch_embed
                      + v.n_patches * v.dim         # pos_embed
                      + v.n_layers * v_layer
                      + v.dim                       # final_ln
                      + v.dim * v.out_dim)          # projector
        return total

    @property
    def n_active_params(self) -> int:
        """Parameters one token's forward multiplies by: ``n_params`` with
        ``per_token`` routed experts a layer in place of the held ones."""
        if self.moe is None:
            return self.n_params
        m = self.moe
        return self.n_params - self.n_expert_layers * m.n_matrices \
            * self.dim * m.expert_dim * (m.n_held - m.per_token)

    def kv_bytes_per_token(self, tp: int = 1, dtype_bytes: int = 2,
                           group: Optional[int] = None) -> int:
        """KV cache bytes per resident token PER TP SHARD (whole GQA
        groups per shard: kv heads divide across tp; a latent cache has
        no heads to divide and is whole on every shard): over every
        attention layer, or over the layers of one retention ``group``
        (``kv_groups``) — what a token costs while that group keeps it."""
        lanes = sum(self.kv_pools)
        if self.latent is None:
            lanes //= tp
        layers = self.n_attn_layers if group is None \
            else self.kv_groups[group][1]
        return lanes * layers * dtype_bytes


def unsupported_path(cfg: ModelConfig, what: str) -> str:
    """The one error text for a path that cannot serve a model, naming
    what of the model the path cannot carry."""
    has = [name for name, on in (
        ("latent attention", cfg.latent is not None),
        ("a learned key selection", cfg.indexer is not None),
        ("short-conv state beside the paged KV", cfg.n_conv_layers > 0),
        ("a pool of recurrent-state records beside the paged KV",
         cfg.n_ssm_layers > 0),
        ("attention with no positional embedding", not cfg.rope),
        ("a q/k norm", cfg.qk_norm),
        ("window and full attention layers mixed", len(cfg.kv_groups) > 1),
        ("a gate on the attention's heads", cfg.attn_gate),
        ("routed experts", cfg.moe is not None)) if on]
    return (f"model {cfg.name} ({', '.join(has)}) is served on the ragged "
            f"paged path of one device only; {what} cannot run it")


def require_plain(cfg: ModelConfig, what: str) -> None:
    """Refuse, at the path's entry, a model it would compute wrongly."""
    if not cfg.plain:
        raise ValueError(unsupported_path(cfg, what))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, ModelConfig] = {}


def register_model(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_model_config(spec: str) -> ModelConfig:
    """Look up by model spec. Accepts ``xla:name`` or bare ``name``.

    Mirrors the reference's ``provider:model`` spec parsing
    (reference lib/quoracle/models/model_query.ex model_spec format).
    """
    name = spec.split(":", 1)[1] if ":" in spec else spec
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {spec!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_models() -> list[str]:
    return sorted(_REGISTRY)


# --- production-scale pool (the BASELINE.json north-star trio) ---

LLAMA3_8B = register_model(ModelConfig(
    name="llama-3-8b",
    vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, rope_theta=500000.0, norm_eps=1e-5,
    context_window=8192, output_limit=4096,
    eos_token_id=128001, bos_token_id=128000,
    # 8.0B params -> 16.1 GB bf16; tp=4 on a v5e-8 leaves ~4 GB/chip
    # weights + page pool + tail headroom (pool_sizing prints the table)
    recommended_tp=4,
))

MISTRAL_7B = register_model(ModelConfig(
    name="mistral-7b",
    vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, rope_theta=1000000.0, norm_eps=1e-5,
    context_window=32768, output_limit=8192, sliding_window=4096,
    # 7.2B params -> 14.5 GB bf16; tp=2 fits 7.3 GB/chip weights with the
    # 4096-token sliding window bounding resident KV per session
    recommended_tp=2,
))

# One v5e chip's cut of mistral-7b (chip_smoke.py; ROADMAP R1's control
# cell builds on it). Source: the `mistral-7b` entry above. Keys changed:
# n_layers 32 → 16. Every width is as published (dim 4096, 32/8 heads,
# head_dim 128, ffn 14336, vocab 32000, window 4096) and all 16 layers are
# the one layer kind the model has. A layer is 436 MB of bf16 weights and
# the two embeddings 524 MB, so 16 layers are 7.5 GB beside the engine's
# 2 GiB page pool (32,768 resident tokens at 64 KiB each) on a 16 GB chip;
# full depth is 14.5 GB and needs tp >= 2 (`chip_smoke.py --chips 4`).
# Weights are random, from a seed (transformer.init_params).
MISTRAL_7B_L16 = register_model(dataclasses.replace(
    MISTRAL_7B, name="mistral-7b-l16", n_layers=16, recommended_tp=1))

GEMMA_7B = register_model(ModelConfig(
    name="gemma-7b",
    vocab_size=256000, dim=3072, n_layers=28, n_heads=16, n_kv_heads=16,
    ffn_dim=24576, head_dim=256, rope_theta=10000.0, norm_eps=1e-6,
    activation="gelu", tie_embeddings=True, scale_embeddings=True,
    rmsnorm_plus_one=True,
    context_window=8192, output_limit=4096,
    # 8.5B params (tied embeddings) -> 17.1 GB bf16; tp=2 -> 8.5 GB/chip:
    # tight but fits with a reduced page pool (MHA KV is the pressure —
    # 16 kv heads x 256 head_dim; pool_sizing flags the headroom)
    recommended_tp=2,
))

# --- bench-scale models (fit a single v5e chip with headroom; same families) ---

LLAMA_1B = register_model(ModelConfig(
    name="llama-1b",
    vocab_size=32768, dim=2048, n_layers=16, n_heads=16, n_kv_heads=4,
    ffn_dim=5632, rope_theta=500000.0,
    context_window=8192, output_limit=4096,
))

MISTRAL_1B = register_model(ModelConfig(
    name="mistral-1b",
    vocab_size=32768, dim=2048, n_layers=16, n_heads=16, n_kv_heads=4,
    ffn_dim=5632, rope_theta=1000000.0, sliding_window=4096,
    context_window=16384, output_limit=4096,
))

GEMMA_1B = register_model(ModelConfig(
    name="gemma-1b",
    vocab_size=32768, dim=1792, n_layers=14, n_heads=14, n_kv_heads=14,
    ffn_dim=7168, head_dim=128, activation="gelu", tie_embeddings=True,
    scale_embeddings=True, rmsnorm_plus_one=True, norm_eps=1e-6,
    context_window=8192, output_limit=4096,
))

# --- tiny test models (CPU-mesh friendly; divisible by 2 and 4 for tp tests) ---

TINY = register_model(ModelConfig(
    name="tiny",
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, context_window=512, output_limit=128,
))

TINY_GEMMA = register_model(ModelConfig(
    name="tiny-gemma",
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
    ffn_dim=128, activation="gelu", tie_embeddings=True,
    scale_embeddings=True, rmsnorm_plus_one=True,
    context_window=512, output_limit=128,
))

def _tiny_vision():
    from quoracle_tpu.models.vision import VisionConfig
    return VisionConfig(image_size=28, patch_size=14, dim=32, n_layers=1,
                        n_heads=2, ffn_dim=64, out_dim=64)


TINY_VLM = register_model(ModelConfig(
    name="tiny-vlm",
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, context_window=512, output_limit=128,
    vision=_tiny_vision(), image_token_id=3,
))

TINY_POOL = ["xla:tiny", "xla:tiny-gemma"]
BENCH_POOL = ["xla:llama-1b", "xla:mistral-1b", "xla:gemma-1b"]
NORTH_STAR_POOL = ["xla:llama-3-8b", "xla:mistral-7b", "xla:gemma-7b"]
