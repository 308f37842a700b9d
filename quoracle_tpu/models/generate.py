"""Batched prefill + decode: the generate step that replaces the reference's
per-model HTTPS fan-out (reference lib/quoracle/models/model_query.ex:88-131,
Task.async per model -> ReqLLM.generate_text). A consensus round here is ONE
batched call per pool member with per-row sampling params.

Functional core (this file) is pure and jit-compiled; the stateful Engine
handles padding, shape-bucketing (to bound recompiles), RNG, and
detokenization. Decode runs a ``lax.while_loop`` with static bounds and
early-exits when every row has emitted EOS — shape-static, data-dependent
only in trip count, exactly what XLA wants.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from quoracle_tpu.infra.telemetry import (
    DECODE_MS, DECODE_STEP_MS, PREFILL_MS, SESSION_DROP_WAIT_MS, TRACER,
    tick_note, tick_op, tick_phase,
)
from quoracle_tpu.models.config import (
    ModelConfig, require_plain, unsupported_path,
)
from quoracle_tpu.models.sampling import sample_tokens
from quoracle_tpu.models.transformer import (
    ConvTick, KVCache, SsmTick, forward_hidden, forward_hidden_ragged,
    init_cache, live_token_slots, put_rows, take_rows,
    moe_stats_len, project_logits,
)

# Finite mask value: a whole-row -inf would NaN the sampling softmax; the
# grammar layer guarantees >= 1 allowed token, this is defense in depth.
NEG_INF_LOGITS = -1e30
REJECT_STATE = -1          # models/constrained.py REJECT


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  prefix_lens: jax.Array, chunk_lens: jax.Array,
                  cache: KVCache,
                  kv_off: Optional[jax.Array] = None,
                  ring: Optional[tuple] = None,
                  input_embeds: Optional[jax.Array] = None,
                  shard: Optional[tuple] = None,
                  ) -> tuple[jax.Array, KVCache]:
    """Fill the cache from a right-padded token CHUNK starting at per-row
    buffer index ``prefix_lens`` (0 = fresh prefill; >0 = resume on top
    of a KV prefix already in the buffer — the prefix-reuse path). Returns
    (last-token logits [B, V], cache with lens = prefix + chunk).

    ``kv_off`` is buffer index 0's absolute position (nonzero only for
    sliding-window sessions whose leading pages were trimmed): RoPE
    positions and the causal mask use kv_off + buffer index.

    The head projection happens AFTER gathering each row's last hidden state —
    projecting the full [B, T, vocab] tensor first would cost ~4 GB/row fp32
    at llama-3-8b scale for values that are immediately discarded."""
    B, T = tokens.shape
    positions = (prefix_lens[:, None]
                 + jnp.arange(T, dtype=jnp.int32)[None, :])
    if kv_off is not None:
        positions = positions + kv_off.astype(jnp.int32)[:, None]
    total = (prefix_lens + chunk_lens).astype(jnp.int32)
    hidden, cache = forward_hidden(
        params, cfg, tokens, positions, cache,
        write_offset=prefix_lens.astype(jnp.int32),
        kv_lens=total,
        kv_pos_offset=kv_off,
        ring=ring,
        input_embeds=input_embeds,
        shard=shard,
    )
    last_h = jnp.take_along_axis(
        hidden, (chunk_lens - 1)[:, None, None].astype(jnp.int32), axis=1)
    last = project_logits(params, cfg, last_h)[:, 0, :]
    return last, cache._replace(lens=total)


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array,
            prompt_lens: jax.Array, cache: KVCache,
            ring: Optional[tuple] = None,
            input_embeds: Optional[jax.Array] = None,
            shard: Optional[tuple] = None,
            ) -> tuple[jax.Array, KVCache]:
    """Fresh prefill = prefill_chunk from position 0."""
    B = tokens.shape[0]
    return prefill_chunk(params, cfg, tokens,
                         jnp.zeros((B,), jnp.int32), prompt_lens, cache,
                         ring=ring, input_embeds=input_embeds, shard=shard)


def grammar_mask(logits: jax.Array, jstate: jax.Array,
                 json_table: jax.Array, eos_id: int) -> jax.Array:
    """THE grammar mask — every constrained decode path (gather decode,
    ragged paged decode, speculative draft + verify) calls this one
    implementation so they can never drift on dead-end or unconstrained
    handling. logits [B, V], jstate [B]; jstate < 0 = unconstrained row;
    a dead-end state (vocab gap: no token allowed) permits eos so the row
    stops instead of sampling an all -inf distribution."""
    with jax.named_scope("grammar_mask"):
        allowed = json_table[jnp.clip(jstate, 0, None)] >= 0       # [B, V]
        none_ok = ~jnp.any(allowed, axis=-1, keepdims=True)
        eos_hot = (jnp.arange(logits.shape[-1]) == eos_id)[None, :]
        allowed = allowed | (none_ok & eos_hot) | (jstate < 0)[:, None]
        return jnp.where(allowed, logits, NEG_INF_LOGITS)


def _sampling_fns(json_table: Optional[jax.Array], eos_id: int,
                  stop_ids: tuple):
    """The stop/grammar closures shared by decode() and decode_ragged() —
    one implementation so the gather and ragged paged paths can never
    drift apart on stop handling or grammar dead-end recovery (the two
    must stay token-exact; tests/test_ragged_attention.py equality
    tests)."""
    stops = jnp.asarray((eos_id,) + tuple(stop_ids), jnp.int32)
    constrained = json_table is not None

    def is_stop(tok):
        return jnp.any(tok[:, None] == stops[None, :], axis=1)

    def mask_logits(logits, jstate):
        if not constrained:
            return logits
        return grammar_mask(logits, jstate, json_table, eos_id)

    def advance(jstate, tok, done):
        if not constrained:
            return jstate
        nxt = json_table[jnp.clip(jstate, 0, None), tok].astype(jnp.int32)
        return jnp.where((jstate >= 0) & ~done, nxt, jstate)

    return is_stop, mask_logits, advance, constrained


def _draw(mask_logits, logits, jstate, rng, temperature, top_p):
    """One sampling step, under the ``sample`` scope (the grammar mask
    and the nucleus sort beneath it): split the key, mask, sample.
    Returns (tokens [B], the carried key)."""
    with jax.named_scope("sample"):
        rng, k = jax.random.split(rng)
        return (sample_tokens(mask_logits(logits, jstate), k, temperature,
                              top_p), rng)


def _first_token(fns, first_logits, rng, temperature, top_p, active,
                 row_limit, json_state, max_new: int, pad_id: int):
    """Shared decode bootstrap: sample token 0 from the prefill logits and
    build the initial (tok0, n0, done0, jstate0, out0, rng) carry."""
    is_stop, mask_logits, advance, constrained = fns
    B = first_logits.shape[0]
    jstate0 = json_state if constrained else jnp.zeros((B,), jnp.int32)
    tok0, rng = _draw(mask_logits, first_logits, jstate0, rng, temperature,
                      top_p)
    n0 = jnp.where(active, 1, 0).astype(jnp.int32)
    done0 = ~active | is_stop(tok0) | (n0 >= row_limit)
    # advance on tok0 for every active row (eos self-loops in accept states)
    jstate0 = advance(jstate0, tok0, ~active)
    out0 = jnp.full((B, max_new), pad_id, jnp.int32).at[:, 0].set(tok0)
    return tok0, n0, done0, jstate0, out0, rng


def decode(
    params: dict,
    cfg: ModelConfig,
    cache: KVCache,
    first_logits: jax.Array,   # [B, V] logits at the last prompt token
    rng: jax.Array,
    temperature: jax.Array,    # [B]
    top_p: jax.Array,          # [B]
    max_new: int,
    eos_id: int,
    active: jax.Array,         # [B] bool — False for batch-bucket padding rows
    row_limit: jax.Array,      # [B] int32 per-row generation budget (<= max_new)
    pad_id: int = 0,
    stop_ids: tuple = (),      # extra stop ids (llama-3 <|eot_id|> style)
    json_table: Optional[jax.Array] = None,   # [S, V] grammar transitions
    json_state: Optional[jax.Array] = None,   # [B] int32; -1 = unconstrained
    kv_off: Optional[jax.Array] = None,       # [B] int32 abs pos of index 0
) -> tuple[jax.Array, jax.Array, KVCache]:
    """Autoregressive decode.

    Returns (tokens [B, max_new], n_emitted [B], final cache) where
    n_emitted counts real tokens written per row INCLUDING a terminal EOS.
    The count is tracked in the loop carry — output extraction must not scan
    for sentinels, because pad_id can be a legitimate vocab token in real
    checkpoints. The returned cache holds the RESPONSE tokens' KV too
    (``lens[b]`` bounds the valid entries: prompt + every emitted token
    except the last sampled one, which never ran forward) — sessions keep it
    so refinement rounds skip re-prefilling the previous response.

    ``max_new`` is the STATIC loop/buffer bound (shape-bucketed for compile
    caching); ``row_limit`` is the TRACED per-row budget — min(requested
    max_new_tokens, context_window - prompt_len). A row stops at EOS or at
    its limit, so bucketing never costs extra forward steps and no row's
    positions run past the context window. Padding rows (``~active``) start
    done, so the early-exit fires when every REAL row has finished.

    With ``json_table``/``json_state`` set, rows whose state is >= 0 sample
    under the JSON grammar mask (models/constrained.py): each step is one
    row gather (allowed = table[state] >= 0) + where() before sampling, and
    a scalar gather to advance the state — output is valid JSON by
    construction (SURVEY §7 hard part 4).
    """
    fns = _sampling_fns(json_table, eos_id, stop_ids)
    is_stop, mask_logits, advance, _ = fns
    tok0, n0, done0, jstate0, out0, rng = _first_token(
        fns, first_logits, rng, temperature, top_p, active, row_limit,
        json_state, max_new, pad_id)

    def cond(carry):
        i, done, *_ = carry
        return (i < max_new) & ~jnp.all(done)

    def body(carry):
        i, done, cur, out, n_emitted, cache, rng, jstate = carry
        positions = cache.lens[:, None]
        if kv_off is not None:
            positions = positions + kv_off.astype(jnp.int32)[:, None]
        hidden, cache = forward_hidden(
            params, cfg, cur[:, None], positions, cache,
            write_offset=cache.lens, kv_lens=cache.lens + 1,
            kv_pos_offset=kv_off,
        )
        logits = project_logits(params, cfg, hidden)
        nxt, rng = _draw(mask_logits, logits[:, 0, :], jstate, rng,
                         temperature, top_p)
        with jax.named_scope("row_state"):
            nxt = jnp.where(done, pad_id, nxt)
            out = jax.lax.dynamic_update_slice_in_dim(out, nxt[:, None], i,
                                                      axis=1)
            n_emitted = n_emitted + jnp.where(done, 0, 1).astype(jnp.int32)
            cache = cache._replace(lens=cache.lens + jnp.where(done, 0, 1))
            jstate = advance(jstate, nxt, done)
            done = done | is_stop(nxt) | (n_emitted >= row_limit)
        return (i + 1, done, nxt, out, n_emitted, cache, rng, jstate)

    # Feed the first sampled token through the loop starting at step 1.
    init = (jnp.asarray(1, jnp.int32), done0, tok0, out0, n0, cache, rng,
            jstate0)
    with jax.named_scope("decode_loop"):
        _, done, _, out, n_emitted, cache, _, jstate = \
            jax.lax.while_loop(cond, body, init)
    # jstate returned so chunked continuations (models/scheduler.py) can
    # resume the grammar mid-stream via initial_json_state.
    return out, n_emitted, cache, jstate


def decode_ragged(
    params: dict,
    cfg: ModelConfig,
    k_pool: jax.Array,         # [L, n_pages, page, KV·hd] — the pool as
    v_pool: jax.Array,         # stored (donated by jit), updated in place
    tables: jax.Array,         # [R, maxp] int32 dst page table per row
    pool_lens: jax.Array,      # [R] int32 valid pool tokens (prompt+chunk)
    kv_off: jax.Array,         # [R] int32 abs position of pool index 0
    first_logits: jax.Array,   # [R, V]
    rng: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    max_new: int,
    eos_id: int,
    active: jax.Array,
    row_limit: jax.Array,
    pad_id: int = 0,
    stop_ids: tuple = (),
    json_table: Optional[jax.Array] = None,
    json_state: Optional[jax.Array] = None,
    shard: Optional[tuple] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,   # [L, n_pages, KV, page] f32 —
    v_scale: Optional[jax.Array] = None,   # int8 pools (ISSUE 13)
    shared: Optional[jax.Array] = None,    # [2 + SHARED_ROWS, R] int32
    state: Optional[jax.Array] = None,     # conv layers' state pool, or
                                           # ssm layers' pair of pools
    records: Optional[jax.Array] = None,   # [R] int32: each row's record
) -> tuple:
    """Autoregressive decode through the UNIFIED ragged kernel (ISSUE 8):
    same sampling/grammar semantics as decode(), but each
    step's KV scatters STRAIGHT into the row's pages before attention and
    the kernel reads everything — prompt, chunk, and generated tokens —
    off the pages. Neither the [B, maxp·page] working cache nor the
    [L, B, max_new] tail buffer exists; decode HBM high-water is the pool
    itself. Every step is one tq=1-block-per-row launch per layer of the
    same kernel that served the mixed prefill chunk. ``shared``
    (ops/paged_attention.shared_walks of ``tables`` and ``pool_lens``)
    tells that kernel which rows' tables begin with the same pages: it
    reads those once a step for all of them. The table holds for the whole
    loop — a shared page is full before the first step, and every step
    writes behind it.

    The pools are loop-carried and every step updates them IN PLACE: the
    forward scatters the step's R rows into the carried buffers and gives
    the same buffers back (transformer.forward_hidden_ragged), so the
    ``while`` has nothing to copy — tests/test_kernels_compile_tpu.py
    holds the compiled program to that.

    With ``state`` (a model with conv layers: one record a page a conv
    layer, transformer.ConvTick) the loop carries that pool the same way:
    a step reads each live row's record under the page of its last
    resident token and writes the new one under the page of the token it
    forwards, so a page that fills keeps the state at its end and a row
    that is done leaves its record alone.

    With ``records`` (a model with ssm layers: ``state`` is its pair of
    record pools, transformer.SsmTick) every row has ONE record — the
    chunk forward left the state at the row's chunk's end there;
    ``n_records`` or more: a slot with no row. The loop reads the rows'
    records once into buffers of its own, every step updates them in
    place (a row that is done leaves its state alone), and one write
    behind the loop puts them back.

    A model with several retention groups of attention layers
    (config.kv_groups) hands in ``k_pool``, ``v_pool`` and ``tables`` as
    TUPLES, a member a group, and gets the pools back so: a step's token
    has a slot in every group's pages, and ``shared`` is the full group's.

    Returns (tokens [R, max_new], n_emitted [R], lens [R], k_pool,
    v_pool, k_scale, v_scale, jstate, moe_stats, state) where lens counts the
    row's valid pool tokens (prompt + chunk + emitted-and-forwarded) and
    ``moe_stats`` is the expert layers' counts summed over the steps
    (transformer.moe_counts; None without experts). With
    ``k_scale``/``v_scale`` (int8 pools, ISSUE 13; None otherwise, and
    returned as they came) each step's token quantizes on write inside
    the forward."""
    R = first_logits.shape[0]
    # a model with several retention groups of attention layers
    # (config.kv_groups) hands in a tuple of pools and of tables, a member
    # a group: each step's token has a slot in every group's pages
    multi = isinstance(tables, tuple)
    group_pools, group_tables = (k_pool, tables) if multi \
        else ((k_pool,), (tables,))
    _, n_pages, page, _ = group_pools[0].shape
    maxp = group_tables[0].shape[1]
    fns = _sampling_fns(json_table, eos_id, stop_ids)
    is_stop, mask_logits, advance, _ = fns
    tok0, n0, done0, jstate0, out0, rng = _first_token(
        fns, first_logits, rng, temperature, top_p, active, row_limit,
        json_state, max_new, pad_id)
    lens0 = pool_lens.astype(jnp.int32)
    if records is not None:
        # the rows' records, read ONCE into the loop's own buffers
        # ``[n_ssm_layers, R, ...]`` (a slot with no row: the scratch
        # record 0) and written back once behind the loop; they lie as
        # the decode kernel takes them: the state transposed, the
        # convolution's inputs float32 (transformer.SsmTick)
        n_rec = state[0].shape[0] // cfg.n_ssm_layers
        rec_ids = jnp.where(records < n_rec, records, 0)
        pools, state = state, tuple(
            jnp.stack([take_rows(pool, c * n_rec + rec_ids)
                       for c in range(cfg.n_ssm_layers)]) for pool in state)
        state = (state[0].transpose(0, 1, 3, 2),
                 state[1].astype(jnp.float32))

    def cond(carry):
        i, done, *_ = carry
        return (i < max_new) & ~jnp.all(done)

    def body(carry):
        (i, done, cur, out, n_emitted, lens, kp, vp, ks, vs, rng,
         jstate, moe, sp) = carry
        with jax.named_scope("row_state"):
            live = (~done).astype(jnp.int32)
            # this step's token writes at buffer slot lens; done rows (and
            # any row at its page-table edge) drop via the sentinel n_tok,
            # which the forward turns into a drop in every layer
            pgs, flats = [], []
            for pool, table in zip(group_pools, group_tables):
                pg = jnp.take_along_axis(
                    table, jnp.minimum(lens // page, maxp - 1)[:, None],
                    axis=1)[:, 0]
                pgs.append(pg)
                flats.append(jnp.where(
                    done | (lens // page >= maxp), pool.shape[1] * page,
                    pg * page + lens % page))
            pg = pgs[0]
            flat = tuple(flats) if multi else flats[0]
            meta = jnp.stack([
                lens + live,          # kv_len incl. the token just written
                lens - (1 - live),    # qpos0 (done rows: inert block)
                live,                 # nq
                jnp.arange(R, dtype=jnp.int32),   # one tq=1 block per row
            ])
            positions = lens + kv_off.astype(jnp.int32)
            conv = ssm = None
            if records is not None:
                ssm = SsmTick(sp[0], sp[1], None, live)
            elif sp is not None:
                # one token a row: it follows the record under the page of
                # the token before it and is recorded under its own page
                last = jnp.take_along_axis(
                    group_tables[0],
                    jnp.clip((lens - 1) // page, 0, maxp - 1)[:, None],
                    axis=1)[:, 0]
                conv = ConvTick(sp, last, None, None,
                                jnp.where(flats[0] < n_pages * page, pg,
                                          n_pages))
        hidden, kp, vp, ks, vs, st, *rest = forward_hidden_ragged(
            params, cfg, cur[None], positions[None], kp, vp, tables,
            meta, flat, tq=1, interpret=interpret, shard=shard,
            k_scale=ks, v_scale=vs, shared=shared, conv=conv, ssm=ssm)
        if sp is not None:
            sp = rest[0]
        if st is not None:
            moe = moe + st
        logits = project_logits(params, cfg, hidden)[0]      # [R, V]
        nxt, rng = _draw(mask_logits, logits, jstate, rng, temperature,
                         top_p)
        with jax.named_scope("row_state"):
            nxt = jnp.where(done, pad_id, nxt)
            out = jax.lax.dynamic_update_slice_in_dim(out, nxt[:, None], i,
                                                      axis=1)
            n_emitted = n_emitted + jnp.where(done, 0, 1).astype(jnp.int32)
            lens = lens + jnp.where(done, 0, 1)
            jstate = advance(jstate, nxt, done)
            done = done | is_stop(nxt) | (n_emitted >= row_limit)
        return (i + 1, done, nxt, out, n_emitted, lens, kp, vp, ks, vs,
                rng, jstate, moe, sp)

    # unquantized loops carry scale placeholders as empty pytrees (None
    # is a valid while_loop carry leaf-less node)
    init = (jnp.asarray(1, jnp.int32), done0, tok0, out0, n0, lens0,
            k_pool, v_pool, k_scale, v_scale, rng, jstate0,
            None if cfg.moe is None else jnp.zeros(
                (moe_stats_len(cfg, interpret),), jnp.int32), state)
    # what the loop itself emits carries ``decode_loop`` and no sub-scope
    # (until PR 25: a copy of each loop-carried pool every step)
    with jax.named_scope("decode_loop"):
        (_, done, _, out, n_emitted, lens, k_pool, v_pool, k_scale,
         v_scale, _, jstate, moe, state) = jax.lax.while_loop(cond, body,
                                                              init)
    if records is not None:
        with jax.named_scope("decode_loop"), jax.named_scope("state_write"):
            state = (state[0].transpose(0, 1, 3, 2), state[1])
            for c in range(cfg.n_ssm_layers):
                pools = tuple(put_rows(pool, c * n_rec + rec_ids, rows[c])
                              for pool, rows in zip(pools, state))
        state = pools
    return (out, n_emitted, lens, k_pool, v_pool, k_scale, v_scale, jstate,
            moe, state)


def conv_past(row: np.ndarray, idx: np.ndarray, taps: int) -> np.ndarray:
    """``ConvTick.past`` from each flat token's row slot and its place in
    its row's chunk (``[Tp]`` each; a padded slot may say anything): entry
    ``[t, j-1]`` is ``t - j`` where the chunk reaches back ``j`` tokens,
    else the record's entry that holds that token (the record's last
    entry is the token before the chunk's first)."""
    n = len(row)
    j = np.arange(1, taps)[None]
    idx = idx[:, None]
    return np.where(
        idx >= j, np.maximum(np.arange(n)[:, None] - j, 0),
        n + row[:, None] * (taps - 1)
        + np.clip(taps - 1 - j + idx, 0, taps - 2)).astype(np.int32)


def _round_up(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return n


# Unified-kernel flat-layout constants (ISSUE 8): rows' query segments are
# padded to RAGGED_TQ-token blocks (the f32 sublane tile) and the flat
# token budget rounds to RAGGED_TOKEN_BUCKETS — the ONLY shape the unified
# programs key on, so steady state compiles one (chunk, decode) pair per
# token-budget bucket instead of prefill×decode per batch bucket.
RAGGED_TQ = 8
RAGGED_TOKEN_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096,
                        8192, 16384, 32768)
# What a bucket's padding costs a plain dense model from 2,048 slots up is
# the padding inside its last block of transformer.LIVE_BLOCK slots: the
# chunk forward's per-token work runs over the blocks that hold a token
# (``live_token_slots``; the tick's ``token_slots_live``).
# Row slots (page tables, sampling state, the decode loop's batch) round
# to these, independent of the token budget: the ContinuousBatcher's
# default 8 slots are one f32 sublane tile and one program.
RAGGED_ROW_BUCKETS = (8, 16, 32, 64)


class ContextOverflowError(ValueError):
    """Prompt does not fit the model's context window. The condensation layer
    catches this and retries after condensing (reference semantics:
    per_model_query.ex:93-120 retry-on-context-overflow)."""


@dataclasses.dataclass
class GenResult:
    token_ids: list[int]
    text: str
    n_prompt_tokens: int
    n_gen_tokens: int
    latency_s: float
    finish_reason: str  # "stop" | "length"
    n_cached_tokens: int = 0   # prompt prefix served from a resident KV session
    json_state: int = -1  # final grammar state (-1 = unconstrained); feed
                          # back as initial_json_state to resume a
                          # constrained stream mid-JSON (chunked
                          # continuation, models/scheduler.py)
    # Speculative serving attribution (models/speculative.py
    # BatchedSpeculator → models/scheduler.py): how much of this result
    # was produced by draft/verify rounds instead of vanilla decode
    # steps. Zero on the plain paths.
    spec_rounds: int = 0
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0
    # Chip-economics attribution (ISSUE 17, infra/costobs.py): this
    # row's share of the measured device wall for the jitted steps it
    # rode, split by real tokens. 0.0 with accounting off or on paths
    # that drive their own jits (v1 batch-1 speculative decoder).
    chip_ms: float = 0.0
    # Device phase times of a continuous-batcher row (ISSUE 24): the
    # prefill and decode fences it waited on, summed over the ticks it
    # rode, from its closed WaitClock (0.0 with the introspect plane
    # off, and on the paths that return phase times beside the result).
    prefill_ms: float = 0.0
    decode_ms: float = 0.0


PAGE = 128   # tokens per KV page


@dataclasses.dataclass
class _Session:
    """Resident KV state for one conversation (agent × model).

    ``tokens`` is the full conversation's token ids (host ints, cheap);
    their K/V live in fixed-size PAGES of the engine's device-resident
    pool — ``pages[j]`` holds buffer positions [j·PAGE, (j+1)·PAGE) of the
    working cache, which map to absolute positions offset by ``start_pos``
    (nonzero after sliding-window trimming drops leading pages). The next
    round's prompt reuses the longest common prefix — refinement rounds
    extend the prior prompt+response, so the whole previous conversation
    (response KV included) resumes for free; after condensation the prefix
    shrinks to the still-shared system prompt (reference analog: cached
    system prompt, consensus_handler.ex:126-152).
    """
    tokens: list[int]
    pages: list[int]
    start_pos: int = 0
    last_used: float = 0.0
    # synthetic donor-prefix marker (cross-session prefix sharing): the
    # pages belong to ANOTHER session; _run_paged refcount-acquires them
    # before using them as this row's dst prefix
    shared_prefix: bool = False
    # A model with a WINDOW group of attention layers beside the full one
    # (config.kv_groups, SessionStore.window): the session's pages in that
    # group's pools, ``wpages[j]`` for the same positions as ``pages[j]``
    # and 0 where the session holds none — the pages behind the window,
    # which it lets go at every store-back, and of an adopted prefix all
    # but the last window's. None: the model has one group.
    wpages: Optional[list[int]] = None
    # A model with ssm layers (SessionStore.records): the session's ONE
    # live record, the state at its end, which its decode steps update in
    # place. On a prefix marker: the snapshot the match ends at, and
    # ``matched`` the tokens the radix cache matched in all — more than
    # ``tokens`` where no snapshot stands at the match's end.
    record: int = 0
    matched: int = 0

    @property
    def resident_len(self) -> int:
        return len(self.tokens) - self.start_pos


class _IdPool:
    """An id space beside the store's own pages, with its own free list
    and reference counts (absent key = 1, as the store's): the page ids of
    a WINDOW group's pools (``_WindowPages``) and the RECORDS of a model
    with ssm layers (``SessionStore.records``: a record has one owner — a
    session, or a node of the radix cache — and a second reference only
    while a tick reads it, so that no eviction hands it out meanwhile). Id
    0 is scratch: never handed out, and 0 in a table or on a session means
    none. The store's lock guards it."""

    def __init__(self, n_ids: int):
        self.n_ids = n_ids
        self._free: list[int] = list(range(n_ids - 1, 0, -1))
        self._refs: dict[int, int] = {}

    def take(self, n: int) -> Optional[list[int]]:
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def acquire(self, pages) -> None:
        for p in pages:
            if p:
                self._refs[p] = self._refs.get(p, 1) + 1

    def release(self, pages) -> int:
        """Give up one reference a page; returns how many went free."""
        freed = 0
        for p in pages:
            if not p:
                continue
            c = self._refs.get(p, 1) - 1
            if c <= 0:
                self._refs.pop(p, None)
                self._free.append(p)
                freed += 1
            else:
                self._refs[p] = c
        return freed


class _WindowPages(_IdPool):
    """The page ids of a WINDOW group's pools (config.kv_groups). Page 0
    is scratch here too: a table entry of 0 is a page the session does not
    hold."""

    def __init__(self, n_pages: int, window: int):
        super().__init__(n_pages)
        self.tokens = window          # positions a query reaches back

    @property
    def n_pages(self) -> int:
        return self.n_ids

    def first_page(self, pos: int, page: int) -> int:
        """The first page a query at position ``pos`` still reaches."""
        return max(pos - self.tokens + 1, 0) // page


class SessionStore:
    """Paged session cache (VERDICT r2 item 4): sessions are PAGE LISTS
    into one pool; resume moves no KV data host-side — the jitted step
    gathers pages in-device from a [B, maxp] int32 table, and the decode
    step scatters prompt+response KV back to the pages in place. Page 0 is
    scratch (rows without a session write there). LRU sessions evict when
    the free list runs dry. Thread-safe; the ENGINE additionally serializes
    paged steps (the pool buffers are donated through them)."""

    def __init__(self, max_tokens: int = 262_144, page: int = PAGE,
                 window: Optional[tuple] = None, records: int = 0):
        from quoracle_tpu.analysis.lockdep import named_lock
        self.page = page
        self.n_pages = max(3, -(-max_tokens // page) + 1)   # +1 scratch
        self.max_tokens = (self.n_pages - 1) * page
        # ``window`` (tokens the group's pools hold, the window): the page
        # ids of a model's WINDOW group of attention layers, which a
        # session holds only as far back as the window reaches
        # (``alloc_window``; the engine's ``_run_paged`` lets go of the
        # rest at every store-back). The ids above are then the FULL
        # group's, which keeps every token.
        self.window: Optional[_WindowPages] = None
        if window is not None:
            self.window = _WindowPages(
                max(3, -(-window[0] // page) + 1), window[1])
        # ``records``: the size of the record pool of a model with ssm
        # layers (config.state_records; GenerateEngine._ensure_pool has
        # what a record holds). A session owns ONE live record; the radix
        # cache owns the snapshots it keeps; ``alloc_record`` hands them
        # out and evicts.
        self.records: Optional[_IdPool] = \
            _IdPool(max(3, records)) if records else None
        self.lock = named_lock("session.store", rlock=True)
        self._sessions: dict[str, _Session] = {}
        self._free: list[int] = list(range(self.n_pages - 1, 0, -1))
        # Page refcounts (cross-session PREFIX SHARING): a page referenced
        # by several sessions frees only when the last reference releases.
        # Absent key = 1 (every allocated page starts singly-owned).
        self._refs: dict[int, int] = {}
        # Radix prefix cache (models/prefix_cache.py): page-aligned token
        # blocks -> pool pages, holding its own reference on each, so
        # cached prefixes outlive the session that prefilled them. The
        # engine feeds it at store-back and consults it for new sessions.
        from quoracle_tpu.models.prefix_cache import RadixPrefixCache
        self.prefix_cache = RadixPrefixCache(self)
        # device pool arrays live on the engine (self.k/self.v set there);
        # the store only manages ids. Quantized-KV engines (ISSUE 13)
        # additionally hold the per-(token, kv-head) fp32 scale pools
        # ([L, n_pages, KV, page]) beside the int8 payload pools.
        self.k: Optional[jax.Array] = None
        self.v: Optional[jax.Array] = None
        self.k_scale: Optional[jax.Array] = None
        self.v_scale: Optional[jax.Array] = None
        # A model with conv layers: one state record a page a conv layer,
        # addressed by the same page ids (GenerateEngine._ensure_pool), so
        # adoption, copy-on-write, reference counts and eviction carry a
        # page's record with no bookkeeping of its own. A model with ssm
        # layers: the PAIR of record pools, addressed by record ids
        # (``records``), not by pages.
        self.state = None
        # Tiered KV (ISSUE 7, serving/kvtier.py): when attached, alloc's
        # eviction ladder DEMOTES victims to the host tier instead of
        # destroying them, and the engine's session lookup restores
        # hibernated sessions by page-in instead of re-prefill.
        self.tier = None
        self.model = ""          # metric label; engine sets cfg.name

    def get(self, key: str) -> Optional[_Session]:
        with self.lock:
            s = self._sessions.get(key)
            if s is not None:
                s.last_used = time.monotonic()
            return s

    def alloc(self, n: int, protect: tuple = (),
              evict: bool = True) -> Optional[list[int]]:
        """Take n pages from the free list, evicting LRU sessions (never
        the ``protect`` keys — the batch's own sessions) as needed.
        Returns None — WITHOUT evicting anything — when the request cannot
        be satisfied even by evicting every unprotected session.

        ``evict=False`` takes only from the free list: TEMP allocations
        (a ragged tick's scratch for sessionless rows) must never destroy
        other agents' resident sessions — or thrash the prefix cache — for
        pages that die at call end; the caller falls back to the gather
        programs instead.

        Eviction order: RADIX-CACHE LEAVES first (a cached-but-unreferenced
        prefix is recomputable; a resident session is another agent's live
        state), then LRU sessions. Attainability is counted exactly per
        page refcount — a page shared with a protected session, an
        in-flight adopter, or a cache node that cannot strip does NOT free
        when its victim releases it, so it must not be counted (the old
        len(pages) sum overcounted shared pages)."""
        with self.lock:
            if not evict:
                if n > len(self._free):
                    return None
                return [self._free.pop() for _ in range(n)]
            victims = [k for k in self._sessions if k not in protect]
            if n > self._attainable(victims):
                return None
            while len(self._free) < n:
                if self.prefix_cache.evict(n - len(self._free)):
                    continue
                if not victims:
                    break        # _attainable guarantees this can't happen
                lru = min(victims, key=lambda k: self._sessions[k].last_used)
                victims.remove(lru)
                sess = self._sessions.pop(lru)
                if self.tier is not None:
                    # eviction is demotion, not destruction (ISSUE 7):
                    # one device_get copies the victim host-side; the
                    # release below drops only the victim's own refs, so
                    # shared/COW pages other holders read stay resident
                    self.tier.demote_session(lru, sess)
                self._release_session(sess)
            if len(self._free) < n:
                # defensive: accounting drift — _attainable promised pages
                # the ladder could not deliver. Formerly a silent None;
                # now counted and flight-recorded (ISSUE 7 satellite) so
                # a refcount bug surfaces as telemetry, not as mystery
                # re-prefills.
                from quoracle_tpu.infra.flightrec import FLIGHT
                from quoracle_tpu.infra.telemetry import KV_ALLOC_DRIFT_TOTAL
                KV_ALLOC_DRIFT_TOTAL.inc(model=self.model)
                FLIGHT.record("kv_alloc_drift", model=self.model,
                              requested=n, free=len(self._free),
                              sessions=len(self._sessions))
                return None
            return [self._free.pop() for _ in range(n)]

    def _attainable(self, victims: list) -> int:
        """Exact count of pages reachable by evicting ``victims`` and then
        stripping freeable prefix-cache leaves: free list + cache pages
        whose every non-tree reference a victim would release + victim
        pages (outside the cache) all of whose references victims hold."""
        import collections
        released: collections.Counter = collections.Counter()
        for k in victims:
            for p in self._sessions[k].pages:
                if p:
                    released[p] += 1
        n_tree = self.prefix_cache.evictable_after(released)
        extra = sum(1 for p, c in released.items()
                    if not self.prefix_cache.holds(p)
                    and c >= self._refs.get(p, 1))
        return len(self._free) + n_tree + extra

    def alloc_window(self, n: int, protect: tuple = (),
                     evict: bool = True) -> Optional[list[int]]:
        """``alloc`` for the WINDOW group's pages. Its ladder, when the
        free list runs dry: first the radix cache lets go of window pages
        no session reads, least recently matched first (a node keeps its
        full-group page; a prefix stays adoptable wherever its last
        window is still cached, prefix_cache.py), then LRU sessions go
        (never the ``protect`` keys), whole, as in ``alloc``. None —
        with nothing evicted — when even that cannot make ``n`` pages."""
        w = self.window
        with self.lock:
            if n <= len(w._free) or not evict:
                return w.take(n)
            victims = [k for k in self._sessions if k not in protect]
            if n > self._attainable_window(victims):
                return None
            while len(w._free) < n:
                if self.prefix_cache.strip_window(n - len(w._free)):
                    continue
                if not victims:
                    break
                lru = min(victims, key=lambda k: self._sessions[k].last_used)
                victims.remove(lru)
                self._release_session(self._sessions.pop(lru))
            return w.take(n)

    def _attainable_window(self, victims: list) -> int:
        """Window-group pages reachable by evicting ``victims`` and
        stripping the radix cache's window pages: the free list, cached
        pages whose every other reference a victim would release, and
        victims' pages outside the cache that only victims hold."""
        import collections
        w = self.window
        released: collections.Counter = collections.Counter()
        for k in victims:
            for p in self._sessions[k].wpages or ():
                if p:
                    released[p] += 1
        cached = self.prefix_cache.window_pages()
        n_tree = sum(1 for p in cached
                     if w._refs.get(p, 1) - released.get(p, 0) <= 1)
        extra = sum(1 for p, c in released.items()
                    if p not in cached and c >= w._refs.get(p, 1))
        return len(w._free) + n_tree + extra

    def alloc_record(self, n: int = 1, protect: tuple = (),
                     evict: bool = True) -> Optional[list[int]]:
        """``alloc`` for RECORDS (a model with ssm layers). The ladder
        when the free list runs dry: first the radix cache gives up
        snapshots no tick is reading, least recently matched first (the
        node keeps its page: a later match there is cut back to a deeper
        snapshot or re-prefilled), then LRU sessions go (never the
        ``protect`` keys), whole, pages and record. None — with nothing
        evicted — when even that cannot make ``n`` records."""
        r = self.records
        with self.lock:
            if n <= len(r._free) or not evict:
                return r.take(n)
            victims = [k for k, s in self._sessions.items()
                       if k not in protect and s.record]
            if n > len(r._free) + self.prefix_cache.idle_records() \
                    + len(victims):
                return None
            from quoracle_tpu.infra.telemetry import SSM_STATE_RECORDS_TOTAL
            while len(r._free) < n:
                freed = self.prefix_cache.strip_records(n - len(r._free))
                if not freed:
                    if not victims:
                        break
                    lru = min(victims,
                              key=lambda k: self._sessions[k].last_used)
                    victims.remove(lru)
                    self._release_session(self._sessions.pop(lru))
                    freed = 1
                SSM_STATE_RECORDS_TOTAL.inc(freed, model=self.model,
                                            kind="evicted")
            return r.take(n)

    def _release_session(self, sess: "_Session") -> None:
        """Give up a session's references in every group, and its live
        record."""
        self._release(sess.pages)
        if sess.wpages:
            self.window.release(sess.wpages)
        if sess.record:
            self.records.release([sess.record])

    def _release(self, pages: list[int]) -> None:
        for p in pages:
            if p == 0:
                continue
            c = self._refs.get(p, 1) - 1
            if c <= 0:
                self._refs.pop(p, None)
                self._free.append(p)
            else:
                self._refs[p] = c

    def release(self, pages: list[int]) -> None:
        with self.lock:
            self._release(pages)

    def acquire(self, pages: list[int]) -> None:
        """Add a reference to already-allocated pages (prefix sharing:
        an adopter holds the donor's prefix pages alive past the donor's
        own drop/eviction)."""
        with self.lock:
            for p in pages:
                if p != 0:
                    self._refs[p] = self._refs.get(p, 1) + 1

    def match_prefix(self, tokens: Sequence[int],
                     max_reuse: int) -> Optional["_Session"]:
        """Cross-session prefix sharing (SURVEY §7 hard part 2's "system
        prompt cache", the vLLM automatic-prefix-caching analog), served
        by the RADIX PREFIX CACHE: the longest PAGE-ALIGNED cached token
        prefix of ``tokens`` — agents of one config share their system
        prompt verbatim, so a freshly spawned agent's first prefill can
        adopt those pages read-only instead of recomputing them, and the
        tree's own page references mean the prefix stays adoptable after
        the session that prefilled it dies. Alignment is a correctness
        requirement: the boundary page may be partially filled by the
        donor, and the adopter's own suffix must never write into a
        shared page. Returns a synthetic marker session (cached prefix
        tokens + page ids, shared_prefix=True) or None."""
        with self.lock:
            if self.tier is not None:
                # tiered extension (ISSUE 7): blocks stripped to the host
                # tier — or persisted to disk by a previous process —
                # page back in and re-enter the tree before the match, so
                # a restart-warm prefix is indistinguishable from a
                # resident one
                self.tier.extend_prefix(tokens, max_reuse)
            pages, matched = self.prefix_cache.match(tokens, max_reuse)
            if matched < self.page:
                return None
            if self.records is not None:
                # a new session starts from a SNAPSHOT: the match is cut
                # back to the deepest boundary that holds one (none: to
                # nothing), and the marker says how far the pages matched
                # — the engine prefills the rest again and takes a
                # snapshot where the match ended
                recs = self.prefix_cache.records_of(pages)
                held = max((j + 1 for j, r in enumerate(recs) if r),
                           default=0)
                return _Session(
                    tokens=list(tokens[:held * self.page]),
                    pages=pages[:held], start_pos=0, shared_prefix=True,
                    record=recs[held - 1] if held else 0, matched=matched)
            wpages = None
            if self.window is not None:
                # the full group's pages whole; of the window group's what
                # a query at the prefix's end still reaches (the match ends
                # where those are cached, prefix_cache._walk)
                first = self.window.first_page(matched, self.page)
                wpages = [0] * first + self.prefix_cache.window_pages_of(
                    pages[first:])
            return _Session(tokens=list(tokens[:matched]), pages=pages,
                            start_pos=0, shared_prefix=True, wpages=wpages)

    def insert_prefix(self, tokens: Sequence[int], pages: Sequence[int],
                      wpages: Optional[Sequence[int]] = None) -> int:
        """Feed a freshly stored session's full pages into the radix
        cache (the engine calls this at store-back for full-attention,
        non-VLM sessions with start_pos == 0; ``wpages``: the session's
        pages in a window group, for the same blocks). With a disk-backed tier
        attached, each full block also writes through to the checksummed
        prefix store (content-addressed — re-inserts cost one stat), so
        a restarted process warm-starts from these prefixes."""
        with self.lock:
            added = self.prefix_cache.insert(tokens, pages, wpages)
            # durable targets: the local disk store and/or the fleet
            # prefix service (ISSUE 12) — persist_block fans out to both
            if (self.tier is not None
                    and (self.tier.disk is not None
                         or self.tier.prefixd is not None)):
                for j in range(len(tokens) // self.page):
                    if j < len(pages) and pages[j]:
                        self.tier.persist_block(
                            [int(t) for t in tokens[:(j + 1) * self.page]],
                            pages[j])
            return added

    def put(self, key: str, sess: _Session) -> None:
        """Replace a session, releasing any of the old session's pages the
        new one no longer references."""
        sess.last_used = time.monotonic()
        with self.lock:
            old = self._sessions.get(key)
            if old is not None and old is not sess:
                self._release([p for p in old.pages if p not in sess.pages])
                if old.record and old.record != sess.record:
                    self.records.release([old.record])
                if old.wpages:
                    self.window.release([p for p in old.wpages
                                         if p not in (sess.wpages or ())])
            self._sessions[key] = sess
            if self.tier is not None:
                self.tier.discard_session(key)   # host copy now stale

    def put_raw(self, key: str, sess: _Session) -> None:
        """Replace WITHOUT page bookkeeping — the caller owns the page
        lifecycle (the engine's paged step releases explicitly)."""
        sess.last_used = time.monotonic()
        with self.lock:
            self._sessions[key] = sess
            if self.tier is not None:
                self.tier.discard_session(key)   # host copy now stale

    def register_restored(self, key: str, tokens: list, pages: list[int],
                          start_pos: int) -> "_Session":
        """Build + register a session the tier just paged back in
        (serving/kvtier.py restore_session — the tier stays ignorant of
        the _Session type, preserving the serving → infra dependency
        direction). Caller holds the lock and owns the pages."""
        sess = _Session(tokens=tokens, pages=pages, start_pos=start_pos)
        self.put_raw(key, sess)
        return sess

    def drop(self, key: str) -> None:
        with self.lock:
            s = self._sessions.pop(key, None)
            if s is not None:
                self._release_session(s)
            if self.tier is not None:
                # a dropped conversation must not resurrect from the
                # host tier under a reused id
                self.tier.discard_session(key)

    def free_pages(self) -> int:
        with self.lock:
            return len(self._free)

    def __len__(self) -> int:
        with self.lock:
            return len(self._sessions)


class CompileRegistry:
    """Per-engine record of every dispatched shape bucket (ISSUE 3):
    replaces the single first-shape ``_seen_shapes`` heuristic with an
    accountable ledger — each (shape-bucket) key remembers its first-call
    wall time (compile-dominated unless the persistent XLA cache held the
    executable) and how many later calls HIT it, and a sliding miss
    window trips a RECOMPILE-STORM gauge when more than ``threshold``
    new shapes compile inside ``window_s`` seconds. A storm is the
    classic capacity incident of bucketed serving (a caller bypassing
    the shape buckets turns every round into a 15-40 s compile) and is
    now attributable from telemetry instead of reproduced.

    Per ENGINE, not process-wide: each engine's jit wrappers own their
    compile caches, so a second engine for the same model genuinely
    recompiles — one shared ledger would miscount that as a hit. The
    process-wide aggregate lives in the METRICS counters the methods
    feed (quoracle_compile_cache_{hits,misses}_total)."""

    def __init__(self, model: str, window_s: float = 120.0,
                 threshold: int = 4):
        from quoracle_tpu.analysis.lockdep import named_lock
        self.model = model
        self.window_s = window_s
        self.threshold = threshold
        self._lock = named_lock("cache.compile")
        self._shapes: dict[tuple, dict] = {}
        self._miss_times: list[float] = []
        self.hits = 0
        self.misses = 0
        self.storm = False
        self.storms_total = 0

    def record(self, shape: tuple, wall_ms: float) -> bool:
        """Record one dispatch; returns True on a MISS (first sight of
        this shape bucket — the call paid the compile)."""
        from quoracle_tpu.infra.telemetry import COMPILE_HITS, COMPILE_MISSES
        # Chaos seam (ISSUE 11): "poison" salts the ledger key so every
        # dispatch books as a fresh miss — a ledger-level recompile
        # storm (the gauge/alerting path end-to-end) with zero actual
        # XLA compiles and zero effect on served bits.
        from quoracle_tpu.chaos.faults import CHAOS
        d = CHAOS.fire("compile.key", model=self.model)
        if d is not None and d.kind == "poison":
            shape = tuple(shape) + ("chaos-poison", d.n)
        now = time.monotonic()
        with self._lock:
            entry = self._shapes.get(shape)
            if entry is None:
                self._shapes[shape] = {
                    "shape": shape, "compile_ms": round(wall_ms, 1),
                    "ts": time.time(), "hits": 0,
                }
                self.misses += 1
                self._miss_times.append(now)
                miss = True
            else:
                entry["hits"] += 1
                self.hits += 1
                miss = False
            self._refresh_locked(now)
        (COMPILE_MISSES if miss else COMPILE_HITS).inc(model=self.model)
        return miss

    def _refresh_locked(self, now: float) -> None:
        from quoracle_tpu.infra.telemetry import (
            COMPILE_MISSES_IN_WINDOW, COMPILE_STORM,
        )
        self._miss_times = [t for t in self._miss_times
                            if now - t <= self.window_s]
        n = len(self._miss_times)
        storm = n >= self.threshold
        COMPILE_MISSES_IN_WINDOW.set(n, model=self.model)
        COMPILE_STORM.set(1.0 if storm else 0.0, model=self.model)
        if storm and not self.storm:
            self.storms_total += 1
            from quoracle_tpu.infra.flightrec import FLIGHT
            FLIGHT.record("compile_storm", model=self.model,
                          misses_in_window=n, window_s=self.window_s)
        self.storm = storm

    def refresh(self) -> None:
        """Re-evaluate the storm window against the clock (collector
        hook: a storm must clear at the next scrape even with no new
        dispatches aging the window)."""
        with self._lock:
            self._refresh_locked(time.monotonic())

    def snapshot(self, max_shapes: int = 32) -> dict:
        """JSON view for /api/resources: totals, hit rate, storm state,
        and the most expensive shape entries."""
        with self._lock:
            shapes = sorted(self._shapes.values(),
                            key=lambda e: -e["compile_ms"])[:max_shapes]
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": round(self.hits / total, 4) if total else None,
                "n_shapes": len(self._shapes),
                "storm": self.storm,
                "storms_total": self.storms_total,
                "misses_in_window": len(self._miss_times),
                "window_s": self.window_s,
                "threshold": self.threshold,
                "shapes": [{**e, "shape": "x".join(map(str, e["shape"]))}
                           for e in shapes],
            }


def _lcp(a: Sequence[int], b: Sequence[int]) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


def splice_session_prompt(tokenizer, sess_tokens: Sequence[int],
                          plain_ids: Sequence[int]) -> Optional[list[int]]:
    """Token-level session splice: rebuild a prompt so it shares the longest
    possible TOKEN prefix with ``sess_tokens`` (the session's actual ids —
    original prompt + the ids the model itself sampled).

    Refinement rounds append the assistant's raw text to the conversation
    and re-render the chat template (consensus/engine.py:161); re-ENCODING
    that text rarely reproduces the ids the model SAMPLED, so a plain token
    LCP stops at the previous round's prompt and the retained response KV
    (already resident, generate.py decode) never matches. Comparing decoded
    TEXT instead — and keeping the session's own ids for the shared region —
    resumes the whole previous conversation from resident KV; only the
    genuinely new suffix (template glue + the refinement message) re-encodes.

    Returns the spliced ids, or None when the plain encoding already matches
    the session at least as far (nothing to gain).
    """
    plain_reuse = _lcp(sess_tokens, plain_ids)
    canonical = tokenizer.decode_raw(plain_ids)
    if not canonical:
        return None
    # Fast path: clean extension — the refinement-round shape.
    if canonical.startswith(tokenizer.decode_raw(sess_tokens)):
        k = len(sess_tokens)
    else:
        # Largest k with decode(sess[:k]) a prefix of the new text (lo always
        # satisfies it). The predicate is NOT strictly monotone: a k ending
        # mid-UTF-8 decodes with trailing U+FFFD and fails even when a
        # LONGER prefix decodes cleanly — and such pockets CHAIN when
        # byte-fallback tokens straddle char boundaries (emoji runs). So:
        # bisect, then scan past the settle point while the mismatch is
        # confined to the trailing replacement-char run (still mid-char);
        # any clean success restarts the bisection from there. A mismatch
        # before the trailing U+FFFDs is genuine divergence (condensation
        # rewrote history) and ends the scan. A probe budget bounds the
        # worst-case decode work on the serving hot path.
        def _pred(j: int) -> bool:
            return canonical.startswith(tokenizer.decode_raw(sess_tokens[:j]))

        lo, hi = 0, len(sess_tokens)
        misses = 64
        while True:
            while lo < hi:
                mid = (lo + hi + 1) // 2
                if _pred(mid):
                    lo = mid
                else:
                    hi = mid - 1
            escaped = False
            j = lo + 1
            while j <= len(sess_tokens) and misses > 0:
                s = tokenizer.decode_raw(sess_tokens[:j])
                if canonical.startswith(s):
                    lo, hi, escaped = j, len(sess_tokens), True
                    break
                misses -= 1
                if not canonical.startswith(s.rstrip("�")):
                    break       # diverges before the partial-char tail
                j += 1
            if not escaped:
                break
        k = lo
    # ≥1 suffix token must run through prefill to produce last-position
    # logits; and the splice must beat the plain prefix to be worth
    # diverging from the canonical tokenization.
    while k > plain_reuse:
        suffix = tokenizer.encode(
            canonical[len(tokenizer.decode_raw(sess_tokens[:k])):])
        if suffix:
            return list(sess_tokens[:k]) + suffix
        k -= 1
    return None


class GenerateEngine:
    """Stateful serving wrapper around the functional core for ONE model.

    Holds params (device-resident), compiles (prefill+decode) per shape
    bucket, and exposes a list-in/list-out generate(). The pool runtime
    (models/runtime.py) owns one Engine per pool member.

    With ``mesh`` set, the engine serves SHARDED: params placed per
    parallel/mesh.param_specs (Megatron-style tp), the KV cache constrained
    to cache_spec, and inputs laid out on the dp axis — GSPMD inserts the
    psums, which ride ICI (SURVEY.md §2.9 tp-sharded serving). A pool on a
    multi-chip slice gives each member its own sub-mesh
    (parallel.mesh.pool_submeshes) and the host scheduler overlaps members
    (models/runtime.py). mesh=None is the single-chip degenerate case.

    generate() is thread-safe: the host-side RNG draw is locked; everything
    else is functional.
    """

    BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)
    # The tests' one seam into the paged path (ragged_fallback): True
    # routes every paged tick to the gather programs, which the equality
    # tests compare the ragged path against. Nothing in the program sets it.
    _force_gather_decode = False

    def __init__(self, cfg: ModelConfig, params: dict, tokenizer,
                 max_seq: Optional[int] = None, seed: int = 0,
                 prompt_buckets: Sequence[int] = (128, 256, 512, 1024, 2048, 4096, 8192),
                 mesh=None, session_max_bytes: int = 2 << 30,
                 sp_window: Optional[int] = None,
                 quantize_weights: bool = False,
                 quantize_kv: bool = False):
        import threading

        from quoracle_tpu.analysis.lockdep import named_lock
        self.cfg = cfg
        self.mesh = mesh
        self.last_prefill_tokens = 0   # diagnostics: suffix actually computed
        # Int8 quantized serving (ISSUE 13, models/quant.py): weights
        # quantize per-channel at build; the KV pool stores int8 pages
        # with per-(token, kv-head) scales beside them. Single-device
        # engines only for now — shard_params has no placement rule for
        # {q8, scale} leaves, and the flat ragged layout is the
        # quantized serving path (it can't ride a dp axis anyway).
        self.quantize_weights = bool(quantize_weights)
        self.quantize_kv = bool(quantize_kv)
        if (self.quantize_weights or self.quantize_kv) \
                and mesh is not None:
            raise ValueError(
                f"engine {cfg.name}: int8 quantized serving "
                f"(--quantize-weights/--quantize-kv) serves on "
                f"single-device engines; drop the mesh or the flags")
        # latent attention / expert layers are served on the ragged paged
        # path alone: one refusal per path, at start
        for on, what in ((mesh is not None, "a device mesh (--tp > 1)"),
                         (self.quantize_kv, "--quantize-kv"),
                         (self.quantize_weights, "--quantize-weights")):
            if on:
                require_plain(cfg, what)
        # Params dtype drives the dense working-cache dtype; capture it
        # BEFORE weight quantization turns leaves int8.
        self._raw_param_dtype = jax.tree.leaves(params)[0].dtype
        self._raw_param_bytes = sum(
            int(x.size) * jnp.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(params))
        if self.quantize_weights:
            from quoracle_tpu.models.quant import quantize_params
            params = quantize_params(params, cfg)
        if mesh is not None:
            from quoracle_tpu.parallel.mesh import shard_params
            params = shard_params(params, mesh, cfg)
        self.params = params
        self.tokenizer = tokenizer
        self.max_seq = max_seq or cfg.context_window
        # Sequence-parallel serving (mesh with an sp axis): prompts longer
        # than one chip's window (``sp_window``, default max_seq / sp) take
        # the ring-attention prefill path; shorter prompts stay on the
        # dense path (SURVEY §5 long-context).
        sp_size = int(mesh.shape.get("sp", 1)) if mesh is not None else 1
        self.sp_window = (sp_window if sp_window is not None
                          else (self.max_seq // sp_size if sp_size > 1
                                else None))
        self.prompt_buckets = tuple(b for b in prompt_buckets if b <= self.max_seq)
        self._rng = jax.random.PRNGKey(seed)
        self._rng_lock = named_lock("engine.rng")
        # KV cache dtype follows the params (bf16 serving, fp32 parity tests)
        # — mixing dtypes would fail the in-place cache scatter. With
        # quantized KV the POOL dtype is int8 (scales beside the pages);
        # dense working caches stay at the params dtype.
        self.cache_dtype = self._raw_param_dtype
        self.pool_dtype = jnp.int8 if self.quantize_kv else self.cache_dtype
        # Session budget in BYTES, converted to tokens for the store: per
        # cached token K+V cost 2 · L · n_kv · hd · itemsize — at 8B scale
        # that's ~128 KiB/token, so a token-denominated default would permit
        # tens of GiB of HBM before "bounding" anything. Also capped at 32
        # full context windows so tiny-KV test models don't allocate a
        # giant pool from the byte budget alone. Int8 pools count their
        # per-(token, head) scales, so resident_kv_tokens lands at ~2x
        # the bf16 figure at the same byte budget (ISSUE 13).
        token_bytes = self.kv_token_pool_bytes()
        window = None
        if len(cfg.kv_groups) > 1:
            # A window group beside the full one: each gets HALF of the
            # byte budget, at its own byte rate (a token costs the full
            # group its layers' rows for as long as the session lives, the
            # window group its layers' for a window). The split is a
            # statement, not a tuning: how many tokens either group ends
            # up holding follows the traffic — the full group every
            # session's whole length, the window group a window and a
            # tick's new tokens a live row plus what the radix cache keeps
            # for adoption (docs/DEPLOY.md §19 has the operator's view).
            assert len(cfg.kv_groups) == 2, cfg.kv_groups
            session_max_bytes //= 2
            wbytes = cfg.kv_bytes_per_token(
                dtype_bytes=jnp.dtype(self.pool_dtype).itemsize, group=1)
            window = (max(PAGE, min(session_max_bytes // wbytes,
                                    32 * self.max_seq)),
                      cfg.kv_groups[1][0])
        self.sessions = SessionStore(
            max_tokens=max(PAGE, min(session_max_bytes // token_bytes,
                                     32 * self.max_seq)), window=window,
            records=cfg.state_records if cfg.n_ssm_layers else 0)
        self.sessions.model = cfg.name     # metric label (alloc drift,
                                           # tier counters)
        # The paged steps donate the pool buffers; calls that touch the pool
        # must serialize (concurrent members use separate engines).
        self._paged_lock = named_lock("engine.paged")
        # Cross-session prefix sharing (SessionStore.match_prefix, backed
        # by the radix prefix cache in models/prefix_cache.py): ON by
        # default for full-attention models; the windowed check lives at
        # the adoption site. Tests flip it off to compare. The flag gates
        # both cache lookups and store-back inserts.
        self.prefix_sharing = True
        # a model with ssm layers: {session id: tokens} where a first-wave
        # row of a batch is to leave a snapshot for the rows deferred
        # behind it (``_prefix_wave_split``; read under ``_paged_lock``)
        self._wave_snaps: dict = {}
        # Grammar-table cache has its OWN lock so sessionless calls (image
        # rows, models/runtime.py) can run concurrently with the continuous
        # batcher's sessioned chunks without serializing on _paged_lock —
        # the cache dict (build/evict) is their only shared mutable state.
        # Order: _paged_lock → _grammar_lock (sessioned path), never
        # reversed.
        self._grammar_lock = named_lock("cache.grammar")
        if self.quantize_kv:
            from quoracle_tpu.infra.telemetry import (
                QUANT_KV_BYTES_PER_TOKEN,
            )
            QUANT_KV_BYTES_PER_TOKEN.set(float(token_bytes),
                                         model=cfg.name)
        if self.quantize_weights:
            from quoracle_tpu.models.quant import params_nbytes
            from quoracle_tpu.infra.telemetry import (
                QUANT_BYTES_SAVED_TOTAL,
            )
            QUANT_BYTES_SAVED_TOTAL.inc(
                max(0, self._raw_param_bytes - params_nbytes(self.params)),
                model=cfg.name, tier="weights")
        # Padding-waste accounting (ISSUE 8 satellite): per generate call
        # (one continuous-batcher tick), how many chunk-token slots the
        # device actually processed vs the tick's real tokens. Ragged
        # ticks reclaim the difference; /api/resources serves the totals.
        self.pad_real_tokens = 0
        self.pad_padded_tokens = 0
        self.pad_live_slots = 0
        self.pad_ticks = 0
        # Per-call hand-off from _run_unified to _record_telemetry /
        # _note_padding. THREAD-LOCAL: sessionless calls (image rows) run
        # concurrently with the batcher's sessioned chunks and must not
        # steal a unified tick's shape key or padded-token count.
        self._pending = threading.local()
        # Per-call phase diagnostics (read by the bench + dashboards):
        # wall seconds of the last prefill / decode device phases.
        self.last_prefill_s = 0.0
        self.last_decode_s = 0.0
        # Replica-tier role restriction (ISSUE 10, serving/cluster.py):
        # None = unrestricted (the monolithic default). "prefill" caps
        # every generate at ONE new token — a prefill-tier engine exists
        # to build KV and emit the first token; a longer decode on it is
        # a routing bug the guard turns into a loud error instead of a
        # silent MFU regression. "decode" is descriptive metadata only
        # (decode engines still prefill continuation suffixes).
        self.role: Optional[str] = None
        # Compile ledger (ISSUE 3): every dispatched shape bucket with
        # wall time + hit/miss counts, plus the recompile-storm window —
        # /api/resources serves its snapshot per engine.
        self.compiles = CompileRegistry(cfg.name)
        self._build_step()

    def _build_step(self):
        """Two jits per call instead of one fused step: PREFILL fills the
        cache from the prompt chunk, DECODE runs the sampling loop. The
        boundary costs one dispatch (~µs) and buys an honest per-phase
        latency split (prefill is compute-bound on the MXU, decode is
        HBM-bandwidth-bound — a single fused number hides which one
        regressed; SURVEY §5 tracing asks for the split)."""
        cfg = self.cfg
        mesh = self.mesh
        # How the flash kernel is laid over this engine's mesh
        # (ops/flash_attention.attend_auto): heads on tp when whole GQA
        # groups divide, rows on dp; None on a single device.
        attn_shard = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from quoracle_tpu.parallel.mesh import cache_spec
            kv_sharding = NamedSharding(mesh, cache_spec(cfg, mesh))
            tp = int(mesh.shape.get("tp", 1))
            attn_shard = (
                mesh,
                "tp" if tp > 1 and cfg.n_heads % tp == 0
                and cfg.n_kv_heads % tp == 0 else None,
                "dp" if int(mesh.shape.get("dp", 1)) > 1 else None)
        self.attn_shard = attn_shard

        def _constrain(cache: KVCache) -> KVCache:
            if mesh is None:
                return cache
            # Pin the cache layout (kv heads on tp, batch on dp) so the
            # decode loop carries a stable sharding instead of whatever
            # GSPMD back-propagates from the first write.
            return cache._replace(
                k=jax.lax.with_sharding_constraint(cache.k, kv_sharding),
                v=jax.lax.with_sharding_constraint(cache.v, kv_sharding))

        @functools.partial(jax.jit, static_argnames=("cache_len",))
        def step_prefill(params, tokens, prompt_lens, cache_len: int):
            B = tokens.shape[0]
            cache = _constrain(init_cache(cfg, B, cache_len,
                                          dtype=self.cache_dtype))
            return prefill(params, cfg, tokens, prompt_lens, cache,
                           shard=attn_shard)

        if mesh is not None and int(mesh.shape.get("sp", 1)) > 1:
            ring_args = (mesh, "sp",
                         "dp" if int(mesh.shape.get("dp", 1)) > 1 else None,
                         "tp" if int(mesh.shape.get("tp", 1)) > 1 else None)

            @functools.partial(jax.jit, static_argnames=("cache_len",))
            def step_prefill_ring(params, tokens, prompt_lens,
                                  cache_len: int):
                # Long-prompt path: the prompt exceeds one chip's window,
                # so prefill attention runs sequence-parallel over the sp
                # ring; the cache stays S-sharded (cache_spec) so the full
                # KV never materializes on one chip.
                B = tokens.shape[0]
                cache = _constrain(init_cache(cfg, B, cache_len,
                                              dtype=self.cache_dtype))
                return prefill(params, cfg, tokens, prompt_lens, cache,
                               ring=ring_args)

            self._step_prefill_ring = step_prefill_ring
        else:
            self._step_prefill_ring = None

        if cfg.vision is not None:
            from quoracle_tpu.models.vision import (
                splice_image_embeds, vision_encode,
            )

            @functools.partial(jax.jit, static_argnames=("cache_len",))
            def step_prefill_vlm(params, tokens, prompt_lens, pixels,
                                 cache_len: int):
                # VLM prefill: the ViT tower runs inside the same jit as
                # the decoder prefill — projected patches replace the
                # image-placeholder tokens' embeddings (LLaVA-style soft
                # prompt; models/vision.py).
                B = tokens.shape[0]
                cache = _constrain(init_cache(cfg, B, cache_len,
                                              dtype=self.cache_dtype))
                img = vision_encode(params["vision"], cfg.vision, pixels)
                embeds = params["embed"][tokens]
                if cfg.scale_embeddings:
                    # text embeds scale BEFORE the splice: projected image
                    # features enter at the projector's own scale (standard
                    # VLM semantics — an sqrt(dim) blow-up on soft tokens
                    # would swamp every gemma-family prompt)
                    embeds = (embeds.astype(jnp.float32)
                              * (cfg.dim ** 0.5)).astype(embeds.dtype)
                embeds = splice_image_embeds(embeds, tokens, img,
                                             cfg.image_token_id)
                return prefill(params, cfg, tokens, prompt_lens, cache,
                               input_embeds=embeds, shard=attn_shard)

            self._step_prefill_vlm = step_prefill_vlm
        else:
            self._step_prefill_vlm = None

        @functools.partial(jax.jit, static_argnames=("max_new",),
                           donate_argnums=(1, 2))   # cache updates in place
        def step_decode(params, k_buf, v_buf, lens, last_logits, rng,
                        temperature, top_p, active, row_limit,
                        json_table, json_state, max_new: int):
            cache = _constrain(KVCache(k=k_buf, v=v_buf, lens=lens))
            return decode(params, cfg, cache, last_logits, rng,
                          temperature, top_p, max_new, cfg.eos_token_id,
                          active=active, row_limit=row_limit,
                          pad_id=self.tokenizer.pad_id,
                          stop_ids=cfg.stop_token_ids,
                          json_table=json_table, json_state=json_state)

        KV, HD, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
        page = self.sessions.page
        # Int8 KV pools (ISSUE 13): the gather programs dequantize page
        # reads into the dense working cache and requantize on scatter;
        # the unified ragged path writes int8+scale directly inside its
        # forward. ``quant`` is a trace-time constant, so the two modes
        # compile disjoint programs off one code path.
        quant = self.quantize_kv
        work_dtype = self.cache_dtype

        def _gather_work(k_pool, v_pool, k_scale, v_scale, src_pages):
            """Resident pages → dense working cache [L, B, maxp·page,
            KV, HD], the [KV, HD] view of the pool's KV·HD lanes taken
            on the gathered pages (int8 pools dequantize per (token,
            kv-head) on the gather)."""
            B, maxp = src_pages.shape
            kw = k_pool[:, src_pages].reshape(L, B, maxp * page, KV, HD)
            vw = v_pool[:, src_pages].reshape(L, B, maxp * page, KV, HD)
            if not quant:
                return kw, vw
            ks = k_scale[:, src_pages].transpose(0, 1, 2, 4, 3) \
                .reshape(L, B, maxp * page, KV)
            vs = v_scale[:, src_pages].transpose(0, 1, 2, 4, 3) \
                .reshape(L, B, maxp * page, KV)
            kw = (kw.astype(jnp.float32) * ks[..., None]).astype(work_dtype)
            vw = (vw.astype(jnp.float32) * vs[..., None]).astype(work_dtype)
            return kw, vw

        def _quant_scatter(k_pool, v_pool, k_scale, v_scale, k_work,
                           v_work, dst_pages):
            """Working cache → dst pages, requantizing per (token,
            kv-head) with the shared write rule (models/quant.kv_quant);
            scales land page-structured beside the pages."""
            from quoracle_tpu.models.quant import kv_quant
            B, maxp = dst_pages.shape
            kp = k_work.reshape(L, B, maxp, page, KV, HD)
            vp = v_work.reshape(L, B, maxp, page, KV, HD)
            kq, ks = kv_quant(kp)          # ks: [L, B, maxp, page, KV]
            vq, vs = kv_quant(vp)
            k_pool = k_pool.at[:, dst_pages].set(
                kq.reshape(L, B, maxp, page, KV * HD), mode="drop")
            v_pool = v_pool.at[:, dst_pages].set(
                vq.reshape(L, B, maxp, page, KV * HD), mode="drop")
            k_scale = k_scale.at[:, dst_pages].set(
                ks.transpose(0, 1, 2, 4, 3), mode="drop")
            v_scale = v_scale.at[:, dst_pages].set(
                vs.transpose(0, 1, 2, 4, 3), mode="drop")
            return k_pool, v_pool, k_scale, v_scale
        # The ragged kernel under a mesh: each tp shard runs the
        # single-device kernel on its local heads under shard_map (heads
        # independent, no collective; whole GQA groups per shard). The
        # token-major flat layout can't ride a dp axis (rows interleave
        # in one token axis) nor an sp ring, so the ragged programs run
        # on single-device engines and tp-only meshes; other meshes take
        # the gather programs (ragged_fallback, condition (a)).
        ragged_shard = None
        if (attn_shard is not None and attn_shard[1] is not None
                and int(mesh.shape.get("sp", 1)) == 1
                and int(mesh.shape.get("dp", 1)) == 1):
            ragged_shard = (mesh, "tp")
        self._ragged_shard = ragged_shard
        self._ragged_ok = mesh is None or ragged_shard is not None
        # query tokens of one row that share a walk of its pages in the
        # chunk forward's attention (ops/paged_attention, the tile kernel);
        # 0: a latent pool's kernel walks a block at a time, no tile table
        from quoracle_tpu.ops.paged_attention import (
            decode_walk_pages, latent_walk_pages, ragged_tile,
        )
        # (kinds of attention layer share the one tile table: the widest's)
        self._ragged_tile = ragged_tile(
            cfg.max_heads, cfg.head_dim, RAGGED_TQ,
            cfg.max_heads // cfg.n_kv_heads) if cfg.latent is None else 0
        # pages a loop iteration of the decode program's walks carries, as
        # the kernel reckons it: from a page's bytes on one shard, or for a
        # latent pool from the keys a block scores at once (its chunk
        # forward's walks, a block of 8 queries each, carry the same).
        # Only the tick span's ``attn_walk_steps`` reads it.
        self._walk_block = decode_walk_pages(
            self.sessions.page,
            cfg.n_kv_heads // (int(mesh.shape["tp"]) if ragged_shard else 1),
            cfg.head_dim, jnp.dtype(self.pool_dtype).itemsize) \
            if cfg.latent is None else latent_walk_pages(self.sessions.page)

        @functools.partial(jax.jit, static_argnames=())
        def step_paged_prefill(params, k_pool, v_pool, k_scale, v_scale,
                               src_pages, tokens, prefix_lens,
                               chunk_lens, kv_off):
            # Resume from the page pool: ONE in-device gather materializes
            # each row's resident prefix into the working cache (HBM→HBM at
            # full bandwidth; zero host-side data movement — the host only
            # uploaded the [B, maxp] int32 page table), then only the
            # suffix chunk runs through the stack. Int8 pools dequantize
            # on the gather (scales are None otherwise).
            B, maxp = src_pages.shape
            kw, vw = _gather_work(k_pool, v_pool, k_scale, v_scale,
                                  src_pages)
            cache = _constrain(KVCache(k=kw, v=vw,
                                       lens=jnp.zeros((B,), jnp.int32)))
            return prefill_chunk(params, cfg, tokens, prefix_lens,
                                 chunk_lens, cache, kv_off=kv_off,
                                 shard=attn_shard)

        @functools.partial(jax.jit, static_argnames=("max_new",),
                           donate_argnums=(1, 2, 5, 6))
        def step_paged_decode(params, k_pool, v_pool, k_scale, v_scale,
                              k_work, v_work, lens,
                              dst_pages, kv_off, last_logits, rng,
                              temperature, top_p, active, row_limit,
                              json_table, json_state, max_new: int):
            cache = _constrain(KVCache(k=k_work, v=v_work, lens=lens))
            out, n_emitted, cache, jstate = decode(
                params, cfg, cache, last_logits, rng, temperature, top_p,
                max_new, cfg.eos_token_id, active=active,
                row_limit=row_limit, pad_id=self.tokenizer.pad_id,
                stop_ids=cfg.stop_token_ids, json_table=json_table,
                json_state=json_state, kv_off=kv_off)
            # Scatter prompt + response KV back into the pool pages in
            # place (pool donated → aliased update). Rows without a session
            # point every dst slot at scratch page 0. Int8 pools
            # requantize on the scatter (scales beside the pages).
            B, maxp = dst_pages.shape
            if quant:
                k_pool, v_pool, k_scale, v_scale = _quant_scatter(
                    k_pool, v_pool, k_scale, v_scale, cache.k, cache.v,
                    dst_pages)
            else:
                kp = cache.k.reshape(L, B, maxp, page, KV * HD)
                vp = cache.v.reshape(L, B, maxp, page, KV * HD)
                k_pool = k_pool.at[:, dst_pages].set(kp, mode="drop")
                v_pool = v_pool.at[:, dst_pages].set(vp, mode="drop")
            # cache.k/v returned (and discarded by the host) so the donated
            # work buffers alias an output — the decode loop then runs
            # truly in place instead of copying the working cache.
            return out, n_emitted, cache.lens, k_pool, v_pool, k_scale, \
                v_scale, cache.k, cache.v, jstate

        @functools.partial(jax.jit, static_argnames=("kmax", "need_probs"))
        def step_paged_verify(params, k_pool, v_pool, k_scale, v_scale,
                              src_pages, tokens,
                              prefix_lens, chunk_lens, kv_off, k_arr,
                              temperature, json_table, json_state,
                              kmax: int, need_probs: bool):
            # Speculative VERIFY (models/speculative.py BatchedSpeculator):
            # teacher-forced chunk forward over [pending, d_1..d_{K-1}]
            # against each row's resident paged prefix, projecting logits
            # at the last k_arr positions of every row's chunk — the
            # positions whose argmax decides draft acceptance. Same gather
            # as step_paged_prefill; the caller scatters the chunk KV back
            # to pages (step_scatter_prompt), so a committed prefix is
            # resident for the next round and rejected draft KV is just
            # dead weight the next chunk's prefill overwrites (the LCP
            # session resume IS the rollback).
            B, maxp = src_pages.shape
            kw, vw = _gather_work(k_pool, v_pool, k_scale, v_scale,
                                  src_pages)
            cache = _constrain(KVCache(k=kw, v=vw,
                                       lens=jnp.zeros((B,), jnp.int32)))
            T = tokens.shape[1]
            positions = (prefix_lens[:, None]
                         + jnp.arange(T, dtype=jnp.int32)[None, :])
            positions = positions + kv_off.astype(jnp.int32)[:, None]
            total = (prefix_lens + chunk_lens).astype(jnp.int32)
            hidden, cache = forward_hidden(
                params, cfg, tokens, positions, cache,
                write_offset=prefix_lens.astype(jnp.int32), kv_lens=total,
                kv_pos_offset=kv_off, shard=attn_shard)
            cache = cache._replace(lens=total)
            # verify window = each row's last k_arr chunk positions
            widx = jnp.clip(
                chunk_lens[:, None] - k_arr[:, None]
                + jnp.arange(kmax, dtype=jnp.int32)[None, :], 0, T - 1)
            wh = jnp.take_along_axis(hidden, widx[:, :, None], axis=1)
            logits = project_logits(params, cfg, wh).astype(jnp.float32)
            if json_table is not None:
                # per-position grammar states walk IN-DEVICE from the
                # state after ctx (json_state) over the window's draft
                # tokens — the mask applied at position t equals the one
                # vanilla decode would apply there (bit-exactness).
                wtok = jnp.take_along_axis(tokens, widx, axis=1)

                def adv(s, tok):
                    nxt = json_table[jnp.clip(s, 0, None),
                                     tok].astype(jnp.int32)
                    s2 = jnp.where(s >= 0, nxt, s)
                    return s2, s2

                _, rest = jax.lax.scan(adv, json_state, wtok[:, 1:].T)
                states = jnp.concatenate(
                    [json_state[None, :], rest], axis=0).T    # [B, kmax]
                V = logits.shape[-1]
                logits = grammar_mask(
                    logits.reshape(B * kmax, V), states.reshape(-1),
                    json_table, cfg.eos_token_id).reshape(B, kmax, V)
            ids = jnp.argmax(logits, axis=-1)                 # [B, kmax]
            if need_probs:
                probs = jax.nn.softmax(
                    logits / jnp.maximum(temperature, 1e-6)[:, None, None],
                    axis=-1)
                # greedy rows in a mixed batch: one-hot keeps the host
                # acceptance rule exact (accept iff d_i == argmax p_i)
                probs = jnp.where(
                    (temperature <= 0)[:, None, None],
                    jax.nn.one_hot(ids, logits.shape[-1]), probs)
            else:
                # dead [B, kmax, V] outputs still cost HBM writes — drop
                # them in the hot greedy path (same as the v1 decoder)
                probs = jnp.zeros((1, 1, 1), jnp.float32)
            return ids, probs, cache

        @functools.partial(jax.jit, donate_argnums=(0, 1, 4, 5))
        def step_scatter_prompt(k_pool, v_pool, k_scale, v_scale, k_work,
                                v_work, dst_pages):
            # Working cache (prefix gather + verify chunk) → dst pages:
            # the gather verify's store-back (step_paged_verify has no
            # decode loop to scatter at its end). k_work/v_work are
            # donated so the working cache's HBM frees here — XLA warns
            # the donation isn't aliasable into an output; that's the
            # point, it's a free, not an alias. Int8 pools requantize on
            # the scatter (scales beside the pages).
            if quant:
                return _quant_scatter(k_pool, v_pool, k_scale, v_scale,
                                      k_work, v_work, dst_pages)
            B, maxp = dst_pages.shape
            kp = k_work.reshape(L, B, maxp, page, KV * HD)
            vp = v_work.reshape(L, B, maxp, page, KV * HD)
            k_pool = k_pool.at[:, dst_pages].set(kp, mode="drop")
            v_pool = v_pool.at[:, dst_pages].set(vp, mode="drop")
            return k_pool, v_pool, k_scale, v_scale

        # the three unified programs donate the pools (and an int8
        # engine's scale pools; None donates nothing): input and output
        # are one buffer, updated in place
        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 12),
                           static_argnames=("tq", "tile"))
        def step_paged_ragged(params, k_pool, v_pool, k_scale, v_scale,
                              tokens_flat,
                              positions_flat, row_tables, block_meta,
                              tiles, flat_dst, last_idx, state=None,
                              conv=None, *, tq: int, tile: int):
            # UNIFIED mixed chunk forward (ISSUE 8): one ragged launch
            # per layer over the token-major flattened tick — prefill
            # suffixes, 1-token continuations, any mix of lengths — with
            # chunk KV scattered to the rows' pages inside the forward.
            # Shapes key on (flat token budget, page-table width) only:
            # the batch-bucket × prompt-bucket program matrix collapses.
            # ``state`` / ``conv``: a model with conv layers hands in its
            # state pool (donated like the others) and where the tick's
            # rows read and write it (ConvTick's fields after the pool).
            # A model with ssm layers hands in its PAIR of record pools
            # and SsmTick's fields after them.
            ssm = None
            if cfg.n_ssm_layers:
                ssm, conv = SsmTick(*state, *conv), None
            elif state is not None:
                conv = ConvTick(state, *conv)
            hidden, k_pool, v_pool, k_scale, v_scale, moe, *rest = \
                forward_hidden_ragged(
                    params, cfg, tokens_flat[None], positions_flat[None],
                    k_pool, v_pool, row_tables, block_meta, flat_dst,
                    tq=tq, shard=ragged_shard, k_scale=k_scale,
                    v_scale=v_scale, tiles=tiles, tile=tile, conv=conv,
                    ssm=ssm)
            last_h = hidden[0][last_idx]                  # [R, D]
            last = project_logits(params, cfg, last_h[:, None])[:, 0, :]
            return (last, k_pool, v_pool, k_scale, v_scale, moe,
                    rest[0] if rest else None)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4),
                           static_argnames=("tq", "tile", "kmax",
                                            "need_probs"))
        def step_paged_ragged_verify(params, k_pool, v_pool, k_scale,
                                     v_scale, tokens_flat,
                                     positions_flat, row_tables,
                                     block_meta, tiles, flat_dst, widx,
                                     temperature, json_table, json_state,
                                     tq: int, tile: int, kmax: int,
                                     need_probs: bool):
            # Speculative VERIFY through the SAME unified kernel: the
            # teacher-forced chunk rides the ragged forward (KV scattered
            # to pages — committed prefixes resident for the next round,
            # LCP resume is still the rollback) and verdict logits
            # project at the flat indices of each row's last K positions.
            hidden, k_pool, v_pool, k_scale, v_scale, _ = \
                forward_hidden_ragged(
                    params, cfg, tokens_flat[None], positions_flat[None],
                    k_pool, v_pool, row_tables, block_meta, flat_dst,
                    tq=tq, shard=ragged_shard, k_scale=k_scale,
                    v_scale=v_scale, tiles=tiles, tile=tile)
            wh = hidden[0][widx]                          # [R, kmax, D]
            logits = project_logits(params, cfg, wh).astype(jnp.float32)
            R = widx.shape[0]
            if json_table is not None:
                # per-position grammar states walk in-device over the
                # window's draft tokens — identical recipe (and therefore
                # identical masks) to step_paged_verify
                wtok = tokens_flat[widx]                  # [R, kmax]

                def adv(s, tok):
                    nxt = json_table[jnp.clip(s, 0, None),
                                     tok].astype(jnp.int32)
                    s2 = jnp.where(s >= 0, nxt, s)
                    return s2, s2

                _, rest = jax.lax.scan(adv, json_state, wtok[:, 1:].T)
                states = jnp.concatenate(
                    [json_state[None, :], rest], axis=0).T
                V = logits.shape[-1]
                logits = grammar_mask(
                    logits.reshape(R * kmax, V), states.reshape(-1),
                    json_table, cfg.eos_token_id).reshape(R, kmax, V)
            ids = jnp.argmax(logits, axis=-1)             # [R, kmax]
            if need_probs:
                probs = jax.nn.softmax(
                    logits / jnp.maximum(temperature,
                                         1e-6)[:, None, None], axis=-1)
                probs = jnp.where(
                    (temperature <= 0)[:, None, None],
                    jax.nn.one_hot(ids, logits.shape[-1]), probs)
            else:
                probs = jnp.zeros((1, 1, 1), jnp.float32)
            return ids, probs, k_pool, v_pool, k_scale, v_scale

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 17),
                           static_argnames=("max_new",))
        def step_paged_decode_ragged(params, k_pool, v_pool, k_scale,
                                     v_scale, tables, shared,
                                     pool_lens, kv_off, last_logits, rng,
                                     temperature, top_p, active,
                                     row_limit, json_table, json_state,
                                     state=None, records=None, *,
                                     max_new: int):
            # Decode continuation of the unified tick: KV written straight
            # to pages inside the loop (no tail buffer, no tail scatter);
            # attention is the same ragged kernel at tq=1 (int8 pools
            # quantize each step's token on write), told by ``shared``
            # which rows' leading pages to read once for all of them.
            return decode_ragged(
                params, cfg, k_pool, v_pool, tables, pool_lens, kv_off,
                last_logits, rng, temperature, top_p, max_new,
                cfg.eos_token_id, active=active, row_limit=row_limit,
                pad_id=self.tokenizer.pad_id, stop_ids=cfg.stop_token_ids,
                json_table=json_table, json_state=json_state,
                shard=ragged_shard, k_scale=k_scale, v_scale=v_scale,
                shared=shared, state=state, records=records)

        self._step_paged_ragged = step_paged_ragged
        self._step_paged_ragged_verify = step_paged_ragged_verify
        self._step_paged_decode_ragged = step_paged_decode_ragged

        self._step_prefill = step_prefill
        self._step_decode = step_decode
        self._step_paged_prefill = step_paged_prefill
        self._step_paged_verify = step_paged_verify
        self._step_paged_decode = step_paged_decode
        self._step_scatter_prompt = step_scatter_prompt

    @contextlib.contextmanager
    def _paged_locked(self):
        """Hold ``_paged_lock``; on the batcher's thread the wait for it is
        the tick's operation ``lock``."""
        with tick_op("lock"):
            self._paged_lock.acquire()
        try:
            yield
        finally:
            self._paged_lock.release()

    def next_rng(self) -> jax.Array:
        with self._rng_lock:
            self._rng, k = jax.random.split(self._rng)
            return k

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        temperature: Sequence[float] | float = 1.0,
        top_p: Sequence[float] | float = 1.0,
        max_new_tokens: Sequence[int] | int = 256,
        rng: Optional[jax.Array] = None,
        session_ids: Optional[Sequence[Optional[str]]] = None,
        constrain_json: Optional[Sequence[bool]] = None,
        action_enums: Optional[Sequence[Optional[Sequence[str]]]] = None,
        images: Optional[Sequence] = None,
        initial_json_state: Optional[Sequence[Optional[int]]] = None,
        image_sessions: bool = False,
    ) -> list[GenResult]:
        """``session_ids`` (aligned with prompts; None entries opt out)
        enables KV residency: each row reuses the longest token prefix it
        shares with its session's resident cache and prefills only the
        suffix; the prompt KV is stored back for the next round. Consensus
        refinement rounds extend the previous prompt, so rounds 2+ skip
        re-prefilling the whole conversation (SURVEY §7 hard part 2).

        ``action_enums`` (aligned; only read where constrain_json is True)
        upgrades the JSON grammar to the schema-aware variant: the row's
        top-level ``"action"`` value is constrained to the given names
        (models/constrained.py action_enum).

        ``images`` (aligned; None entries = text-only row) enables the VLM
        path on vision-configured models: each entry is a preprocessed
        [H, W, 3] float array whose projected patches replace the row's
        image-placeholder tokens. By default image rows skip KV sessions
        (identical placeholder ids under different images must not
        prefix-match); ``image_sessions=True`` keeps them — the CALLER
        asserts the hazard is gone by keying each row's session id with an
        image digest (models/runtime.py does), so a resumed prefix always
        encodes the same image and VLM refinement rounds stop re-prefilling
        their whole prompt (VERDICT r3 weak #5)."""
        has_images = images is not None and any(i is not None
                                                for i in images)
        if self.role == "prefill":
            budgets = (max_new_tokens if not isinstance(
                max_new_tokens, int) else [max_new_tokens])
            if any(int(b) > 1 for b in budgets):
                raise ValueError(
                    f"engine {self.cfg.name} is a prefill-tier replica "
                    f"(role='prefill'): it builds KV and emits at most "
                    f"one token per row; route decode to a decode-tier "
                    f"replica (serving/cluster.py)")
        if has_images and self.cfg.vision is None:
            raise ValueError(f"model {self.cfg.name} has no vision tower")
        if has_images and not image_sessions:
            # Image rows opt out of sessions (identical placeholder ids
            # under different images must not prefix-match). Text rows
            # KEEP their resident prefixes: a mixed batch splits into a
            # VLM sub-batch and a (possibly paged) text sub-batch.
            txt_idx = [i for i, im in enumerate(images) if im is None]
            if txt_idx and session_ids is not None and any(
                    session_ids[i] for i in txt_idx):
                img_idx = [i for i, im in enumerate(images)
                           if im is not None]

                def pick(seq, idxs):
                    if seq is None or isinstance(seq, (int, float)):
                        return seq
                    return [seq[i] for i in idxs]

                res_img = self.generate(
                    [prompts[i] for i in img_idx],
                    pick(temperature, img_idx), pick(top_p, img_idx),
                    pick(max_new_tokens, img_idx), None, None,
                    pick(constrain_json, img_idx),
                    pick(action_enums, img_idx),
                    [images[i] for i in img_idx],
                    pick(initial_json_state, img_idx))
                res_txt = self.generate(
                    [prompts[i] for i in txt_idx],
                    pick(temperature, txt_idx), pick(top_p, txt_idx),
                    pick(max_new_tokens, txt_idx), None,
                    pick(session_ids, txt_idx),
                    pick(constrain_json, txt_idx),
                    pick(action_enums, txt_idx), None,
                    pick(initial_json_state, txt_idx))
                merged: list = [None] * len(prompts)
                for j, i in enumerate(img_idx):
                    merged[i] = res_img[j]
                for j, i in enumerate(txt_idx):
                    merged[i] = res_txt[j]
                return merged
            session_ids = None       # image-only (or sessionless) batch
        if session_ids is not None and any(session_ids):
            # Sessioned calls serialize per engine: session lookup, page
            # allocation/eviction, the pool-donating steps, and the store
            # must be one atomic unit, or a concurrent call could evict and
            # recycle pages this batch still references.
            with self._paged_locked():
                with tick_op("wave_split"):
                    later = self._prefix_wave_split(prompts, session_ids)
                if later:
                    return self._generate_waves(
                        later, prompts, temperature, top_p, max_new_tokens,
                        rng, session_ids, constrain_json, action_enums,
                        images, initial_json_state)
                return self._generate_impl(
                    prompts, temperature, top_p, max_new_tokens, rng,
                    session_ids, constrain_json, action_enums, images,
                    initial_json_state)
        return self._generate_impl(prompts, temperature, top_p,
                                   max_new_tokens, rng, session_ids,
                                   constrain_json, action_enums, images,
                                   initial_json_state)

    def _prefix_wave_split(self, prompts, session_ids) -> list[int]:
        """Intra-batch prefix dedup (the consensus fan-out shape: K new
        agent sessions arrive in ONE batch sharing the built system/task
        prompt): rows that would re-prefill a page-aligned prefix another
        row of the SAME batch is about to prefill — and that the radix
        cache does not cover yet — are deferred to a SECOND wave, which
        then adopts the first wave's freshly cached pages. The shared
        prompt prefills once; rows 2..K prefill only their suffix.
        Returns the deferred row indices ([] = single wave)."""
        if (not self.prefix_sharing or session_ids is None
                or self.cfg.sliding_window is not None
                or self.cfg.vision is not None):
            return []
        st = self.sessions
        page = st.page
        first: list[int] = []
        later: list[int] = []
        self._wave_snaps.clear()
        from collections import Counter
        sid_counts = Counter(s for s in session_ids if s)
        with st.lock:
            seen: set = set()
            for i, sid in enumerate(session_ids):
                if not sid or sid in seen:
                    continue        # sessionless / duplicate-sid rows
                seen.add(sid)
                if sid_counts[sid] > 1:
                    # duplicated sid in one batch: deferring the first
                    # occurrence would hand the session to the duplicate —
                    # keep the existing first-occurrence-owns semantics
                    continue
                if st._sessions.get(sid) is not None:
                    continue        # resident: resumes off its own pages
                cap = len(prompts[i]) - 1
                best, donor = 0, None
                for j in first:
                    l = min(_lcp(prompts[j], prompts[i]), cap)
                    if (l // page) * page > best:
                        best, donor = (l // page) * page, j
                # defer only when waiting gains >= 1 full page over what
                # the cache would already serve this row today
                if (best >= page and
                        st.prefix_cache.match_len(prompts[i], cap)
                        < best):
                    later.append(i)
                    # a model with ssm layers: the first wave's row leaves
                    # a snapshot where the deferred row will start from
                    # (its deepest such boundary: a row takes one a tick)
                    sid_j = session_ids[donor]
                    self._wave_snaps[sid_j] = max(
                        self._wave_snaps.get(sid_j, 0), best)
                else:
                    first.append(i)
        return later

    def _generate_waves(self, later, prompts, temperature, top_p,
                        max_new_tokens, rng, session_ids, constrain_json,
                        action_enums, images, initial_json_state):
        """Two-wave sessioned generate (caller holds _paged_lock): wave 1
        prefills the batch's unique prefixes and stores them (radix-cache
        inserts included), wave 2 runs the deferred duplicate-prefix rows,
        which now adopt those pages and prefill only their suffixes.
        Phase/telemetry fields accumulate across both waves."""
        n = len(prompts)
        later_set = set(later)
        first_idx = [i for i in range(n) if i not in later_set]

        def pick(seq, idxs):
            if seq is None or isinstance(seq, (int, float)):
                return seq
            return [seq[i] for i in idxs]

        rng1 = rng2 = None
        if rng is not None:
            rng1, rng2 = jax.random.split(rng)

        def run(idxs, wave_rng):
            return self._generate_impl(
                [prompts[i] for i in idxs], pick(temperature, idxs),
                pick(top_p, idxs), pick(max_new_tokens, idxs), wave_rng,
                pick(session_ids, idxs), pick(constrain_json, idxs),
                pick(action_enums, idxs),
                pick(images, idxs) if images is not None else None,
                pick(initial_json_state, idxs))

        res1 = run(first_idx, rng1)
        w1 = (self.last_prefill_tokens, self.last_prefill_s,
              self.last_decode_s)
        res2 = run(later, rng2)
        self.last_prefill_tokens += w1[0]
        self.last_prefill_s += w1[1]
        self.last_decode_s += w1[2]
        merged: list = [None] * n
        for j, i in enumerate(first_idx):
            merged[i] = res1[j]
        for j, i in enumerate(later):
            merged[i] = res2[j]
        return merged

    def kv_signature(self) -> str:
        """The engine's exact KV geometry + dtype as a string: the disk
        prefix store's directory key AND the cross-replica handoff
        compatibility check (serving/handoff.py) — two engines may only
        exchange KV bytes when their signatures match exactly."""
        cfg = self.cfg
        # Quantized KV is part of the signature (ISSUE 13): a
        # quantized↔unquantized peer pair must reject handoff BEFORE any
        # bytes move (and never share a disk-store directory) — the
        # degrade is a cold re-prefill, exactly the version-skew path.
        # Unquantized engines keep the historic signature unchanged.
        geometry = (f"x{cfg.n_kv_heads}x{cfg.head_dim}" if cfg.latent is None
                    else "xlatent" + "+".join(map(str, cfg.kv_pools)))
        if cfg.n_conv_layers:
            geometry += (f"-A{cfg.n_attn_layers}"
                         f"-conv{cfg.n_conv_layers}x{cfg.state_lanes}")
        if cfg.n_ssm_layers:
            geometry += (f"-A{cfg.n_attn_layers}-ssm{cfg.n_ssm_layers}x"
                         + "+".join(str(n) for n, _ in cfg.state_record))
        if len(cfg.kv_groups) > 1:
            geometry += "-G" + "+".join(
                f"{layers}w{window or 0}" for window, layers in cfg.kv_groups)
        return (f"{cfg.name.replace('/', '_')}-L{cfg.n_layers}"
                f"{geometry}-p{self.sessions.page}"
                f"-{jnp.dtype(self.pool_dtype).name}"
                + ("-q8kv" if self.quantize_kv else ""))

    def attach_tier(self, host_mb: int = 256,
                    disk_dir: Optional[str] = None,
                    disk_gb: float = 8.0):
        """Enable tiered KV (ISSUE 7, serving/kvtier.py): HBM eviction
        demotes to a ``host_mb``-bounded host page store, touches restore
        by page-in, and (with ``disk_dir``) prefix-cache blocks persist
        to a checksummed disk store — ``disk_gb``-bounded, oldest-LRU
        entries pruned — that warm-starts the next process. The disk
        signature binds entries to this engine's exact KV geometry and
        dtype, so mismatched processes can never exchange bytes.
        Returns the TierManager (also at ``sessions.tier``)."""
        from quoracle_tpu.serving.kvtier import TierManager
        cfg = self.cfg
        require_plain(cfg, "the host and disk KV tiers (--host-kv-mb, "
                           "--disk-kv-dir, and --disaggregate, which "
                           "hands sessions over through them)")
        tier = TierManager(self.sessions, model=cfg.name,
                           host_mb=host_mb, disk_dir=disk_dir,
                           paged_lock=self._paged_lock,
                           signature=self.kv_signature(),
                           disk_gb=disk_gb)
        self.sessions.tier = tier
        return tier

    def prefetch_session(self, session_id: str) -> bool:
        """Warm a hibernated session before its owner needs it (the
        scheduler/agent-tick prefetch hook, ISSUE 7): restore it by
        page-in if it sits in the host tier. TRY-acquires the paged lock
        — a busy engine skips the warm-up rather than blocking the
        caller; the sessioned generate path restores synchronously
        anyway, so prefetch is purely an overlap optimization."""
        tier = self.sessions.tier
        if tier is None or not tier.has_session(session_id):
            return False
        if self.sessions.get(session_id) is not None:
            return False                  # already resident
        if not self._paged_lock.acquire(blocking=False):
            return False
        try:
            self._ensure_pool()
            return tier.restore_session(session_id) is not None
        finally:
            self._paged_lock.release()

    def drop_session(self, session_id: str) -> None:
        """Release a session's pages — including any image-digest-qualified
        variants ("<sid>|img:<sha>", models/runtime.py VLM sessions).
        Serialized with sessioned generate calls so an in-flight batch
        never loses pages it references: a sessioned tick holds the lock
        from end to end, and what the caller waited for it is booked here
        (``quoracle_session_drop_wait_ms``, and ``qtpu.session_drop`` on
        the caller's line of a profiler trace)."""
        with jax.profiler.TraceAnnotation("qtpu.session_drop") as span:
            t0 = time.monotonic_ns()
            self._paged_lock.acquire()
            t1 = time.monotonic_ns()
            try:
                self.sessions.drop(session_id)
                prefix = session_id + "|img:"
                for key in [k for k in self.sessions._sessions
                            if k.startswith(prefix)]:
                    self.sessions.drop(key)
                tier = self.sessions.tier
                if tier is not None:
                    # digest-keyed variants may live ONLY in the host tier
                    # (hibernated) — discard those too, or a dead agent's
                    # image sessions linger until host-LRU
                    for key in [k for k in tier.host.sessions
                                if k.startswith(prefix)]:
                        tier.discard_session(key)
            finally:
                self._paged_lock.release()
                t2 = time.monotonic_ns()
                SESSION_DROP_WAIT_MS.observe((t1 - t0) / 1e6,
                                             model=self.cfg.name)
                span.set_metadata(model=self.cfg.name,
                                  lock_wait_us=(t1 - t0) // 1000,
                                  held_us=(t2 - t1) // 1000)

    def session_tokens(self, session_id: str) -> Optional[list[int]]:
        """The session's resident conversation ids (host ints, prompt +
        retained response), or None. Callers use these to SPLICE the next
        round's prompt (splice_session_prompt) so its token prefix matches
        the resident KV exactly. Snapshot copy: generate replaces the
        _Session object wholesale, never mutates tokens in place.
        Hibernated sessions answer from the host tier — the splice works
        against the hibernated ids and the generate then restores the
        pages (tokens are host ints in either tier)."""
        s = self.sessions.get(session_id)
        if s is not None:
            return list(s.tokens)
        tier = self.sessions.tier
        if tier is not None:
            return tier.peek_tokens(session_id)
        return None

    def verify_chunk(self, prompts, session_ids, verify_k, *,
                     temperature=0.0, constrain_json=None,
                     action_enums=None, initial_json_state=None,
                     need_probs: bool = False) -> list[dict]:
        """Speculative VERIFY against the paged session KV (the target
        side of models/speculative.py BatchedSpeculator): each row i's
        prompt is ctx_i + proposals_i[:-1] and ``verify_k[i]`` =
        len(proposals_i); ONE teacher-forced chunk forward resumes the
        row's session (LCP prefix reuse, exactly like generate) and
        returns the target's verdict at the K_i positions that predict
        proposals_i — ``ids`` (grammar-masked argmax per position) plus
        ``probs`` ([K_i, V] masked softmax) when ``need_probs``. The
        chunk KV is stored back to the session's pages, so the session
        afterwards holds the full prompt; rejected draft KV past the
        committed prefix is overwritten by the next round's suffix
        prefill (LCP resume IS the rollback — no explicit cache surgery).

        ``initial_json_state`` is the row's grammar state after ctx_i
        (the scheduler's relative-state convention). Every row must be
        sessioned; speculative serving never runs on sliding-window or
        vision engines (the BatchedSpeculator enforces eligibility)."""
        if self.cfg.n_conv_layers or self.cfg.n_ssm_layers \
                or self.sessions.window is not None:
            # a rejected draft's tokens would have advanced the conv or
            # ssm state, and no record is kept to roll it back to; a window
            # group's pages behind a rejected draft are gone by then
            raise ValueError(unsupported_path(
                self.cfg, "verify_chunk (speculative drafts)"))
        assert session_ids is not None and all(session_ids), \
            "verify_chunk requires a session per row"
        assert len(verify_k) == len(prompts)
        assert all(1 <= int(k) <= len(p)
                   for k, p in zip(verify_k, prompts))
        with self._paged_locked():
            return self._generate_impl(
                prompts, temperature, 1.0, 1, None, session_ids,
                constrain_json, action_enums, None, initial_json_state,
                verify=([int(k) for k in verify_k], bool(need_probs)))

    def _generate_impl(self, prompts, temperature=1.0, top_p=1.0,
                       max_new_tokens=256, rng=None, session_ids=None,
                       constrain_json=None, action_enums=None,
                       images=None,
                       initial_json_state=None, verify=None):
        t0 = time.monotonic()
        n = len(prompts)
        if n == 0:
            return []
        vk = verify[0] if verify is not None else None
        temps = [temperature] * n if isinstance(temperature, (int, float)) else list(temperature)
        tops = [top_p] * n if isinstance(top_p, (int, float)) else list(top_p)
        # Per-row decode budgets: consensus rows grouped into one batch keep
        # their own caps (traced row limits; the static bound is the max).
        if isinstance(max_new_tokens, int):
            row_budgets = [max_new_tokens] * n
        else:
            row_budgets = [int(m) for m in max_new_tokens]
            assert len(row_budgets) == n

        max_prompt = max(len(p) for p in prompts)
        if max_prompt >= self.max_seq:
            # The context layer (condensation) is responsible for fitting
            # prompts; a prompt at/over the window is a caller bug, parallel
            # to the reference's context-overflow error path
            # (per_model_query.ex:93-120) — loud, never silent garbage.
            raise ContextOverflowError(
                f"prompt of {max_prompt} tokens >= max_seq {self.max_seq} "
                f"for model {self.cfg.name}")

        # Session prefix lookup: how much of each prompt is already
        # resident in the page pool. ``reuse_abs`` counts ABSOLUTE tokens
        # reused; the row's buffer-index prefix is reuse_abs - start_pos
        # (sliding-window sessions trim leading pages, offsetting the
        # buffer). A session id appearing twice in one batch would collide
        # on its pages — later duplicates run sessionless.
        # Long-prompt sequence-parallel path: prompts beyond one chip's
        # window ring-prefill over sp. Sessions don't compose with the
        # S-sharded ring layout yet — such rows run a full fresh prefill.
        use_ring = (self._step_prefill_ring is not None
                    and self.sp_window is not None
                    and max_prompt > self.sp_window)

        sess_rows: list[Optional[_Session]] = [None] * n
        reuse_abs = [0] * n
        reprefill = 0        # matched tokens that hold no conv or ssm state
        # a model with ssm layers: where each new row's chunk forward is to
        # take a SNAPSHOT for the radix cache (tokens from the prompt's
        # start, a whole number of pages; 0: nowhere) — the end of a cached
        # prefix that the row matched and found no snapshot at
        snap_at = [0] * n
        kv_off_host = [0] * n
        store_sids: list[Optional[str]] = [None] * n
        paged = False
        if session_ids is not None and not use_ring:
            with tick_op("session_lookup"):
                seen: set[str] = set()
                for i, sid in enumerate(session_ids):
                    if not sid or sid in seen:
                        continue
                    seen.add(sid)
                    store_sids[i] = sid
                    paged = True
                    s = self.sessions.get(sid)
                    if s is None and self.sessions.tier is not None \
                            and self.sessions.tier.has_session(sid):
                        # hibernated session: restore by page-in instead of
                        # re-prefill (ISSUE 7; the caller holds _paged_lock,
                        # so the pool scatter cannot race a paged step). A
                        # restore failure of ANY kind degrades to re-prefill
                        # — the tier is never a correctness dependency.
                        try:
                            with tick_op("tier_restore"):
                                self._ensure_pool()
                                s = self.sessions.tier.restore_session(sid)
                        except Exception:     # noqa: BLE001 — fall back
                            import logging
                            logging.getLogger(__name__).exception(
                                "kv restore failed for %s; re-prefilling",
                                sid)
                            s = None
                    p_win = 0
                    if s is not None and self.sessions.window is not None:
                        p_win = self._window_match(s, prompts[i])
                        if not p_win:
                            # what the window layers would need behind the
                            # match is gone: the session is forgotten, and
                            # the row starts over as a new one (from the
                            # radix cache, where that still holds its prompt)
                            self.sessions.drop(sid)
                            s = None
                    lost = 0
                    if s is not None and self.cfg.n_ssm_layers:
                        # the session's ONE record is the state at its
                        # end: a prompt that parts from its tokens before
                        # that cannot go on from it. The session is
                        # forgotten, and the row starts over as a new one,
                        # from the deepest snapshot the radix cache holds
                        # on its way
                        lost = min(_lcp(s.tokens, prompts[i]),
                                   len(prompts[i]) - 1)
                        if lost < len(s.tokens):
                            self.sessions.drop(sid)
                            s = None
                    if s is None:
                        # Cross-session prefix sharing: a NEW session whose
                        # prompt starts with a RADIX-CACHED page-aligned
                        # prefix (same system prompt across the tree's
                        # agents; models/prefix_cache.py) adopts those pages
                        # read-only — _run_paged refcount-acquires them and
                        # uses them as this row's dst prefix, so only the
                        # suffix prefills.
                        if (self.prefix_sharing
                                and self.cfg.sliding_window is None
                                # VLM engines: identical placeholder token
                                # ids can front DIFFERENT images — adopting
                                # another session's prefix KV would condition
                                # on the wrong image (the digest-keyed
                                # session safeguard, models/runtime.py)
                                and self.cfg.vision is None):
                            # verify mode: the last K_i positions are the
                            # verify window and must run through the chunk
                            # forward — never be served from reused KV
                            cap = (len(prompts[i]) - 1 if vk is None
                                   else len(prompts[i]) - vk[i])
                            if self.sessions.tier is not None:
                                # tiered lookup may page disk/host blocks
                                # into the pool — it must exist first
                                self._ensure_pool()
                            with tick_op("prefix_match"):
                                d = (self.sessions.match_prefix(
                                    prompts[i], cap) if cap > 0 else None)
                            if d is not None and d.tokens:
                                sess_rows[i] = d
                                reuse_abs[i] = len(d.tokens)
                                kv_off_host[i] = 0
                            if self.cfg.n_ssm_layers:
                                matched = d.matched if d is not None else 0
                                # where a later wave's rows will start
                                # from, or the end of a match that found
                                # no snapshot: the deeper of the two
                                snap = max(matched,
                                           self._wave_snaps.pop(sid, 0))
                                if snap > reuse_abs[i]:
                                    snap_at[i] = snap
                                reprefill += max(matched, lost) \
                                    - reuse_abs[i]
                        continue
                    # ≥1 suffix token must run to produce last-position
                    # logits (verify mode: the whole K_i window must run —
                    # see above)
                    p = p_win or min(_lcp(s.tokens, prompts[i]),
                                     len(prompts[i]) - 1 if vk is None
                                     else len(prompts[i]) - vk[i])
                    if (self.cfg.sliding_window is not None
                            and p < len(s.tokens)):
                        # Windowed models resume only on clean extension: after
                        # a divergence the resident window [start_pos, p) would
                        # leave a hole below the new tokens' attention windows.
                        continue
                    if self.cfg.n_conv_layers and p < len(s.tokens):
                        # conv state is held at the session's end and at page
                        # boundaries only (_ensure_pool): a match that ends
                        # elsewhere is reused up to the last boundary at or
                        # below it, and the rest runs again
                        held = p // self.sessions.page * self.sessions.page
                        reprefill += p - held
                        p = held
                    if p > s.start_pos:
                        sess_rows[i] = s
                        reuse_abs[i] = p
                        kv_off_host[i] = s.start_pos

        if not self.cfg.plain:
            # no dense-cache forward for this family: rows without a
            # session ride the paged path on scratch pages
            if use_ring or images is not None:
                require_plain(self.cfg,
                              "the sequence-parallel ring / image rows")
            paged = True
        if self.cfg.n_conv_layers or self.cfg.n_ssm_layers:
            with tick_op("account"):
                self._note_state(sess_rows, reuse_abs, reprefill)
        prefixes = [r - o for r, o in zip(reuse_abs, kv_off_host)]  # buffer
        suffixes = [list(p[r:]) for p, r in zip(prompts, reuse_abs)]
        max_chunk = max(len(s) for s in suffixes)
        # verify chunks are K-token windows (steady state K ≤ 8, plus the
        # occasional full re-prefill after eviction) — padding them to the
        # 128-floor prompt buckets would forward 16-20x the needed
        # positions per round. The verify jit is its own program, so the
        # extra small buckets cost no compile churn on the main prefill.
        T = _round_up(max_chunk,
                      tuple(sorted({8, 16, 32, 64,
                                    *self.prompt_buckets}))
                      if vk is not None else self.prompt_buckets)
        if use_ring:
            sp = int(self.mesh.shape["sp"])
            T = ((T + sp - 1) // sp) * sp   # ring shards the chunk evenly
        B = _round_up(n, self.BATCH_BUCKETS)
        if self.mesh is not None:
            # batch rows ride the dp axis — pad the bucket to a multiple
            dp = int(self.mesh.shape.get("dp", 1))
            B = ((B + dp - 1) // dp) * dp
        # Bucket the decode bound too: consensus computes a DYNAMIC max_tokens
        # per round (reference per_model_query.ex:136-145), which would
        # otherwise trigger one XLA compile per unique value. Per-row TRACED
        # limits stop each row at its own budget, so bucketing costs nothing.
        max_new = _round_up(min(max(row_budgets), self.max_seq - 1),
                            (64, 128, 256, 512, 1024, 2048, 4096))
        # The padded chunk is written at write_offset=prefix_i, so the
        # buffer must cover max(prefix) + T (the full padded extent, NOT
        # just max prompt length): dynamic_update_slice CLAMPS start
        # indices, and an under-sized buffer would silently scribble the
        # pad region over valid prefix KV.
        cache_len = _round_up(max(prefixes) + T,
                              self.prompt_buckets) + max_new
        page = self.sessions.page
        maxp = -(-cache_len // page)      # pages per row (paged path)
        if paged:
            cache_len = maxp * page

        with tick_op("layout"):
            tokens = np.full((B, T), self.tokenizer.pad_id, np.int32)
            pre_arr = np.zeros((B,), np.int32)
            off_arr = np.zeros((B,), np.int32)
            chunk_arr = np.ones((B,), np.int32)  # padded rows: 1 (harmless)
            limits = np.ones((B,), np.int32)
            for i, s in enumerate(suffixes):
                tokens[i, :len(s)] = s
                pre_arr[i] = prefixes[i]
                off_arr[i] = kv_off_host[i]
                chunk_arr[i] = max(1, len(s))
                total = max(1, len(prompts[i]))
                limits[i] = max(1, min(row_budgets[i],
                                       self.max_seq - total))
            temp_arr = np.zeros((B,), np.float32)
            temp_arr[:n] = temps
            top_arr = np.ones((B,), np.float32)
            top_arr[:n] = tops
            active = np.zeros((B,), bool)
            active[:n] = True

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            row = NamedSharding(self.mesh, P("dp"))
            mat = NamedSharding(self.mesh, P("dp", None))
            put = lambda a, s: jax.device_put(a, s)
        else:
            row = mat = None
            put = lambda a, s: jnp.asarray(a)
        with tick_op("rng"):
            rng_key = rng if rng is not None else self.next_rng()
        with tick_op("h2d"):
            samp = (put(temp_arr, row), put(top_arr, row),
                    put(active, row), put(limits, row))

        # JSON grammar constraint: rows flagged True start in their
        # grammar's start state; -1 rows sample unconstrained. Rows may
        # carry different action enums — distinct grammars stack into one
        # table with offset state ids.
        grammar_bases = None
        if constrain_json is not None and any(constrain_json):
            enums = [None] * n
            if action_enums is not None:
                enums = [tuple(sorted(set(e))) if e else None
                         for e in action_enums]
            distinct = sorted({e for e, f in zip(enums, constrain_json)
                               if f},
                              key=lambda e: (e is not None, e or ()))
            table, offsets, bases = self._json_table_device(tuple(distinct))
            grammar_bases = [bases.get(e, 0) for e in enums]
            jstate = np.full((B,), -1, np.int32)
            for i, flag in enumerate(constrain_json):
                if flag:
                    # resume a mid-stream grammar state (chunked
                    # continuation): states travel RELATIVE to their
                    # grammar's block base, so they survive different
                    # table stackings across calls
                    init_js = (initial_json_state[i]
                               if initial_json_state is not None else None)
                    if init_js is not None and init_js >= 0:
                        jstate[i] = grammar_bases[i] + init_js
                    else:
                        jstate[i] = offsets[enums[i]]
            json_args = (table, put(jstate, row))
            jstate_np = jstate
        else:
            json_args = (None, None)
            jstate_np = None

        vrun = None
        if verify is not None:
            # verify is paged by construction (every row sessioned) and
            # never rides the sp ring (BatchedSpeculator eligibility)
            assert paged and not use_ring, \
                "verify_chunk requires the paged session path"
            k_arr = np.ones((B,), np.int32)
            k_arr[:n] = vk
            vrun = (k_arr, _round_up(max(vk), (4, 8, 16)), verify[1])
        if paged:
            out, n_emitted, jstate_f, t_prefill, now, vout = \
                self._run_paged(
                    prompts, suffixes, sess_rows, reuse_abs, kv_off_host,
                    store_sids, B, maxp, tokens, pre_arr, off_arr,
                    chunk_arr, limits, rng_key, samp, json_args, max_new,
                    put, mat, row,
                    (temp_arr, top_arr, active, limits), jstate_np,
                    verify=vrun, snap_at=snap_at)
        else:
            if images is not None and any(i is not None for i in images):
                vc = self.cfg.vision
                pixels = np.zeros((B, vc.image_size, vc.image_size, 3),
                                  np.float32)
                for i, img in enumerate(images):
                    if img is not None:
                        pixels[i] = np.asarray(img, np.float32)
                last_logits, cache = self._step_prefill_vlm(
                    self.params, put(tokens, mat), put(chunk_arr, row),
                    jnp.asarray(pixels), cache_len=cache_len)
            else:
                step_pre = (self._step_prefill_ring if use_ring
                            else self._step_prefill)
                last_logits, cache = step_pre(
                    self.params, put(tokens, mat), put(chunk_arr, row),
                    cache_len=cache_len)
            jax.block_until_ready(last_logits)  # phase fence: prefill done
            t_prefill = time.monotonic()
            out, n_emitted, _, jstate_f = self._step_decode(
                self.params, cache.k, cache.v, cache.lens, last_logits,
                rng_key, *samp, *json_args, max_new=max_new)
            out = np.asarray(out)
            n_emitted = np.asarray(n_emitted)
            jstate_f = np.asarray(jstate_f)
            now = time.monotonic()
        self.last_prefill_tokens = sum(len(s) for s in suffixes)
        self.last_prefill_s = t_prefill - t0
        self.last_decode_s = now - t_prefill
        latency = now - t0
        # Padding-waste telemetry (ISSUE 8 satellite): chunk-token slots
        # the device processed this tick vs the tick's real tokens. The
        # unified path overrides the [B, T] rectangle with its flat token
        # budget (_run_unified sets the thread-local).
        padded_toks = getattr(self._pending, "padded_tokens", None)
        live_slots = getattr(self._pending, "live_slots", None)
        self._pending.padded_tokens = self._pending.live_slots = None
        from quoracle_tpu.infra import costobs, introspect
        with tick_op("account"):
            self._note_padding(
                sum(max(1, len(s)) for s in suffixes),
                B * T if padded_toks is None else padded_toks, live_slots)
            # Chip-economics charge (ISSUE 17): split each phase's measured
            # wall across the live rows by real tokens; padding waste lands
            # on the overhead pseudo-tenant. Read-only — consumes the row
            # keys the batcher declared on this thread, touches no RNG or
            # device state.
            chip_ms_rows = costobs.charge_step(
                self, n=n,
                prefill_weights=([max(1, len(s)) for s in suffixes[:n]]
                                 if vrun is None else [int(k) for k in vk]),
                decode_weights=[int(n_emitted[i]) for i in range(n)],
                padded_prefill=(B * T if padded_toks is None
                                else padded_toks),
                padded_decode=(B * vrun[1] if vrun is not None
                               else B * max_new),
                cache_len=cache_len, verify=vrun is not None,
                prefill_bucket=vrun[1] if vrun is not None else T,
                decode_bucket=max_new)
            self._record_telemetry(n, B, T, cache_len,
                                   vrun[1] if vrun is not None else max_new,
                                   "verify" if vrun is not None else paged,
                                   n_emitted, latency)
        # Liveness heartbeat (ISSUE 18): tokens the device actually
        # produced this call — a frozen counter under live rows is the
        # stall detector's engine-level signal.
        with tick_op("observe"):
            introspect.beat(f"engine.tokens:{self.cfg.name}",
                            sum(int(n_emitted[i]) for i in range(n)))

        if verify is not None:
            vids, vprobs = vout
            return [{
                # window position t predicts proposals[t]; valid verdicts
                # are the first K_i entries (kmax padding is garbage)
                "ids": [int(x) for x in vids[i, :vk[i]]],
                "probs": (np.asarray(vprobs[i, :vk[i]], np.float32)
                          if vprobs is not None else None),
                "n_cached": reuse_abs[i],
                "chip_ms": chip_ms_rows[i],
            } for i in range(n)]

        with tick_op("results"):
            results = []
            for i in range(n):
                # Extract by emitted COUNT, not by sentinel scan: pad_id
                # may be a real vocab token in HF checkpoints.
                k = min(int(n_emitted[i]), row_budgets[i])
                ids = [int(t) for t in out[i, :k]]
                finish = "length"
                stop_set = {self.cfg.eos_token_id,
                            *self.cfg.stop_token_ids}
                if ids and ids[-1] in stop_set:
                    ids.pop()
                    finish = "stop"
                results.append(GenResult(
                    token_ids=ids,
                    text=self.tokenizer.decode(ids),
                    n_prompt_tokens=len(prompts[i]),
                    n_gen_tokens=len(ids),
                    latency_s=latency,
                    finish_reason=finish,
                    n_cached_tokens=reuse_abs[i],
                    json_state=(int(jstate_f[i]) - grammar_bases[i]
                                if constrain_json is not None
                                and constrain_json[i] else -1),
                    chip_ms=chip_ms_rows[i],
                ))
        return results

    def _window_match(self, s: _Session, prompt) -> int:
        """How much of a resident session a prompt reuses in a model with
        a window group: the common prefix (a token short of the prompt:
        one has to run), cut back to a page boundary where it ends inside
        the session's tokens — the pages behind a divergence are shared
        with the radix cache in both groups, and a tick writes whole
        fresh pages, never into a shared one. 0 where the session no
        longer holds, in the window group, every page a query behind the
        match reaches: it does after a clean extension (store-back keeps
        the last window); after a divergence further back than that the
        window layers' rows are gone, and nothing short of the prompt's
        start could make them again."""
        st = self.sessions
        p = min(_lcp(s.tokens, prompt), len(prompt) - 1)
        if p < len(s.tokens):
            p = p // st.page * st.page
        held = s.wpages[st.window.first_page(p, st.page):-(-p // st.page)]
        return p if all(held) else 0

    def _record_telemetry(self, n: int, B: int, T: int, cache_len: int,
                          max_new: int, paged: bool, n_emitted,
                          latency: float) -> None:
        """Per-call histogram observations + first-shape (JIT compile)
        events for this generate (infra/telemetry.py): device phase
        latencies, per-emitted-token decode time, and the tick's
        ``decode_steps`` / ``program`` arguments. Pure observation — no RNG, no device work — so
        temp-0 outputs are bit-identical with telemetry sinks on or off.
        A shape key unseen by this engine marks the call as a first-call
        compile (the wall time is compile-dominated unless the persistent
        XLA cache already held the executable)."""
        name = self.cfg.name
        PREFILL_MS.observe(self.last_prefill_s * 1000, model=name)
        DECODE_MS.observe(self.last_decode_s * 1000, model=name)
        steps = max((int(n_emitted[i]) for i in range(n)), default=0)
        if steps > 0 and self.last_decode_s > 0:
            DECODE_STEP_MS.observe(self.last_decode_s * 1000 / steps,
                                   model=name)
        # The unified ragged path keys its programs on (flat token budget,
        # page-table width, decode bound) — _run_paged stashes that exact
        # key so CompileRegistry ledgers the REAL program identity (and
        # the tier-1 collapse assertion can count it), not the meaningless
        # [B, T] rectangle the flat layout never compiles.
        shape = getattr(self._pending, "shape_key", None)
        self._pending.shape_key = None
        if shape is None:
            shape = (B, T, cache_len, max_new, paged)
        # the tick's annotation arguments: what ran, under CompileRegistry's
        # own spelling of the key (TraceMe values hold no comma)
        tick_note(decode_steps=steps, program="x".join(map(str, shape)))
        if self.compiles.record(shape, latency * 1000):
            if self.quantize_kv:
                # the dequant path's program identity (ISSUE 13): a
                # storm here is the quantized twin of a compile storm
                from quoracle_tpu.infra.telemetry import (
                    QUANT_DEQUANT_COMPILES_TOTAL,
                )
                QUANT_DEQUANT_COMPILES_TOTAL.inc(model=name)
            TRACER.emit(
                "generate.first_shape_compile", latency * 1000,
                model=name, phase="compile",
                shape=f"B{B}xT{T}xC{cache_len}xN{max_new}"
                      + ("p" if paged else ""))

    def _note_padding(self, real: int, padded: int,
                      live: Optional[int] = None) -> None:
        """Account one tick's chunk-token padding waste (ISSUE 8
        satellite): ``real`` tokens the caller actually submitted vs
        ``padded`` device slots of the chosen path's shape ([B·T] for the
        bucketed paths, the flat token budget for the unified kernel) and
        ``live``, those of them whose per-token work ran (None: all; the
        dense chunk forward skips a bucket's dead blocks,
        transformer.live_token_slots). Counters feed Prometheus; the
        cumulative totals ride /api/resources via padding_stats()."""
        from quoracle_tpu.infra.telemetry import (
            SCHED_LIVE_TOKEN_SLOTS_TOTAL, SCHED_PADDED_TOKENS_TOTAL,
            SCHED_REAL_TOKENS_TOTAL,
        )
        name = self.cfg.name
        live = int(padded if live is None else live)
        self.pad_real_tokens += int(real)
        self.pad_padded_tokens += int(padded)
        self.pad_live_slots += live
        self.pad_ticks += 1
        SCHED_REAL_TOKENS_TOTAL.inc(int(real), model=name)
        SCHED_PADDED_TOKENS_TOTAL.inc(int(padded), model=name)
        SCHED_LIVE_TOKEN_SLOTS_TOTAL.inc(live, model=name)
        tick_note(real_tokens=int(real), padded_tokens=int(padded),
                  token_slots_live=live)

    def _note_moe(self, stats) -> None:
        """Book one tick's expert-layer counts (the int32 vector the two
        programs return with their outputs: assignments, of them to held
        experts, held experts reached summed over layers and steps,
        expert layers run; where the grouped kernel ran them, the blocks
        it ran and the rows those hold), once a tick, on the counters and
        the tick span."""
        from quoracle_tpu.infra.telemetry import (
            MOE_ASSIGNMENTS_TOTAL, MOE_BLOCK_ROWS_TOTAL,
            MOE_EXPERTS_REACHED_TOTAL, MOE_LAYER_STEPS_TOTAL,
        )
        total, held, reached, steps, *grouped = (int(v) for v in stats)
        name = self.cfg.name
        if grouped:
            blocks, rows = grouped
            MOE_BLOCK_ROWS_TOTAL.inc(held, model=name, kind="assigned")
            MOE_BLOCK_ROWS_TOTAL.inc(rows, model=name, kind="run")
            tick_note(moe_blocks=blocks, moe_block_rows=rows)
        MOE_ASSIGNMENTS_TOTAL.inc(held, model=name, held="true")
        MOE_ASSIGNMENTS_TOTAL.inc(total - held, model=name, held="false")
        MOE_EXPERTS_REACHED_TOTAL.inc(reached, model=name)
        MOE_LAYER_STEPS_TOTAL.inc(steps, model=name)
        tick_note(moe_assignments=total, moe_held=held,
                  moe_reached=reached, moe_layer_steps=steps)

    def _note_state(self, sess_rows, reuse_abs, reprefill: int) -> None:
        """Book where each row of a tick of a model with conv layers takes
        its conv state from: its session's own end record (``carried``), a
        page boundary's record (``adopted``: a cached prefix's, or the
        session's own below a match that ended inside a page), or zeros
        (``cold``: a sequence's start); and the matched tokens that ran
        again for want of a record where the match ended."""
        from quoracle_tpu.infra import telemetry
        # a model with ssm layers books the same three sources under the
        # record pool's own names (``adopted``: a snapshot of the radix
        # cache's, copied)
        kind = "SSM" if self.cfg.n_ssm_layers else "CONV"
        rows_total = getattr(telemetry, f"{kind}_STATE_ROWS_TOTAL")
        reprefill_total = getattr(
            telemetry, f"{kind}_STATE_REPREFILL_TOKENS_TOTAL")
        rows = {"carried": 0, "adopted": 0, "zero": 0}
        for s, r in zip(sess_rows, reuse_abs):
            rows["zero" if not r else
                 "carried" if r == len(s.tokens) and not s.shared_prefix
                 else "adopted"] += 1
        name = self.cfg.name
        for source, k in rows.items():
            rows_total.inc(k, model=name, source=source)
        reprefill_total.inc(reprefill, model=name)
        tick_note(state_rows_carried=rows["carried"],
                  state_rows_adopted=rows["adopted"],
                  state_rows_cold=rows["zero"],
                  state_reprefill_tokens=reprefill)

    def _note_selection(self, kv_reads: int, pairs: int, ctx, seg,
                        fwd) -> None:
        """Book one tick of a model whose attention selects its keys: the
        indexer streams every index key the attention kernel's walk covers
        and scores every visible pair (``index_kv_reads`` / ``index_pairs``
        are the tick's ``attn_kv_reads`` / ``attn_pairs``), and the softmax
        runs over ``min(visible, topk)`` pairs a query
        (``attn_selected_pairs``), queries of the chunk forward (positions
        ``ctx - seg .. ctx``) and of each decode forward alike. Counts are
        per layer, as the other tick arguments."""
        from quoracle_tpu.infra.telemetry import SPARSE_ATTN_PAIRS_TOTAL
        k = self.cfg.indexer.topk

        def selected(lo, hi):
            """Σ min(v, k) over visible counts v in (lo, hi]."""
            cut = np.clip(k, lo, hi)
            return (cut * (cut + 1) - lo * (lo + 1)) // 2 + (hi - cut) * k

        sel = int((selected(ctx - seg, ctx)
                   + selected(ctx, ctx + fwd)).sum())
        tick_note(index_kv_reads=kv_reads, index_pairs=pairs,
                  attn_selected_pairs=sel)
        L = self.cfg.n_layers
        SPARSE_ATTN_PAIRS_TOTAL.inc(pairs * L, model=self.cfg.name,
                                    kind="visible")
        SPARSE_ATTN_PAIRS_TOTAL.inc(sel * L, model=self.cfg.name,
                                    kind="selected")

    def padding_stats(self) -> dict:
        """Cumulative padding-waste view for /api/resources: what
        raggedness reclaims, quantified per engine."""
        padded = self.pad_padded_tokens
        return {
            "ticks": self.pad_ticks,
            "real_tokens": self.pad_real_tokens,
            "padded_tokens": padded,
            "waste_ratio": (round(1 - self.pad_real_tokens / padded, 4)
                            if padded else None),
            # the share of those slots whose per-token work ran
            "live_slot_share": (round(self.pad_live_slots / padded, 4)
                                if padded else None),
        }

    def kv_token_pool_bytes(self) -> int:
        """Pool bytes per resident KV token (int8 payload + scales when
        quantized; plain cache bytes otherwise) — the shared byte rate
        for resources attribution, /api/kv compression and planning."""
        from quoracle_tpu.models.quant import kv_token_bytes
        if not self.cfg.plain:             # never int8
            # a model with a window group: the FULL group's rate, what a
            # token costs for as long as its session lives
            return self.cfg.kv_bytes_per_token(
                dtype_bytes=jnp.dtype(self.pool_dtype).itemsize,
                group=0 if len(self.cfg.kv_groups) > 1 else None)
        return kv_token_bytes(
            self.cfg.n_layers, self.cfg.n_kv_heads, self.cfg.head_dim,
            jnp.dtype(self.pool_dtype).itemsize, self.quantize_kv)

    def quant_stats(self) -> dict:
        """The member's quantization posture for /api/kv and bench
        config 19: mode flags, the per-token KV byte rate vs the bf16
        rate, and the resulting compression ratio."""
        multi = len(self.cfg.kv_groups) > 1
        bf16_rate = self.cfg.kv_bytes_per_token(
            dtype_bytes=jnp.dtype(self.cache_dtype).itemsize,
            group=0 if multi else None)
        rate = self.kv_token_pool_bytes()
        return {
            "quantize_weights": self.quantize_weights,
            "quantize_kv": self.quantize_kv,
            "kv_bytes_per_token": rate,
            "kv_bytes_per_token_bf16": bf16_rate,
            "kv_compression": round(bf16_rate / rate, 3) if rate else None,
            "resident_kv_tokens": self.sessions.max_tokens,
            # what rides every page beside its tokens' rows: the conv
            # layers' state at the page's end (0 without conv layers)
            "state_bytes_per_record": self.cfg.state_bytes_per_record(
                jnp.dtype(self.pool_dtype).itemsize),
            # what a token holds in a WINDOW group of attention layers,
            # for at most a window and a page (0: the model has none; the
            # rate above is then every attention layer's, else the full
            # group's alone), and the tokens that group's pools hold
            "window_kv_bytes_per_token": self.cfg.kv_bytes_per_token(
                dtype_bytes=jnp.dtype(self.pool_dtype).itemsize, group=1)
            if multi else 0,
            "resident_window_kv_tokens":
            (self.sessions.window.n_pages - 1) * self.sessions.page
            if multi else 0,
        }

    def _ensure_pool(self) -> None:
        """Allocate the device page pool on first sessioned call (engines
        that never see sessions never pay for it).

        ONE stored layout, ``[L, n_pages, page, KV·hd]``: a token's
        kv-heads lie side by side in the lane dimension, which is the
        form the ragged kernel streams a page in
        (ops/paged_attention.ragged_attend). What a token holds in a
        layer is ``cfg.kv_pools``: a latent model has ONE pool,
        ``[L, n_pages, page, latent.lanes]`` under ``st.k`` (``st.v``
        stays None, as the scale pools do), and with an indexer a second,
        narrower one under ``st.v`` for the tokens' index keys: the same
        page ids address both, so sessions, the prefix cache and eviction
        carry a token's two rows together. Only ATTENTION layers have
        pages (``cfg.n_attn_layers``; a pool is indexed by a layer's
        place among them). A model with conv layers holds, under
        ``st.state``, ``[n_conv_layers · n_pages, state_lanes]`` (row
        ``layer · n_pages + page``; stored flat, because ``n_pages`` is no
        multiple of a tile's rows and merging the two dimensions inside a
        program would copy the pool): ONE
        record a page a conv layer, the layer's state (its last
        ``conv_cache - 1`` conv inputs) after the LAST token written to
        that page. A full page's record is therefore the state at the
        page's end, which is what a session that adopts the page from the
        prefix cache must start from; a session's last page's record is
        the state at the session's end, which is what its next turn
        continues from. The page id addresses both, so reference counts,
        copy-on-write, the radix cache and eviction carry a page's record
        with the page. A model with SSM layers (Mamba-2) holds megabytes
        of state a session — a float32 matrix ``[head_dim, state_dim]`` a
        head a layer, 2 MiB a layer at 64 heads of 64 × 128 — which no
        page could carry (a record a page would be tens of GiB): its
        ``st.state`` is a PAIR of pools of RECORDS, ``[n_ssm_layers ·
        n_records, H·P, N]`` float32 and ``[n_ssm_layers · n_records,
        (conv_kernel - 1) · conv_dim]``, addressed by record ids of their
        own (``SessionStore.records``, ``cfg.state_records`` of them). A
        session owns ONE live record, the state at its end, which its
        chunk forwards and decode steps update in place; the radix cache
        owns SNAPSHOTS at the boundaries the engine chose (prefix_cache.py;
        ``_run_paged``: the end of a cached prefix that a new session
        matched and found none at); a row without a session takes a record
        for the length of its tick. Adoption is a copy: the chunk forward
        reads the snapshot and writes the row's own record. The serving
        programs carry
        these buffers through their layer scan and decode loop and
        update them in place; who wants ``[…, KV, hd]`` takes a view —
        a reshape of the fresh rows on the device, of the pages on the
        host (serving/kvtier.py).

        Quantized-KV engines allocate int8 pools plus the
        page-structured fp32 scale pools ([L, n_pages, KV, page] — a
        page's scales are one contiguous block that tier moves carry
        beside the page)."""
        st = self.sessions
        if st.k is not None:
            return
        lanes = self.cfg.kv_pools
        if st.window is not None:
            # a window group beside the full one (config.kv_groups): a
            # PAIR of pools a stream, ``[layers of the group, the group's
            # n_pages, page, KV·hd]``, each under its own page ids —
            # ``st.k = (full, window)`` — which the serving programs carry
            # and update in place as they do one
            st.k, st.v = (tuple(
                jnp.zeros((layers, n_pages, st.page, lanes[0]),
                          self.pool_dtype)
                for (_, layers), n_pages in zip(
                    self.cfg.kv_groups, (st.n_pages, st.window.n_pages)))
                for _ in range(2))
            return
        shape = (self.cfg.n_attn_layers, st.n_pages, st.page)
        sh = None
        if self.mesh is not None:
            # created in its sharding: no chip ever holds the whole pool
            from jax.sharding import NamedSharding, PartitionSpec as P
            tp = int(self.mesh.shape.get("tp", 1))
            # whole kv-heads per shard: a split of the KV·hd lanes into
            # tp runs is the split of the KV axis, byte for byte
            kv_axis = "tp" if self.cfg.n_kv_heads % tp == 0 else None
            sh = NamedSharding(self.mesh, P(None, None, None, kv_axis))
        k = jnp.zeros(shape + lanes[:1], self.pool_dtype, device=sh)
        v = (jnp.zeros(shape + lanes[1:], self.pool_dtype, device=sh)
             if len(lanes) == 2 else None)
        if self.quantize_kv:
            sshape = (self.cfg.n_layers, st.n_pages,
                      self.cfg.n_kv_heads, st.page)
            st.k_scale = jnp.ones(sshape, jnp.float32)
            st.v_scale = jnp.ones(sshape, jnp.float32)
        if self.cfg.n_conv_layers:
            st.state = jnp.zeros((self.cfg.n_conv_layers * st.n_pages,
                                  self.cfg.state_lanes), self.pool_dtype)
        if self.cfg.n_ssm_layers:
            # the RECORD pool (config.state_record has what a record holds
            # in a layer): a layer's state matrices, float32, ``[H·P, N]``
            # a record — the form the scan kernel reads and writes — and
            # its convolution's last inputs, flat over (layer, record) as
            # the conv layers' pool is
            m, n_rec = self.cfg.ssm, self.cfg.n_ssm_layers \
                * st.records.n_ids
            (ssm_lanes, _), (conv_lanes, _) = self.cfg.state_record
            st.state = (
                jnp.zeros((n_rec, ssm_lanes // m.state_dim, m.state_dim),
                          jnp.float32),
                jnp.zeros((n_rec, conv_lanes), self.pool_dtype))
        st.k, st.v = k, v

    def _window_row(self, s: Optional[_Session], pre: int, need: int,
                    protect: tuple) -> Optional[list[int]]:
        """A storing row's pages in the WINDOW group for one tick
        (SessionStore.window; the caller holds the store's lock): entry j
        the page of positions ``[j·page, (j+1)·page)``, for ``need`` pages.
        The row keeps what it holds (its own, or an adopted prefix's)
        from the first page a query of this tick still reaches up to the
        ``pre`` tokens it reuses — the page a clean extension goes on
        filling among them — takes a FRESH page for every page behind
        that, the tick's own tokens and the decode loop's (a chunk's
        tokens attend each other across it, so all of them are held for
        the length of the tick), and lets go of the rest of what it held:
        what lies behind the window by now, and the tail a divergence
        left. Pages before the first reachable one stay 0 — the kernel's
        walk starts behind them. None, with nothing taken, where the pool
        cannot make the fresh pages."""
        st = self.sessions
        win, page = st.window, st.page
        first = win.first_page(pre, page)
        fresh_from = -(-pre // page)
        fresh = st.alloc_window(need - fresh_from, protect=protect)
        if fresh is None:
            return None
        held = list(s.wpages) if s is not None else []
        assert all(held[first:fresh_from]) and \
            len(held) >= fresh_from, (pre, held)
        win.release(held[:first] + held[fresh_from:])
        from quoracle_tpu.infra.telemetry import KV_GROUP_PAGES_TOTAL
        KV_GROUP_PAGES_TOTAL.inc(len(fresh), model=self.cfg.name,
                                 group="window", event="allocated")
        if s is not None and s.shared_prefix:
            KV_GROUP_PAGES_TOTAL.inc(fresh_from - first, model=self.cfg.name,
                                     group="window", event="adopted")
            KV_GROUP_PAGES_TOTAL.inc(len(s.pages), model=self.cfg.name,
                                     group="full", event="adopted")
        return [0] * first + held[first:fresh_from] + fresh

    def _note_window(self, behind: list[int], valid: int,
                     kept: int) -> int:
        """Let go of a stored session's window-group pages ``behind`` the
        window and book it: the pages released, and what the session now
        holds in either group (``quoracle_kv_session_held_tokens_total``:
        tokens, summed over store-backs — window over full is the share
        of its length a session still holds in the window group).
        Returns the count released."""
        from quoracle_tpu.infra.telemetry import (
            KV_GROUP_PAGES_TOTAL, KV_SESSION_HELD_TOKENS_TOTAL,
        )
        st = self.sessions
        with st.lock:
            st.window.release(behind)
        n = sum(1 for pg in behind if pg)
        name = self.cfg.name
        KV_GROUP_PAGES_TOTAL.inc(n, model=name, group="window",
                                 event="released_behind_window")
        KV_SESSION_HELD_TOKENS_TOTAL.inc(valid, model=name, group="full")
        KV_SESSION_HELD_TOKENS_TOTAL.inc(
            min(valid, kept * st.page), model=name, group="window")
        return n

    @staticmethod
    def ragged_fallback(*, mesh_lays_flat: bool, forced: bool,
                        reuse_without_store: bool,
                        boundary_page_swapped: bool) -> Optional[str]:
        """THE rule of the paged path, stated once: a paged tick runs the
        ragged programs (one token-major launch per layer, KV written
        straight to the rows' pages) unless THIS tick shows a reason it
        cannot; the reason comes back as text (None = ragged) and the
        tick takes the gather programs instead. A function of the tick
        and the engine's mesh, never of configuration:

        (a) the mesh cannot lay the flat token-major batch — a dp or sp
            axis, or heads that do not divide over tp (``_ragged_ok``);
        (b) a row reuses a resident prefix but its store was declined
            (pool exhausted even after eviction): it would write to
            temporary pages, and there is no gather to relocate the
            prefix it reads from its session's own;
        (c) a shared boundary page was swapped for a fresh one in this
            allocation (copy-on-write at a partially reused page): the
            ragged forward writes chunk positions only and would leave
            the page's reused head unwritten; the gather scatter
            rewrites every slot.

        A fourth reason is found only by trying, at the site: no free
        page for a sessionless row's temporaries. ``forced`` is the
        tests' seam, ``_force_gather_decode``: the gather programs stay
        reachable on the chip, and the equality tests compare the
        ragged path against them."""
        if forced:
            return "_force_gather_decode"
        if not mesh_lays_flat:
            return "the mesh cannot lay a flat token-major batch"
        if reuse_without_store:
            return "page pool exhausted: a prefix-reusing row's store " \
                   "was declined"
        if boundary_page_swapped:
            return "a shared boundary page was swapped"
        return None

    def _run_paged(self, prompts, suffixes, sess_rows, reuse_abs,
                   kv_off_host, store_sids, B, maxp, tokens, pre_arr,
                   off_arr, chunk_arr, limits, rng_key, samp, json_args,
                   max_new, put, mat, row, samp_np, jstate_np,
                   verify=None, snap_at=None):
        """The paged-session call: the ragged programs write the suffix's
        and the response's KV straight to the rows' pages; where the
        tick cannot take them (``ragged_fallback``) the gather programs
        materialize resident pages in-device, prefill the suffix, decode
        and scatter prompt+response KV back. Either way the session page
        lists update host-side afterwards (ints only — no KV bytes move
        through the host). The CALLER holds self._paged_lock for the
        whole sessioned generate — lookup, allocation, the pool-donating
        steps, and the store are one atomic unit."""
        n = len(prompts)
        st = self.sessions
        page = st.page
        self._ensure_pool()
        src = np.zeros((B, maxp), np.int32)
        dst = np.zeros((B, maxp), np.int32)
        dst_lists: list[Optional[list[int]]] = [None] * n
        temp_lists: list[Optional[list[int]]] = [None] * n
        spills: list[list[int]] = [[] for _ in range(n)]
        protect = tuple(s for s in store_sids if s)
        adopted_release: list[list[int]] = [[] for _ in range(n)]
        partial_swap = False        # a swapped boundary page: condition
                                    # (c) of ragged_fallback
        # A model with a window group (config.kv_groups): each row's pages
        # in THAT group's pools, for the same positions as ``dst`` and 0
        # where the row holds none (``_window_row``); what it took for the
        # tick alone, and the references it took on an adopted prefix
        win = st.window
        wdst = np.zeros((B, maxp), np.int32) if win is not None else None
        wrows: list[Optional[list[int]]] = [None] * n
        wtemps: list[list[int]] = [[] for _ in range(n)]
        wadopted: list[list[int]] = [[] for _ in range(n)]
        # one allocation transaction for the batch
        with tick_op("page_alloc"), st.lock:
            # Refcount-acquire every adopted donor prefix FIRST: an alloc
            # below may LRU-evict the donor mid-transaction, and the
            # adopted pages must survive until this call's steps have
            # consumed (or stored) them.
            for i in range(n):
                s = sess_rows[i]
                if s is not None and s.shared_prefix:
                    st.acquire(s.pages)
                    adopted_release[i] = list(s.pages)
                    if win is not None:
                        win.acquire(s.wpages)
                        wadopted[i] = list(s.wpages)
            for i in range(n):
                s = sess_rows[i]
                if s is not None:
                    # pages beyond this call's table width hold KV past the
                    # reusable prefix — never gathered (prefix <= maxp·page)
                    k = min(len(s.pages), maxp)
                    src[i, :k] = s.pages[:k]
                if store_sids[i] is None:
                    continue
                # dst reuses the STORED session's pages even when the
                # prefix-reuse decision declined them (e.g. windowed
                # divergence): their content is dead either way, and
                # put_raw replacing the session must not leak them.
                stored = st._sessions.get(store_sids[i])
                old = list(stored.pages) if stored is not None else []
                if (stored is None and s is not None and s.shared_prefix):
                    # adopted prefix pages become this row's dst prefix:
                    # the scatter rewrites them with byte-identical values
                    # (the gathered prefix), and the stored session then
                    # OWNS the reference acquired above
                    old = list(s.pages)
                    adopted_release[i] = []
                # resident pages past the table width can't be rewritten
                # this call: release them after the batch runs
                spills[i], old = old[maxp:], old[:maxp]
                # SHARED pages are writable only inside the row's
                # identical-prefix region (the scatter rewrites that part
                # with the gathered, byte-identical values). A shared page
                # past it — a diverged/condensed conversation whose prefix
                # shrank below a page some adopter still reads — would be
                # rewritten with DIFFERENT values (the gather-path scatter
                # writes EVERY dst slot): swap ones this call needs for
                # fresh pages, and drop ones past ``need`` from dst
                # entirely (they would only be garbage-scattered and then
                # released at store-back).
                pre_buf = reuse_abs[i] - kv_off_host[i]
                safe_full = pre_buf // page
                need_tokens = min(
                    pre_buf + len(suffixes[i]) + int(limits[i]),
                    maxp * page)
                need = -(-need_tokens // page)
                tail_shared = [pg for j, pg in enumerate(old)
                               if j >= need and st._refs.get(pg, 1) > 1]
                if tail_shared:
                    old = [pg for j, pg in enumerate(old)
                           if not (j >= need
                                   and st._refs.get(pg, 1) > 1)]
                shared_beyond = [j for j, pg in enumerate(old)
                                 if safe_full <= j < need
                                 and st._refs.get(pg, 1) > 1]
                # Swapping the PARTIALLY-reused boundary page leaves a
                # dst hole the ragged forward would never fill (it
                # writes only chunk positions >= pre_buf; the gather
                # scatter covers everything): ragged_fallback (c).
                if any(j == safe_full and pre_buf % page
                       for j in shared_beyond):
                    partial_swap = True
                if shared_beyond:
                    # copy-on-write: the divergent rewrite lands on fresh
                    # pages; the shared copies (radix cache / adopters)
                    # keep their content (prefix_cache.py invariant I2)
                    st.prefix_cache.note_cow(len(shared_beyond))
                n_extra = max(0, need - len(old)) + len(shared_beyond)
                extra = st.alloc(n_extra, protect=protect) if n_extra else []
                if extra is not None and win is not None:
                    wrows[i] = self._window_row(s, reuse_abs[i], need,
                                                protect)
                    if wrows[i] is None:
                        st._release(extra)
                        extra = None
                    else:
                        wadopted[i] = []    # the stored session owns them
                        wdst[i, :need] = wrows[i]
                if extra is None:
                    # pool exhausted even after eviction: serve the
                    # row without storing (old session stays valid).
                    # An adopted prefix reverts to read-only use: its
                    # reference releases after the steps run.
                    store_sids[i] = None
                    spills[i] = []
                    if s is not None and s.shared_prefix:
                        adopted_release[i] = list(s.pages)
                    continue
                for j in shared_beyond:
                    st._release([old[j]])       # our ref; adopters keep
                    old[j] = extra.pop()
                old = old + extra
                st._release(tail_shared)        # our refs; adopters keep
                dst_lists[i] = old
                dst[i, :len(old)] = old
            fallback = self.ragged_fallback(
                mesh_lays_flat=self._ragged_ok,
                forced=self._force_gather_decode,
                reuse_without_store=any(
                    sess_rows[i] is not None and dst_lists[i] is None
                    for i in range(n)),
                boundary_page_swapped=partial_swap)
            if fallback is None:
                # The ragged programs read every row's prompt from pages,
                # so rows without a stored session need TEMP pages for
                # this call. Exhaustion falls back to gather.
                for i in range(n):
                    if dst_lists[i] is not None:
                        continue
                    need_tokens = min(len(suffixes[i]) + int(limits[i])
                                      + int(pre_arr[i]), maxp * page)
                    # free-list only: scratch pages that die at call end
                    # must not evict other agents' resident sessions
                    tmp = st.alloc(-(-need_tokens // page),
                                   protect=protect, evict=False)
                    wtmp = [] if win is None or tmp is None else \
                        st.alloc_window(len(tmp), evict=False)
                    if tmp is None or wtmp is None:
                        if tmp is not None:
                            st._release(tmp)
                        fallback = "no free page for a sessionless " \
                                   "row's temporaries"
                        break
                    temp_lists[i] = tmp
                    dst[i, :len(tmp)] = tmp
                    if win is not None:
                        wtemps[i] = wtmp
                        wdst[i, :len(wtmp)] = wtmp
                if fallback is not None:
                    for i, tmp in enumerate(temp_lists):
                        if tmp:
                            st._release(tmp)
                        temp_lists[i] = None
                    for wtmp in wtemps:
                        if wtmp:
                            win.release(wtmp)
                    wtemps = [[] for _ in range(n)]

            # A model with ssm layers: each row's records (_ensure_pool).
            # ``rec_src`` the record a row's state starts from (its
            # session's own, a snapshot's, or -1: zeros), ``rec_dst`` the
            # one its state at the tick's end is written to (a storing
            # row's own — the session's, or a fresh one that the stored
            # session will own — a sessionless row's for the tick), and
            # ``snaps`` (tokens into the row's chunk, record) where the
            # chunk forward also writes a snapshot for the radix cache.
            # A snapshot a row adopts is held by a second reference until
            # the steps have run: no eviction below may hand it out.
            rec = st.records
            rec_taken: list[int] = []     # fresh records no session owns yet
            rec_pinned: list[int] = []
            rec_temp: list[int] = []
            ssm_rows = None
            if rec is not None and fallback is None:
                rec_src = np.full((n,), -1, np.int32)
                rec_dst = np.full((n,), rec.n_ids, np.int32)
                snaps = [(0, 0)] * n
                for i in range(n):
                    s = sess_rows[i]
                    if s is not None and s.record:
                        rec_src[i] = s.record
                        if s.shared_prefix:
                            rec.acquire([s.record])
                            rec_pinned.append(s.record)
                for i in range(n):
                    stored = st._sessions.get(store_sids[i] or "")
                    if dst_lists[i] is None:
                        got = st.alloc_record(1, evict=False)
                        rec_temp += got or []
                    elif stored is not None and stored.record:
                        got = [stored.record]
                    else:
                        got = st.alloc_record(1, protect=protect)
                        rec_taken += got or []
                    if got is None:
                        fallback = "no free record for a row's state"
                        break
                    rec_dst[i] = got[0]
                    at = (snap_at[i] if snap_at else 0) - reuse_abs[i]
                    seg = min(len(suffixes[i]),
                              maxp * page - int(pre_arr[i]))
                    if dst_lists[i] is not None and 0 < at < seg \
                            and reuse_abs[i] % page == 0:
                        got = st.alloc_record(1, protect=protect)
                        if got is not None:
                            snaps[i] = (at, got[0])
                            rec_taken += got
                ssm_rows = (rec_src, rec_dst, snaps)
                if fallback is not None:
                    rec.release(rec_taken + rec_pinned + rec_temp)
                    rec_taken, rec_pinned, rec_temp = [], [], []
                    for i, tmp in enumerate(temp_lists):
                        if tmp:
                            st._release(tmp)
                        temp_lists[i] = None

        vout = None
        if fallback is not None and not self.cfg.plain:
            # give back what this call took, then refuse: the gather
            # programs compute per-head K and V and a dense MLP. A row
            # whose stored session keeps every page it had loses only
            # the pages added now; any other row's session is forgotten
            # with the pages this call left it (it re-prefills).
            with st.lock:
                for i in range(n):
                    stored = st._sessions.get(store_sids[i] or "")
                    pages = dst_lists[i]
                    if pages is not None and stored is not None \
                            and set(stored.pages) <= set(pages):
                        had = set(stored.pages)
                        st._release([pg for pg in pages if pg not in had])
                    elif pages is not None:
                        st._sessions.pop(store_sids[i], None)
                        st._release(pages)
                    for taken in (temp_lists[i], adopted_release[i]):
                        if taken:
                            st._release(taken)
                    if win is not None:
                        if wrows[i] is not None:
                            # _window_row re-dealt the session's window
                            # pages: it is forgotten with the row's
                            kept = st._sessions.pop(store_sids[i], None)
                            if kept is not None:
                                st._release(kept.pages)
                            win.release(wrows[i])
                        win.release(wtemps[i] + wadopted[i])
            raise RuntimeError(unsupported_path(
                self.cfg, f"the gather fallback of a paged tick "
                          f"({fallback})"))
        if fallback is None:
            (out, n_emitted, final_lens, jstate_f, vout, t_prefill,
             now) = self._run_unified(
                 n, suffixes, dst, pre_arr, off_arr, chunk_arr,
                 samp_np, jstate_np, json_args[0], rng_key, max_new,
                 maxp, verify, wdst, ssm_rows)
        elif verify is not None:
            # Speculative verify: ONE teacher-forced chunk forward with
            # window logits (no decode loop). The chunk KV scatters back
            # to the rows' own pages so committed tokens are resident for
            # the next round; rejected-draft KV past the commit point is
            # dead weight the next LCP resume overwrites.
            k_arr, kmax, need_probs = verify
            vids, vprobs, cache = self._step_paged_verify(
                self.params, st.k, st.v, st.k_scale, st.v_scale,
                put(src, mat), put(tokens, mat),
                put(pre_arr, row), put(chunk_arr, row), put(off_arr, row),
                put(k_arr, row), samp[0], json_args[0], json_args[1],
                kmax=kmax, need_probs=need_probs)
            jax.block_until_ready(vids)   # phase fence: chunk forward done
            t_prefill = time.monotonic()
            st.k, st.v, st.k_scale, st.v_scale = self._step_scatter_prompt(
                st.k, st.v, st.k_scale, st.v_scale, cache.k, cache.v,
                put(dst, mat))
            cache = None   # k/v donated to the scatter; HBM freed
            vout = (np.asarray(vids),
                    np.asarray(vprobs) if need_probs else None)
            jax.block_until_ready(st.k)
            now = time.monotonic()
            out = np.zeros((B, 0), np.int32)
            n_emitted = np.zeros((B,), np.int32)
            jstate_f = np.full((B,), -1, np.int32)
            final_lens = pre_arr + chunk_arr
        else:
            tick_phase("dispatch_prefill")
            last_logits, cache = self._step_paged_prefill(
                self.params, st.k, st.v, st.k_scale, st.v_scale,
                put(src, mat), put(tokens, mat),
                put(pre_arr, row), put(chunk_arr, row), put(off_arr, row))
            tick_phase("wait_prefill")
            jax.block_until_ready(last_logits)  # phase fence: prefill done
            t_prefill = time.monotonic()
            tick_phase("dispatch_decode")
            (out, n_emitted, final_lens, st.k, st.v, st.k_scale,
             st.v_scale, _, _, jstate_f) = \
                self._step_paged_decode(
                    self.params, st.k, st.v, st.k_scale, st.v_scale,
                    cache.k, cache.v, cache.lens,
                    put(dst, mat), put(off_arr, row), last_logits, rng_key,
                    *samp, *json_args, max_new=max_new)
            tick_phase("wait_decode")
            out = np.asarray(out)
            n_emitted = np.asarray(n_emitted)
            jstate_f = np.asarray(jstate_f)
            now = time.monotonic()

        tick_phase("commit")
        with tick_op("session_put"):
            lens_host = np.asarray(final_lens)
            released = 0        # window-group pages let go behind windows
            for i in range(n):
                sid, pages = store_sids[i], dst_lists[i]
                if sid is None or pages is None:
                    continue
                valid = int(lens_host[i])            # buffer tokens with KV
                used = max(1, -(-valid // page))
                st.release(spills[i])
                st.release(pages[used:])
                pages = pages[:used]
                start = kv_off_host[i]
                abs_valid = start + valid
                plen = len(prompts[i])
                toks = list(prompts[i]) + [
                    int(t) for t in out[i, :abs_valid - plen]]
                W = self.cfg.sliding_window
                if W is not None and valid - W >= page:
                    # bound the resident footprint to the attention window
                    drop = (valid - W) // page
                    st.release(pages[:drop])
                    pages = pages[drop:]
                    start += drop * page
                wpages = None
                if win is not None:
                    win.release(wrows[i][used:])
                    wpages = wrows[i][:used]
                # put_raw: page lifecycle handled explicitly above (the old
                # session's pages are all in dst_lists + spills, so the
                # releases above cover exactly the no-longer-referenced ones)
                record = 0
                if rec is not None:
                    # the record the tick wrote the row's end state to is
                    # the session's live one from now on
                    record = int(ssm_rows[1][i])
                    if record in rec_taken:
                        rec_taken.remove(record)
                st.put_raw(sid, _Session(tokens=toks, pages=pages,
                                         start_pos=start, wpages=wpages,
                                         record=record))
                # Radix prefix cache insert: every FULL page of the stored
                # conversation (prompt + retained response KV) becomes
                # adoptable by future sessions. Windowed/trimmed sessions are
                # excluded (their pages don't start at position 0) and VLM
                # engines never share (image hazard, see the lookup site).
                # verify-mode store-backs carry unverified DRAFT tokens at the
                # tail — correct to resume from (token-keyed LCP) but not
                # worth polluting the shared prefix cache with
                if (self.prefix_sharing and start == 0
                        and self.cfg.sliding_window is None
                        and self.cfg.vision is None and verify is None):
                    with tick_op("prefix_insert"):
                        st.insert_prefix(toks, pages, wpages)
                if win is not None:
                    # ... and only then does the session let go of the
                    # window group's pages that no position it can still
                    # query reaches (the next is ``valid``): the radix
                    # cache has taken its own references first, so a
                    # prefix stays adoptable at every boundary
                    behind = win.first_page(valid, page)
                    released += self._note_window(
                        wpages[:behind], valid, used - behind)
                    wpages[:behind] = [0] * behind
            if win is not None:
                tick_note(window_pages_released=released)
                for i in range(n):
                    with st.lock:
                        win.release(wtemps[i] + wadopted[i])
            if rec is not None:
                self._commit_records(prompts, snap_at, ssm_rows, rec_taken,
                                     rec_pinned, rec_temp)
            # temp pages (sessionless rows of a ragged tick) die with the call
            for tmp in temp_lists:
                if tmp:
                    st.release(tmp)
            # adopted-prefix references that no stored session took over
            # (read-only adoption, or a declined store) release now — the
            # steps above have consumed the pages
            for pages in adopted_release:
                if pages:
                    st.release(pages)
        return out, n_emitted, jstate_f, t_prefill, now, vout

    def _commit_records(self, prompts, snap_at, ssm_rows, taken: list,
                        pinned: list, temps: list) -> None:
        """After a tick of a model with ssm layers: the snapshots its
        chunk forward wrote go to the radix cache (the node at the
        snapshot's depth takes the record; where it has one by now, or is
        gone, the record goes back), what the tick borrowed goes back (the
        second reference on each adopted snapshot, ``pinned``: one a copy
        the chunk forward made; a sessionless row's record, ``temps``),
        and the pool's occupancy is booked."""
        from quoracle_tpu.infra.telemetry import (
            SSM_STATE_RECORDS_HELD, SSM_STATE_RECORDS_TOTAL,
        )
        st, name = self.sessions, self.cfg.name
        rec = st.records
        with st.lock:
            kept = 0
            for i, (_, rid) in enumerate(ssm_rows[2]):
                if rid and st.prefix_cache.attach_record(
                        prompts[i][:snap_at[i]], rid):
                    taken.remove(rid)
                    kept += 1
            rec.release(taken + pinned + temps)
            n_sess = sum(1 for s in st._sessions.values() if s.record)
            n_snap = st.prefix_cache.stats()["cached_records"]
        SSM_STATE_RECORDS_TOTAL.inc(kept, model=name, kind="snapshot")
        SSM_STATE_RECORDS_TOTAL.inc(len(pinned), model=name, kind="copy")
        for holder, v in (("session", n_sess), ("snapshot", n_snap),
                          ("total", rec.n_ids - 1 - len(rec._free)),
                          ("pool", rec.n_ids - 1)):
            SSM_STATE_RECORDS_HELD.set(v, model=name, holder=holder)
        tick_note(ssm_records_held=rec.n_ids - 1 - len(rec._free),
                  ssm_records_pool=rec.n_ids - 1, ssm_snapshots=kept)

    def _run_unified(self, n, suffixes, dst, pre_arr, off_arr, chunk_arr,
                     samp_np, jstate_np, json_table, rng_key,
                     max_new, maxp, verify, wdst=None, ssm_rows=None):
        """One UNIFIED ragged tick (ISSUE 8): lay every row's suffix out
        token-major (segments padded to RAGGED_TQ blocks so a block never
        spans rows), run ONE mixed chunk forward through the ragged
        kernel — KV written straight to each row's dst pages — then
        either project verify-window verdicts or continue into the
        ragged decode loop. Device work and compile keys scale with the
        tick's real tokens (the flat budget), never with batch × max:
        program identity is ("ragged", token budget, row slots, table
        width, decode bound), which CompileRegistry ledgers for the
        collapse assertion. Returns (out, n_emitted, final_lens, jstate_f,
        vout, t_prefill, now) with all row-indexed arrays sized [R] whose
        first ``n`` slots are the batch rows in order.

        ``wdst`` (a model with a window group, config.kv_groups): the
        rows' pages in that group's pools, beside ``dst`` the full
        group's. The tick then lays a SECOND table and a second set of
        write slots over the same positions (``window_tables``) and hands
        the programs pairs — pools, tables, slots — where they take one;
        the program's key does not change: both tables are ``maxp_p2``
        wide."""
        from quoracle_tpu.ops.paged_attention import (
            ragged_tile_slots, ragged_tiles, shared_walks,
        )
        tick_phase("pack")
        st = self.sessions
        page = st.page
        page_cap = maxp * page
        n_tok = st.n_pages * page
        TQ = RAGGED_TQ
        with tick_op("layout"):
            segs, nb_rows = [], []
            for i in range(n):
                s = max(1, min(int(chunk_arr[i]),
                               page_cap - int(pre_arr[i])))
                segs.append(s)
                nb_rows.append(-(-s // TQ))
            raw = sum(b * TQ for b in nb_rows)
            TB = _round_up(raw, RAGGED_TOKEN_BUCKETS)
            if TB == raw and raw > RAGGED_TOKEN_BUCKETS[-1]:
                TB = -(-raw // 4096) * 4096     # beyond the ladder: 4k steps
            NB = TB // TQ                       # blocks
            R = _round_up(n, RAGGED_ROW_BUCKETS)   # row slots
            maxp_p2 = 1 << max(0, maxp - 1).bit_length()   # pow2 table width
            pad_id = self.tokenizer.pad_id
            flat_tok = np.full((TB,), pad_id, np.int32)
            flat_pos = np.zeros((TB,), np.int32)
            flat_dst = np.full((TB,), n_tok, np.int32)     # OOB = drop
            if wdst is not None:
                w_tok = st.window.n_pages * page
                flat_wdst = np.full((TB,), w_tok, np.int32)
                w_tables = np.zeros((R, maxp_p2), np.int32)
            bmeta = np.zeros((4, NB), np.int32)   # kv_len, qpos0, nq, row
            last_idx = np.zeros((R,), np.int32)
            r_tables = np.zeros((R, maxp_p2), np.int32)
            r_pool_lens = np.zeros((R,), np.int32)
            r_off = np.zeros((R,), np.int32)
            temp_arr, top_arr, active, limits_np = samp_np
            r_temp = np.zeros((R,), np.float32)
            r_top = np.ones((R,), np.float32)
            r_active = np.zeros((R,), bool)
            r_limits = np.ones((R,), np.int32)
            r_temp[:n] = temp_arr[:n]
            r_top[:n] = top_arr[:n]
            r_active[:n] = active[:n]
            r_limits[:n] = limits_np[:n]
            if json_table is not None:
                r_jstate = np.full((R,), -1, np.int32)
                r_jstate[:n] = jstate_np[:n]
            if verify is not None:
                k_arr, kmax, need_probs = verify
                widx = np.zeros((R, kmax), np.int32)
            cur = 0
            starts = []                 # each row's first flat slot
            for i in range(n):
                s, nb = segs[i], nb_rows[i]
                pre = int(pre_arr[i])
                toks = suffixes[i][:s]
                flat_tok[cur:cur + len(toks)] = toks
                pos = pre + np.arange(s, dtype=np.int32)
                flat_pos[cur:cur + s] = int(off_arr[i]) + pos
                flat_dst[cur:cur + s] = (dst[i, pos // page] * page
                                         + pos % page)
                if wdst is not None:
                    flat_wdst[cur:cur + s] = (wdst[i, pos // page] * page
                                              + pos % page)
                    w_tables[i, :maxp] = wdst[i]
                kv_len = pre + s
                blk = cur // TQ + np.arange(nb)
                bmeta[0, blk] = kv_len
                bmeta[1, blk] = pre + np.arange(nb) * TQ
                bmeta[2, blk] = np.minimum(TQ, s - np.arange(nb) * TQ)
                bmeta[3, blk] = i
                last_idx[i] = cur + s - 1
                r_tables[i, :maxp] = dst[i]
                r_pool_lens[i] = kv_len
                r_off[i] = int(off_arr[i])
                if verify is not None:
                    widx[i] = cur + np.clip(
                        s - int(k_arr[i]) + np.arange(kmax, dtype=np.int32),
                        0, s - 1)
                starts.append(cur)
                cur += nb * TQ
            self._pending.padded_tokens = TB
            # ... of which the chunk forward's per-token work runs over
            # these (``cur``: the first slot behind the last row)
            self._pending.live_slots = TB if not self.cfg.plain else \
                live_token_slots(TB, cur, self._ragged_shard is not None)
        conv = records = None
        if ssm_rows is not None:
            conv, records = self._ssm_tick(n, R, TB, segs, starts,
                                           last_idx, ssm_rows)
        elif st.state is not None:
            # transformer.ConvTick's fields after the pool: the record each
            # row starts from, where each flat token's predecessors lie
            # (from its row and its place in its chunk; padding: its own
            # neighbours, which no one reads), and the tokens after which
            # the state is recorded — every one that ends a page and each
            # row's last — with their pages
            with tick_op("state_adopt"):
                c_src = np.full((R,), -1, np.int32)
                c_row = np.zeros((TB,), np.int32)
                c_idx = np.full((TB,), TB, np.int32)
                rec_src = np.zeros((TB // page + 2 * R,), np.int32)
                rec_dst = np.full(rec_src.shape, st.n_pages, np.int32)
                n_rec = 0
                for i, cur in enumerate(starts):
                    s, pre = segs[i], int(pre_arr[i])
                    pos = pre + np.arange(s, dtype=np.int32)
                    if pre:
                        c_src[i] = dst[i, (pre - 1) // page]
                    c_row[cur:cur + s] = i
                    c_idx[cur:cur + s] = np.arange(s)
                    ends = np.flatnonzero((pos + 1) % page == 0)
                    ends = np.union1d(ends, [s - 1])
                    rec_src[n_rec:n_rec + len(ends)] = cur + ends
                    rec_dst[n_rec:n_rec + len(ends)] = \
                        dst[i, pos[ends] // page]
                    n_rec += len(ends)
                past = conv_past(c_row, c_idx, self.cfg.conv_cache)
            with tick_op("h2d"):
                conv = tuple(jnp.asarray(a) for a in (
                    c_src, past, rec_src, rec_dst))
        # the same blocks grouped for the attention kernel's walk: up to
        # ``tile`` tokens of a row read its pages once between them
        # (where the kernel walks block by block, the blocks are the walk)
        tile = self._ragged_tile
        tiles, walked = None, bmeta
        if tile:
            with tick_op("tiles"):
                walked = ragged_tiles(bmeta, TQ, tile,
                                      ragged_tile_slots(NB, R, TQ, tile))
        # ... and the decode steps' one-token rows: which of them have
        # leading pages in common, read once a step for all of them (the
        # dense kernel's walk and a latent pool's alike)
        shared = None
        if verify is None:
            with tick_op("shared_walks"):
                shared = shared_walks(r_tables, r_pool_lens, page,
                                      self.cfg.sliding_window)
        flat_dsts, tables_np = flat_dst, r_tables
        if wdst is not None:
            # the window group's table and slots ride beside the full
            # group's, a pair where a one-group model has an array
            flat_dsts, tables_np = (flat_dst, flat_wdst), (r_tables,
                                                           w_tables)
        with tick_op("h2d"):
            js_dev = (None if json_table is None
                      else jnp.asarray(r_jstate))
            if tile:
                tiles = jnp.asarray(walked)

        if verify is not None:
            self._pending.shape_key = ("ragged_verify", TB, R, maxp_p2,
                                       kmax)
            with tick_op("h2d"):
                args = (jnp.asarray(flat_tok), jnp.asarray(flat_pos),
                        jnp.asarray(r_tables), jnp.asarray(bmeta), tiles,
                        jnp.asarray(flat_dst), jnp.asarray(widx),
                        jnp.asarray(r_temp))
            with tick_op("enqueue"):
                (vids, vprobs, st.k, st.v, st.k_scale,
                 st.v_scale) = self._step_paged_ragged_verify(
                    self.params, st.k, st.v, st.k_scale, st.v_scale, *args,
                    json_table, js_dev, tq=TQ, tile=tile, kmax=kmax,
                    need_probs=need_probs)
            with tick_op("device"):
                jax.block_until_ready(vids)  # phase fence: chunk forward
            t_prefill = time.monotonic()
            with tick_op("fetch"):
                vout = (np.asarray(vids),
                        np.asarray(vprobs) if need_probs else None)
                jax.block_until_ready(st.k)
            now = time.monotonic()
            out = np.zeros((R, 0), np.int32)
            n_emitted = np.zeros((R,), np.int32)
            jstate_f = np.full((R,), -1, np.int32)
            return (out, n_emitted, r_pool_lens, jstate_f, vout,
                    t_prefill, now)

        self._pending.shape_key = ("ragged", TB, R, maxp_p2, max_new)
        # resident tokens this tick's rows attend to (their kv length at
        # the chunk's end): what the attention kernel streams each step
        tick_note(context_tokens=int(r_pool_lens.sum()))
        tick_phase("dispatch_prefill")
        with tick_op("h2d"):
            as_dev = functools.partial(jax.tree.map, jnp.asarray)
            args = (jnp.asarray(flat_tok), jnp.asarray(flat_pos),
                    as_dev(tables_np), jnp.asarray(bmeta), tiles,
                    as_dev(flat_dsts), jnp.asarray(last_idx))
        with tick_op("enqueue"):
            (last_logits, st.k, st.v, st.k_scale, st.v_scale, moe_pre,
             st.state) = self._step_paged_ragged(
                    self.params, st.k, st.v, st.k_scale, st.v_scale, *args,
                    st.state, conv, tq=TQ, tile=tile)
        tick_phase("wait_prefill")
        with tick_op("device"):
            jax.block_until_ready(last_logits)  # phase fence: prefill done
        t_prefill = time.monotonic()
        tick_phase("dispatch_decode")
        with tick_op("h2d"):
            tables = (as_dev(tables_np),
                      None if shared is None else jnp.asarray(shared),
                      jnp.asarray(r_pool_lens), jnp.asarray(r_off))
            samp = (jnp.asarray(r_temp), jnp.asarray(r_top),
                    jnp.asarray(r_active), jnp.asarray(r_limits))
        with tick_op("enqueue"):
            (out, n_emitted, final_lens, st.k, st.v, st.k_scale,
             st.v_scale, jstate_f, moe_dec, st.state) = \
                self._step_paged_decode_ragged(
                    self.params, st.k, st.v, st.k_scale, st.v_scale,
                    *tables, last_logits, rng_key, *samp, json_table,
                    js_dev, st.state, records, max_new=max_new)
        tick_phase("wait_decode")
        # the first fetch blocks until the program has ended (its copy is
        # queued behind the program: a fence before it would put the
        # host's wake-up between the two); what follows in this phase is
        # the host's, with the chip idle: the other copies, then the
        # instruments
        with tick_op("device"):
            out = np.asarray(out)
        with tick_op("fetch"):
            n_emitted = np.asarray(n_emitted)
            jstate_f = np.asarray(jstate_f)
            final_lens = np.asarray(final_lens)
            jax.block_until_ready(st.k)
        now = time.monotonic()
        if records is not None:
            # the rows' forwards in the decode loop: each read and wrote
            # its record once (a row's first token is the chunk forward's)
            tick_note(ssm_row_steps=int(np.maximum(
                n_emitted[:n] - 1, 0).sum()))
        if moe_pre is not None:
            with tick_op("fetch"):
                moe = np.asarray(moe_pre) + np.asarray(moe_dec)
        with tick_op("account"):
            if moe_pre is not None:
                self._note_moe(moe)
            self._note_attention(n, segs, r_pool_lens, final_lens, walked,
                                 shared, page)
        return out, n_emitted, final_lens, jstate_f, None, t_prefill, now

    def _ssm_tick(self, n, R, TB, segs, starts, last_idx,
                  ssm_rows) -> tuple:
        """transformer.SsmTick's fields after the pools for one chunk
        forward, and the rows' records for the decode loop behind it: the
        records each row reads and writes, its tokens' predecessors for
        the convolution (``conv_past``), and the SCAN layout — every
        row's tokens from a chunk's first slot, ``cfg.ssm.chunk`` to a
        chunk, ``TB // chunk + R`` chunks (each row wastes less than
        one). A row that takes a snapshot starts on a page boundary, and
        the chunk divides the page: the snapshot's place is a chunk's
        end."""
        m = self.cfg.ssm
        Q, NR = m.chunk, self.sessions.records.n_ids
        assert self.sessions.page % Q == 0
        rec_src, rec_dst, snaps = ssm_rows
        with tick_op("state_adopt"):
            NC = TB // Q + R
            src = np.full((R,), -1, np.int32)
            dst = np.full((R,), NR, np.int32)
            src[:n], dst[:n] = rec_src, rec_dst
            c_row = np.zeros((TB,), np.int32)
            c_idx = np.full((TB,), TB, np.int32)
            scan_idx = np.full((NC * Q,), TB, np.int32)
            scan_pos = np.zeros((TB,), np.int32)
            chunk_row = np.zeros((NC,), np.int32)
            chunk_first = np.zeros((NC,), np.int32)
            row_end = np.zeros((R,), np.int32)
            snap = np.zeros((2, R), np.int32)
            snap_dst = np.full((R,), NR, np.int32)
            ck = 0
            for i, cur in enumerate(starts):
                s = segs[i]
                c_row[cur:cur + s] = i
                c_idx[cur:cur + s] = np.arange(s)
                nck = -(-s // Q)
                scan_idx[ck * Q:ck * Q + s] = cur + np.arange(s)
                scan_pos[cur:cur + s] = ck * Q + np.arange(s)
                chunk_row[ck:ck + nck] = i
                chunk_first[ck] = 1
                row_end[i] = ck + nck - 1
                at, rid = snaps[i]
                if rid:
                    snap[:, i] = ck + at // Q - 1, cur + at - 1
                    snap_dst[i] = rid
                ck += nck
            chunk_row[ck:] = chunk_row[max(ck - 1, 0)]
            past = conv_past(c_row, c_idx, m.conv_kernel)
        tick_note(ssm_scan_tokens=int(sum(segs)), ssm_scan_chunks=ck,
                  ssm_decode_rows=n)
        with tick_op("h2d"):
            return tuple(jnp.asarray(a) for a in (
                src, dst, past, last_idx, scan_idx, scan_pos, chunk_row,
                chunk_first, row_end, snap[0], snap[1], snap_dst,
                np.asarray([ck], np.int32))), jnp.asarray(dst)

    def _note_attention(self, n: int, segs, r_pool_lens, final_lens, walked,
                        shared, page: int) -> None:
        """Book what the attention kernel had to do in one ragged tick and
        what its programs brought in, on the tick span (and, for a model
        that selects its keys, ``_note_selection``): a LAYER's numbers.
        A model with a window group beside the full one
        (config.kv_groups) books the full group's layer under these
        names and the window group's, reckoned under its window and
        with no shared walk, under the same names with ``_window``
        behind them."""
        work = self._attention_work(n, segs, r_pool_lens, final_lens,
                                    walked, shared, page,
                                    *self.cfg.kv_groups[0])
        tick_note(**work)
        for group in self.cfg.kv_groups[1:]:
            tick_note(**{f"{k}_window": v for k, v in self._attention_work(
                n, segs, r_pool_lens, final_lens, walked, None, page,
                *group).items()})
        if self.cfg.indexer is not None:
            seg = np.asarray(segs, np.int64)
            ctx = r_pool_lens[:n].astype(np.int64)
            self._note_selection(work["attn_kv_reads"], work["attn_pairs"],
                                 ctx, seg, final_lens[:n].astype(np.int64)
                                 - ctx)

    def _attention_work(self, n: int, segs, r_pool_lens, final_lens, walked,
                        shared, page: int, window: Optional[int],
                        layers: int) -> dict:
        """One attention layer's work in a ragged tick, as tick-span
        arguments: for a group of ``layers`` layers with ``window`` (None:
        none) and the decode program's ``shared`` walk table (None:
        none)."""
        from quoracle_tpu.ops.paged_attention import (
            decode_walks, ragged_tile_walk, ragged_walk_steps,
            shared_walk_steps, shared_walk_tokens,
        )
        # what the attention kernel had to do this tick, for its roofline
        # (a reader's lower bounds): resident tokens streamed — each row's
        # context once for its chunk and once per decode step — and
        # query-key pairs attended under the causal mask (and the window:
        # a query reaches ``window`` keys at most, a chunk's queries
        # between them the chunk and a window before it)
        seg = np.asarray(segs, np.int64)
        ctx = r_pool_lens[:n].astype(np.int64)
        fwd = final_lens[:n].astype(np.int64) - ctx     # decode forwards
        # ... and what its programs did bring into VMEM: the pages each
        # tile of the chunk forward walked, and a row's pages once a
        # decode step (forward j of a row is a one-token tile that sees
        # ctx + j tokens) — but for the leading pages a shared walk
        # covers, which come in once a step for all of its rows.
        # streamed / reads is how many times a needed token was fetched.
        steps = np.arange(1, int(fwd.max(initial=0)) + 1)
        seen = ctx[:, None] + steps
        live = steps <= fwd[:, None]
        decode = np.stack([seen, seen - 1, live])
        if window is None:
            dec = int((fwd * ctx + fwd * (fwd + 1) // 2).sum())
            kv_reads = int(ctx.sum()) + dec
            pairs = int((seg * (ctx - seg) + seg * (seg + 1) // 2).sum()) \
                + dec
        else:
            dec = int((np.minimum(seen, window) * live).sum())
            kv_reads = int(np.minimum(ctx, seg + window - 1).sum()) + dec
            # query i of a chunk (0-based) sits at position ctx - seg + i
            # and reaches min(position + 1, window) keys
            first = ctx - seg + 1
            full = np.clip(window - first, 0, seg)   # queries below window
            pairs = int((full * first + full * (full - 1) // 2
                         + (seg - full) * window).sum()) + dec
        skip = np.zeros((n,), np.int64) if shared is None else shared[0, :n]
        streamed, n_tiles = (a + b for a, b in zip(
            ragged_tile_walk(walked, page, window),
            ragged_tile_walk(decode, page, window, skip=skip[:, None])))
        # ... and the loop iterations those walks made: a page each in
        # the tile kernel, a block of pages in the block kernel and the
        # latent kernels (every decode step; the chunk forward where the
        # engine builds no tiles), so streamed / page / walk_steps is how
        # full they ran
        block = self._walk_block
        walk_steps = ragged_walk_steps(
            walked, page, 1 if self._ragged_tile else block, window) \
            + ragged_walk_steps(decode, page, block, window,
                                skip=skip[:, None])
        # ... and the walks themselves, a decode step's: a row's own and
        # a group's shared one; the dense block kernel starts the first
        # block of every walk of a call but its first while the walk
        # before it attends its last (a latent pool's kernels start each
        # cold)
        n_walks, n_ahead = decode_walks(decode, page, window,
                                        skip[:, None], shared)
        if self.cfg.latent is not None:
            n_ahead = 0
        work = {}
        if shared is not None:
            from quoracle_tpu.infra.telemetry import (
                ATTN_SHARED_KV_TOKENS_TOTAL,
            )
            needed, shared_in = shared_walk_tokens(shared, fwd, page)
            streamed += shared_in
            walk_steps += shared_walk_steps(shared, fwd, block)
            work.update(
                attn_shared_rows=int((skip > 0).sum()),
                attn_shared_pages=int(skip[shared[1, :n] > 0].sum()))
            ATTN_SHARED_KV_TOKENS_TOTAL.inc(
                needed * layers, model=self.cfg.name, kind="needed")
            ATTN_SHARED_KV_TOKENS_TOTAL.inc(
                shared_in * layers, model=self.cfg.name, kind="walked")
        work.update(attn_kv_reads=kv_reads, attn_pairs=pairs,
                    attn_kv_streamed=streamed, attn_tiles=n_tiles,
                    attn_walk_steps=walk_steps, attn_walks=n_walks,
                    attn_walks_started_ahead=n_ahead)
        return work

    def _json_table_device(self, enum_set: tuple):
        """Lazily build + cache grammar tables for this tokenizer (one
        vocab walk per distinct grammar, a few hundred ms; then
        device-resident int16). ``enum_set`` is the tuple of DISTINCT
        action enums present in the batch (None = plain JSON); returns
        (stacked table, {enum: start-state offset into it}). Single-grammar
        batches (the common case) hit a per-enum device cache; mixed
        batches additionally cache the stacked result. Guarded by
        _grammar_lock: sessionless image calls share this cache with the
        batcher thread's sessioned chunks (dict eviction mid-read would
        corrupt)."""
        with self._grammar_lock:
            return self._json_table_device_impl(enum_set)

    def _json_table_device_impl(self, enum_set: tuple):
        from quoracle_tpu.models.constrained import JsonTokenTable
        if not hasattr(self, "_json_cache"):
            self._json_cache: dict = {}

        def _evict(kind: str, keep: int) -> None:
            # Bounded cache: device tables are padded_states × vocab int16
            # (tens-to-hundreds of MB at 128k vocab); agents with varied
            # capability sets must not accumulate tables until HBM OOM.
            # dict preserves insertion order → drop oldest first.
            keys = [k for k in self._json_cache if k[0] == kind]
            for k in keys[:max(0, len(keys) - keep)]:
                del self._json_cache[k]

        def build(enum):
            key = ("one", enum)
            if key not in self._json_cache:
                tt = JsonTokenTable.for_tokenizer(
                    self.tokenizer,
                    # vocab per the MODEL (logit width), padding beyond the
                    # tokenizer's ids stays rejected
                    self.cfg.vocab_size, self.cfg.eos_token_id,
                    extra_stop_ids=tuple(self.cfg.stop_token_ids),
                    action_enum=enum)
                self._json_cache[key] = tt
            return self._json_cache[key]

        if len(enum_set) == 1:
            tt = build(enum_set[0])
            dkey = ("dev", enum_set[0])
            if dkey not in self._json_cache:
                _evict("dev", keep=3)
                _evict("one", keep=7)
                self._json_cache[dkey] = jnp.asarray(tt.table)
            # third element: each grammar's state-block BASE — states
            # relative to it are portable across calls with different
            # stackings (chunked continuation, models/scheduler.py)
            return (self._json_cache[dkey], {enum_set[0]: tt.start_state},
                    {enum_set[0]: 0})
        skey = ("stack", enum_set)
        if skey not in self._json_cache:
            _evict("stack", keep=1)
            _evict("one", keep=7)
            tables, offsets, bases, off = [], {}, {}, 0
            for enum in enum_set:
                tt = build(enum)
                shifted = tt.table.astype(np.int32)
                shifted = np.where(shifted >= 0, shifted + off, REJECT_STATE)
                tables.append(shifted.astype(np.int16))
                offsets[enum] = off + tt.start_state
                bases[enum] = off
                off += tt.table.shape[0]
            assert off < 32767, "stacked grammar state space exceeds int16"
            self._json_cache[skey] = (jnp.asarray(np.concatenate(tables)),
                                      offsets, bases)
        return self._json_cache[skey]
