"""Speculative decoding: a small draft model proposes K tokens, the
target model verifies them in ONE chunk forward.

No reference counterpart (the reference never executes attention,
SURVEY §2.8) — this is a TPU-first throughput feature aimed squarely at
the measured bottleneck: BASELINE.md's decode roofline shows batch-1
decode streams the member's full bf16 weights from HBM per token (~47%
of v5e peak bandwidth, compute nearly idle). Verifying K draft tokens in
one target pass reads the weights ONCE for K positions — the accepted-
token rate converts memory-bound decode steps into one compute-denser
chunk, exactly the regime the MXU wants.

Algorithm (leapfrog variant, no bonus token — keeps draft and target
caches in lockstep):

  invariant   ctx = prompt + emitted; BOTH caches hold KV for ctx[:-1];
              ``pending`` = ctx[-1], not yet forwarded by either model.
  propose     draft runs a K-step scan from ``pending``: d_1..d_K with
              per-step draft probs q_i  (draft cache advances K steps,
              through d_{K-1}).
  verify      target runs ONE chunk [pending, d_1..d_{K-1}] → logits
              p_1..p_K (p_i is the target distribution that d_i was
              proposed against; target cache advances the same K steps).
  accept      greedy rows: d_i accepted while d_i == argmax(p_i).
              sampled rows: d_i accepted with prob min(1, p_i[d_i] /
              q_i[d_i]); on rejection the correction token is drawn from
              the residual max(0, p_i - q_i) renormalized — the
              standard rejection-sampling construction, which preserves
              the target model's output distribution exactly
              (PAPERS.md speculative-decoding literature; re-derived
              here, no code reused).
  commit      j accepted → emit d_1..d_j (+ the correction token when
              j < K); roll BOTH caches back to len(ctx')-1 by shrinking
              ``lens`` (KV past lens is masked by attention, later
              writes overwrite it in place); pending' = d_K on full
              accept else the correction token.

Greedy (temperature 0) output is bit-identical to vanilla decode: every
accepted d_i equals argmax(p_i) and every correction IS argmax(p_i).
tests/test_speculative.py asserts equality against GenerateEngine.

Grammar-constrained speculation is supported (``constrain_json`` /
``action_enum``): the draft proposes under the SAME token-DFA mask the
engine decodes with (models/constrained.py) — the proposal distribution
is the masked one, so acceptance math stays exact — and the verify pass
masks p_i with the state in effect at that position (host table walk).
This is what makes speculation applicable to the production consensus
workload, which always decodes constrained action JSON.

v1 scope: batch 1, dense cache (no sessions/pages), text-only, full
attention (no sliding window). The draft and target MUST share one
tokenizer/vocab — verified at construction.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from quoracle_tpu.analysis.lockdep import named_lock
from quoracle_tpu.infra import costobs
from quoracle_tpu.infra.flightrec import FLIGHT
from quoracle_tpu.infra.telemetry import (
    SPEC_ACCEPTANCE, SPEC_ACCEPTED, SPEC_DRAFTED, SPEC_ENGAGED,
    SPEC_FALLBACK_TOTAL, SPEC_K, SPEC_ROUNDS, SPEC_TOKENS_PER_ROUND,
)
from quoracle_tpu.models.config import ModelConfig
from quoracle_tpu.models.generate import (
    grammar_mask, prefill, prefill_chunk,
)
from quoracle_tpu.models.sampling import sample_tokens
from quoracle_tpu.training.capture import CAPTURE, spec_example
from quoracle_tpu.models.transformer import (
    KVCache, forward_hidden, init_cache, project_logits,
)


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _row_keys(rows) -> list:
    """Chip-economics attribution keys (ISSUE 17) for scheduler
    _Row-likes — integer QoS priorities render as class names so the
    ledger shares the budget plane's vocabulary."""
    from quoracle_tpu.serving.qos import class_name
    return [(str(getattr(r, "tenant", None) or "-"),
             class_name(getattr(r, "priority", 1)),
             str(getattr(r, "task_id", None) or "-"),
             str(getattr(r, "decide", None) or "-")) for r in rows]


@dataclasses.dataclass
class SpecResult:
    token_ids: list
    text: str
    n_prompt_tokens: int
    n_gen_tokens: int
    latency_s: float
    finish_reason: str
    rounds: int                  # speculative rounds executed
    drafted: int                 # draft tokens proposed in total
    accepted: int                # draft tokens accepted in total
    n_cached_tokens: int = 0     # session-resident prefix reused

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(1, self.drafted)

    @property
    def tokens_per_round(self) -> float:
        return self.n_gen_tokens / max(1, self.rounds)


# The batch-1 decoder of training/draft_check.py (the backend serves no
# row through it): compiles once at _build per (which, cache_len); the
# serving path ledgers through the owning engine's CompileRegistry
# instead (BatchedSpeculator + verify_chunk).
# qlint: allow[jit-unregistered] batch-1 decoder; engines own the ledger
class SpeculativeDecoder:
    """Draft/verify decoder over two models sharing one tokenizer.

    ``target_cfg``/``draft_cfg`` + params are the same structures
    GenerateEngine serves; K is the draft length per round. Construct
    once per (target, draft) pair — the three jits (two prefills, the
    draft scan, the verify chunk) compile per cache-length bucket and
    are reused across calls.
    """

    def __init__(self, target_cfg: ModelConfig, target_params: dict,
                 draft_cfg: ModelConfig, draft_params: dict,
                 tokenizer, *, k: int = 6, max_seq: int = 2048,
                 seed: int = 0, cache_dtype=None):
        from quoracle_tpu.models.config import require_plain
        for c in (target_cfg, draft_cfg):
            require_plain(c, "speculative serving (--draft)")
        assert target_cfg.vocab_size == draft_cfg.vocab_size, \
            "draft and target must share one tokenizer/vocab"
        assert target_cfg.sliding_window is None \
            and draft_cfg.sliding_window is None, \
            "speculative v1 requires full attention (no sliding window)"
        self.tc, self.tp = target_cfg, target_params
        self.dc, self.dp = draft_cfg, draft_params
        self.tokenizer = tokenizer
        self.k = int(k)
        self.max_seq = max_seq
        # match each model's params dtype like GenerateEngine does — a
        # bf16 cache under fp32 params trips lax.scatter's dtype check in
        # the KV write
        self.t_cache_dtype = (cache_dtype if cache_dtype is not None
                              else jax.tree.leaves(target_params)[0].dtype)
        self.d_cache_dtype = (cache_dtype if cache_dtype is not None
                              else jax.tree.leaves(draft_params)[0].dtype)
        self._rng = jax.random.PRNGKey(seed)
        # NOT thread-safe: sessions/caches/rng mutate per call. Callers
        # that share a decoder serialize through this lock (TPUBackend
        # try-acquires it and falls back to batched vanilla on contention)
        self.lock = named_lock("spec.decoder")
        self._build()

    # ------------------------------------------------------------------

    def _build(self) -> None:
        K = self.k

        @functools.partial(jax.jit, static_argnames=("cache_len", "which"))
        def _prefill(params, tokens, lens, cache_len: int, which: str):
            cfg = self.tc if which == "t" else self.dc
            dt = self.t_cache_dtype if which == "t" else self.d_cache_dtype
            cache = init_cache(cfg, 1, cache_len, dtype=dt)
            return prefill(params, cfg, tokens, lens, cache)

        @functools.partial(jax.jit, static_argnames=("which",))
        def _extend(params, cache: KVCache, tokens, chunk_lens,
                    which: str):
            """Session resume: forward a right-padded suffix chunk on top
            of the resident prefix (prefill_chunk at prefix = cache.lens)
            — the speculative counterpart of the engine's token-splice."""
            cfg = self.tc if which == "t" else self.dc
            _, cache = prefill_chunk(params, cfg, tokens, cache.lens,
                                     chunk_lens, cache)
            return cache

        eos_id = self.tc.eos_token_id
        # generate.grammar_mask IS the engine's mask — one implementation,
        # zero drift (the bit-exactness guarantee depends on it)
        _mask = functools.partial(grammar_mask, eos_id=eos_id)

        @functools.partial(jax.jit,
                           static_argnames=("constrained", "greedy"))
        def _draft_scan(params, cache: KVCache, pending, rng, temperature,
                        top_p, json_table, jstate0,
                        constrained: bool = False, greedy: bool = False):
            """K autoregressive draft steps from ``pending``.

            Returns (d_tokens [K], q_probs [K, V], cache'): step i
            forwards the previous token (pending for i=0), samples d_i
            from the draft distribution q_i — grammar-masked when
            ``constrained`` (the proposal distribution IS the masked one,
            so acceptance math stays exact). The cache advances K
            positions — through d_{K-1} — matching the target's verify
            chunk exactly (module docstring invariant)."""
            cfg = self.dc

            def step(carry, _):
                cache, tok, rng, jstate = carry
                pos = cache.lens[:, None]
                hidden, cache = forward_hidden(
                    params, cfg, tok[:, None], pos, cache,
                    write_offset=cache.lens, kv_lens=cache.lens + 1)
                cache = cache._replace(lens=cache.lens + 1)
                logits = project_logits(params, cfg, hidden)[:, 0, :]
                logits = logits.astype(jnp.float32)
                if constrained:
                    logits = _mask(logits, jstate, json_table)
                rng, ks = jax.random.split(rng)
                nxt = sample_tokens(logits, ks, temperature, top_p)
                if greedy:
                    # acceptance needs no proposal distribution: the host
                    # compares token ids — skip the [V] softmax entirely
                    q = jnp.zeros((1, 1), jnp.float32)
                else:
                    q = jax.nn.softmax(
                        logits / jnp.maximum(temperature, 1e-6)[:, None],
                        axis=-1)
                    # greedy rows draft greedily: q as one-hot keeps the
                    # acceptance rule exact (accept iff d_i == argmax p_i)
                    q = jnp.where(
                        (temperature <= 0)[:, None],
                        jax.nn.one_hot(nxt, logits.shape[-1]), q)
                if constrained:
                    jstate = jnp.where(
                        jstate >= 0,
                        json_table[jnp.clip(jstate, 0, None),
                                   nxt].astype(jnp.int32), jstate)
                return (cache, nxt, rng, jstate), (nxt[0], q[0])

            (cache, _, rng, _), (toks, qs) = jax.lax.scan(
                step, (cache, pending, rng, jstate0), None, length=K)
            return toks, qs, cache

        @functools.partial(jax.jit,
                           static_argnames=("constrained", "greedy"))
        def _verify_chunk(params, cache: KVCache, chunk, temperature,
                          json_table, jstate0, constrained: bool = False,
                          greedy: bool = False):
            """One target pass over [pending, d_1..d_{K-1}] → p_1..p_K
            (full per-position distributions) with the cache advanced K
            positions. Under constraint the per-position grammar states
            are walked IN-DEVICE from ``jstate0`` over the draft tokens
            (chunk[1:]) — no host sync sits between the draft scan and
            this dispatch — and the mask applied to p_i equals the one
            the vanilla engine would apply at that position."""
            cfg = self.tc
            T = K
            lens0 = cache.lens
            positions = (lens0[:, None]
                         + jnp.arange(T, dtype=jnp.int32)[None, :])
            hidden, cache = forward_hidden(
                params, cfg, chunk[None, :], positions, cache,
                write_offset=lens0, kv_lens=lens0 + T)
            cache = cache._replace(lens=lens0 + T)
            logits = project_logits(params, cfg, hidden)[0].astype(
                jnp.float32)                                     # [K, V]
            if constrained:
                def adv(s, tok):
                    nxt = json_table[jnp.clip(s, 0, None),
                                     tok].astype(jnp.int32)
                    s2 = jnp.where(s >= 0, nxt, s)
                    return s2, s2
                _, rest = jax.lax.scan(adv, jstate0[0], chunk[1:])
                jstates = jnp.concatenate([jstate0, rest])       # [K]
                logits = _mask(logits, jstates, json_table)
            argmax_ids = jnp.argmax(logits, axis=-1)         # [K]
            if greedy:
                # the [K, V] probs would be a dead jit output the compiler
                # must still write to HBM — drop it in the hot greedy path
                probs = jnp.zeros((1, 1), jnp.float32)
            else:
                probs = jax.nn.softmax(
                    logits / jnp.maximum(temperature, 1e-6)[:, None],
                    axis=-1)
                greedy_probs = jax.nn.one_hot(argmax_ids,
                                              logits.shape[-1])
                probs = jnp.where((temperature <= 0)[:, None],
                                  greedy_probs, probs)
            return probs, argmax_ids, cache

        self._prefill = _prefill
        self._extend = _extend
        self._draft_scan = _draft_scan
        self._verify_chunk = _verify_chunk
        self._sessions: dict = {}

    def _grammar(self, action_enum) -> tuple:
        """(numpy table, start_state, device table) per enum, cached. One
        DFA serves both models — they share the tokenizer by contract.
        Key is normalized (sorted, deduped — CharDFA normalizes the enum
        internally, so permutations build byte-identical tables) and the
        cache is BOUNDED: device tables are states × vocab int16, tens of
        MB at large vocabs, and varied capability sets must not
        accumulate until HBM OOM (same rationale as the engine's
        _json_table_device eviction)."""
        if not hasattr(self, "_grammar_cache"):
            self._grammar_cache = {}
        key = tuple(sorted(set(action_enum))) if action_enum else None
        if key not in self._grammar_cache:
            from quoracle_tpu.models.constrained import JsonTokenTable
            tt = JsonTokenTable.for_tokenizer(
                self.tokenizer, self.tc.vocab_size, self.tc.eos_token_id,
                extra_stop_ids=tuple(self.tc.stop_token_ids),
                action_enum=list(action_enum) if action_enum else None)
            for old in list(self._grammar_cache)[:max(
                    0, len(self._grammar_cache) - 3)]:
                del self._grammar_cache[old]     # keep newest 3 + this
            self._grammar_cache[key] = (tt.table, tt.start_state,
                                        jnp.asarray(tt.table))
        return self._grammar_cache[key]

    def next_rng(self) -> jax.Array:
        self._rng, k = jax.random.split(self._rng)
        return k

    # ------------------------------------------------------------------

    def drop_session(self, session_id: str) -> None:
        with self.lock:
            self._sessions.pop(session_id, None)

    def session_tokens(self, session_id: str) -> Optional[list]:
        """The session's resident conversation ids, or None — mirrors
        GenerateEngine.session_tokens EXACTLY so callers splice the next
        round's prompt identically against whichever store holds the
        session. Engine parity detail: on a "length" finish the final
        emitted token was sampled but never forwarded (no KV), and the
        engine's store-back retains only KV-valid ids — so the view
        drops ctx's trailing pending token for length-finished sessions
        (a "stop" finish already popped its unforwarded terminal).
        Splicing from a different id set than the engine would let the
        next prompt's BPE merge differently and silently fork temp-0
        bits between the speculative and vanilla paths."""
        with self.lock:
            s = self._sessions.get(session_id)
            if s is None:
                return None
            ctx = s["ctx"]
            return list(ctx[:-1] if s.get("finish") == "length" else ctx)

    def generate(self, prompt, *, max_new_tokens: int = 128,
                 temperature: float = 0.0, top_p: float = 1.0,
                 constrain_json: bool = False,
                 action_enum=None,
                 session_id: Optional[str] = None,
                 rng: Optional[jax.Array] = None) -> SpecResult:
        t0 = time.monotonic()
        K = self.k
        prompt = list(prompt)
        assert prompt, "empty prompt"
        assert len(prompt) + max_new_tokens < self.max_seq, \
            f"prompt {len(prompt)} + max_new {max_new_tokens} >= " \
            f"max_seq {self.max_seq}"
        assert temperature <= 0 or top_p >= 1.0, \
            ("speculative v1 supports top_p only in greedy mode: the "
             "acceptance test needs q to be the ACTUAL proposal "
             "distribution, and the nucleus mask is not applied to q")
        rng = rng if rng is not None else self.next_rng()
        rng_np = np.random.default_rng(int(jax.random.bits(rng) & 0x7fffffff))
        temp = jnp.asarray([float(temperature)], jnp.float32)
        topp = jnp.asarray([float(top_p)], jnp.float32)
        if constrain_json:
            tbl_np, start_state, tbl_dev = self._grammar(action_enum)
            jstate = start_state
        else:
            tbl_np, jstate = None, -1
            tbl_dev = jnp.zeros((1, self.tc.vocab_size), jnp.int16)

        # --- cache resolution: session resume or fresh prefill ----------
        # Session resume (speculative counterpart of the engine's token
        # splice): caches hold ctx[:-1] of the PRIOR call's prompt +
        # response; a new prompt that cleanly extends ctx forwards only
        # the suffix — a refinement round re-prefills template glue, not
        # the conversation — then decode speculates as usual.
        n_cached = 0
        sess = self._sessions.get(session_id) if session_id else None
        need = len(prompt) + max_new_tokens + K + 1
        if sess is not None:
            ctx = sess["ctx"]
            lcp = 0
            for a, b in zip(ctx, prompt):
                if a != b:
                    break
                lcp += 1
            suffix = prompt[len(ctx) - 1:-1]
            # dynamic_update_slice CLAMPS out-of-range starts — an
            # overrunning chunk would silently shift left over valid
            # prefix KV, so BOTH the decode chunks (need, which includes
            # K+1) and the 64-padded extend chunk must provably fit
            fits = (need <= sess["cache_len"]
                    and (len(ctx) - 1 + _round_up(max(1, len(suffix)), 64)
                         <= sess["cache_len"]))
            if lcp == len(ctx) and len(prompt) >= len(ctx) and fits:
                tcache, dcache = sess["t"], sess["d"]
                n_cached = len(ctx)
                # forward ctx[-1] .. prompt[-2] so caches hold prompt[:-1]
                if suffix:
                    pad = _round_up(len(suffix), 64)
                    sf = np.zeros((1, pad), np.int32)
                    sf[0, :len(suffix)] = suffix
                    cl = jnp.asarray([len(suffix)], jnp.int32)
                    tcache = self._extend(self.tp, tcache,
                                          jnp.asarray(sf), cl, "t")
                    dcache = self._extend(self.dp, dcache,
                                          jnp.asarray(sf), cl, "d")
            else:
                sess = None                      # diverged or outgrown
                self._sessions.pop(session_id, None)
        if sess is None:
            # session caches carry decode slack (K+1) plus the extend
            # pad overhang (63) ABOVE max_seq — see the clamp note above
            cache_len = (_round_up(self.max_seq + K + 64, 128)
                         if session_id else _round_up(need, 128))
            pad = _round_up(len(prompt), 64)
            toks = np.zeros((1, pad), np.int32)
            toks[0, :len(prompt)] = prompt
            lens = jnp.asarray([len(prompt)], jnp.int32)
            # Both caches prefill ctx[:-1] = prompt minus its last token,
            # so the invariant (pending un-forwarded) holds from the
            # start. Prefill with full prompt length then roll lens back
            # one: the last column's KV is simply overwritten by the
            # first chunk.
            _, tcache = self._prefill(self.tp, jnp.asarray(toks), lens,
                                      cache_len, "t")
            _, dcache = self._prefill(self.dp, jnp.asarray(toks), lens,
                                      cache_len, "d")
            tcache = tcache._replace(lens=lens - 1)
            dcache = dcache._replace(lens=lens - 1)
        else:
            cache_len = sess["cache_len"]
        pending = jnp.asarray([prompt[-1]], jnp.int32)

        stops = {self.tc.eos_token_id, *self.tc.stop_token_ids}
        emitted: list[int] = []
        rounds = drafted = accepted_total = 0
        finish = "length"
        def host_advance(s: int, tok: int) -> int:
            if not constrain_json or s < 0:
                return s
            return int(tbl_np[s, tok])

        while len(emitted) < max_new_tokens:
            rounds += 1
            rng, kd = jax.random.split(rng)
            jstate0 = jnp.asarray([jstate], jnp.int32)
            d_toks, q_probs, dcache = self._draft_scan(
                self.dp, dcache, pending, kd, temp, topp,
                tbl_dev, jstate0, constrained=constrain_json,
                greedy=temperature <= 0)
            chunk = jnp.concatenate([pending, d_toks[:-1]])
            # verify dispatches on DEVICE values only (the per-position
            # grammar states walk in-device from jstate0) — no host sync
            # sits between the draft scan and the target chunk
            p_probs, p_am, tcache = self._verify_chunk(
                self.tp, tcache, chunk, jnp.broadcast_to(temp, (K,)),
                tbl_dev, jstate0, constrained=constrain_json,
                greedy=temperature <= 0)
            d = np.asarray(d_toks)
            if temperature <= 0:
                # greedy needs only the [K] argmax ids — accepted drafts
                # equal them and corrections ARE them. The [K, V] prob
                # tensors never materialize host-side (at 128k vocab
                # that's megabytes per round through the dispatch
                # channel).
                pam = np.asarray(p_am)
                q = p = None
            else:
                q = np.asarray(q_probs)
                p = np.asarray(p_probs)
                pam = None
            drafted += K

            j = 0
            correction: Optional[int] = None
            while j < K:
                di = int(d[j])
                if temperature <= 0:
                    ok = di == int(pam[j])
                else:
                    ok = rng_np.random() < min(
                        1.0, float(p[j, di]) / max(float(q[j, di]), 1e-20))
                if not ok:
                    if temperature <= 0:
                        correction = int(pam[j])
                    else:
                        residual = np.maximum(p[j] - q[j], 0.0)
                        tot = residual.sum()
                        correction = (int(np.argmax(p[j])) if tot <= 0
                                      else int(rng_np.choice(
                                          residual.shape[0],
                                          p=residual / tot)))
                    break
                j += 1
            accepted_total += j

            new_tokens = [int(x) for x in d[:j]]
            if correction is not None:
                new_tokens.append(correction)
            # commit: truncate at stop/max_new, roll caches to ctx'[:-1].
            # The budget cut applies FIRST — a stop token that lands just
            # past max_new is cut away and must report "length", exactly
            # as vanilla decode's row_limit would (engine parity).
            cut = len(new_tokens)
            stop_at = None
            for idx, t in enumerate(new_tokens):
                if t in stops:
                    stop_at = idx
                    cut = idx + 1
                    break
            room = max_new_tokens - len(emitted)
            cut = min(cut, room)
            if stop_at is not None and stop_at < cut:
                finish = "stop"
            new_tokens = new_tokens[:cut]
            emitted.extend(new_tokens)
            for t in new_tokens:
                jstate = host_advance(jstate, t)
            if finish == "stop" or len(emitted) >= max_new_tokens:
                break
            # lens' = len(ctx') - 1; ctx' grew by len(new_tokens)
            ctx_len = len(prompt) + len(emitted)
            new_lens = jnp.asarray([ctx_len - 1], jnp.int32)
            tcache = tcache._replace(lens=new_lens)
            dcache = dcache._replace(lens=new_lens)
            pending = jnp.asarray([emitted[-1]], jnp.int32)

        # engine parity: the terminal stop token is popped from the output
        # (generate.py result assembly does the same)
        if emitted and emitted[-1] in stops:
            emitted.pop()
            finish = "stop"
        if session_id and emitted:
            # store at the invariant: caches hold ctx'[:-1]. Committed
            # tokens' KV is valid through ctx'-2 (a trailing correction's
            # position is excluded by the -1; rejected drafts past it are
            # masked and later overwritten in place).
            ctx_out = prompt + emitted
            norm = jnp.asarray([len(ctx_out) - 1], jnp.int32)
            # LRU, not FIFO: pop-then-reinsert moves a re-stored session
            # to the end, so the hot session is never the eviction victim
            self._sessions.pop(session_id, None)
            for old in list(self._sessions)[:max(
                    0, len(self._sessions) - 7)]:
                self._sessions.pop(old)          # bound: newest 7 + this
            self._sessions[session_id] = {
                "t": tcache._replace(lens=norm),
                "d": dcache._replace(lens=norm),
                "ctx": ctx_out, "cache_len": cache_len,
                "finish": finish,
            }
        return SpecResult(
            token_ids=emitted,
            text=self.tokenizer.decode(emitted),
            n_prompt_tokens=len(prompt),
            n_gen_tokens=len(emitted),
            latency_s=time.monotonic() - t0,
            finish_reason=finish,
            rounds=rounds,
            drafted=drafted,
            accepted=accepted_total,
            n_cached_tokens=n_cached,
        )


# ---------------------------------------------------------------------------
# Batched speculation for the CONTINUOUS serving path (ISSUE 6 tentpole)
# ---------------------------------------------------------------------------


class BatchedSpeculator:
    """Draft/verify decoding over the ContinuousBatcher's live slots.

    Where :class:`SpeculativeDecoder` (v1) owns a private batch-1 dense
    cache, this operates entirely on the two engines' PAGED SESSION
    stores — the same KV the vanilla continuous path uses — so rows can
    mix speculative and vanilla ticks freely and nothing is resident
    twice:

      propose   ``draft.generate`` over every eligible slot's context in
                ONE batched call (greedy, grammar-masked — the draft's own
                sessions track ctx, so each round forwards one suffix
                token + K draft steps);
      verify    ``target.verify_chunk`` — ONE teacher-forced chunk
                forward per round across all rows against the target's
                paged session KV, returning per-position grammar-masked
                argmax (greedy rows) and masked softmax probs (sampled
                rows);
      commit    host-side accept/rollback per row. Rollback is FREE: both
                engines resume sessions by longest-common-prefix, so a
                rejected draft's stale KV is simply overwritten by the
                next round's suffix prefill.

    Acceptance math: greedy rows accept d_i iff d_i == argmax(p_i) —
    temp-0 output is bit-identical to vanilla decode. Sampled rows
    (top_p == 1 only) draft GREEDILY, i.e. a deterministic one-hot
    proposal distribution: accept d_i with prob p_i[d_i], else draw the
    correction from p_i with d_i's mass removed, renormalized — the
    standard rejection-sampling construction with q = δ(d_i), which
    preserves the target distribution exactly without shipping draft
    probs to the host.

    ADAPTIVE K (per member): a rolling EWMA of per-round acceptance
    shrinks K toward ``k_min`` when acceptance sags below
    ``shrink_below``, grows it back toward ``k_max`` above
    ``grow_above``, and DISENGAGES to vanilla decode entirely below
    ``accept_floor`` — after ``reprobe_after`` vanilla ticks the member
    re-probes at ``k_min``. All transitions are flight-recorded and the
    current state exports as quoracle_spec_* gauges.

    Not thread-safe for ``run_round`` (the batcher's single worker thread
    owns it); ``stats()``/eligibility reads are lock-guarded snapshots.
    """

    def __init__(self, target_engine, draft_engine, *, k: int = 6,
                 k_min: int = 2, k_max: int = 8,
                 accept_floor: float = 0.35, shrink_below: float = 0.6,
                 grow_above: float = 0.85, ewma_alpha: float = 0.15,
                 reprobe_after: int = 24, seed: int = 0):
        from quoracle_tpu.models.config import require_plain
        for eng in (target_engine, draft_engine):
            require_plain(eng.cfg, "speculative serving (--draft)")
        assert target_engine.cfg.vocab_size == draft_engine.cfg.vocab_size, \
            "draft and target must share one tokenizer/vocab"
        assert target_engine.cfg.sliding_window is None \
            and draft_engine.cfg.sliding_window is None, \
            "speculative serving requires full attention (no sliding window)"
        self.target = target_engine
        self.draft = draft_engine
        self.model = target_engine.cfg.name
        self.k_init = max(1, int(k))
        self.k_min = max(1, min(int(k_min), self.k_init))
        self.k_max = max(self.k_init, int(k_max))
        self.accept_floor = float(accept_floor)
        self.shrink_below = float(shrink_below)
        self.grow_above = float(grow_above)
        self.ewma_alpha = float(ewma_alpha)
        self.reprobe_after = int(reprobe_after)
        self._rng_np = np.random.default_rng(seed)
        self._lock = named_lock("spec.adaptive")
        self._k = self.k_init
        self._engaged = True
        self._ewma: Optional[float] = None
        self._vanilla_ticks = 0            # ticks since disengage
        self._rounds_since_probe = 0       # evidence behind the EWMA
        self._stops = {target_engine.cfg.eos_token_id,
                       *target_engine.cfg.stop_token_ids}
        # cumulative counters (stats() snapshot)
        self.rounds = 0
        self.drafted = 0
        self.accepted = 0
        self.emitted = 0
        self.disengages = 0
        self.reprobes = 0
        self.fallbacks: dict = {}
        self._tables: dict = {}            # enum key -> (np table, start)
        SPEC_K.set(self._k, model=self.model)
        SPEC_ENGAGED.set(1.0, model=self.model)

    # -- eligibility ----------------------------------------------------

    @property
    def k(self) -> int:
        return self._k

    @property
    def engaged(self) -> bool:
        return self._engaged

    def ineligible_reason(self, ctx_len: int, temperature: float,
                          top_p: float) -> Optional[str]:
        """None when a row with this shape may speculate this tick;
        otherwise the fallback reason (exported per-tick by the
        scheduler via note_fallback)."""
        if not self._engaged:
            return "disengaged"
        if temperature > 0 and top_p < 1.0:
            # the acceptance test needs the ACTUAL proposal/target
            # distributions; the nucleus mask is not applied to either
            return "sampling"
        if (ctx_len + self._k + 1 >= self.target.max_seq
                or ctx_len + self._k + 1 >= self.draft.max_seq):
            # overflow-near-window: the verify prompt (ctx + K - 1) and
            # the draft's decode slack must both fit — rows this close to
            # the window decode vanilla and retire at the edge
            return "window"
        return None

    def note_fallback(self, reason: str, n: int = 1) -> None:
        with self._lock:
            self.fallbacks[reason] = self.fallbacks.get(reason, 0) + n
        SPEC_FALLBACK_TOTAL.inc(n, model=self.model, reason=reason)

    def tick_vanilla(self) -> None:
        """Count one disengaged tick; re-probe after ``reprobe_after``."""
        with self._lock:
            if self._engaged:
                return
            self._vanilla_ticks += 1
            if self._vanilla_ticks < self.reprobe_after:
                return
            self._engaged = True
            self._k = self.k_min
            self._ewma = None              # fresh measurement window
            self._rounds_since_probe = 0
            self.reprobes += 1
        SPEC_ENGAGED.set(1.0, model=self.model)
        SPEC_K.set(self._k, model=self.model)
        FLIGHT.record("spec_reprobe", model=self.model, k=self.k_min)

    def drop_session(self, session_id: str) -> None:
        """Release the DRAFT engine's session for a retired row (the
        target session is dropped by the scheduler/engine as usual)."""
        self.draft.drop_session(session_id)

    def swap_draft(self, new_engine):
        """Hot-swap the draft engine (ISSUE 19 promotion path) and
        return the incumbent for instant rollback.

        Safe mid-serving because draft KV is DERIVED state: the new
        engine simply has no sessions yet, so each row's next round
        cold-prefills its context into the new draft — exactly the
        longest-common-prefix resume path a rejected chunk already
        takes. Adaptive state resets to a fresh measurement window
        (k_init, no EWMA) so the incumbent's acceptance history cannot
        disengage — or shield — the candidate."""
        assert new_engine.cfg.vocab_size == self.target.cfg.vocab_size, \
            "draft and target must share one tokenizer/vocab"
        assert new_engine.cfg.sliding_window is None, \
            "speculative serving requires full attention"
        with self._lock:
            old = self.draft
            self.draft = new_engine
            self._k = self.k_init
            self._engaged = True
            self._ewma = None
            self._vanilla_ticks = 0
            self._rounds_since_probe = 0
            self._tables = {}
        SPEC_K.set(self._k, model=self.model)
        SPEC_ENGAGED.set(1.0, model=self.model)
        return old

    # -- the round ------------------------------------------------------

    def _host_table(self, action_enum) -> tuple:
        """(np transition table, start_state) for host-side grammar
        walks, sourced from the TARGET engine's own table cache so the
        mask/table can never drift from what the device applied."""
        key = tuple(sorted(set(action_enum))) if action_enum else None
        hit = self._tables.get(key)
        if hit is None:
            self.target._json_table_device((key,))     # ensure built
            tt = self.target._json_cache[("one", key)]
            for old in list(self._tables)[:max(0, len(self._tables) - 7)]:
                del self._tables[old]                   # keep newest 7 +1
            hit = self._tables[key] = (tt.table, tt.start_state)
        return hit

    def run_round(self, rows) -> dict:
        """One draft/verify round over ``rows`` (scheduler _Row-likes:
        .prompt/.emitted/.temperature/.top_p/.max_new/.session_id/
        .constrain/.action_enum/.json_state/.spec_* fields). Mutates each
        row's emitted/json_state/spec counters in place and returns
        {id(row): "stop" | None} — "stop" rows hit a stop token and must
        retire. Raises on engine failure (the scheduler falls back to
        vanilla for the tick)."""
        K = self._k
        eos = self.draft.cfg.eos_token_id
        ctxs = [list(r.prompt) + list(r.emitted) for r in rows]
        k_req = [max(1, min(K, r.max_new - len(r.emitted))) for r in rows]
        # chip-economics attribution (ISSUE 17): the scheduler's active
        # set shrinks between rounds, so keys are re-declared per engine
        # call, not per tick — one declaration covers exactly one call
        costobs.set_row_keys(_row_keys(rows))
        drafts = self.draft.generate(
            ctxs, temperature=0.0, top_p=1.0, max_new_tokens=k_req,
            session_ids=[r.session_id for r in rows],
            constrain_json=[bool(r.constrain) for r in rows],
            action_enums=[r.action_enum for r in rows],
            initial_json_state=[r.json_state for r in rows])
        proposals = []
        for r, g, kq in zip(rows, drafts, k_req):
            r.chip_ms = getattr(r, "chip_ms", 0.0) + g.chip_ms
            p = list(g.token_ids)
            if g.finish_reason == "stop" and len(p) < kq:
                # the engine pops the terminal stop id; re-propose A stop
                # (eos) — if the target wants a different stop id the
                # verify correction supplies it
                p.append(eos)
            proposals.append(p or [eos])
        need_probs = any(r.temperature > 0 for r in rows)
        costobs.set_row_keys(_row_keys(rows))
        vres = self.target.verify_chunk(
            [c + p[:-1] for c, p in zip(ctxs, proposals)],
            [r.session_id for r in rows],
            [len(p) for p in proposals],
            temperature=[r.temperature for r in rows],
            constrain_json=[bool(r.constrain) for r in rows],
            action_enums=[r.action_enum for r in rows],
            initial_json_state=[r.json_state for r in rows],
            need_probs=need_probs)

        finishes: dict = {}
        drafted = accepted = committed_total = 0
        # serving flywheel intake (ISSUE 19): when the capture plane is
        # live, copy each row's (ctx, proposal, verdicts, correction)
        # AFTER the commit math below — pure reads of values the round
        # computed anyway, so temp-0 bits are identical on or off
        cap_rows: Optional[list] = [] if CAPTURE.active else None
        for r, ctx, props, v in zip(rows, ctxs, proposals, vres):
            ids, probs = v["ids"], v["probs"]
            r.chip_ms = getattr(r, "chip_ms", 0.0) + v.get("chip_ms", 0.0)
            if r.n_cached_first is None:
                r.n_cached_first = v["n_cached"]
            j = 0
            correction: Optional[int] = None
            greedy = r.temperature <= 0
            for t, d in enumerate(props):
                if greedy:
                    ok = d == ids[t]
                else:
                    # one-hot proposal: accept with prob p_t[d]
                    ok = self._rng_np.random() < float(probs[t, d])
                if not ok:
                    if greedy:
                        correction = int(ids[t])
                    else:
                        resid = np.asarray(probs[t], np.float64).copy()
                        resid[d] = 0.0
                        z = resid.sum()
                        correction = (int(ids[t]) if z <= 0 else
                                      int(self._rng_np.choice(
                                          resid.shape[0], p=resid / z)))
                    break
                j += 1
            drafted += len(props)
            accepted += j
            new_tokens = props[:j]
            if correction is not None:
                new_tokens = new_tokens + [correction]
            # stop/budget cut — v1 commit semantics: the budget cut
            # applies FIRST, so a stop landing past max_new reports
            # "length" exactly as vanilla row_limit would
            cut = len(new_tokens)
            stop_at = None
            for idx, t in enumerate(new_tokens):
                if t in self._stops:
                    stop_at = idx
                    cut = idx + 1
                    break
            room = r.max_new - len(r.emitted)
            cut = min(cut, room)
            finish = None
            if stop_at is not None and stop_at < cut:
                finish = "stop"
            out_tokens = new_tokens[:cut]
            if finish == "stop":
                out_tokens = out_tokens[:-1]   # engine parity: stop popped
            r.emitted.extend(out_tokens)
            committed_total += len(out_tokens)
            r.spec_rounds += 1
            r.spec_drafted += len(props)
            r.spec_accepted += j
            if r.constrain and out_tokens:
                table, start = self._host_table(r.action_enum)
                s = r.json_state if (r.json_state is not None
                                     and r.json_state >= 0) else start
                for t in out_tokens:
                    if s >= 0:
                        s = int(table[s, t])
                r.json_state = s
            finishes[id(r)] = finish
            if cap_rows is not None:
                cap_rows.append(spec_example(
                    ctx, props, [int(x) for x in ids[:len(props)]],
                    j, correction, r.temperature, r.constrain,
                    r.action_enum))

        with self._lock:
            self.rounds += 1
            self.drafted += drafted
            self.accepted += accepted
            self.emitted += committed_total
            rate = accepted / max(1, drafted)
            self._ewma = (rate if self._ewma is None else
                          self.ewma_alpha * rate
                          + (1 - self.ewma_alpha) * self._ewma)
            self._rounds_since_probe += 1
            changed = self._adapt_locked()
        SPEC_ROUNDS.inc(model=self.model)
        SPEC_DRAFTED.inc(drafted, model=self.model)
        SPEC_ACCEPTED.inc(accepted, model=self.model)
        SPEC_ACCEPTANCE.observe(rate, model=self.model)
        SPEC_TOKENS_PER_ROUND.observe(committed_total / max(1, len(rows)),
                                      model=self.model)
        if changed:
            SPEC_K.set(self._k, model=self.model)
            SPEC_ENGAGED.set(1.0 if self._engaged else 0.0,
                             model=self.model)
        if cap_rows:
            # outside every lock; the plane absorbs all failures
            CAPTURE.observe_spec_round(self.model, self.draft.cfg.name,
                                       cap_rows)
        return finishes

    def _adapt_locked(self) -> bool:
        """Adaptive-K state machine (caller holds the lock). Returns True
        when K or engagement changed."""
        ewma = self._ewma
        if ewma is None:
            return False
        if ewma < self.accept_floor and self._rounds_since_probe >= 3:
            # acceptance collapse — speculation now COSTS latency (every
            # round pays draft + verify for ~1 token). Disengage; the
            # scheduler's vanilla ticks count toward the re-probe.
            self._engaged = False
            self._vanilla_ticks = 0
            self._ewma = None
            self.disengages += 1
            k_was, self._k = self._k, self.k_init
            FLIGHT.record("spec_disengage", model=self.model,
                          ewma=round(ewma, 3), k=k_was)
            return True
        if ewma < self.shrink_below and self._k > self.k_min:
            self._k -= 1
            return True
        if ewma > self.grow_above and self._k < self.k_max:
            self._k += 1
            return True
        return False

    # -- observability --------------------------------------------------

    def stats(self) -> dict:
        """Point-in-time snapshot for /api/models + the scorecards."""
        with self._lock:
            return {
                "mode": "continuous",
                "draft": self.draft.cfg.name,
                "engaged": self._engaged,
                "k": self._k,
                "k_init": self.k_init,
                "acceptance_ewma": (round(self._ewma, 4)
                                    if self._ewma is not None else None),
                "rounds": self.rounds,
                "drafted_tokens": self.drafted,
                "accepted_tokens": self.accepted,
                "emitted_tokens": self.emitted,
                "acceptance_rate": (round(self.accepted
                                          / max(1, self.drafted), 4)
                                    if self.drafted else None),
                "tokens_per_round": (round(self.emitted
                                           / max(1, self.rounds), 2)
                                     if self.rounds else None),
                "disengages": self.disengages,
                "reprobes": self.reprobes,
                "fallbacks": dict(self.fallbacks),
            }
