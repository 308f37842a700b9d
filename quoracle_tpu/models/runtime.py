"""ModelBackend: the seam between the consensus pipeline and model execution.

This interface replaces the reference's entire provider layer — where
ModelQuery fanned out one HTTPS task per model
(reference lib/quoracle/models/model_query.ex:51,88-131), here
``query()`` receives the whole round and batches rows per pool member into
single generate steps on the TPU. Two implementations:

  * TPUBackend  — real serving: one GenerateEngine per pool member + an
    EmbeddingEncoder; zero external calls.
  * MockBackend — deterministic, scripted; the test seam the reference gets
    from mock: model specs + injectable model_query_fn
    (reference consensus/manager.ex:17-21, per_model_query.ex:84,227).

Both are handed to components explicitly (no globals), preserving the
reference's cardinal DI rule (root AGENTS.md:5-33).
"""

from __future__ import annotations

import abc
import dataclasses
import logging
import time
from typing import Any, Callable, Optional, Sequence

import numpy as np

from quoracle_tpu.chaos.faults import CHAOS, InjectedFault
from quoracle_tpu.models.config import (
    OUTPUT_FLOOR, ModelConfig, get_model_config,
)
from quoracle_tpu.infra.telemetry import TRACER
from quoracle_tpu.models.generate import (
    ContextOverflowError, GenerateEngine, splice_session_prompt,
)
from quoracle_tpu.models.tokenizer import Tokenizer, get_tokenizer
from quoracle_tpu.serving.admission import (
    AdmissionError, DeadlineExceededError,
)

logger = logging.getLogger(__name__)


def _row_key(r: dict) -> tuple:
    """Chip-economics attribution key (ISSUE 17) for one generate-row
    dict — integer QoS priorities render as class names so the ledger
    shares the budget plane's vocabulary."""
    from quoracle_tpu.serving.qos import class_name
    return (str(r.get("tenant") or "-"),
            class_name(r.get("priority") if r.get("priority") is not None
                       else 1),
            str(r.get("task_id") or "-"), str(r.get("decide") or "-"))


@dataclasses.dataclass
class QueryRequest:
    """One model's slice of a consensus round."""
    model_spec: str                    # "xla:llama-3-8b"
    messages: list[dict]               # chat messages (system injected already)
    temperature: float = 1.0
    top_p: float = 1.0
    max_tokens: Optional[int] = None   # None = dynamic (window - input, capped)
    # KV residency key (normally the agent id): rows with a session reuse
    # the prompt prefix already resident in that session's cache and refill
    # only the suffix (GenerateEngine sessions; SURVEY §7 hard part 2).
    session_id: Optional[str] = None
    # Grammar-masked sampling: the response is a syntactically valid JSON
    # object by construction (models/constrained.py; SURVEY §7 hard part 4).
    constrain_json: bool = False
    # Schema-aware variant: constrain the top-level "action" value to this
    # capability-gated set (None = syntax-only). Only read when
    # constrain_json is True.
    action_enum: Optional[tuple] = None
    # -- serving QoS (ISSUE 4) ----------------------------------------
    # Multi-tenant attribution + scheduling class (serving/qos.Priority;
    # None = AGENT) + a relative latency budget: a row still queued when
    # ``deadline_ms`` has elapsed since query() entry is failed at admit
    # (DeadlineExceededError → a "deadline_exceeded:" member miss), not
    # decoded. QoS moves WHEN rows run, never what they compute.
    tenant: str = "default"
    priority: Optional[int] = None
    deadline_ms: Optional[float] = None
    # -- fleet observability (ISSUE 15) -------------------------------
    # Compact trace context ({"trace_id", "span_id"}, infra/fleetobs.
    # TraceContext.to_dict) stamped by the sender so a peer process can
    # rebind TRACER and its spans land in the SAME trace. None = root
    # locally (the un-traced behavior). Observability only: never read
    # by generate/sampling paths, so temp-0 bits are identical with or
    # without it.
    trace: Optional[dict] = None
    # -- chip economics (ISSUE 17) -------------------------------------
    # Attribution keys for the ChipLedger: the owning task/agent-tree
    # (the PR 5 audit's task_id) and the decide id within it. Read only
    # by the costobs charge path — never by generate/sampling.
    task_id: Optional[str] = None
    decide: Optional[str] = None
    # -- session-graph observability (ISSUE 20) ------------------------
    # Compact tree context (infra/treeobs.TreeContext.to_dict: tree /
    # node / parent ids + depth + spawn ordinal) stamped at the agent
    # spawn that issued this request, riding rows and wire headers like
    # ``trace`` above. Read only by treeobs charge sites — never by
    # generate/sampling, so temp-0 bits are identical with or without
    # it.
    tree: Optional[dict] = None


@dataclasses.dataclass
class Usage:
    prompt_tokens: int = 0
    completion_tokens: int = 0
    cost: float = 0.0


@dataclasses.dataclass
class QueryResult:
    model_spec: str
    text: str = ""
    usage: Usage = dataclasses.field(default_factory=Usage)
    latency_ms: float = 0.0
    # Per-phase device timing (SURVEY §5 tracing): prefill is MXU-bound,
    # decode is HBM-bound — a single latency hides which one regressed.
    prefill_ms: float = 0.0
    decode_ms: float = 0.0
    # Prompt tokens served from resident KV (session resume or a radix
    # prefix-cache hit, models/prefix_cache.py) instead of re-prefilled.
    cached_tokens: int = 0
    # Speculative serving attribution (ISSUE 6): draft/verify rounds this
    # row rode and draft tokens the target accepted — rolls up into
    # ConsensusOutcome.spec_{accepted_tokens,rounds} for per-decide
    # speedup attribution at /api/consensus.
    spec_rounds: int = 0
    spec_accepted_tokens: int = 0
    # Chip economics (ISSUE 17): this result's measured share of device
    # wall (infra/costobs.ChipLedger row shares, ms). 0.0 with
    # accounting off.
    chip_ms: float = 0.0
    error: Optional[str] = None        # None = success
    permanent_error: bool = False      # parity: only auth-type errors are
                                       # permanent (model_query.ex:322-332)

    @property
    def ok(self) -> bool:
        return self.error is None


class ModelBackend(abc.ABC):
    """What the consensus layer depends on. All methods are synchronous and
    thread-safe; the agent runtime calls them from executor threads."""

    @abc.abstractmethod
    def query(self, requests: Sequence[QueryRequest]) -> list[QueryResult]: ...

    @abc.abstractmethod
    def embed(self, texts: Sequence[str]) -> list[np.ndarray]: ...

    @abc.abstractmethod
    def count_tokens(self, model_spec: str, text: str) -> int: ...

    def count_message_tokens(self, model_spec: str, messages: Sequence[dict]) -> int:
        from quoracle_tpu.models.tokenizer import _stringify_content
        total = 0
        for m in messages:
            content = m.get("content", "")
            if not isinstance(content, str):
                content = _stringify_content(content)
            total += self.count_tokens(model_spec, content) + 4  # role overhead
        return total

    @abc.abstractmethod
    def context_window(self, model_spec: str) -> int: ...

    @abc.abstractmethod
    def output_limit(self, model_spec: str) -> int: ...

    def drop_session(self, session_id: str,
                     model_specs: Optional[Sequence[str]] = None) -> None:
        """Release resident KV state for a conversation (called on agent
        termination / pool switch). ``model_specs`` limits the drop to those
        members' engines — a pool switch keeps unchanged members' still-valid
        prefixes resident. No-op for backends without KV residency."""

    def attach_bus(self, bus) -> None:
        """Optional: give the backend an event bus to broadcast serving
        telemetry on (TOPIC_SERVING — prefix-cache hit/miss/evict counters,
        phase timings). No-op for backends without serving internals."""

    def watchdog_sources(self) -> list:
        """(name, progress_fn) pairs for the Runtime's stall watchdog
        (runtime.StallWatchdog); each fn returns (active, progress
        counter). Empty for backends without decode loops to watch."""
        return []

    def scheduler_stats(self) -> dict:
        """Per-member continuous-batcher health snapshots for
        /api/resources (queue depth, live rows, retired/failed counts).
        Empty for backends without a scheduler."""
        return {}

    def qos_stats(self) -> dict:
        """Serving-QoS snapshot for /api/qos (admission controller,
        per-member weighted-fair queues, SLO tracker). ``enabled`` False
        for backends without QoS wiring."""
        return {"enabled": False}

    def spec_stats(self) -> dict:
        """Speculative-serving snapshot for /api/models and the
        /telemetry view (ISSUE 6): per-member acceptance, adaptive-K
        state, and fallback attribution. ``enabled`` False for backends
        without draft models."""
        return {"enabled": False}

    def kv_stats(self) -> dict:
        """Tiered-KV snapshot for /api/kv (ISSUE 7): per-engine tier
        occupancy (HBM pages / host bytes / disk entries) and the
        demote/restore counters. ``enabled`` False for backends without
        tiering."""
        return {"enabled": False}

    def prefetch_sessions(self, session_id: str) -> int:
        """Warm hibernated KV for a conversation before it runs (the
        agent-tick prefetch hook, ISSUE 7): best-effort page-in on every
        engine holding a host-tier copy. Returns engines warmed. No-op
        for backends without tiering."""
        return 0


# ---------------------------------------------------------------------------
# TPU backend
# ---------------------------------------------------------------------------

def _encode_multimodal(engine, messages) -> tuple[list[int], Optional[object]]:
    """VLM prompt construction: the first image part in the conversation
    becomes ``n_patches`` placeholder ids at its position in the rendered
    chat (the engine's VLM prefill splices projected patches there); any
    further images degrade to the textual "[image]" marker. Returns
    (ids, preprocessed HWC image or None).

    Reference parity: ImageDetector collects base64/URL image parts into
    the provider payload (reference agent/consensus/image_detector.ex);
    here the payload is the in-tree vision tower's pixel input."""
    import base64

    cfg = engine.cfg
    tok = engine.tokenizer
    SENT = "\x00IMG\x00"
    image = None
    flat = []
    for m in messages:
        content = m.get("content", "")
        if isinstance(content, str):
            flat.append({"role": m.get("role", "user"), "content": content})
            continue
        parts_txt = []
        for part in content if isinstance(content, (list, tuple)) else []:
            if not isinstance(part, dict):
                parts_txt.append(str(part))
                continue
            if part.get("type") in ("image", "image_base64", "image_url"):
                data = (part.get("data") or part.get("image_base64")
                        or part.get("base64"))
                if image is None and data:
                    try:
                        from quoracle_tpu.native.image import (
                            preprocess_for_vision,
                        )
                        image = preprocess_for_vision(
                            base64.b64decode(data),
                            size=cfg.vision.image_size)
                        parts_txt.append(SENT)
                        continue
                    except Exception:
                        logger.warning(
                            "image part could not be decoded; degrading "
                            "to [image]")
                parts_txt.append("[image]")
            else:
                parts_txt.append(str(part.get("text", "")))
        flat.append({"role": m.get("role", "user"),
                     "content": "\n".join(parts_txt)})
    rendered = tok.render_chat(flat)
    if image is not None and SENT in rendered:
        pre, post = rendered.split(SENT, 1)
        ids = (tok.encode(pre, add_bos=True)
               + [cfg.image_token_id] * cfg.vision.n_patches
               + tok.encode(post))
        return ids, image
    return tok.encode(rendered, add_bos=True), None


class TPUBackend(ModelBackend):
    """Serves a pool of catalog models resident on the chip/mesh.

    With exact tokenizers there is no 12% estimation margin (reference
    per_model_query.ex:20-24) — max_tokens = window - exact_input, floored.
    """

    def __init__(self, pool: Sequence[str], *, seed: int = 0,
                 embed_model: Optional[str] = None,
                 engines: Optional[dict[str, GenerateEngine]] = None,
                 embedder=None,
                 submeshes: Optional[Sequence] = None,
                 overlap: bool = True,
                 continuous_chunk: int = 32, continuous_slots: int = 8,
                 draft_map: Optional[dict] = None, draft_k: int = 6,
                 qos=None, host_kv_mb: int = 0,
                 disk_kv_dir: Optional[str] = None,
                 disk_kv_gb: float = 8.0,
                 quantize_weights: bool = False,
                 quantize_kv: bool = False):
        """``submeshes``: one jax Mesh per pool member (parallel.mesh.
        pool_submeshes) — each member's engine serves tp-sharded on its own
        chips, and ``overlap`` runs members concurrently from host threads
        instead of the sequential loop (SURVEY §7 hard part 1). None =
        single-device engines.

        Every member serves through ONE batcher, DECODE-level continuous
        batching (models/scheduler.py): each member runs a chunked decode
        loop that concurrent agents' text rows join and leave at
        ``continuous_chunk``-token boundaries, up to ``continuous_slots``
        rows per step. Image rows (which skip KV sessions by design) take
        a direct engine call.

        ``qos`` turns on serving QoS (ISSUE 4): pass True for defaults
        or a serving/qos.QoSConfig. Each member's continuous batcher
        then admits through a weighted-fair DRR queue (aging floor
        included), a shared AdmissionController sheds under overload
        with structured ``retry_after_ms`` rejects, and a shared
        SLOTracker demotes bulk-class weight while the INTERACTIVE
        latency tail is over target."""
        import jax
        from quoracle_tpu.models.embeddings import EmbeddingEncoder
        from quoracle_tpu.models.transformer import init_params

        self.pool = list(pool)
        self.engines: dict[str, GenerateEngine] = dict(engines or {})
        self.overlap = overlap
        self._bus = None          # attach_bus: serving-telemetry broadcasts
        # Int8 quantized serving (ISSUE 13, models/quant.py): applied
        # uniformly to every engine this backend builds — pool members
        # AND their draft engines — so a member's whole decode stack
        # (draft, verify, vanilla) shares one numeric regime and the
        # quantized self-consistency gates hold across modes.
        self.quantize_weights = bool(quantize_weights)
        self.quantize_kv = bool(quantize_kv)

        def build_engine(spec: str, i: int, mesh=None) -> GenerateEngine:
            cfg = get_model_config(spec)
            if cfg.checkpoint_path:
                # Real weights: HF safetensors → stacked pytree
                # (models/loader.py); the catalog entry carries the path
                # (register_hf_checkpoint). With a mesh, leave params as
                # host numpy — the engine's shard_params places them
                # directly; going through to_device first would park a
                # whole replicated copy on one chip.
                from quoracle_tpu.models.loader import load_params, to_device
                params = load_params(cfg.checkpoint_path, cfg)
                if mesh is None:
                    params = to_device(params)
            else:
                # with a mesh, every weight is created in its shard_params
                # placement — a whole-model init would not fit chip 0
                params = init_params(cfg, jax.random.PRNGKey(seed + i),
                                     mesh=mesh)
            return GenerateEngine(cfg, params, get_tokenizer(spec),
                                  seed=seed + i, mesh=mesh,
                                  quantize_weights=self.quantize_weights,
                                  quantize_kv=self.quantize_kv)

        for i, spec in enumerate(self.pool):
            if spec in self.engines:
                continue
            mesh = submeshes[i % len(submeshes)] if submeshes else None
            self.engines[spec] = build_engine(spec, i, mesh)

        # Tiered KV (ISSUE 7, serving/kvtier.py): HBM eviction demotes
        # hibernating sessions to a per-member host-RAM page store
        # (``host_kv_mb`` each) and prefix-cache blocks persist to a
        # checksummed disk store under ``disk_kv_dir`` that warm-starts
        # the next process. Pool members only — draft engines' shadow
        # sessions are derived state, cheaper to re-draft than to park.
        self.kv_tiered = bool(host_kv_mb or disk_kv_dir)
        if self.kv_tiered:
            for spec in self.pool:
                self.engines[spec].attach_tier(
                    host_mb=host_kv_mb or 256, disk_dir=disk_kv_dir,
                    disk_gb=disk_kv_gb)

        # Speculative serving (models/speculative.py): draft_map routes a
        # member's decode through draft-K/verify-one-chunk decoding —
        # output stays token-exact at temperature 0. Draft engines load
        # like members but never serve as pool members themselves. One
        # BatchedSpeculator per drafted member rides the ContinuousBatcher's
        # decode ticks (ISSUE 6: batched draft scan + one chunked multi-row
        # verify per round against the paged session KV; adaptive K with
        # vanilla fallback). Built below, handed to the batcher.
        self.draft_map = dict(draft_map or {})
        self._speculators: dict = {}
        if draft_map:
            from quoracle_tpu.models.speculative import BatchedSpeculator
            for j, (tspec, dspec) in enumerate(sorted(draft_map.items())):
                if tspec not in self.engines:
                    raise KeyError(f"draft_map target {tspec!r} is not in "
                                   f"the pool")
                if dspec not in self.engines:
                    self.engines[dspec] = build_engine(
                        dspec, len(self.pool) + 100 + j)
                self._speculators[tspec] = BatchedSpeculator(
                    self.engines[tspec], self.engines[dspec], k=draft_k)

        # Serving QoS (ISSUE 4): ONE controller + SLO tracker shared
        # across members (overload and tail burn are system conditions),
        # one weighted-fair queue per member. qos=True → defaults.
        self.qos_controller = None
        self.slo = None
        qos_policies: dict[str, Any] = {}
        if qos:
            from quoracle_tpu.serving.admission import AdmissionController
            from quoracle_tpu.serving.qos import (
                QoSConfig, WeightedFairPolicy,
            )
            from quoracle_tpu.serving.slo import SLOTracker
            qcfg = qos if isinstance(qos, QoSConfig) else QoSConfig()
            self.slo = SLOTracker(targets_ms=qcfg.slo_targets_ms)
            # HBM-headroom signal (ISSUE 7): with tiering on, pages held
            # by demotable sessions/cache leaves are RECLAIMABLE without
            # loss — the controller sees raw headroom plus that margin,
            # so bulk classes are not shed for memory the tier ladder
            # can free on demand.
            from quoracle_tpu.infra.resources import (
                effective_headroom_fraction,
            )
            self.qos_controller = AdmissionController(
                config=qcfg.admission, tenants=qcfg.tenants,
                headroom_fn=(lambda: effective_headroom_fraction(self))
                if self.kv_tiered else None)
            qos_policies = {
                spec: WeightedFairPolicy(
                    weights=qcfg.weights, quantum=qcfg.quantum,
                    aging_floor_s=qcfg.aging_floor_s,
                    weight_fn=self.slo.weight_multiplier, model=spec)
                for spec in self.pool}
        # One batcher per POOL member (draft engines never serve
        # directly): every text row of the member rides its decode loop
        from quoracle_tpu.models.scheduler import ContinuousBatcher
        self._cbatchers = {
            spec: ContinuousBatcher(self.engines[spec],
                                    chunk=continuous_chunk,
                                    max_slots=continuous_slots,
                                    policy=qos_policies.get(spec),
                                    admission=self.qos_controller,
                                    slo=self.slo,
                                    speculator=self._speculators.get(spec))
            for spec in self.pool}
        if self.qos_controller is not None:
            for spec, pol in qos_policies.items():
                self.qos_controller.register_depth_source(spec, pol.qsize)

        if embedder is not None:
            self.embedder = embedder
        else:
            espec = embed_model or self.pool[0]
            eshard = None
            if espec in self.engines:
                e = self.engines[espec]
                eparams, ecfg, etok = e.params, e.cfg, e.tokenizer
                if e.attn_shard is not None:
                    # embedding batches are not padded to dp: rows stay
                    # replicated, heads still split over tp
                    eshard = (*e.attn_shard[:2], None)
            else:
                ecfg = get_model_config(espec)
                eparams = init_params(ecfg, jax.random.PRNGKey(seed + 101))
                etok = get_tokenizer(espec)
            self.embedder = EmbeddingEncoder(ecfg, eparams, etok,
                                             shard=eshard)

    def close(self) -> None:
        """Stop the batcher threads. Queued rows fail loudly rather than
        stranding waiters — scheduler.close() semantics. Tiered engines
        drain their queued disk spills so a clean shutdown hands its
        successor every persisted prefix (an abrupt kill loses at most
        the queue — the store is an optimization, never state)."""
        for cb in self._cbatchers.values():
            cb.close()
        for eng in self.engines.values():
            tier = getattr(eng.sessions, "tier", None)
            if tier is not None:
                try:
                    tier.flush_spills()
                except Exception:         # noqa: BLE001 — best-effort
                    pass

    # -- ModelBackend --

    def query(self, requests: Sequence[QueryRequest]) -> list[QueryResult]:
        """Group rows by pool member; one batched generate per member.

        Members OVERLAP: each member's generate is dispatched from its own
        host thread, so on sub-meshed slices the three models decode
        concurrently on their own chips (SURVEY.md §7 hard part 1; replaces
        the reference's Task.async-per-model HTTPS fan-out,
        per_model_query.ex:312-342). On a single chip the dispatches
        serialize on the device queue — same latency as the sequential loop.
        """
        by_model: dict[str, list[int]] = {}
        for i, r in enumerate(requests):
            by_model.setdefault(r.model_spec, []).append(i)

        results: list[Optional[QueryResult]] = [None] * len(requests)
        groups = list(by_model.items())
        # Span propagation across the member-thread hop: the consensus
        # round's span is thread-local to THIS thread, so capture it here
        # and rebind it inside each member thread (telemetry.TRACER.use).
        parent = TRACER.current()
        if self.overlap and len(groups) > 1:
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(max_workers=len(groups),
                                    thread_name_prefix="pool-member") as ex:
                list(ex.map(lambda g: self._query_member(
                    g[0], g[1], requests, results, parent), groups))
        else:
            for spec, idxs in groups:
                self._query_member(spec, idxs, requests, results, parent)
        self._broadcast_serving(by_model)
        return [r for r in results if r is not None]

    def attach_bus(self, bus) -> None:
        self._bus = bus

    def watchdog_sources(self) -> list:
        return [(f"decode-loop:{spec}", cb.progress)
                for spec, cb in self._cbatchers.items()]

    def scheduler_stats(self) -> dict:
        return {spec: cb.stats() for spec, cb in self._cbatchers.items()}

    def swap_draft(self, tspec: str, engine, name: Optional[str] = None):
        """Hot-swap the draft engine behind ``tspec``'s speculator
        (ISSUE 19 promotion path) and return the incumbent engine for
        instant rollback. The caller owns both engines' lifecycles — the
        swapped-out incumbent is NOT closed (a rollback re-installs the
        same object), and ``close()`` never reaches a swapped-in engine.
        Draft KV is derived state: rows cold re-prefill into the new
        draft's sessions on their next round."""
        speculator = self._speculators.get(tspec)
        if speculator is None:
            raise KeyError(f"no speculator for {tspec!r} "
                           f"(draft_map: {sorted(self.draft_map)})")
        old = speculator.swap_draft(engine)
        self.draft_map[tspec] = name or engine.cfg.name
        return old

    def spec_stats(self) -> dict:
        if not self._speculators:
            return {"enabled": False}
        return {"enabled": True, "draft_map": dict(self.draft_map),
                "members": {spec: s.stats()
                            for spec, s in self._speculators.items()}}

    def kv_stats(self) -> dict:
        if not self.kv_tiered:
            return {"enabled": False}
        members = {}
        for spec in self.pool:
            e = self.engines[spec]
            st = e.sessions
            tier = st.tier
            if tier is None:
                continue
            with st.lock:
                free = len(st._free)
                n_sessions = len(st._sessions)
                occ = st.prefix_cache.occupancy()
            members[spec] = {
                "hbm": {
                    "pages": st.n_pages,
                    "free_pages": free,
                    "used_pages": st.n_pages - 1 - free,
                    "sessions": n_sessions,
                    "prefix_cache": occ,
                },
                # compression posture (ISSUE 13): /api/kv's compression
                # column — int8 members report their per-token byte
                # rate vs the bf16 rate they would otherwise pay
                "quant": e.quant_stats(),
                **tier.stats(),
            }
        return {"enabled": True, "members": members}

    def prefetch_sessions(self, session_id: str) -> int:
        if not self.kv_tiered:
            return 0
        warmed = 0
        for spec in self.pool:
            if self.engines[spec].prefetch_session(session_id):
                warmed += 1
        return warmed

    def qos_stats(self) -> dict:
        if self.qos_controller is None:
            return {"enabled": False}
        return {
            "enabled": True,
            "admission": self.qos_controller.stats(),
            "slo": self.slo.stats() if self.slo is not None else None,
            "queues": {spec: cb.stats().get("qos")
                       for spec, cb in self._cbatchers.items()},
        }

    def _broadcast_serving(self, by_model: dict) -> None:
        """One TOPIC_SERVING event per query round: each queried member's
        phase timings + radix-prefix-cache counters, for the dashboard's
        ring-buffer replay (infra/event_history.py) and SSE tail. Never
        raises into the serving path."""
        if self._bus is None:
            return
        try:
            from quoracle_tpu.infra.bus import TOPIC_SERVING
            members = {}
            for spec in by_model:
                e = self.engines.get(spec)
                if e is None:
                    continue
                members[spec] = {
                    "prefill_tokens": e.last_prefill_tokens,
                    "prefill_ms": round(e.last_prefill_s * 1000, 1),
                    "decode_ms": round(e.last_decode_s * 1000, 1),
                    "kv_free_pages": e.sessions.free_pages(),
                    "prefix_cache": e.sessions.prefix_cache.stats(),
                }
            self._bus.broadcast(TOPIC_SERVING, {
                "event": "serving_round", "ts": time.time(),
                "members": members})
        except Exception:                 # noqa: BLE001 — telemetry only
            logger.exception("serving telemetry broadcast failed")

    def _query_member(self, spec: str, idxs: list[int],
                      requests: Sequence[QueryRequest],
                      results: list[Optional[QueryResult]],
                      parent=None) -> None:
        """One pool member's slice of the round, wrapped in a
        ``backend.member`` span (rebinding ``parent`` — the consensus
        round span captured on the query() thread). The member's device
        prefill/decode phases enter the trace retroactively from the
        QueryResult timings (the actual fences live in generate.py)."""
        with TRACER.use(parent):
            with TRACER.span("backend.member", model=spec) as msp:
                self._query_member_impl(spec, idxs, requests, results)
                done = [results[i] for i in idxs
                        if results[i] is not None and results[i].ok]
                msp.attrs.update(
                    n_rows=len(idxs),
                    cached_tokens=sum(r.cached_tokens for r in done))
                if done and (done[0].prefill_ms or done[0].decode_ms):
                    # the first row's own device fences stand for the
                    # member's slice — one retroactive span per phase
                    TRACER.emit("generate.prefill", done[0].prefill_ms,
                                parent=msp, phase="prefill", model=spec)
                    TRACER.emit("generate.decode", done[0].decode_ms,
                                parent=msp, phase="decode", model=spec)

    def _query_member_impl(self, spec: str, idxs: list[int],
                           requests: Sequence[QueryRequest],
                           results: list[Optional[QueryResult]]) -> None:
        """Writes into disjoint ``results`` positions — safe from
        concurrent member threads."""
        if spec not in self._cbatchers:
            # not a pool member — includes draft engines, which load into
            # self.engines but never serve directly
            for i in idxs:
                results[i] = QueryResult(
                    model_spec=spec, error=f"unknown model {spec!r}",
                    permanent_error=True)
            return
        # Chaos seam (ISSUE 11): member crash / slow / garbage at the
        # per-member query entry — a crash fails this member's rows with
        # the structured InjectedFault text (the consensus layer counts
        # it like any transport failure), a garbage directive perturbs
        # the member's OUTPUT after serving (drift-detection food).
        try:
            chaos = CHAOS.fire("pool.member", model=spec)
        except InjectedFault as e:
            for i in idxs:
                results[i] = QueryResult(model_spec=spec, error=str(e))
            return
        t0 = time.monotonic()
        rows, live_idxs = self._build_rows(spec, idxs, requests, results,
                                           t0)
        if not live_idxs:
            return
        self._dispatch_rows(spec, rows, live_idxs, results, t0)
        if chaos is not None and chaos.kind == "garbage":
            for i in live_idxs:
                r = results[i]
                if r is not None and r.ok:
                    results[i] = dataclasses.replace(
                        r, text=f"{r.text} [chaos-garbage:{chaos.n}]")

    def _build_rows(self, spec: str, idxs: list[int],
                    requests: Sequence[QueryRequest],
                    results: list, t0: float) -> tuple[list[dict],
                                                       list[int]]:
        """Row preparation for one member: chat-template encode (VLM
        splice included), session-token splice, per-row overflow /
        deadline checks (failed rows get their QueryResult written into
        ``results`` here), and the output-budget math. Split out of
        ``_query_member_impl`` so the cluster plane (serving/cluster.py)
        prepares IDENTICAL rows for its disaggregated prefill→decode
        flow — one row-construction semantics, zero drift."""
        engine = self.engines[spec]
        rows: list[dict] = []
        live_idxs: list[int] = []
        max_seq = engine.max_seq
        for i in idxs:
            r = requests[i]
            has_image = engine.cfg.vision is not None and any(
                isinstance(m.get("content"), (list, tuple))
                and any(isinstance(p, dict) and p.get("type") in
                        ("image", "image_base64", "image_url")
                        for p in m["content"])
                for m in r.messages)
            if has_image:
                ids, img = _encode_multimodal(engine, r.messages)
            else:
                # text-only requests keep the tokenizer's own chat template
                # (HF checkpoints) — only image-carrying prompts need the
                # placeholder-splicing render
                ids, img = engine.tokenizer.encode_chat(r.messages), None
                if r.session_id:
                    # Token-level session splice: share the session's ACTUAL
                    # ids (prompt + sampled response) as the prompt prefix so
                    # the retained response KV resumes too — re-encoding the
                    # assistant text would break the token match at the
                    # previous prompt's end (generate.splice_session_prompt).
                    sess_toks = engine.session_tokens(r.session_id)
                    if sess_toks:
                        spliced = splice_session_prompt(
                            engine.tokenizer, sess_toks, ids)
                        # dropped-id decode asymmetries can inflate the
                        # spliced length — never let the splice push a
                        # fitting prompt over the window
                        if spliced is not None and len(spliced) < max_seq:
                            ids = spliced
            if len(ids) >= max_seq:
                # Per-ROW overflow: only the oversized row errors; the
                # rest of the group still runs (the condensation layer
                # retries this model after condensing).
                results[i] = QueryResult(
                    model_spec=spec,
                    error=f"context_overflow: prompt {len(ids)} tokens "
                          f">= window {max_seq}")
                continue
            window, out_lim = engine.cfg.context_window, engine.cfg.output_limit
            floor = min(OUTPUT_FLOOR, out_lim)
            budget = min(out_lim, max(floor, window - len(ids)))
            # QoS deadline: the relative budget anchors at query() entry
            # (t0) — time already burned tokenizing/splicing counts.
            deadline_s = (t0 + r.deadline_ms / 1000.0
                          if r.deadline_ms is not None else None)
            if deadline_s is not None and time.monotonic() >= deadline_s:
                # already dead at build time
                results[i] = QueryResult(
                    model_spec=spec,
                    error=f"deadline_exceeded: {r.deadline_ms:.0f}ms "
                          f"budget elapsed before dispatch")
                continue
            rows.append({
                "prompt": ids, "temperature": r.temperature,
                "top_p": r.top_p,
                "budget": min(r.max_tokens, budget) if r.max_tokens
                          else budget,
                "session_id": r.session_id,
                "constrain_json": r.constrain_json,
                "action_enum": r.action_enum, "image": img,
                "priority": r.priority, "tenant": r.tenant,
                "deadline_s": deadline_s,
                "task_id": r.task_id, "decide": r.decide,
                "tree": r.tree,
            })
            live_idxs.append(i)
        return rows, live_idxs

    def _dispatch_rows(self, spec: str, rows: list[dict],
                       live_idxs: list[int], results: list,
                       t0: float) -> None:
        """Serve prepared rows: text rows join the member's shared decode
        loop (models/scheduler.py) at chunk boundaries; image rows — which
        skip KV sessions by design — take a direct engine call."""
        engine = self.engines[spec]
        cfg = engine.cfg
        cb = self._cbatchers[spec]
        futs = []
        for r in rows:
            if r["image"] is not None:
                from concurrent.futures import Future
                f = Future()
                try:
                    # Sessionless image calls never touch the page pool
                    # (generate.py: paged stays False without session_ids)
                    # and the grammar cache now has its own lock
                    # (_grammar_lock), so a long VLM round runs WITHOUT
                    # engine._paged_lock — holding it here stalled every
                    # concurrent text agent's sessioned chunks for the
                    # whole image generate (ADVICE r4).
                    g = engine.generate(
                        [r["prompt"]], temperature=r["temperature"],
                        top_p=r["top_p"], max_new_tokens=r["budget"],
                        constrain_json=[r["constrain_json"]],
                        action_enums=[r["action_enum"]],
                        images=[r["image"]])[0]
                    f.set_result(g)
                except Exception as e:    # noqa: BLE001 — per-row capture
                    f.set_exception(e)
                futs.append(f)
            else:
                futs.append(cb.submit(
                    r["prompt"], temperature=r["temperature"],
                    top_p=r["top_p"], max_new_tokens=r["budget"],
                    session_id=r["session_id"],
                    constrain_json=r["constrain_json"],
                    action_enum=r["action_enum"],
                    priority=r["priority"], tenant=r["tenant"],
                    deadline_s=r["deadline_s"],
                    task_id=r.get("task_id"), decide=r.get("decide"),
                    tree=r.get("tree")))
        for i, f in zip(live_idxs, futs):
            try:
                g = f.result()
            except ContextOverflowError as e:
                results[i] = QueryResult(model_spec=spec,
                                         error=f"context_overflow: {e}")
                continue
            except DeadlineExceededError as e:
                results[i] = QueryResult(model_spec=spec,
                                         error=f"deadline_exceeded: {e}")
                continue
            except AdmissionError as e:   # structured shed, row-level
                results[i] = QueryResult(
                    model_spec=spec,
                    error=f"admission_rejected: {e} "
                          f"(retry_after_ms={e.retry_after_ms})")
                continue
            except Exception as e:        # noqa: BLE001 — row-level error
                results[i] = QueryResult(model_spec=spec,
                                         error=f"generate failed: {e}")
                continue
            latency_ms = (time.monotonic() - t0) * 1000
            cost = (g.n_prompt_tokens * cfg.input_cost_per_mtok
                    + g.n_gen_tokens * cfg.output_cost_per_mtok) / 1e6
            results[i] = QueryResult(
                model_spec=spec, text=g.text,
                usage=Usage(g.n_prompt_tokens, g.n_gen_tokens, cost),
                latency_ms=latency_ms,
                # the row's own device fences, from its row record
                # (models/scheduler.py _finish_row)
                prefill_ms=g.prefill_ms, decode_ms=g.decode_ms,
                cached_tokens=g.n_cached_tokens,
                spec_rounds=getattr(g, "spec_rounds", 0),
                spec_accepted_tokens=getattr(g, "spec_accepted_tokens",
                                             0),
                chip_ms=getattr(g, "chip_ms", 0.0))

    def embed(self, texts: Sequence[str]) -> list[np.ndarray]:
        return self.embedder.embed(texts)

    def drop_session(self, session_id: str,
                     model_specs: Optional[Sequence[str]] = None) -> None:
        keep = None if model_specs is None else set(model_specs)
        for spec, engine in self.engines.items():
            if keep is None or spec in keep:
                # the ENGINE's drop serializes with in-flight sessioned
                # generates — a bare store drop could free pages a running
                # batch still references
                engine.drop_session(session_id)

    def count_tokens(self, model_spec: str, text: str) -> int:
        return self.engines[model_spec].tokenizer.count(text)

    def context_window(self, model_spec: str) -> int:
        return get_model_config(model_spec).context_window

    def output_limit(self, model_spec: str) -> int:
        return get_model_config(model_spec).output_limit


# ---------------------------------------------------------------------------
# Mock backend (tests)
# ---------------------------------------------------------------------------

class MockBackend(ModelBackend):
    """Deterministic scripted backend.

    ``respond`` maps a QueryRequest to response text; default echoes a valid
    wait-action JSON so agent loops terminate. Per-model scripts let consensus
    tests drive disagreement/malformed/invalid scenarios the way the
    reference's MockResponseGenerator does
    (reference agent/consensus/mock_response_generator.ex:31-45).
    Every call is recorded for assertion (the reference's message-capture
    ``model_query_fn`` seam).
    """

    DEFAULT_POOL = ["mock:consensus-model-1", "mock:consensus-model-2",
                    "mock:consensus-model-3"]

    def __init__(self, respond: Optional[Callable[[QueryRequest], str]] = None,
                 scripts: Optional[dict[str, list[str]]] = None,
                 embedder=None, context_window_tokens: int = 128_000,
                 output_limit_tokens: int = 4096,
                 latency_ms: float = 0.0):
        from quoracle_tpu.models.embeddings import HashingEmbedder
        self._respond = respond
        self._scripts = {k: list(v) for k, v in (scripts or {}).items()}
        self._embedder = embedder or HashingEmbedder()
        self._window = context_window_tokens
        self._output_limit = output_limit_tokens
        self._latency_ms = latency_ms
        self.calls: list[QueryRequest] = []

    def query(self, requests: Sequence[QueryRequest]) -> list[QueryResult]:
        out = []
        for r in requests:
            self.calls.append(r)
            # Chaos seam (ISSUE 11): the SAME pool.member injection
            # point as TPUBackend, so member crash/slow/garbage
            # scenarios (drift storms feeding PR 5 detection) run on the
            # mock pool in tier-1 at zero device cost.
            try:
                chaos = CHAOS.fire("pool.member", model=r.model_spec)
            except InjectedFault as e:
                out.append(QueryResult(model_spec=r.model_spec,
                                       error=str(e)))
                continue
            # same span shape as the TPU backend so span-linkage tests
            # (and trace consumers) see decide → round → member on mocks
            with TRACER.span("backend.member", model=r.model_spec):
                script = self._scripts.get(r.model_spec)
                if script:
                    text = script.pop(0)
                elif self._respond is not None:
                    text = self._respond(r)
                else:
                    text = ('{"action": "wait", "params": {"duration": 1}, '
                            '"reasoning": "mock default"}')
            if chaos is not None and chaos.kind == "garbage":
                # a VALID but divergent proposal (a real registered
                # action, different from the healthy members' answer):
                # clusters away from them → dissent, which is what the
                # drift detector keys on. An unknown action would book
                # as a parse failure instead — a different signal.
                text = ('{"action": "orient", "params": '
                        '{"current_understanding": '
                        f'"chaos divergence {chaos.n}", '
                        '"progress_assessment": "diverging"}, '
                        '"wait": 30, '
                        '"reasoning": "chaos-injected divergence"}')
            if text == "__error__":
                out.append(QueryResult(model_spec=r.model_spec,
                                       error="scripted failure"))
                continue
            n_in = self.count_message_tokens(r.model_spec, r.messages)
            out.append(QueryResult(
                model_spec=r.model_spec, text=text,
                usage=Usage(n_in, max(1, len(text) // 4), 0.0),
                latency_ms=self._latency_ms))
        return out

    def embed(self, texts: Sequence[str]) -> list[np.ndarray]:
        return self._embedder.embed(texts)

    def count_tokens(self, model_spec: str, text: str) -> int:
        return max(1, len(text) // 4)

    def context_window(self, model_spec: str) -> int:
        return self._window

    def output_limit(self, model_spec: str) -> int:
        return self._output_limit
