"""Consensus orchestrator: query pool -> parse -> validate -> cluster ->
majority / refinement loop.

Parity with the reference's Agent.Consensus
(reference lib/quoracle/agent/consensus.ex:64,113,129,269-293,295,332-390)
re-shaped for the TPU runtime: the per-model fan-out of the reference (one
Task + HTTPS call per model) is ONE ModelBackend.query call whose rows carry
per-model temperatures — on the TPUBackend that is a single batched generate
step per pool member, refinement rounds included (SURVEY.md §7: batched
refinement is where the TPU design wins over sequential HTTPS).

Pure-logic layer: no persistence, no event bus — the agent runtime (M7)
wires those around it. Dependencies (backend, embedder) arrive explicitly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Sequence

from quoracle_tpu.actions.validator import validate_params, validate_wait_param
from quoracle_tpu.consensus.aggregator import (
    build_refinement_prompt, cluster_proposals, find_majority_cluster,
)
from quoracle_tpu.consensus.parser import (
    ActionProposal, ParseFailure, parse_response,
)
from quoracle_tpu.consensus.quality import QUALITY, build_audit_record
from quoracle_tpu.consensus.result import (
    Decision, pick_winner, select_winner_cluster,
)
from quoracle_tpu.consensus.rules import EmbedAccumulator
from quoracle_tpu.consensus.temperature import temperature_for_round
from quoracle_tpu.infra.telemetry import (
    COST_DECIDE_CHIP_MS, COST_DECIDE_TOKENS,
    DECIDE_MS, ROUND_MS, ROUNDS_TOTAL, TRACER,
)
from quoracle_tpu.models.runtime import ModelBackend, QueryRequest

DEFAULT_THRESHOLD = 0.5          # reference consensus/manager.ex:11-21
DEFAULT_MAX_REFINEMENT_ROUNDS = 4
REASONING_WINDOW_ROUNDS = 2      # sliding window of refinement history kept


def _note_failures(failures: list["ModelFailure"],
                   failure_kinds: dict[str, dict[str, int]],
                   corrected: set[str]) -> None:
    """Fold one round's failures into the decide-wide quality scratch
    (per-member kind counts + who got correction feedback)."""
    for f in failures:
        kinds = failure_kinds.setdefault(f.model_spec, {})
        kinds[f.kind] = kinds.get(f.kind, 0) + 1
        if f.correction is not None:
            corrected.add(f.model_spec)


@dataclasses.dataclass
class ConsensusConfig:
    model_pool: list[str]
    max_refinement_rounds: int = DEFAULT_MAX_REFINEMENT_ROUNDS
    threshold: float = DEFAULT_THRESHOLD
    force_reflection: bool = False   # single-model pools still refine once
    allowed_actions: Optional[set[str]] = None
    profile_optional_spawn: bool = False
    max_tokens: Optional[int] = None
    # KV-residency key (the agent id): refinement rounds and later cycles
    # reuse the resident prompt prefix on the TPU backend.
    session_key: Optional[str] = None
    # Grammar-masked decoding: proposals are valid JSON by construction on
    # backends that support it (TPU); mock/HTTP backends ignore the flag and
    # the parser's markdown-unwrap recovery still applies.
    constrained_json: bool = True
    # Serving QoS (ISSUE 4): class/tenant attribution for every row this
    # engine submits, derived from agent depth by the agent runtime
    # (serving/qos.priority_for_depth — root agents outrank
    # grandchildren), plus an optional per-round latency budget.
    priority: Optional[int] = None
    tenant: str = "default"
    deadline_ms: Optional[float] = None
    # Consensus-quality observability (ISSUE 5, consensus/quality.py):
    # task attribution for the per-decide audit record, and the master
    # switch for the whole quality layer (audit record + scorecard +
    # entropy/margin metrics). Instrumentation is READ-ONLY: temp-0
    # decisions are bit-identical with it on or off.
    task_id: Optional[str] = None
    quality: bool = True
    # Session-graph observability (ISSUE 20): the owning agent's tree
    # context dict (treeobs.TreeContext.to_dict), stamped onto every
    # QueryRequest this engine issues so remote peers book waits to the
    # same tree node, and consumed by the decide chokepoint's per-node
    # chip/token charge. Observed-only; never read by decision logic.
    tree: Optional[dict] = None


@dataclasses.dataclass
class ModelFailure:
    model_spec: str
    error: str
    correction: Optional[str] = None  # feeds per-model correction feedback
    raw_text: str = ""                # the failing response, for history
    # Failure attribution by CAUSE (ISSUE 5): transport = backend error
    # row, parse = not a JSON action, schema = failed param validation,
    # deadline = expired at QoS admission. Scorecards and the audit trail
    # account by kind instead of one undifferentiated list.
    kind: str = "transport"


@dataclasses.dataclass
class ConsensusOutcome:
    status: str                      # "ok" | "all_invalid" | "all_failed"
    decision: Optional[Decision] = None
    proposals: list[ActionProposal] = dataclasses.field(default_factory=list)
    failures: list[ModelFailure] = dataclasses.field(default_factory=list)
    rounds_used: int = 1
    latency_ms: float = 0.0
    prefill_ms: float = 0.0          # summed per-member device phase times
    decode_ms: float = 0.0
    prompt_tokens: int = 0
    completion_tokens: int = 0
    # Prompt tokens served from resident KV (session resume + radix
    # prefix-cache hits) instead of re-prefilled, summed over all rounds
    # and members — the per-turn view of the serving layer's reuse.
    cached_tokens: int = 0
    # Rows that missed their QoS deadline (serving/admission.py) across
    # all rounds. A deadline miss is a MEMBER miss — the member simply
    # has no proposal this round — never a pool failure by itself.
    deadline_misses: int = 0
    # Speculative serving (ISSUE 6): draft/verify rounds and accepted
    # draft tokens summed over all rounds and members — the per-decide
    # speedup attribution beside cached_tokens (an accepted token is a
    # decode step the target never paid weight streaming for). Logged in
    # the decision audit record, queryable at /api/consensus.
    spec_rounds: int = 0
    spec_accepted_tokens: int = 0
    # Chip economics (ISSUE 17): measured device wall this decide
    # consumed (ChipLedger row shares summed over all rounds/members),
    # per member and total, and the decide id the ledger keyed rows by
    # (drawn BEFORE the first round so rows and audit share one id).
    chip_ms: float = 0.0
    member_chip_ms: dict[str, float] = dataclasses.field(
        default_factory=dict)
    decide_id: Optional[str] = None
    cost: float = 0.0
    embed_texts: int = 0
    # Summed per-member proposal latency across all rounds (ms) — the
    # scorecard's per-member latency signal (consensus/quality.py).
    member_latency_ms: dict[str, float] = dataclasses.field(
        default_factory=dict)
    # The per-decide audit record (ISSUE 5): member -> cluster mapping,
    # winner, entropy, margin, failures by kind. None when
    # ConsensusConfig.quality is off.
    audit: Optional[dict] = None
    bug_reports: list[tuple[str, str]] = dataclasses.field(default_factory=list)
    condense_requests: dict[str, int] = dataclasses.field(default_factory=dict)
    # Refinement transcript per model, for history merging by the agent layer:
    # list of (refinement_prompt, model_response_text) pairs, capped to the
    # sliding window (reference consensus/manager.ex:82-93).
    refinement_history: dict[str, list[tuple[str, str]]] = \
        dataclasses.field(default_factory=dict)


class ConsensusEngine:
    """One instance per agent; stateless between decide() calls."""

    def __init__(self, backend: ModelBackend, config: ConsensusConfig,
                 log: Optional[Callable[[str, dict], None]] = None):
        self.backend = backend
        self.config = config
        self._log = log or (lambda event, data: None)

    # ------------------------------------------------------------------

    def decide(self, messages_per_model: dict[str, list[dict]]) -> ConsensusOutcome:
        """Run the full consensus process over per-model message histories.

        ``messages_per_model`` maps model_spec -> chat messages (system prompt
        included) — each pool member fills its own context window (reference
        per-model histories, README.md:642-650).

        Traced end to end (infra/telemetry.py): one ``consensus.decide``
        span (child of the agent's decide-tick span when called from the
        agent runtime) wrapping per-round ``consensus.round`` spans, with
        quoracle_decide_ms / quoracle_round_ms histogram observations.
        """
        t0 = time.monotonic()
        with TRACER.span("consensus.decide",
                         agent_id=self.config.session_key,
                         n_models=len(self.config.model_pool)) as sp:
            outcome = self._decide(messages_per_model)
            sp.attrs.update(status=outcome.status,
                            rounds=outcome.rounds_used,
                            prefill_ms=round(outcome.prefill_ms, 1),
                            decode_ms=round(outcome.decode_ms, 1),
                            cached_tokens=outcome.cached_tokens,
                            spec_accepted_tokens=outcome.
                            spec_accepted_tokens)
        DECIDE_MS.observe((time.monotonic() - t0) * 1000)
        from quoracle_tpu.infra import costobs
        if costobs.enabled():
            # Economics-per-decide (ISSUE 17): chip-ms and emitted tokens,
            # so cost-per-answer trends are visible without joining audit
            # records.  Zero chip-ms decides (attribution off / CPU stub
            # engines that never ran a jitted step) are still observed —
            # the histogram's zero bucket is the "unmetered" population.
            COST_DECIDE_CHIP_MS.observe(outcome.chip_ms)
            COST_DECIDE_TOKENS.observe(float(outcome.completion_tokens))
        from quoracle_tpu.infra import treeobs
        if treeobs.enabled():
            # Session-graph rollup (ISSUE 20): exactly ONE node charge
            # per decide — the unit the subtree conservation contract
            # counts. Falls back to the thread binding so engines built
            # without an explicit tree (tests, bench) still attribute
            # when a caller bound one.
            treeobs.charge_decide(
                self.config.tree or treeobs.current(),
                outcome.chip_ms, outcome.completion_tokens,
                audit=outcome.audit)
        if outcome.audit is not None:
            # Scorecards + entropy/margin instruments + drift detection +
            # audit-record fan-out (consensus/quality.py). After the
            # decide histogram observation so the quality layer's own
            # cost never skews the latency it reports on.
            QUALITY.observe_decide(outcome.audit)
        return outcome

    def _decide(self, messages_per_model: dict[str, list[dict]]) -> ConsensusOutcome:
        t0 = time.monotonic()
        cfg = self.config
        outcome = ConsensusOutcome(status="ok")
        if cfg.quality:
            from quoracle_tpu.consensus.quality import next_decide_id
            outcome.decide_id = next_decide_id()
        pool = list(cfg.model_pool)
        # Working copy: refinement appends to these, not the caller's lists.
        histories = {m: list(msgs) for m, msgs in messages_per_model.items()}
        acc = EmbedAccumulator()
        # Quality scratch (ISSUE 5): failure attribution + correction
        # tracking across ALL rounds (outcome.failures only keeps the
        # last round's), and the final clustering for the audit record.
        # Pure observation — nothing here feeds back into control flow.
        failure_kinds: dict[str, dict[str, int]] = {}
        corrected: set[str] = set()
        audit_clusters: list = []
        winner_index: Optional[int] = None

        max_rounds = 1 + max(0, cfg.max_refinement_rounds)
        single_model = len(pool) == 1 and not cfg.force_reflection

        proposals: list[ActionProposal] = []
        round_num = 0
        while round_num < max_rounds:
            round_num += 1
            proposals, failures = self._query_round(histories, pool, round_num,
                                                    outcome)
            _note_failures(failures, failure_kinds, corrected)
            if not proposals:
                outcome.failures = failures
                outcome.status = ("all_failed" if all(
                    f.correction is None for f in failures) else "all_invalid")
                outcome.rounds_used = round_num
                outcome.latency_ms = (time.monotonic() - t0) * 1000
                self._attach_audit(outcome, pool, [], None, acc,
                                   failure_kinds, corrected)
                return outcome

            if single_model:
                break

            clusters = cluster_proposals(proposals, self.backend, acc)
            majority = find_majority_cluster(clusters, len(proposals),
                                             round_num, cfg.threshold)
            self._log("consensus_round", {
                "round": round_num, "clusters": len(clusters),
                "responses": len(proposals), "majority": majority is not None,
                "prefill_ms": round(outcome.prefill_ms, 1),
                "decode_ms": round(outcome.decode_ms, 1),
                "cached_tokens": outcome.cached_tokens})
            # force_reflection: a round-1 majority is not accepted as-is; the
            # pool reviews once before committing (reference consensus.ex
            # single-model/force_reflection refinement, :304-329).
            reflect_first = (cfg.force_reflection and round_num == 1
                             and max_rounds > 1)
            if (majority is not None and not reflect_first) \
                    or round_num >= max_rounds:
                audit_clusters = clusters
                winner_index = clusters.index(
                    select_winner_cluster(clusters, majority)[0])
                outcome.decision = pick_winner(clusters, len(proposals),
                                               round_num, majority,
                                               self.backend, acc)
                break

            # No accepted majority: append refinement prompt + own response
            # per model; failed models get their correction feedback so the
            # next round doesn't replay the identical prompt.
            for p in proposals:
                own_prompt = build_refinement_prompt(
                    clusters, p, round_num + 1, cfg.max_refinement_rounds)
                h = histories.setdefault(p.model_spec, [])
                h.append({"role": "assistant", "content": p.raw_text})
                h.append({"role": "user", "content": own_prompt})
                rh = outcome.refinement_history.setdefault(p.model_spec, [])
                rh.append((own_prompt, p.raw_text))
                del rh[:-REASONING_WINDOW_ROUNDS]
            for f in failures:
                if f.correction is None:
                    continue
                h = histories.setdefault(f.model_spec, [])
                if f.raw_text:
                    h.append({"role": "assistant", "content": f.raw_text})
                h.append({"role": "user", "content": f.correction})

        if outcome.decision is None:
            # Single-model fast path (reference consensus.ex:267-275 analog):
            # the lone valid proposal IS the decision, full confidence.
            clusters = cluster_proposals(proposals, self.backend, acc)
            majority = find_majority_cluster(clusters, len(proposals), 1,
                                             cfg.threshold)
            audit_clusters = clusters
            winner_index = clusters.index(
                select_winner_cluster(clusters, majority)[0])
            outcome.decision = pick_winner(clusters, len(proposals),
                                           round_num, majority,
                                           self.backend, acc)

        outcome.rounds_used = round_num
        outcome.embed_texts = acc.texts
        outcome.latency_ms = (time.monotonic() - t0) * 1000
        self._attach_audit(outcome, pool, audit_clusters, winner_index, acc,
                           failure_kinds, corrected)
        return outcome

    def _attach_audit(self, outcome: ConsensusOutcome, pool: list[str],
                      clusters: list, winner_index: Optional[int],
                      acc: EmbedAccumulator,
                      failure_kinds: dict[str, dict[str, int]],
                      corrected: set[str]) -> None:
        """Build the per-decide audit record (ISSUE 5) once the outcome is
        final. Gated by ``ConsensusConfig.quality``; reads only what the
        decide already computed."""
        cfg = self.config
        if not cfg.quality:
            return
        current = TRACER.current()
        task_id = cfg.task_id or (current.trace_id
                                  if current is not None else None)
        outcome.audit = build_audit_record(
            task_id=task_id, agent_id=cfg.session_key, pool=pool,
            outcome=outcome, clusters=clusters, winner_index=winner_index,
            sim_margins=acc.margins, failure_counts=failure_kinds,
            corrected=corrected, decide_id=outcome.decide_id)

    # ------------------------------------------------------------------

    def _query_round(self, histories: dict[str, list[dict]], pool: list[str],
                     round_num: int, outcome: ConsensusOutcome,
                     ) -> tuple[list[ActionProposal], list[ModelFailure]]:
        # One round = query + parse + validate; the span parents the
        # backend's per-member generate spans, and quoracle_round_ms is
        # where a round's p50/p95 are read from.
        t0 = time.monotonic()
        with TRACER.span("consensus.round", round=round_num,
                         agent_id=self.config.session_key):
            result = self._query_round_impl(histories, pool, round_num,
                                            outcome)
        ROUND_MS.observe((time.monotonic() - t0) * 1000)
        ROUNDS_TOTAL.inc()
        return result

    def _query_round_impl(self, histories: dict[str, list[dict]],
                          pool: list[str], round_num: int,
                          outcome: ConsensusOutcome,
                          ) -> tuple[list[ActionProposal], list[ModelFailure]]:
        cfg = self.config
        requests = [
            QueryRequest(
                model_spec=m,
                # Snapshot: refinement mutates histories after the request is
                # built; a live reference would retro-edit recorded calls.
                messages=list(histories.get(m, [])),
                temperature=temperature_for_round(
                    m, round_num, cfg.max_refinement_rounds),
                max_tokens=cfg.max_tokens,
                session_id=cfg.session_key,
                constrain_json=cfg.constrained_json,
                # Schema-aware grammar: a constrained row cannot name an
                # action outside the capability-gated set (VERDICT r2
                # item 7) — the validator keeps the params check.
                action_enum=(tuple(sorted(cfg.allowed_actions))
                             if cfg.constrained_json and cfg.allowed_actions
                             else None),
                priority=cfg.priority,
                tenant=cfg.tenant,
                deadline_ms=cfg.deadline_ms,
                # chip-economics keys (ISSUE 17): the ledger rolls this
                # round's device wall up by (task, decide)
                task_id=cfg.task_id,
                decide=outcome.decide_id,
                # session-graph lineage (ISSUE 20): rides rows + wire
                # headers so every peer books to the same tree node
                tree=cfg.tree,
            )
            for m in pool
        ]
        results = self.backend.query(requests)

        proposals: list[ActionProposal] = []
        failures: list[ModelFailure] = []
        for res in results:
            outcome.prompt_tokens += res.usage.prompt_tokens
            outcome.completion_tokens += res.usage.completion_tokens
            outcome.cost += res.usage.cost
            outcome.prefill_ms += getattr(res, "prefill_ms", 0.0)
            outcome.decode_ms += getattr(res, "decode_ms", 0.0)
            outcome.cached_tokens += getattr(res, "cached_tokens", 0)
            outcome.spec_rounds += getattr(res, "spec_rounds", 0)
            outcome.spec_accepted_tokens += getattr(
                res, "spec_accepted_tokens", 0)
            chip = getattr(res, "chip_ms", 0.0)
            if chip:
                outcome.chip_ms += chip
                outcome.member_chip_ms[res.model_spec] = \
                    outcome.member_chip_ms.get(res.model_spec, 0.0) + chip
            outcome.member_latency_ms[res.model_spec] = \
                outcome.member_latency_ms.get(res.model_spec, 0.0) \
                + getattr(res, "latency_ms", 0.0)
            if not res.ok:
                # Deadline-expired rows (serving/admission.py
                # DeadlineExceededError, surfaced as a "deadline_exceeded:"
                # error) are a MEMBER miss: no correction feedback (the
                # model never answered — nothing to correct), and the other
                # members' proposals carry the round. Only when EVERY
                # member misses does the round degrade to all_failed, the
                # same as any other total outage.
                deadline = res.error.startswith("deadline_exceeded")
                if deadline:
                    outcome.deadline_misses += 1
                failures.append(ModelFailure(
                    res.model_spec, res.error,
                    kind="deadline" if deadline else "transport"))
                continue
            parsed = parse_response(res.model_spec, res.text)
            if isinstance(parsed, ParseFailure):
                failures.append(ModelFailure(
                    res.model_spec, parsed.error,
                    correction=f"Your previous response was invalid: "
                               f"{parsed.error}. Respond with a single JSON "
                               f'object {{"action", "params", "reasoning", '
                               f'"wait"}}.',
                    raw_text=res.text,
                    kind="parse"))
                continue
            errors = validate_params(
                parsed.action, parsed.params,
                allowed_actions=cfg.allowed_actions,
                profile_optional=cfg.profile_optional_spawn)
            wait_error = validate_wait_param(parsed.action, parsed.wait)
            if wait_error:
                errors.append(wait_error)
            if errors:
                failures.append(ModelFailure(
                    res.model_spec,
                    f"invalid {parsed.action} params: " + "; ".join(errors),
                    correction="Your previous response failed validation: "
                               + "; ".join(errors)
                               + ". Correct the parameters and respond again.",
                    raw_text=res.text,
                    kind="schema"))
                continue
            if parsed.condense:
                outcome.condense_requests[parsed.model_spec] = parsed.condense
            if parsed.bug_report:
                outcome.bug_reports.append((parsed.model_spec, parsed.bug_report))
            proposals.append(parsed)

        outcome.proposals = proposals
        outcome.failures = failures
        return proposals, failures
