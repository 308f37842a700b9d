"""Decode-level continuous batching across agents (VERDICT r4 item 4).

THE batcher: every text row of every pool member (models/runtime.py)
joins and leaves its member's shared decode loop at CHUNK granularity —
the classic continuous-batching scheme (reference never executes
attention, SURVEY §2.8; the pattern is Orca/vLLM's, re-derived for XLA's
static shapes):

  * each engine gets ONE worker thread running a chunked loop: every
    iteration batches all live rows into a single ``engine.generate``
    call bounded at ``chunk`` tokens;
  * a row's cross-chunk state is exactly its KV SESSION plus the grammar
    state: the continuation prompt (prior prompt + tokens emitted so far)
    token-extends the session, so each chunk re-prefills ONE token (the
    last sampled, never-forwarded one) and decodes ``chunk`` more;
    ``GenResult.json_state`` → ``initial_json_state`` resumes constrained
    rows mid-JSON (states travel relative to their grammar block);
  * between chunks, finished rows retire (futures resolve) and queued
    rows are admitted into free slots — a new agent's row starts decoding
    ``chunk`` tokens after the CURRENT CHUNK, not after every other
    agent's full round;
  * a row's FIRST chunk goes through the engine's radix prefix cache
    (models/prefix_cache.py): a new session whose prompt starts with a
    cached page-aligned prefix (the fleet's shared system/task preamble)
    prefills only its suffix, and same-chunk admissions sharing an
    uncached prefix are wave-split so the batch prefills it once. A
    scheduler-owned session is dropped when its row retires, but the
    prefix pages it prefilled stay adoptable in the cache until LRU
    eviction reclaims them.

Static-shape discipline: on the bucketed paths batch sizes ride the
engine's BATCH_BUCKETS and ``chunk`` is a fixed decode bound, so steady
state compiles exactly two programs (prefill bucket × decode chunk) per
batch bucket. With the UNIFIED ragged kernel engaged (ISSUE 8 — the TPU
default), ticks are admitted truly RAGGED: the engine lays every row's
suffix out token-major, device work and compile keys scale with the
tick's total real tokens (one token-budget bucket), and the batch-bucket
× prompt-bucket program matrix collapses to one (chunk, decode) program
pair per token budget — CompileRegistry asserts the collapse in tier-1,
and the per-tick real-vs-padded token counters
(quoracle_sched_{real,padded}_tokens_total) quantify the reclaimed
padding. Sampled rows draw fresh RNG per chunk — the stream differs from
a one-shot call (same distribution); temperature-0 rows are bit-identical
to one-shot (tests/test_scheduler.py equality).

Admission ORDER is a policy (ISSUE 4): the batcher queues through a
``serving/qos.AdmissionPolicy`` — FIFO by default, weighted-fair DRR with
an aging floor under QoS — and an optional
``serving/admission.AdmissionController`` sheds at submit (structured
reject with ``retry_after_ms``) while deadline-expired rows are failed at
admit instead of decoded. QoS reorders *scheduling* only: what a row
computes once admitted is untouched, so temp-0 equality holds with QoS on
or off (tests/test_qos.py).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, Optional, Sequence

from quoracle_tpu.analysis.lockdep import named_lock
from quoracle_tpu.infra import costobs, fleetobs, introspect, treeobs
from quoracle_tpu.infra.flightrec import FLIGHT
from quoracle_tpu.infra.telemetry import (
    QOS_ADMIT_WAIT_MS, SCHED_ADMIT_WAIT_MS, SCHED_NUCLEUS_ROWS_TOTAL,
    SCHED_QUEUE_DEPTH, SCHED_ROWS_TOTAL, SCHED_SLOTS_BUSY, TRACER,
    tick_close, tick_note, tick_op, tick_open, tick_phase,
)
from quoracle_tpu.models.generate import GenResult
from quoracle_tpu.serving.admission import (
    AdmissionError, DeadlineExceededError,
)
from quoracle_tpu.serving.qos import (
    AdmissionPolicy, FifoPolicy, class_name, coerce_priority,
)

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class _Row:
    """One agent row riding the shared decode loop."""

    prompt: list
    temperature: float
    top_p: float
    max_new: int
    session_id: str
    constrain: bool
    action_enum: Optional[Sequence[str]]
    future: Future
    emitted: list = dataclasses.field(default_factory=list)
    json_state: Optional[int] = None
    n_cached_first: Optional[int] = None
    owns_session: bool = False          # scheduler-created → drop at end
    t_submit: float = 0.0
    # QoS (ISSUE 4): class + tenant attribution and the absolute
    # (monotonic) deadline after which the row is failed, not decoded.
    priority: int = 1                   # Priority.AGENT
    tenant: str = "default"
    deadline_s: Optional[float] = None
    # Speculative serving attribution (ISSUE 6): draft/verify rounds this
    # row rode and how many draft tokens the target accepted — surfaced
    # on the retiring GenResult for per-decide speedup attribution.
    spec_rounds: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    # Fleet observability (ISSUE 15): the submitter's trace context —
    # queue-wait and decode spans emitted from the worker thread parent
    # onto it, so a row's lifecycle lands in the SAME trace that placed
    # it (possibly opened on another host). t_admit anchors the decode
    # span so queue wait is never double-counted in the decomposition.
    trace: Optional[Any] = None
    t_admit: float = 0.0
    # The row record (ISSUE 24): admission and first token in monotonic
    # ns — the first token is the prefill fence of the first tick the
    # row rode (TickRecord.fence_ns) — and the ticks it rode. With the
    # closed WaitClock they go into introspect's row ring at retire.
    t_admit_ns: int = 0
    t_first_token_ns: int = 0
    ticks: int = 0
    # Chip economics (ISSUE 17): task/decide attribution keys carried
    # down from the consensus layer, and this row's accumulated share
    # of measured device wall across every chunk it rode.
    task_id: Optional[str] = None
    decide: Optional[str] = None
    chip_ms: float = 0.0
    # Wait-state decomposition (ISSUE 18): the row's integer-ns wait
    # ledger, opened at submit while the introspect plane is on (None
    # when off — the gated fast path allocates nothing). Closed at
    # retire; the named waits + exact remainder ride the sched.decode
    # span as ``waits_ns``.
    waits: Optional[Any] = None
    # Session-graph observability (ISSUE 20): the submitting agent's
    # tree context dict (treeobs.TreeContext.to_dict), carried so the
    # retire site can book this row's wait decomposition to the right
    # tree node — on whichever peer the row lands after a handoff.
    tree: Optional[dict] = None


class ContinuousBatcher:
    """Per-engine chunked decode loop with admission between chunks.

    ``submit()`` returns a Future[GenResult]; rows from any number of
    callers (agents) batch into the same device steps. Sessionless
    submissions get a scheduler-owned session (dropped on completion) —
    the session IS the row's cross-chunk KV state.
    """

    def __init__(self, engine, chunk: int = 32, max_slots: int = 8,
                 admit_wait_s: float = 0.002,
                 policy: Optional[AdmissionPolicy] = None,
                 admission=None, slo=None, speculator=None):
        """``policy`` orders admission (default: the original FIFO;
        serving/qos.WeightedFairPolicy for DRR + aging). ``admission``
        is an optional serving/admission.AdmissionController consulted
        on every submit — sheds fail the row's future with a structured
        AdmissionError instead of growing the queue. ``slo`` is an
        optional serving/slo.SLOTracker fed per-class retire latency.
        ``speculator`` (models/speculative.BatchedSpeculator, ISSUE 6)
        turns eligible rows' decode ticks into batched draft/verify
        rounds; ineligible rows decode vanilla in the same tick and
        temp-0 outputs stay bit-identical either way."""
        self.engine = engine
        self.chunk = chunk
        self.max_slots = max_slots
        self.admit_wait_s = admit_wait_s
        self._policy = policy if policy is not None else FifoPolicy()
        self.admission = admission
        self.slo = slo
        self.speculator = speculator
        self._live: list[_Row] = []
        self._seq = 0
        self._lock = named_lock("batcher")
        self._wake = threading.Event()
        self._stop = False
        # health telemetry (ISSUE 3): monotonic progress/outcome counters.
        # ``steps`` is the stall watchdog's progress signal — frozen steps
        # with live rows means the decode loop is wedged.
        self.steps = 0
        self.retired = 0
        self.failed = 0
        self._model = engine.cfg.name
        self._thread = threading.Thread(
            target=self._loop, name=f"qtpu-batcher-{engine.cfg.name}",
            daemon=True)
        self._thread.start()

    def submit(self, prompt: Sequence[int], *, temperature: float = 1.0,
               top_p: float = 1.0, max_new_tokens: int = 256,
               session_id: Optional[str] = None,
               constrain_json: bool = False,
               action_enum: Optional[Sequence[str]] = None,
               priority=None, tenant: str = "default",
               deadline_s: Optional[float] = None,
               initial_json_state: Optional[int] = None,
               task_id: Optional[str] = None,
               decide: Optional[str] = None,
               tree: Optional[dict] = None) -> Future:
        """``initial_json_state`` resumes a constrained row MID-GRAMMAR:
        the prompt's tail already contains generated JSON (a prefill-tier
        replica's first token after a KV handoff, serving/cluster.py) and
        decoding must continue from that grammar state, not from the
        block start — exactly the state the chunked loop already threads
        between its own chunks via GenResult.json_state."""
        t_submit_ns = time.monotonic_ns()
        row = _Row(prompt=list(prompt), temperature=temperature,
                   top_p=top_p, max_new=max(1, max_new_tokens),
                   session_id=session_id or self._own_session_id(),
                   constrain=constrain_json, action_enum=action_enum,
                   future=Future(), t_submit=t_submit_ns / 1e9,
                   priority=int(coerce_priority(priority)),
                   tenant=tenant, deadline_s=deadline_s,
                   json_state=initial_json_state,
                   task_id=task_id, decide=decide,
                   tree=(tree if treeobs.enabled() else None),
                   # trace capture only while something listens — the
                   # un-traced fast path stays allocation-identical
                   trace=(fleetobs.TraceContext.current()
                          if TRACER.active() else None))
        row.owns_session = session_id is None
        if introspect.enabled():
            row.waits = introspect.WaitClock(t_submit_ns)
        # Per-row admission check: an over-window prompt must fail ONLY
        # its own future — inside a shared chunk the engine's
        # ContextOverflowError would poison every live row's in-flight
        # work (the engine applies the same bound at generate()).
        if len(row.prompt) >= self.engine.max_seq:
            from quoracle_tpu.models.generate import ContextOverflowError
            row.future.set_exception(ContextOverflowError(
                f"prompt of {len(row.prompt)} tokens >= max_seq "
                f"{self.engine.max_seq} for model {self.engine.cfg.name}"))
            return row.future
        # QoS admission (ISSUE 4): shed BEFORE the row can queue — a
        # structured reject on the row's OWN future (same idiom as the
        # overflow check above), never silent queue growth. The
        # controller may clamp the class to the tenant's floor.
        if self.admission is not None:
            t_adm = (time.monotonic_ns()
                     if row.waits is not None else 0)
            try:
                row.priority = int(self.admission.admit(
                    tenant=row.tenant, priority=row.priority,
                    deadline_s=row.deadline_s,
                    queue_depth=self._policy.qsize()))
                if row.waits is not None:
                    row.waits.note("admission",
                                   time.monotonic_ns() - t_adm)
            except AdmissionError as e:
                row.future.set_exception(e)
                self.failed += 1
                SCHED_ROWS_TOTAL.inc(model=self._model, status="failed")
                # error-budget score (ISSUE 17): a shed burns the
                # tenant class's budget — observed signal only
                costobs.BUDGET.record(row.tenant,
                                      class_name(row.priority),
                                      ok=False, t=time.monotonic())
                return row.future
        # Reject-after-closed UNDER THE LOCK (ISSUE 3 satellite): close()
        # flips _stop under this same lock, so a row can only enter the
        # queue strictly BEFORE the flip — and close()'s drain (which runs
        # after) is then guaranteed to see it. The old unlocked
        # check-put-recheck dance left a window where a concurrently
        # submitted row landed after the drain and stranded its future.
        with self._lock:
            if self._stop:
                raise RuntimeError("ContinuousBatcher is closed")
            self._policy.put(row)
            depth = self._policy.qsize()
        SCHED_QUEUE_DEPTH.set(depth, model=self._model)
        self._wake.set()
        # Tiered-KV prefetch (ISSUE 7): a row resuming a HIBERNATED
        # session warms it now, overlapping the page-in with its queue
        # wait. Best-effort and non-blocking (try-acquire inside): a
        # busy engine skips it and the sessioned generate restores
        # synchronously at lookup instead.
        if session_id is not None:
            prefetch = getattr(self.engine, "prefetch_session", None)
            if prefetch is not None:
                try:
                    prefetch(session_id)
                except Exception:   # noqa: BLE001 — warm-up only
                    pass
        return row.future

    def close(self) -> None:
        with self._lock:
            self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            # mid-chunk device call still running; give it one longer
            # grace period — touching _live while the worker owns it
            # would race its set_result calls (InvalidStateError)
            self._thread.join(timeout=50)
        # never strand a waiter: still-queued rows fail loudly instead of
        # leaving callers blocked on futures forever. LIVE rows are failed
        # by the worker's own exit cleanup (it owns _live); only a worker
        # confirmed dead can't do that, so take over just in that case.
        err = RuntimeError("ContinuousBatcher closed")
        leftovers = []
        if not self._thread.is_alive():
            leftovers = list(self._live)
            self._live = []
        leftovers.extend(self._policy.drain())
        for row in leftovers:
            if not row.future.done():
                row.future.set_exception(err)
                self.failed += 1
                SCHED_ROWS_TOTAL.inc(model=self._model, status="failed")
            self._drop_row_sessions(row)
        # Zero the live gauges (ISSUE 4 satellite): the queue is drained
        # and no slot can ever be busy again — leaving the last-set
        # values would show phantom depth/occupancy on /metrics scrapes
        # after shutdown.
        SCHED_QUEUE_DEPTH.set(0, model=self._model)
        SCHED_SLOTS_BUSY.set(0, model=self._model)

    def _own_session_id(self) -> str:
        with self._lock:
            self._seq += 1
            return f"__cb{self._seq}"

    # -- health telemetry (ISSUE 3) ------------------------------------

    def stats(self) -> dict:
        """Point-in-time health snapshot for /api/resources (racy reads
        of worker-owned state — a snapshot, not an invariant)."""
        padding = getattr(self.engine, "padding_stats", None)
        return {
            "queued": self._policy.qsize(),
            "live": len(self._live),
            "max_slots": self.max_slots,
            "chunk": self.chunk,
            "steps": self.steps,
            "retired": self.retired,
            "failed": self.failed,
            "closed": self._stop,
            "qos": self._policy.snapshot(),
            # padding-waste accounting (ISSUE 8): real vs padded chunk
            # tokens per tick — what ragged admission reclaims
            "padding": padding() if padding is not None else None,
            "speculative": (self.speculator.stats()
                            if self.speculator is not None else None),
        }

    def progress(self) -> tuple[bool, int]:
        """Stall-watchdog source (runtime.StallWatchdog): (work pending?,
        monotonic progress counter). Active with a frozen counter past
        the deadline = the decode loop is wedged."""
        active = (not self._stop
                  and (bool(self._live) or self._policy.qsize() > 0))
        return active, self.steps

    # ------------------------------------------------------------------

    def _admit(self) -> int:
        admitted = 0
        while len(self._live) < self.max_slots:
            row = self._policy.pop()
            if row is None:
                break
            now_ns = time.monotonic_ns()
            now = now_ns / 1e9
            # Deadline-aware drop (ISSUE 4): a row whose deadline passed
            # while queued is failed AT ADMIT — decoding tokens nobody
            # will wait for would steal the slot from a live request.
            if row.deadline_s is not None and now >= row.deadline_s:
                if not row.future.done():
                    row.future.set_exception(DeadlineExceededError(
                        f"deadline passed after "
                        f"{(now - row.t_submit) * 1000:.0f}ms in queue",
                        tenant=row.tenant, priority=row.priority))
                self._drop_row_sessions(row)
                self.failed += 1
                SCHED_ROWS_TOTAL.inc(model=self._model, status="failed")
                from quoracle_tpu.infra.telemetry import QOS_SHED_TOTAL
                QOS_SHED_TOTAL.inc(cls=class_name(row.priority),
                                   tenant=row.tenant, reason="deadline")
                FLIGHT.record("qos_deadline_drop", model=self._model,
                              cls=class_name(row.priority),
                              tenant=row.tenant,
                              waited_ms=round(
                                  (now - row.t_submit) * 1000, 1))
                costobs.BUDGET.record(row.tenant,
                                      class_name(row.priority),
                                      ok=False, t=now)
                continue
            wait_ms = (now - row.t_submit) * 1000
            SCHED_ADMIT_WAIT_MS.observe(wait_ms, model=self._model)
            QOS_ADMIT_WAIT_MS.observe(wait_ms,
                                      cls=class_name(row.priority))
            row.t_admit, row.t_admit_ns = now, now_ns
            if row.waits is not None:
                # batch-queue wait = submit→admit minus the admission
                # call's own wall (already booked as "admission")
                row.waits.note(
                    "queue",
                    int(wait_ms * 1e6)
                    - row.waits.waits.get("admission", 0))
            if TRACER.active():
                # retroactive queue-wait span, parented on the
                # submitter's (possibly remote) trace context
                TRACER.emit("sched.queue_wait", wait_ms,
                            parent=row.trace,
                            ts=time.time() - wait_ms / 1000.0,
                            session=row.session_id, model=self._model,
                            cls=class_name(row.priority))
            self._live.append(row)
            admitted += 1
        if admitted:
            FLIGHT.record("sched_admit", model=self._model, rows=admitted,
                          live=len(self._live))
        SCHED_QUEUE_DEPTH.set(self._policy.qsize(), model=self._model)
        SCHED_SLOTS_BUSY.set(len(self._live), model=self._model)
        return admitted

    def _loop(self) -> None:
        sampled = None                    # (step, closed record) to emit
        while not self._stop:
            # One tick record per iteration (ISSUE 24): the phases the
            # worker passes through from here to tick_close() tile the
            # iteration, each a TraceAnnotation on this thread's line.
            tick_open(self._model)
            if sampled is not None:
                with tick_op("observe"):
                    self._emit_tick_span(*sampled)
                sampled = None
            admitted = self._admit()
            n_rows = len(self._live)
            # the rows that make the sampler sort the vocabulary: with
            # none, the tick's programs take the branch without the sort
            nucleus = sum(r.temperature > 0 and r.top_p < 1
                          for r in self._live)
            tick_note(rows=n_rows, admitted=admitted, nucleus_rows=nucleus)
            if not self._live:
                tick_phase("idle")
                self._wake.wait(timeout=0.2)
                self._wake.clear()
                tick_close()
                continue
            SCHED_NUCLEUS_ROWS_TOTAL.inc(nucleus, model=self._model)
            tick_phase("prepare")
            try:
                self._live = self._step(self._live)
            except Exception:             # noqa: BLE001 — isolate, don't
                self._live = self._isolate_failure(self._live)  # nuke all
            step = self.steps
            self.steps += 1               # watchdog progress signal
            with tick_op("observe"):
                introspect.beat(f"sched.tick:{self._model}")
                self._chaos_tick()
            rec = tick_close()
            # Sampled decode-tick span (ISSUE 15 satellite): 1-in-N
            # ticks (QUORACLE_TRACE_DECODE_SAMPLE, keyed on the
            # monotonic step counter — deterministic, no RNG) so
            # serving decode traffic cannot starve consensus traces
            # out of the bounded span rings. The span IS the closed tick
            # record, so it is emitted at the head of the NEXT
            # iteration, where its cost has a record to be booked on.
            if TRACER.active() and fleetobs.sample_tick(step):
                sampled = (step, rec)
        if sampled is not None:
            self._emit_tick_span(*sampled)
        # worker exit (close()): the worker owns _live, so it fails any
        # remaining rows itself — close() only takes over when this
        # thread is confirmed dead
        err = RuntimeError("ContinuousBatcher closed")
        for row in self._live:
            if not row.future.done():
                row.future.set_exception(err)
                self.failed += 1
                SCHED_ROWS_TOTAL.inc(model=self._model, status="failed")
            self._drop_row_sessions(row)
        self._live = []
        # gauge reset on the worker-exit path too (ISSUE 4 satellite):
        # whichever of close()/worker runs last, the scrape reads zero
        SCHED_SLOTS_BUSY.set(0, model=self._model)

    def _emit_tick_span(self, step: int, rec) -> None:
        """``sched.decode_tick``: the closed tick record, its phases, named
        operations and arguments as attributes."""
        attrs = rec.as_attrs()
        TRACER.emit("sched.decode_tick", attrs["wall_ns"] / 1e6,
                    ts=time.time() - attrs["wall_ns"] / 1e9,
                    step=step, **attrs)

    def _chaos_tick(self) -> None:
        """Chaos seam (ISSUE 11): per-tick fault hook in the decode
        loop. ``demote`` forces the eviction ladder to hibernate every
        demotable session MID-TRAFFIC (the still-live rows restore by
        page-in next tick — PR 7's invariants under hostile
        interleaving); ``delay`` stretches the tick. Worker-thread
        exceptions here must never kill the loop — the faults this seam
        injects are tier churn, not thread death."""
        from quoracle_tpu.chaos.faults import (
            CHAOS, chaos_demote_churn,
        )
        if not CHAOS.armed():
            return
        try:
            d = CHAOS.fire("sched.tick", model=self._model)
            if d is not None and d.kind == "demote":
                chaos_demote_churn(self.engine)
        except Exception:                 # noqa: BLE001 — isolate
            logger.exception("chaos tick hook failed")

    def _isolate_failure(self, rows: list) -> list:
        """A shared chunk raised. One poisoned row must not discard every
        other agent's partial work: rerun each row as its own single-row
        chunk — rows that fail alone get THEIR error, the rest survive
        with their emitted state intact. Engine-wide failures (device
        dead) fail every row with its own raise, same end state as the
        old all-rows-fail path."""
        survivors: list = []
        for row in rows:
            if row.future.done():
                # _step resolved this row (and dropped its session) before
                # the exception hit a later row — nothing left to rerun
                continue
            try:
                survivors.extend(self._step([row]))
            except Exception as e:        # noqa: BLE001 — per-row capture
                if not row.future.done():
                    row.future.set_exception(e)
                self._drop_row_sessions(row)
                self.failed += 1
                SCHED_ROWS_TOTAL.inc(model=self._model, status="failed")
                FLIGHT.record("sched_row_failed", model=self._model,
                              session=row.session_id, error=repr(e))
        return survivors

    def _drop_row_sessions(self, row) -> None:
        """Owned-session cleanup for a terminal row — the engine session
        AND (under speculative serving) the draft engine's shadow session
        the speculator keyed by the same id."""
        if row.owns_session:
            self.engine.drop_session(row.session_id)
            if self.speculator is not None:
                self.speculator.drop_session(row.session_id)

    def _finish_row(self, row, finish_reason: str,
                    json_state: int = -1) -> None:
        """Resolve a finished row's future from its accumulated state and
        account the retirement (shared by the vanilla and speculative
        paths — one retire semantics, zero drift)."""
        # Wait-state decomposition (ISSUE 18): close the row's wait
        # ledger at retire — the named waits + exact remainder sum to
        # the row's observed wall by construction. Closed FIRST: the
        # result's device phase times come from it (ISSUE 24).
        t_done_ns = time.monotonic_ns()
        t_done = t_done_ns / 1e9
        closed = row.waits.close(t_done_ns) if row.waits is not None else None
        waits = closed["waits_ns"] if closed is not None else {}
        if not row.future.done():           # close() may have failed it
            row.future.set_result(GenResult(
                token_ids=list(row.emitted),
                text=self.engine.tokenizer.decode(row.emitted),
                n_prompt_tokens=len(row.prompt),
                n_gen_tokens=len(row.emitted),
                latency_s=t_done - row.t_submit,
                finish_reason=finish_reason,
                n_cached_tokens=row.n_cached_first or 0,
                json_state=json_state,
                spec_rounds=row.spec_rounds,
                spec_drafted_tokens=row.spec_drafted,
                spec_accepted_tokens=row.spec_accepted,
                chip_ms=round(row.chip_ms, 6),
                prefill_ms=waits.get("device_prefill", 0) / 1e6,
                decode_ms=waits.get("device_decode", 0) / 1e6,
            ))
        self._drop_row_sessions(row)
        self.retired += 1
        # error-budget score (ISSUE 17): a retire past its deadline is
        # an SLO miss; everything else is budget-ok
        costobs.BUDGET.record(
            row.tenant, class_name(row.priority),
            ok=not (row.deadline_s is not None and t_done > row.deadline_s),
            t=t_done)
        SCHED_ROWS_TOTAL.inc(model=self._model, status="retired")
        # The closed ledger rides the decode span, so /api/timeline
        # aggregates it per trace, and goes with the row's stamps and
        # counts into introspect's row ring (/api/profile ``rows``).
        if closed is not None:
            introspect.record_row_waits(self._model, closed, row={
                "session": row.session_id,
                "t_submit_ns": row.waits.t0_ns,
                "t_admit_ns": row.t_admit_ns,
                "t_first_token_ns": row.t_first_token_ns,
                "t_done_ns": t_done_ns,
                "ticks": row.ticks,
                "prompt_tokens": len(row.prompt),
                "cached_tokens": row.n_cached_first or 0,
                "emitted_tokens": len(row.emitted),
                "finish": finish_reason})
            introspect.beat(f"sched.retired:{self._model}")
            # Session-graph rollup (ISSUE 20): the same exact-sum wait
            # decomposition, booked to the tree node this row belongs
            # to — on THIS peer's registry; the front door federates.
            if row.tree is not None and treeobs.enabled():
                treeobs.charge_row_waits(row.tree, closed)
        if TRACER.active():
            # one decode span per row lifetime, anchored at admission
            # so queue wait is never double-counted in the TTFT
            # decomposition (fleetobs.assemble_timeline)
            dur_ms = (time.monotonic()
                      - (row.t_admit or row.t_submit)) * 1000
            extra = ({"wall_ns": closed["wall_ns"],
                      "waits_ns": closed["waits_ns"]}
                     if closed is not None else {})
            TRACER.emit("sched.decode", dur_ms, parent=row.trace,
                        ts=time.time() - dur_ms / 1000.0,
                        session=row.session_id, model=self._model,
                        tokens=len(row.emitted), finish=finish_reason,
                        **extra)
        if self.slo is not None:
            # per-class tail tracking (serving/slo.py): feeds the
            # INTERACTIVE-burn → BATCH-demotion control loop
            self.slo.observe(
                row.priority,
                (time.monotonic() - row.t_submit) * 1000)
        FLIGHT.record("sched_retire", model=self._model,
                      session=row.session_id,
                      n_tokens=len(row.emitted),
                      finish=finish_reason)

    def _step(self, rows: list) -> list:
        """One decode tick. Under speculative serving (ISSUE 6) the tick
        splits: eligible rows ride batched draft/verify rounds
        (models/speculative.BatchedSpeculator) while ineligible rows —
        nucleus-sampled, window-edge, or disengaged-member rows — decode
        vanilla in the same tick. Both kinds retire through _finish_row;
        temp-0 outputs are bit-identical either way."""
        spec = self.speculator
        spec_rows: list = []
        spec_ids: set = set()
        finishes: dict = {}
        if spec is not None:
            spec.tick_vanilla()         # re-probe countdown while off
            for r in rows:
                reason = spec.ineligible_reason(
                    len(r.prompt) + len(r.emitted), r.temperature,
                    r.top_p)
                if reason is None:
                    spec_rows.append(r)
                    spec_ids.add(id(r))
                else:
                    spec.note_fallback(reason)
            if spec_rows:
                t_sp = (time.monotonic_ns()
                        if any(r.waits is not None for r in spec_rows)
                        else None)
                if t_sp is not None:
                    introspect.drain_inner_waits()
                finishes, leftover = self._spec_step(spec_rows)
                if t_sp is not None:
                    self._book_step_waits(
                        spec_rows, time.monotonic_ns() - t_sp)
                if leftover:            # speculator failed mid-tick:
                    lids = set(map(id, leftover))   # decode those vanilla
                    spec_rows = [r for r in spec_rows
                                 if id(r) not in lids]
                    spec_ids -= lids
        plain = [r for r in rows if id(r) not in spec_ids]
        still = self._plain_step(plain) if plain else []
        for row in spec_rows:
            row.ticks += 1
            if not row.t_first_token_ns:    # no fence of its own yet
                row.t_first_token_ns = time.monotonic_ns()
            fin = finishes.get(id(row))
            finished = (fin == "stop"
                        or len(row.emitted) >= row.max_new
                        or (len(row.prompt) + len(row.emitted)
                            >= self.engine.max_seq - 1))
            if finished:
                self._finish_row(
                    row, "stop" if fin == "stop" else "length",
                    json_state=(row.json_state
                                if row.json_state is not None else -1))
            else:
                still.append(row)
        return still

    def _spec_step(self, rows: list) -> tuple[dict, list]:
        """Speculative sub-tick: repeated draft/verify rounds until every
        row has committed ~chunk tokens, finished, or become ineligible.
        Returns ({id(row): "stop" | None}, leftover) where ``leftover``
        rows hit a speculator error and must decode vanilla this tick —
        their committed progress (rows + sessions mutate in place) is
        already consistent, so the fallback is seamless."""
        spec = self.speculator
        finishes: dict = {}
        active = list(rows)
        baseline = {id(r): len(r.emitted) for r in rows}
        try:
            while active:
                for rid, fin in spec.run_round(active).items():
                    if fin is not None:
                        finishes[rid] = fin
                active = [
                    r for r in active
                    if finishes.get(id(r)) is None
                    and len(r.emitted) < r.max_new
                    and len(r.emitted) - baseline[id(r)] < self.chunk
                    and spec.ineligible_reason(
                        len(r.prompt) + len(r.emitted), r.temperature,
                        r.top_p) is None]
        except Exception as e:    # noqa: BLE001 — isolate, don't kill rows
            spec.note_fallback("error", len(active))
            FLIGHT.record("spec_error", model=self._model, error=repr(e))
            leftover = [r for r in active if finishes.get(id(r)) is None]
            return finishes, leftover
        return finishes, []

    def _row_key(self, row) -> tuple:
        """Chip-economics attribution key (ISSUE 17): the scheduler's
        integer priority renders as its QoS class name so ledger
        rollups share the budget plane's vocabulary."""
        return (str(row.tenant or "-"), class_name(row.priority),
                str(row.task_id or "-"), str(row.decide or "-"))

    def _book_step_waits(self, rows: list, step_ns: int,
                         device_ns: Optional[tuple] = None) -> None:
        """Partition one engine call's wall across its rows' wait
        ledgers (ISSUE 18). Every row in the batch waits the WHOLE call
        concurrently, so each is booked the full wall — the KV-restore
        and contended-lock walls this thread accumulated inside the
        call, and the rest split by the tick record (ISSUE 24):
        ``device_ns`` is its (wait_prefill, wait_decode) time over the
        call, what remains is ``host`` (prepare, pack, dispatch,
        commit). The speculative sub-tick has no such split yet and
        books the rest as one ``dispatch`` lump."""
        restore_ns, lock_ns = introspect.drain_inner_waits()
        rest = max(0, step_ns - restore_ns - lock_ns)
        if device_ns is None:
            split = {"dispatch": rest}
        else:
            prefill_ns, decode_ns = device_ns
            split = {"host": max(0, rest - prefill_ns - decode_ns),
                     "device_prefill": prefill_ns,
                     "device_decode": decode_ns}
        for r in rows:
            if r.waits is None:
                continue
            for state, ns in split.items():
                r.waits.note(state, ns)
            r.waits.note("kv_restore", restore_ns)
            r.waits.note("lock", lock_ns)

    def _plain_step(self, rows: list) -> list:
        with tick_op("splice"):
            prompts = [r.prompt + r.emitted for r in rows]
            budgets = [min(self.chunk, r.max_new - len(r.emitted))
                       for r in rows]
            sampling = dict(
                temperature=[r.temperature for r in rows],
                top_p=[r.top_p for r in rows],
                max_new_tokens=budgets,
                session_ids=[r.session_id for r in rows],
                constrain_json=[r.constrain for r in rows],
                action_enums=[r.action_enum for r in rows],
                initial_json_state=[r.json_state for r in rows])
        tick = tick_phase("prepare")      # always inside _loop's tick
        before = tick.snapshot()
        with tick_op("observe"):
            introspect.drain_inner_waits()
            # declare this chunk's attribution keys on the worker thread —
            # the engine's charge site consumes them (one call, one set)
            costobs.set_row_keys([self._row_key(r) for r in rows])
        results = self.engine.generate(prompts, **sampling)
        # the engine call's wall, split by the tick record: two
        # snapshots differ by exactly the time between them
        after = tick.snapshot()
        with tick_op("observe"):
            self._book_step_waits(
                rows, sum(after.values()) - sum(before.values()),
                (after["wait_prefill"] - before["wait_prefill"],
                 after["wait_decode"] - before["wait_decode"]))
        tick_phase("retire")
        with tick_op("retire_rows"):
            return self._retire_rows(tick, rows, results, budgets)

    def _retire_rows(self, tick, rows, results, budgets) -> list:
        """The engine call's results onto their rows; rows that ended
        retire through _finish_row, the rest ride the next tick."""
        # a path with no prefill fence of its own: the call's end
        fence_ns = tick.fence_ns or time.monotonic_ns()
        still = []
        for row, res, budget in zip(rows, results, budgets):
            row.ticks += 1
            if not row.t_first_token_ns:
                row.t_first_token_ns = fence_ns
            if row.n_cached_first is None:
                row.n_cached_first = res.n_cached_tokens
            row.chip_ms += res.chip_ms
            row.emitted.extend(res.token_ids)
            row.json_state = (res.json_state
                              if res.json_state >= 0 else row.json_state)
            finished = (res.finish_reason == "stop"
                        or len(res.token_ids) < budget
                        or len(row.emitted) >= row.max_new
                        # context exhausted: the next continuation prompt
                        # (prompt+emitted) would reach the window and the
                        # whole shared batch would ContextOverflow — retire
                        # at the window edge instead (the engine clamps
                        # row_limit the same way, so when remaining space
                        # is an exact chunk multiple only this check fires)
                        or (len(row.prompt) + len(row.emitted)
                            >= self.engine.max_seq - 1))
            if finished:
                self._finish_row(row, res.finish_reason, res.json_state)
            else:
                still.append(row)
        return still
