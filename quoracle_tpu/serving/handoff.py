"""Cross-replica KV handoff (ISSUE 10 tentpole, part b).

A disaggregated cluster prefills a prompt on a PREFILL replica and
decodes it on a DECODE replica. The bytes that cross the boundary are
the session's KV pages, and the transfer is deliberately NOT a new
mechanism: it is PR 7's hibernate/restore round trip split across two
engines — "hibernate on the prefill replica, restore on the decode
replica":

  1. export — ``TierManager.export_session`` hibernates the session out
     of the prefill engine's pool (the eviction ladder's demote: one
     ``device_get``, refcounted release, the radix tree and any adopters
     keep their resident copies) and hands the host-side copy here
     instead of parking it in the prefill tier's store;
  2. envelope — the copy travels as a :class:`HandoffEnvelope` stamped
     with the source engine's KV SIGNATURE (geometry + page size +
     dtype, ``GenerateEngine.kv_signature``) and the grammar state after
     the prefill-emitted token;
  3. adopt — ``TierManager.adopt_session`` places the copy in the
     decode engine's host tier, and the ordinary restore machinery
     (prefetch / the engine's session lookup) pages it in. The decode
     engine neither knows nor cares that the pages were prefilled on
     another replica — which is exactly why the restore bit-equality
     invariant (ARCHITECTURE §9, tier-1 tested) carries over to the
     cluster unchanged.

Signatures must match EXACTLY or the handoff is rejected
(:class:`HandoffError`) before any bytes move — a version-skewed
replica pair (different checkpoint geometry, page size, or cache dtype)
must degrade to a cold re-prefill on the decode side, never to
plausible-looking garbage KV.

The ledger keeps every in-flight envelope until its row retires, so a
decode replica dying mid-row can be RE-PLACED: the same envelope adopts
into a surviving decode replica and decode reruns from the handoff
point (serving/cluster.py drives this; ``kv_handoff_replace``).

Locking: the ledger lock ("handoff", rank 8) is a pure bookkeeping
lock — all device work happens inside the engines' own paged/store
locks (ranks 25/30), acquired strictly after it or not at all.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

from quoracle_tpu.analysis.lockdep import named_lock
from quoracle_tpu.infra import fleetobs
from quoracle_tpu.infra.flightrec import FLIGHT
from quoracle_tpu.infra.telemetry import (
    CLUSTER_HANDOFF_MS, CLUSTER_HANDOFFS_TOTAL, TRACER,
)


class HandoffError(RuntimeError):
    """A KV handoff could not be performed — signature mismatch or
    export failure. The caller degrades to a cold re-prefill on the
    decode side; this error never propagates to the user."""

    def __init__(self, message: str, reason: str = "rejected"):
        super().__init__(message)
        self.reason = reason


@dataclasses.dataclass
class HandoffEnvelope:
    """One session's KV in transit between replicas. ``entry`` is the
    kvtier host-side copy (``_HostSession``: tokens + start_pos + numpy
    K/V); ``signature`` binds it to the exact engine geometry that
    produced it; ``json_state`` is the grammar state after the last
    prefill-emitted token (-1 / None = unconstrained)."""

    session_id: str
    model_spec: str
    signature: str
    entry: Any
    json_state: Optional[int] = None
    src_replica: str = ""
    ts: float = 0.0
    # Trace context (ISSUE 15): {"trace_id", "span_id"} stamped at
    # export so the adopting peer's restore/decode spans land in the
    # same trace. Rides the wire blob's JSON header; un-upgraded peers
    # skip it (unknown header keys are ignored by construction).
    trace: Optional[dict] = None
    # Tree context (ISSUE 20): the owning agent's lineage stamp
    # (treeobs.TreeContext.to_dict) so the adopting peer's continuation
    # books its waits to the SAME tree node. Same wire contract as
    # ``trace``: unknown header keys are ignored by un-upgraded peers.
    tree: Optional[dict] = None

    @property
    def n_tokens(self) -> int:
        return len(self.entry.tokens)


class KVHandoff:
    """The handoff broker for one cluster plane: export/adopt between
    role-tagged engines plus the in-flight envelope ledger that makes
    decode-replica death recoverable."""

    def __init__(self):
        self._lock = named_lock("handoff")
        self._inflight: dict[str, HandoffEnvelope] = {}
        self.exports = 0
        self.adopts = 0
        self.rejects = 0
        self.replaced = 0

    # -- export (prefill side) ------------------------------------------

    def export(self, engine, session_id: str, model_spec: str,
               src_replica: str = "",
               json_state: Optional[int] = None) -> HandoffEnvelope:
        """Hibernate ``session_id`` out of ``engine`` into an envelope.
        Raises :class:`HandoffError` when the engine holds no such
        session (nothing prefilled — caller re-prefills downstream)."""
        # Chaos seam (ISSUE 11): a "fail" directive aborts the export
        # before any pages move — the caller's contract (degrade to a
        # cold re-prefill on the decode side, request still served) is
        # exactly what the scenario harness asserts.
        from quoracle_tpu.models.config import require_plain
        require_plain(engine.cfg, "session handoff (--disaggregate)")
        from quoracle_tpu.chaos.faults import CHAOS
        d = CHAOS.fire("handoff.export", model=model_spec)
        if d is not None and d.kind == "fail":
            CLUSTER_HANDOFFS_TOTAL.inc(model=model_spec,
                                       status="export_failed")
            raise HandoffError(
                f"chaos-injected export failure for session "
                f"{session_id!r}", reason="export_failed")
        tier = engine.sessions.tier
        if tier is None:
            raise HandoffError(
                f"engine {engine.cfg.name} has no KV tier attached — "
                f"the cluster plane attaches tiers to every replica",
                reason="no_tier")
        t0 = time.monotonic()
        with engine._paged_lock:
            entry = tier.export_session(session_id)
        if entry is None:
            CLUSTER_HANDOFFS_TOTAL.inc(model=model_spec,
                                       status="export_failed")
            raise HandoffError(
                f"session {session_id!r} not exportable from "
                f"{engine.cfg.name}", reason="export_failed")
        ctx = fleetobs.TraceContext.current()
        from quoracle_tpu.infra import treeobs
        tctx = treeobs.current() if treeobs.enabled() else None
        env = HandoffEnvelope(
            session_id=session_id, model_spec=model_spec,
            signature=engine.kv_signature(), entry=entry,
            json_state=json_state, src_replica=src_replica,
            ts=time.monotonic(),
            trace=ctx.to_dict() if ctx is not None else None,
            tree=tctx.to_dict() if tctx is not None else None)
        if getattr(entry, "k_scale", None) is not None:
            # int8 entry (ISSUE 13): this envelope ships ~half the
            # bytes its bf16 twin would — count the savings per tier
            from quoracle_tpu.infra.telemetry import (
                QUANT_BYTES_SAVED_TOTAL,
            )
            payload = int(entry.k.nbytes) + int(entry.v.nbytes)
            QUANT_BYTES_SAVED_TOTAL.inc(
                max(0, 2 * payload - entry.nbytes),
                model=model_spec, tier="handoff")
        with self._lock:
            self._inflight[self._key(model_spec, session_id)] = env
            self.exports += 1
        export_ms = (time.monotonic() - t0) * 1000
        FLIGHT.record("kv_handoff_export", model=model_spec,
                      session=session_id, replica=src_replica,
                      tokens=env.n_tokens, ms=round(export_ms, 2))
        if TRACER.active():
            TRACER.emit("kv.export", export_ms,
                        ts=time.time() - export_ms / 1000.0,
                        session=session_id, model=model_spec,
                        replica=src_replica, tokens=env.n_tokens)
        return env

    # -- adopt (decode side) --------------------------------------------

    def adopt(self, engine, env: HandoffEnvelope,
              dst_replica: str = "") -> None:
        """Place the envelope into ``engine``'s host tier and page it in
        (best-effort prefetch — a full pool restores lazily at the
        session lookup, which is always correct). Raises
        :class:`HandoffError` on a KV-signature mismatch BEFORE any
        bytes reach the destination tier."""
        sig = engine.kv_signature()
        if sig != env.signature:
            with self._lock:
                self.rejects += 1
            CLUSTER_HANDOFFS_TOTAL.inc(model=env.model_spec,
                                       status="signature_mismatch")
            FLIGHT.record("kv_handoff_reject", model=env.model_spec,
                          session=env.session_id,
                          src_signature=env.signature, dst_signature=sig,
                          replica=dst_replica)
            raise HandoffError(
                f"KV signature mismatch: prefill replica produced "
                f"{env.signature!r}, decode engine expects {sig!r} — "
                f"version-skewed replica pair", reason="signature")
        tier = engine.sessions.tier
        if tier is None:
            raise HandoffError(
                f"decode engine {engine.cfg.name} has no KV tier",
                reason="no_tier")
        t0 = time.monotonic()
        tier.adopt_session(env.session_id, env.entry)
        engine.prefetch_session(env.session_id)
        ms = (time.monotonic() - t0) * 1000
        with self._lock:
            self.adopts += 1
        CLUSTER_HANDOFFS_TOTAL.inc(model=env.model_spec, status="ok")
        CLUSTER_HANDOFF_MS.observe(
            ms + max(0.0, (t0 - env.ts) * 1000), model=env.model_spec)
        FLIGHT.record("kv_handoff_adopt", model=env.model_spec,
                      session=env.session_id, replica=dst_replica,
                      tokens=env.n_tokens, ms=round(ms, 2))
        if TRACER.active():
            # parent onto the exporting side's context when the local
            # thread carries none (the envelope's trace crossed the
            # wire with the pages)
            TRACER.emit("kv.adopt", ms,
                        parent=(TRACER.current()
                                or fleetobs.TraceContext.from_dict(
                                    env.trace)),
                        ts=time.time() - ms / 1000.0,
                        session=env.session_id, model=env.model_spec,
                        replica=dst_replica, tokens=env.n_tokens)

    # -- ledger ----------------------------------------------------------

    @staticmethod
    def _key(model_spec: str, session_id: str) -> str:
        return f"{model_spec}\x00{session_id}"

    def inflight(self, model_spec: str,
                 session_id: str) -> Optional[HandoffEnvelope]:
        """The retained envelope for a still-running row — the failover
        source when its decode replica dies mid-stream."""
        with self._lock:
            return self._inflight.get(self._key(model_spec, session_id))

    def note_replaced(self, model_spec: str) -> None:
        with self._lock:
            self.replaced += 1
        CLUSTER_HANDOFFS_TOTAL.inc(model=model_spec, status="replaced")

    def forget(self, model_spec: str, session_id: str) -> None:
        """Row retired (or permanently failed): drop its envelope."""
        with self._lock:
            self._inflight.pop(self._key(model_spec, session_id), None)

    def stats(self) -> dict:
        with self._lock:
            return {
                "exports": self.exports,
                "adopts": self.adopts,
                "rejects": self.rejects,
                "replaced": self.replaced,
                "inflight": len(self._inflight),
            }
