"""Runtime lock-order sanitizer + the declared lock hierarchy (ISSUE 9).

A ThreadSanitizer-lite for the serving plane. The repo's threaded
modules create their locks through :func:`named_lock`, which names each
lock and assigns it a RANK in the declared hierarchy below. When the
sanitizer is enabled (``QUORACLE_LOCKDEP=1`` at process start, or
:func:`enable` — tests/conftest.py turns it on for the whole tier-1
suite), every acquisition is checked per thread: blocking-acquiring a
lock whose rank is not strictly greater than every lock the thread
already holds is a LOCK-ORDER INVERSION — the precondition for an
ABBA deadlock — and is recorded to :data:`LOCKDEP`, the flight recorder
(``lockdep_inversion``), and the ``quoracle_lockdep_inversions_total``
counter. The static mirror (analysis/locks.py) checks the same ranks
over the AST, so a violation is caught whether or not a test happens to
thread through it.

Design rules (mirroring kernel lockdep):

* **Try-acquires are exempt.** ``acquire(blocking=False)`` cannot
  deadlock — backing off on contention is the sanctioned way to take a
  lock against the declared order (GenerateEngine.prefetch_session).
  Successful try-acquires still enter the held stack and the
  observed-edge graph.
* **Re-entrant re-acquisition is exempt.** Taking a lock the thread
  already holds (RLocks) blocks on nothing.
* **Coarse locks** (``coarse=True``) serialize device work by design —
  the engine's paged lock, the native build lock.
  The flag is metadata for the STATIC pass (blocking calls under them
  are their purpose, not a finding); ranks still apply at runtime.
* **Disabled is near-free.** ``named_lock`` always returns a
  :class:`TrackedLock`; when the sanitizer is off, acquire/release is
  one attribute load and a branch on top of the raw primitive, so
  production keeps the wrapper without the bookkeeping.

The hierarchy (ISSUE 9's session → tier → cache → metrics, refined to
one rank per named lock — a thread acquires STRICTLY DOWN this table):
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Optional

# ---------------------------------------------------------------------------
# The declared hierarchy: (name, rank, coarse). Outermost (lowest rank)
# first; a thread holding rank r may blocking-acquire only ranks > r.
# analysis/locks.py statically checks the same table over the AST, and
# ARCHITECTURE.md §12 renders it as the lock-discipline diagram.
# ---------------------------------------------------------------------------

HIERARCHY: tuple = (
    # -- fleet simulator (outermost of all — the replay driver's status
    #    board is pure bookkeeping, but an engine-sampled replay calls
    #    straight into ClusterPlane.query, so the sim lock must release
    #    before any serving lock is taken) -----------------------------
    # -- serving flywheel (outermost of everything — the promotion
    #    orchestrator drains replicas through the fleet controller (5)
    #    and reaches engine locks (25) while holding it, so it must
    #    sit in front of the whole serving hierarchy) ------------------
    ("train.promote",   2, False),  # training/promote.py incumbent
                                    # ledger + guard state: pure
                                    # bookkeeping, the drain/swap work
                                    # happens through fleet/cluster
                                    # locks acquired under it
    ("sim.replay",      3, False),  # sim/replay.py SIM status board
    # -- cluster plane (outermost serving lock — the router sits in
    #    FRONT of every replica's batcher, so its locks must release
    #    before any replica-internal lock is taken) ---------------------
    ("cluster.plane",   4, False),  # ClusterPlane replica table / seq
    ("fleet",           5, False),  # FleetController ledger + policy
                                    # state (ISSUE 14): decisions read
                                    # router/replica signals (6+) and
                                    # drains reach engine locks (25),
                                    # so it sits above both — pure
                                    # bookkeeping, no device work under
                                    # it
    ("router",          6, False),  # ClusterRouter affinity + liveness
    ("fabric.plane",    7, False),  # FabricPlane peer table + retained
                                    # envelope-bytes ledger (below the
                                    # router it serves, above every
                                    # peer-side lock)
    ("handoff",         8, False),  # KVHandoff in-flight envelope ledger
    ("fabric.transport", 9, True),  # one wire request in flight per
                                    # transport: socket I/O under it is
                                    # its purpose (coarse), taken under
                                    # plane/router/handoff, never above
                                    # a replica-internal lock
    # -- admission / scheduling plane -----------------------------------
    ("batcher",        10, False),  # ContinuousBatcher queue/close lock
    ("qos.admission",  12, False),  # AdmissionController tenant table
    ("qos.signals",    14, False),  # AdmissionController cached signals
    ("qos.queue",      16, False),  # Fifo/WeightedFair policy queues
    ("qos.slo",        18, False),  # SLOTracker EWMA tail state
    ("qos.bucket",     19, False),  # per-tenant TokenBucket
    # -- pool-member serialization --------------------------------------
    ("spec.decoder",   22, True),   # v1 batch-1 speculative decoder
    ("spec.adaptive",  23, False),  # BatchedSpeculator adaptive-K state
    # -- session plane --------------------------------------------------
    ("engine.paged",   25, True),   # GenerateEngine pool entry: donated
                                    # paged steps serialize through it
    ("session.store",  30, False),  # SessionStore pages/refs/radix tree
    # -- tier plane -----------------------------------------------------
    ("fabric.prefixd", 32, True),   # fleet prefix-service client: its
                                    # wire I/O serializer, acquired on
                                    # the restore path under
                                    # session.store (30); the loopback
                                    # handler then takes tier.disk (35)
    ("tier.disk",      35, False),  # DiskPrefixStore size accounting
    # -- cache plane ----------------------------------------------------
    ("cache.grammar",  40, False),  # grammar-table cache
    ("cache.compile",  41, False),  # CompileRegistry ledger
    ("cache.lru",      42, False),  # utils/cache.TTLCache
    ("engine.rng",     43, False),  # engine RNG split
    ("native.build",   45, True),   # serialize native toolchain builds
    ("train.capture",  46, True),   # replay capture store buffer +
                                    # segment ledger: the sealed-
                                    # segment file write under it is
                                    # its purpose (coarse); taken with
                                    # no serving lock held (speculator
                                    # tap and quality sink both fire
                                    # outside their planes' locks) and
                                    # may fire chaos.plan (48) beneath
    ("treeobs",        47, False),  # session-graph registry (ISSUE 20,
                                    # infra/treeobs.py): node records +
                                    # integer rollup counters — charge
                                    # sites run under serving locks, so
                                    # it sits above them; metric/flight
                                    # emission happens strictly OUTSIDE
                                    # it (costobs discipline)
    # -- chaos plane (ISSUE 11) -----------------------------------------
    ("chaos.plan",     48, False),  # ChaosPlane armed-plan + fire ledger:
                                    # fire() is called under store/tier
                                    # locks (30/35) and records to
                                    # flight/metrics (58/60), so it sits
                                    # strictly between them
    # -- observability plane (leaves) -----------------------------------
    ("introspect",     49, False),  # liveness & hotspot plane (ISSUE 18,
                                    # infra/introspect.py): heartbeat
                                    # counters, profiler windows, wait
                                    # aggregates — beat() runs under any
                                    # serving lock, so it sits above
                                    # them all; flight/metric emission
                                    # and frame walking happen strictly
                                    # OUTSIDE it (costobs discipline)
    ("quality",        50, False),  # consensus scorecards/drift
    ("quality.sinks",  51, False),  # quality sink list
    ("history",        52, False),  # EventHistory rings (OUTER of bus:
                                    # track_* subscribes under it)
    ("bus",            53, False),  # EventBus subscriber table
    ("costobs",        54, False),  # chip-economics ledger (ISSUE 17):
                                    # pure bookkeeping — charge cells,
                                    # roofline observations, budget
                                    # windows; metric/flight calls
                                    # happen strictly OUTSIDE it
    ("tracer.sinks",   55, False),  # Tracer sink list
    ("fleetobs.spans", 56, False),  # fleetobs span ring (ISSUE 15):
                                    # appended from tracer sinks under
                                    # arbitrary serving locks, reads
                                    # nothing below it
    ("fleetobs.incidents", 57, False),  # incident ledger counters/ids
                                    # (ISSUE 15): pure bookkeeping —
                                    # flight dumps and file I/O happen
                                    # strictly OUTSIDE it
    ("flight",         58, False),  # flight-recorder ring
    ("metrics.registry", 59, False),  # MetricsRegistry name table
    ("metrics",        60, False),  # per-metric cells (innermost)
)

RANKS: dict = {name: rank for name, rank, _ in HIERARCHY}
COARSE: frozenset = frozenset(n for n, _, c in HIERARCHY if c)


def _env_enabled() -> bool:
    return os.environ.get("QUORACLE_LOCKDEP", "").strip().lower() not in (
        "", "0", "false", "off")


class _State:
    __slots__ = ("enabled",)

    def __init__(self) -> None:
        self.enabled = _env_enabled()


_STATE = _State()


def enabled() -> bool:
    return _STATE.enabled


def enable() -> None:
    """Turn the sanitizer on for every TrackedLock in the process (the
    tier-1 conftest calls this; QUORACLE_LOCKDEP=1 does it at import)."""
    _STATE.enabled = True


def disable() -> None:
    _STATE.enabled = False


def _caller() -> str:
    """First stack frame outside this module — the acquisition site."""
    f = sys._getframe(2)
    here = __file__
    while f is not None and f.f_code.co_filename == here:
        f = f.f_back
    if f is None:
        return "?"
    return f"{f.f_code.co_filename}:{f.f_lineno}"


class LockDep:
    """Per-thread held-lock stacks + the inversion/edge ledger."""

    def __init__(self) -> None:
        self._tls = threading.local()
        self._lock = threading.Lock()          # guards the ledgers only
        self._inversions: list[dict] = []
        self._seen: set = set()                # (held_name, acq_name)
        self._edges: set = set()               # (outer_name, inner_name)
        # thread ident -> (thread name, that thread's held stack LIST —
        # the same object _stack() mutates, so holders() can snapshot
        # every thread's held locks without stopping the world
        self._stacks: dict[int, tuple] = {}

    # -- held-stack plumbing (called from TrackedLock) -------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
            t = threading.current_thread()
            with self._lock:
                self._stacks[t.ident] = (t.name, st)
        return st

    def note_acquire(self, lock: "TrackedLock", blocking: bool) -> None:
        """Record (and rank-check, for blocking acquires) BEFORE the
        base primitive blocks — an inversion is reported even when the
        interleaving that would deadlock doesn't happen this run."""
        stack = self._stack()
        for frame in stack:
            if frame[0] is lock:
                return                          # re-entrant: exempt
        if blocking and not getattr(self._tls, "reporting", False):
            bad = [(f[1], f[2]) for f in stack if f[2] >= lock.rank]
            if bad:
                self._report(lock, bad, list(stack))

    def note_acquired(self, lock: "TrackedLock") -> None:
        stack = self._stack()
        for frame in stack:
            if frame[0] is lock:
                frame[3] += 1                   # re-entrant depth
                return
        if stack and not getattr(self._tls, "reporting", False):
            with self._lock:
                for f in stack:
                    self._edges.add((f[1], lock.name))
        stack.append([lock, lock.name, lock.rank, 1])

    def note_release(self, lock: "TrackedLock") -> None:
        stack = self._stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] is lock:
                stack[i][3] -= 1
                if stack[i][3] <= 0:
                    del stack[i]
                return

    # -- reporting -------------------------------------------------------

    def _report(self, lock: "TrackedLock", bad: list, held: list) -> None:
        key = (bad[-1][0], lock.name)
        with self._lock:
            first = key not in self._seen
            self._seen.add(key)
            site = _caller()
            event = {
                "ts": time.time(),
                "thread": threading.current_thread().name,
                "acquiring": lock.name,
                "rank": lock.rank,
                "held": [(f[1], f[2]) for f in held],
                "violates": bad,
                "site": site,
            }
            self._inversions.append(event)
        if not first:
            return
        # flight + metrics OUTSIDE our ledger lock, with recursion
        # guarded: FLIGHT/METRICS take their own (ranked) locks.
        self._tls.reporting = True
        try:
            from quoracle_tpu.infra.flightrec import FLIGHT
            FLIGHT.record("lockdep_inversion", **{
                k: v for k, v in event.items() if k != "ts"})
            from quoracle_tpu.infra.telemetry import LOCKDEP_INVERSIONS
            LOCKDEP_INVERSIONS.inc(acquiring=lock.name, held=bad[-1][0])
        except Exception:               # noqa: BLE001 — sanitizer must
            pass                        # never take the serving path down
        finally:
            self._tls.reporting = False

    # -- introspection (tests, qlint --lockdep-report) -------------------

    def inversions(self) -> list[dict]:
        with self._lock:
            return list(self._inversions)

    def observed_edges(self) -> set:
        with self._lock:
            return set(self._edges)

    def drain(self) -> list[dict]:
        """Return-and-clear the inversion ledger (the per-test conftest
        guard consumes it; the seeded-inversion race test drains its own
        report so the guard stays green)."""
        with self._lock:
            out, self._inversions = self._inversions, []
            self._seen.clear()
            return out

    def held(self) -> list[tuple]:
        """This thread's held stack as (name, rank, depth) tuples."""
        return [(f[1], f[2], f[3]) for f in self._stack()]

    def holders(self) -> dict:
        """EVERY thread's held locks — ``thread-name:ident`` →
        ``[(name, rank, depth), ...]`` — for the stall detector's
        capture bundle (ISSUE 18): who holds what while a stage is
        wedged. Best-effort without stopping the world: each stack
        list is copied atomically under the GIL, dead threads' entries
        are pruned as a side effect. Threads holding nothing are
        omitted."""
        alive = {t.ident: t.name for t in threading.enumerate()}
        with self._lock:
            for ident in [i for i in self._stacks if i not in alive]:
                del self._stacks[ident]
            items = list(self._stacks.items())
        out: dict = {}
        for ident, (tname, st) in items:
            frames = [(f[1], f[2], f[3]) for f in list(st)]
            if frames:
                out[f"{tname}:{ident}"] = frames
        return out


LOCKDEP = LockDep()

# Contended-acquire wait hook (ISSUE 18): infra/introspect.py installs
# a ``fn(lock_name, waited_ns)`` here when wait-state decomposition is
# on. Only a CONTENDED blocking acquire pays the two clock reads — the
# uncontended fast path is one extra try-acquire. The hook runs while
# the caller may hold arbitrary ranked locks, so it must take none.
LOCK_WAIT_HOOK: Optional[Any] = None


class TrackedLock:
    """A named, ranked lock. Delegates to a raw Lock/RLock; when the
    sanitizer is enabled, acquisitions thread through :data:`LOCKDEP`."""

    __slots__ = ("_base", "name", "rank", "coarse", "reentrant")

    def __init__(self, name: str, base: Any, rank: int, coarse: bool,
                 reentrant: bool):
        self._base = base
        self.name = name
        self.rank = rank
        self.coarse = coarse
        self.reentrant = reentrant

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if not _STATE.enabled:
            if LOCK_WAIT_HOOK is None:
                return self._base.acquire(blocking, timeout)
            return self._acquire_timed(blocking, timeout)
        LOCKDEP.note_acquire(self, blocking)
        got = (self._base.acquire(blocking, timeout)
               if LOCK_WAIT_HOOK is None
               else self._acquire_timed(blocking, timeout))
        if got:
            LOCKDEP.note_acquired(self)
        return got

    def _acquire_timed(self, blocking: bool, timeout: float) -> bool:
        """Acquire with the contended-wait hook armed: try first (free
        when uncontended — and re-entrant RLocks succeed here), time
        only the blocking wait."""
        hook = LOCK_WAIT_HOOK
        if hook is None or not blocking:
            return self._base.acquire(blocking, timeout)
        if self._base.acquire(False):
            return True
        t0 = time.monotonic_ns()
        got = self._base.acquire(True, timeout)
        try:
            hook(self.name, time.monotonic_ns() - t0)
        except Exception:             # noqa: BLE001 — telemetry only
            pass
        return got

    def release(self) -> None:
        if _STATE.enabled:
            LOCKDEP.note_release(self)
        self._base.release()

    def __enter__(self) -> "TrackedLock":
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.release()

    def locked(self) -> bool:
        return self._base.locked()

    def __repr__(self) -> str:
        return (f"<TrackedLock {self.name!r} rank={self.rank}"
                f"{' coarse' if self.coarse else ''}>")


def named_lock(name: str, *, rlock: bool = False) -> TrackedLock:
    """Create a lock registered in the declared hierarchy. ``name`` MUST
    appear in :data:`HIERARCHY` — an unknown name fails fast at
    construction so the table stays the single authority (qlint's static
    pass reads the same names off the ``named_lock`` call sites)."""
    try:
        rank = RANKS[name]
    except KeyError:
        raise ValueError(
            f"lock name {name!r} is not in the declared hierarchy "
            f"(analysis/lockdep.HIERARCHY); add it with a rank before "
            f"use") from None
    base = threading.RLock() if rlock else threading.Lock()
    return TrackedLock(name, base, rank, name in COARSE, rlock)
