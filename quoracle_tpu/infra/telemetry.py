"""Serving-path telemetry: metrics registry + span tracing.

The reference feeds Phoenix LiveDashboard from `telemetry.ex` summaries;
here the equivalent is split in two primitives sized for the TPU serving
path:

* **MetricsRegistry** — counters, gauges, and fixed-bucket EXPONENTIAL
  latency histograms. Recording is lock-cheap (one small per-metric lock,
  a bisect, two adds — no allocation on the hot path); snapshots derive
  p50/p95/p99 by linear interpolation inside the owning bucket, and
  `render_prometheus()` emits the text exposition format for scraping at
  ``GET /metrics`` (web/server.py).
* **Tracer** — span-based tracing. A :class:`Span` carries ``trace_id``
  (the task), ``agent_id``, ``round``, and ``phase`` attributes and links
  to its parent; finished spans go to registered sinks (the Runtime's
  sink broadcasts them on ``TOPIC_TRACE``, ring-buffered by
  infra/event_history.py and queryable at ``/api/trace?task_id=…``).
  Propagation across the thread hops of the serving path (agent executor
  thread → pool-member threads → the batcher's worker) is explicit:
  ``TRACER.use(parent)`` rebinds the current span in a foreign thread.

Telemetry is the ONE deliberately process-wide component in a codebase
that otherwise injects every dependency (root AGENTS.md DI rule): metrics
are write-mostly aggregates and spans carry their own ``trace_id``, so
cross-Runtime isolation comes from filtering, not instancing. Tests that
need a hermetic view build their own :class:`MetricsRegistry` /
:class:`Tracer` or attach a private sink.

Recording never touches RNG or device state — temp-0 outputs are
bit-identical with tracing on or off (ISSUE 2 acceptance).
"""

from __future__ import annotations

import bisect
import contextlib
import itertools
import threading
import time
from typing import Any, Callable, Iterable, Optional, Sequence

from quoracle_tpu.analysis.lockdep import named_lock

# ---------------------------------------------------------------------------
# Buckets
# ---------------------------------------------------------------------------

# Latency buckets in MILLISECONDS: powers of two from 0.5 ms to ~65 s.
# Exponential spacing keeps relative quantile error bounded (~±50% worst
# case, far tighter after interpolation) across the 5 decades the serving
# path spans (µs-scale cache lookups to multi-second compile rounds).
DEFAULT_MS_BUCKETS: tuple[float, ...] = tuple(2.0 ** i for i in range(-1, 17))


def quantile(bounds: Sequence[float], counts: Sequence[int],
             p: float) -> Optional[float]:
    """The p-quantile (0 < p < 1) of a bucketed distribution.

    ``counts`` has ``len(bounds) + 1`` slots (the last is the +Inf
    overflow). Linear interpolation inside the owning bucket; the overflow
    bucket reports its lower edge (no upper bound to interpolate to).
    Returns None for an empty histogram. Exposed as a module function so
    a reader can compute quantiles of COUNT DELTAS (before/after a
    measured window) without a second histogram instance.
    """
    total = sum(counts)
    if total <= 0:
        return None
    target = p * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= target:
            lo = bounds[i - 1] if i > 0 else 0.0
            if i >= len(bounds):          # +Inf overflow bucket
                return lo
            hi = bounds[i]
            frac = (target - cum) / c
            return lo + frac * (hi - lo)
        cum += c
    return bounds[-1]


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _label_str(key: tuple) -> str:
    return ",".join(f"{k}={v}" for k, v in key)


def _escape(v: Any) -> str:
    return (str(v).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


def _fmt_labels(key: tuple, extra: tuple = ()) -> str:
    items = tuple(key) + tuple(extra)
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{_escape(v)}"' for k, v in items) + "}"


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = named_lock("metrics")
        # label-key tuple -> cell (shape depends on the metric kind)
        self._cells: dict[tuple, Any] = {}


class Counter(_Metric):
    kind = "counter"

    def inc(self, n: float = 1.0, **labels: Any) -> None:
        key = _label_key(labels)
        with self._lock:
            self._cells[key] = self._cells.get(key, 0.0) + n

    def value(self, **labels: Any) -> float:
        with self._lock:
            return float(self._cells.get(_label_key(labels), 0.0))

    def total(self) -> float:
        with self._lock:
            return float(sum(self._cells.values()))

    def _snapshot(self) -> dict:
        with self._lock:
            cells = dict(self._cells)
        return {"type": self.kind, "total": sum(cells.values()),
                "series": {_label_str(k): v for k, v in cells.items()}}

    def _render(self, out: list[str]) -> None:
        with self._lock:
            cells = dict(self._cells)
        for key, v in sorted(cells.items()):
            out.append(f"{self.name}{_fmt_labels(key)} {_num(v)}")


class Gauge(_Metric):
    kind = "gauge"

    def set(self, v: float, **labels: Any) -> None:
        with self._lock:
            self._cells[_label_key(labels)] = float(v)

    def value(self, **labels: Any) -> Optional[float]:
        with self._lock:
            return self._cells.get(_label_key(labels))

    def _snapshot(self) -> dict:
        with self._lock:
            cells = dict(self._cells)
        return {"type": self.kind,
                "series": {_label_str(k): v for k, v in cells.items()}}

    def _render(self, out: list[str]) -> None:
        with self._lock:
            cells = dict(self._cells)
        for key, v in sorted(cells.items()):
            out.append(f"{self.name}{_fmt_labels(key)} {_num(v)}")


class _HistCell:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * (n_buckets + 1)   # +1 = +Inf overflow
        self.sum = 0.0
        self.count = 0


class Histogram(_Metric):
    """Fixed-bucket exponential histogram. ``observe`` is the hot path:
    one lock, one bisect, three adds."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = DEFAULT_MS_BUCKETS):
        super().__init__(name, help)
        self.buckets = tuple(float(b) for b in buckets)
        assert list(self.buckets) == sorted(set(self.buckets)), \
            "histogram buckets must be strictly increasing"

    def observe(self, v: float, **labels: Any) -> None:
        key = _label_key(labels)
        idx = bisect.bisect_left(self.buckets, v)
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells[key] = _HistCell(len(self.buckets))
            cell.counts[idx] += 1
            cell.sum += v
            cell.count += 1

    # -- reads -----------------------------------------------------------

    def counts(self, **labels: Any) -> tuple[list[int], float, int]:
        """(bucket counts incl. +Inf slot, sum, count). With no labels the
        counts AGGREGATE across every label set — a reader diffs these
        around a measured window."""
        with self._lock:
            if labels:
                cell = self._cells.get(_label_key(labels))
                cells = [cell] if cell is not None else []
            else:
                cells = list(self._cells.values())
        agg = [0] * (len(self.buckets) + 1)
        s, n = 0.0, 0
        for c in cells:
            for i, v in enumerate(c.counts):
                agg[i] += v
            s += c.sum
            n += c.count
        return agg, s, n

    def percentiles(self, ps: Iterable[float] = (0.50, 0.95, 0.99),
                    **labels: Any) -> dict[float, Optional[float]]:
        agg, _, _ = self.counts(**labels)
        return {p: quantile(self.buckets, agg, p) for p in ps}

    # -- federation (ISSUE 15) -------------------------------------------

    def merge(self, other: "Histogram") -> None:
        """LOSSLESS merge of another histogram's cells into this one:
        identical bucket boundaries → per-bucket summed counts, so every
        quantile of the merged histogram equals the quantile of one
        histogram that observed both streams (the fleet-rollup
        guarantee; mismatched boundaries refuse loudly — a lossy
        re-bucketing would silently corrupt the federated tails)."""
        if tuple(other.buckets) != self.buckets:
            raise ValueError(
                f"histogram {self.name!r}: cannot merge mismatched "
                f"bucket boundaries ({len(other.buckets)} vs "
                f"{len(self.buckets)})")
        with other._lock:
            cells = {k: (list(c.counts), c.sum, c.count)
                     for k, c in other._cells.items()}
        for key, (counts, s, n) in cells.items():
            self.merge_cell(key, counts, s, n)

    def merge_cell(self, key: tuple, counts: Sequence[int],
                   s: float, n: int) -> None:
        """Merge one exported cell (bucket counts + sum + count) under
        ``key`` — the primitive both :meth:`merge` and the wire-state
        federation (infra/fleetobs.py) build on."""
        if len(counts) != len(self.buckets) + 1:
            raise ValueError(
                f"histogram {self.name!r}: cell has {len(counts)} "
                f"buckets, expected {len(self.buckets) + 1}")
        key = tuple(sorted((str(k), str(v)) for k, v in key))
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells[key] = _HistCell(len(self.buckets))
            for i, c in enumerate(counts):
                cell.counts[i] += int(c)
            cell.sum += float(s)
            cell.count += int(n)

    def _snapshot(self) -> dict:
        def q(agg):
            return {f"p{int(p * 100)}": quantile(self.buckets, agg, p)
                    for p in (0.50, 0.95, 0.99)}
        with self._lock:
            cells = {k: (list(c.counts), c.sum, c.count)
                     for k, c in self._cells.items()}
        agg, s, n = [0] * (len(self.buckets) + 1), 0.0, 0
        series = {}
        for k, (counts, cs, cn) in cells.items():
            for i, v in enumerate(counts):
                agg[i] += v
            s += cs
            n += cn
            series[_label_str(k)] = {"count": cn, "sum": cs, **q(counts)}
        return {"type": self.kind, "count": n, "sum": s, **q(agg),
                "series": series}

    def _render(self, out: list[str]) -> None:
        with self._lock:
            cells = {k: (list(c.counts), c.sum, c.count)
                     for k, c in self._cells.items()}
        for key, (counts, s, n) in sorted(cells.items()):
            cum = 0
            for b, c in zip(self.buckets, counts):
                cum += c
                out.append(f"{self.name}_bucket"
                           f"{_fmt_labels(key, (('le', _num(b)),))} {cum}")
            out.append(f"{self.name}_bucket"
                       f"{_fmt_labels(key, (('le', '+Inf'),))} {n}")
            out.append(f"{self.name}_sum{_fmt_labels(key)} {_num(s)}")
            out.append(f"{self.name}_count{_fmt_labels(key)} {n}")


def _num(v: float) -> str:
    """Prometheus number formatting: integral floats render bare."""
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


class MetricsRegistry:
    """Get-or-create registry; re-registering a name returns the existing
    metric (type mismatch raises — two layers silently recording into
    differently-typed metrics of one name would corrupt both).

    COLLECTORS are scrape-time callbacks (ISSUE 3): values that are a
    *view of live state* (device memory, queue depth, open fds) rather
    than an event stream would go stale the moment they were set — so a
    collector re-derives them lazily at every ``snapshot()`` /
    ``render_prometheus()``, setting plain gauges the exposition then
    renders. Collector exceptions are swallowed: a broken sampler must
    never take a scrape (or the serving path behind it) down."""

    def __init__(self) -> None:
        self._lock = named_lock("metrics.registry")
        self._metrics: dict[str, _Metric] = {}
        self._collectors: list[Callable[[], None]] = []

    # -- collectors ------------------------------------------------------

    def register_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def remove_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn in self._collectors:
                self._collectors.remove(fn)

    def collect(self) -> None:
        """Run every registered collector (outside the registry lock —
        collectors call back into gauge()/set())."""
        with self._lock:
            fns = list(self._collectors)
        for fn in fns:
            try:
                fn()
            except Exception:             # noqa: BLE001 — telemetry only
                pass

    def _get(self, cls, name: str, help: str, **kw) -> Any:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, **kw)
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{m.kind}, not {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_MS_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def snapshot(self) -> dict:
        """JSON-friendly view for /api/metrics: per metric the aggregate
        (and per-label-series) counts + p50/p95/p99 quantiles — the
        histogram replacement for the last-call scalars. Collectors run
        first so lazily-sampled gauges are current."""
        self.collect()
        with self._lock:
            metrics = list(self._metrics.values())
        return {m.name: m._snapshot() for m in metrics}

    def render_prometheus(self) -> str:
        """Text exposition format (version 0.0.4). HELP/TYPE headers are
        emitted for every registered metric even before first traffic, so
        scrapers and tests see the full metric surface immediately.
        Collectors run first (scrape-time gauge refresh)."""
        self.collect()
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        out: list[str] = []
        for m in metrics:
            if m.help:
                out.append(f"# HELP {m.name} {m.help}")
            out.append(f"# TYPE {m.name} {m.kind}")
            m._render(out)
        return "\n".join(out) + "\n"

    def reset(self) -> None:
        """Drop every registered metric (tests). Collectors survive — they
        get-or-create their gauges by name at the next scrape."""
        with self._lock:
            self._metrics.clear()

    # -- portable state (ISSUE 15 federation) -----------------------------

    def export_state(self) -> dict:
        """The registry's full state as a JSON-able dict — the wire
        payload a fleet front door scrapes from each peer (fleetobs's
        MSG_OBS "metrics" op). Unlike the Prometheus text exposition
        this is LOSSLESS for histograms (raw bucket counts travel, not
        quantiles), so the front door's merged rollup interpolates
        quantiles over summed counts exactly as one process would.
        Collectors run first, like every other scrape."""
        self.collect()
        with self._lock:
            metrics = list(self._metrics.values())
        out: dict = {}
        for m in metrics:
            entry: dict = {"kind": m.kind, "help": m.help}
            with m._lock:
                cells = dict(m._cells)
            if isinstance(m, Histogram):
                entry["buckets"] = list(m.buckets)
                entry["series"] = [
                    [list(map(list, k)),
                     {"counts": list(c.counts), "sum": c.sum,
                      "count": c.count}]
                    for k, c in cells.items()]
            else:
                entry["series"] = [[list(map(list, k)), v]
                                   for k, v in cells.items()]
            out[m.name] = entry
        return out


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------

_span_ids = itertools.count(1)


class Span:
    """One timed unit of work. Attributes are free-form; the serving path
    uses trace_id (task), agent_id, model, round, phase."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "_t0", "ts", "duration_ms", "_tracer")

    def __init__(self, tracer: "Tracer", name: str,
                 trace_id: Optional[str], parent_id: Optional[str],
                 attrs: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = f"s{next(_span_ids):x}"
        self.parent_id = parent_id
        self.attrs = attrs
        self._t0 = time.monotonic()
        self.ts = time.time()
        self.duration_ms: Optional[float] = None
        self._tracer = tracer

    def finish(self, **attrs: Any) -> None:
        if self.duration_ms is not None:
            return                        # idempotent
        if attrs:
            self.attrs.update(attrs)
        self.duration_ms = (time.monotonic() - self._t0) * 1000.0
        self._tracer._emit(self)

    def as_event(self) -> dict:
        return {"event": "span", "ts": self.ts, "name": self.name,
                "trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id,
                "duration_ms": (round(self.duration_ms, 3)
                                if self.duration_ms is not None else None),
                **self.attrs}


class _SpanCtx:
    """Context manager: binds the span as the thread's current on enter,
    restores the previous current and finishes on exit."""

    __slots__ = ("_tracer", "_span", "_bind", "_prev")

    def __init__(self, tracer: "Tracer", span: Span, bind: bool):
        self._tracer = tracer
        self._span = span
        self._bind = bind

    def __enter__(self) -> Span:
        if self._bind:
            self._prev = self._tracer.current()
            self._tracer._set_current(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._bind:
            self._tracer._set_current(self._prev)
        self._span.finish(**({"error": repr(exc)} if exc is not None
                             else {}))


class _UseCtx:
    __slots__ = ("_tracer", "_span", "_prev")

    def __init__(self, tracer: "Tracer", span: Optional[Span]):
        self._tracer = tracer
        self._span = span

    def __enter__(self) -> Optional[Span]:
        self._prev = self._tracer.current()
        self._tracer._set_current(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        self._tracer._set_current(self._prev)


class Tracer:
    """Thread-local current-span stack + sink fan-out. Sinks receive the
    finished span's event dict; sink exceptions are swallowed (telemetry
    must never take the serving path down)."""

    def __init__(self) -> None:
        self._tls = threading.local()
        self._sinks: list[Callable[[dict], None]] = []
        self._sink_lock = named_lock("tracer.sinks")

    # -- sinks -----------------------------------------------------------

    def add_sink(self, fn: Callable[[dict], None]) -> None:
        with self._sink_lock:
            if fn not in self._sinks:
                self._sinks.append(fn)

    def remove_sink(self, fn: Callable[[dict], None]) -> None:
        with self._sink_lock:
            if fn in self._sinks:
                self._sinks.remove(fn)

    def active(self) -> bool:
        """True when at least one sink would receive finished spans —
        the hot-path guard (scheduler decode ticks, tier restores) that
        keeps span construction off the serving path entirely while
        nothing is listening. Racy by design: a stale read costs one
        span either way, never correctness."""
        return bool(self._sinks)

    def _emit(self, span: Span) -> None:
        with self._sink_lock:
            sinks = list(self._sinks)
        if not sinks:
            return
        event = span.as_event()
        for fn in sinks:
            try:
                fn(event)
            except Exception:             # noqa: BLE001 — telemetry only
                pass

    # -- current-span plumbing ------------------------------------------

    def current(self) -> Optional[Span]:
        return getattr(self._tls, "span", None)

    def _set_current(self, span: Optional[Span]) -> None:
        self._tls.span = span

    def use(self, span: Optional[Span]) -> _UseCtx:
        """Rebind ``span`` as current in THIS thread (cross-thread
        propagation: capture `current()` before the hop, `use()` it
        inside). Restores the previous binding on exit."""
        return _UseCtx(self, span)

    # -- span creation ---------------------------------------------------

    def span(self, name: str, trace_id: Optional[str] = None,
             parent: Optional[Span] = None, bind: bool = True,
             **attrs: Any) -> _SpanCtx:
        """Open a span as a context manager. ``parent`` defaults to the
        thread's current span; ``trace_id`` inherits from the parent.
        ``bind=False`` creates + times the span without making it current
        (for async code on the event loop, where a thread-local binding
        would leak across interleaved tasks)."""
        return _SpanCtx(self, self.start(name, trace_id, parent, **attrs),
                        bind)

    def start(self, name: str, trace_id: Optional[str] = None,
              parent: Optional[Span] = None, **attrs: Any) -> Span:
        """Open an unbound span; the caller must ``finish()`` it."""
        p = parent if parent is not None else self.current()
        tid = trace_id or (p.trace_id if p is not None else None)
        return Span(self, name, tid, p.span_id if p is not None else None,
                    attrs)

    def emit(self, name: str, duration_ms: float,
             trace_id: Optional[str] = None, parent: Optional[Span] = None,
             ts: Optional[float] = None, **attrs: Any) -> None:
        """Retroactive span: a phase whose duration was measured elsewhere
        (e.g. the engine's device-fenced prefill/decode seconds) enters
        the trace after the fact. ``ts`` backdates the span's start so
        timeline assembly (infra/fleetobs.py) orders it where the work
        actually began, not where it was reported."""
        span = self.start(name, trace_id, parent, **attrs)
        span.duration_ms = float(duration_ms)
        if ts is not None:
            span.ts = float(ts)
        self._emit(span)


# ---------------------------------------------------------------------------
# Process-wide defaults + the serving path's named instruments
# ---------------------------------------------------------------------------

METRICS = MetricsRegistry()
TRACER = Tracer()

# Histograms (ms unless noted). Registered at import so GET /metrics
# exposes the full surface before first traffic.
PREFILL_MS = METRICS.histogram(
    "quoracle_prefill_ms", "per-generate prefill device phase (ms)")
DECODE_MS = METRICS.histogram(
    "quoracle_decode_ms", "per-generate decode device phase (ms)")
ROUND_MS = METRICS.histogram(
    "quoracle_round_ms", "one consensus query round: query+parse+validate (ms)")
DECIDE_MS = METRICS.histogram(
    "quoracle_decide_ms", "full ConsensusEngine.decide, refinement included (ms)")
ACTION_MS = METRICS.histogram(
    "quoracle_action_ms", "action executor wall time (ms)")
DECODE_STEP_MS = METRICS.histogram(
    "quoracle_decode_step_ms", "decode phase per emitted token (ms)",
    buckets=tuple(2.0 ** i for i in range(-4, 12)))
ROUNDS_TOTAL = METRICS.counter(
    "quoracle_consensus_rounds_total", "consensus query rounds run")
ACTIONS_TOTAL = METRICS.counter(
    "quoracle_actions_total", "actions executed, labeled by status")
LIVE_AGENTS = METRICS.gauge(
    "quoracle_live_agents", "live agents at last scrape")
KV_FREE_PAGES = METRICS.gauge(
    "quoracle_kv_free_pages", "free KV pool pages per engine at last scrape")

# -- resource observability (ISSUE 3) ---------------------------------------
# HBM accounting gauges are COLLECTOR-refreshed (infra/resources.py sets
# them from jax device.memory_stats() / live_arrays at scrape time).
HBM_USED_BYTES = METRICS.gauge(
    "quoracle_hbm_used_bytes", "device memory in use, per device")
HBM_LIMIT_BYTES = METRICS.gauge(
    "quoracle_hbm_limit_bytes", "device memory capacity, per device")
HBM_HEADROOM_RATIO = METRICS.gauge(
    "quoracle_hbm_headroom_ratio",
    "min over devices of (limit - used) / limit; -1 when no device "
    "reports a limit")
HBM_COMPONENT_BYTES = METRICS.gauge(
    "quoracle_hbm_component_bytes",
    "per-engine HBM attribution: params / kv_pool / prefix_cache bytes")
COMPILE_HITS = METRICS.counter(
    "quoracle_compile_cache_hits_total",
    "generate() dispatches whose (model, shape-bucket) was already "
    "compiled (models/generate.py CompileRegistry)")
COMPILE_MISSES = METRICS.counter(
    "quoracle_compile_cache_misses_total",
    "first-dispatch (model, shape-bucket) compiles")
COMPILE_MISSES_IN_WINDOW = METRICS.gauge(
    "quoracle_compile_misses_in_window",
    "compile misses inside the storm window, per model")
COMPILE_STORM = METRICS.gauge(
    "quoracle_compile_storm",
    "1 while a model's compile misses exceed the storm threshold "
    "inside the window (recompile storm), else 0")
SCHED_QUEUE_DEPTH = METRICS.gauge(
    "quoracle_sched_queue_depth",
    "rows waiting for a continuous-batcher slot, per model")
SCHED_SLOTS_BUSY = METRICS.gauge(
    "quoracle_sched_slots_busy",
    "rows live in the shared decode loop, per model")
SCHED_ADMIT_WAIT_MS = METRICS.histogram(
    "quoracle_sched_admit_wait_ms",
    "submit → decode-loop admission wait (ms)")
SCHED_ROWS_TOTAL = METRICS.counter(
    "quoracle_sched_rows_total",
    "continuous-batcher rows by terminal status (retired | failed)")
# -- ragged serving kernel (ISSUE 8) ----------------------------------------
# Padding-waste accounting for the serving hot path: per generate call
# (one continuous-batcher tick), the chunk-token slots the device actually
# processed vs the tick's REAL tokens. The gather programs pad every tick
# to a [batch-bucket × prompt-bucket] rectangle; the ragged kernel
# processes per-row tq-aligned segments — the delta between these two
# counters is exactly what raggedness reclaims.
SCHED_REAL_TOKENS_TOTAL = METRICS.counter(
    "quoracle_sched_real_tokens_total",
    "real chunk tokens submitted across generate ticks, per model")
SCHED_PADDED_TOKENS_TOTAL = METRICS.counter(
    "quoracle_sched_padded_tokens_total",
    "device chunk-token slots processed across generate ticks (real + "
    "padding), per model — [B·T] on the bucketed paths, the flat token "
    "budget on the unified ragged path")
SCHED_LIVE_TOKEN_SLOTS_TOTAL = METRICS.counter(
    "quoracle_sched_live_token_slots_total",
    "of the padded slots, those whose per-token work (norms, projections, "
    "MLP) ran, per model: the dense chunk forward of a tick of 2,048 "
    "slots and more skips the blocks behind its last token; every other "
    "tick computes its whole shape")
SCHED_NUCLEUS_ROWS_TOTAL = METRICS.counter(
    "quoracle_sched_nucleus_rows_total",
    "rows of a batcher tick that ask for a nucleus (temperature > 0 and "
    "top_p < 1), summed over ticks, per model: a tick with none sorts no "
    "vocabulary (models/sampling.py)")
# -- expert layers (ISSUE 27) ------------------------------------------------
# Booked once a tick from the int32 [4] the ragged programs return with
# their outputs (transformer._routed_experts): what the router sent where.
MOE_ASSIGNMENTS_TOTAL = METRICS.counter(
    "quoracle_moe_assignments_total",
    "token-expert assignments the router made (valid tokens × experts per "
    "token, summed over expert layers and steps), per model; held = true "
    "for those to experts this process holds and computes")
MOE_EXPERTS_REACHED_TOTAL = METRICS.counter(
    "quoracle_moe_experts_reached_total",
    "held experts that received at least one token, summed over expert "
    "layers and steps, per model")
MOE_LAYER_STEPS_TOTAL = METRICS.counter(
    "quoracle_moe_layer_steps_total",
    "expert layers run (one per layer per forward step with a valid "
    "token), per model")
MOE_BLOCK_ROWS_TOTAL = METRICS.counter(
    "quoracle_moe_block_rows_total",
    "rows of the grouped experts' kernel (ops/grouped_experts.py: a block "
    "holds ONE expert's assignments, padded up to the tick's block "
    "height), summed over expert layers and steps, per model: kind = "
    "assigned for the rows that hold an assignment to a held expert, run "
    "for the rows of the blocks the kernel ran; assigned / run is how full "
    "its blocks were. Not booked where the loop over blocks serves (the "
    "CPU, the latent models)")
# -- learned sparse attention (ISSUE 31) --------------------------------------
SPARSE_ATTN_PAIRS_TOTAL = METRICS.counter(
    "quoracle_sparse_attn_pairs_total",
    "query-key pairs of a model whose attention selects its keys "
    "(config.IndexerConfig), summed over layers' queries of chunk forwards "
    "and decode steps, per model: kind = visible under the causal mask "
    "(each is scored by the indexer), kind = selected the softmax ran over "
    "(min(visible, topk) a query)")
# -- the decode program's shared walk (ISSUE 32) ------------------------------
ATTN_SHARED_KV_TOKENS_TOTAL = METRICS.counter(
    "quoracle_attn_shared_kv_tokens_total",
    "resident tokens of pages that rows of a decode tick have in common "
    "(ops/paged_attention.shared_walks), a layer, per model: kind = needed "
    "what the rows needed of them (rows x pages x page x steps), kind = "
    "walked what the shared walks brought into VMEM (once a group a step)")
# -- state that is not keys and values (ISSUE 33) -----------------------------
# A model with conv layers holds, beside its K/V pages, one state record a
# page (generate.py ``_ensure_pool``); booked once a tick by the engine.
CONV_STATE_ROWS_TOTAL = METRICS.counter(
    "quoracle_conv_state_rows_total",
    "rows of a tick of a model with conv layers, per model, by where the "
    "row's chunk took its conv state from: source = carried (the "
    "session's own record at its end), adopted (a cached page's record, "
    "the prefix cache's or an earlier boundary of the session's own), "
    "zero (a sequence's start)")
CONV_STATE_REPREFILL_TOKENS_TOTAL = METRICS.counter(
    "quoracle_conv_state_reprefill_tokens_total",
    "prompt tokens whose K/V was resident and matched but which ran "
    "through the chunk forward again because no conv state is held at the "
    "match's end (reuse is rounded down to a page boundary), per model")
# -- records of recurrent state in a pool of their own (ISSUE 47) -------------
# A model with ssm layers holds megabytes of state a session (a matrix a
# head a layer): ONE live record a session, updated in place by decode, and
# snapshots the prefix cache keeps at boundaries it chose (generate.py
# ``_ensure_pool``, ``SessionStore.records``); booked once a tick.
SSM_STATE_ROWS_TOTAL = METRICS.counter(
    "quoracle_ssm_state_rows_total",
    "rows of a tick of a model with ssm layers, per model, by where the "
    "row's chunk took its state from: source = carried (the session's own "
    "live record), adopted (a snapshot the prefix cache holds, copied into "
    "the row's own record by the chunk forward), zero (a sequence's start)")
SSM_STATE_REPREFILL_TOKENS_TOTAL = METRICS.counter(
    "quoracle_ssm_state_reprefill_tokens_total",
    "prompt tokens whose K/V was resident and matched but which ran "
    "through the chunk forward again because no record holds the state at "
    "the match's end (a match is cut back to the deepest boundary that "
    "has a snapshot; a session whose live record lies past the match is "
    "forgotten), per model")
SSM_STATE_RECORDS_TOTAL = METRICS.counter(
    "quoracle_ssm_state_records_total",
    "records of the ssm state pool, per model, by kind: snapshot (a state "
    "written at a page boundary and handed to the prefix cache), copy (a "
    "snapshot read into an adopting row's own record), evicted (a "
    "snapshot or a session's live record given up under pressure)")
SSM_STATE_RECORDS_HELD = METRICS.gauge(
    "quoracle_ssm_state_records_held",
    "records of the ssm state pool in use, per model and holder (session "
    "| snapshot | total | pool: the pool's size)")
# -- retention groups of attention layers (ISSUE 39) -------------------------
# A model whose window and full attention layers are mixed holds a session's
# pages in two groups of pools (config.kv_groups; generate.py ``_run_paged``):
# the full group keeps every token, the window group what a window reaches.
KV_GROUP_PAGES_TOTAL = METRICS.counter(
    "quoracle_kv_group_pages_total",
    "pages of a model with a window group of attention layers, per model "
    "and group (full | window), by event: allocated (fresh pages a storing "
    "row took for a tick), adopted (pages of a cached prefix a new session "
    "took by reference: the full group's whole, the window group's for the "
    "last window), released_behind_window (window-group pages a session "
    "let go at store-back because no position it can still query reaches "
    "them)")
KV_SESSION_HELD_TOKENS_TOTAL = METRICS.counter(
    "quoracle_kv_session_held_tokens_total",
    "tokens a session holds after a store-back, summed over store-backs, "
    "per model and group (full | window): window over full is the share of "
    "its length a session still holds in the window group (near 1: nothing "
    "is released)")
# -- the batcher's tick record (ISSUE 24) -----------------------------------
# One record per ContinuousBatcher._loop iteration, built on the worker
# thread where the work happens (models/scheduler.py, models/generate.py).
# The phases tile the iteration: opening one closes the one before, so a
# tick's phases sum to its wall by construction. Each phase is also a
# ``jax.profiler.TraceAnnotation`` (a TraceMe: about half a microsecond
# with no profiler session open), so a profiler trace holds ``qtpu.tick``
# and ``qtpu.tick.<phase>`` on the worker thread's line, on the device's
# clock. The record feeds the phase counter below, the rows' WaitClocks
# (scheduler._book_step_waits) and the sampled ``sched.decode_tick`` span.
TICK_PHASES: tuple = (
    "admit",              # _admit: policy pop, deadline drops, queue-wait spans
    "prepare",            # splice, session lookup, page alloc, prefix match,
                          # tier restore (generate → _run_paged)
    "pack",               # _run_unified: the numpy layout of the flat tick
    "dispatch_prefill",   # host→device transfers + enqueue of the chunk program
    "wait_prefill",       # block_until_ready(last_logits): the prefill fence
    "dispatch_decode",    # enqueue of the decode program
    "wait_decode",        # fetch of its outputs, block_until_ready(pool)
    "commit",             # session put, prefix insert, telemetry, chip ledger
    "retire",             # per-row bookkeeping, _finish_row for rows that ended
    "idle",               # _wake.wait: nothing live
)
TICK_PHASE_MS_TOTAL = METRICS.counter(
    "quoracle_tick_phase_ms_total",
    "continuous-batcher worker time by tick phase (ms), per model: the "
    "wait_* phases are the device, idle is an empty loop, the rest is "
    "host work between device programs")
_TICK_NAMES = {p: "qtpu.tick." + p for p in TICK_PHASES}
# Named operations INSIDE the phases (ISSUE 37): a phase is a lump (the
# worker's whole path from the splice to the flat layout is ``prepare``),
# and the chip idles under single operations of it. ``tick_op(name)`` times
# one: a child annotation ``qtpu.op.<name>`` — NOT ``qtpu.tick.<…>``, which
# every reader takes for a phase — and integer ns of SELF time on the
# record (an operation opened inside another is taken out of it), so a
# phase's own time is its span less the operations inside it. The phases,
# their tiling and their counter stay as they were. Classes: ``schedule``
# the batcher's and the layout's own work, ``session`` the session store
# and the prefix cache, ``transfer`` what crosses to the device,
# ``observe`` what the instruments cost on the worker's path.
TICK_OPS: tuple = (
    # -- schedule
    "splice",           # _plain_step: r.prompt + r.emitted, budgets, arguments
    "wave_split",       # generate._prefix_wave_split
    "layout",           # _generate_impl's row arrays, _run_unified's flat tick
    "tiles",            # ragged_tiles: the blocks grouped for the kernel walk
    "shared_walks",     # shared_walks: decode rows whose leading pages agree
    "results",          # _generate_impl: ids out of the fetched array, decode
    "retire_rows",      # _plain_step after the engine call, _finish_row
    # -- session
    "lock",             # the wait for _paged_lock (generate, verify_chunk)
    "session_lookup",   # sessions.get, the common prefix with the held tokens
    "tier_restore",     # tier.restore_session: a hibernated session paged in
    "prefix_match",     # sessions.match_prefix: the radix match and adoption
    "page_alloc",       # _run_paged's allocation: alloc, eviction, COW (and a
                        # window group's pages for the tick: _window_row)
    "state_adopt",      # a conv model's tables: the record a row starts from,
                        # each token's predecessors, the records to write
    "session_put",      # the stored tokens, put_raw, page release (and a
                        # window group's pages behind the window let go)
    "prefix_insert",    # sessions.insert_prefix: the radix insert
    # -- transfer
    "rng",              # next_rng: the split and its unpacking (2 programs)
    "h2d",              # the jnp.asarray arguments of a program
    "enqueue",          # the jitted call itself
    "device",           # wait_*: until the program's first output is there
                        # (wait_decode: on the host, the first np.asarray)
    "fetch",            # the other np.asarray copies behind it
    # -- observe
    "observe",          # calls into an observe-only plane: introspect, costobs
                        # row keys, _book_step_waits, chaos, the sampled span
    "account",          # what the instruments compute on the hot path: padding
                        # chip ledger, telemetry, attention/expert/state counts
)
TICK_OP_MS_TOTAL = METRICS.counter(
    "quoracle_tick_op_ms_total",
    "continuous-batcher worker time by named operation inside the tick's "
    "phases (ms of self time), per model (infra/telemetry.TICK_OPS): a "
    "phase's own time is quoracle_tick_phase_ms_total less the operations "
    "opened inside it")
_OP_NAMES = {o: "qtpu.op." + o for o in TICK_OPS}
# -- the session drop, from inside (ISSUE 37) --------------------------------
SESSION_DROP_WAIT_MS = METRICS.histogram(
    "quoracle_session_drop_wait_ms",
    "engine.drop_session's wait for the engine's paged lock (ms), per "
    "model: a sessioned tick holds the lock from end to end, so a caller "
    "that drops a session waits out the tick under way")


_TRACE_ANNOTATION: Any = None


def _annotation(name: str):
    """An entered TraceAnnotation (jax imported at first use: the mock
    backend's processes never open a tick)."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _TRACE_ANNOTATION = TraceAnnotation
    ann = _TRACE_ANNOTATION(name)
    ann.__enter__()
    return ann


class TickRecord:
    """One batcher loop iteration: integer-ns time per phase and per named
    operation inside the phases (``op_ns``, self time: ``tick_op``), the
    annotation arguments a trace reader needs (``model``, ``rows``,
    ``admitted``, ``nucleus_rows``, ``real_tokens``, ``padded_tokens``,
    ``decode_steps``, ``program``, ``context_tokens``, an expert model's
    ``moe_*`` counts), and the prefill fence of the tick (``fence_ns``:
    the instant ``wait_prefill`` last ended — a row's first-token
    stamp)."""

    __slots__ = ("t0_ns", "t1_ns", "phase_ns", "op_ns", "args", "fence_ns",
                 "_phase", "_t_phase", "_ann", "_tick_ann", "_op")

    def __init__(self, model: str):
        self.args: dict = {"model": model}
        self.phase_ns = dict.fromkeys(TICK_PHASES, 0)
        self.op_ns: dict = {}             # operation -> ns of self time
        self._op: Optional[_TickOp] = None    # the innermost one open
        self.fence_ns = 0
        self.t1_ns = 0
        self._tick_ann = _annotation("qtpu.tick")
        self._phase = TICK_PHASES[0]      # an iteration begins by admitting
        self._ann = _annotation(_TICK_NAMES[self._phase])
        self.t0_ns = self._t_phase = time.monotonic_ns()

    def _end_phase(self) -> int:
        self._ann.__exit__(None, None, None)
        now = time.monotonic_ns()
        self.phase_ns[self._phase] += now - self._t_phase
        if self._phase == "wait_prefill":
            self.fence_ns = now
        return now

    def phase(self, name: str) -> None:
        if name == self._phase:           # already there: one span, not two
            return
        now = self._end_phase()
        self._ann = _annotation(_TICK_NAMES[name])
        self._phase, self._t_phase = name, now

    def snapshot(self) -> dict:
        """phase -> ns so far, the open phase counted up to now: two
        snapshots differ by exactly the wall between them."""
        out = dict(self.phase_ns)
        out[self._phase] += time.monotonic_ns() - self._t_phase
        return out

    def close(self) -> None:
        self.t1_ns = self._end_phase()
        # TraceMe arguments are "k=v,k=v": a value holds no comma
        self._tick_ann.set_metadata(**self.args)
        self._tick_ann.__exit__(None, None, None)
        model = self.args["model"]
        for name, ns in self.phase_ns.items():
            if ns:
                TICK_PHASE_MS_TOTAL.inc(ns / 1e6, model=model, phase=name)
        for name, ns in self.op_ns.items():
            TICK_OP_MS_TOTAL.inc(ns / 1e6, model=model, op=name)

    def as_attrs(self) -> dict:
        """The record as span attributes (``sched.decode_tick``)."""
        return {**self.args, "wall_ns": self.t1_ns - self.t0_ns,
                "phases_ns": {k: v for k, v in self.phase_ns.items() if v},
                "ops_ns": dict(self.op_ns)}


class _TickOp:
    """One named operation of a tick, open: ``qtpu.op.<name>`` on the
    worker's line and, at its end, its SELF time on the record."""

    __slots__ = ("rec", "name", "outer", "inner_ns", "ann", "t0")

    def __init__(self, rec: TickRecord, name: str):
        self.rec = rec
        self.name = name

    def __enter__(self) -> None:
        rec = self.rec
        self.outer = rec._op
        rec._op = self
        self.inner_ns = 0
        self.ann = _annotation(_OP_NAMES[self.name])
        self.t0 = time.monotonic_ns()

    def __exit__(self, *exc) -> None:
        ns = time.monotonic_ns() - self.t0
        self.ann.__exit__(None, None, None)
        rec, outer, name = self.rec, self.outer, self.name
        rec.op_ns[name] = rec.op_ns.get(name, 0) + ns - self.inner_ns
        rec._op = outer
        if outer is not None:
            outer.inner_ns += ns


_NO_OP = contextlib.nullcontext()    # ``tick_op`` with no open tick


class _TickLocal(threading.local):
    record: Optional[TickRecord] = None


_TICK = _TickLocal()


def tick_open(model: str) -> TickRecord:
    """Start this thread's tick record, in phase ``admit`` (the batcher
    worker, once per loop iteration)."""
    _TICK.record = rec = TickRecord(model)
    return rec


def tick_phase(name: str) -> Optional[TickRecord]:
    """THE phase helper: on a thread with an open tick record, close the
    phase under way and open ``name`` (one of TICK_PHASES); returns the
    record. Elsewhere — the engine driven directly — one thread-local
    read and nothing else."""
    rec = _TICK.record
    if rec is not None:
        rec.phase(name)
    return rec


def tick_op(name: str):
    """THE operation helper: ``with tick_op(name):`` around one named
    operation (one of TICK_OPS) inside the phase under way. On a thread
    with no open tick — the engine driven directly, a client that drops a
    session — one thread-local read and nothing else."""
    rec = _TICK.record
    return _NO_OP if rec is None else _TickOp(rec, name)


def tick_note(**args: Any) -> None:
    """Arguments of this thread's open tick, known only where the work
    happens (the engine's token counts and program key)."""
    rec = _TICK.record
    if rec is not None:
        rec.args.update(args)


def tick_close() -> Optional[TickRecord]:
    rec, _TICK.record = _TICK.record, None
    if rec is not None:
        rec.close()
    return rec


WATCHDOG_STALLS = METRICS.counter(
    "quoracle_watchdog_stalls_total",
    "stall-watchdog trips (decode loop made no progress past deadline)")
WATCHDOG_STALLED = METRICS.gauge(
    "quoracle_watchdog_stalled",
    "1 while a watched source is tripped, per source")
PREFIX_CACHE_PAGES = METRICS.gauge(
    "quoracle_prefix_cache_pages",
    "radix prefix-cache occupancy per model: kind = resident | "
    "referenced | evictable")

# -- serving QoS (ISSUE 4) ---------------------------------------------------
# Admission control + weighted-fair scheduling (quoracle_tpu/serving/):
# every admit/shed decision and the per-class queue/latency state.
QOS_ADMITTED_TOTAL = METRICS.counter(
    "quoracle_qos_admitted_total",
    "requests admitted past QoS admission control, by class and tenant")
QOS_SHED_TOTAL = METRICS.counter(
    "quoracle_qos_shed_total",
    "requests shed by QoS admission control, by class/tenant/reason "
    "(rate_limit | overload | deadline)")
QOS_ADMIT_WAIT_MS = METRICS.histogram(
    "quoracle_qos_admit_wait_ms",
    "submit → decode-loop admission wait per QoS class (ms)")
QOS_QUEUE_DEPTH = METRICS.gauge(
    "quoracle_qos_queue_depth",
    "rows waiting in the weighted-fair queue, per class and model")
QOS_CLASS_TAIL_MS = METRICS.gauge(
    "quoracle_qos_class_tail_ms",
    "EWMA latency-tail estimate per QoS class (serving/slo.py)")
QOS_WEIGHT_MULTIPLIER = METRICS.gauge(
    "quoracle_qos_weight_multiplier",
    "SLO-driven DRR weight multiplier per class (1.0 = undemoted)")
QOS_DEMOTIONS_TOTAL = METRICS.counter(
    "quoracle_qos_demotions_total",
    "bulk-class weight demotions while the INTERACTIVE tail is over "
    "its SLO target")

# -- speculative serving (ISSUE 6) -------------------------------------------
# Batched draft/verify decoding in the continuous serving path
# (models/speculative.py BatchedSpeculator): per-member acceptance,
# realized tokens-per-round, adaptive-K state, and fallback attribution —
# the scorecard inputs for /api/models and the /telemetry view.
SPEC_ROUNDS = METRICS.counter(
    "quoracle_spec_rounds_total",
    "speculative draft/verify rounds executed, per model")
SPEC_DRAFTED = METRICS.counter(
    "quoracle_spec_drafted_tokens_total",
    "draft tokens proposed across all rounds, per model")
SPEC_ACCEPTED = METRICS.counter(
    "quoracle_spec_accepted_tokens_total",
    "draft tokens accepted by the target verify, per model")
SPEC_ACCEPTANCE = METRICS.histogram(
    "quoracle_spec_acceptance",
    "per-round acceptance rate (accepted / drafted), per model",
    buckets=(0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 0.95, 1.0))
SPEC_TOKENS_PER_ROUND = METRICS.histogram(
    "quoracle_spec_tokens_per_round",
    "tokens committed per speculative round per row (accepted + "
    "correction), per model",
    buckets=(1, 2, 3, 4, 5, 6, 8, 10, 12, 16))
SPEC_K = METRICS.gauge(
    "quoracle_spec_k",
    "current adaptive draft length K, per model")
SPEC_ENGAGED = METRICS.gauge(
    "quoracle_spec_engaged",
    "1 while the member's speculator is engaged, 0 while it has "
    "disengaged to vanilla decode (acceptance collapse)")
SPEC_FALLBACK_TOTAL = METRICS.counter(
    "quoracle_spec_fallback_total",
    "decode ticks a row fell back to vanilla, per model and reason "
    "(disengaged | sampling | window | draft_error | verify_error)")

# -- tiered KV (ISSUE 7) -----------------------------------------------------
# Host offload + session hibernation + the disk prefix store
# (serving/kvtier.py TierManager): tier occupancy, demote/restore flow,
# and restore latency — the observability contract of the capacity layer.
KV_TIER_BYTES = METRICS.gauge(
    "quoracle_kv_tier_bytes",
    "KV bytes resident per tier (hbm | host | disk), per model — "
    "collector-refreshed (infra/resources.py)")
KV_TIER_ENTRIES = METRICS.gauge(
    "quoracle_kv_tier_entries",
    "entries per tier and kind (session | prefix), per model")
KV_DEMOTES_TOTAL = METRICS.counter(
    "quoracle_kv_demotes_total",
    "HBM→host demotions by kind (session | prefix), per model — "
    "eviction that preserved state instead of destroying it")
KV_RESTORES_TOTAL = METRICS.counter(
    "quoracle_kv_restores_total",
    "host/disk→HBM restores by kind and source, per model — touches "
    "served by page-in instead of re-prefill")
KV_RESTORE_MS = METRICS.histogram(
    "quoracle_kv_restore_ms",
    "page-in latency per restore (ms), by kind — compare against "
    "quoracle_prefill_ms for the hibernation win")
KV_DISK_SPILLS_TOTAL = METRICS.counter(
    "quoracle_kv_disk_spills_total",
    "prefix blocks written to the checksummed disk store, per model")
KV_DISK_LOADS_TOTAL = METRICS.counter(
    "quoracle_kv_disk_loads_total",
    "disk prefix loads by status (ok | corrupt), per model — corrupt "
    "entries are skipped and unlinked, never served")
KV_HOST_EVICTIONS_TOTAL = METRICS.counter(
    "quoracle_kv_host_evictions_total",
    "host-tier LRU evictions by kind (session | prefix), per model")
KV_ALLOC_DRIFT_TOTAL = METRICS.counter(
    "quoracle_kv_alloc_drift_total",
    "SessionStore.alloc accounting-drift refusals (the formerly silent "
    "defensive branch), per model — any nonzero value is a bug report")

# -- quantized serving (ISSUE 13) --------------------------------------------
# Int8 weights + int8 KV pages (models/quant.py): the byte-economy
# instruments — bytes each tier move avoided shipping, the per-token KV
# rate capacity planning actually gets, and the dequant-path program
# identity — so a quantized member's 2x capacity claim is auditable
# from /metrics.
QUANT_BYTES_SAVED_TOTAL = METRICS.counter(
    "quoracle_quant_bytes_saved_total",
    "bytes NOT held or shipped because a member serves int8, by tier "
    "(weights | demote | disk_spill | handoff), per model — each event "
    "counts the bf16-equivalent minus the actual int8+scales bytes")
QUANT_KV_BYTES_PER_TOKEN = METRICS.gauge(
    "quoracle_quant_kv_bytes_per_token",
    "pool bytes per resident KV token (int8 payload + per-(token, "
    "kv-head) fp32 scales) for quantized members — compare against "
    "2·L·KV·hd·2 for the bf16 rate the member would otherwise pay")
QUANT_DEQUANT_COMPILES_TOTAL = METRICS.counter(
    "quoracle_quant_dequant_compiles_total",
    "compile-ledger misses booked by quantized-KV engines, per model — "
    "the dequant path's program identities; a storm here is the same "
    "capacity incident as quoracle_compile_cache_misses_total")

# -- disaggregated serving plane (ISSUE 10) ----------------------------------
# Cluster/router/handoff instruments (serving/cluster.py, router.py,
# handoff.py): replica topology, placement flow, and the prefill→decode
# KV handoff — the observability contract of the multi-replica layer.
CLUSTER_REPLICAS = METRICS.gauge(
    "quoracle_cluster_replicas",
    "replicas registered in the cluster plane, by role "
    "(prefill | decode | unified) and liveness (alive | dead)")
CLUSTER_REQUESTS_TOTAL = METRICS.counter(
    "quoracle_cluster_requests_total",
    "requests the cluster plane served, by replica and path "
    "(disagg | affinity | unified | image | failover)")
CLUSTER_HANDOFFS_TOTAL = METRICS.counter(
    "quoracle_cluster_handoffs_total",
    "prefill→decode KV handoffs by status (ok | export_failed | "
    "signature_mismatch | replaced | replace_failed), per model")
CLUSTER_HANDOFF_MS = METRICS.histogram(
    "quoracle_cluster_handoff_ms",
    "KV handoff latency (ms): prefill-side hibernate through decode-side "
    "adopt — compare against quoracle_prefill_ms for the re-prefill it "
    "replaces")
ROUTER_PLACEMENTS_TOTAL = METRICS.counter(
    "quoracle_router_placements_total",
    "router placement decisions, by role and reason "
    "(affinity | least_loaded | only | failover)")
ROUTER_SHED_TOTAL = METRICS.counter(
    "quoracle_router_shed_total",
    "submissions shed at the cluster front door because every eligible "
    "replica's admission controller rejected them, by class and tenant")
ROUTER_SIGNAL_AGE_MS = METRICS.histogram(
    "quoracle_router_signal_age_ms",
    "age of the per-replica admission signal snapshot at placement time "
    "(ms) — large values mean the router is steering on stale load data")

# -- cluster fabric (ISSUE 12) -----------------------------------------------
# Wire-layer instruments (serving/fabric/): every cross-host exchange —
# handoffs, placements, prefix fetches — is one framed request/response,
# so the fabric's health is legible as request/retry/reject series plus
# an RTT histogram per operation.
FABRIC_REQUESTS_TOTAL = METRICS.counter(
    "quoracle_fabric_requests_total",
    "fabric wire requests by op (serve | prefill | decode | signals | "
    "admit | prefix_get | prefix_put | hello | stats | ...) and status "
    "(ok | error | unreachable)")
FABRIC_RTT_MS = METRICS.histogram(
    "quoracle_fabric_rtt_ms",
    "round-trip latency (ms) of one fabric request by op — includes "
    "retries/backoff, so a flapping link widens this tail before it "
    "trips unreachable")
FABRIC_RETRIES_TOTAL = METRICS.counter(
    "quoracle_fabric_retries_total",
    "fabric request retry attempts by op — a rising rate means a lossy "
    "or flapping peer link the bounded backoff is still absorbing")
FABRIC_FRAME_REJECTS_TOTAL = METRICS.counter(
    "quoracle_fabric_frame_rejects_total",
    "wire frames rejected at the codec boundary, by reason (crc | "
    "truncated | magic | version | oversize) — corruption and version "
    "skew are rejected structurally, never adopted")
FABRIC_BYTES_TOTAL = METRICS.counter(
    "quoracle_fabric_bytes_total",
    "bytes moved over fabric TCP transports, by direction "
    "(sent | received)")
FABRIC_PEERS = METRICS.gauge(
    "quoracle_fabric_peers",
    "remote peers registered at the fabric front door, by role "
    "(prefill | decode | unified) and liveness (alive | dead)")
FABRIC_PREFIXD_TOTAL = METRICS.counter(
    "quoracle_fabric_prefixd_total",
    "fleet prefix-service client operations, by op (get | put) and "
    "status (hit | miss | stored | dup | error) — the error rate is "
    "the prefixd-unavailable alert input")

# -- chaos plane (ISSUE 11) --------------------------------------------------
# Deterministic fault injection (chaos/faults.py) + the scenario harness
# (chaos/scenarios.py): every fired fault and every machine-checked
# invariant verdict is a first-class series, so a game-day run is
# attributable from /metrics alone.
CHAOS_ARMED = METRICS.gauge(
    "quoracle_chaos_armed",
    "1 while a FaultPlan is armed on the process-wide chaos plane — "
    "production should alert on this outside announced game-day windows")
CHAOS_FAULTS_TOTAL = METRICS.counter(
    "quoracle_chaos_faults_total",
    "faults fired by the chaos plane, by injection point and kind "
    "(crash | slow | garbage | drop | delay | corrupt | poison | fail | "
    "demote)")
CHAOS_SCENARIOS_TOTAL = METRICS.counter(
    "quoracle_chaos_scenarios_total",
    "chaos scenario runs by scenario name and result (pass | fail)")
CHAOS_INVARIANT_FAILURES = METRICS.counter(
    "quoracle_chaos_invariant_failures_total",
    "invariant checks that FAILED during a chaos scenario, by scenario "
    "and invariant name — any nonzero value is a recovery-path bug "
    "report, alert like a crash")

# -- elastic fleet controller (ISSUE 14) -------------------------------------
# Signal-driven autoscaling + role re-tiering + live session migration
# (serving/fleet.py): every policy action, every migrated session, and
# the drain latency are first-class series — a scale event must be as
# attributable from /metrics as a shed or a handoff.
FLEET_ACTIONS_TOTAL = METRICS.counter(
    "quoracle_fleet_actions_total",
    "fleet-controller policy actions executed, by action (scale_up | "
    "scale_down | retier | drain) and target role — the action ledger's "
    "counter twin; a flapping rate here means the hysteresis bounds are "
    "too tight for the traffic")
FLEET_TICKS_TOTAL = METRICS.counter(
    "quoracle_fleet_ticks_total",
    "fleet-controller policy ticks evaluated, by outcome (action | "
    "hold | cooldown) — the denominator that turns the action counter "
    "into a flap rate")
FLEET_SESSIONS_MIGRATED_TOTAL = METRICS.counter(
    "quoracle_fleet_sessions_migrated_total",
    "sessions live-migrated off a draining replica through the handoff "
    "path, by model and status (ok | failed) — failed means the session "
    "degraded to a re-prefill on its next touch, never wrong bits")
FLEET_DRAIN_MS = METRICS.histogram(
    "quoracle_fleet_drain_ms",
    "wall time (ms) of one replica drain: settle-wait through the last "
    "session's migration — the zero-downtime retirement budget")
FLEET_DRAINING = METRICS.gauge(
    "quoracle_fleet_draining",
    "replicas currently draining (new placements excluded, affinities "
    "still serving until each session's migration lands)")

# -- fleet observability (ISSUE 15) ------------------------------------------
# Cross-process tracing + metrics federation + correlated incident
# capture (infra/fleetobs.py): span-ring health, the front door's
# peer-scrape loop, and the incident ledger — the observability OF the
# observability layer, so a starved trace ring or a stale federation
# window is itself alertable.
TRACE_DROPPED_TOTAL = METRICS.counter(
    "quoracle_trace_dropped_total",
    "finished spans dropped on span-ring overflow, per ring "
    "(fleetobs | history) — the ring overwrites oldest-first; a "
    "sustained rate means serving traffic is starving consensus traces "
    "and the ring size / decode-tick sample knobs need retuning")
FLEETOBS_SCRAPE_MS = METRICS.histogram(
    "quoracle_fleetobs_scrape_ms",
    "wall time (ms) of one fleet metrics-federation sweep: every "
    "peer's MSG_OBS metrics state pulled + merged at the front door")
FLEETOBS_PEERS = METRICS.gauge(
    "quoracle_fleetobs_peers",
    "peers in the last federation sweep, by status (ok | failed) — a "
    "failed peer's series go stale in the rollup until it answers")
FLEETOBS_STALENESS_S = METRICS.gauge(
    "quoracle_fleetobs_staleness_s",
    "age of the last successful federation sweep at scrape time — the "
    "federation-staleness alert input (DEPLOY §16)")
FLEETOBS_SLO_BURN = METRICS.gauge(
    "quoracle_fleetobs_slo_burn",
    "max INTERACTIVE SLO-burn ratio reported by any peer in the last "
    "federation sweep — the fleet-wide worst-tail gauge")
FLEETOBS_GOODPUT = METRICS.gauge(
    "quoracle_fleetobs_goodput_tokens_per_s",
    "fleet-wide goodput (real chunk tokens/s summed over peers) "
    "computed from consecutive federation sweeps' counter deltas")
INCIDENTS_TOTAL = METRICS.counter(
    "quoracle_incidents_total",
    "correlated incidents opened, by kind (watchdog | replica_dead | "
    "chaos_invariant | manual) — each one is a retention-pruned bundle "
    "of every reachable peer's flight-ring dump under one incident id")

# -- fleet simulator (ISSUE 16) ----------------------------------------------
# Deterministic workload simulator (quoracle_tpu/sim/): per-replay
# traffic/outcome counters and the modeled-fleet gauges the /telemetry
# sim panel and GET /api/sim read. Instruments carry MODELED quantities
# (virtual-clock TTFT, virtual goodput) — they share the registry so
# one scrape shows real and simulated planes side by side, but nothing
# here is a chip measurement.
SIM_EVENTS_TOTAL = METRICS.counter(
    "quoracle_sim_events_total",
    "trace events replayed, by workload stream and modeled outcome "
    "(ok | shed | deadline) — flushed once per replay, not per event")
SIM_REPLAYS_TOTAL = METRICS.counter(
    "quoracle_sim_replays_total",
    "completed trace replays, by mode (compressed | paced) and result")
SIM_TTFT_MS = METRICS.histogram(
    "quoracle_sim_ttft_ms",
    "modeled time-to-first-token (virtual ms: queue wait + tier "
    "restore + prefill) for admitted events, by class — sampled every "
    "16th event on large traces",
    buckets=(1, 5, 20, 50, 100, 250, 500, 1_000, 1_500, 3_000, 6_000,
             15_000))
SIM_GOODPUT = METRICS.gauge(
    "quoracle_sim_goodput_tokens_per_s",
    "delivered tokens per VIRTUAL second over the last replayed trace")
SIM_SESSIONS = METRICS.gauge(
    "quoracle_sim_sessions",
    "virtual sessions by final ladder tier (resident | host | disk | "
    "prefixd | dropped) after the last replay — the conservation "
    "census the sim gate checks")
SIM_GATE_FAILURES = METRICS.counter(
    "quoracle_sim_gate_failures_total",
    "sim scenarios that failed at least one workload invariant, by "
    "scenario — the acceptance gate's alarm counter")

# -- chip economics (ISSUE 17) -----------------------------------------------
# Chip-economics plane (infra/costobs.py): per-stage chip-second
# attribution, roofline/MFU per compiled program, per-decide cost
# rollups, and tenant error budgets. Everything here is READ-ONLY
# measurement — the attribution invariant (stage chip-seconds sum to
# engine busy wall, exactly) and the temp-0 on/off bit-equality gate
# both depend on these series never touching the serving path.
COST_CHIP_MS_TOTAL = METRICS.counter(
    "quoracle_cost_chip_ms_total",
    "device wall (ms, float) charged by the ChipLedger, by model, "
    "stage (prefill | decode | verify | restore) and tenant class — "
    "tenant='overhead' rows are padding/ragged waste; the sum over all "
    "labels equals the engine's measured busy wall by construction")
COST_DECIDE_CHIP_MS = METRICS.histogram(
    "quoracle_cost_decide_chip_ms",
    "measured chip-ms one consensus decide consumed across all member "
    "generates and verify chunks — the denominator of the adaptive-"
    "consensus roadmap item's tokens-per-chip objective")
COST_DECIDE_TOKENS = METRICS.histogram(
    "quoracle_cost_decide_tokens",
    "completion tokens one consensus decide consumed across all pool "
    "members and rounds (tokens-per-decide, the adaptive-consensus "
    "baseline)",
    buckets=(8, 16, 32, 64, 128, 256, 512, 1_024, 2_048, 4_096,
             8_192, 16_384))
COST_GOODPUT_PER_CHIP = METRICS.gauge(
    "quoracle_cost_goodput_per_chip_s",
    "fleet-wide real chunk tokens per CHIP-SECOND, computed at the "
    "front door from consecutive federation sweeps' token and chip-ms "
    "counter deltas — the elastic fleet's cost objective input")
MFU_RATIO = METRICS.histogram(
    "quoracle_mfu_ratio",
    "model FLOPs utilization per charged step: analytic FLOPs of the "
    "ragged kernel/matmuls (geometry x real tokens, int8-aware) over "
    "measured step wall x device peak, by model, stage and padded "
    "token bucket — a cliff at a fixed bucket means a recompile or "
    "padding regression",
    buckets=(0.005, 0.01, 0.02, 0.04, 0.08, 0.15, 0.25, 0.4, 0.6, 0.8,
             1.0))
MFU_HBM_BOUND = METRICS.gauge(
    "quoracle_mfu_hbm_bound",
    "1 while the roofline model says the program's last observation "
    "was HBM-bandwidth-bound (bytes/peak_bw > flops/peak_flops), per "
    "model and stage — decode at small batch should sit at 1")
MFU_CLIFFS_TOTAL = METRICS.counter(
    "quoracle_mfu_cliffs_total",
    "MFU-cliff crossings per model, stage and padded token bucket — "
    "an observation fell below half the program's running best; the "
    "mfu_cliff flight event's counter twin and the DEPLOY §18 alert "
    "input (a recompile or padding regression eating chip-seconds)")
BUDGET_BURN_RATE = METRICS.gauge(
    "quoracle_budget_burn_rate",
    "error-budget burn rate per tenant class and window (1h | 6h): "
    "observed error fraction over the window divided by the class SLO "
    "error allowance — 1.0 burns the whole budget in exactly one "
    "window; the multi-window alert input (DEPLOY §18)")
BUDGET_REMAINING_RATIO = METRICS.gauge(
    "quoracle_budget_remaining_ratio",
    "fraction of the tenant class's 6h error budget still unburned "
    "(1.0 = untouched, 0 = exhausted) — floor-clamped at 0")
BUDGET_EVENTS_TOTAL = METRICS.counter(
    "quoracle_budget_events_total",
    "requests scored against a tenant-class error budget, by class "
    "and outcome (ok | error) — errors are sheds, deadline drops and "
    "SLO misses; the budget denominator")

# -- liveness & hotspot plane (ISSUE 18) -------------------------------------
# Introspection plane (infra/introspect.py): progress-heartbeat stall
# detection, sampled wall-clock profiling, and per-row wait-state
# decomposition. Read-only measurement like the chip-economics series
# above — temp-0 on/off bit-equality depends on none of these touching
# a serving decision.
INTROSPECT_STALLS_TOTAL = METRICS.counter(
    "quoracle_introspect_stalls_total",
    "stall-detector trips per progress source — an ACTIVE source whose "
    "heartbeat froze for two intervals; each trip ships an all-thread "
    "stack + lock-holder incident bundle (DEPLOY §19 StallDetected)")
INTROSPECT_PROFILE_SAMPLES = METRICS.counter(
    "quoracle_introspect_profile_samples_total",
    "wall-clock profiler sampling ticks folded into collapsed-stack "
    "windows — the /api/profile hotspot denominator")
INTROSPECT_OVERHEAD_RATIO = METRICS.gauge(
    "quoracle_introspect_profiler_overhead_ratio",
    "observed fraction of process wall the frame sampler itself "
    "consumed since start — self-measured; alert above 1 percent at the "
    "default rate (DEPLOY §19 ProfilerOverhead)")
INTROSPECT_WAIT_MS = METRICS.histogram(
    "quoracle_introspect_wait_ms",
    "per-row wait-state decomposition by state (admission | queue | "
    "dispatch | kv_restore | wire | lock | other) and model — the "
    "named waits plus the exact integer-ns remainder bucket sum to "
    "each row's observed wall by construction",
    buckets=(0.1, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1_000,
             2_500, 5_000, 10_000))
INTROSPECT_WAIT_SKEW_TOTAL = METRICS.counter(
    "quoracle_introspect_wait_skew_total",
    "rows whose measured sub-waits overran the observed wall (clock "
    "skew / overlapping measurements) and were deterministically "
    "trimmed to preserve the sum-to-wall invariant — a steady rate "
    "means an instrumentation bug (DEPLOY §19 WaitStateSkew)")

# -- serving flywheel (ISSUE 19) ---------------------------------------------
# Training plane (quoracle_tpu/training/): replay capture store,
# draft-distillation trainer, and the bench-gated promotion pipeline.
# The capture series is read-only measurement like the two planes above
# — temp-0 on/off bit-equality depends on capture never touching a
# serving decision (QUORACLE_TRAIN_CAPTURE=0 kills the whole plane).
TRAIN_CAPTURE_RECORDS_TOTAL = METRICS.counter(
    "quoracle_train_capture_records_total",
    "capture-plane record dispositions by source (spec | consensus) "
    "and status (ok | sampled_out | dropped) — dropped counts faults "
    "and errors the serving path absorbed without blocking")
TRAIN_CAPTURE_BYTES = METRICS.gauge(
    "quoracle_train_capture_bytes",
    "sealed on-disk bytes in the replay capture store — maintained "
    "incrementally (O(1), no per-scrape directory walk) and bounded "
    "by --capture-mb (DEPLOY §20 CaptureStoreFull)")
TRAIN_CAPTURE_EVICTIONS_TOTAL = METRICS.counter(
    "quoracle_train_capture_evictions_total",
    "oldest capture segments unlinked to hold the size budget — a "
    "steady rate means the budget is smaller than the retention the "
    "trainer needs (DEPLOY §20 CaptureStoreFull)")
TRAIN_STEPS_TOTAL = METRICS.counter(
    "quoracle_train_steps_total",
    "optimizer steps taken by the pjit distillation trainer, by model")
TRAIN_LOSS = METRICS.gauge(
    "quoracle_train_loss",
    "last observed distillation loss (weighted CE against recorded "
    "target tokens), by model")
TRAIN_EVAL_ACCEPTANCE = METRICS.gauge(
    "quoracle_train_eval_acceptance",
    "offline replay acceptance through the real verify_chunk path, by "
    "model, role (candidate | incumbent) and stat (p50 | p95 | mean) — "
    "the promotion gate's evidence")
TRAIN_PROMOTIONS_TOTAL = METRICS.counter(
    "quoracle_train_promotions_total",
    "draft promotion attempts by model and outcome (promoted | "
    "rejected | failed | rolled_back) — failed means the hot-swap "
    "aborted mid-fleet and the incumbent was restored; rolled_back "
    "means the live acceptance guard tripped after promotion "
    "(DEPLOY §20 PromotionRollback / AcceptanceRegression)")

# -- session-graph observability (ISSUE 20) ----------------------------------
# Agent-tree plane (infra/treeobs.py): lineage registry and subtree
# rollups over what the planes above already measure. Read-only like
# costobs/introspect — temp-0 on/off bit-equality depends on tree
# bookkeeping never touching a serving decision (QUORACLE_TREEOBS=0
# kills the whole plane).
TREE_NODES_TOTAL = METRICS.counter(
    "quoracle_tree_nodes_total",
    "agent-tree node registrations by event (spawned | completed) — "
    "the spawned-minus-completed gap is the live node census")
TREE_ORPHANS_TOTAL = METRICS.counter(
    "quoracle_tree_orphans_total",
    "nodes flagged orphaned at tree assembly: the parent record is "
    "missing (its peer crashed before federation) — flagged, never "
    "silently unparented (DEPLOY §21 TreeOrphanRate)")
TREE_BUDGET_OVERRUNS_TOTAL = METRICS.counter(
    "quoracle_tree_budget_overruns_total",
    "subtrees that overspent the token budget inherited at spawn — "
    "observed only, no policy acts on it (DEPLOY §21 "
    "TreeBudgetOverrun)")
TREE_DEPTH = METRICS.histogram(
    "quoracle_tree_depth",
    "spawn depth of each registered agent-tree node (root = 0) — a "
    "drifting upper tail is runaway recursion (DEPLOY §21 "
    "TreeDepthRunaway)",
    buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16, 24))
TREE_FANOUT = METRICS.gauge(
    "quoracle_tree_fanout",
    "mean children per node at each depth over the registry's current "
    "window, by depth — the fan-out prior exported read-only into "
    "FleetSignals for the elastic-fleet roadmap item")

# -- consensus quality (ISSUE 5) ---------------------------------------------
# Decision-quality instruments (consensus/quality.py): per-decide
# contestedness and the per-member scorecard counters. Registered at
# import so the full quoracle_consensus_* surface scrapes before first
# traffic, like everything above.
# -- lock discipline (ISSUE 9) -----------------------------------------------
# Runtime lock-order sanitizer (analysis/lockdep.py): inversions seen by
# the tier-1 suite (conftest enables QUORACLE_LOCKDEP) or a production
# process run with the env flag. Any nonzero value is a latent ABBA
# deadlock report — alert on it like a crash, not like a latency burn.
LOCKDEP_INVERSIONS = METRICS.counter(
    "quoracle_lockdep_inversions_total",
    "lock-order inversions observed by the runtime sanitizer, labeled "
    "by the acquiring and held lock names — any nonzero value is a "
    "latent ABBA deadlock report")

CONSENSUS_ENTROPY = METRICS.histogram(
    "quoracle_consensus_vote_entropy_bits",
    "Shannon entropy (bits) of the cluster-share distribution per decide: "
    "0 = unanimous, log2(k) = k-way even split",
    buckets=(0.01, 0.05, 0.1, 0.2, 0.35, 0.5, 0.7, 0.92, 1.1, 1.4,
             1.59, 2.0, 2.33, 3.0))
CONSENSUS_MARGIN = METRICS.histogram(
    "quoracle_consensus_winner_margin",
    "winner share minus runner-up share per decide (1 = unanimous, "
    "0 = tiebroken)",
    buckets=(0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0))
CONSENSUS_ROUNDS_TO_DECISION = METRICS.histogram(
    "quoracle_consensus_rounds_to_decision",
    "rounds a decide needed (1 = round-1 consensus)",
    buckets=(1, 2, 3, 4, 5, 6, 8))
CONSENSUS_SIM_MARGIN = METRICS.histogram(
    "quoracle_consensus_similarity_margin",
    "|cosine - threshold| of semantic-compatibility checks during "
    "clustering, side = above (joined) | below (split): mass near 0 "
    "means clusters are forming on a knife edge",
    buckets=(0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6))
MEMBER_DECIDES = METRICS.counter(
    "quoracle_consensus_member_decides_total",
    "decides a pool member participated in, per model")
MEMBER_AGREEMENTS = METRICS.counter(
    "quoracle_consensus_member_agreement_total",
    "decides where the member's valid proposal landed in the winning "
    "cluster, per model")
MEMBER_DISSENTS = METRICS.counter(
    "quoracle_consensus_member_dissent_total",
    "decides where the member's valid proposal lost to another cluster, "
    "per model")
MEMBER_FAILURES = METRICS.counter(
    "quoracle_consensus_member_failures_total",
    "member failures by cause, per model and kind "
    "(transport | parse | schema | deadline)")
MEMBER_RECOVERIES = METRICS.counter(
    "quoracle_consensus_member_recoveries_total",
    "decides where a corrected member produced a valid proposal in a "
    "later round, per model")
MEMBER_LATENCY_MS = METRICS.histogram(
    "quoracle_consensus_member_latency_ms",
    "per-decide summed proposal latency per pool member (ms)")
MEMBER_DRIFT_EVENTS = METRICS.counter(
    "quoracle_consensus_drift_total",
    "model_health_drift trips per model and signal (dissent | failure)")
MEMBER_DRIFTING = METRICS.gauge(
    "quoracle_consensus_member_drifting",
    "1 while a member's recent dissent/failure EWMA deviates from its "
    "baseline past the drift threshold, per model and signal")

# Process self-observation (ISSUE 3 satellite): sampled lazily by the
# collector below so /api/metrics and GET /metrics always carry a current
# view — no writer has to remember to refresh them.
_PROC_T0 = time.monotonic()


def open_fd_count() -> Optional[int]:
    """Open file descriptors of this process (Linux /proc; None where the
    kernel doesn't expose it)."""
    import os
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return None


def _process_collector() -> None:
    import threading as _threading
    METRICS.gauge("quoracle_process_uptime_s",
                  "seconds since telemetry import").set(
        round(time.monotonic() - _PROC_T0, 3))
    METRICS.gauge("quoracle_process_threads",
                  "live threads at scrape").set(
        _threading.active_count())
    fds = open_fd_count()
    if fds is not None:
        METRICS.gauge("quoracle_process_open_fds",
                      "open file descriptors at scrape").set(fds)


METRICS.register_collector(_process_collector)
