"""One trace, one clock (ISSUE 24): the batcher's tick record, the row
record, and the names on the device side.

  * a tick's phases come from one fixed tuple and sum EXACTLY to its wall
    (opening a phase closes the one before), with or without a profiler
    session; outside a tick the helper does nothing;
  * a ``jax.profiler`` trace holds ``qtpu.tick`` and ``qtpu.tick.<phase>``
    on ONE thread line, the tick carrying its arguments;
  * the one record feeds the phase counter, the rows' WaitClocks
    (``host`` / ``device_prefill`` / ``device_decode``, sum-to-wall kept),
    the sampled ``sched.decode_tick`` span and ``QueryResult.prefill_ms /
    decode_ms``;
  * a retired row's stamps are ordered and land in introspect's row ring;
  * every forward names its device work (``jax.named_scope``) and every
    ``pallas_call`` carries its pinned ``name``;
  * read-only: temp-0 output is bit-identical with a profiler session
    open and closed;
  * inside the phases, named operations (ISSUE 37): ``tick_op`` opens
    ``qtpu.op.<name>`` from one fixed tuple, each inside one phase, self
    time on the record, the counter and the sampled span; every name is
    reached by some tick and nothing outside the tuple is; a session drop
    books its wait for the paged lock on a histogram and on
    ``qtpu.session_drop``;
  * scope names are part of the persistent compile cache's key, the
    checkout's path is not (utils/compile_cache.py).

No timing thresholds anywhere: times are compared with each other, never
with a constant.
"""

import collections
import glob
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from quoracle_tpu.infra import introspect, telemetry
from quoracle_tpu.infra.telemetry import (
    TICK_OPS, TICK_PHASES, TRACER, tick_close, tick_note, tick_op,
    tick_open, tick_phase,
)
from quoracle_tpu.models.config import get_model_config
from quoracle_tpu.models.generate import GenerateEngine
from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
from quoracle_tpu.models.tokenizer import ByteTokenizer
from quoracle_tpu.models.transformer import init_params

MEMBER = "xla:tiny"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    monkeypatch.setenv("QUORACLE_TRACE_DECODE_SAMPLE", "1")
    introspect.reset()
    introspect.enable()
    yield
    introspect.reset()
    introspect.enable()


def ask(backend, text="tick record probe", max_tokens=20):
    out = backend.query([QueryRequest(
        MEMBER, [{"role": "user", "content": text}], temperature=0.0,
        max_tokens=max_tokens)])[0]
    assert out.ok, out.error
    return out


@pytest.fixture
def served():
    """One greedy turn through a continuous backend, with a sink
    listening: (result, spans emitted, model name)."""
    events: list = []
    TRACER.add_sink(events.append)
    b = TPUBackend([MEMBER], continuous_chunk=8)
    try:
        out = ask(b)
        name = b.engines[MEMBER].cfg.name
    finally:
        b.close()
        TRACER.remove_sink(events.append)
    return out, events, name


# ---------------------------------------------------------------------------
# The tick record
# ---------------------------------------------------------------------------

def test_tick_phases_tile_the_tick_and_come_from_the_fixed_list():
    rec = tick_open("m")
    for name in ("prepare", "pack", "wait_prefill", "dispatch_decode",
                 "prepare", "commit"):             # a phase may come again
        assert tick_phase(name) is rec
    before = rec.snapshot()
    tick_note(rows=3, program="raggedx64x8x4x64")
    after = rec.snapshot()
    assert tick_close() is rec
    assert sum(rec.phase_ns.values()) == rec.t1_ns - rec.t0_ns
    assert set(rec.phase_ns) == set(TICK_PHASES)
    assert rec.fence_ns and rec.t0_ns <= rec.fence_ns <= rec.t1_ns
    assert rec.args == {"model": "m", "rows": 3,
                        "program": "raggedx64x8x4x64"}
    # two snapshots differ by exactly the time between them, all of it
    # in the phase that was open
    grown = {k for k in after if after[k] != before[k]}
    assert grown <= {"commit"}
    assert rec.as_attrs()["wall_ns"] == rec.t1_ns - rec.t0_ns
    with pytest.raises(KeyError):
        tick_open("m").phase("not-a-phase")
    tick_close()


def test_outside_a_tick_the_phase_helper_does_nothing():
    assert tick_close() is None
    assert tick_phase("pack") is None
    tick_note(rows=1)                              # no record: no error
    seen = []
    t = threading.Thread(target=lambda: seen.append(tick_phase("pack")))
    rec = tick_open("m")                           # this thread's only
    t.start()
    t.join()
    assert seen == [None]
    assert tick_close() is rec


def test_decode_tick_span_is_the_tick_record(served):
    _, events, name = served
    ticks = [e for e in events if e["name"] == "sched.decode_tick"]
    assert ticks, "no tick span reached the sink"
    for e in ticks:
        phases = e["phases_ns"]
        assert set(phases) <= set(TICK_PHASES)
        assert sum(phases.values()) == e["wall_ns"]
        assert e["duration_ms"] == pytest.approx(e["wall_ns"] / 1e6, abs=1e-3)
        assert e["model"] == name and e["rows"] >= 1
        for arg in ("admitted", "nucleus_rows", "real_tokens",
                    "padded_tokens", "decode_steps", "program", "step"):
            assert arg in e, arg
        assert "," not in str(e["program"])        # a TraceMe argument
        assert phases["wait_prefill"] > 0 and phases["wait_decode"] > 0
    assert ticks[0]["admitted"] == 1
    assert [e["step"] for e in ticks] == list(range(len(ticks)))


def test_tick_books_the_rows_that_ask_for_a_nucleus():
    """``nucleus_rows`` and ``quoracle_sched_nucleus_rows_total``: rows
    with ``temperature`` > 0 AND ``top_p`` < 1, the ones the sampler sorts
    the vocabulary for; a sampled row at 1.0 and a greedy row at 0.5 are
    not among them."""
    events: list = []
    TRACER.add_sink(events.append)
    b = TPUBackend([MEMBER], continuous_chunk=8)
    name = b.engines[MEMBER].cfg.name
    worker = b._cbatchers[MEMBER]
    booked = telemetry.SCHED_NUCLEUS_ROWS_TOTAL

    def turn(temperature, top_p):
        before, n = booked.value(model=name), len(events)
        out = b.query([QueryRequest(
            MEMBER, [{"role": "user", "content": "nucleus probe"}],
            temperature=temperature, top_p=top_p, max_tokens=12)])[0]
        assert out.ok, out.error
        # the row's future resolves inside its last tick: wait for that
        # tick's span (every tick is sampled here)
        deadline = time.monotonic() + 30
        while (sum(e["name"] == "sched.decode_tick" for e in events)
               < worker.steps and time.monotonic() < deadline):
            time.sleep(0.01)
        ticks = [e for e in events[n:] if e["name"] == "sched.decode_tick"]
        assert len(ticks) >= 2                     # 12 tokens, chunks of 8
        return ([e["nucleus_rows"] for e in ticks],
                booked.value(model=name) - before)
    try:
        for temperature, top_p in ((1.0, 1.0), (0.0, 0.5)):
            per_tick, grown = turn(temperature, top_p)
            assert set(per_tick) == {0} and grown == 0
        per_tick, grown = turn(1.0, 0.5)
        assert set(per_tick) == {1} and grown == len(per_tick)
    finally:
        b.close()
        TRACER.remove_sink(events.append)


def test_phase_counter_grows_by_the_ticks_phases(served):
    _, events, name = served
    ticks = [e for e in events if e["name"] == "sched.decode_tick"]
    for phase in ("admit", "prepare", "wait_prefill", "wait_decode",
                  "commit", "retire"):
        spanned = sum(e["phases_ns"].get(phase, 0) for e in ticks) / 1e6
        # the counter also holds the idle iterations' admit time
        assert telemetry.TICK_PHASE_MS_TOTAL.value(
            model=name, phase=phase) >= spanned * 0.999 > 0
    text = telemetry.METRICS.render_prometheus()
    assert "quoracle_tick_phase_ms_total{" in text
    assert "quoracle_sched_pad_waste_ratio" not in text


# ---------------------------------------------------------------------------
# The row record
# ---------------------------------------------------------------------------

def test_retired_row_stamps_are_ordered_and_waits_sum_to_wall(served):
    out, _, name = served
    rows = [r for r in introspect.row_ring() if r["model"] == name]
    assert len(rows) == 1
    r = rows[0]
    assert (r["t_submit_ns"] <= r["t_admit_ns"] <= r["t_first_token_ns"]
            <= r["t_done_ns"])
    assert r["t_done_ns"] - r["t_submit_ns"] == r["wall_ns"]
    assert sum(r["waits_ns"].values()) == r["wall_ns"]
    assert set(r["waits_ns"]) <= set(introspect.WAIT_STATES)
    for state in ("host", "device_prefill", "device_decode"):
        assert r["waits_ns"][state] > 0, state
    assert "dispatch" not in r["waits_ns"]         # the lump is split
    assert r["ticks"] == 3                         # 20 tokens, chunk 8
    assert r["emitted_tokens"] == out.usage.completion_tokens == 20
    assert r["prompt_tokens"] == out.usage.prompt_tokens
    # served under `rows` by /api/profile, beside the waits totals
    payload = introspect.profile_payload()
    assert payload["rows"][-1] == r and name in payload["waits"]
    json.dumps(payload)


def test_query_result_phase_times_come_from_the_row_record(served):
    out, _, name = served
    r = [r for r in introspect.row_ring() if r["model"] == name][0]
    assert out.prefill_ms + out.decode_ms > 0
    assert out.prefill_ms == pytest.approx(
        r["waits_ns"]["device_prefill"] / 1e6)
    assert out.decode_ms == pytest.approx(
        r["waits_ns"]["device_decode"] / 1e6)
    assert out.prefill_ms + out.decode_ms <= out.latency_ms


def test_row_ring_is_bounded_and_gated():
    closed = {"wall_ns": 5, "waits_ns": {"other": 5}, "skew_ns": 0}
    for i in range(introspect.ROW_RING_SIZE + 10):
        introspect.record_row_waits("m", closed, row={"session": str(i)})
    ring = introspect.row_ring()
    assert len(ring) == introspect.ROW_RING_SIZE
    assert ring[-1]["session"] == str(introspect.ROW_RING_SIZE + 9)
    assert introspect.row_ring(last=3) == ring[-3:]
    introspect.reset()
    introspect.disable()
    introspect.record_row_waits("m", closed, row={"session": "x"})
    assert introspect.row_ring() == []


# ---------------------------------------------------------------------------
# In the profiler's trace, and read-only
# ---------------------------------------------------------------------------

def test_profiler_trace_holds_tick_and_phases_on_one_thread_line(tmp_path):
    from jax.profiler import ProfileData
    b = TPUBackend([MEMBER], continuous_chunk=8)
    try:
        closed = ask(b, "bit equality probe").text
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            opened = ask(b, "bit equality probe").text
        finally:
            jax.profiler.stop_trace()
        again = ask(b, "bit equality probe").text
    finally:
        b.close()
    # read-only: the same greedy tokens with a session open and closed
    assert opened == closed == again
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    lines = [line for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines]
    holding = [line for line in lines
               if any(ev.name.startswith("qtpu.") for ev in line.events)]
    assert len(holding) == 1, "qtpu.* spans on more than one thread line"
    events = list(holding[0].events)
    ticks = [ev for ev in events if ev.name == "qtpu.tick"]
    riding = [dict(ev.stats) for ev in ticks
              if str(dict(ev.stats).get("rows", "0")) != "0"]
    assert riding, "no tick with rows in the trace"
    for args in riding:
        for arg in ("model", "rows", "admitted", "nucleus_rows",
                    "real_tokens", "padded_tokens", "decode_steps",
                    "program"):
            assert arg in args, arg
    names = {ev.name for ev in events if ev.name.startswith("qtpu.tick.")}
    assert names <= {"qtpu.tick." + p for p in TICK_PHASES}
    assert {"qtpu.tick.admit", "qtpu.tick.prepare",
            "qtpu.tick.wait_prefill", "qtpu.tick.wait_decode",
            "qtpu.tick.commit", "qtpu.tick.retire"} <= names
    # the phases of a tick lie inside it
    t = max(ticks, key=lambda ev: ev.duration_ns)
    inside = [ev for ev in events if ev.name.startswith("qtpu.tick.")
              and t.start_ns <= ev.start_ns and ev.end_ns <= t.end_ns]
    assert sum(ev.duration_ns for ev in inside) <= t.duration_ns
    assert sum(ev.duration_ns for ev in inside) > 0


# ---------------------------------------------------------------------------
# Named operations inside the phases (ISSUE 37)
# ---------------------------------------------------------------------------

SYSTEM = "system: " + "policy rules apply here. " * 8     # over one page


def sessioned(backend, sid, text, max_tokens=12):
    out = backend.query([QueryRequest(
        MEMBER, [{"role": "system", "content": SYSTEM},
                 {"role": "user", "content": text}],
        temperature=0.0, max_tokens=max_tokens, session_id=sid)])[0]
    assert out.ok, out.error
    return out


Event = collections.namedtuple(
    "Event", "name start_ns end_ns duration_ns stats")


def host_events(trace_dir) -> list:
    """[[Event]]: the lines of the `/host:CPU` plane of the trace."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    return [[Event(ev.name, ev.start_ns, ev.end_ns, ev.duration_ns,
                   dict(ev.stats)) for ev in line.events]
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines]


def start_trace(trace_dir) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


@pytest.fixture(scope="module")
def reached(tmp_path_factory):
    """What four kinds of tick open: {operation: ns} summed over (a) the
    ticks of a continuous backend that serves two sessions with one system
    prompt, under a profiler session (the radix cache, the batcher's own
    operations); (b) a tick of an engine with conv state; (c) a tick that
    restores a hibernated session; (d) a tick of an engine with a window
    group of attention layers. With (a)'s trace and span events."""
    env = pytest.MonkeyPatch()
    env.setenv("QUORACLE_TRACE_DECODE_SAMPLE", "1")
    introspect.enable()
    ops: dict = {}

    def add(op_ns):
        for name, ns in op_ns.items():
            ops[name] = ops.get(name, 0) + ns

    events: list = []
    TRACER.add_sink(events.append)
    trace_dir = tmp_path_factory.mktemp("ops")
    b = TPUBackend([MEMBER], continuous_chunk=8)
    try:
        start_trace(trace_dir)
        try:
            for sid in ("a", "b", "a"):
                sessioned(b, sid, f"turn of {sid}")
        finally:
            jax.profiler.stop_trace()
    finally:
        b.close()
        TRACER.remove_sink(events.append)
        env.undo()
    ticks = [e for e in events if e["name"] == "sched.decode_tick"]
    for e in ticks:
        add(e["ops_ns"])

    def one_tick(eng, prompt, sid):
        rec = tick_open(eng.cfg.name)
        try:
            eng.generate([prompt], temperature=0.0, max_new_tokens=4,
                         session_ids=[sid])
        finally:
            tick_close()
        add(rec.op_ns)

    from tests.test_shortconv_moe import RAW, f32, model
    cfg, params, _ = model(RAW)
    conv = GenerateEngine(cfg, f32(params), ByteTokenizer(), max_seq=1024,
                          prompt_buckets=(32, 64, 128, 256, 512))
    one_tick(conv, list(range(3, 40)), "c")

    # (d) a tick of a model with a window group beside the full one
    from tests import test_window_moe as wm
    cfg, params, _ = wm.model(wm.RAW)
    mixed = GenerateEngine(cfg, wm.f32(params), ByteTokenizer(),
                           max_seq=1024,
                           prompt_buckets=(32, 64, 128, 256, 512))
    one_tick(mixed, list(range(3, 40)), "w")

    tiny = get_model_config(MEMBER)
    eng = GenerateEngine(
        tiny, init_params(tiny, jax.random.PRNGKey(0), dtype=jnp.float32),
        ByteTokenizer(), max_seq=512, prompt_buckets=(32, 64, 128, 256))
    tier = eng.attach_tier(host_mb=64)
    prompt = ByteTokenizer().encode(SYSTEM + " task", add_bos=True)
    res = eng.generate([prompt], temperature=0.0, max_new_tokens=4,
                       session_ids=["t"])[0]
    st = eng.sessions
    with eng._paged_lock, st.lock:          # the ladder: hibernate it
        st._release(st.alloc(st.n_pages - 1))
    assert tier.has_session("t") and st.get("t") is None
    one_tick(eng, prompt + res.token_ids + [5, 6, 7], "t")
    assert tier.restored_sessions == 1
    return {"ops": ops, "ticks": ticks, "host": host_events(trace_dir)}


@pytest.mark.parametrize("op", TICK_OPS)
def test_every_named_operation_is_reached_by_some_tick(reached, op):
    assert reached["ops"].get(op, 0) > 0, f"no tick opened {op}"


def test_ticks_open_no_operation_outside_the_fixed_list(reached):
    assert set(reached["ops"]) <= set(TICK_OPS)
    assert len(set(TICK_OPS)) == len(TICK_OPS)
    assert not set(TICK_OPS) & set(TICK_PHASES)
    named = {ev.name for evs in reached["host"] for ev in evs
             if ev.name.startswith("qtpu.")}
    assert {n for n in named if n.startswith("qtpu.op.")} \
        <= {"qtpu.op." + o for o in TICK_OPS}
    # a child of a phase is NOT named like a phase: every reader takes
    # `qtpu.tick.<x>` for one of the ten
    assert {n for n in named if n.startswith("qtpu.tick.")} \
        <= {"qtpu.tick." + p for p in TICK_PHASES}
    with pytest.raises(KeyError):
        rec = tick_open("m")
        try:
            with tick_op("not-an-operation"):
                pass
        finally:
            tick_close()
    assert rec.op_ns == {}


def test_an_operation_lies_inside_one_phase(reached):
    """On the worker's line every `qtpu.op.*` span lies inside ONE
    `qtpu.tick.<phase>` span, and the operations inside a phase take no
    more than the phase (an operation inside another counts once)."""
    worker = [evs for evs in reached["host"]
              if any(ev.name == "qtpu.tick" for ev in evs)]
    assert len(worker) == 1
    phases = sorted((ev for ev in worker[0]
                     if ev.name.startswith("qtpu.tick.")),
                    key=lambda ev: ev.start_ns)
    ops = [ev for ev in worker[0] if ev.name.startswith("qtpu.op.")]
    assert ops and phases
    inside: dict = {}
    for ev in ops:
        holds = [i for i, ph in enumerate(phases)
                 if ph.start_ns <= ev.start_ns and ev.end_ns <= ph.end_ns]
        assert len(holds) == 1, (ev.name, len(holds))
        inside.setdefault(holds[0], []).append(ev)
    for i, evs in inside.items():
        outer = [ev for ev in evs if not any(
            o is not ev and o.start_ns <= ev.start_ns
            and ev.end_ns <= o.end_ns for o in evs)]
        assert sum(ev.duration_ns for ev in outer) <= phases[i].duration_ns


def test_tick_span_carries_the_operations_self_time(reached):
    assert reached["ticks"]
    for e in reached["ticks"]:
        assert set(e["ops_ns"]) <= set(TICK_OPS)
        assert all(ns >= 0 for ns in e["ops_ns"].values())
        # self time: nested operations count once, so all of them together
        # fit the tick's phases other than the empty loop's wait
        assert sum(e["ops_ns"].values()) <= e["wall_ns"]
    # the waits for the device are named too, inside their fences
    e = max(reached["ticks"], key=lambda e: e["wall_ns"])
    assert 0 < e["ops_ns"]["device"] <= (
        e["phases_ns"]["wait_prefill"] + e["phases_ns"]["wait_decode"])


def test_operations_nest_by_self_time_and_feed_the_counter_exactly():
    model = "ops-counter-probe"
    rec = tick_open(model)
    tick_phase("commit")
    with tick_op("session_put"):
        time.sleep(0.002)
        with tick_op("prefix_insert"):
            time.sleep(0.004)
        with tick_op("prefix_insert"):           # a name may come again
            pass
    with tick_op("account"):
        pass
    assert tick_close() is rec
    assert set(rec.op_ns) == {"session_put", "prefix_insert", "account"}
    assert rec.op_ns["prefix_insert"] > rec.op_ns["session_put"] > 0
    assert sum(rec.op_ns.values()) <= rec.phase_ns["commit"]
    assert rec.as_attrs()["ops_ns"] == rec.op_ns
    for name, ns in rec.op_ns.items():
        assert telemetry.TICK_OP_MS_TOTAL.value(model=model, op=name) \
            == ns / 1e6
    assert telemetry.TICK_OP_MS_TOTAL.value(model=model, op="layout") == 0
    assert "quoracle_tick_op_ms_total{" in \
        telemetry.METRICS.render_prometheus()


def test_outside_a_tick_the_operation_helper_records_nothing():
    before = telemetry.TICK_OP_MS_TOTAL.total()
    with tick_op("layout") as nothing:
        assert nothing is None
    assert tick_op("layout") is tick_op("fetch")     # the shared no-op
    seen = []
    rec = tick_open("m")                             # this thread's only

    def elsewhere():
        with tick_op("layout"):
            seen.append(tick_phase("pack"))
    t = threading.Thread(target=elsewhere)
    t.start()
    t.join()
    assert tick_close() is rec
    assert seen == [None] and rec.op_ns == {}
    assert telemetry.TICK_OP_MS_TOTAL.total() == before


class SeenLock:
    """The engine's paged lock, noting when a caller starts to wait."""

    def __init__(self, base):
        self.base = base
        self.waiting = threading.Event()
        self.t_wait_ns = 0

    def acquire(self, *a):
        self.t_wait_ns = time.monotonic_ns()
        self.waiting.set()
        return self.base.acquire(*a)

    def release(self):
        self.base.release()


def test_a_session_drop_books_its_wait_for_the_paged_lock(tmp_path):
    cfg = get_model_config(MEMBER)
    eng = GenerateEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
        ByteTokenizer(), max_seq=256, prompt_buckets=(32, 64, 128))
    eng.generate([[5, 6, 7, 8]], temperature=0.0, max_new_tokens=2,
                 session_ids=["gone"])
    assert eng.sessions.get("gone") is not None
    _, sum0, n0 = telemetry.SESSION_DROP_WAIT_MS.counts(model=cfg.name)
    lock = eng._paged_lock
    eng._paged_lock = seen = SeenLock(lock)
    start_trace(tmp_path)
    try:
        lock.acquire()                     # a sessioned tick under way
        t = threading.Thread(target=eng.drop_session, args=("gone",))
        t.start()
        assert seen.waiting.wait(30)
        time.sleep(0.05)
        held_until_ns = time.monotonic_ns()
        lock.release()
        t.join(30)
    finally:
        jax.profiler.stop_trace()
        eng._paged_lock = lock
    assert eng.sessions.get("gone") is None
    # the drop read its clock before it asked for the lock and after it
    # got it: it waited at least from the asking to the release
    known_ns = held_until_ns - seen.t_wait_ns
    assert known_ns > 0
    _, sum1, n1 = telemetry.SESSION_DROP_WAIT_MS.counts(model=cfg.name)
    assert n1 == n0 + 1 and (sum1 - sum0) * 1e6 >= known_ns
    drops = [ev for evs in host_events(tmp_path) for ev in evs
             if ev.name == "qtpu.session_drop"]
    assert len(drops) == 1
    args = drops[0].stats
    assert args["model"] == cfg.name
    assert int(args["lock_wait_us"]) >= known_ns // 1000
    assert int(args["held_us"]) >= 0
    assert drops[0].duration_ns >= known_ns
    # on the caller's line, not the batcher's: no tick is open there
    assert "quoracle_session_drop_wait_ms" in \
        telemetry.METRICS.render_prometheus()


# --- a latent engine's decode ticks carry the shared walk (ISSUE 40) ---------

def test_latent_decode_tick_books_the_shared_walk(monkeypatch):
    """Three sessions of a latent, routed-expert toy on one system prompt,
    decoded in one tick: the tick span carries the rows and pages the
    kernel's shared walk served, streams fewer resident tokens than its
    rows needed, and counts the loop turns of a walk that carries
    ``latent_walk_pages`` a turn; with nothing in common (no group forms)
    the same tick reads as a latent tick always did, but for the pages a
    turn of the decode steps' walks."""
    import numpy as np

    from benchmark.families import latent_moe
    from quoracle_tpu.models.tokenizer import get_tokenizer
    from quoracle_tpu.ops import paged_attention as pa
    from tests.test_latent_moe import RAW
    raw = {**RAW, "name": "toy-axk1-long",
           "serving": dict(context_window=2048, output_limit=128)}
    cfg = get_model_config(latent_moe.register(raw))
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    page, rng = 128, np.random.default_rng(40)
    n_shared = pa.SHARED_MIN_PAGES + 1
    system = [int(t) for t in rng.integers(3, 512, n_shared * page + 1)]
    # suffixes of one 8-token block: a latent chunk forward walks a row's
    # pages once a block, which is not this test's matter
    asks = [system + [int(t) for t in rng.integers(3, 512, n)]
            for n in (3, 7, 5)]
    steps = 5                    # forwards a row: the sixth token stays

    def run():
        eng = GenerateEngine(cfg, params, get_tokenizer("tiny"),
                             max_seq=2048,
                             prompt_buckets=(256, 512, 1024, 2048))
        eng.generate([system + [7, 8, 9]], temperature=0.0,
                     max_new_tokens=2, session_ids=["donor"])
        telemetry.tick_open("m")
        try:
            res = eng.generate(asks, temperature=0.0,
                               max_new_tokens=steps + 1,
                               session_ids=["a", "b", "c"])
        finally:
            args = telemetry.tick_close().args
        assert all(r.n_cached_tokens == n_shared * page for r in res)
        return [r.token_ids for r in res], args, eng._walk_block

    with monkeypatch.context() as patch:
        patch.setattr(pa, "SHARED_MIN_PAGES", 10 ** 6)
        want, off, _ = run()
    got, on, block = run()
    assert got == want
    assert block == pa.latent_walk_pages(page) == 4
    assert off["attn_shared_rows"] == 0 and off["attn_shared_pages"] == 0
    assert off["attn_kv_streamed"] >= off["attn_kv_reads"]
    assert on["attn_shared_rows"] == 3
    assert on["attn_shared_pages"] == n_shared
    assert on["attn_kv_streamed"] < on["attn_kv_reads"] \
        == off["attn_kv_reads"]
    assert off["attn_kv_streamed"] - on["attn_kv_streamed"] == \
        2 * n_shared * page * steps
    # a decode step's walks by hand: a row alone walks all of its pages,
    # ``block`` a turn; with the walk the group's pages are one walk and a
    # row's own begin behind them
    pages = [[-(-(len(a) + j) // page) for j in range(1, steps + 1)]
             for a in asks]
    turns = lambda n: -(-n // block)                    # noqa: E731
    alone = sum(turns(n) for row in pages for n in row)
    walked = steps * turns(n_shared) + sum(
        turns(n - n_shared) for row in pages for n in row)
    assert off["attn_walk_steps"] - on["attn_walk_steps"] == alone - walked
    # the chunk forward's walks are the same in both
    chunk = off["attn_walk_steps"] - alone
    assert chunk > 0 and chunk == on["attn_walk_steps"] - walked
    # a step's walks: each row's own, and with the table the group's; the
    # latent kernels start every walk cold (ISSUE 45 changed the dense
    # block kernel alone), so none counts as started ahead
    assert (off["attn_walks"], on["attn_walks"]) == (3 * steps, 4 * steps)
    assert off["attn_walks_started_ahead"] == 0 \
        == on["attn_walks_started_ahead"]


def test_latent_chunk_forward_counts_a_block_of_pages_a_walk_step():
    """A latent chunk forward's ``attn_walk_steps`` (ISSUE 44): its kernel
    walks a row's visible pages once per block of 8 queries,
    ``latent_walk_pages`` pages a loop turn, so a tick with no decode step
    counts Σ ⌈pages / 4⌉ over its blocks where it streams Σ pages."""
    import numpy as np

    from benchmark.families import latent_moe
    from quoracle_tpu.models.generate import RAGGED_TQ
    from quoracle_tpu.models.tokenizer import get_tokenizer
    from quoracle_tpu.ops import paged_attention as pa
    from tests.test_latent_moe import RAW
    raw = {**RAW, "name": "toy-axk1-chunks",
           "serving": dict(context_window=2048, output_limit=128)}
    cfg = get_model_config(latent_moe.register(raw))
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    eng = GenerateEngine(cfg, params, get_tokenizer("tiny"), max_seq=2048,
                         prompt_buckets=(256, 512, 1024, 2048))
    page, rng = 128, np.random.default_rng(44)
    block = eng._walk_block
    assert block == pa.latent_walk_pages(page) == 4
    # 6 pages + 3 tokens, a page and a half, under a block of queries
    rows = [[int(t) for t in rng.integers(3, 512, n)] for n in (771, 200, 5)]
    telemetry.tick_open("m")
    try:
        eng.generate(rows, temperature=0.0, max_new_tokens=1)
    finally:
        args = telemetry.tick_close().args
    pages = [-(-min(len(r), (b + 1) * RAGGED_TQ) // page) for r in rows
             for b in range(-(-len(r) // RAGGED_TQ))]
    assert max(pages) == 7 and min(pages) == 1
    assert args["attn_tiles"] == len(pages)
    assert args["attn_kv_streamed"] == page * sum(pages)
    assert args["attn_walk_steps"] == sum(-(-n // block) for n in pages) \
        < sum(pages)


# ---------------------------------------------------------------------------
# Names on the device side
# ---------------------------------------------------------------------------

def op_names(lowered) -> set:
    """The name of every location of the lowered module: the scope path
    an operation was traced under, its primitive last."""
    import re
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


def has_scope(names: set, scope: str) -> bool:
    """`sample/top_p`: some operation under `top_p` inside `sample`.
    `layers/mlp`: the scan's body is a function of its own, whose
    locations are relative to the call (`jit(step)/layers/while/body/
    closed_call`) — the chip's profiler shows them joined, as
    `…/layers/while/body/closed_call/mlp/dot_general`."""
    outer, _, inner = scope.partition("/")
    if outer == "layers":
        return (any("layers" in n.split("/") for n in names)
                and any(inner in n.split("/")[:-1] for n in names))
    return any(scope in "/".join(n.split("/")[:-1]) + "/" for n in names)


def make_engine():
    cfg = get_model_config(MEMBER)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return GenerateEngine(cfg, params, ByteTokenizer(), max_seq=256,
                          prompt_buckets=(32, 64, 128))


@pytest.fixture(scope="module")
def ragged_steps():
    """The two jitted steps of a unified tick with the shapes they were
    called with: {name: (jitted function, args, kwargs)}."""
    eng = make_engine()
    seen: dict = {}

    def spy(name):
        fn = getattr(eng, name)

        def call(*args, **kwargs):
            seen[name[1:]] = (fn, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                if hasattr(a, "shape") else a, args), kwargs)
            return fn(*args, **kwargs)
        setattr(eng, name, call)

    spy("_step_paged_ragged")
    spy("_step_paged_decode_ragged")
    prompt = ByteTokenizer().encode("names on the device side",
                                    add_bos=True)
    eng.generate([prompt], temperature=0.0, max_new_tokens=4,
                 session_ids=["s"])
    assert set(seen) == {"step_paged_ragged", "step_paged_decode_ragged"}
    return seen


@pytest.mark.parametrize("step,scopes", [
    ("step_paged_ragged",
     ("embed", "layers/qkv", "layers/rope", "layers/kv_write",
      "layers/attn", "layers/attn_out", "layers/mlp", "final_norm",
      "head")),
    ("step_paged_decode_ragged",
     ("embed", "layers/qkv", "layers/rope", "layers/kv_write",
      "layers/attn", "layers/attn_out", "layers/mlp", "final_norm", "head",
      "decode_loop", "decode_loop/while/body/sample/top_p",
      "decode_loop/while/body/row_state")),
])
def test_lowered_steps_carry_the_scope_names(ragged_steps, step, scopes):
    fn, args, kwargs = ragged_steps[step]
    names = op_names(fn.lower(*args, **kwargs))
    assert any(n.startswith(f"jit({step})/") for n in names)   # its name
    for scope in scopes:
        assert has_scope(names, scope), scope


def test_constrained_decode_names_the_grammar_mask_beneath_sample():
    from quoracle_tpu.models.generate import _draw, grammar_mask
    table = jnp.zeros((3, 16), jnp.int32)

    def f(logits, jstate, key):
        return _draw(lambda lg, js: grammar_mask(lg, js, table, 2),
                     logits, jstate, key, jnp.zeros((2,)), jnp.ones((2,)))
    names = op_names(jax.jit(f).lower(
        jnp.zeros((2, 16)), jnp.zeros((2,), jnp.int32),
        jax.random.PRNGKey(0)))
    assert has_scope(names, "sample/grammar_mask")
    assert has_scope(names, "sample/top_p")


def test_the_dense_cache_forward_names_the_same_scopes():
    """``forward_hidden`` (the gather programs, sessionless calls, the
    trainers) names its device work as the ragged forward does."""
    from quoracle_tpu.models import transformer as tr
    cfg = get_model_config(MEMBER)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, T = 2, 8
    tok = jnp.zeros((B, T), jnp.int32)
    pos = jnp.zeros((B, T), jnp.int32)
    lens = jnp.full((B,), T, jnp.int32)

    def f(p):
        return tr.forward_hidden(p, cfg, tok, pos,
                                 tr.init_cache(cfg, B, 32, jnp.float32),
                                 jnp.zeros((B,), jnp.int32), lens)
    names = op_names(jax.jit(f).lower(params))
    for scope in ("embed", "layers/qkv", "layers/rope", "layers/kv_write",
                  "layers/attn", "layers/attn_out", "layers/mlp",
                  "final_norm"):
        assert has_scope(names, scope), scope


def pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += pallas_names(inner)
    return out


def test_every_pallas_call_of_the_ragged_steps_has_its_pinned_name(
        ragged_steps, monkeypatch):
    """On the CPU the dispatcher picks the gather reference; with the
    kernel forced (interpret mode) the steps' jaxprs hold one
    ``pallas_call`` a layer body, under the name the trace shows."""
    from quoracle_tpu.ops import paged_attention as pa
    auto = pa.ragged_attend_auto

    def kernel(*args, **kwargs):
        kwargs["interpret"] = True
        return auto(*args, **kwargs)
    monkeypatch.setattr(pa, "ragged_attend_auto", kernel)
    eng = make_engine()                  # fresh jits, traced through it
    for step, (_, args, kwargs) in ragged_steps.items():
        fn = getattr(eng, "_" + step)
        names = pallas_names(fn.trace(*args, **kwargs).jaxpr.jaxpr)
        assert names and set(names) == {"ragged_attend"}, (step, names)


@pytest.mark.parametrize("kernel", ["ragged_attend", "ragged_attend_tiny",
                                    "ragged_attend_latent", "flash_attend"])
def test_each_kernel_entry_point_pins_its_name(kernel):
    from quoracle_tpu.ops import flash_attention as fa
    from quoracle_tpu.ops import paged_attention as pa
    H, KV, page, n_pages, B = 4, 2, 16, 4, 2
    HD = 16 if kernel == "ragged_attend_tiny" else 128
    tables = jnp.zeros((B, 2), jnp.int32)
    lens = jnp.full((B,), 8, jnp.int32)
    meta = jnp.zeros((4, 1), jnp.int32)
    if kernel.startswith("ragged_attend") and kernel != "ragged_attend_latent":
        stored = jnp.zeros((2, n_pages, page, KV * HD))

        def f():
            return pa.ragged_attend(
                jnp.zeros((8, H, HD)), stored, stored, tables, meta, 1,
                tq=8, interpret=True)
    elif kernel == "ragged_attend_latent":
        latent = jnp.zeros((2, n_pages, page, 256))

        def f():
            return pa.ragged_attend_latent(
                jnp.zeros((8, H, 256)), latent, tables, meta, 1, tq=8,
                v_lanes=128, scale=0.1, interpret=True)
    else:
        def f():
            return fa.flash_attend(
                jnp.zeros((B, 128, H, HD)), jnp.zeros((B, 128, KV, HD)),
                jnp.zeros((B, 128, KV, HD)),
                jnp.zeros((B, 128), jnp.int32), lens, interpret=True)
    assert pallas_names(jax.make_jaxpr(f)().jaxpr) == [
        kernel.removesuffix("_tiny")]
    names = op_names(jax.jit(f).lower())
    if kernel == "ragged_attend_tiny":
        # a test model's head_dim below the lane width: the one layer
        # the call reads is padded, under a scope of its own
        assert has_scope(names, "kv_layout")
    elif kernel == "ragged_attend":
        # the serving kernel reads the pool as stored: at a production
        # head_dim nothing stands between the store and the kernel
        assert not has_scope(names, "kv_layout")


# ---------------------------------------------------------------------------
# Names are part of a cached program's identity
# ---------------------------------------------------------------------------

MODULE = textwrap.dedent('''
    import jax
    @jax.jit
    def f(x):
        with jax.named_scope("SCOPE"):
            return (x @ x).sum()
''')
RUN = textwrap.dedent('''
    import json, os, sys
    sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp
    from quoracle_tpu.utils import compile_cache
    compile_cache.REPO_ROOT = sys.argv[1]      # this copy is "the checkout"
    hits, misses = [], []
    jax.monitoring.register_event_listener(lambda e, **_: (
        hits if e.endswith("/cache_hits") else
        misses if e.endswith("/cache_misses") else []).append(e))
    compile_cache.enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import mod
    mod.f(jnp.ones((64, 64))).block_until_ready()
    print(json.dumps([len(hits), len(misses)]))
''')


def test_scope_names_are_in_the_cache_key_and_the_checkout_path_is_not(
        tmp_path):
    """Two copies of one module at different paths share their entries; the
    same module with another scope name misses for the program that holds
    it (JAX's default key strips the names, and a cache warmed by a build
    without them would serve executables that carry none)."""
    results = []
    for copy, scope in (("a", "mlp"), ("b", "mlp"), ("c", "renamed")):
        root = tmp_path / copy
        root.mkdir()
        (root / "mod.py").write_text(MODULE.replace("SCOPE", scope))
        (root / "run.py").write_text(RUN)
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        p = subprocess.run([sys.executable, str(root / "run.py"), str(root)],
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        results.append(json.loads(p.stdout.strip().splitlines()[-1]))
    (hits_a, miss_a), (hits_b, miss_b), (hits_c, miss_c) = results
    assert hits_a == 0 and miss_a >= 1
    assert miss_b == 0 and hits_b == miss_a       # another path: all hits
    assert miss_c == 1 and hits_c == miss_a - 1   # another name: f misses
