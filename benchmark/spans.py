"""What the program writes where the profiler and the harness can see it,
for the readers of the span and scope metrics (PR 24):

* the profiler trace of a `--trace 1` run: the batcher's `qtpu.tick` and
  `qtpu.tick.<phase>` spans on its worker thread's line of the `/host:CPU`
  plane, and on the device plane each operation's `tf_op` — the path of
  `jax.named_scope` names it was traced under;
* the program's row ring (`quoracle_tpu.infra.introspect.row_ring`): one
  entry per retired batcher row, with its four stamps.

`trace_reduce.load` gives (plane, line, name, start, duration) and drops the
rest; an operation's `tf_op` is a stat of its event METADATA, which
`jax.profiler.ProfileData` does not show at all. So this module reads the
`.xplane.pb` itself, with `google.protobuf` and the few messages of
`xplane.proto` written out below. Nothing here knows a model or a cell;
scope names live in `scopes.json`, patterns in the metric files.

A reader gets no trace path in `ctx`: `trace_of_this_process()` finds the one
`trace-*` directory this process wrote under `run.OUT_DIR`. Everything here
returns None where there is nothing to read — a program from before PR 24
writes no `qtpu.*` span, names no scope and keeps no ring — and then the
metric is left out of the line.

Clocks. Host spans and device events share one trace, but the profiler sets
the device's clock against the host's only to about a millisecond (1.2 ms
early on the v5e, my chip run, PR 24). The runtime's own `DoEnqueueProgram`
event on the host and the program's execution on the device carry the same
`run_id`, and a program cannot start before it is enqueued: the least shift
that puts every execution at or after its enqueue is the offset, and
`device_offset_ns` returns it.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
HOST_PLANE = "/host:CPU"
TICK = "qtpu.tick"
PHASE_PREFIX = "qtpu.tick."
ENQUEUE = "DoEnqueueProgram"
UNSCOPED = "(unscoped)"


# -- the file -------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _xspace_class():
    """XSpace and its parts, from field numbers (xplane.proto, unchanged
    since 2020): only what is read here."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchmark.xplane",
        syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            m.field.add(
                name=fname, number=number, type=ftype,
                label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL,
                type_name=(".benchmark.xplane." + type_name
                           if type_name else None))
        return m

    message("XStat", ("metadata_id", 1, T.TYPE_INT64, 0, ""),
            ("double_value", 2, T.TYPE_DOUBLE, 0, ""),
            ("uint64_value", 3, T.TYPE_UINT64, 0, ""),
            ("int64_value", 4, T.TYPE_INT64, 0, ""),
            ("str_value", 5, T.TYPE_STRING, 0, ""),
            ("bytes_value", 6, T.TYPE_BYTES, 0, ""),
            ("ref_value", 7, T.TYPE_UINT64, 0, ""))
    message("XEvent", ("metadata_id", 1, T.TYPE_INT64, 0, ""),
            ("offset_ps", 2, T.TYPE_INT64, 0, ""),
            ("duration_ps", 3, T.TYPE_INT64, 0, ""),
            ("stats", 4, T.TYPE_MESSAGE, 1, "XStat"))
    message("XLine", ("id", 1, T.TYPE_INT64, 0, ""),
            ("name", 2, T.TYPE_STRING, 0, ""),
            ("timestamp_ns", 3, T.TYPE_INT64, 0, ""),
            ("events", 4, T.TYPE_MESSAGE, 1, "XEvent"))
    message("XEventMetadata", ("id", 1, T.TYPE_INT64, 0, ""),
            ("name", 2, T.TYPE_STRING, 0, ""),
            ("stats", 5, T.TYPE_MESSAGE, 1, "XStat"))
    message("XStatMetadata", ("id", 1, T.TYPE_INT64, 0, ""),
            ("name", 2, T.TYPE_STRING, 0, ""))
    # a map<int64, M> field is a repeated entry message {key=1, value=2}
    message("EventMetadataEntry", ("key", 1, T.TYPE_INT64, 0, ""),
            ("value", 2, T.TYPE_MESSAGE, 0, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, T.TYPE_INT64, 0, ""),
            ("value", 2, T.TYPE_MESSAGE, 0, "XStatMetadata"))
    message("XPlane", ("name", 2, T.TYPE_STRING, 0, ""),
            ("lines", 3, T.TYPE_MESSAGE, 1, "XLine"),
            ("event_metadata", 4, T.TYPE_MESSAGE, 1, "EventMetadataEntry"),
            ("stat_metadata", 5, T.TYPE_MESSAGE, 1, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, T.TYPE_MESSAGE, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark.xplane.XSpace"))


def _stat_values(stats, stat_names: dict) -> dict:
    """{stat name: value}; a `ref_value` names another stat's name."""
    out = {}
    for st in stats:
        name = stat_names.get(st.metadata_id)
        if name is None:
            continue
        if st.ref_value:
            out[name] = stat_names.get(st.ref_value, "")
        else:
            out[name] = (st.str_value or st.int64_value or st.uint64_value
                         or st.double_value)
    return out


def load(path: str) -> dict:
    """The `.xplane.pb` as plain data:

    `host`: {line id: [(name, start_ns, duration_ns, {stat: value})]} of the
    `/host:CPU` plane, each line in order of start;
    `device`: {device number: {"modules": [(name, start, dur, stats)],
    "ops": [(short name, start, dur, tf_op)]}}.

    Times are ns on the trace's clock, as `trace_reduce.load` gives them."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    host: dict = {}
    device: dict = {}
    for plane in space.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if plane.name != HOST_PLANE and not m:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        for line in plane.lines:
            if m and line.name not in (trace_reduce.MODULE_LINE,
                                       trace_reduce.OPS_LINE):
                continue
            t0 = line.timestamp_ns
            events = []
            tf_ops: dict = {}
            for ev in line.events:
                md = meta.get(ev.metadata_id)
                if md is None:
                    continue
                start = t0 + ev.offset_ps // 1000
                dur = ev.duration_ps // 1000
                if m and line.name == trace_reduce.OPS_LINE:
                    tf_op = tf_ops.get(ev.metadata_id)
                    if tf_op is None:
                        tf_op = tf_ops[ev.metadata_id] = _stat_values(
                            md.stats, stat_names).get("tf_op", "")
                    events.append((trace_reduce.short_name(md.name), start,
                                   dur, tf_op))
                else:
                    events.append((md.name, start, dur,
                                   _stat_values(ev.stats, stat_names)))
            events.sort(key=lambda e: (e[1], -e[2]))
            if m:
                key = ("modules" if line.name == trace_reduce.MODULE_LINE
                       else "ops")
                device.setdefault(int(m.group(1)), {})[key] = events
            else:
                host[line.id] = events
    return {"host": host, "device": device}


def trace_of_this_process():
    """The loaded trace of the one `trace-*` directory this process wrote
    under `run.OUT_DIR`, or None: a `--trace 1` run removes its cell's
    directory before it starts the profiler, so what is there was written
    now. Loaded once a process."""
    return _trace_of(os.getpid())


@functools.lru_cache(maxsize=1)
def _trace_of(pid: int):
    from benchmark import run
    newest, newest_t = None, 0.0
    for d in glob.glob(os.path.join(run.OUT_DIR, "trace-*")):
        try:
            path = trace_reduce.find_xplane(d)
        except FileNotFoundError:
            continue
        t = os.path.getmtime(path)
        if t >= newest_t:
            newest, newest_t = path, t
    if newest is None:
        return None
    try:
        return load(newest)
    except Exception as e:        # noqa: BLE001 — no protobuf, a torn file
        print(f"[spans] trace not read: {e!r}", flush=True)
        return None


# -- host spans -----------------------------------------------------------

def batcher_lines(trace: dict) -> list:
    """The host lines that hold `qtpu.tick` spans: one per batcher worker
    thread. (The thread is named `qtpu-batcher-<model>`, but a line is
    named by the operating system's name of the thread, which Python
    before 3.14 does not set: a line is found by what it holds.)"""
    return [evs for evs in trace["host"].values()
            if any(e[0] == TICK for e in evs)]


def phases(trace: dict) -> list:
    """[(phase, start_ns, end_ns)] of every `qtpu.tick.<phase>` span on
    the batcher lines, by start."""
    out = [(e[0][len(PHASE_PREFIX):], e[1], e[1] + e[2])
           for evs in trace["host"].values() for e in evs
           if e[0].startswith(PHASE_PREFIX)]
    return sorted(out, key=lambda p: p[1])


def ticks(trace: dict) -> list:
    """[{start, end, args, phases: {phase: ns}}] of every whole
    `qtpu.tick` span, with the time of each phase inside it."""
    out = []
    for evs in batcher_lines(trace):
        ph = [(e[0][len(PHASE_PREFIX):], e[1], e[1] + e[2]) for e in evs
              if e[0].startswith(PHASE_PREFIX)]
        starts = [p[1] for p in ph]
        for name, s, d, args in evs:
            if name != TICK:
                continue
            inside: dict = {}
            i = bisect.bisect_left(starts, s)
            while i < len(ph) and ph[i][1] < s + d:
                inside[ph[i][0]] = (inside.get(ph[i][0], 0)
                                    + min(ph[i][2], s + d) - ph[i][1])
                i += 1
            out.append({"start": s, "end": s + d, "args": args,
                        "phases": inside})
    return sorted(out, key=lambda t: t["start"])


def device_offset_ns(trace: dict):
    """ns to ADD to a device time to put it on the host's clock: the least
    shift under which no program starts before the runtime enqueued it
    (module docstring). None without both sides of a `run_id`."""
    enqueued = {}
    for evs in trace["host"].values():
        for name, s, _, args in evs:
            if name == ENQUEUE and "run_id" in args:
                enqueued[str(args["run_id"])] = s
    shifts = [enqueued[str(args["run_id"])] - s
              for dev in trace["device"].values()
              for _, s, _, args in dev.get("modules", [])
              if str(args.get("run_id")) in enqueued]
    return max(shifts) if shifts else None


def idle_by_phase(trace: dict, min_gap_ns: int = trace_reduce.MIN_GAP_NS):
    """The device's idle gaps of at least `min_gap_ns`, each filed under
    the `qtpu.tick.<phase>` that covers most of it (on the host's clock):
    {"by_phase": {phase: seconds}, "unattributed_s", "short_gaps_s" (the
    pauses under `min_gap_ns`), "offset_ns"}. None where the trace holds
    no device operation or no phase."""
    devices = trace["device"]
    if not devices:
        return None
    ops = devices[min(devices)].get("ops") or devices[min(devices)].get(
        "modules") or []
    ph = phases(trace)
    if not ops or not ph:
        return None
    offset = device_offset_ns(trace) or 0
    merged = trace_reduce.union([(s, s + d) for _, s, d, _ in ops])
    starts = [p[1] for p in ph]
    by_phase: dict = {}
    unattributed = short = 0
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        gap = s1 - e0
        if gap <= 0:
            continue
        if gap < min_gap_ns:
            short += gap
            continue
        a, b = e0 + offset, s1 + offset
        cover: dict = {}
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(ph) and ph[i][1] < b:
            c = min(b, ph[i][2]) - max(a, ph[i][1])
            if c > 0:
                cover[ph[i][0]] = cover.get(ph[i][0], 0) + c
            i += 1
        if cover:
            best = max(cover, key=cover.get)
            by_phase[best] = by_phase.get(best, 0) + gap
        else:
            unattributed += gap
    return {"by_phase": {k: v / 1e9 for k, v in by_phase.items()},
            "unattributed_s": unattributed / 1e9,
            "short_gaps_s": short / 1e9, "offset_ns": offset}


# -- scopes ---------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def scope_names() -> frozenset:
    with open(os.path.join(HERE, "scopes.json")) as f:
        return frozenset(json.load(f)["scopes"])


def scope_of(tf_op: str) -> str:
    """The innermost `jax.named_scope` name of the program's own
    (`scopes.json`) on an operation's `tf_op` path
    (`jit(step)/…/layers/while/body/closed_call/mlp/dot_general:` → `mlp`;
    `jit(step)/…/layers/while:` → `layers`: what the scan itself emits)."""
    known = scope_names()
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part in known:
            return part
    return UNSCOPED


def scope_seconds(trace: dict, module_pattern: str):
    """{scope: seconds} of device time, nested operations taken out of
    their parents, over the executions of the programs whose name matches
    `module_pattern`; their sum is those programs' busy time. None where
    no such program ran."""
    rx = re.compile(module_pattern)
    total: dict = {}
    hit = False
    for dev in trace["device"].values():
        mods = sorted((s, s + d) for n, s, d, _ in dev.get("modules", [])
                      if rx.search(trace_reduce.module_name(n)))
        if not mods:
            continue
        hit = True
        m_starts = [s for s, _ in mods]
        inside = []
        for _, s, d, tf_op in dev.get("ops", []):
            i = bisect.bisect_right(m_starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                inside.append((scope_of(tf_op), s, d))
        for scope, ns in trace_reduce.exclusive(inside).items():
            total[scope] = total.get(scope, 0) + ns
    return {k: v / 1e9 for k, v in total.items()} if hit else None


# -- rows -----------------------------------------------------------------

def window_rows(ctx: dict):
    """The program's row records of the window's turns: for each turn of
    `ctx["ok"]` the entry of the program's ring with its session, submitted
    inside the turn (the harness's stamps lie around `query`, the
    record's inside it; both are `time.monotonic`). None where the program
    keeps no ring."""
    try:
        from quoracle_tpu.infra import introspect
        ring = introspect.row_ring()
    except (ImportError, AttributeError):
        return None
    by_session: dict = {}
    for r in ring:
        by_session.setdefault(r["session"], []).append(r)
    out = []
    for turn in ctx["ok"]:
        lo, hi = turn["t_submit"] * 1e9, turn["t_done"] * 1e9
        out += [r for r in by_session.get(turn.get("sid"), [])
                if lo <= r["t_submit_ns"] and r["t_done_ns"] <= hi]
    return out or None
