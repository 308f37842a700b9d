"""The device's idle time, split among the host operations that ran while
it waited (PR 37). On the host's clock (`spans.device_offset_ns`, as
`idle_attributed` does) every idle gap of the device of at least 50 us is
split BY EXACT OVERLAP: among the `qtpu.op.<name>` spans of the batcher's
line, an operation opened inside another taken out of it; what is left of
the gap among the `qtpu.tick.<phase>` spans, which is the phases' self time
(a phase less the operations inside it); and what no span covers is
`unnamed` (between `tick_close` and the next `tick_open`, or a thread that is
not the batcher's). The parts of a gap sum to the gap in whole ns, and the
reader asserts it.

One split a trace; the `[gaps-by-op]` line is printed once: per operation
its calls, the median self time of a call (us), its self time over the trace
(`busy_s`) and the idle time under it (`idle_s`); per phase the idle time
under its self time; `unnamed_s`; the ticks that had rows. A metric file
asks for one number with `stat`:

`idle_ms_per_tick`   all idle time in gaps >= 50 us, per tick with rows;
`ops_ms_per_tick`    the idle time under the operations of `ops` plus the
                     self time of the phases of `phases_self`, per tick;
`named_share_pct`    of that idle time, the part under any operation; the
                     self time of the phases of `not_host` (the empty
                     loop's wait: the chip waits for the callers there, and
                     no operation of a tick is missing) is left out of the
                     whole, as `batcher.tick_host_share_pct` leaves it out.

A program from before PR 37 opens no `qtpu.op.*` span: the first stat still
reads there (it needs the ticks and the device alone), the other two give
nothing."""

import bisect
import json
import statistics

from benchmark import spans, trace_reduce

OP_PREFIX = "qtpu.op."


def innermost(events) -> tuple:
    """[(name, start, end)] properly nested → ([(name, start, end)]
    disjoint pieces, each under the innermost span that covers it, by
    start; {name: [self ns of each span]})."""
    pieces, selfs, stack = [], {}, []      # stack: [name, end, cursor, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, cursor, own = stack.pop()
            if end > cursor:
                pieces.append((name, cursor, end))
            selfs.setdefault(name, []).append(own + end - cursor)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            top = stack[-1]
            e = min(e, top[1])             # a child ends with its parent
            if s > top[2]:
                pieces.append((top[0], top[2], s))
                top[3] += s - top[2]
            top[2] = max(top[2], s)
        stack.append([name, e, s, 0])
    close(float("inf"))
    return sorted(pieces, key=lambda p: p[1]), selfs


def overlap(pieces, starts, a: int, b: int, into: dict) -> int:
    """Add to `into[name]` the ns of [a, b) under each of the disjoint
    `pieces` (by start; `starts` their starts); returns the ns covered."""
    covered = 0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(pieces) and pieces[i][1] < b:
        c = min(b, pieces[i][2]) - max(a, pieces[i][1])
        if c > 0:
            into[pieces[i][0]] = into.get(pieces[i][0], 0) + c
            covered += c
        i += 1
    return covered


def split(trace: dict, min_gap_ns: int = trace_reduce.MIN_GAP_NS):
    """{"idle_ns", "by_op": {op: ns}, "by_phase_self": {phase: ns},
    "unnamed_ns", "ticks", "ops": {op: [self ns a call]}, "offset_ns"},
    or None where the trace holds no device operation or no tick."""
    devices = trace["device"]
    if not devices:
        return None
    dev = devices[min(devices)]
    busy = dev.get("ops") or dev.get("modules") or []
    lines = spans.batcher_lines(trace)
    if not busy or not lines:
        return None
    # one worker's line: two workers' phases overlap, and a gap under both
    # would be counted twice (no cell runs two batchers)
    worker = max(lines, key=len)
    # phases tile the tick and an operation lies inside one phase, so the
    # two kinds nest: a piece under a phase's own name is its self time
    cover, selfs = innermost(
        [(("op", n[len(OP_PREFIX):]), s, s + d) for n, s, d, _ in worker
         if n.startswith(OP_PREFIX)]
        + [(("phase", n[len(spans.PHASE_PREFIX):]), s, s + d)
           for n, s, d, _ in worker if n.startswith(spans.PHASE_PREFIX)])
    starts = [p[1] for p in cover]
    offset = spans.device_offset_ns(trace) or 0
    merged = trace_reduce.union([(s, s + d) for _, s, d, _ in busy])
    under: dict = {}
    idle = named = 0
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        if s1 - e0 < min_gap_ns:
            continue
        idle += s1 - e0
        named += overlap(cover, starts, e0 + offset, s1 + offset, under)
    assert named == sum(under.values()) <= idle, (named, idle)
    # (a TraceMe argument of 0 is read back as 0.0, not "0")
    ticks = sum(float(t["args"].get("rows") or 0) > 0
                for t in spans.ticks(trace))
    return {"idle_ns": idle, "unnamed_ns": idle - named, "ticks": ticks,
            "by_op": {n: ns for (k, n), ns in under.items() if k == "op"},
            "by_phase_self": {n: ns for (k, n), ns in under.items()
                              if k == "phase"},
            "ops": {n: v for (k, n), v in selfs.items() if k == "op"},
            "offset_ns": offset}


def split_once(trace: dict):
    """The split of this trace, kept on the trace (`trace_of_this_process`
    hands every reader the same one); its line is printed the first time."""
    if "gap_by_op" not in trace:
        got = trace["gap_by_op"] = split(trace)
        if got is not None:
            print("[gaps-by-op] " + json.dumps(line(got)), flush=True)
    return trace["gap_by_op"]


def line(got: dict) -> dict:
    names = sorted(got["ops"], key=lambda o: -got["by_op"].get(o, 0))
    return {
        "ticks_with_rows": got["ticks"], "idle_s": got["idle_ns"] / 1e9,
        "by_op": {o: {"calls": len(got["ops"][o]),
                      "median_us": statistics.median(got["ops"][o]) / 1e3,
                      "busy_s": sum(got["ops"][o]) / 1e9,
                      "idle_s": got["by_op"].get(o, 0) / 1e9}
                  for o in names},
        "idle_s_by_phase_self": {
            p: ns / 1e9 for p, ns in sorted(
                got["by_phase_self"].items(), key=lambda kv: -kv[1])},
        "unnamed_s": got["unnamed_ns"] / 1e9,
        "device_clock_offset_us": got["offset_ns"] / 1e3}


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    if trace is None:
        return None
    got = split_once(trace)
    if got is None or not got["ticks"]:
        return None
    stat = metric["stat"]
    if stat == "idle_ms_per_tick":
        return got["idle_ns"] / 1e6 / got["ticks"]
    if not got["ops"] or not got["idle_ns"]:
        return None                       # a program with no qtpu.op.* span
    if stat == "named_share_pct":
        whole = got["idle_ns"] - sum(got["by_phase_self"].get(p, 0)
                                     for p in metric.get("not_host", []))
        return 100.0 * sum(got["by_op"].values()) / whole if whole else None
    if stat == "ops_ms_per_tick":
        ns = (sum(got["by_op"].get(o, 0) for o in metric["ops"])
              + sum(got["by_phase_self"].get(p, 0)
                    for p in metric.get("phases_self", [])))
        return ns / 1e6 / got["ticks"]
    raise ValueError(f"gap_by_op: unknown stat {stat!r}")
