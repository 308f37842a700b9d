#!/usr/bin/env python3
"""What a trace holds of the program's own names, for reading it by hand:
the builder's tool beside `describe_trace.py` (which lists planes and
lines). No run of the benchmark calls it.

    python3 -m benchmark.run --workload <cell> --seed 1 --seconds 20 --trace 1
    python3 -m benchmark.describe_spans <cell> FILE

Writes to FILE: every line of the `/host:CPU` plane with its event count
and its most expensive names (the batcher's line is the one that holds
`qtpu.tick`); every `qtpu.*` name with its count, its time and the
arguments of its first event; the device's clock offset against the
host's; per program, the time under each scope (`spans.scope_seconds`) and
the most expensive operations with their `tf_op` — where the scope names of
`scopes.json` and the `scopes` lists of the metric files come from. Beside
it, as `FILE.spans.json.gz`, a short slice of the trace in the form
`spans.load` gives: a recorded trace small enough to keep as a test's
fixture.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import spans, trace_reduce      # noqa: E402


def describe(trace: dict, top: int = 25) -> dict:
    lines = {}
    names: dict = {}
    for line_id, evs in trace["host"].items():
        by_name: dict = {}
        for name, _, d, args in evs:
            n = by_name.setdefault(name, [0, 0])
            n[0] += 1
            n[1] += d
            if name.startswith("qtpu."):
                q = names.setdefault(name, {"count": 0, "seconds": 0.0,
                                            "first_args": args})
                q["count"] += 1
                q["seconds"] += d / 1e9
        lines[str(line_id)] = {
            "events": len(evs),
            "holds_qtpu_tick": any(e[0] == spans.TICK for e in evs),
            "top": sorted(([n, c, t / 1e9] for n, (c, t) in by_name.items()),
                          key=lambda x: -x[2])[:top]}
    programs: set = set()
    ops: dict = {}
    for dev in trace["device"].values():
        programs.update(trace_reduce.module_name(m[0])
                        for m in dev.get("modules", []))
        for name, _, d, tf_op in dev.get("ops", []):
            o = ops.setdefault(name, [0, 0, tf_op])
            o[0] += 1
            o[1] += d
    return {
        "host_lines": lines, "qtpu_names": names,
        "device_clock_offset_ns": spans.device_offset_ns(trace),
        "idle_by_phase": spans.idle_by_phase(trace),
        "scope_seconds_by_program": {
            p: spans.scope_seconds(trace, "^" + p + "$")
            for p in sorted(programs)},
        # inclusive times: a `while` holds its body
        "top_ops": sorted(([n, c, t / 1e9, spans.scope_of(tf), tf]
                           for n, (c, t, tf) in ops.items()),
                          key=lambda x: -x[2])[:2 * top]}


def short_slice(trace: dict, seconds: float) -> dict:
    """`seconds` of the trace from the start of the first whole
    `qtpu.tick` that rode rows: the batcher's phases and the runtime's
    enqueues on the host, programs and operations on the device."""
    ticks = [t for t in spans.ticks(trace)
             if str(t["args"].get("rows", "0")) != "0"]
    if not ticks:
        return {"host": {}, "device": {}}
    t0 = ticks[0]["start"]
    t1 = t0 + int(seconds * 1e9)

    def keep(evs, names=None):
        return [list(e) for e in evs if t0 <= e[1] and e[1] + e[2] <= t1
                and (names is None or e[0].startswith(names))]
    return {"host": {str(k): kept for k, evs in trace["host"].items()
                     if (kept := keep(evs, ("qtpu.", spans.ENQUEUE)))},
            "device": {str(k): {kind: keep(evs) for kind, evs in dev.items()}
                       for k, dev in trace["device"].items()}}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    cell, out = argv
    trace = spans.load(trace_reduce.find_xplane(os.path.join(
        ROOT, ".bench_out", f"trace-{cell}")))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(describe(trace), f, indent=1, default=str)
    with gzip.open(out + ".spans.json.gz", "wt") as f:
        json.dump(short_slice(trace, 1.5), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
