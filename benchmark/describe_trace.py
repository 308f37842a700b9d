#!/usr/bin/env python3
"""What a trace holds, for reading it by hand. The builder's tool for
finding the names a new metric's file needs: no run of the benchmark calls
it.

    python3 -m benchmark.run --workload <cell> --seed 1 --seconds 20 --trace 1
    python3 -m benchmark.describe_trace <cell> FILE

A traced run leaves its trace in `.bench_out/trace-<cell>/` until the next
one. This writes every plane and line of it to FILE with its event count,
its time and its most expensive names — where `^%ragged_attend` (the
attention kernel's custom call) and `step_paged_decode_ragged$` (the decode
program) in the metric files come from — and, beside it, a quarter second
of the device planes' events as `FILE.events.json.gz`: a recorded trace
small enough to keep as a test's fixture.
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

from benchmark.trace_reduce import (DEVICE_PLANE, MODULE_LINE,    # noqa: E402
                                    OPS_LINE, find_xplane, load)


def describe(events: list, top: int = 40) -> dict:
    """What a trace holds, for reading it by hand: every plane and line
    with its event count and time, and each line's most expensive names."""
    lines: dict = {}
    for plane, line, name, s, d in events:
        e = lines.setdefault(f"{plane} | {line}", {"events": 0, "ns": 0,
                                                   "names": {}})
        e["events"] += 1
        e["ns"] += d
        n = e["names"].setdefault(name, [0, 0])
        n[0] += 1
        n[1] += d
    return {k: {"events": v["events"], "seconds": v["ns"] / 1e9,
                "top": sorted(([n, c, t / 1e9]
                               for n, (c, t) in v["names"].items()),
                              key=lambda x: -x[2])[:top]}
            for k, v in lines.items()}


def device_slice(events: list, seconds: float) -> list:
    """The device planes' events of the first `seconds` after the first
    program starts: a small recorded trace to check the reduction on."""
    dev = [e for e in events if DEVICE_PLANE.match(e[0])
           and e[1] in (MODULE_LINE, OPS_LINE)]
    mods = [e for e in dev if e[1] == MODULE_LINE]
    if not mods:
        return []
    t0 = min(e[3] for e in mods)
    return [e for e in dev if t0 <= e[3] and e[3] + e[4] <= t0 + seconds * 1e9]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    cell, out = argv
    events = load(find_xplane(os.path.join(ROOT, ".bench_out",
                                           f"trace-{cell}")))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(describe(events), f, indent=1)
    with gzip.open(out + ".events.json.gz", "wt") as f:
        json.dump(device_slice(events, 0.25), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
