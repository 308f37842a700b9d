#!/usr/bin/env python3
"""One run of a cell as `benchmark.run` makes it, reporting also the
per-layer metrics of `span_metrics.json` — those that read the program's
tick spans, row ring and scope names (PR 24). The builder's tool for their
first readings: no run of the benchmark calls it.

    python3 -m benchmark.with_spans --workload <cell> --seed <n> \
                                    --seconds <s> --trace 1

`run.py` reports the metrics a cell's traffic file names, and a PR that adds
a metric edits no file that is there. So this appends the list to the
traffic file's own in memory and changes nothing else: the same server,
warm-up, traffic, window, trace and result line. The benchmark PR that admits
the metrics appends the same list to `traffic/*.json` and one entry each to
`BENCHMARK.json`, and this file goes.

With `--trace 1` it also says what the profiler session costs: the program's
own phase counter (`quoracle_tick_phase_ms_total`) is read when the session
starts, when it stops and the same time again later, and a `[tracing]` line
gives the worker's time, by kind of phase, with the session open and in the
untraced stretch of the same window right after it.

And it times what a client does BETWEEN turns, which no latency sees: a
`[drops]` line (on stderr, after the result) gives the calls of `backend.drop_session` (a traffic mix's
`drop`: before its next turn a client releases the sessions that ended) and
the time they took — the engine's drop waits for the lock the batcher holds
through a whole tick.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run, traffic      # noqa: E402


def watch_tracing(model: str) -> None:
    """Wrap the profiler's start and stop so that the phase counter is
    read at both, and once more as long after the stop as the session
    lasted; then print the `[tracing]` line."""
    import threading
    import time

    import jax
    from quoracle_tpu.infra.telemetry import TICK_PHASE_MS_TOTAL, TICK_PHASES
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    marks: list = []

    def read() -> dict:
        return {"t": time.monotonic(),
                "ms": {p: TICK_PHASE_MS_TOTAL.value(model=model, phase=p)
                       for p in TICK_PHASES}}

    def per_tick(a: dict, b: dict) -> dict:
        d = {p: b["ms"][p] - a["ms"][p] for p in TICK_PHASES}
        device = d["wait_prefill"] + d["wait_decode"]
        return {"seconds": round(b["t"] - a["t"], 3),
                "device_wait_ms": round(device, 1),
                "idle_ms": round(d["idle"], 1),
                "host_ms": round(sum(d.values()) - device - d["idle"], 1),
                "host_ms_by_phase": {p: round(v, 2) for p, v in d.items()
                                     if v and not p.startswith("wait_")
                                     and p != "idle"}}

    def later() -> None:
        time.sleep(marks[1]["t"] - marks[0]["t"])
        marks.append(read())
        run.say("tracing", {"session_open": per_tick(marks[0], marks[1]),
                            "right_after": per_tick(marks[1], marks[2])})

    def start_trace(*a, **k):
        marks.append(read())
        return start(*a, **k)

    def stop_trace(*a, **k):
        out = stop(*a, **k)
        marks.append(read())
        threading.Thread(target=later, daemon=True).start()
        return out

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace


def watch_drops() -> list:
    """Time every `TPUBackend.drop_session`; returns the list the times
    (seconds) are appended to."""
    import time

    from quoracle_tpu.models.runtime import TPUBackend
    times: list = []
    drop = TPUBackend.drop_session

    def timed(self, *args, **kwargs):
        t = time.monotonic()
        try:
            return drop(self, *args, **kwargs)
        finally:
            times.append(time.monotonic() - t)

    TPUBackend.drop_session = timed
    return times


def main(argv=None) -> int:
    with open(os.path.join(HERE, "span_metrics.json")) as f:
        more = json.load(f)["per_layer"]
    load = traffic.load_traffic

    def with_more(name: str) -> dict:
        mix = load(name)
        mix["per_layer"] = mix["per_layer"] + [
            m for m in more if m not in mix["per_layer"]]
        return mix

    traffic.load_traffic = with_more
    drops = watch_drops()
    args = run.parser("").parse_args(argv)
    if args.trace:
        real, tiny, _ = run.load_cells()
        cell = real.get(args.workload) or tiny.get(args.workload)
        if cell is not None:      # the model's name is its configuration's
            watch_tracing(cell["config"])
    try:
        return run.main(argv)
    finally:
        traffic.load_traffic = load
        # on stderr: the result stays the last line of stdout
        print("[drops] " + json.dumps({
            "calls": len(drops), "total_s": round(sum(drops), 3),
            "max_ms": round(1000 * max(drops, default=0.0), 1),
            "over_100_ms": sum(t > 0.1 for t in drops),
            "note": "whole run, lead-in included"}), file=sys.stderr,
            flush=True)


if __name__ == "__main__":
    sys.exit(main())
