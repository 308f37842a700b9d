"""Mean duration (ms) of a named host span over the traced window (PR 37):
every event named `span` on any line of the `/host:CPU` plane (a client's
thread, not the batcher's: `qtpu.session_drop` lies on the line of whoever
called `drop_session`). The `[<line_tag>]` line gives the count, the sum, the
longest, the mean, and the mean of the span's argument `arg`; where the
metric file names a `histogram` of the program, its count and sum over the
WHOLE run beside them (the harness's `[drops]` line covers the whole run too).
Nothing to read (a program that opens no such span, a window in which none
ended) gives nothing."""

import json

from benchmark import spans


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    if trace is None:
        return None
    found = [(d, args) for evs in trace["host"].values()
             for name, _, d, args in evs if name == metric["span"]]
    said = {"metric": metric["name"], "span": metric["span"],
            "count": len(found)}
    if found:
        ms = [d / 1e6 for d, _ in found]
        arg = metric.get("arg")
        said.update(sum_ms=sum(ms), max_ms=max(ms), mean_ms=sum(ms) / len(ms))
        if arg:
            said[f"mean_{arg}"] = (sum(float(a.get(arg, 0)) for _, a in found)
                                   / len(found))
    if metric.get("histogram"):
        try:
            from quoracle_tpu.infra.telemetry import METRICS
            _, total, n = METRICS.histogram(metric["histogram"]).counts()
            said["whole_run"] = {"histogram": metric["histogram"],
                                 "count": n, "sum_ms": total}
        except (ImportError, AttributeError):
            pass
    print(f"[{metric['line_tag']}] " + json.dumps(said), flush=True)
    return said.get("mean_ms")
