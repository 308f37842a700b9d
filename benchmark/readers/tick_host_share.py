"""Share of the batcher worker's time that is host work between device
programs: on its thread's line of the trace, the time in `qtpu.tick.<phase>`
spans other than the metric's `not_host` phases (the two device fences and
the empty loop's wait), over the time in whole `qtpu.tick` spans."""

from benchmark import spans


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    if trace is None:
        return None
    ticks = spans.ticks(trace)
    wall = sum(t["end"] - t["start"] for t in ticks)
    if not wall:
        return None
    host = sum(ns for t in ticks for phase, ns in t["phases"].items()
               if phase not in metric["not_host"])
    return 100.0 * host / wall
