"""A ratio of two arguments of the `qtpu.tick` spans (PR 37): the sum of
`numerator` over the sum of `denominator`, times `scale`, over the traced
window's ticks that carry both (`spans.ticks`: what the engine notes on a
tick where the work happens). A program that does not note them gives
nothing."""

from benchmark import spans


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    if trace is None:
        return None
    above = below = 0.0
    for t in spans.ticks(trace):
        a = t["args"].get(metric["numerator"])
        b = t["args"].get(metric["denominator"])
        if a is None or b is None:
            continue
        above += float(a)
        below += float(b)
    if not below:
        return None
    return float(metric.get("scale", 1.0)) * above / below
