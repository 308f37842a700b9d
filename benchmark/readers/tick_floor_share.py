"""A kernel's share of its roofline over the traced ticks: the least time
the configuration's family reckons for the work the program says it did
(`tick_args` of the `qtpu.tick` spans, summed tick by tick; the family's
function `<floor>_floor_s(config, *args, peaks)`), over the kernel's device
time in the trace. The kernel's time is that of the operations matching
`op_pattern`, or of the operations under `scopes` (filed by the metric
file's `known_scopes`) in the programs matching `module_pattern`. The
floor is a lower bound, so the share cannot pass 100. A program that
writes no such argument, or a family without the function, gives
nothing."""

from benchmark import spans
from benchmark.readers._scope_paths import seconds_by_scope
from benchmark.trace_reduce import matching


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    floor = getattr(ctx["family"], metric["floor"] + "_floor_s", None)
    if trace is None or floor is None or not ctx["peaks"] or not ctx["trace"]:
        return None
    if "op_pattern" in metric:
        spent = sum(matching(ctx["trace"]["ops"],
                             metric["op_pattern"]).values())
    else:
        by_scope = seconds_by_scope(trace, metric["module_pattern"],
                                    metric["known_scopes"]) or {}
        spent = sum(by_scope.get(s, 0.0) for s in metric["scopes"])
    least = 0.0
    for tick in spans.ticks(trace):
        try:
            args = [float(tick["args"][a]) for a in metric["tick_args"]]
        except (KeyError, ValueError):
            continue
        least += floor(ctx["config"], *args, ctx["peaks"])
    if not spent or not least:
        return None
    return 100.0 * least / spent
