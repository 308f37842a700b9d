"""Shared by the two readers of the decode step's device time."""

from benchmark.trace_reduce import matching


def decode_step_seconds(ctx, metric):
    t = ctx["trace"]
    if not t:
        return None
    mods = matching(t["modules"], metric["module_pattern"])
    busy = sum(m["busy_s"] for m in mods.values())
    # the operation the configuration's family says marks a step, and how
    # often it runs in one, counted inside the decode programs only
    mark = ctx["family"].decode_step_mark(ctx["config"])
    hits = sum(sum(matching(t["module_ops"].get(m, {}),
                            mark["op_pattern"]).values())
               for m in mods)
    steps = hits / mark["per_step"]
    if not busy or not steps:
        return None
    return busy / steps
