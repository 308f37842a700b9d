"""Shared by the two readers of the decode step's device time."""

from benchmark.trace_reduce import matching


def decode_step_seconds(ctx, metric):
    t = ctx["trace"]
    if not t:
        return None
    mods = matching(t["modules"], metric["module_pattern"])
    busy = sum(m["busy_s"] for m in mods.values())
    # the operation that runs once per layer per step, counted inside the
    # decode programs only
    hits = sum(sum(matching(t["module_ops"].get(m, {}),
                            metric["step_op_pattern"]).values())
               for m in mods)
    steps = hits / int(ctx["config"]["num_hidden_layers"])
    if not busy or not steps:
        return None
    return busy / steps
