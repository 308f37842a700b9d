"""Device time of the operations whose name matches `op_pattern` (the
metric's file), over the device's busy time in the trace."""

from benchmark.trace_reduce import matching


def read(ctx, metric):
    t = ctx["trace"]
    if not t or not t["busy_s"]:
        return None
    hit = matching(t["ops"], metric["op_pattern"])
    if not hit:
        return None
    return 100.0 * sum(hit.values()) / t["busy_s"]
