"""Device time of one decode step: the busy time of the decode program's
executions in the trace, over the decode steps they ran. A step is counted
by the operation that runs once per layer per step (`step_op_pattern`, the
attention kernel), so steps = its executions inside decode programs / the
configuration's layers."""

from benchmark.readers._decode import decode_step_seconds


def read(ctx, metric):
    s = decode_step_seconds(ctx, metric)
    return None if s is None else s * 1000.0
