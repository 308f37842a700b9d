"""Device time of one decode step: the busy time of the decode program's
executions in the trace, over the decode steps they ran. A step is counted
by the operation the configuration's family names for it
(`decode_step_mark`: for the dense family the attention kernel, once a
layer), so steps = its executions inside decode programs / its runs a
step."""

from benchmark.readers._decode import decode_step_seconds


def read(ctx, metric):
    s = decode_step_seconds(ctx, metric)
    return None if s is None else s * 1000.0
