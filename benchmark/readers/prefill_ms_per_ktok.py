"""Device time of prefill per thousand real prompt tokens: the mean busy
time of a prefill program's execution in the trace, over the mean real
tokens a tick prefilled in the window (the engine's padding counters:
real tokens / ticks)."""

from benchmark.trace_reduce import matching


def read(ctx, metric):
    t = ctx["trace"]
    if not t:
        return None
    mods = matching(t["modules"], metric["module_pattern"])
    calls = sum(m["count"] for m in mods.values())
    ticks = ctx["after"]["ticks"] - ctx["before"]["ticks"]
    real = ctx["after"]["real_tokens"] - ctx["before"]["real_tokens"]
    if not calls or ticks <= 0 or real <= 0:
        return None
    per_call_s = sum(m["busy_s"] for m in mods.values()) / calls
    return per_call_s * 1000.0 / (real / ticks / 1000.0)
