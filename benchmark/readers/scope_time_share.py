"""Device time of the operations traced under the metric's `scopes` (the
innermost `jax.named_scope` name on each operation's `tf_op` path,
`benchmark/scopes.json`), over the busy time of the programs whose name
matches `module_pattern`. One reader, one data file a class: the scopes are
data. `layers` alone, with no sub-scope, is what the layer scan itself
emits: its slices of the stacked weights (and, before PR 25, of the pools).
A `[scopes]` line gives every scope's share, `(unscoped)` among them."""

from benchmark import spans


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    if trace is None:
        return None
    by_scope = spans.scope_seconds(trace, metric["module_pattern"])
    if not by_scope:
        return None
    total = sum(by_scope.values())
    if not total or set(by_scope) == {spans.UNSCOPED}:
        return None               # a program that names no scope
    print("[scopes] " + metric["name"] + " " + " ".join(
        f"{k}={100.0 * v / total:.2f}%" for k, v in
        sorted(by_scope.items(), key=lambda kv: -kv[1])), flush=True)
    return 100.0 * sum(by_scope.get(s, 0.0)
                       for s in metric["scopes"]) / total
