"""Device time of the operations traced under the metric's `scopes`, over
the busy time of the programs whose name matches `module_pattern` — as
`scope_time_share`, but an operation is filed under the innermost name of
the METRIC FILE'S OWN list (`known_scopes`) on its `tf_op` path, so a
configuration whose program names scopes that `scopes.json` does not know
(a family's own layers inside `qkv` or `mlp`) brings them in its metric
files. A program that names none of `scopes` gives nothing."""

from benchmark import spans
from benchmark.readers._scope_paths import seconds_by_scope


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    if trace is None:
        return None
    by_scope = seconds_by_scope(trace, metric["module_pattern"],
                                metric["known_scopes"])
    if not by_scope or not any(s in by_scope for s in metric["scopes"]):
        return None
    total = sum(by_scope.values())
    print("[scopes] " + metric["name"] + " " + " ".join(
        f"{k}={100.0 * v / total:.2f}%" for k, v in
        sorted(by_scope.items(), key=lambda kv: -kv[1])), flush=True)
    return 100.0 * sum(by_scope.get(s, 0.0)
                       for s in metric["scopes"]) / total
