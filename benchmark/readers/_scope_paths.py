"""Shared by the readers that file device operations under a metric file's
own list of scope names."""

import bisect
import re

from benchmark import spans, trace_reduce


def seconds_by_scope(trace: dict, module_pattern: str, known: list):
    """{scope: seconds} of device time, nested operations taken out of
    their parents, over the executions of the programs matching
    `module_pattern`; an operation's scope is the innermost name of
    `known` on its `tf_op` path. None where no such program ran."""
    known = frozenset(known)

    def scope_of(tf_op: str) -> str:
        for part in reversed(tf_op.rstrip(":").split("/")):
            if part in known:
                return part
        return spans.UNSCOPED

    rx = re.compile(module_pattern)
    total: dict = {}
    hit = False
    for dev in trace["device"].values():
        mods = sorted((s, s + d) for n, s, d, _ in dev.get("modules", [])
                      if rx.search(trace_reduce.module_name(n)))
        if not mods:
            continue
        hit = True
        starts = [s for s, _ in mods]
        inside = []
        for _, s, d, tf_op in dev.get("ops", []):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                inside.append((scope_of(tf_op), s, d))
        for scope, ns in trace_reduce.exclusive(inside).items():
            total[scope] = total.get(scope, 0) + ns
    return {k: v / 1e9 for k, v in total.items()} if hit else None
