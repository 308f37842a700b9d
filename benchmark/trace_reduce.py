"""From a profiler trace (`.xplane.pb`) to what the per-layer readers use.

`load` turns the file into plain events, `reduce` turns events into numbers:
the traced window, the union of the intervals in which an operation ran on
each device (busy), device time per operation name and per compiled program
(module), executions per program, and the idle gaps with what stood on
either side. Nothing here knows a model or a cell; names and patterns live
in the metric files.

On a TPU the profiler writes one plane per chip ("/device:TPU:<n>") with a
line of whole-program executions ("XLA Modules") and a line of single
operations ("XLA Ops"); operations nest (a `while` spans its body), so busy
time is a union and per-name time is exclusive of nested children.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 50_000


def short_name(name: str) -> str:
    """A device operation's event carries its whole HLO line,
    `%fusion.3 = bf16[8,4096]{...} fusion(...)`: keep `%fusion.3`."""
    return name.split(" = ", 1)[0]


def load(path: str) -> list:
    """[(plane, line, name, start_ns, duration_ns)] of every event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, short_name(ev.name),
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def exclusive(events: list) -> dict:
    """name -> time in that operation itself, nested children taken out.
    events: [(name, start, dur)] of ONE line."""
    total: dict = {}
    stack: list = []                   # (end, name)
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            total[stack[-1][1]] = total.get(stack[-1][1], 0) - d
        total[name] = total.get(name, 0) + d
        stack.append((s + d, name))
    return total


def module_name(name: str) -> str:
    """`jit_step(123456)` -> `jit_step`: executions of one program share a
    name whatever its fingerprint."""
    return re.sub(r"\(.*\)$", "", name).strip()


def reduce(events: list, n_devices: int = 1) -> dict:
    planes: dict = {}
    for plane, line, name, s, d in events:
        m = DEVICE_PLANE.match(plane)
        if m:
            planes.setdefault(int(m.group(1)), {}).setdefault(
                line, []).append((name, s, d))
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0, "ops": {},
                "modules": {}, "gaps": [], "module_ops": {}}
    starts = [s for ls in planes.values() for evs in ls.values()
              for _, s, _ in evs]
    ends = [s + d for ls in planes.values() for evs in ls.values()
            for _, s, d in evs]
    w0, w1 = min(starts), max(ends)
    busy_ns, ops, modules, gaps = 0, {}, {}, []
    module_ops: dict = {}
    for dev, lines in sorted(planes.items()):
        op_events = lines.get(OPS_LINE) or lines.get(MODULE_LINE) or []
        merged = union([(s, s + d) for _, s, d in op_events])
        busy_ns += sum(e - s for s, e in merged)
        for name, t in exclusive(op_events).items():
            ops[name] = ops.get(name, 0) + t
        mods = sorted((s, s + d, module_name(n))
                      for n, s, d in lines.get(MODULE_LINE, []))
        for s, e, n in mods:
            m = modules.setdefault(n, {"count": 0, "ns": 0, "busy_ns": 0})
            m["count"] += 1
            m["ns"] += e - s
        # busy time inside each module execution (its operations' union)
        j = 0
        for s, e, n in mods:
            while j < len(merged) and merged[j][1] <= s:
                j += 1
            k = j
            while k < len(merged) and merged[k][0] < e:
                modules[n]["busy_ns"] += (min(e, merged[k][1])
                                          - max(s, merged[k][0]))
                k += 1
        # which program each operation ran in (by its start)
        m_starts = [s for s, _, _ in mods]
        for name, s, _ in op_events:
            i = bisect.bisect_right(m_starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                c = module_ops.setdefault(mods[i][2], {})
                c[name] = c.get(name, 0) + 1
        if dev == min(planes):
            gaps = _gaps(merged, mods, w0, w1)
    n = max(1, min(n_devices, len(planes)))
    return {"busy_s": busy_ns / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "devices": len(planes),
            "ops": {k: v / n / 1e9 for k, v in ops.items()},
            "module_ops": module_ops,
            "modules": {k: {"count": v["count"], "s": v["ns"] / 1e9,
                            "busy_s": v["busy_ns"] / 1e9}
                        for k, v in modules.items()},
            "gaps": gaps}


def _gaps(merged: list, mods: list, w0: int, w1: int) -> list:
    """Idle gaps of one device, by kind, the kind with most time first:
    `<program> -> <program>` is the host between two programs, `in
    <program>` a stall inside one; gaps under MIN_GAP_NS are the pauses
    between operations and go under one label."""
    m_starts = [s for s, _, _ in mods]

    def label(s, e):
        if e - s < MIN_GAP_NS:
            return "between operations (each under 50 us)"
        i = bisect.bisect_right(m_starts, (s + e) // 2) - 1
        if i >= 0 and (s + e) // 2 < mods[i][1]:
            return f"in {mods[i][2]}"
        j = bisect.bisect_right(m_starts, s) - 1
        before = mods[j][2] if j >= 0 else "window edge"
        k = bisect.bisect_left(m_starts, e)
        after = mods[k][2] if k < len(mods) else "window edge"
        return f"{before} -> {after}"

    out: dict = {}
    edges = [(w0, merged[0][0])] if merged else []
    edges += [(merged[i][1], merged[i + 1][0])
              for i in range(len(merged) - 1)]
    if merged:
        edges.append((merged[-1][1], w1))
    for s, e in edges:
        if e <= s:
            continue
        g = out.setdefault(label(s, e), {"s": 0.0, "count": 0,
                                         "longest_s": 0.0})
        g["s"] += (e - s) / 1e9
        g["count"] += 1
        g["longest_s"] = max(g["longest_s"], (e - s) / 1e9)
    return sorted(([k, v["s"], v["count"], v["longest_s"]]
                   for k, v in out.items()), key=lambda g: -g[1])


def breakdown(reduced: dict) -> dict:
    """The result line's `breakdown`: the ten operations with the most
    device time, and the ten kinds of idle gap with the most."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[g[0], g[1]] for g in reduced["gaps"][:10]]}


def matching(table: dict, pattern: str) -> dict:
    rx = re.compile(pattern)
    return {k: v for k, v in table.items() if rx.search(k)}
