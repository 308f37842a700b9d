"""Of the device's idle gaps of at least 50 us, the share of their time
that lies under a named `qtpu.tick.<phase>` of the batcher worker (the gap
goes to the phase that covers most of it, the device's clock first set
against the host's: `spans.device_offset_ns`). The phases tile the worker's
loop, so less than nearly all means a phase is missing. The `[gaps]` line
gives the seconds of idle per phase; a gap under one of the metric's
`runtime_latency` phases (the two fences) is the runtime's latency between
programs, not host Python, and is printed as such."""

import json

from benchmark import spans


def read(ctx, metric):
    trace = spans.trace_of_this_process()
    if trace is None:
        return None
    idle = spans.idle_by_phase(trace)
    if idle is None:
        return None
    attributed = sum(idle["by_phase"].values())
    total = attributed + idle["unattributed_s"]
    latency = set(metric["runtime_latency"])
    print("[gaps] " + json.dumps({
        "idle_s_by_phase": {
            (k + " (runtime latency)" if k in latency else k): v
            for k, v in sorted(idle["by_phase"].items(),
                               key=lambda kv: -kv[1])},
        "unattributed_s": idle["unattributed_s"],
        "between_operations_under_50us_s": idle["short_gaps_s"],
        "idle_s": total + idle["short_gaps_s"],
        "device_clock_offset_us": idle["offset_ns"] / 1000.0}), flush=True)
    if not total:
        return None
    return 100.0 * attributed / total
