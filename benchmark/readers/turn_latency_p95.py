"""The 95th percentile of the window's turn latencies, by the harness's own
clock around `query`. A per-layer number and not an end-to-end one: a
window completes 120 to 165 turns, so the 95th percentile is the middle of
the dozen 128-token turns (or of six requests in `cold-prompts`), and its
runs spread by more than half of the widest bound the contract allows
(PERF.md section 2). The tail is made in the batcher: waiting for a slot
and for the chunk under way."""

from benchmark import stats


def read(ctx, metric):
    lat = [r["latency_ms"] for r in ctx["ok"]]
    return stats.percentile(lat, 95) if lat else None
