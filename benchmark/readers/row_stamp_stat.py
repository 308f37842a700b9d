"""A statistic (`stat`: `p50` or `max`) of the time between two stamps
(`from`, `to`) of the program's own row records, over the window's turns
(`spans.window_rows`): `t_submit_ns <= t_admit_ns <= t_first_token_ns <=
t_done_ns`, monotonic ns. One reader, one data file a metric. The `[rows]`
line gives the rows found and, as a second source for
`batcher.queue_share_pct`, the ring's own sum of admit waits over the sum
of the turns' latencies."""

import json

from benchmark import spans, stats


def read(ctx, metric):
    rows = spans.window_rows(ctx)
    if not rows:
        return None
    ms = [(r[metric["to"]] - r[metric["from"]]) / 1e6 for r in rows
          if r[metric["to"]] and r[metric["from"]]]
    if not ms:
        return None
    value = max(ms) if metric["stat"] == "max" else stats.percentile(ms, 50)
    latency = sum(r["latency_ms"] for r in ctx["ok"])
    waited = sum(r["t_admit_ns"] - r["t_submit_ns"] for r in rows) / 1e6
    print("[rows] " + json.dumps({
        "metric": metric["name"], "rows": len(rows), "turns": len(ctx["ok"]),
        "value": value, "ring_queue_share_pct":
            100.0 * waited / latency if latency else None}), flush=True)
    return value
