"""Share of the turns' latency spent queued for a batcher slot: the growth
of the program's own admit-wait histogram (`quoracle_sched_admit_wait_ms`,
submit -> admitted into a tick) over the window, against the sum of the
window's turn latencies. (`QueryResult.prefill_ms / decode_ms` are 0 under
the continuous batcher, so the split cannot be read per row.)"""


def read(ctx, metric):
    total = sum(r["latency_ms"] for r in ctx["ok"])
    if not total:
        return None
    waited = (ctx["after"]["admit_wait_ms_sum"]
              - ctx["before"]["admit_wait_ms_sum"])
    return 100.0 * waited / total
