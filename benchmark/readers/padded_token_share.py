"""Token slots the prefill programs processed that held no real token:
1 - real / padded, from the engine's padding counters over the window."""


def read(ctx, metric):
    padded = ctx["after"]["padded_tokens"] - ctx["before"]["padded_tokens"]
    if padded <= 0:
        return None
    real = ctx["after"]["real_tokens"] - ctx["before"]["real_tokens"]
    return 100.0 * (1.0 - real / padded)
