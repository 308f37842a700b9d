"""Decode rows riding a batcher step, mean over the window: each completed
turn rode ceil(completion_tokens / chunk) steps; the batcher's `steps`
counter says how many steps there were."""


def read(ctx, metric):
    steps = ctx["after"]["steps"] - ctx["before"]["steps"]
    if steps <= 0:
        return None
    chunk = ctx["after"]["chunk"]
    rode = sum(-(-r["completion_tokens"] // chunk) for r in ctx["ok"])
    return rode / steps
