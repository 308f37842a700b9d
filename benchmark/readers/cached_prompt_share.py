"""Prompt tokens served from resident KV (session resume or a prefix-cache
hit) over all prompt tokens of the window's turns."""


def read(ctx, metric):
    prompt = sum(r["prompt_tokens"] for r in ctx["ok"])
    if not prompt:
        return None
    return 100.0 * sum(r["cached_tokens"] for r in ctx["ok"]) / prompt
