"""A ratio of the program's own counters (`infra/telemetry.METRICS`), read
after the window: the metric file names the `numerator` counter and the
`denominator` counters (summed), each with its labels; the label `model` is
the configuration's name. The counters run from the start of the process
(`ctx` holds no edge readings of them), so warm-up and lead-in are in the
ratio: it is a property of the routing, which they share with the window.
A program without these counters reads zero over zero and gives nothing."""


def _value(ctx, spec) -> float:
    from quoracle_tpu.infra.telemetry import METRICS
    return METRICS.counter(spec["counter"]).value(
        model=ctx["config"]["name"], **spec["labels"])


def read(ctx, metric):
    try:
        below = sum(_value(ctx, s) for s in metric["denominator"])
        above = _value(ctx, metric["numerator"])
    except (ImportError, AttributeError):
        return None
    if not below:
        return None
    return float(metric["scale"]) * above / below
