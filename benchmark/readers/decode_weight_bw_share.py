"""How close a decode step comes to the time the chip needs just to read
the weights: (the weight bytes a step must read, which the configuration's
family reckons from its shapes, over the device's peak HBM bandwidth) over
the device time of a decode step. The weight bytes are a lower bound on
what a step must read, so this cannot pass 100."""

from benchmark.readers._decode import decode_step_seconds


def read(ctx, metric):
    step_s = decode_step_seconds(ctx, metric)
    if step_s is None or not ctx["peaks"]:
        return None
    floor_s = (ctx["family"].decode_weight_bytes(ctx["config"])
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / step_s
