"""Device self time of the ops outside every ``scala.*`` scope (such as
the image rows' gather from the device pool), in ms per round of the
traced window (``span_reduce.unscoped_s``). With the five stages it
sums to about ``device_ms_per_round.train`` (the busy union)."""
from bench.span_reduce import stage_ms_per_round


def read(ctx):
    return stage_ms_per_round(ctx, None)
