"""Device self time of the round around the local steps, the ops under
the ``scala.fed`` scope (``make_round_runner``, ``fed/``: masks,
gather and scatter, the local loop, aggregation), in ms per round of the
traced window (``span_reduce.stage_s``)."""
from bench.span_reduce import stage_ms_per_round


def read(ctx):
    return stage_ms_per_round(ctx, "fed")
