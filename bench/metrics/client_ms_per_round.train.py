"""Device self time of the client halves, the ops under the
``scala.client`` scope (``core/engine.py`` stage 2, ``_client_pullback``),
in ms per round of the traced window (``span_reduce.stage_s``)."""
from bench.span_reduce import stage_ms_per_round


def read(ctx):
    return stage_ms_per_round(ctx, "client")
