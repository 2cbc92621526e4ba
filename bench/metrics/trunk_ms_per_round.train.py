"""Device self time of the server trunk, the ops under the
``scala.trunk`` scope (``_server_vjp``, ``_dual_pullbacks``: forward and
both pullbacks), in ms per round of the traced window
(``span_reduce.stage_s``)."""
from bench.span_reduce import stage_ms_per_round


def read(ctx):
    return stage_ms_per_round(ctx, "trunk")
