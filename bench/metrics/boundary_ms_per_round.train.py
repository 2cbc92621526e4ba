"""Device self time of the split boundary, the ops under the
``scala.boundary`` scope (the priors and the eq. 14/15 losses with their
gradients, ``kernels/lace``), in ms per round of the traced window
(``span_reduce.stage_s``)."""
from bench.span_reduce import stage_ms_per_round


def read(ctx):
    return stage_ms_per_round(ctx, "boundary")
