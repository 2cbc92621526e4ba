"""Device self time of the optimizer step, the ops under the
``scala.update`` scope (``_apply_updates``, ``optim/``), in ms per round
of the traced window (``span_reduce.stage_s``). Reads 0.0 where the
update is fused into other stages' ops."""
from bench.span_reduce import stage_ms_per_round


def read(ctx):
    return stage_ms_per_round(ctx, "update")
