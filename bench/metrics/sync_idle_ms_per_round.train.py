"""Device idle time inside the ``trainer.sync`` host span (the host
reading the round's metrics, ``Trainer.step``), in ms per round of the
traced window (``span_reduce.span_idle_s``). None where the trace has no
such span."""
from bench.span_reduce import span_ms_per_round


def read(ctx):
    return span_ms_per_round(
        ctx, lambda s: s.span_idle_s.get("trainer.sync"))
