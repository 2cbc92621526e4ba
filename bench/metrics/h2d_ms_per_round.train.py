"""Time of the runtime's host-to-device path (the union of its
relayout, dispatch and DMA events on the host planes), in ms per round
of the traced window (``span_reduce.h2d_s``). None where the runtime
logged no transfer."""
from bench.span_reduce import span_ms_per_round


def read(ctx):
    return span_ms_per_round(
        ctx, lambda s: s.h2d_s if s.h2d_events else None)
