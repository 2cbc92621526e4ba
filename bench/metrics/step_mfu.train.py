"""Model FLOP utilization of the whole round program: the matmul FLOPs
one round requires (the cell's model file, ``round_flops``) times the
rounds in the traced window, over the window and the chip's bf16 peak.
Read in every training cell (they report ``round_s``)."""


def read(ctx):
    s = ctx["trace"]
    if s is None or s.window_s <= 0 or not ctx["rounds"]:
        return None
    return (100.0 * ctx["flops_per_round"] * ctx["rounds"]
            / s.window_s / ctx["peak"].flops_bf16)
