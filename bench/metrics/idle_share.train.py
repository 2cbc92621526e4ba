"""Share of the traced window in which no operation ran on the device.
Read in every training cell (they report ``round_s``)."""


def read(ctx):
    s = ctx["trace"]
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
