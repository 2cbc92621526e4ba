"""Device busy time (union of device op intervals) per round of the
traced window.
Read in every training cell (they report ``round_s``)."""


def read(ctx):
    s = ctx["trace"]
    if s is None or not ctx["rounds"] or s.busy_s <= 0:
        return None
    return 1000.0 * s.busy_s / ctx["rounds"]
