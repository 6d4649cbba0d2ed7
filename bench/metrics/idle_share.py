"""Device: share of the traced window in which no operation ran on the
chip (1 minus the union of the operations' intervals over the window)."""


def read(ctx):
    if ctx["chip"] is None or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
