"""1 - busy/window over the steady window, on the idlest chip."""


def read(ctx):
    rd = ctx["reduce"]
    windows = ctx["windows"]
    if not windows:
        return None
    return 100.0 * max(
        1.0 - rd.measure(rd.busy_intervals(dev, lo, hi)) / (hi - lo)
        for dev, lo, hi, _ in windows
    )
