"""Device busy milliseconds per round in the steady window, busiest chip."""


def read(ctx):
    rd = ctx["reduce"]
    windows = ctx["windows"]
    if not windows:
        return None
    return max(
        rd.measure(rd.busy_intervals(dev, lo, hi)) / (periods * ctx["fuse"])
        for dev, lo, hi, periods in windows
    ) / 1e6
