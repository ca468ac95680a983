"""Milliseconds a collective is in flight per round (chip where longest)."""


def read(ctx):
    rd = ctx["reduce"]
    windows = ctx["windows"]
    if not windows:
        return None
    per_dev = [
        rd.measure(rd.collective_intervals(dev, lo, hi)) / (periods * ctx["fuse"])
        for dev, lo, hi, periods in windows
    ]
    if max(per_dev) <= 0:
        return None
    return max(per_dev) / 1e6
