"""Share of the steady window with a collective in flight and no compute."""


def read(ctx):
    rd = ctx["reduce"]
    windows = ctx["windows"]
    if not windows:
        return None
    shares = []
    for dev, lo, hi, _ in windows:
        coll = rd.collective_intervals(dev, lo, hi)
        if not coll:
            return None
        exposed = rd.subtract(coll, rd.compute_intervals(dev, lo, hi))
        shares.append(rd.measure(exposed) / (hi - lo))
    return 100.0 * max(shares)
