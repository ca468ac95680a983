"""Device op self time under the given named scopes per round (busiest chip)."""


def read(ctx, scopes):
    rd = ctx["reduce"]
    windows = ctx["windows"]
    if not windows:
        return None
    per_dev = []
    for dev, lo, hi, periods in windows:
        by_scope = rd.self_time_by_scope(dev, lo, hi, ctx["scopes"])
        per_dev.append(sum(by_scope[s] for s in scopes) / (periods * ctx["fuse"]))
    return max(per_dev) / 1e6
