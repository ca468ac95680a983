"""Share of device op self time under the given named scopes (mean over chips)."""


def read(ctx, scopes):
    rd = ctx["reduce"]
    windows = ctx["windows"]
    if not windows:
        return None
    shares = []
    for dev, lo, hi, _ in windows:
        by_scope = rd.self_time_by_scope(dev, lo, hi, ctx["scopes"])
        total = sum(by_scope.values())
        if total <= 0:
            return None
        shares.append(sum(by_scope[s] for s in scopes) / total)
    return 100.0 * sum(shares) / len(shares)
