"""Device idle milliseconds per round, by what the program's dispatching
thread was doing meanwhile.

The idle time is ``device_idle_pct``'s: the steady window minus the union
of op intervals, on the idlest chip. Each idle interval is laid over the
program's ``round.*`` spans on the thread that holds ``bench.dispatch``
(``harness/host_spans.py``) and counts, by overlap, under the innermost
span open then. ``spans`` names the spans to sum; ``complement`` sums the
idle time under any other ``round.*`` span or none instead, so that the
two readings add up to the chip's idle time. None where the trace holds
no ``round.*`` span (a program from before PR 23)."""

from harness import host_spans


def read(ctx, spans, complement=False):
    rd = ctx["reduce"]
    windows = ctx["windows"]
    path = host_spans.find_trace(ctx["bench_dir"], ctx["cell"]["name"])
    if not windows or path is None:
        return None
    host = host_spans.load(path)
    if not any(s.name.startswith(host_spans.PROGRAM) for s in host):
        return None
    idlest = None  # (idle share, idle intervals, periods): device_idle_pct's chip
    for dev, lo, hi, periods in windows:
        idle = rd.subtract([(lo, hi)], rd.busy_intervals(dev, lo, hi))
        share = rd.measure(idle) / (hi - lo)
        if idlest is None or share > idlest[0]:
            idlest = (share, idle, periods)
    _, idle, periods = idlest
    by_span = host_spans.idle_by_span(idle, host)
    named = sum(by_span.get(s, 0.0) for s in spans)
    ns = rd.measure(idle) - named if complement else named
    return ns / (periods * ctx["fuse"]) / 1e6
