"""A count that rides a program span (``Tracer.count``, reported by
``drain()`` beside the span's ``count`` / ``total_ms``) as a percentage
of another count of the same span over the window. ``None`` where the
program keeps no such counts."""


def read(ctx, span, count, of):
    entry = ctx["spans"].get(span, {})
    if count not in entry or not entry.get(of):
        return None
    return 100.0 * entry[count] / entry[of]
