"""Host milliseconds of the named program spans' SELF time per round of
the window: a span's duration minus what the spans opened inside it, on
its thread, cover (``self_ms`` of the tracer's aggregates, PR 23)."""


def read(ctx, spans):
    rounds = ctx["window"]["completed"]
    found = [ctx["spans"][s]["self_ms"] for s in spans
             if "self_ms" in ctx["spans"].get(s, ())]
    if not found or not rounds:
        return None
    return sum(found) / rounds
