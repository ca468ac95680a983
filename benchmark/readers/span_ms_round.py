"""Host milliseconds of the named program spans per round of the window."""


def read(ctx, spans):
    rounds = ctx["window"]["completed"]
    found = [ctx["spans"][s]["total_ms"] for s in spans if s in ctx["spans"]]
    if not found or not rounds:
        return None
    return sum(found) / rounds
