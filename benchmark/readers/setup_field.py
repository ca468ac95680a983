"""A field of the set-up split (``ctx["setup"]``)."""


def read(ctx, field):
    return ctx["setup"].get(field)
