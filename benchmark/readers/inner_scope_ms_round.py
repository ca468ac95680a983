"""Device op self time per round under the scopes INSIDE one of the
round program's outer scopes.

An op's outer scope is ``scope_of_op``'s (``harness/trace_reduce.py``).
Its inner scope is the innermost component of its ``op_name`` path whose
name, stripped of transform wrappers, is one of ``scopes``: under the
megabatch ``vmap`` the trainer's ``local_grad`` is the component
``vmap(local_grad)``, and a transposed region is
``transpose(vmap(local_grad))``. An op without one (the copies the
compiler puts into a loop carry no ``op_name``) takes that of the
instruction whose body it runs in, up to the instruction that carries the
outer scope. ``part="backward"`` keeps the ops at or beneath a
``transpose(...)`` component from the inner scope on (the backward pass
of ``jax.grad``; the forward pass sits under ``jvp(...)`` alone).
``complement`` sums what lies under ``outer`` and under none of
``scopes``, so that readings over a partition of the inner scopes add up
to ``scope_ms_round`` of ``outer``. All readings are of ONE chip, the one
with most self time under ``outer`` (``scope_ms_round``'s). None where no
op of the trace carries any of ``scopes`` (a program from before PR 23)."""

import functools
import re

WRAPPER = re.compile(r"^(\w+)\((.*)\)$")


def unwrap(component):
    """(``local_grad``, [``vmap``, ``transpose``, ``jvp``]) from
    ``vmap(transpose(jvp(local_grad)))``."""
    wrappers = []
    m = WRAPPER.match(component)
    while m:
        wrappers.append(m.group(1))
        component = m.group(2)
        m = WRAPPER.match(component)
    return component, wrappers


@functools.lru_cache(maxsize=None)
def inner_in_path(path, scopes):
    """(inner scope, beneath a transpose?) from one ``op_name`` path,
    or None where it has none of ``scopes``."""
    parts = path.split("/")
    for i in range(len(parts) - 1, -1, -1):
        name = unwrap(parts[i])[0]
        if name in scopes:
            return name, any("transpose" in unwrap(p)[1] for p in parts[i:])
    return None


def inner_of_op(op, outer_scopes, scopes):
    """(inner scope or "", beneath a transpose?) of a trace op."""
    while op is not None:
        found = inner_in_path(op.scope, scopes)
        if found is not None:
            return found
        if any(p in outer_scopes for p in op.scope.split("/")):
            break  # the instruction that carries the outer scope
        op = op.parent
    return "", False


def read(ctx, scopes, outer="round_local_train", part=None, complement=False):
    if part not in (None, "backward") or (part and complement):
        raise ValueError(f"part={part!r} complement={complement!r}")
    rd = ctx["reduce"]
    windows = ctx["windows"]
    scopes = tuple(scopes)
    if not windows or not any(
        inner_in_path(path, scopes)
        for path in {op.scope for dev, *_ in windows for op in dev.ops}
    ):
        return None
    per_dev = []
    for dev, lo, hi, periods in windows:
        under_outer = chosen = 0.0
        for op in dev.ops:
            if op.end <= lo or op.start >= hi or op.dur <= 0:
                continue
            if rd.scope_of_op(op, ctx["scopes"]) != outer:
                continue
            # as self_time_by_scope: a straddling op counts by its share
            ns = op.self_ns * (min(op.end, hi) - max(op.start, lo)) / op.dur
            under_outer += ns
            inner, backward = inner_of_op(op, ctx["scopes"], scopes)
            if (bool(inner) != complement
                    and (part is None or backward)):
                chosen += ns
        per_dev.append((under_outer, chosen / (periods * ctx["fuse"])))
    return max(per_dev)[1] / 1e6
