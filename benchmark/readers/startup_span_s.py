"""Seconds (or counts) of set-up from the program's start-up record.

The program's tracer keeps every span named ``setup.*``, ``compile.*``,
``init.*`` or ``obs.executables`` past ``drain()``, whole, with one
``compile`` entry per drain that saw compiles (the program's
``obs/spans.py``: ``Tracer.startup_record()``, found here through
``live_tracers()``). Set-up ends at the window's first dispatch, and
``check_reference`` calls ``init_state`` again after the window, so only
entries that ended before that instant count. The harness does not hand
the instant over: it is rebuilt from ``ctx["setup"]["setup_s"]`` (process
start to the window, on the host clock) and the process's age now.

A program without a start-up record (the parent of the PR that added it)
has nothing to read: every metric of this reader is then left out.

``read(ctx, ...)`` sums, over the entries before the window:

- ``spans``: durations of the entries with these names, minus those named
  in ``minus`` (``setup.experiment`` without its two data spans);
- ``program``: only entries whose ``program`` argument starts with it
  (``round.``: the round program among the registry's compiles);
- ``count``: that key of the entries (``cache_misses`` of ``compile``)
  in place of their durations;
- ``unattributed``: ``setup_s`` minus ``import_and_runtime_s``, the
  top-level ``setup.*`` spans of the main thread, ``first_dispatch_s`` and
  ``further_warmup_s``: what no span holds.
"""

import time

# the rebuilt instant is good to a few hundredths of a second (the
# kernel's clock tick, the harness's own imports); nothing that is kept
# ends inside the first half second of a window, and the reference's
# init_state starts a window's length after it
SLACK_S = 0.5


def window_start(ctx):
    """The window's first dispatch on the ``perf_counter`` clock."""
    from harness import window

    age = window.process_age_s()
    if age is None:
        return None
    return time.perf_counter() - age + ctx["setup"]["setup_s"] + SLACK_S


def startup_record(ctx):
    """The entries that ended before the window, or None."""
    try:
        from colearn_federated_learning_tpu.obs import spans
    except ImportError:
        return None
    live = getattr(spans, "live_tracers", None)
    cut = window_start(ctx) if live is not None else None
    if cut is None:
        return None
    return [e for tracer in live() for e in tracer.startup_record()
            if e["end"] <= cut] or None


def reduce(entries, setup, spans=(), minus=(), program="", count="",
           unattributed=False):
    if unattributed:
        top = sum(e["end"] - e["start"] for e in entries
                  if e["name"].startswith("setup.") and e["parent"] is None
                  and e["lane"] == 0)
        return (setup["setup_s"] - setup["import_and_runtime_s"] - top
                - setup["first_dispatch_s"] - setup["further_warmup_s"])

    def named(names):
        return [e for e in entries if e["name"] in names
                and str(e["args"].get("program", "")).startswith(program)]

    def total(found):
        return sum(e.get(count, 0) if count else e["end"] - e["start"]
                   for e in found)

    found = named(spans)
    if not found:
        return None
    return total(found) - total(named(minus))


def read(ctx, **args):
    entries = startup_record(ctx)
    if entries is None:
        return None
    return reduce(entries, ctx["setup"], **args)
