"""Phase-span tracer for the round lifecycle.

Design constraints, in order:

1. **Off is free.** With ``run.obs.spans=false`` a ``span()`` call
   returns a shared no-op context manager — no clock reads, no
   allocation — so the round loop's hot path pays one attribute check.
2. **On is cheap.** An enabled span is two ``perf_counter`` reads, a
   push and a pop on its thread's own stack, one
   ``jax.profiler.TraceAnnotation`` (a flag check while no profiler
   session runs) and one dict update under a lock (spans fire from the
   fit loop AND the stream-prefetch worker thread). Chrome-trace event
   objects are only built when ``run.obs.trace=true``.
3. **Drain-at-flush.** The driver drains per-phase aggregates at its
   metrics-flush boundaries and logs ONE ``spans`` record per window —
   the JSONL stays one-line-per-round-scale, not one-line-per-span.

Counts ride spans: ``Tracer.count(name, **counts)`` adds whole numbers
to the aggregate of the span of that name, and ``drain()`` reports their
sums beside its ``count`` / ``total_ms`` (the block trainer's
client-steps, dead and skipped, on ``round.host_inputs.slab_build``), so
a ratio is measured where the work is laid out.

One clock: while a ``jax.profiler`` session runs (``--profile N``, the
benchmark's traced run) every enabled span is also an event of its name
on the ``/host:CPU`` plane of that trace, on the thread that opened it
and with its arguments as stats — beside the device planes, so a gap
between device ops can be laid against what the program was doing.

Work split from wait: each thread keeps a stack of its open spans, so a
span knows its parent and the aggregates carry ``self_ms`` — a span's
duration minus what the spans opened inside it, on the same thread,
cover. The ``round`` argument names the request: a span opened inside
one that carries it carries it too, and the prefetch worker sets it to
the first round of the dispatch its entry is for.

Retrace attribution: ``jax.monitoring`` fires a
``.../backend_compile_duration`` event for every XLA compilation; a
module-level listener forwards those into every live tracer, so an
unexpected mid-run retrace shows up as a ``compile`` pseudo-phase in
the same window it stalled (and as a timeline block in the trace).
The persistent cache's ``/jax/compilation_cache/cache_hits`` and
``cache_misses`` events are counted beside it (``compile`` carries
``cache_hits`` / ``cache_misses``), and every span that is open on the
compiling thread counts the compiles that ran inside it.

What is kept past ``drain()``: the start-up record. A process pays its
set-up once, before the first flush window means anything, and a reader
that drains at its window's start (the benchmark does) would throw it
away; a compile in the middle of a run is as rare and has to say which
program and which round it belonged to. So every span whose name starts
with ``setup.``, ``compile.`` or ``init.``, or is ``obs.executables``,
is, besides the aggregate, appended whole to a list that lives as long
as the tracer (:meth:`Tracer.startup_record`: name, start, end, self
time, arguments, parent, lane, and the compiles counted inside it), and
each ``drain()`` that saw compiles appends its ``compile`` pseudo-phase
there too. The list is bounded by construction: tens of entries for a
set-up, two per program the executable registry compiles later. Hot
spans (``round.*``) are never kept.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence

# exported-trace size past which export() warns once: multi-GB
# trace.json files load poorly (or not at all) in Perfetto and are
# almost always an unintended artifact of a very long traced run
TRACE_SIZE_WARN_BYTES = 256 * 2**20


class _NullSpan:
    """Shared no-op span: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args):
        pass

    cache = None


_NULL_SPAN = _NullSpan()

# live tracers the jax.monitoring compile listener forwards into; weak
# so finished Experiments don't accumulate across a process's lifetime
_ACTIVE: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_LISTENER_INSTALLED = False


# the persistent compilation cache's two outcomes (jax 0.9.0:
# _src/compiler.py fires the hit, _src/compilation_cache.py the miss
# where it writes the new entry); a program compiled with the cache off
# fires neither
# (slots of a span's ``_compiles``: compiles, seconds, hits, misses)
_HIT, _MISS = 2, 3
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": _HIT,
                 "/jax/compilation_cache/cache_misses": _MISS}

# names the start-up record keeps (module docstring)
_KEPT_PREFIXES = ("setup.", "compile.", "init.")
_KEPT_NAMES = ("obs.executables",)


def _on_event_duration(event, duration, **kw):
    if "backend_compile" not in event:
        return
    for tracer in list(_ACTIVE):
        tracer._note_compile(float(duration))


def _on_event(event, **kw):
    slot = _CACHE_EVENTS.get(event)
    if slot is None:
        return
    for tracer in list(_ACTIVE):
        tracer._note_cache(slot)


def live_tracers() -> List["Tracer"]:
    """The enabled tracers alive in this process: how a reader that was
    not handed the ``Experiment`` (the benchmark's per-layer readers)
    finds the start-up record."""
    return list(_ACTIVE)


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    _LISTENER_INSTALLED = True  # never retry a failed install per call
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:
        pass  # no jax / no monitoring API: spans still work, no retrace attribution


# span arguments that pass from a span to the spans opened inside it on
# the same thread: the identifier that spans of one dispatch share
_INHERITED_ARGS = ("round",)


def _startup_entry(name, start, end, self_s, args, parent, lane,
                   compiles) -> Dict[str, Any]:
    entry = {"name": name, "start": start, "end": end, "self_s": self_s,
             "args": dict(args) if args else {}, "parent": parent,
             "lane": lane}
    if compiles is not None:
        entry.update(compiles=int(compiles[0]), compile_s=float(compiles[1]),
                     cache_hits=int(compiles[2]),
                     cache_misses=int(compiles[3]))
    return entry


class _Lane:
    """One thread's open spans, and its lane in the Chrome trace."""

    __slots__ = ("index", "stack")

    def __init__(self, index: int):
        self.index = index
        self.stack: List["_Span"] = []

    def count(self, slot: int, amount=1) -> None:
        """Books a compile event of this thread on its innermost open
        span (its ``_compiles``; the span passes it outwards as it
        closes)."""
        if self.stack:
            inside = self.stack[-1]
            if inside._compiles is None:
                inside._compiles = [0, 0.0, 0, 0]
            inside._compiles[slot] += amount


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_start", "_lane",
                 "_children", "_annotation", "_compiles")

    def __init__(self, tracer: "Tracer", name: str, args=None):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        tracer = self._tracer
        self._lane = lane = tracer._lane()
        if lane.stack:
            above = lane.stack[-1]._args or {}
            passed = {k: above[k] for k in _INHERITED_ARGS if k in above}
            if passed:
                self._args = {**passed, **(self._args or {})}
        lane.stack.append(self)
        self._children = 0.0
        # [compiles, seconds, cache hits, cache misses] of the programs
        # jax compiled on this thread while the span was open; None
        # until the first one
        self._compiles = None
        self._annotation = tracer._annotate(self._name, **(self._args or {}))
        self._annotation.__enter__()
        self._start = tracer._clock()
        return self

    def note(self, **args) -> None:
        """Arguments known only once the span's work is done. They
        reach the aggregate's record and the Chrome event; the
        profiler's annotation was written at entry and lacks them."""
        self._args = {**(self._args or {}), **args}

    @property
    def cache(self) -> str:
        """What the persistent compilation cache said of the programs
        compiled inside this span so far: ``miss`` if any was written
        anew, else ``hit`` if any was loaded, else ``off``."""
        c = self._compiles
        if c is None or not (c[_HIT] or c[_MISS]):
            return "off"
        return "miss" if c[_MISS] else "hit"

    def __exit__(self, *exc):
        end = self._tracer._clock()
        self._annotation.__exit__(*exc)
        stack = self._lane.stack
        stack.pop()
        dur = end - self._start
        compiles = self._compiles
        parent = None
        if stack:
            above = stack[-1]
            parent = above._name
            above._children += dur
            if compiles is not None:
                held = above._compiles or (0, 0.0, 0, 0)
                above._compiles = [a + b for a, b in zip(held, compiles)]
        self._tracer._record(self._name, self._start, dur,
                             dur - self._children, self._lane.index,
                             self._args, parent, compiles)
        return False


class Tracer:
    """Aggregating span tracer with optional Chrome-trace export.

    ``span(name)`` is a context manager; nesting is expressed naturally
    (a child span's interval lies inside its parent's) and survives into
    the exported trace because complete ("X") events on the same thread
    track stack in Perfetto's flame view.
    """

    def __init__(self, enabled: bool = True, trace: bool = False, clock=None,
                 max_events: int = 0, process_index: int = 0):
        self.enabled = enabled
        self.trace = trace and enabled
        # multi-process runs: the process index IS the Chrome-trace pid,
        # so each host gets its own lane group in Perfetto and
        # :meth:`export` can merge per-host fragments into one timeline
        # (os.getpid() would collide semantics across re-runs and says
        # nothing about WHICH host a lane belongs to)
        self.process_index = int(process_index)
        self._clock = clock or time.perf_counter
        self._lock = threading.Lock()
        # name -> [count, total_s, max_s, self_s]
        self._agg: Dict[str, List[float]] = {}
        # name -> {what: sum} of the counts that ride that span
        self._counts: Dict[str, Dict[str, int]] = {}
        # per-thread span stacks; a thread's lane index is the order in
        # which it first opened a span here (the Chrome trace's tid)
        self._local = threading.local()
        self._lanes = 0
        self._events: List[Dict[str, Any]] = []
        # cap on accumulated Chrome-trace events (run.obs.
        # trace_max_events): long runs otherwise grow trace.json without
        # bound. 0 = unlimited; past the cap events are DROPPED with one
        # warning — the per-phase aggregates keep counting everything.
        self._max_events = int(max_events)
        self._truncated = False
        self._size_warned = False
        self._t0 = self._clock()
        self._compiles = 0
        self._compile_secs = 0.0
        self._compile_max = 0.0
        self._cache_hits = 0
        self._cache_misses = 0
        # the start-up record (module docstring): never cleared
        self._startup: List[Dict[str, Any]] = []
        self._compile_kept_to = self._t0
        if enabled:
            from jax.profiler import TraceAnnotation

            self._annotate = TraceAnnotation
            _install_listener()
            _ACTIVE.add(self)

    # ------------------------------------------------------------------

    def span(self, name: str, **args):
        """``args`` annotate the span (e.g. ``span("round.dispatch",
        fuse=10)``): they ride into the Chrome-trace event's ``args``
        dict so the timeline shows per-chunk attributes; the per-phase
        aggregates stay keyed by name only (one stable phase taxonomy
        regardless of attribute values)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args or None)

    def note_past(self, name: str, start: float, end: float, **args) -> None:
        """A phase that was over before this tracer existed, timed by
        whoever saw it on this tracer's clock (the driver module's own
        import, ``setup.import``): it enters the aggregates and the
        start-up record as a top-level span of the calling thread."""
        if self.enabled:
            self._record(name, start, end - start, end - start,
                         self._lane().index, args or None)

    def count(self, name: str, **counts: int) -> None:
        """Adds ``counts`` to what the span ``name`` has counted since
        the last :meth:`drain`, which reports the sums in that span's
        entry (module docstring)."""
        if not self.enabled:
            return
        with self._lock:
            # a drain between the span's end and this call: the entry
            # still has every key its readers expect
            self._agg.setdefault(name, [0, 0.0, 0.0, 0.0])
            held = self._counts.setdefault(name, {})
            for what, n in counts.items():
                held[what] = held.get(what, 0) + int(n)

    def _lane(self) -> _Lane:
        lane = getattr(self._local, "lane", None)
        if lane is None:
            with self._lock:
                lane = self._local.lane = _Lane(self._lanes)
                self._lanes += 1
        return lane

    def _record(self, name: str, start: float, dur: float, self_s: float,
                lane: int, args=None, parent: Optional[str] = None,
                compiles: Optional[List[float]] = None) -> None:
        kept = None
        if name.startswith(_KEPT_PREFIXES) or name in _KEPT_NAMES:
            kept = _startup_entry(name, start, start + dur, self_s, args,
                                  parent, lane, compiles)
        with self._lock:
            if kept is not None:
                self._startup.append(kept)
            agg = self._agg.get(name)
            if agg is None:
                self._agg[name] = [1, dur, dur, self_s]
            else:
                agg[0] += 1
                agg[1] += dur
                if dur > agg[2]:
                    agg[2] = dur
                agg[3] += self_s
            if self.trace:
                event = {
                    "name": name,
                    "ph": "X",
                    "pid": self.process_index,
                    "tid": lane,
                    "ts": (start - self._t0) * 1e6,  # µs, run-relative
                    "dur": dur * 1e6,
                }
                if args:
                    event["args"] = args
                self._append_event(event)

    def _note_compile(self, duration: float) -> None:
        lane = self._lane()  # the compiling thread's
        lane.count(0)
        lane.count(1, duration)
        lane = lane.index
        with self._lock:
            self._compiles += 1
            self._compile_secs += duration
            if duration > self._compile_max:
                self._compile_max = duration
            if self.trace:
                now = self._clock()
                self._append_event({
                    "name": "compile",
                    "ph": "X",
                    "pid": self.process_index,
                    "tid": lane,
                    # the monitoring hook fires at compile END; back-date
                    # the block so the timeline shows when it ran
                    "ts": max(0.0, (now - self._t0 - duration)) * 1e6,
                    "dur": duration * 1e6,
                })

    def _note_cache(self, slot: int) -> None:
        """One program's outcome at the persistent compilation cache
        (``slot`` ``_HIT``: loaded, ``_MISS``: compiled and written),
        on the thread that asked for it."""
        self._lane().count(slot)
        with self._lock:
            if slot == _HIT:
                self._cache_hits += 1
            else:
                self._cache_misses += 1

    def _append_event(self, event: Dict[str, Any]) -> None:
        """Append one Chrome-trace event under the event cap (caller
        holds the lock). Warn ONCE when the cap truncates the trace."""
        if self._max_events and len(self._events) >= self._max_events:
            if not self._truncated:
                self._truncated = True
                logging.getLogger(__name__).warning(
                    "trace event cap reached (%d events): further trace "
                    "events are dropped — raise run.obs.trace_max_events "
                    "(or set 0 for unbounded) if you need the full "
                    "timeline; span aggregates are unaffected",
                    self._max_events,
                )
            return
        self._events.append(event)

    # ------------------------------------------------------------------

    def compile_stats(self) -> tuple:
        """Non-draining snapshot of the backend_compile listener's
        counters since the last :meth:`drain`: ``(count, total_secs)``.
        The driver brackets a dispatch with two snapshots to attribute
        compiles to the shape bucket that triggered them (the
        per-bucket retrace accounting of ``run.shape_buckets``)."""
        with self._lock:
            return self._compiles, self._compile_secs

    def startup_record(self) -> List[Dict[str, Any]]:
        """The start-up record (module docstring), oldest first: one
        entry per kept span, ``{name, start, end, self_s, args, parent,
        lane}`` on this tracer's clock (``time.perf_counter`` unless one
        was given), with ``compiles``, ``compile_s``, ``cache_hits`` and
        ``cache_misses`` where jax compiled programs inside it; and one
        entry named ``compile`` per :meth:`drain` that saw compiles,
        from the previous such entry's end to that drain. ``drain()``
        does not clear it."""
        with self._lock:
            return list(self._startup)

    def drain(self) -> Dict[str, Dict[str, float]]:
        """Return and reset the per-phase aggregates since the last
        drain: ``{phase: {count, total_ms, max_ms, self_ms}}`` and what
        :meth:`count` added to a phase beside them, with compiles
        (retraces included) reported as the ``compile`` pseudo-phase
        (which nothing nests in: all of it is self time; it also counts
        the persistent cache's ``cache_hits`` and ``cache_misses``)."""
        with self._lock:
            agg, self._agg = self._agg, {}
            counts, self._counts = self._counts, {}
            compiles, self._compiles = self._compiles, 0
            csecs, self._compile_secs = self._compile_secs, 0.0
            cmax, self._compile_max = self._compile_max, 0.0
            hits, self._cache_hits = self._cache_hits, 0
            misses, self._cache_misses = self._cache_misses, 0
            if compiles:
                now = self._clock()
                self._startup.append(_startup_entry(
                    "compile", self._compile_kept_to, now, csecs, None,
                    None, None, (compiles, csecs, hits, misses)))
                self._compile_kept_to = now
        out = {
            name: {
                "count": int(c),
                "total_ms": round(t * 1000.0, 3),
                "max_ms": round(m * 1000.0, 3),
                "self_ms": round(own * 1000.0, 3),
            }
            for name, (c, t, m, own) in sorted(agg.items())
        }
        for name, held in counts.items():
            out[name].update(held)
        if compiles:
            out["compile"] = {
                "count": compiles,
                "total_ms": round(csecs * 1000.0, 3),
                "max_ms": round(cmax * 1000.0, 3),
                "self_ms": round(csecs * 1000.0, 3),
                "cache_hits": hits,
                "cache_misses": misses,
            }
        return out

    def export(self, path: str,
               fragments: Sequence[str] = ()) -> Optional[str]:
        """Write the accumulated Chrome-trace events as a Perfetto-
        loadable ``trace.json`` (open at ui.perfetto.dev or
        chrome://tracing). Returns the path, or None when tracing is
        off. Events are NOT cleared — export is an end-of-run dump.

        ``fragments`` are sibling trace files written by OTHER
        processes of a multi-host run (the driver's ``trace.p<i>.json``
        per-host exports): their events are merged into this export so
        the timeline shows one lane group per host instead of silently
        reflecting process 0 only. Unreadable fragments are skipped —
        a host that crashed before exporting must not take down the
        survivors' merged trace."""
        if not self.trace:
            return None
        with self._lock:
            events = list(self._events)
        for frag in fragments:
            try:
                with open(frag) as f:
                    frag_events = json.load(f).get("traceEvents", [])
            except (OSError, ValueError):
                continue
            events.extend(
                e for e in frag_events if e.get("ph") != "M"
            )
        lanes = sorted({e.get("pid", 0) for e in events} | {self.process_index})
        doc = {
            "displayTimeUnit": "ms",
            "traceEvents": [
                *({"ph": "M", "pid": pid, "name": "process_name",
                   "args": {"name": f"colearn host {pid} round lifecycle"}}
                  for pid in lanes),
                *events,
            ],
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        try:
            size = os.path.getsize(path)
        except OSError:
            size = 0
        if size > TRACE_SIZE_WARN_BYTES and not self._size_warned:
            # warn once: multi-GB traces from long runs are almost
            # never intentional and stall (or crash) the trace viewer
            self._size_warned = True
            logging.getLogger(__name__).warning(
                "exported trace %s is %.1f MiB (> %.0f MiB): long runs "
                "produce very large traces — lower run.obs."
                "trace_max_events or trace a shorter run",
                path, size / 2**20, TRACE_SIZE_WARN_BYTES / 2**20,
            )
        return path
