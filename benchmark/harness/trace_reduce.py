"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.

What the trace looks like on the TPU v5e of this installation (jax 0.9.0,
libtpu 0.0.34; looked at by hand in PR 22, PERF.md section 3):

- one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Modules`` has
  one event per executed program, named ``jit_round_fn(<fingerprint>)``;
  its line ``XLA Ops`` one event per executed HLO instruction, nested
  where an instruction has a body (``while``); its line ``Async XLA
  Ops`` one event per asynchronous pair, from ``*-start`` to ``*-done``.
- an op event's *name* is the instruction's whole HLO text,
  ``%fusion.2230 = (f32[16,64,64]{...}, ...) fusion(...), kind=kOutput,
  calls=...``, without its ``metadata={op_name=...}``; its stats are
  ``device_offset_ps``, ``device_duration_ps`` only. So no event carries
  the ``jax.named_scope`` path. The reduction takes the instruction name
  (``fusion.2230``) from the event and joins it against the ``op_name``
  metadata of the compiled program's own text (``scopes_from_hlo``; the
  program's executable registry holds the compiled object).
- a Pallas kernel is a custom call that XLA names after the innermost
  scope it was traced under: the fused server apply is
  ``%round_server_apply.<n>``.
- host threads are lines of ``/host:CPU``; a
  ``jax.profiler.TraceAnnotation`` is an event of its name there, on the
  same clock as the device lines.

Event times are nanoseconds as the trace has them.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
INSTRUCTION = re.compile(r"^%?([^\s=]+)")
HLO_OP_NAME = re.compile(
    r"^\s*(?:ROOT\s+)?%?([^\s=]+) = .*metadata=\{[^}]*op_name=\"([^\"]*)\"",
    re.MULTILINE,
)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.|$)"
)
# instructions whose event only brackets the events of their body
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")

Interval = Tuple[float, float]


@dataclass
class Op:
    name: str
    start: float
    end: float
    scope: str = ""       # full framework path, "" if the program has none
    self_ns: float = 0.0  # duration minus the events nested inside
    parent: Optional["Op"] = field(default=None, repr=False, compare=False)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class DeviceTrace:
    ordinal: int
    ops: List[Op] = field(default_factory=list)
    modules: List[Op] = field(default_factory=list)
    async_ops: List[Op] = field(default_factory=list)  # start -> done spans


@dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Op]  # TraceAnnotations and other named host events


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def scopes_from_hlo(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} from a compiled program's text
    (``compiled.as_text()``): the path of ``jax.named_scope``s each
    instruction was traced under. A fusion carries its root's."""
    return dict(HLO_OP_NAME.findall(hlo_text))


def instruction_name(event_name: str) -> str:
    """``fusion.2230`` from ``%fusion.2230 = (...) fusion(...)``."""
    return INSTRUCTION.match(event_name).group(1)


def load(path: str, op_names: Optional[Dict[str, str]] = None,
         host_names: Sequence[str] = ("bench.",)) -> Trace:
    """Device ops and modules of every ``/device:TPU:<n>`` plane, each op
    with its ``op_name`` from ``op_names`` (see ``scopes_from_hlo``), and
    the host events whose name starts with one of ``host_names``."""
    from jax.profiler import ProfileData

    op_names = op_names or {}
    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    host: List[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTrace(int(m.group(1)))
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    into = dev.ops if line.name == OPS_LINE else dev.async_ops
                    for e in line.events:
                        name = instruction_name(e.name)
                        into.append(Op(
                            name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns),
                            op_names.get(name, ""),
                        ))
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        dev.modules.append(Op(
                            e.name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns),
                        ))
            dev.ops.sort(key=lambda o: (o.start, -o.end))
            dev.modules.sort(key=lambda o: o.start)
            _fill_self_times(dev.ops)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tuple(host_names)):
                        host.append(Op(
                            e.name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns),
                        ))
    devices.sort(key=lambda d: d.ordinal)
    host.sort(key=lambda o: o.start)
    return Trace(devices, host)


def _fill_self_times(ops: List[Op]) -> None:
    """``self_ns`` = duration minus directly nested events, ``parent`` =
    the event an op is nested in (the ``while`` whose body it runs in).
    ``ops`` is sorted by (start, -end), so a parent precedes its children."""
    stack: List[Op] = []
    for op in ops:
        op.self_ns = op.dur
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.dur
            op.parent = stack[-1]
        stack.append(op)


# -- interval arithmetic -----------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both are unions (sorted, disjoint)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- what the readers ask for ------------------------------------------


def steady_window(dev: DeviceTrace, program: str) -> Optional[Tuple[float, float, int]]:
    """(start, end, periods): from the start of the second execution of
    ``program`` in the trace to the start of the last — a whole number
    of dispatch periods, each holding one execution and the gap before
    the next, so that time the device waits for the host between
    programs counts exactly once per period. The first execution is left
    out: a dispatch is always in flight when the profiler starts, and its
    event begins where the trace begins, not where the execution began
    (PR 22: 1,502 ms for an execution of 1,552 ms). None with fewer than
    three executions."""
    runs = [m for m in dev.modules if m.name.startswith(program)][1:]
    if len(runs) < 2:
        return None
    return runs[0].start, runs[-1].start, len(runs) - 1


def steady_windows(trace: Trace, program: str):
    """[(device, start, end, periods), ...] for every device of the
    trace, or None where there is no device or one has no steady
    window: the readers then have nothing to read."""
    out = []
    for dev in trace.devices:
        w = steady_window(dev, program)
        if w is None:
            return None
        out.append((dev,) + w)
    return out or None


def busy_intervals(dev: DeviceTrace, lo: float, hi: float) -> List[Interval]:
    return union(clip(((o.start, o.end) for o in dev.ops), lo, hi))


def scope_of_op(op: Op, scopes: Sequence[str]) -> str:
    """The first of ``scopes`` that is a component of the op's path,
    searching from the innermost component outwards. An op that has none
    (the copies the compiler puts into a loop carry no ``op_name``: a
    sixth of ``vit_silo_dp``'s device time, PR 22) counts under the scope
    of the instruction whose body it runs in; "" if that has none either."""
    while op is not None:
        for part in reversed(op.scope.split("/")):
            if part in scopes:
                return part
        op = op.parent
    return ""


def self_time_by_scope(dev: DeviceTrace, lo: float, hi: float,
                       scopes: Sequence[str]) -> Dict[str, float]:
    """Nanoseconds of op self time inside [lo, hi) per named scope; ops
    under none of ``scopes`` are summed under "". Containers (``while``)
    contribute only their own overhead, their bodies' ops count under
    the bodies' own scopes."""
    out: Dict[str, float] = {s: 0.0 for s in scopes}
    out[""] = 0.0
    for op in dev.ops:
        if op.end <= lo or op.start >= hi or op.dur <= 0:
            continue
        # an op that straddles the window edge counts by its share
        share = (min(op.end, hi) - max(op.start, lo)) / op.dur
        out[scope_of_op(op, scopes)] += op.self_ns * share
    return out


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def collective_intervals(dev: DeviceTrace, lo: float, hi: float) -> List[Interval]:
    """Union of the time a collective is in flight on this device: a
    synchronous collective's own event; for an asynchronous pair, from
    the start of ``<kind>-start`` to the end of the next ``<kind>-done``
    (pairs of one kind complete in the order they were started), which
    is also what the ``Async XLA Ops`` line records as one event."""
    spans: List[Interval] = [(o.start, o.end) for o in dev.async_ops
                             if COLLECTIVE.match(o.name)]
    open_starts: Dict[str, List[float]] = {}
    for op in dev.ops:
        m = COLLECTIVE.match(op.name)
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_starts.setdefault(kind, []).append(op.start)
        elif phase == "-done":
            started = open_starts.get(kind)
            spans.append((started.pop(0) if started else op.start, op.end))
        else:
            spans.append((op.start, op.end))
    return union(clip(spans, lo, hi))


def compute_intervals(dev: DeviceTrace, lo: float, hi: float) -> List[Interval]:
    """Union of leaf ops that are neither collectives nor containers."""
    return union(clip(
        ((o.start, o.end) for o in dev.ops
         if not is_collective(o.name) and not CONTAINERS.match(o.name)),
        lo, hi,
    ))


def top_ops(dev: DeviceTrace, lo: float, hi: float, scopes: Sequence[str],
            n: int = 10) -> List[List[Any]]:
    """[[``<scope>:<op name>``, seconds of self time], ...], largest
    first, summed over executions."""
    total: Dict[str, float] = {}
    for op in dev.ops:
        if op.end <= lo or op.start >= hi or op.self_ns <= 0:
            continue
        tail = "/".join(op.scope.split("/")[-2:])
        key = f"{scope_of_op(op, scopes) or '-'}:{op.name} {tail}".strip()
        total[key] = total.get(key, 0.0) + op.self_ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(dev: DeviceTrace, host: Sequence[Op], lo: float, hi: float,
              n: int = 5) -> List[List[Any]]:
    """[[what the host was doing, seconds], ...] for the ``n`` longest
    gaps between device ops inside [lo, hi): the innermost host
    annotation open at the middle of the gap, or "-"."""
    gaps = subtract([(lo, hi)], busy_intervals(dev, lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out: List[List[Any]] = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        open_now = [h for h in host if h.start <= mid < h.end]
        label = min(open_now, key=lambda h: h.dur).name if open_now else "-"
        out.append([label, (e - s) / 1e9])
    return out


def describe(path: str, limit: int = 6) -> Dict[str, Any]:
    """Planes, lines, event counts and the stats of a few events: what
    one looks at by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = {
                "events": len(events),
                "sample": [
                    {"name": e.name, "start_ns": e.start_ns,
                     "duration_ns": e.duration_ns,
                     "stats": {k: (v if isinstance(v, (int, float)) else
                                   str(v)[:160]) for k, v in e.stats}}
                    for e in events[:limit]
                ],
            }
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys

    json.dump(describe(sys.argv[1]), sys.stdout, indent=1)
