"""The program's own spans, read from the profiler's trace.

Since PR 23 an enabled span of the program's tracer (``obs/spans.py``)
is also a ``jax.profiler.TraceAnnotation``: while a profiler session
runs, every ``round.*`` span is an event of its name on a line (one per
thread) of ``/host:CPU``, on the clock of the device planes, with its
arguments (``round``, ``what``, ``fuse``) as stats. ``trace_reduce.load``
keeps the benchmark's own ``bench.*`` annotations only and forgets which
line an event was on; this module reads the same file again, the host
plane alone, and keeps both.

The traced run's file is still on disk while the readers run:
``<bench_dir>/out/trace/<cell name>.<seed>/**/*.xplane.pb``.

Times are nanoseconds as the trace has them, like ``trace_reduce``'s.
"""

from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from harness import trace_reduce as rd

PREFIXES = ("bench.", "round.")  # the harness's annotations, the program's spans
PROGRAM = "round."
DISPATCH = "bench.dispatch"  # the harness's bracket around run_round


@dataclass(frozen=True)
class HostSpan:
    name: str
    start: float
    end: float
    line: int              # index of the thread's line in the host plane
    args: Tuple[Tuple[str, Any], ...] = ()


def find_trace(bench_dir: str, cell: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` of a traced run of ``cell``, or None."""
    paths = glob.glob(os.path.join(bench_dir, "out", "trace", cell + ".*",
                                   "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=2)
def load(path: str) -> List[HostSpan]:
    """Events of ``/host:CPU`` whose name starts with one of
    ``PREFIXES``, each with the line it was on, sorted by start."""
    from jax.profiler import ProfileData

    out: List[HostSpan] = []
    plane = ProfileData.from_file(path).find_plane_with_name(rd.HOST_PLANE)
    if plane is None:
        return out
    for index, line in enumerate(plane.lines):
        for e in line.events:
            if e.name.startswith(PREFIXES):
                out.append(HostSpan(
                    e.name, float(e.start_ns),
                    float(e.start_ns + e.duration_ns), index,
                    tuple(sorted((str(k), v) for k, v in e.stats)),
                ))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def dispatch_line(spans: Sequence[HostSpan]) -> Optional[int]:
    """The line of the thread that dispatches: the one that holds the
    harness's ``bench.dispatch``."""
    for s in spans:
        if s.name == DISPATCH:
            return s.line
    return None


def innermost_intervals(spans: Sequence[HostSpan]) -> Dict[str, List[rd.Interval]]:
    """{span name: the intervals in which a span of that name is the
    innermost one open}, for spans of ONE thread (they nest): each
    span's own interval minus what the spans opened inside it cover."""
    out: Dict[str, List[rd.Interval]] = {}
    ordered = sorted(spans, key=lambda s: (s.start, -s.end))
    for i, s in enumerate(ordered):
        inside = []
        for c in ordered[i + 1:]:
            if c.start >= s.end:
                break
            inside.append((c.start, min(c.end, s.end)))
        out.setdefault(s.name, []).extend(
            rd.subtract([(s.start, s.end)], rd.union(inside))
        )
    return {name: rd.union(iv) for name, iv in out.items()}


def overlap(a: List[rd.Interval], b: List[rd.Interval]) -> float:
    """Measure of the intersection of two unions."""
    return rd.measure(a) - rd.measure(rd.subtract(a, b))


def idle_by_span(idle: List[rd.Interval],
                 spans: Sequence[HostSpan]) -> Dict[str, float]:
    """Nanoseconds of ``idle`` (a union of device-idle intervals) by the
    innermost span of the program open on the dispatching thread while
    the device idled, by overlap; "" holds what lies under none."""
    line = dispatch_line(spans)
    mine = [s for s in spans if s.line == line and s.name.startswith(PROGRAM)]
    out = {name: overlap(idle, iv)
           for name, iv in innermost_intervals(mine).items()}
    out[""] = rd.measure(idle) - sum(out.values())
    return out


if __name__ == "__main__":
    # what one looks at by hand: the lines, and the spans of each
    # (from benchmark/: python -m harness.host_spans <xplane.pb> [events])
    import collections
    import sys

    spans = load(sys.argv[1])
    print("dispatching line:", dispatch_line(spans))
    per_line = collections.defaultdict(collections.Counter)
    for s in spans:
        per_line[s.line][s.name] += 1
    for line, names in sorted(per_line.items()):
        print("line", line, dict(names))
    for s in spans[:int(sys.argv[2]) if len(sys.argv) > 2 else 40]:
        print(f"{s.line:3d} {s.start:16.0f} {(s.end - s.start) / 1e6:12.3f} ms "
              f"{s.name} {dict(s.args)}")
