"""Set-up, warm-up and the measured window of one cell.

The path driven is the one ``fit`` drives, one call lower: ``resolve_config``
-> ``Experiment`` -> ``init_state`` -> ``_place_state`` -> ``run_round`` per
dispatch (one round, or one fused chunk), with the executable registry
installed as ``fit`` installs it. No evaluation, no checkpoint, no log.

The window keeps at most two dispatches in flight: fetch the oldest
dispatch's metrics, count its rounds as completed, look at the clock,
dispatch the next. The device queue never runs dry because of the
harness, the host never runs more than one dispatch ahead of the device,
and a round counts only when its result is on the host.
"""

from __future__ import annotations

import collections
import contextlib
import math
import os
import time
from typing import Any, Dict, List, Optional

def process_age_s() -> Optional[float]:
    """Seconds since the kernel started this process (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class CompileClock:
    """Counts and sums jax's ``backend_compile`` duration events
    (``jax.monitoring``) from the moment it is installed. The event fires
    once per program, for a persistent-cache hit too (its duration is then
    the load), and never nests, so the sum is wall time."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def install(self) -> "CompileClock":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if "backend_compile" in event:
            self.seconds += duration
            self.count += 1


class Marks:
    """Named points on the host clock, relative to process start."""

    def __init__(self, t0_perf: float, age_at_t0: float) -> None:
        self._t0 = t0_perf - age_at_t0
        self.at: Dict[str, float] = {}

    def mark(self, name: str) -> float:
        self.at[name] = time.perf_counter() - self._t0
        return self.at[name]


def _metrics_rounds(fetched, fuse: int) -> List[Dict[str, float]]:
    """Per-round {train_loss, examples} from one dispatch's fetched
    metrics (fields are scalars, or [fuse]-stacked under fusion)."""
    import numpy as np

    loss = np.asarray(fetched.train_loss, np.float64).reshape(-1)
    examples = np.asarray(fetched.examples, np.float64).reshape(-1)
    if len(loss) != fuse:
        raise RuntimeError(f"dispatch returned {len(loss)} rounds of metrics, "
                           f"expected {fuse}")
    return [{"train_loss": float(a), "examples": float(b)}
            for a, b in zip(loss, examples)]


class Run:
    """One cell, from a built ``Experiment`` to the end of its window."""

    def __init__(self, exp, seed: int) -> None:
        self.exp = exp
        self.seed = seed
        self.fuse = max(1, int(exp.cfg.run.fuse_rounds))
        self.state: Optional[Dict[str, Any]] = None
        self.next_round = 0
        # absolute round number (1-based, from the seeded initial
        # state) -> fetched metrics, warm-up included
        self.rounds: Dict[int, Dict[str, float]] = {}
        self.first_dispatch_params = None  # host copy, for the reference

    def start(self) -> None:
        state = self.exp.init_state(self.seed)
        self.state = self.exp._place_state(state)

    def dispatch(self):
        self.state = self.exp.run_round(self.state, self.next_round)
        first = self.next_round + 1
        self.next_round += self.fuse
        return first, self.state.pop("_metrics")

    def fetch(self, first_round: int, metrics) -> List[Dict[str, float]]:
        import jax

        rounds = _metrics_rounds(jax.device_get(metrics), self.fuse)
        for j, r in enumerate(rounds):
            self.rounds[first_round + j] = r
        return rounds

    def first_dispatch(self) -> None:
        """Compiles or loads the round program and runs it once. The
        resulting parameters are kept on the host — the system's side of
        the comparison with the reference (they are donated to the next
        dispatch, so this is the only moment they can be read)."""
        import jax

        first, metrics = self.dispatch()
        self.fetch(first, metrics)
        self.first_dispatch_params = jax.device_get(self.state["params"])

    def warm_up(self, dispatches: int) -> None:
        for _ in range(dispatches):
            first, metrics = self.dispatch()
            self.fetch(first, metrics)

    def measure(self, seconds: float, trace_dir: Optional[str] = None,
                trace_dispatches: int = 3, trace_max_s: float = 5.0,
                trace_after_round: int = 0) -> Dict[str, Any]:
        """The window. With ``trace_dir`` a steady sub-window of
        ``trace_dispatches`` dispatches (at most ``trace_max_s`` seconds
        from its start) is recorded with ``jax.profiler``. It starts once
        round ``trace_after_round`` (the cell's loss-check round) is on
        the host, or three quarters into the window: stopping the
        profiler holds the host for tens of seconds, and a traced run
        still has to reach the round its loss is checked at."""
        import jax

        annotate = (jax.profiler.TraceAnnotation if trace_dir
                    else (lambda name: contextlib.nullcontext()))
        in_flight = collections.deque()
        attempted = completed = failed = 0
        examples = 0.0
        trace = {"state": "off" if not trace_dir else "waiting",
                 "dispatched": 0, "t_start": 0.0, "pending_first": None}
        error: Optional[str] = None

        def send() -> None:
            nonlocal attempted
            if trace["state"] == "on":
                trace["dispatched"] += 1
                if trace["dispatched"] == trace_dispatches:
                    trace["pending_first"] = self.next_round + 1
            with annotate("bench.dispatch"):
                in_flight.append(self.dispatch())
            attempted += self.fuse

        t_first = time.perf_counter()
        t_last = t_first
        try:
            send()
            send()
            while in_flight:
                first, metrics = in_flight.popleft()
                with annotate("bench.fetch"):
                    rounds = self.fetch(first, metrics)
                t_last = time.perf_counter()
                for r in rounds:
                    if math.isfinite(r["train_loss"]):
                        completed += 1
                        examples += r["examples"]
                    else:
                        failed += 1
                if trace["state"] == "on" and (
                    trace["pending_first"] == first
                    or t_last - trace["t_start"] > trace_max_s
                ):
                    # the last traced dispatch's result is on the host
                    with contextlib.suppress(RuntimeError):
                        jax.profiler.stop_trace()
                    trace["state"] = "done"
                if t_last - t_first >= seconds:
                    break
                if trace["state"] == "waiting" and (
                    first + self.fuse - 1 >= trace_after_round
                    or t_last - t_first > 0.75 * seconds
                ):
                    jax.profiler.start_trace(trace_dir)
                    trace["state"] = "on"
                    trace["t_start"] = time.perf_counter()
                send()
        except Exception as e:  # a round that raised: count it, end the window
            error = f"{type(e).__name__}: {e}"
            failed += attempted - completed - failed
            in_flight.clear()
        finally:
            if trace["state"] == "on":
                with contextlib.suppress(RuntimeError):
                    jax.profiler.stop_trace()
                trace["state"] = "cut"
        # what is still in flight ran past the window: wait for it (the
        # memory peak is read after this), count it as neither
        while in_flight:
            first, metrics = in_flight.popleft()
            with contextlib.suppress(Exception):
                self.fetch(first, metrics)
        wall = t_last - t_first
        return {
            "attempted": attempted,
            "completed": completed,
            "failed": failed,
            "wall_s": wall,
            "rounds_per_s": completed / wall if wall > 0 else 0.0,
            "examples_per_round": examples / completed if completed else 0.0,
            "trace_state": trace["state"],
            "error": error,
        }


def peak_memory_bytes(devices) -> Dict[str, int]:
    """The fullest chip's high-water mark, from the two counters of the
    device runtime that each see a part of it: ``peak_bytes_in_use``
    (arrays: weights, corpus, the inputs and outputs of the dispatches in
    flight) and ``peak_bytes_reserved`` (what the runtime sets aside for
    executing programs: their temporaries). The first alone read 0.34 GB
    while a program with 6 GB of temporaries ran (PR 22)."""
    best = None
    for d in devices:
        stats = d.memory_stats() or {}
        missing = {"peak_bytes_in_use", "peak_bytes_reserved"} - set(stats)
        if missing:
            raise RuntimeError(f"{d} reports no {sorted(missing)}")
        arrays = int(stats["peak_bytes_in_use"])
        reserved = int(stats["peak_bytes_reserved"])
        if best is None or arrays + reserved > best["memory_peak_bytes"]:
            best = {"arrays_peak_bytes": arrays,
                    "programs_reserved_peak_bytes": reserved,
                    "memory_peak_bytes": arrays + reserved}
    return best
