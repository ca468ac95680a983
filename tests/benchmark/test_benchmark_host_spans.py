"""The program's spans on the profiler's clock (PR 23):
``harness/host_spans.py`` and ``readers/idle_by_host_span_ms_round.py``
on hand-made events, then on a trace recorded on the chip
(``fixtures/host_*.xplane.pb.gz``, cut down by
``fixtures/make_host_fixture.py``)."""

import pytest

from bench_paths import BENCH_DIR
from harness import catalog, host_spans
from harness import trace_reduce as rd
from harness.host_spans import HostSpan
from harness.trace_reduce import DeviceTrace, Op

IDLE = catalog.load_reader("idle_by_host_span_ms_round", BENCH_DIR)

# the dispatching thread (line 3): one call of run_round inside the
# harness's bracket; the prefetch worker (line 7) with sub-millisecond
# spans of its own, one of them in the middle of a device gap
MAIN = [
    HostSpan("bench.dispatch", 100, 1000, 3),
    HostSpan("round.run", 110, 990, 3, (("round", 5),)),
    HostSpan("round.host_inputs", 120, 200, 3),
    HostSpan("round.host_inputs.sampler", 130, 150, 3),
    HostSpan("round.placement", 200, 300, 3),
    HostSpan("round.device_wait", 300, 800, 3, (("what", "rng_keys"),)),
    HostSpan("round.placement", 800, 820, 3),
    HostSpan("round.dispatch", 850, 950, 3),
    HostSpan("bench.fetch", 1000, 1100, 3),
]
WORKER = [
    HostSpan("round.prefetch", 760, 790, 7, (("round", 9),)),
    HostSpan("round.host_inputs.sampler", 765, 785, 7),
]
SPANS = sorted(MAIN + WORKER, key=lambda s: (s.start, -s.end))


def test_the_dispatching_thread_is_the_line_that_holds_bench_dispatch():
    assert host_spans.dispatch_line(SPANS) == 3
    assert host_spans.dispatch_line(WORKER) is None


def test_innermost_intervals_are_a_spans_own_time():
    own = host_spans.innermost_intervals(
        [s for s in MAIN if s.name.startswith("round.")])
    assert own["round.run"] == [(110, 120), (820, 850), (950, 990)]
    assert own["round.host_inputs"] == [(120, 130), (150, 200)]
    assert own["round.host_inputs.sampler"] == [(130, 150)]
    assert own["round.placement"] == [(200, 300), (800, 820)]
    assert own["round.device_wait"] == [(300, 800)]
    # every instant of round.run belongs to exactly one span
    assert sum(rd.measure(iv) for iv in own.values()) == 990 - 110


def test_idle_time_counts_by_overlap_with_the_innermost_span():
    """A gap that begins while the host still waits and ends in the
    dispatch call is split where the spans change — the midpoint rule of
    ``trace_reduce.idle_gaps`` would hand all of it to one of them — and
    the worker's short spans inside the gap get none of it."""
    idle = [(50, 105), (750, 900), (1050, 1060)]
    got = host_spans.idle_by_span(idle, SPANS)
    assert got == {
        "round.run": 30.0,             # [820, 850)
        "round.host_inputs": 0.0, "round.host_inputs.sampler": 0.0,
        "round.placement": 20.0,       # [800, 820)
        "round.device_wait": 50.0,     # [750, 800)
        "round.dispatch": 50.0,        # [850, 900)
        "": 55.0 + 10.0,               # before and after run_round
    }
    assert sum(got.values()) == rd.measure(idle)


def _ctx(ops, fuse=2):
    dev = DeviceTrace(0, [Op(*o) for o in ops])
    dev.ops.sort(key=lambda o: (o.start, -o.end))
    rd._fill_self_times(dev.ops)
    return {"windows": [(dev, 0.0, 1000.0, 1)], "fuse": fuse, "reduce": rd,
            "bench_dir": BENCH_DIR, "cell": {"name": "hand"}}


@pytest.fixture
def on_disk(monkeypatch):
    """The reader finds its trace by the cell's name; here it is handed
    hand-made spans instead."""
    def use(spans):
        monkeypatch.setattr(host_spans, "find_trace", lambda *a: "hand.pb")
        monkeypatch.setattr(host_spans, "load", lambda path: spans)
    return use


def test_idle_readings_add_up_to_the_chips_idle_time(on_disk):
    on_disk(SPANS)
    # busy [0,750) and [900,1000): idle 150 ns in one period of 2 rounds
    ctx = _ctx([("fusion.1", 0, 750, ""), ("fusion.2", 900, 1000, "")])
    in_wait = IDLE(ctx, spans=["round.device_wait"])
    elsewhere = IDLE(ctx, spans=["round.device_wait"], complement=True)
    assert in_wait == pytest.approx(50 / 2 / 1e6)
    assert elsewhere == pytest.approx(100 / 2 / 1e6)
    idle_pct = catalog.load_reader("device_idle_pct", BENCH_DIR)(ctx)
    assert (in_wait + elsewhere) * 2 * 1e6 == pytest.approx(idle_pct / 100 * 1000)


def test_idle_readings_are_of_the_idlest_chip(on_disk):
    on_disk(SPANS)
    ctx = _ctx([("fusion.1", 0, 1000, "")])  # chip 0 is never idle
    idler = _ctx([("fusion.1", 0, 700, ""), ("fusion.2", 900, 1000, "")])
    ctx["windows"] = ctx["windows"] + [
        (DeviceTrace(1, idler["windows"][0][0].ops), 0.0, 1000.0, 1)]
    # chip 1: [700, 900) = 100 in the wait, 20 placement, 30 run, 50 dispatch
    assert IDLE(ctx, spans=["round.device_wait"]) == pytest.approx(100 / 2 / 1e6)
    assert IDLE(ctx, spans=["round.device_wait"],
                complement=True) == pytest.approx(100 / 2 / 1e6)


def test_without_round_spans_the_idle_readers_read_nothing(on_disk):
    """The parent program under this PR's benchmark files: the trace
    holds ``bench.*`` only; and a run whose trace is gone."""
    ctx = _ctx([("fusion.1", 0, 750, "")])
    on_disk([s for s in SPANS if s.name.startswith("bench.")])
    assert IDLE(ctx, spans=["round.device_wait"]) is None
    assert IDLE(ctx, spans=["round.device_wait"], complement=True) is None
    on_disk(SPANS)
    assert IDLE({**ctx, "windows": None}, spans=["round.device_wait"]) is None


def test_a_cell_that_never_waits_reads_zero_not_nothing(on_disk):
    on_disk([s for s in SPANS if s.name != "round.device_wait"])
    ctx = _ctx([("fusion.1", 0, 750, ""), ("fusion.2", 900, 1000, "")])
    assert IDLE(ctx, spans=["round.device_wait"]) == 0.0
    assert IDLE(ctx, spans=["round.device_wait"], complement=True) > 0.0


def test_no_trace_on_disk_is_nothing_to_read(tmp_path):
    assert host_spans.find_trace(str(tmp_path), "r18_c16_k8") is None
    ctx = {**_ctx([("fusion.1", 0, 750, "")]), "bench_dir": str(tmp_path)}
    assert IDLE(ctx, spans=["round.device_wait"]) is None


def test_span_self_time_reader_reads_self_ms_where_there_is_one():
    read = catalog.load_reader("span_self_ms_round", BENCH_DIR)
    ctx = {"window": {"completed": 8}, "spans": {
        "round.run": {"count": 2, "total_ms": 800.0, "max_ms": 500.0,
                      "self_ms": 4.0},
        "round.placement": {"count": 4, "total_ms": 3.0, "max_ms": 1.0}}}
    assert read(ctx, spans=["round.run"]) == 0.5
    # the parent's tracer has no self time, and no such span
    assert read(ctx, spans=["round.placement"]) is None
    assert read(ctx, spans=["round.nothing"]) is None


# -- traces recorded on the chip -----------------------------------------

import host_fixtures  # noqa: E402


@pytest.fixture(scope="module", params=sorted(host_fixtures.RECORDED))
def recorded(request, tmp_path_factory):
    ctx = host_fixtures.unpack(request.param,
                               tmp_path_factory.mktemp("bench"))
    path = host_spans.find_trace(ctx["bench_dir"], ctx["cell"]["name"])
    return request.param, ctx, host_spans.load(path)


def test_recorded_round_spans_nest_inside_bench_dispatch(recorded):
    """The program's spans on ``/host:CPU``: every ``round.run`` lies
    inside a ``bench.dispatch`` on the same line and carries its
    dispatch's first round; the spans inside it carry the same round;
    the worker's spans sit on another line."""
    name, ctx, spans = recorded
    line = host_spans.dispatch_line(spans)
    main = [s for s in spans if s.line == line]
    brackets = [s for s in main if s.name == host_spans.DISPATCH]
    runs = [s for s in main if s.name == "round.run"]
    assert runs and len(runs) <= len(brackets)
    fuse = ctx["fuse"]
    rounds = [dict(r.args)["round"] for r in runs]
    assert all(b - a == fuse for a, b in zip(rounds, rounds[1:]))
    for run in runs:
        assert any(b.start <= run.start and run.end <= b.end for b in brackets)
        inside = [s for s in main if s.name.startswith("round.")
                  and run.start <= s.start and s.end <= run.end]
        assert {"round.host_inputs", "round.dispatch"} <= {s.name for s in inside}
        assert all(dict(s.args)["round"] == dict(run.args)["round"]
                   for s in inside)
        waits = [s for s in inside if s.name == "round.device_wait"]
        assert len(waits) == (1 if fuse > 1 else 0)  # the fused chunk's keys
        assert all(dict(w.args)["what"] == "rng_keys" for w in waits)
    worker = [s for s in spans if s.name == "round.prefetch"]
    assert worker and all(s.line != line for s in worker)


def test_recorded_idle_readings_add_up_to_device_idle_pct(recorded):
    """``idle_in_wait`` + ``idle_elsewhere`` is the idle time
    ``device_idle_pct`` is computed from, and the table by span leaves
    nothing out."""
    _, ctx, spans = recorded
    in_wait = IDLE(ctx, spans=["round.device_wait"])
    elsewhere = IDLE(ctx, spans=["round.device_wait"], complement=True)
    assert in_wait >= 0 and elsewhere >= 0
    (dev, lo, hi, periods), = ctx["windows"]
    idle_pct = catalog.load_reader("device_idle_pct", BENCH_DIR)(ctx)
    idle_ms_round = idle_pct / 100 * (hi - lo) / (periods * ctx["fuse"]) / 1e6
    assert in_wait + elsewhere == pytest.approx(idle_ms_round, rel=1e-9)
    idle = rd.subtract([(lo, hi)], rd.busy_intervals(dev, lo, hi))
    by_span = host_spans.idle_by_span(idle, spans)
    assert sum(by_span.values()) == pytest.approx(rd.measure(idle), rel=1e-9)
    assert all(v >= -1e-6 for v in by_span.values())
