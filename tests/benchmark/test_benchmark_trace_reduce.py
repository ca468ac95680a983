"""The reduction from trace to numbers: interval arithmetic and
attribution on hand-made events, then on traces recorded on the chip
(``fixtures/*.xplane.pb.gz``, cut down by ``fixtures/make_fixture.py``;
``*.op_names.json.gz`` is the compiled program's instruction -> op_name
map for the instructions the fixture has). ``fixtures/expected.json``
holds what ``make_fixture.expected`` worked out from the fixtures' events
the long way round, slice by slice, independently of the reduction."""

import gzip
import json
import os
import shutil

import pytest

from bench_paths import FIXTURES
from harness import trace_reduce as rd
from harness.trace_reduce import DeviceTrace, Op

LT, AG, SA = "round_local_train", "round_aggregate", "round_server_apply"
SCOPES = (LT, AG, SA)


def _dev(ops, modules=()):
    dev = DeviceTrace(0, [Op(*o) for o in ops], [Op(*m) for m in modules])
    dev.ops.sort(key=lambda o: (o.start, -o.end))
    rd._fill_self_times(dev.ops)
    return dev


@pytest.mark.parametrize("got,want", [
    (rd.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]), [(0, 3), (5, 8)]),
    (rd.measure([(0, 3), (5, 8)]), 6),
    (rd.clip([(0, 3), (5, 8)], 2, 6), [(2, 3), (5, 6)]),
    (rd.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]), [(0, 1), (2, 4), (6, 9)]),
    (rd.subtract([(0, 2), (3, 5)], [(1, 4)]), [(0, 1), (4, 5)]),
    (rd.subtract([(0, 2)], []), [(0, 2)]),
], ids=["union", "measure", "clip", "subtract", "subtract_two", "subtract_none"])
def test_interval_arithmetic(got, want):
    assert got == want


# one device; the execution the trace began in (cut at -50), then two
# executions of the round program starting at 0 and 100: a while loop (10..60) holding two fusions under local training, a
# synchronous all-reduce (60..70) under aggregate, an apply kernel
# (72..80) under server apply, then idle until the next execution.
HAND = _dev(
    ops=[
        ("while.1", 10, 60, f"jit(round_fn)/{LT}/while"),
        ("fusion.1", 10, 30, f"jit(round_fn)/{LT}/while/body/dot_general"),
        ("fusion.2", 35, 60, f"jit(round_fn)/{LT}/while/body/transpose"),
        ("all-reduce.1", 60, 70, f"jit(round_fn)/{AG}/psum"),
        ("custom-call.1", 72, 80, f"jit(round_fn)/{SA}/pallas_call"),
        ("fusion.1", 110, 130, f"jit(round_fn)/{LT}/while/body/dot_general"),
    ],
    modules=[("jit_round_fn(1)", -50, -10), ("jit_round_fn(1)", 0, 90),
             ("jit_other(2)", 91, 95), ("jit_round_fn(1)", 100, 190)],
)


def test_steady_window_is_whole_periods_of_the_round_program():
    assert rd.steady_window(HAND, "jit_round_fn") == (0, 100, 1)
    assert rd.steady_window(HAND, "jit_other") is None
    assert rd.steady_window(_dev([]), "jit_round_fn") is None
    two = _dev([], modules=[("jit_round_fn(1)", 0, 90), ("jit_round_fn(1)", 100, 190)])
    assert rd.steady_window(two, "jit_round_fn") is None  # the first is cut


@pytest.mark.parametrize("got,want", [
    # busy: [10,70) and [72,80) inside the window = 68 of 100
    (rd.measure(rd.busy_intervals(HAND, 0, 100)), 68),
    # self times: the while keeps only its own 5 (50 - 20 - 25)
    (rd.self_time_by_scope(HAND, 0, 100, SCOPES),
     {LT: 50.0, AG: 10.0, SA: 8.0, "": 0.0}),
    (rd.measure(rd.collective_intervals(HAND, 0, 100)), 10),
    # the while is a container, so nothing computes during the all-reduce
    (rd.measure(rd.subtract(rd.collective_intervals(HAND, 0, 100),
                            rd.compute_intervals(HAND, 0, 100))), 10),
    (rd.top_ops(HAND, 0, 100, SCOPES, 2),
     [[f"{LT}:fusion.2 body/transpose", 25e-9],
      [f"{LT}:fusion.1 body/dot_general", 20e-9]]),
], ids=["busy", "scope_self_time", "collective", "exposed", "top_ops"])
def test_reduction_of_hand_made_events(got, want):
    assert got == want


def test_async_collective_is_in_flight_from_start_to_done():
    dev = _dev(ops=[
        ("all-reduce-start.1", 0, 2, ""), ("fusion.1", 2, 12, ""),
        ("all-reduce-done.1", 12, 20, ""), ("fusion.2", 20, 30, ""),
    ])
    in_flight = rd.collective_intervals(dev, 0, 30)
    assert in_flight == [(0, 20)]
    exposed = rd.subtract(in_flight, rd.compute_intervals(dev, 0, 30))
    assert exposed == [(0, 2), (12, 20)]


def test_idle_gaps_are_named_by_the_innermost_host_annotation():
    host = [Op("bench.dispatch", 60, 100), Op("bench.fetch", 0, 65),
            Op("bench.inner", 81, 99)]
    gaps = rd.idle_gaps(HAND, host, 0, 100, n=3)
    # [80,100) mid 90 -> bench.inner; [0,10) mid 5 -> bench.fetch; [70,72)
    assert gaps == [["bench.inner", 20e-9], ["bench.fetch", 10e-9],
                    ["bench.dispatch", 2e-9]]


def test_scope_is_the_innermost_known_component():
    op = Op("f", 0, 1, f"jit(round_fn)/{LT}/while/body/{SA}/mul")
    assert rd.scope_of_op(op, SCOPES) == SA
    assert rd.scope_of_op(Op("f", 0, 1, "jit(other)/mul"), SCOPES) == ""
    assert rd.scope_of_op(Op("f", 0, 1, ""), SCOPES) == ""


HLO = '''
HloModule jit_round_fn, is_scheduled=true

%fused_computation.50 (p0: bf16[16,64]) -> bf16[16,64] {
  %p0 = bf16[16,64]{1,0} parameter(0)
  ROOT %multiply.7 = bf16[16,64]{1,0} multiply(%p0, %p0), metadata={op_name="jit(round_fn)/while/body/round_local_train/mul" source_file="trainer.py" source_line=266}
}

ENTRY %main (a: bf16[16,64]) -> bf16[16,64] {
  %a = bf16[16,64]{1,0} parameter(0)
  %fusion.2230 = bf16[16,64]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop, calls=%fused_computation.50, metadata={op_name="jit(round_fn)/while/body/round_local_train/mul" source_file="trainer.py"}
  ROOT %round_server_apply.3 = bf16[16,64]{1,0} custom-call(%fusion.2230), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/while/body/round_server_apply/pallas_call"}
}
'''


def test_scopes_come_from_the_compiled_programs_text():
    names = rd.scopes_from_hlo(HLO)
    assert names == {
        "multiply.7": "jit(round_fn)/while/body/round_local_train/mul",
        "fusion.2230": "jit(round_fn)/while/body/round_local_train/mul",
        "round_server_apply.3":
            "jit(round_fn)/while/body/round_server_apply/pallas_call",
    }
    event = ("%fusion.2230 = (f32[16,64,64]{2,1,0:T(8,128)S(1)}, bf16[16,64]) "
             "fusion(f32[16,64,64] %copy-done.75), kind=kOutput, calls=%fc.50")
    assert rd.instruction_name(event) == "fusion.2230"
    assert rd.instruction_name("all-reduce-start.4") == "all-reduce-start.4"
    op = Op("fusion.2230", 0, 1, names[rd.instruction_name(event)])
    assert rd.scope_of_op(op, SCOPES) == LT


# -- traces recorded on the chip -----------------------------------------

with open(os.path.join(FIXTURES, "expected.json")) as _f:
    EXPECTED = json.load(_f)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = {}
    for name in EXPECTED:
        path = tmp_path_factory.mktemp("xplane") / (name + ".xplane.pb")
        with gzip.open(os.path.join(FIXTURES, name + ".xplane.pb.gz")) as src, \
                open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        with gzip.open(os.path.join(FIXTURES, name + ".op_names.json.gz"), "rt") as f:
            out[name] = rd.load(str(path), json.load(f))
    return out


@pytest.mark.parametrize("name,key", [
    (name, key) for name, want in EXPECTED.items() for key in want["values"]
])
def test_reduction_of_a_recorded_trace(recorded, name, key):
    want = EXPECTED[name]
    trace = recorded[name]
    windows = rd.steady_windows(trace, "jit_round_fn")
    assert windows and len(windows) == want["devices"]
    scopes = tuple(want["scopes"])
    got = {
        "periods": [w[3] for w in windows],
        "window_ns": [w[2] - w[1] for w in windows],
        "busy_ns": [rd.measure(rd.busy_intervals(d, lo, hi))
                    for d, lo, hi, _ in windows],
        "local_train_self_ns": [
            rd.self_time_by_scope(d, lo, hi, scopes)[LT]
            for d, lo, hi, _ in windows],
        "unscoped_self_ns": [
            rd.self_time_by_scope(d, lo, hi, scopes)[""]
            for d, lo, hi, _ in windows],
        "collective_ns": [rd.measure(rd.collective_intervals(d, lo, hi))
                          for d, lo, hi, _ in windows],
        "collective_exposed_ns": [
            rd.measure(rd.subtract(rd.collective_intervals(d, lo, hi),
                                   rd.compute_intervals(d, lo, hi)))
            for d, lo, hi, _ in windows],
        "host_annotations": sorted({h.name for h in trace.host}),
    }[key]
    assert got == pytest.approx(want["values"][key], rel=1e-9)


READERS = EXPECTED["chip4_r18_c64_k2_x4"]["readers"]


@pytest.mark.parametrize("metric", sorted(READERS["values"]))
def test_readers_on_the_recorded_four_chip_trace(recorded, metric):
    """Every per-layer metric of the device trace through its own reader
    and data file, on the four-chip cell's recorded (thinned) trace: the
    Pallas apply kernel sits inside the manual region there
    (``shard_map.<n>``), which the reader's first version missed on the
    chip. Expected values are this fixture's own (thinning the short ops
    moved ``local_train_pct`` and ``agg_apply_ms_round`` a little from
    the full trace's 96.1 % and 1.12 ms; the others are the full
    trace's)."""
    import run as bench_run
    from harness import catalog, flops

    with open(os.path.join(catalog.BENCH_DIR, "harness", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    cell = catalog.load_workload(READERS["cell"])
    trace = recorded["chip4_r18_c64_k2_x4"]
    ctx = {
        "cell": cell, "config": catalog.load_config(cell["config"]),
        "bench_dir": catalog.BENCH_DIR, "peaks": peaks, "trace": trace,
        "windows": rd.steady_windows(trace, bench_run.ROUND_PROGRAM),
        "fuse": cell["reference"]["rounds"], "scopes": bench_run.SCOPES,
        "flops": flops, "reduce": rd,
        "counters": {"examples_per_round": READERS["examples_per_round"],
                     "server_momentum": False},
    }
    got = bench_run.layer_metrics([{"name": metric}], ctx, catalog.BENCH_DIR)
    assert got[metric]["value"] == pytest.approx(READERS["values"][metric],
                                                 rel=1e-9)
