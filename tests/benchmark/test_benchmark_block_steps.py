"""``dead_steps_pct`` / ``skipped_steps_pct``: the reader on a hand-made
``ctx`` (the counts that ride ``round.host_inputs.slab_build``; a
program without them), and the two data files against their
BENCHMARK.json entries."""

import pytest

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from harness import catalog

R18_CELLS = ["r18_c16_k8", "r18_c64_k2_x4"]
SPAN = "round.host_inputs.slab_build"
# (metric, the count it reads, better)
METRICS = [("dead_steps_pct", "dead_steps", "lower"),
           ("skipped_steps_pct", "skipped_steps", "higher")]


def _ctx(**slab_build):
    return {"spans": {
        SPAN: {"count": 140, "total_ms": 310.0, "max_ms": 4.1,
               "self_ms": 310.0, **slab_build},
        "round.run": {"count": 35, "total_ms": 90.0},
    }}


@pytest.mark.parametrize("name,count,_", METRICS)
def test_reader_divides_the_spans_count_by_its_client_steps(name, count, _):
    spec = catalog.load_layer_metric(name)
    read = catalog.load_reader(spec["reader"])
    # 140 rounds of 64 clients x 2 steps, as r18_c64_k2_x4 builds them
    ctx = _ctx(client_steps=17920, dead_steps=6854, skipped_steps=5936)
    want = {"dead_steps": 100 * 6854 / 17920,
            "skipped_steps": 100 * 5936 / 17920}[count]
    assert read(ctx, **spec["args"]) == pytest.approx(want)
    # every step live: a number, not nothing
    full = _ctx(client_steps=17920, dead_steps=0, skipped_steps=0)
    assert read(full, **spec["args"]) == 0.0


@pytest.mark.parametrize("name,count,_", METRICS)
def test_reader_finds_nothing_where_the_program_keeps_no_counts(
        name, count, _):
    """The parent's tracer reports the span without counts, a run
    without spans no span at all: ``None`` both times, nothing raised."""
    spec = catalog.load_layer_metric(name)
    read = catalog.load_reader(spec["reader"])
    assert read(_ctx(), **spec["args"]) is None
    assert read({"spans": {}}, **spec["args"]) is None
    assert read(_ctx(client_steps=0, **{count: 0}), **spec["args"]) is None
    assert read(_ctx(client_steps=128), **spec["args"]) is None


@pytest.mark.parametrize("name,count,better", METRICS)
def test_data_file_and_benchmark_entry_agree(name, count, better):
    bench = catalog.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = catalog.load_layer_metric(name)
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == spec[key], key
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", better, "program_counter")
    assert (entry["layer"], entry["moves"]) == ("round engine",
                                                "rounds_per_s")
    assert entry["workloads"] == R18_CELLS
    assert spec["reader"] == "span_count_pct"
    assert spec["args"] == {"span": SPAN, "count": count,
                            "of": "client_steps"}
    # appended after everything the benchmark already had, and read in
    # the two cells that run the block trainer alone
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(name) > names.index("setup_unattributed_s")
    for cell in bench["workloads"]:
        reported = {m["name"] for m in catalog.cell_metrics(
            bench, cell["name"], "per_layer")}
        assert (name in reported) == (cell["name"] in R18_CELLS)
