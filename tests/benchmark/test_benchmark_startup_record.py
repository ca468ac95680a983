"""The entry layer's metrics that read the program's start-up record
(``readers/startup_span_s.py``): the reduction on a hand-made record,
the cut at the window's start in a live process, each metric's data file
against its ``BENCHMARK.json`` entry, and the whole path in a rehearsal
of ``dry_r18_fused`` through ``startup_probe.py``."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench_paths import BENCH_DIR, BENCHMARK_JSON, ROOT, bench_line
from harness import catalog, window

READER = "startup_span_s"
with open(BENCHMARK_JSON) as _f:
    BENCH = json.load(_f)
SPECS = {}
for _entry in BENCH["per_layer"]:
    _spec = catalog.load_layer_metric(_entry["name"])
    if _spec["reader"] == READER or _entry["name"] == "import_runtime_s":
        SPECS[_entry["name"]] = (_entry, _spec)

# what ISSUE 34's table lists, and setup_import_s (the driver module's own
# import, which the table's unattributed row would otherwise hold)
TABLE = ["import_runtime_s", "setup_import_s", "setup_data_load_s",
         "setup_partition_s", "setup_engine_build_s", "setup_init_s",
         "setup_place_s", "round_lower_s", "round_backend_s",
         "setup_cache_misses", "setup_unattributed_s"]
SPAN_NAMES = ["setup.import", "setup.experiment", "setup.model",
              "setup.data.load", "setup.data.partition", "setup.engine",
              "setup.eval_batches", "setup.init_state", "setup.init.model",
              "setup.init.server_opt", "setup.place_state", "obs.executables",
              "compile.lower", "compile.backend", "compile"]


def _reader():
    return catalog.load_module("readers", READER, ("read",), BENCH_DIR)


def _entry(name, start, end, parent=None, lane=0, args=None, **more):
    return {"name": name, "start": start, "end": end, "self_s": end - start,
            "args": args or {}, "parent": parent, "lane": lane, **more}


# process start at 0: runtime up at 10, the experiment 14..40 after an
# import 10..13, init 40..46, placement 46..47, the first dispatch
# 47..60 (lower 48..54, load 54..58), one more warm-up dispatch to 62
RECORD = [
    _entry("setup.import", 10.0, 13.0),
    _entry("setup.model", 14.0, 14.5, "setup.experiment"),
    _entry("setup.data.load", 14.5, 30.0, "setup.experiment",
           args={"dataset": "cifar10", "examples": 60000}),
    _entry("setup.data.partition", 30.0, 36.0, "setup.experiment"),
    _entry("setup.engine", 36.0, 39.0, "setup.experiment"),
    _entry("setup.experiment", 14.0, 40.0),
    _entry("setup.init.model", 40.0, 45.0, "setup.init_state", compiles=90,
           compile_s=3.0, cache_hits=90, cache_misses=0),
    _entry("setup.init_state", 40.0, 46.0),
    _entry("setup.place_state", 46.0, 47.0),
    _entry("compile.lower", 48.0, 54.0, "obs.executables",
           args={"round": 1, "program": "round.fused"}),
    _entry("compile.backend", 54.0, 58.0, "obs.executables",
           args={"round": 1, "program": "round.fused", "cache": "hit"}),
    _entry("compile.lower", 58.0, 58.5, "obs.executables",
           args={"round": 1, "program": "eval.all"}),
    _entry("obs.executables", 47.5, 58.6, "round.dispatch"),
    # a worker thread's top-level span is not the main thread's set-up
    _entry("setup.engine", 20.0, 25.0, lane=1),
    _entry("compile", 10.0, 61.9, lane=None, compiles=97, compile_s=8.0,
           cache_hits=95, cache_misses=2),
]
SETUP = {"setup_s": 62.0, "import_and_runtime_s": 10.0,
         "first_dispatch_s": 13.0, "further_warmup_s": 2.0}
EXPECTED = {"setup_import_s": 3.0, "setup_data_load_s": 15.5,
            "setup_partition_s": 6.0, "setup_engine_build_s": 26.0 - 21.5,
            "setup_init_s": 6.0, "setup_place_s": 1.0, "round_lower_s": 6.0,
            "round_backend_s": 4.0, "setup_cache_misses": 2,
            # 62 - 10 - (3 + 26 + 6 + 1) - 13 - 2: the second between
            # the import and the experiment
            "setup_unattributed_s": 1.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reduction_of_a_hand_made_record(name):
    args = SPECS[name][1]["args"]
    assert _reader().reduce(RECORD, SETUP, **args) == pytest.approx(
        EXPECTED[name])


def test_the_parts_add_up_to_setup_s():
    reduce = _reader().reduce
    parts = [reduce(RECORD, SETUP, **SPECS[n][1]["args"])
             for n in TABLE if n not in ("import_runtime_s", "round_lower_s",
                                         "round_backend_s",
                                         "setup_cache_misses")]
    total = (SETUP["import_and_runtime_s"] + sum(parts)
             + SETUP["first_dispatch_s"] + SETUP["further_warmup_s"])
    assert total == pytest.approx(SETUP["setup_s"])


def test_a_span_that_never_opened_leaves_its_metric_out():
    reduce = _reader().reduce
    no_place = [e for e in RECORD if e["name"] != "setup.place_state"]
    assert reduce(no_place, SETUP, spans=["setup.place_state"]) is None
    # but what is subtracted may be missing
    no_data = [e for e in RECORD if not e["name"].startswith("setup.data.")]
    assert reduce(no_data, SETUP, spans=["setup.experiment"],
                  minus=["setup.data.load", "setup.data.partition"]) == 26.0


def test_the_reader_cuts_at_the_windows_start_in_a_live_process(monkeypatch):
    """A tracer's record made now, with the window's first dispatch
    declared between two spans: the init_state after it (the reference's)
    is not read."""
    from colearn_federated_learning_tpu.obs import spans

    reader = _reader()
    monkeypatch.setattr(reader, "SLACK_S", 0.0)
    tracer = spans.Tracer()
    with tracer.span("setup.init_state"):
        time.sleep(0.02)
    tracer._note_compile(0.01)
    tracer._note_cache(3)
    tracer.drain()  # the harness drains at the window's start
    time.sleep(0.15)
    ctx = {"setup": {"setup_s": window.process_age_s()}}
    time.sleep(0.15)
    with tracer.span("setup.init_state"):
        time.sleep(0.05)
    assert [e["name"] for e in tracer.startup_record()] == [
        "setup.init_state", "compile", "setup.init_state"]
    # other tests' tracers may still be alive in this process
    monkeypatch.setattr(spans, "live_tracers", lambda: [tracer])
    assert [e["name"] for e in reader.startup_record(ctx)] == [
        "setup.init_state", "compile"]
    got = reader.read(ctx, spans=["setup.init_state"])
    assert 0.02 <= got < 0.05
    assert reader.read(ctx, spans=["compile"], count="cache_misses") == 1
    assert reader.read(ctx, spans=["setup.place_state"]) is None


def test_a_program_without_the_record_has_nothing_to_read(monkeypatch):
    """The parent of the PR that added the record, under these benchmark
    files: every metric of the reader is left out, nothing raises."""
    from colearn_federated_learning_tpu.obs import spans

    monkeypatch.delattr(spans, "live_tracers")
    reader = _reader()
    ctx = {"setup": dict(SETUP)}
    for name, (_, spec) in SPECS.items():
        if spec["reader"] == READER:
            assert reader.read(ctx, **spec["args"]) is None, name


@pytest.mark.parametrize("name", TABLE)
def test_data_file_and_benchmark_entry_agree(name):
    entry, spec = SPECS[name]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert entry["layer"] == "entry" and entry["moves"] == "setup_s"
    assert entry["better"] == "lower"
    assert "workloads" not in entry and "workloads" not in spec
    assert entry["unit"] == ("programs" if name == "setup_cache_misses"
                             else "s")
    assert entry["source"] == {
        "import_runtime_s": "host_clock", "setup_unattributed_s": "host_clock",
        "setup_cache_misses": "program_counter"}.get(name, "program_span")
    if name == "import_runtime_s":
        assert spec["reader"] == "setup_field"
        assert spec["args"] == {"field": "import_and_runtime_s"}
    else:
        assert spec["reader"] == READER
    assert len(spec["what"]) > 40


def test_the_new_entries_are_appended_after_the_accepted_ones():
    """By name, not by place: later PRs append after them (PR 35 did)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(n) == 1 for n in TABLE)
    # in the table's order among themselves
    assert sorted(TABLE, key=names.index) == TABLE
    # after the two metrics they split, which stay as they were
    first = names.index(TABLE[0])
    assert {"compile_s", "host_prep_s"} <= set(names[:first])


def test_rehearsal_reports_every_span_and_every_metric(tmp_path):
    """``dry_r18_fused`` on the CPU through the probe: a non-empty record
    with every span name, every metric read, the sum checked, and the
    reference's second init_state on the far side of the cut."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "benchmark", "startup_probe.py"),
         "--workload", "dry_r18_fused", "--seed", "5", "--seconds", "1",
         "--dry"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["correct"] is True
    setup = bench_line(proc.stdout, "setup")
    startup = bench_line(proc.stdout, "startup")
    for name in SPAN_NAMES:
        assert name in startup["before_window"], name
    assert "setup.init_state" in startup["after_window"]
    assert "setup.experiment" not in startup["after_window"]
    metrics = startup["metrics"]
    assert set(metrics) == set(TABLE) - {"import_runtime_s"}
    assert all(v is not None and v >= 0 for v in metrics.values()), metrics
    # an empty cache directory: the round program and the helpers miss
    assert metrics["setup_cache_misses"] >= 1
    backend = [e for e in startup["record"] if e["name"] == "compile.backend"]
    assert backend[0]["args"] == {"round": 1, "program": "round.fused",
                                  "cache": "miss"}
    total = (setup["import_and_runtime_s"] + metrics["setup_import_s"]
             + metrics["setup_data_load_s"] + metrics["setup_partition_s"]
             + metrics["setup_engine_build_s"] + metrics["setup_init_s"]
             + metrics["setup_place_s"] + setup["first_dispatch_s"]
             + setup["further_warmup_s"] + metrics["setup_unattributed_s"])
    assert total == pytest.approx(setup["setup_s"], abs=1e-6)
    assert metrics["setup_unattributed_s"] < 0.05 * setup["setup_s"]
    split = metrics["round_lower_s"] + metrics["round_backend_s"]
    assert split == pytest.approx(setup["round_program_compile_s"], rel=0.05)
    hashes = bench_line(proc.stdout, "round_programs")
    assert len(hashes) == 1 and all(len(h) == 64 for h in hashes.values())
