"""bench.py's device-time regression gate (VERDICT r3 weak-#5): for
dispatch-bound configs (MFU < 5%) ``vs_baseline`` must gate on the round
program's measured DEVICE time — their wall r/s is mostly host time, so
a 2× real device regression could hide inside its swing. Pinned here: the
perfetto-trace parser (host/device track disambiguation) and the pure
gating rule, including that a simulated 2× device-time regression trips
the gate under ANY wall-clock reading."""

import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench


def _write_trace(path, events):
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_parse_device_ms_picks_device_track(tmp_path):
    """Host dispatch spans share the fn name; the parser must choose the
    track with the dominant total time (the device executions)."""
    events = [
        # host dispatch spans: pid 1, ~2ms each
        {"ph": "X", "pid": 1, "name": "jit_round_fn", "dur": 2000},
        {"ph": "X", "pid": 1, "name": "jit_round_fn", "dur": 2100},
        # device execution spans: pid 7, ~50ms each
        {"ph": "X", "pid": 7, "name": "jit_round_fn.12", "dur": 50000},
        {"ph": "X", "pid": 7, "name": "jit_round_fn.12", "dur": 52000},
        # unrelated op
        {"ph": "X", "pid": 7, "name": "fusion.3", "dur": 9000},
        # metadata event (no dur)
        {"ph": "M", "pid": 7, "name": "process_name"},
    ]
    _write_trace(str(tmp_path / "host.trace.json.gz"), events)
    ms = bench._parse_device_ms(str(tmp_path))
    assert ms == (50.0 + 52.0) / 2


def test_parse_device_ms_empty(tmp_path):
    assert bench._parse_device_ms(str(tmp_path)) is None


def test_gate_uses_device_time_for_dispatch_bound_configs():
    name = "femnist_fedprox_500"
    base_ms = bench.DEVICE_MS_BASELINES[name]
    # healthy: device time at baseline → vs ≈ 1 on the device basis
    vs, basis = bench._gate(name, rounds_per_sec=6.0,
                            device_ms=base_ms, mfu_pct=1.2)
    assert basis == "device_ms" and abs(vs - 1.0) < 1e-9
    # simulated 2× device-time regression: trips the gate EVEN IF the
    # wall clock reads better than baseline (a quiet host)
    vs, basis = bench._gate(name, rounds_per_sec=19.0,
                            device_ms=2 * base_ms, mfu_pct=1.2)
    assert basis == "device_ms" and vs == 0.5
    # and a 2× device-time WIN reads as 2× regardless of a loaded host
    vs, _ = bench._gate(name, rounds_per_sec=2.0,
                        device_ms=base_ms / 2, mfu_pct=1.2)
    assert vs == 2.0


def test_gate_keeps_wall_clock_for_device_bound_configs():
    """High-MFU configs gate on wall r/s (device-dominated clock), and
    configs without a device baseline fall back to r/s too."""
    vs, basis = bench._gate("cifar10_fedavg_100", rounds_per_sec=3.3,
                            device_ms=280.0, mfu_pct=40.0)
    assert basis == "rounds_per_sec"
    assert vs == 3.3 / bench.BASELINES["cifar10_fedavg_100"]
    # no trace available (device_ms None) → honest fallback to the
    # r/s baseline (re-pinned r5 at the adopted cohort-32 shape)
    vs, basis = bench._gate(
        "shakespeare_fedavg",
        rounds_per_sec=bench.BASELINES["shakespeare_fedavg"],
        device_ms=None, mfu_pct=0.7,
    )
    assert basis == "rounds_per_sec" and abs(vs - 1.0) < 1e-9


def test_gate_unknown_mfu_counts_as_dispatch_bound():
    """No cost model (mfu None) must not silently disable the device
    gate — it matches bench_config's measurement condition."""
    name = "shakespeare_fedavg"
    vs, basis = bench._gate(name, rounds_per_sec=40.0,
                            device_ms=2 * bench.DEVICE_MS_BASELINES[name],
                            mfu_pct=None)
    assert basis == "device_ms" and vs == 0.5


def test_bench_shapes_validate_and_divide_fuse():
    """Every bench shape's override set must validate against its named
    config (a bad pairing — e.g. fuse not dividing the bench round
    count — would kill the whole BENCH record at driver time)."""
    from colearn_federated_learning_tpu.config import get_named_config

    for name, (warmup, timed, overrides) in bench._SHAPES.items():
        cfg = get_named_config(bench._base_shape_name(name))
        cfg.server.num_rounds = warmup + timed
        cfg.server.eval_every = 0
        cfg.server.checkpoint_every = 0
        cfg.run.out_dir = ""
        cfg.apply_overrides(overrides)
        cfg.validate()
        fuse = cfg.run.fuse_rounds
        assert warmup % fuse == 0 and timed % fuse == 0, (name, fuse)


def test_peak_host_rss_is_measurable():
    """Every bench result now records num_clients + peak host RSS (the
    clients-scale axis, ROADMAP item 1): the measurement itself must be
    a sane positive MB figure on this platform."""
    rss = bench._peak_host_rss_mb()
    assert isinstance(rss, float) and 1.0 < rss < 1_000_000.0
    # monotone: a later reading never shrinks (ru_maxrss is a peak)
    assert bench._peak_host_rss_mb() >= rss


def test_store_scale_configs_validate():
    """The clients-scale bench entries (store_scale_1k/1m) must build a
    validating config — at the 1k scale end-to-end shape, without
    paying the store build here (bench does that lazily)."""
    from colearn_federated_learning_tpu.config import get_named_config

    assert set(bench._STORE_SCALE) == {"store_scale_1k", "store_scale_1m"}
    for n in bench._STORE_SCALE.values():
        cfg = get_named_config("mnist_fedavg_2")
        cfg.apply_overrides({
            "data.num_clients": n, "data.store.dir": "/nonexistent",
            "data.placement": "stream", "server.sampling": "streaming",
            "server.cohort_size": 16, "client.batch_size": 2,
            "server.num_rounds": 8, "server.eval_every": 0,
            "run.out_dir": "",
        })
        cfg.validate()


def test_mfu_basis_tracks_compute_dtype():
    """r7 hygiene: bf16-compute configs divide by the bf16 peak, pure
    f32 configs by the f32 stand-in — and the basis is recorded."""
    from colearn_federated_learning_tpu.config import get_named_config

    bf16 = get_named_config("cifar10_fedavg_100")
    basis, peak = bench._mfu_basis(bf16)
    assert basis == "bf16_peak" and peak == bench.PEAK_BF16_FLOPS
    f32 = get_named_config("mnist_fedavg_2")
    basis, peak = bench._mfu_basis(f32)
    assert basis == "f32_peak" and peak == bench.PEAK_F32_FLOPS


# ---------------------------------------------------------------------------
# bench regression observatory (r8): `colearn bench-report` trajectory
# + per-phase budget gates over BENCH_r*.json (obs/roofline.py)
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURE_HISTORY = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures", "bench_history"
)


def test_peaks_are_single_sourced_from_roofline():
    """bench.py re-exports the roofline peaks — a drifted local copy
    would make `colearn mfu`'s waterfall stop summing to the bench's
    headline MFU."""
    from colearn_federated_learning_tpu.obs import roofline

    assert bench.PEAK_BF16_FLOPS is roofline.PEAK_BF16_FLOPS
    assert bench.PEAK_F32_FLOPS is roofline.PEAK_F32_FLOPS


def test_load_bench_history_tolerates_pre_mfu_entries():
    """The r01 fixture mirrors the real first bench record, which
    predates every post-PR-7 extra (mfu_basis, compute_dtype,
    phase_ms, device_ms): loading and rendering must produce n/a
    fields, never a KeyError."""
    from colearn_federated_learning_tpu.obs import roofline

    entries = roofline.load_bench_history(_FIXTURE_HISTORY)
    assert len(entries) == 1
    e = entries[0]
    assert e["value"] == 3.0479 and e["n"] == 1
    for missing in ("mfu_pct", "mfu_basis", "compute_dtype",
                    "phase_ms_per_round", "device_ms_per_round"):
        assert e[missing] is None
    report = roofline.bench_report(entries, {"rounds_per_sec_min": 2.0})
    text = roofline.format_bench_report(report, _FIXTURE_HISTORY)
    assert "n/a" in text and report["violations"] == []


def test_bench_report_cli_passes_on_real_history(capsys):
    """A recorded driver entry (the fixture history: the first headline
    record) must pass the checked-in BENCH_BUDGETS.json — keeps the
    committed budgets honest (a budget nobody can meet would make
    every CI run red)."""
    from colearn_federated_learning_tpu import cli

    assert cli.main(["bench-report", "--dir", _FIXTURE_HISTORY,
                     "--baseline",
                     os.path.join(_ROOT, "BENCH_BUDGETS.json")]) == 0
    out = capsys.readouterr().out
    assert "BENCH_r01.json" in out and "PASS" in out


def _seed_history(tmp_path, phase_ms=None, value=3.42, n=6):
    """Copy the fixture history into tmp and append a synthetic newest
    entry (optionally carrying phase_ms extras)."""
    import shutil

    for src in sorted(glob.glob(
            os.path.join(_FIXTURE_HISTORY, "BENCH_r0*.json"))):
        shutil.copy(src, tmp_path / os.path.basename(src))
    extra = {"timed_rounds": 16, "mfu_pct": 41.0}
    if phase_ms is not None:
        extra["phase_ms"] = phase_ms
    entry = {"n": n, "rc": 0,
             "parsed": {"value": value, "vs_baseline": value / 2.22,
                        "extra": extra}}
    with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as f:
        json.dump(entry, f)


def test_bench_report_scalar_floor_gate_trips(tmp_path, capsys):
    from colearn_federated_learning_tpu import cli

    _seed_history(tmp_path, value=1.0)  # collapse vs the 3.0 floor
    with open(tmp_path / "BENCH_BUDGETS.json", "w") as f:
        json.dump({"rounds_per_sec_min": 3.0}, f)
    assert cli.main(["bench-report", "--dir", str(tmp_path)]) == 1
    assert "rounds_per_sec" in capsys.readouterr().out


def test_bench_report_phase_regression_names_the_phase(tmp_path, capsys):
    """The tier-1 observatory smoke (ISSUE 8 satellite): inject a
    synthetic per-phase regression into a copied bench history and the
    gate must exit non-zero NAMING the offending phase — the plateau
    is localized the moment it appears."""
    from colearn_federated_learning_tpu import cli

    _seed_history(tmp_path, n=6, phase_ms={
        "round.dispatch": 1600.0, "round.host_inputs": 160.0,
    })
    # newest entry: dispatch blown 2× per round, host_inputs healthy
    _seed_history(tmp_path, n=7, phase_ms={
        "round.dispatch": 3200.0, "round.host_inputs": 150.0,
    })
    with open(tmp_path / "BENCH_BUDGETS.json", "w") as f:
        json.dump({"rounds_per_sec_min": 3.0,
                   "phase_regression_factor": 1.25}, f)
    assert cli.main(["bench-report", "--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "round.dispatch" in out and "GATE FAILURES" in out
    # the healthy phase is not blamed
    assert not any("round.host_inputs" in line
                   for line in out.splitlines() if "exceeds" in line)


def test_bench_report_first_phase_appearance_pins_not_gates(tmp_path):
    """A phase's FIRST measured appearance has no best-so-far and no
    explicit budget: it becomes the pin, it cannot fail the gate (the
    r01-r05 history has no phase_ms at all — the first TPU run that
    records phases must go green)."""
    from colearn_federated_learning_tpu.obs import roofline

    _seed_history(tmp_path, phase_ms={"round.dispatch": 9999.0})
    entries = roofline.load_bench_history(str(tmp_path))
    report = roofline.bench_report(
        entries, {"rounds_per_sec_min": 3.0,
                  "phase_regression_factor": 1.25},
    )
    assert report["violations"] == []


def test_bench_report_explicit_phase_budget_overrides_best(tmp_path):
    from colearn_federated_learning_tpu.obs import roofline

    _seed_history(tmp_path, phase_ms={"round.dispatch": 1600.0})
    entries = roofline.load_bench_history(str(tmp_path))
    report = roofline.bench_report(entries, {
        "phase_budget_ms": {"round.dispatch": 50.0},  # 1600/16 = 100 > 50
    })
    assert any("round.dispatch" in v and "explicit" in v
               for v in report["violations"])


def _hier_async_entry(tmp_path, ups, max_stale, n=8):
    entry = {"n": n, "rc": 0, "parsed": {
        "value": ups, "vs_baseline": 1.0, "config": "hier_async_1m",
        "extra": {"staleness_bound": 4,
                  "max_realized_staleness": max_stale,
                  "hier_edges": 4, "async_versions": 2,
                  "per_version_absorbed": {"0": 50, "1": 50},
                  "per_edge_absorbed": {"0": 25, "1": 25,
                                        "2": 25, "3": 25}},
    }}
    with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as f:
        json.dump(entry, f)


def test_bench_report_hier_async_gates_on_both_axes(tmp_path):
    """The hier_async entries gate TWICE (ISSUE 16 satellite): the
    shared updates/sec floor AND the realized-staleness ceiling — a
    hierarchy that buys throughput by letting staleness run away
    still fails the report, naming the axis that tripped."""
    from colearn_federated_learning_tpu.obs import roofline

    budgets = {"async_updates_per_sec_min": 50.0,
               "hier_async_staleness_bound": 4}
    _seed_history(tmp_path)
    # healthy: above the floor, within the bound
    _hier_async_entry(tmp_path, ups=500.0, max_stale=3)
    entries = roofline.load_bench_history(str(tmp_path))
    assert entries[-1]["async_throughput"][0]["per_edge_absorbed"]
    assert roofline.bench_report(entries, budgets)["violations"] == []
    # staleness runs away while throughput stays green: still a failure
    _hier_async_entry(tmp_path, ups=500.0, max_stale=7)
    entries = roofline.load_bench_history(str(tmp_path))
    vios = roofline.bench_report(entries, budgets)["violations"]
    assert any("staleness 7" in v and "hier_async_1m" in v for v in vios)
    assert not any("updates/sec" in v for v in vios)
    # throughput collapse trips the shared floor too
    _hier_async_entry(tmp_path, ups=5.0, max_stale=3)
    entries = roofline.load_bench_history(str(tmp_path))
    vios = roofline.bench_report(entries, budgets)["violations"]
    assert any("updates/sec" in v for v in vios)


def test_hier_async_bench_entry_defined():
    assert bench._HIER_ASYNC_SCALE == {"hier_async_1m": 1_000_000}


# ---------------------------------------------------------------------------
# weak-scaling axis (r12): weak_scale_* entries + the bench-report line
# ---------------------------------------------------------------------------


def test_weak_scale_entries_defined():
    """The n_chips axis is measurement-ready: cohort-in-the-hundreds
    per-chip workloads reachable via --config and the matrix, with the
    per-chip cohort recorded so bench-report can group them."""
    assert bench._WEAK_SCALE == {
        "weak_scale_64": 64, "weak_scale_128": 128, "weak_scale_256": 256,
    }


def _weak_record(per_chip, n_chips, ups, config="weak_scale_64"):
    return {
        "metric": f"FL rounds/sec (weak scaling: {per_chip}/chip)",
        "value": 3.0,
        "unit": "rounds/sec",
        "vs_baseline": 1.0,
        "config": config,
        "extra": {
            "weak_scale_per_chip_cohort": per_chip,
            "cohort_size": per_chip * n_chips,
            "n_chips": n_chips,
            "client_updates_per_sec_per_chip": ups,
            "cohort_layout": "megabatch",
        },
    }


def test_bench_report_weak_scaling_efficiency_line(tmp_path):
    """A history whose tail carries weak_scale records (matrix-mode
    output) produces the efficiency line vs the 1-chip pin; the
    headline entry keeps parsing as before."""
    from colearn_federated_learning_tpu.obs import roofline

    one = _weak_record(64, 1, 400.0)
    four = _weak_record(64, 4, 300.0)
    headline = {
        "metric": "FL rounds/sec (100-client cifar10)",
        "value": 3.4, "unit": "rounds/sec", "vs_baseline": 1.5,
        "extra": {"n_chips": 1,
                  "client_updates_per_sec_per_chip": 54.7,
                  "cohort_layout": "megabatch"},
    }
    doc = {
        "n": 9,
        "tail": "\n".join([json.dumps(one), json.dumps(four),
                           json.dumps(headline)]),
        "parsed": headline,
    }
    with open(os.path.join(str(tmp_path), "BENCH_r09.json"), "w") as f:
        json.dump(doc, f)
    entries = roofline.load_bench_history(str(tmp_path))
    assert len(entries) == 1
    e = entries[0]
    # the new columns ride the normalized entry
    assert e["n_chips"] == 1 and e["updates_per_sec_per_chip"] == 54.7
    assert e["cohort_layout"] == "megabatch"
    assert len(e["weak_scale"]) == 2
    report = roofline.bench_report(entries)
    ws = report["weak_scaling"]
    assert [r["n_chips"] for r in ws] == [1, 4]
    assert ws[0]["efficiency"] == 1.0
    assert ws[1]["efficiency"] == 300.0 / 400.0
    assert ws[1]["pin_n_chips"] == 1
    text = roofline.format_bench_report(report, str(tmp_path))
    assert "weak scaling" in text and "upd/s/chip" in text
    assert "eff 0.75" in text


def test_bench_report_weak_scaling_na_on_historical_shapes():
    """The r01-era history has no weak_scale entries anywhere: the
    report carries an empty weak_scaling list and the formatter prints
    n/a — never a KeyError (ISSUE 12 satellite)."""
    from colearn_federated_learning_tpu.obs import roofline

    entries = roofline.load_bench_history(_FIXTURE_HISTORY)
    report = roofline.bench_report(entries)
    assert report["weak_scaling"] == []
    text = roofline.format_bench_report(report, _FIXTURE_HISTORY)
    assert "weak scaling: n/a" in text


def test_bench_report_weak_scaling_pin_fallback(tmp_path):
    """No 1-chip measurement yet: the smallest-chip entry becomes the
    pin and the readout says so (pin_n_chips) instead of silently
    normalizing against nothing."""
    from colearn_federated_learning_tpu.obs import roofline

    rec2 = _weak_record(128, 2, 380.0, config="weak_scale_128")
    rec8 = _weak_record(128, 8, 342.0, config="weak_scale_128")
    doc = {"n": 10, "tail": json.dumps(rec2) + "\n" + json.dumps(rec8),
           "parsed": rec8}
    with open(os.path.join(str(tmp_path), "BENCH_r10.json"), "w") as f:
        json.dump(doc, f)
    entries = roofline.load_bench_history(str(tmp_path))
    report = roofline.bench_report(entries)
    ws = report["weak_scaling"]
    assert [r["n_chips"] for r in ws] == [2, 8]
    assert ws[0]["pin_n_chips"] == 2 and ws[0]["efficiency"] == 1.0
    assert ws[1]["efficiency"] == 342.0 / 380.0


def test_weak_scale_configs_validate_per_chip_count():
    """Every weak_scale entry's config must validate at 1, 4, and 8
    chips (construction only — the ResNet run itself is TPU-budget):
    megabatch layout, cohort = per_chip × n_chips, federation 2× the
    cohort."""
    for per_chip in bench._WEAK_SCALE.values():
        for chips in (1, 4, 8):
            cfg = bench._weak_scale_cfg(per_chip, chips, 2, 4)
            assert cfg.run.cohort_layout == "megabatch"
            assert cfg.server.cohort_size == per_chip * chips
            assert cfg.data.num_clients == 2 * per_chip * chips
            assert cfg.server.num_rounds == 6


def test_bench_report_weak_scaling_from_direct_run_record(tmp_path):
    """A dedicated `bench.py --config weak_scale_*` BENCH file (no
    matrix tail, no `config` key — the driver's single-config shape)
    still feeds the weak-scaling line: the per-chip-cohort extra is the
    marker and names the group."""
    from colearn_federated_learning_tpu.obs import roofline

    rec = _weak_record(64, 1, 410.0)
    del rec["config"]
    doc = {"n": 11, "tail": json.dumps(rec), "parsed": rec}
    with open(os.path.join(str(tmp_path), "BENCH_r11.json"), "w") as f:
        json.dump(doc, f)
    entries = roofline.load_bench_history(str(tmp_path))
    ws = roofline.bench_report(entries)["weak_scaling"]
    assert len(ws) == 1
    assert ws[0]["name"] == "weak_scale_64"
    assert ws[0]["efficiency"] == 1.0
