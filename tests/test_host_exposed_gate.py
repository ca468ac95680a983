"""`host_exposed_pct` observability-tax budget: the roofline helper's
span accounting, its passthrough from BENCH_r*.json extras, the
`bench-report` ceiling gate (n/a-tolerant — the checked-in r01–r05
history predates the field and must keep passing), and the table
column."""

import json

from colearn_federated_learning_tpu import cli
from colearn_federated_learning_tpu.obs.roofline import (
    _NON_HOST_EXPOSED_SPANS,
    bench_report,
    format_bench_report,
    host_exposed_pct,
    load_bench_history,
)


def test_host_exposed_pct_counts_only_host_spans():
    phase_ms = {
        "round": 1000.0,           # parent bracket — excluded
        "round.dispatch": 700.0,   # device work hides here — excluded
        "compile": 50.0,           # fires inside dispatch — excluded
        # the registry's compile brackets duplicate the `compile`
        # pseudo-phase's wall — excluded for the same reason
        "obs.executables": 40.0,
        "obs.preflight": 30.0,
        "round.host_inputs": 100.0,
        "round.fetch": 100.0,
    }
    # 200 host ms over a 1 s wall = 20%
    assert host_exposed_pct(phase_ms, 1.0) == 20.0
    assert set(_NON_HOST_EXPOSED_SPANS) == {
        "round", "round.dispatch", "compile",
        "obs.executables", "obs.preflight"}


def test_host_exposed_pct_unmeasured_wall_is_none():
    assert host_exposed_pct({"round.fetch": 5.0}, 0.0) is None
    assert host_exposed_pct({}, 2.0) == 0.0


def _bench_doc(value, extra):
    return {"n": 1, "parsed": {"value": value, "extra": extra}}


def _write_history(tmp_path, host_pcts):
    for i, pct in enumerate(host_pcts, start=1):
        extra = {} if pct is None else {"host_exposed_pct": pct}
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps(_bench_doc(3.5, extra)))
    return str(tmp_path)


def test_history_passthrough_and_na_tolerance(tmp_path):
    entries = load_bench_history(_write_history(tmp_path, [None, 42.5]))
    assert entries[0]["host_exposed_pct"] is None
    assert entries[1]["host_exposed_pct"] == 42.5
    table = format_bench_report(bench_report(entries))
    assert "host%" in table
    assert "42.5" in table


def test_gate_fires_only_over_budget(tmp_path):
    entries = load_bench_history(_write_history(tmp_path, [None, 42.5]))
    budgets = {"host_exposed_pct_max": 60.0}
    assert bench_report(entries, budgets)["violations"] == []
    budgets = {"host_exposed_pct_max": 40.0}
    violations = bench_report(entries, budgets)["violations"]
    assert len(violations) == 1
    assert "host_exposed_pct 42.5" in violations[0]
    assert "40.0" in violations[0]
    table = format_bench_report(bench_report(entries, budgets))
    assert "GATE FAILURES" in table


def test_gate_never_fires_on_missing_field(tmp_path):
    # a history that predates the field: the ceiling must render n/a,
    # not trip — exactly the checked-in r01–r05 situation
    entries = load_bench_history(_write_history(tmp_path, [None, None]))
    budgets = {"host_exposed_pct_max": 0.001}
    assert bench_report(entries, budgets)["violations"] == []


def test_checked_in_history_still_passes_repo_budgets(capsys):
    # the recorded fixture history against the repo's own
    # BENCH_BUDGETS.json (which now carries host_exposed_pct_max)
    budgets = json.load(open("BENCH_BUDGETS.json"))
    assert "host_exposed_pct_max" in budgets
    assert cli.main(["bench-report", "--dir",
                     "tests/fixtures/bench_history",
                     "--baseline", "BENCH_BUDGETS.json"]) == 0
    out = capsys.readouterr().out
    assert "gates: PASS" in out
    assert "host%" in out
