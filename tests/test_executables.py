"""Compiled-program observatory (run.obs.executables, obs/executables.py):
AOT registry records + HBM watermarks, the bitwise no-op contract,
fingerprint/flop rerun parity across {sharded, sequential} × {fuse 1, 4},
CPU degradation to partial records, the OOM preflight (driver + CLI +
budget abort), retrace forensics on the shape-bucket ladder, and the
registry's FLOP count against the benchmark's shape count."""

import json
import os

import jax
import numpy as np
import pytest

from colearn_federated_learning_tpu import cli
from colearn_federated_learning_tpu.config import get_named_config
from colearn_federated_learning_tpu.obs import executables as exec_mod
from colearn_federated_learning_tpu.obs.executables import (
    ExecutableRegistry,
    HbmBudgetError,
    format_preflight_report,
    instrument,
)
from colearn_federated_learning_tpu.obs.summary import (
    format_summary,
    load_records,
    summarize_records,
)
from colearn_federated_learning_tpu.server.round_driver import Experiment


def _tiny_cfg(out="", engine="sharded", fuse=1, rounds=2, **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.data.synthetic_train_size = 256
    cfg.data.synthetic_test_size = 64
    cfg.data.max_examples_per_client = 64
    cfg.client.batch_size = 16
    cfg.server.cohort_size = 2
    cfg.server.num_rounds = rounds
    cfg.server.eval_every = 0
    cfg.server.checkpoint_every = 0
    cfg.run.out_dir = out
    cfg.run.engine = engine
    cfg.run.fuse_rounds = fuse
    cfg.run.metrics_flush_every = 1
    for k, v in over.items():
        cfg.apply_overrides({k: v})
    return cfg.validate()


def _fit(cfg):
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    records = []
    if cfg.run.out_dir:
        hits = sorted(
            (os.path.join(cfg.run.out_dir, f)
             for f in os.listdir(cfg.run.out_dir)
             if f.endswith(".metrics.jsonl")),
            key=os.path.getmtime,
        )
        records = load_records(hits[-1])
    return exp, state, records


def _events(records, event):
    return [r for r in records if r.get("event") == event]


# ---------------------------------------------------------------------------
# registry wrapper unit behavior (no driver)
# ---------------------------------------------------------------------------


def test_instrument_passthrough_without_registry():
    fn = instrument("unit.addone", jax.jit(lambda x: x + 1))
    assert exec_mod.current() is None
    np.testing.assert_array_equal(
        np.asarray(fn(np.arange(4.0))), np.arange(4.0) + 1
    )


def test_registry_caches_by_shape_and_emits_retrace():
    reg = ExecutableRegistry()
    exec_mod.install(reg)
    try:
        fn = instrument("unit.scale", jax.jit(lambda x: x * 2.0))
        a = np.ones((4, 3), np.float32)
        fn(a)
        fn(a + 1)  # same avals: cache hit, no recompile
        compiled = reg.drain_records()
        assert [r["name"] for r in compiled] == ["unit.scale"]
        assert len(compiled[0]["fingerprint"]) == 16
        assert compiled[0]["compile_ms"] > 0
        # a new shape is a retrace: record names the changed argument
        fn(np.ones((8, 3), np.float32))
        recs = reg.drain_records()
        kinds = {r["event"] for r in recs}
        assert kinds == {"executable_compiled", "retrace"}
        ret = next(r for r in recs if r["event"] == "retrace")
        assert ret["name"] == "unit.scale"
        assert ret["prev_fingerprint"] == compiled[0]["fingerprint"]
        assert [c["arg"] for c in ret["changed"]] == ["x"]
    finally:
        exec_mod.uninstall()


def test_instrumented_program_nests_under_outer_trace():
    # the device plane inlines instrumented programs under its own jit
    # trace: the wrapper must pass through (no lowering of tracers)
    reg = ExecutableRegistry()
    exec_mod.install(reg)
    try:
        inner = instrument("unit.inner", jax.jit(lambda x: x + 1))
        outer = jax.jit(lambda x: inner(x) * 2)
        np.testing.assert_array_equal(
            np.asarray(outer(np.arange(3.0))), (np.arange(3.0) + 1) * 2
        )
        assert all(
            r["name"] != "unit.inner" for r in reg.drain_records()
            if r.get("event") == "executable_compiled"
        )
    finally:
        exec_mod.uninstall()


# ---------------------------------------------------------------------------
# fit integration: records, watermarks, run_summary, bitwise contract
# ---------------------------------------------------------------------------


def test_fit_emits_records_watermarks_and_run_summary(tmp_path):
    _, _, records = _fit(_tiny_cfg(out=str(tmp_path)))
    compiled = _events(records, "executable_compiled")
    names = {r["name"] for r in compiled}
    assert "round.sync" in names
    for r in compiled:
        assert len(r["fingerprint"]) == 16
        assert r["compile_ms"] > 0
        assert r["rounds_per_call"] >= 1
        assert r["preflight"] is False
    wm = _events(records, "hbm_watermark")
    assert wm and all(w["watermark_bytes"] > 0 for w in wm)
    assert any(w.get("program") == "round.sync" for w in wm)
    run_sum = _events(records, "run_summary")[-1]
    assert run_sum["hbm_peak_bytes"] > 0
    assert run_sum["hbm_peak_program"] in names
    assert run_sum["executables_compiled"] >= len(names)
    # the registry runs under its own named span, outside round.dispatch
    span_names = set()
    for rec in _events(records, "spans"):
        span_names |= set(rec.get("phases") or {})
    assert "obs.executables" in span_names


def test_registry_on_off_params_bitwise_identical(tmp_path):
    _, on_state, _ = _fit(_tiny_cfg(out=str(tmp_path / "on")))
    cfg_off = _tiny_cfg(out=str(tmp_path / "off"))
    cfg_off.run.obs.executables = False
    _, off_state, off_records = _fit(cfg_off)
    assert not _events(off_records, "executable_compiled")
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        on_state["params"], off_state["params"],
    )


# sequential × fuse 4 is not a combo: validate() rejects fuse_rounds > 1
# off the sharded engine, so the realizable matrix has three cells
@pytest.mark.parametrize("engine,fuse",
                         [("sharded", 1), ("sharded", 4), ("sequential", 1)])
def test_fingerprint_flop_columns_parity_on_rerun(tmp_path, engine, fuse):
    # same config, two runs: the registry streams are pinned
    # deterministic on fingerprint/flop/memory columns (timing
    # stripped) — per engine × fuse combo
    def columns(sub):
        _, _, records = _fit(_tiny_cfg(
            out=str(tmp_path / sub), engine=engine, fuse=fuse, rounds=4))
        compiled = sorted(
            (r["name"], r["fingerprint"], r["flops"], r["bytes_accessed"],
             r["peak_bytes"], r["donated_args"], r["rounds_per_call"])
            for r in _events(records, "executable_compiled")
        )
        watermarks = [
            (w["round"], w["watermark_bytes"], w.get("program"))
            for w in _events(records, "hbm_watermark")
        ]
        retraces = sorted(
            (r["name"], r["fingerprint"], r["prev_fingerprint"],
             r["n_changed"], json.dumps(r["changed"]))
            for r in _events(records, "retrace")
        )
        return compiled, watermarks, retraces
    first = columns("a")
    assert first[0]  # the combo actually produced registry records
    assert first == columns("b")


def test_degrades_to_partial_records_when_analyses_unavailable(
        tmp_path, monkeypatch):
    # a backend without cost/memory analysis: fields go null, training
    # is never taken down
    from jax._src import stages

    def unavailable(self, *a, **k):
        raise NotImplementedError("no analysis on this backend")

    monkeypatch.setattr(stages.Compiled, "cost_analysis", unavailable)
    monkeypatch.setattr(stages.Compiled, "memory_analysis", unavailable)
    _, state, records = _fit(_tiny_cfg(out=str(tmp_path)))
    assert int(state["round"]) == 2
    compiled = _events(records, "executable_compiled")
    assert compiled
    for r in compiled:
        assert r["flops"] is None
        assert r["peak_bytes"] is None
        assert r["compile_ms"] > 0  # the compile itself still happened
    assert not _events(records, "hbm_watermark")  # nothing to watermark


# ---------------------------------------------------------------------------
# OOM preflight + HBM budget
# ---------------------------------------------------------------------------


def test_preflight_predicts_measured_peak_within_25pct(tmp_path):
    exp = Experiment(_tiny_cfg(out=str(tmp_path / "pf")), echo=False)
    report = exp.preflight()
    predicted = report["predicted_peak_bytes"]
    assert predicted > 0
    assert report["predicted_peak_program"] == "round.sync"
    dom = next(p for p in report["programs"] if p["name"] == "round.sync")
    assert dom["dominant"]  # names the dominant buffers
    table = format_preflight_report(report)
    assert "round.sync" in table and "predicted peak" in table
    _, _, records = _fit(_tiny_cfg(out=str(tmp_path / "fit")))
    measured = max(
        w["watermark_bytes"] for w in _events(records, "hbm_watermark")
    )
    assert abs(predicted - measured) / measured <= 0.25


def test_preflight_rejects_sequential_oracle(tmp_path):
    exp = Experiment(
        _tiny_cfg(out=str(tmp_path), engine="sequential"), echo=False)
    with pytest.raises(ValueError, match="sharded"):
        exp.preflight()


def test_hbm_budget_aborts_fit_at_compile_time(tmp_path):
    cfg = _tiny_cfg(out=str(tmp_path))
    cfg.run.obs.hbm_budget_mb = 1  # tiny: every real program exceeds it
    exp = Experiment(cfg, echo=False)
    with pytest.raises(HbmBudgetError, match="dominant buffers"):
        exp.fit()


def _preflight_argv(tmp, *extra):
    return ["preflight", "--config", "mnist_fedavg_2",
            "--out-dir", str(tmp),
            "--set", "data.synthetic_train_size=256",
            "--set", "data.synthetic_test_size=64",
            "--set", "data.max_examples_per_client=64",
            "--set", "client.batch_size=16",
            "--set", "server.cohort_size=2", *extra]


def test_preflight_cli_exit_codes(tmp_path, capsys):
    assert cli.main(_preflight_argv(tmp_path / "ok")) == 0
    out = capsys.readouterr().out
    assert "predicted peak" in out and "round.sync" in out
    # oversized config vs a tiny budget: non-zero, names the dominant
    # buffer on stderr
    assert cli.main(_preflight_argv(
        tmp_path / "over", "--set", "run.obs.hbm_budget_mb=1")) == 1
    err = capsys.readouterr().err
    assert "dominant buffers" in err and "round.sync" in err
    # the sequential oracle cannot preflight: distinct exit code
    assert cli.main(_preflight_argv(
        tmp_path / "seq", "--set", "run.engine=sequential")) == 2


# ---------------------------------------------------------------------------
# retrace forensics: the shape-bucket ladder documents itself
# ---------------------------------------------------------------------------


def test_shape_bucket_retraces_name_the_step_grid_arg(tmp_path):
    cfg = _tiny_cfg(out=str(tmp_path), rounds=6)
    cfg.data.num_clients = 8
    cfg.data.partition = "dirichlet"
    cfg.data.dirichlet_alpha = 0.3
    cfg.client.batch_size = 8
    cfg.run.host_pipeline = "numpy"
    cfg.run.shape_buckets.enabled = True
    cfg.run.shape_buckets.base = 2.0
    cfg.run.shape_buckets.count = 3
    cfg.validate()
    _, _, records = _fit(cfg)
    retraces = [r for r in _events(records, "retrace")
                if r["name"] == "round.sync"]
    assert retraces  # the ladder realized more than one rung
    for r in retraces:
        assert r["fingerprint"] != r["prev_fingerprint"]
        # each rung's retrace names the step-grid argument
        assert "idx" in [c["arg"] for c in r["changed"]]
    table = format_summary(summarize_records(records))
    assert "retraces" in table and "idx" in table


# ---------------------------------------------------------------------------
# summarize: compile table + n/a fallback
# ---------------------------------------------------------------------------


def test_summarize_compile_table(tmp_path):
    _, _, records = _fit(_tiny_cfg(out=str(tmp_path)))
    table = format_summary(summarize_records(records))
    assert "executable" in table and "round.sync" in table
    assert "hbm peak:" in table


def test_summarize_pre_pr20_log_never_keyerrors(tmp_path):
    # strip every registry artifact: exactly a pre-PR-20 log
    _, _, records = _fit(_tiny_cfg(out=str(tmp_path)))
    old = []
    for r in records:
        if r.get("event") in ("executable_compiled", "hbm_watermark",
                              "retrace"):
            continue
        if r.get("event") == "run_summary":
            r = {k: v for k, v in r.items()
                 if not k.startswith("hbm_") and k != "executables_compiled"}
        old.append(r)
    summary = summarize_records(old)
    assert "executables" not in summary
    table = format_summary(summary)
    assert "per-executable table n/a" in table


# ---------------------------------------------------------------------------
# the two FLOP answers that remain, against each other
# ---------------------------------------------------------------------------

# XLA's cost analysis counts the body of a `while` once, whatever its
# trip count (CPU, PR 47: the compiled programs' known_trip_count), so
# the registry's figure is that of ONE trip through the round program's
# nested loops, and "the same work" from shapes is one trip's examples:
#   dry_r18_fused: fused rounds (2) x local steps (2) x clients of the
#     megabatch block (4); a trip trains one client's batch.
#   dry_vit_dp: clients (2) x local steps (2) x DP microbatches (2); a
#     trip trains one microbatch.
# `reading` is registry / shape count as read at commit 838c17d on the
# CPU backend. The ResNet's sits inside the 40 % band around 1 that
# `flop_drift_pct_max` used to keep. The ViT's does not, for reasons
# that are the shape count's own rules (benchmark/harness/flops.py):
# DP-SGD's norm pass and clipped pass (privacy/dp.py: two Gram matrices
# and one weighted product a kernel, per-example gradients for every
# other leaf) are re-evaluation it leaves out, and at hidden 32 the
# elementwise work it leaves out as "under 1 %" at published widths
# (LayerNorm, GELU, softmax, the float32 clip-and-noise over every
# leaf, which XLA counts at one FLOP an element) is of the order of the
# products. So the test states that reading and keeps the band around it.
_SHAPE_COUNT_CASES = {
    "dry_resnet": dict(
        workload="dry_r18_fused", reading=0.97,
        trip_examples=lambda cfg: cfg.client.batch_size),
    "dry_vit": dict(
        workload="dry_vit_dp", reading=2.78,
        trip_examples=lambda cfg: cfg.dp.microbatch_size),
}
_FLOP_BAND = 0.40


@pytest.mark.parametrize("config_name", sorted(_SHAPE_COUNT_CASES))
def test_registry_flops_agree_with_the_shape_count(config_name):
    """``cost_analysis`` flops of the compiled round program (what the
    registry harvests) against forward MACs x 6 x examples from
    ``benchmark/flops/<family>.py``: a loop restructured, a pass added
    to the trainer or a family's count gone wrong moves one and not the
    other."""
    import sys

    beside_the_benchmarks_tests = os.path.join(os.path.dirname(__file__),
                                               "benchmark")
    if beside_the_benchmarks_tests not in sys.path:
        sys.path.insert(0, beside_the_benchmarks_tests)
    import bench_paths  # noqa: F401  (puts benchmark/ on sys.path)
    from harness import catalog, flops, window

    from colearn_federated_learning_tpu.config import resolve_config

    case = _SHAPE_COUNT_CASES[config_name]
    cell = catalog.load_workload(case["workload"])
    assert cell["config"] == config_name
    config = catalog.load_config(config_name)
    cfg = resolve_config(cell["named_config"],
                         catalog.experiment_overrides(cell, config, 0))
    exp = Experiment(cfg, echo=False)
    run = window.Run(exp, 0)
    exec_mod.install(exp._exec_reg)
    try:
        run.start()
        run.first_dispatch()
    finally:
        exp._stop_prefetch()
        exec_mod.uninstall()
    compiled = [r for r in exp._exec_reg.drain_records()
                if r["event"] == "executable_compiled"
                and r["name"].startswith("round.")]
    assert len(compiled) == 1, [r["name"] for r in compiled]
    if compiled[0]["flops"] is None:
        pytest.skip(f"backend {compiled[0]['backend']!r} gives no cost model")
    shape_count = (flops.train_flops_per_example(config["flops"])
                   * case["trip_examples"](cfg))
    ratio = compiled[0]["flops"] / shape_count
    assert abs(ratio / case["reading"] - 1.0) <= _FLOP_BAND, (
        f"{compiled[0]['name']}: registry {compiled[0]['flops']:.4g} FLOPs, "
        f"shape count of one trip {shape_count:.4g}: ratio {ratio:.3f}, "
        f"read {case['reading']} at 838c17d")


def test_lowering_runs_on_a_frame_with_a_chunk_of_its_own():
    """``_on_roomy_stack`` forwards the call and asks the interpreter
    for a frame larger than its 16 KiB stack chunks, so that frames
    pushed after it (a whole trace) cross no chunk's end; with it a
    loop of calls at any depth takes next to no page faults."""
    import resource

    roomy = exec_mod._on_roomy_stack
    assert roomy(lambda a, b=0: (a, b), 1, b=2) == (1, 2)
    assert roomy.__code__.co_stacksize * 8 >= 1 << 20
    with pytest.raises(ZeroDivisionError):
        roomy(lambda: 1 / 0)

    def leaf():
        a = b = c = d = e = f = g = h = None  # noqa: F841 (a wider frame)

    def at_depth(depth, n):
        if depth:
            return at_depth(depth - 1, n)
        before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        for _ in range(n):
            leaf()
        return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before

    # some depth in any 16 KiB of frames straddles a chunk's end without it
    worst = max(roomy(at_depth, depth, 2000) for depth in range(0, 200, 2))
    assert worst < 200
