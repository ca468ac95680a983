"""Observability layer (obs/, run.obs): span nesting + trace
well-formedness, analytic comm-counter parity between engines, the
JSONL schema contract, health monitoring's NaN/divergence detection and
abort paths, and the `summarize` aggregation the CLI serves."""

import json
import os

import pytest

from colearn_federated_learning_tpu import cli
from colearn_federated_learning_tpu.config import (
    ServerConfig,
    get_named_config,
)
from colearn_federated_learning_tpu.obs import (
    HealthAbortError,
    HealthMonitor,
    Tracer,
    round_comm_bytes,
)
from colearn_federated_learning_tpu.obs.spans import _NULL_SPAN
from colearn_federated_learning_tpu.obs.summary import (
    format_summary,
    load_records,
    resolve_metrics_path,
    summarize_records,
)
from colearn_federated_learning_tpu.utils.metrics import (
    SCHEMA_VERSION,
    MetricsLogger,
)


# ---------------------------------------------------------------------------
# spans


def test_tracer_nesting_and_aggregation():
    clock = iter(float(t) for t in range(100))
    tracer = Tracer(enabled=True, trace=True, clock=lambda: next(clock))
    # t0 consumed at construction; outer spans [1, 6], inner [2, 3]
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
        with tracer.span("inner"):
            pass
    agg = tracer.drain()
    assert agg["outer"]["count"] == 1
    assert agg["inner"]["count"] == 2
    # inner spans each took 1 "second" on the fake clock
    assert agg["inner"]["total_ms"] == pytest.approx(2000.0)
    assert agg["inner"]["max_ms"] == pytest.approx(1000.0)
    # drain resets
    assert tracer.drain() == {}


def test_tracer_trace_export_is_wellformed_and_nested(tmp_path):
    clock = iter(float(t) for t in range(100))
    tracer = Tracer(enabled=True, trace=True, clock=lambda: next(clock))
    with tracer.span("parent"):
        with tracer.span("child"):
            pass
    path = tracer.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"parent", "child"}
    for e in events:
        assert e["dur"] >= 0 and e["ts"] >= 0 and "pid" in e and "tid" in e
    p, c = by_name["parent"], by_name["child"]
    # the child's interval lies INSIDE the parent's (nesting survives
    # into the trace, so Perfetto stacks them)
    assert p["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= p["ts"] + p["dur"]


def test_self_ms_is_duration_minus_child_spans():
    """Nested and sibling spans on a fake clock that ticks once per
    read: every span lasts 1 s plus what it holds."""
    clock = iter(float(t) for t in range(100))
    tracer = Tracer(clock=lambda: next(clock))
    with tracer.span("run"):                # [1, 10]
        with tracer.span("inputs"):         # [2, 5]
            with tracer.span("sampler"):    # [3, 4]
                pass
        with tracer.span("wait"):           # [6, 7]
            pass
        with tracer.span("wait"):           # [8, 9]
            pass
    agg = tracer.drain()
    assert agg["run"]["total_ms"] == pytest.approx(9000.0)
    # minus inputs (3 s) and the two waits (1 s each), not the sampler
    # again: that is inside inputs already
    assert agg["run"]["self_ms"] == pytest.approx(4000.0)
    assert agg["inputs"]["self_ms"] == pytest.approx(2000.0)
    assert agg["sampler"]["self_ms"] == agg["sampler"]["total_ms"]
    assert agg["wait"]["count"] == 2
    assert agg["wait"]["self_ms"] == pytest.approx(2000.0)
    for name, a in agg.items():
        assert set(a) == {"count", "total_ms", "max_ms", "self_ms"}, name


def test_spans_of_two_threads_nest_apart_and_have_lanes_of_their_own():
    """A span opened on the worker while the main thread's span is open
    is not that span's child: it neither shortens its self time nor
    inherits its round, and the two threads' Chrome events sit in lanes
    0 and 1 (``get_ident() & 0xFFFF`` could merge two threads)."""
    import threading

    ticks = iter(float(t) for t in range(100))
    lock = threading.Lock()

    def clock():
        with lock:
            return next(ticks)

    tracer = Tracer(trace=True, clock=clock)
    opened, done = threading.Event(), threading.Event()

    def worker():
        assert opened.wait(10)
        with tracer.span("round.prefetch", round=5):
            with tracer.span("round.host_inputs.sampler"):
                pass
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    with tracer.span("round.run", round=1):
        opened.set()
        assert done.wait(10)
        with tracer.span("round.dispatch", fuse=4):
            pass
    t.join(10)
    assert not t.is_alive()
    agg = tracer.drain()
    # run: [1, 8] holds the worker's four ticks and dispatch [6, 7]
    assert agg["round.run"]["total_ms"] == pytest.approx(7000.0)
    assert agg["round.run"]["self_ms"] == pytest.approx(6000.0)
    assert agg["round.prefetch"]["self_ms"] == pytest.approx(2000.0)
    events = {e["name"]: e for e in tracer._events}
    assert events["round.run"]["tid"] == events["round.dispatch"]["tid"] == 0
    assert events["round.prefetch"]["tid"] == 1
    assert events["round.host_inputs.sampler"]["tid"] == 1
    # the request identifier passes down a thread's own stack only
    assert events["round.dispatch"]["args"] == {"round": 1, "fuse": 4}
    assert events["round.host_inputs.sampler"]["args"] == {"round": 5}


def test_an_own_round_argument_wins_over_the_inherited_one():
    tracer = Tracer(trace=True)
    with tracer.span("round.run", round=1):
        with tracer.span("round.prefetch", round=9):
            with tracer.span("leaf", what="x"):
                pass
    with tracer.span("outside"):
        pass
    events = {e["name"]: e for e in tracer._events}
    assert events["round.prefetch"]["args"] == {"round": 9}
    assert events["leaf"]["args"] == {"round": 9, "what": "x"}
    assert "args" not in events["outside"]


def test_counts_ride_a_spans_aggregate_until_the_next_drain():
    clock = iter(float(t) for t in range(100))
    tracer = Tracer(enabled=True, clock=lambda: next(clock))
    for dead in (5, 7):
        with tracer.span("round.host_inputs.slab_build"):
            pass
        tracer.count("round.host_inputs.slab_build",
                     client_steps=32, dead_steps=dead)
    with tracer.span("round.run"):
        pass
    agg = tracer.drain()
    assert agg["round.host_inputs.slab_build"] == {
        "count": 2, "total_ms": 2000.0, "max_ms": 1000.0, "self_ms": 2000.0,
        "client_steps": 64, "dead_steps": 12}
    assert set(agg["round.run"]) == {"count", "total_ms", "max_ms", "self_ms"}
    # a count that lands after the drain that took its span: an entry of
    # its own in the next window, with the keys every reader expects
    tracer.count("round.host_inputs.slab_build", client_steps=32)
    assert tracer.drain() == {"round.host_inputs.slab_build": {
        "count": 0, "total_ms": 0.0, "max_ms": 0.0, "self_ms": 0.0,
        "client_steps": 32}}
    assert tracer.drain() == {}
    off = Tracer(enabled=False)
    off.count("round.host_inputs.slab_build", client_steps=32)
    assert off.drain() == {}


def test_tracer_disabled_is_noop():
    tracer = Tracer(enabled=False)
    assert tracer.span("anything") is _NULL_SPAN  # shared singleton
    assert tracer.span("round.run", round=3) is _NULL_SPAN
    with tracer.span("anything"):
        pass
    assert tracer.drain() == {}
    assert tracer.export("/nonexistent/never-written.json") is None


# ---------------------------------------------------------------------------
# counters (pure wire model)


def test_comm_bytes_uncompressed():
    out = round_comm_bytes(ServerConfig(), n_participants=3, n_downloads=4,
                           n_coords=1000, param_bytes=4000)
    assert out == {
        "upload_bytes": 12000, "upload_bytes_raw": 12000,
        "download_bytes": 16000, "download_bytes_raw": 16000,
    }


def test_comm_bytes_topk_and_qsgd_and_secagg():
    topk = round_comm_bytes(
        ServerConfig(compression="topk", compression_topk_ratio=0.01),
        n_participants=2, n_downloads=2, n_coords=10_000, param_bytes=40_000,
    )
    # 100 kept coords × (4 B value + 4 B index) per participant
    assert topk["upload_bytes"] == 2 * 100 * 8
    assert topk["upload_bytes_raw"] == 2 * 40_000

    qsgd = round_comm_bytes(
        ServerConfig(compression="qsgd", compression_qsgd_levels=256),
        n_participants=1, n_downloads=1, n_coords=8000, param_bytes=32_000,
    )
    # 1 sign + 8 level bits = 9 bits/coord
    assert qsgd["upload_bytes"] == (8000 * 9 + 7) // 8

    sec = round_comm_bytes(
        ServerConfig(secure_aggregation=True, clip_delta_norm=1.0),
        n_participants=2, n_downloads=2, n_coords=1000, param_bytes=4000,
    )
    assert sec["upload_bytes"] == 2 * 1000 * 4  # dense int32 wire

    down = round_comm_bytes(
        ServerConfig(downlink_compression="qsgd", downlink_qsgd_levels=16),
        n_participants=1, n_downloads=3, n_coords=800, param_bytes=3200,
    )
    assert down["download_bytes"] == 3 * ((800 * 5 + 7) // 8)
    assert down["download_bytes_raw"] == 3 * 3200


# ---------------------------------------------------------------------------
# health monitor


def test_health_monitor_nan_and_divergence():
    mon = HealthMonitor(divergence_factor=2.0)
    assert mon.observe_loss(1, 1.0) is None
    assert mon.observe_loss(2, 0.5) is None  # improving
    ev = mon.observe_loss(3, float("nan"))
    assert ev["kind"] == "non_finite_loss" and ev["round"] == 3
    ev = mon.observe_loss(4, 1.5)  # > 2 × best (0.5)
    assert ev["kind"] == "divergence" and ev["best_loss"] == 0.5
    assert mon.observe_loss(5, 0.9) is None  # within the band
    ev = mon.observe_params_finite(6, False)
    assert ev["kind"] == "non_finite_params"
    assert mon.observe_params_finite(6, True) is None


# ---------------------------------------------------------------------------
# MetricsLogger contract (satellites: held handle + schema validation)


def test_metrics_logger_holds_one_handle_and_reopens(tmp_path):
    log = MetricsLogger(str(tmp_path), "run", echo=False)
    log.log({"round": 1, "x": 1.0})
    fh = log._fh
    assert fh is not None
    log.log({"round": 2, "x": 2.0})
    assert log._fh is fh  # no reopen per record
    log.close()
    assert log._fh is None
    log.log({"event": "late"})  # a close()d logger reopens (fit-after-fit)
    log.close()
    recs = [json.loads(l) for l in open(tmp_path / "run.metrics.jsonl")]
    assert [r.get("round") for r in recs] == [1, 2, None]
    assert all(r["schema"] == SCHEMA_VERSION for r in recs)


def test_metrics_logger_truncates_lazily(tmp_path):
    """An evaluate/export-style logger (constructed, never logged) must
    not wipe the fit log summarize reads; a fresh run that DOES log
    still gets its own file."""
    log = MetricsLogger(str(tmp_path), "run", echo=False)
    log.log({"round": 1})
    log.close()
    # evaluate-style: construct + close without logging → file intact
    MetricsLogger(str(tmp_path), "run", echo=False).close()
    recs = [json.loads(l) for l in open(tmp_path / "run.metrics.jsonl")]
    assert [r["round"] for r in recs] == [1]
    # a fresh run that logs truncates (one file per fresh run)
    log = MetricsLogger(str(tmp_path), "run", echo=False)
    log.log({"round": 7})
    log.close()
    recs = [json.loads(l) for l in open(tmp_path / "run.metrics.jsonl")]
    assert [r["round"] for r in recs] == [7]


def test_metrics_logger_rejects_freeform_records(tmp_path):
    log = MetricsLogger(str(tmp_path), "run", echo=False)
    with pytest.raises(ValueError, match="'event' or 'round'"):
        log.log({"loss": 1.0})
    log.close()


# ---------------------------------------------------------------------------
# e2e: fit → JSONL/trace → summarize


def _tiny_cfg(tmp, engine="sharded", **overrides):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.apply_overrides({
        "server.num_rounds": 3, "server.eval_every": 3,
        "server.cohort_size": 2,
        "data.synthetic_train_size": 256, "data.synthetic_test_size": 64,
        "data.max_examples_per_client": 64, "client.batch_size": 16,
        "run.out_dir": str(tmp), "run.metrics_flush_every": 2,
        "run.engine": engine,
        **overrides,
    })
    return cfg.validate()


def _fit(cfg):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    path = os.path.join(cfg.run.out_dir, f"{cfg.name}.metrics.jsonl")
    return exp, state, [json.loads(l) for l in open(path)], path


def test_fit_emits_spans_counters_trace_and_summarizes(tmp_path, capsys):
    cfg = _tiny_cfg(tmp_path, "sharded", **{"run.obs.trace": True})
    exp, state, recs, path = _fit(cfg)
    # schema contract: every record carries schema + event-or-round
    assert recs, "no records logged"
    for r in recs:
        assert r["schema"] == SCHEMA_VERSION
        assert "event" in r or "round" in r, r
    # span records cover the lifecycle phases
    phases = {}
    for r in recs:
        if r.get("event") == "spans":
            for k, v in r["phases"].items():
                phases[k] = phases.get(k, 0) + v["count"]
    for name in ("round", "round.host_inputs", "round.placement",
                 "round.dispatch", "round.fetch", "round.eval",
                 "round.checkpoint"):
        assert phases.get(name), f"missing span phase {name}: {phases}"
    assert phases["round"] == cfg.server.num_rounds
    # per-round comm counters ride the round records
    rounds = [r for r in recs if "train_loss" in r]
    assert len(rounds) == cfg.server.num_rounds
    for r in rounds:
        assert r["upload_bytes"] > 0 and r["download_bytes_raw"] > 0
    # trace.json is a valid Chrome trace with round events
    doc = json.load(open(os.path.join(tmp_path, cfg.name, "trace.json")))
    names = {e.get("name") for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "round" in names and "round.dispatch" in names
    assert any(r.get("event") == "trace" for r in recs)
    # summarize: module-level aggregation and the CLI table
    summary = summarize_records(recs)
    assert summary["rounds"] == cfg.server.num_rounds
    assert summary["comm"]["upload_bytes"] == sum(r["upload_bytes"] for r in rounds)
    table = format_summary(summary, path)
    assert "round.dispatch" in table and "comm:" in table
    assert cli.main(["summarize", path]) == 0
    out = capsys.readouterr().out
    assert "round.dispatch" in out and "phase" in out
    # and by run name under --out-dir
    assert cli.main(["summarize", cfg.name, "--out-dir", str(tmp_path)]) == 0


def test_comm_counter_parity_sharded_vs_sequential(tmp_path):
    """The analytic wire model is engine-independent BY CONSTRUCTION —
    pin it: the same config under both engines logs identical per-round
    byte counters (dropout changes realized participation; same seed ⇒
    same realization)."""
    outs = {}
    for engine in ("sharded", "sequential"):
        sub = tmp_path / engine
        cfg = _tiny_cfg(sub, engine, **{
            "server.eval_every": 0,
            "server.dropout_rate": 0.4,
            "server.compression": "qsgd",
        })
        _, _, recs, _ = _fit(cfg)
        outs[engine] = [
            {k: r.get(k, 0) for k in
             ("round", "upload_bytes", "upload_bytes_raw",
              "download_bytes", "download_bytes_raw", "dropped_clients")}
            for r in recs if "train_loss" in r
        ]
    assert outs["sharded"] == outs["sequential"]
    # compression makes wire < raw
    assert all(r["upload_bytes"] < r["upload_bytes_raw"]
               for r in outs["sharded"])


def test_failure_counters_recorded(tmp_path):
    cfg = _tiny_cfg(tmp_path, "sequential", **{
        "server.eval_every": 0, "server.dropout_rate": 0.9,
        "data.num_clients": 4, "server.cohort_size": 4,
    })
    _, _, recs, _ = _fit(cfg)
    rounds = [r for r in recs if "train_loss" in r]
    assert sum(r.get("dropped_clients", 0) for r in rounds) > 0


def test_nan_triggers_health_event_and_abort(tmp_path):
    cfg = _tiny_cfg(tmp_path, "sequential", **{
        "server.eval_every": 0, "client.lr": 1e38,
        "run.obs.on_unhealthy": "abort", "run.metrics_flush_every": 1,
    })
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    with pytest.raises(HealthAbortError, match="non_finite_loss"):
        exp.fit()
    recs = [json.loads(l) for l in
            open(os.path.join(tmp_path, f"{cfg.name}.metrics.jsonl"))]
    health = [r for r in recs if r.get("event") == "health"]
    assert health and health[0]["kind"] == "non_finite_loss"


def test_nan_checkpoint_abort_saves_postmortem(tmp_path):
    cfg = _tiny_cfg(tmp_path, "sequential", **{
        "server.eval_every": 0, "client.lr": 1e38,
        "run.obs.on_unhealthy": "checkpoint_abort",
        "run.metrics_flush_every": 1,
    })
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    with pytest.raises(HealthAbortError):
        exp.fit()
    ckpt = os.path.join(tmp_path, cfg.name, "ckpt")
    steps = [d for d in os.listdir(ckpt) if d.isdigit()]
    assert steps, f"no post-mortem checkpoint in {ckpt}"


def test_health_abort_is_not_retried(tmp_path):
    """max_retries must NOT eat a health abort — a NaN run restored from
    its own checkpoint re-NaNs; the verdict has to surface."""
    cfg = _tiny_cfg(tmp_path, "sequential", **{
        "server.eval_every": 0, "client.lr": 1e38,
        "run.obs.on_unhealthy": "abort", "run.metrics_flush_every": 1,
        "run.max_retries": 3,
    })
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    with pytest.raises(HealthAbortError):
        exp.fit()
    recs = [json.loads(l) for l in
            open(os.path.join(tmp_path, f"{cfg.name}.metrics.jsonl"))]
    assert not any(r.get("event") == "retry" for r in recs)


def test_divergence_detection_warn_keeps_training(tmp_path):
    """A diverging (but finite) loss with the default on_unhealthy=warn
    logs health events and completes the run."""
    cfg = _tiny_cfg(tmp_path, "sequential", **{
        "server.eval_every": 0, "client.lr": 1e25,  # explodes, stays finite
        "run.obs.divergence_factor": 1.5, "run.metrics_flush_every": 1,
        "server.num_rounds": 4,
    })
    _, state, recs, _ = _fit(cfg)
    assert int(state["round"]) == 4  # warn ⇒ the run completed
    kinds = {r["kind"] for r in recs if r.get("event") == "health"}
    assert "divergence" in kinds


def test_profile_event_logged_and_trace_closed(tmp_path):
    cfg = _tiny_cfg(tmp_path, "sequential", **{
        "server.eval_every": 0, "run.profile_round": 1,
    })
    _, _, recs, _ = _fit(cfg)
    prof = [r for r in recs if r.get("event") == "profile"]
    assert prof and prof[0]["round"] == 2 and os.path.isdir(prof[0]["dir"])
    import jax

    # the profiler session was stopped (a second start would raise if
    # the wrap leaked one open)
    jax.profiler.start_trace(str(tmp_path / "p2"))
    jax.profiler.stop_trace()


def _host_plane_spans(trace_dir, prefix="round."):
    """[(name, start, end, line index, stats)] of the ``/host:CPU``
    plane of the newest trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, i, dict(e.stats))
        for i, line in enumerate(plane.lines) for e in line.events
        if e.name.startswith(prefix)
    ]


def test_profiler_session_holds_the_round_spans_on_the_host_plane(tmp_path):
    """One clock: under a ``jax.profiler`` session the program's spans
    are events of ``/host:CPU``, nested as the program nests them, each
    with the first round of its dispatch as argument ``round``."""
    import jax

    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cfg = _tiny_cfg(tmp_path, "sharded", **{
        "run.fuse_rounds": 2, "server.num_rounds": 8,
        "server.eval_every": 0, "run.out_dir": "",
    })
    exp = Experiment(cfg, echo=False)
    try:
        state = exp._place_state(exp.init_state())
        state = exp.run_round(state, 0)  # compiles, starts the worker
        state.pop("_metrics")
        jax.profiler.start_trace(str(tmp_path / "prof"))
        try:
            for r in (2, 4):
                state = exp.run_round(state, r)
                jax.block_until_ready(state.pop("_metrics"))
        finally:
            jax.profiler.stop_trace()
    finally:
        exp._stop_prefetch()
    spans = _host_plane_spans(str(tmp_path / "prof"))
    runs = [s for s in spans if s[0] == "round.run"]
    assert [s[4]["round"] for s in runs] == [3, 5]
    for _, lo, hi, line, stats in runs:
        inside = {s[0]: s for s in spans
                  if s[3] == line and lo <= s[1] and s[2] <= hi and s[0] != "round.run"}
        for name in ("round.host_inputs", "round.placement",
                     "round.device_wait", "round.dispatch"):
            assert name in inside, (name, sorted(inside))
            assert inside[name][4]["round"] == stats["round"]
        assert inside["round.device_wait"][4]["what"] == "rng_keys"
        assert inside["round.dispatch"][4]["fuse"] == 2
        # waiting is not placement: the two never overlap
        wait = inside["round.device_wait"]
        for s in spans:
            if s[0] == "round.placement" and s[3] == line:
                assert s[2] <= wait[1] or wait[2] <= s[1]
    # the worker's spans sit on a line of their own and name the
    # dispatch their entry is for
    worker = [s for s in spans if s[0] == "round.prefetch"]
    assert worker and {s[3] for s in worker}.isdisjoint({s[3] for s in runs})
    assert {s[4]["round"] for s in worker} <= {5, 7, 9}
    sub = [s for s in spans if s[0] == "round.host_inputs.sampler"
           and s[3] == worker[0][3]]
    assert sub and all("round" in s[4] for s in sub)


def test_summary_resolution_errors(tmp_path):
    with pytest.raises(FileNotFoundError):
        resolve_metrics_path("no_such_run", out_dir=str(tmp_path))
    assert cli.main(["summarize", "no_such_run",
                     "--out-dir", str(tmp_path)]) == 2


def test_summary_tolerates_torn_tail_line(tmp_path):
    p = tmp_path / "x.metrics.jsonl"
    p.write_text('{"round": 1, "train_loss": 1.0, "schema": 1}\n{"round": 2, "tr')
    recs = load_records(str(p))
    assert len(recs) == 1
    assert summarize_records(recs)["rounds"] == 1


# ---------------------------------------------------------------------------
# run_summary + summarize hardening + trace caps (r8 satellites)


def test_run_summary_record_totals(tmp_path):
    cfg = _tiny_cfg(tmp_path, "sharded")
    _, _, recs, _ = _fit(cfg)
    rs = [r for r in recs if r.get("event") == "run_summary"]
    assert len(rs) == 1, "exactly one end-of-fit run_summary"
    rs = rs[0]
    rounds = [r for r in recs if "train_loss" in r]
    assert rs["rounds"] == cfg.server.num_rounds
    for k in ("upload_bytes", "upload_bytes_raw", "download_bytes",
              "download_bytes_raw"):
        assert rs[k] == sum(r.get(k, 0) for r in rounds), k
    assert rs["wall_time_sec"] > 0
    # the first dispatch compiled at least the round program
    assert rs["compiles"] >= 1 and rs["compile_ms"] > 0


def test_run_summary_lands_on_abort(tmp_path):
    cfg = _tiny_cfg(tmp_path, "sequential", **{
        "server.eval_every": 0, "client.lr": 1e38,
        "run.obs.on_unhealthy": "abort", "run.metrics_flush_every": 1,
    })
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    with pytest.raises(HealthAbortError):
        exp.fit()
    recs = [json.loads(l) for l in
            open(os.path.join(tmp_path, f"{cfg.name}.metrics.jsonl"))]
    rs = [r for r in recs if r.get("event") == "run_summary"]
    assert rs and rs[-1]["rounds"] >= 1  # partial totals still land


def test_summarize_empty_log_clean_error(tmp_path, capsys):
    p = tmp_path / "empty.metrics.jsonl"
    p.write_text("")
    assert cli.main(["summarize", str(p)]) == 2
    err = capsys.readouterr().err
    assert "no metrics records" in err and "Traceback" not in err
    # an empty run DIRECTORY errors cleanly too (no *.metrics.jsonl)
    d = tmp_path / "emptydir"
    d.mkdir()
    assert cli.main(["summarize", str(d)]) == 2
    # and --json on a real run emits one parseable object
    cfg = _tiny_cfg(tmp_path, "sequential", **{"server.eval_every": 0})
    _, _, _, path = _fit(cfg)
    assert cli.main(["summarize", path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["rounds"] == cfg.server.num_rounds and doc["path"] == path


def test_trace_event_cap_truncates_and_warns_once(caplog):
    import logging

    clock = iter(float(t) for t in range(1000))
    tracer = Tracer(enabled=True, trace=True, clock=lambda: next(clock),
                    max_events=3)
    with caplog.at_level(logging.WARNING):
        for _ in range(6):
            with tracer.span("s"):
                pass
    assert len(tracer._events) == 3  # capped
    warns = [r for r in caplog.records if "trace event cap" in r.message]
    assert len(warns) == 1  # warn-once
    # span AGGREGATES keep counting past the cap
    assert tracer.drain()["s"]["count"] == 6


def test_trace_export_size_warning_once(tmp_path, caplog, monkeypatch):
    import logging

    from colearn_federated_learning_tpu.obs import spans as spans_mod

    monkeypatch.setattr(spans_mod, "TRACE_SIZE_WARN_BYTES", 10)
    clock = iter(float(t) for t in range(1000))
    tracer = Tracer(enabled=True, trace=True, clock=lambda: next(clock))
    with tracer.span("s"):
        pass
    with caplog.at_level(logging.WARNING):
        tracer.export(str(tmp_path / "t1.json"))
        tracer.export(str(tmp_path / "t2.json"))
    warns = [r for r in caplog.records if "exported trace" in r.message]
    assert len(warns) == 1  # warn-once per tracer


def test_summarize_surfaces_precision_line(tmp_path):
    """r7: every run logs a `precision` record at fit start; summarize
    renders it as the compute_dtype column next to the throughput."""
    from colearn_federated_learning_tpu.obs.summary import (
        format_summary,
        summarize_records,
    )

    recs = [
        {"schema": 1, "event": "precision", "param_dtype": "float32",
         "compute_dtype": "bfloat16", "local_param_dtype": "bfloat16",
         "fused_apply": True, "double_buffer": True},
        {"schema": 1, "round": 1, "train_loss": 1.0, "examples": 8.0},
    ]
    summary = summarize_records(recs)
    assert summary["precision"]["compute_dtype"] == "bfloat16"
    text = format_summary(summary)
    assert "precision: compute=bfloat16  params=float32" in text
    assert "fused_apply" in text and "double_buffer" in text


# ---------------------------------------------------------------------------
# r8 satellites: summarize's run_summary fast path + multi-process
# trace lanes / fragment merge + process_index tagging
# ---------------------------------------------------------------------------


def test_summarize_consumes_run_summary_totals():
    """When the log carries the end-of-fit run_summary record, the
    totals come from IT (the authoritative every-exit-path record) —
    not from re-summing per-round counters — and the table says which
    path produced them."""
    recs = [
        {"schema": 1, "round": 1, "train_loss": 1.0, "examples": 8.0,
         "upload_bytes": 100, "upload_bytes_raw": 100,
         "download_bytes": 50, "download_bytes_raw": 50},
        # a torn/partial final window: the per-round records only saw
        # round 1, but the run_summary knows the real totals
        {"schema": 1, "event": "run_summary", "rounds": 3,
         "wall_time_sec": 2.5, "compiles": 7, "compile_ms": 120.0,
         "upload_bytes": 300, "upload_bytes_raw": 300,
         "download_bytes": 150, "download_bytes_raw": 150},
    ]
    summary = summarize_records(recs)
    assert summary["source"] == "run_summary"
    assert summary["rounds"] == 3
    assert summary["comm"]["upload_bytes"] == 300  # NOT the re-sum (100)
    assert summary["wall_time_sec"] == 2.5 and summary["compiles"] == 7
    text = format_summary(summary)
    assert "totals: run_summary record" in text


def test_summarize_falls_back_for_pre_run_summary_logs():
    recs = [
        {"schema": 1, "round": 1, "train_loss": 1.0, "examples": 8.0,
         "upload_bytes": 100, "upload_bytes_raw": 100,
         "download_bytes": 50, "download_bytes_raw": 50},
        {"schema": 1, "round": 2, "train_loss": 0.9, "examples": 8.0,
         "upload_bytes": 100, "upload_bytes_raw": 100,
         "download_bytes": 50, "download_bytes_raw": 50},
    ]
    summary = summarize_records(recs)
    assert summary["source"] == "reaggregated"
    assert summary["comm"]["upload_bytes"] == 200  # the per-round re-sum
    assert "re-aggregated" in format_summary(summary)


def test_tracer_pid_is_the_process_index():
    clock = iter(float(t) for t in range(100))
    tr = Tracer(trace=True, clock=lambda: next(clock), process_index=3)
    with tr.span("round"):
        pass
    assert all(e["pid"] == 3 for e in tr._events)


def test_trace_export_merges_per_host_fragments(tmp_path):
    """Multi-process runs: non-primary hosts export trace.p<i>.json
    fragments and the primary merges them into one timeline — one lane
    group (pid) per host, instead of silently reflecting process 0."""
    clock1 = iter(float(t) for t in range(100))
    worker = Tracer(trace=True, clock=lambda: next(clock1),
                    process_index=1)
    with worker.span("round.dispatch"):
        pass
    frag = str(tmp_path / "trace.p1.json")
    assert worker.export(frag) == frag
    json.load(open(frag))  # the fragment is loadable on its own

    clock0 = iter(float(t) for t in range(100))
    primary = Tracer(trace=True, clock=lambda: next(clock0),
                     process_index=0)
    with primary.span("round"):
        pass
    merged = str(tmp_path / "trace.json")
    primary.export(merged, fragments=[frag,
                                      str(tmp_path / "missing.json")])
    doc = json.load(open(merged))
    events = doc["traceEvents"]
    pids = {e["pid"] for e in events if e.get("ph") == "X"}
    assert pids == {0, 1}
    # one process_name metadata lane per host, labelled by host index
    lanes = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert set(lanes) == {0, 1} and "host 1" in lanes[1]


def test_spans_records_carry_process_index(tmp_path):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.apply_overrides({
        "server.num_rounds": 2, "server.eval_every": 0,
        "server.checkpoint_every": 0,
        "data.num_clients": 4, "server.cohort_size": 2,
        "data.synthetic_train_size": 64, "data.synthetic_test_size": 32,
        "data.max_examples_per_client": 16, "client.batch_size": 8,
        "run.out_dir": str(tmp_path),
    })
    cfg.validate()
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    Experiment(cfg, echo=False).fit()
    path = os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl")
    recs = load_records(path)
    tagged = [r for r in recs if r.get("event") == "spans"]
    assert tagged, "expected spans records"
    assert all(r.get("process_index") == 0 for r in tagged)


# two rounds of `mnist_fedavg_2` as commit 838c17d logged them (cohort 2,
# batch 8), with the two record types that commit was the last to write
_LOG_OF_838C17D = (
    '{"event": "precision", "param_dtype": "float32", "compute_dtype": '
    '"float32", "local_param_dtype": "float32", "fused_apply": false, '
    '"double_buffer": true, "control_plane": "host", '
    '"time": 1791202926.6288173, "schema": 1}',
    '{"round": 1, "train_loss": 2.46985125541687, "examples": 32.0, '
    '"upload_bytes": 493648, "download_bytes": 493648, '
    '"host_input_bytes": 152, "time": 1791202927.8084252, "schema": 1}',
    '{"round": 2, "train_loss": 2.4448280334472656, "examples": 32.0, '
    '"upload_bytes": 493648, "download_bytes": 493648, '
    '"host_input_bytes": 152, "rounds_per_sec": 1.7245, '
    '"client_updates_per_sec_per_chip": 3.4491, '
    '"time": 1791202927.8085961, "schema": 1}',
    '{"event": "spans", "round": 2, "phases": {"round": {"count": 2, '
    '"total_ms": 1122.298, "max_ms": 1120.954, "self_ms": 0.109}}, '
    '"process_index": 0, "time": 1791202927.8088, "schema": 1}',
    '{"event": "run_summary", "rounds": 2, "wall_time_sec": 13.079, '
    '"compiles": 51, "compile_ms": 4410.381, "upload_bytes": 987296, '
    '"download_bytes": 987296, "time": 1791202927.886285, "schema": 1}',
)
_RECORDS_OF_838C17D = {
    "phase_cost": (
        '{"event": "phase_cost", "round": 1, "process_index": 0, "phases": '
        '{"local_train": {"flops": 11847552, "bytes": 3949336}, '
        '"aggregation": {"flops": 246824, "bytes": 987296}, "server_apply": '
        '{"flops": 246824, "bytes": 1480944}}, "time": 1791202927.808547, '
        '"schema": 1}'),
    "phase_cost_model": (
        '{"event": "phase_cost_model", "step_flops": 2961888, "flop_source": '
        '"analytic", "n_coords": 61706, "n_coords_full": 61706, '
        '"param_bytes": 246824, "compute_bytes": 4, "mfu_basis": "f32_peak", '
        '"peak_flops": 98500000000000.0, "peak_hbm_bytes_per_sec": '
        '819000000000.0, "device_kind": "cpu", "n_chips": 1, '
        '"process_index": 0, "cohort_layout": "spatial", '
        '"clients_per_lane": 2, "gemm_rows": 8, "lora_all_steps": false, '
        '"mxu_tile_pad_fraction": 0.9375, "windowed_conv_share": 0.0389, '
        '"shared_weight_phase": false, "time": 1791202926.6485455, '
        '"schema": 1}'),
}


@pytest.mark.parametrize("record", sorted(_RECORDS_OF_838C17D))
@pytest.mark.parametrize("reader,rounds_shown", [
    (["summarize"], "rounds: 2"), (["watch", "--once"], "round 2"),
], ids=["summarize", "watch"])
def test_logs_of_earlier_versions_still_read(tmp_path, capsys, reader,
                                             rounds_shown, record):
    """A record type no reader knows any more is passed over: an
    append-only log outlives the version that wrote it."""
    lines = list(_LOG_OF_838C17D)
    lines.insert(2, _RECORDS_OF_838C17D[record])
    path = tmp_path / "old.metrics.jsonl"
    path.write_text("\n".join(lines) + "\n")
    assert cli.main([*reader, str(path)]) == 0
    out = capsys.readouterr()
    assert rounds_shown in out.out, out.out
    # its phases are FLOP counts, not spans: no row of the timing table
    assert "local_train" not in out.out and not out.err


# ---------------------------------------------------------------------------
# the start-up record: what the tracer keeps past drain()


def test_kept_spans_survive_drain_and_the_aggregate_is_still_reset():
    clock = iter(float(t) for t in range(100))
    tracer = Tracer(clock=lambda: next(clock))
    with tracer.span("setup.experiment"):            # [1, 6]
        with tracer.span("setup.data.load", dataset="toy"):   # [2, 3]
            pass
        with tracer.span("round.host_inputs"):       # [4, 5]: hot, not kept
            pass
    first = tracer.drain()
    assert set(first) == {"setup.experiment", "setup.data.load",
                          "round.host_inputs"}
    assert tracer.drain() == {}  # the window's aggregate was reset
    with tracer.span("init.frozen_base"):            # [7, 8]
        pass
    record = tracer.startup_record()
    assert [e["name"] for e in record] == [
        "setup.data.load", "setup.experiment", "init.frozen_base"]
    load, exp, base = record
    assert (load["start"], load["end"], load["self_s"]) == (2.0, 3.0, 1.0)
    assert load["args"] == {"dataset": "toy"}
    assert load["parent"] == "setup.experiment" and load["lane"] == 0
    # the experiment's self time excludes both children, kept or not
    assert (exp["start"], exp["end"], exp["self_s"]) == (1.0, 6.0, 3.0)
    assert exp["parent"] is None and base["parent"] is None
    assert set(tracer.drain()) == {"init.frozen_base"}
    assert len(tracer.startup_record()) == 3  # and drain() left it alone
    # the accessor hands out a copy
    tracer.startup_record().clear()
    assert len(tracer.startup_record()) == 3


def test_note_past_enters_aggregate_and_record_as_a_top_level_span():
    tracer = Tracer()
    tracer.note_past("setup.import", 10.0, 12.5)
    (entry,) = tracer.startup_record()
    assert (entry["name"], entry["start"], entry["end"], entry["self_s"],
            entry["parent"], entry["lane"]) == (
                "setup.import", 10.0, 12.5, 2.5, None, 0)
    assert tracer.drain()["setup.import"]["total_ms"] == pytest.approx(2500.0)
    off = Tracer(enabled=False)
    off.note_past("setup.import", 10.0, 12.5)
    assert off.startup_record() == [] and off.drain() == {}


def test_compiles_are_counted_under_the_spans_they_ran_in():
    """jax's backend_compile and compilation-cache events reach the
    innermost open span of the compiling thread and add up outwards;
    each drain that saw compiles leaves its ``compile`` pseudo-phase in
    the record."""
    tracer = Tracer()
    with tracer.span("setup.init_state"):
        with tracer.span("setup.init.model") as model:
            tracer._note_compile(0.25)
            tracer._note_cache(3)  # written anew
            tracer._note_compile(0.5)
            tracer._note_cache(2)  # loaded
            assert model.cache == "miss"
        with tracer.span("setup.init.server_opt") as opt:
            assert opt.cache == "off"
            tracer._note_compile(0.125)
            tracer._note_cache(2)
            assert opt.cache == "hit"
    tracer._note_compile(1.0)  # under no span: the drain still counts it
    phases = tracer.drain()
    assert phases["compile"]["count"] == 4
    assert phases["compile"]["cache_hits"] == 2
    assert phases["compile"]["cache_misses"] == 1
    by_name = {e["name"]: e for e in tracer.startup_record()}
    assert (by_name["setup.init.model"]["compiles"],
            by_name["setup.init.model"]["compile_s"],
            by_name["setup.init.model"]["cache_hits"],
            by_name["setup.init.model"]["cache_misses"]) == (2, 0.75, 1, 1)
    assert by_name["setup.init_state"]["compiles"] == 3
    assert by_name["setup.init_state"]["cache_hits"] == 2
    kept = by_name["compile"]
    assert (kept["compiles"], kept["cache_hits"], kept["cache_misses"],
            kept["compile_s"]) == (4, 2, 1, 1.875)
    assert kept["end"] >= by_name["setup.init_state"]["end"]
    tracer.drain()  # no compile since: no second entry
    assert [e["name"] for e in tracer.startup_record()].count("compile") == 1


def test_a_compile_after_set_up_names_its_program_and_its_round():
    """The registry's two spans, for a program whose shape changes in a
    later round: both compiles are in the record under obs.executables,
    with the program's name and the dispatch's round."""
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.obs import executables as exec_mod

    tracer = Tracer()
    reg = exec_mod.ExecutableRegistry(tracer=tracer)
    double = exec_mod.instrument("round.toy", jax.jit(lambda x: x * 2))
    exec_mod.install(reg)
    try:
        with tracer.span("round.run", round=1):
            with tracer.span("round.dispatch"):
                double(jnp.ones(4))
        tracer.drain()  # set-up is over
        with tracer.span("round.run", round=7):
            with tracer.span("round.dispatch"):
                double(jnp.ones(4))  # cached: no compile
                double(jnp.ones(8))  # a new shape
    finally:
        exec_mod.uninstall()
    record = tracer.startup_record()
    names = [e["name"] for e in record if e["name"] != "compile"]
    assert names == ["compile.lower", "compile.backend", "obs.executables"] * 2
    for e in record:
        if e["name"].startswith("compile."):
            assert e["parent"] == "obs.executables"
            assert e["args"]["program"] == "round.toy"
    assert [e["args"]["round"] for e in record
            if e["name"] == "compile.lower"] == [1, 7]
    backends = [e for e in record if e["name"] == "compile.backend"]
    assert all(e["args"]["cache"] in ("hit", "miss", "off") for e in backends)
    assert all(e["compiles"] == 1 for e in backends)
    # what is left as the registry's self time is its harvest
    for e in record:
        if e["name"] == "obs.executables":
            assert 0 <= e["self_s"] < e["end"] - e["start"]


def _driven(cfg):
    """An Experiment driven as the benchmark drives it: init, place, two
    dispatches, with the registry installed."""
    from colearn_federated_learning_tpu.obs import executables as exec_mod
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    exec_mod.install(exp._exec_reg)
    try:
        state = exp._place_state(exp.init_state(cfg.run.seed))
        for r in range(2):
            state = exp.run_round(state, r)
            state.pop("_metrics")
    finally:
        exp._stop_prefetch()
        exec_mod.uninstall()
    return exp


def test_experiment_set_up_is_in_the_record_and_a_second_one_starts_empty(
        tmp_path):
    first = _driven(_tiny_cfg(tmp_path / "a"))
    record = first.tracer.startup_record()
    names = [e["name"] for e in record]
    for name in ("setup.experiment", "setup.model", "setup.data.load",
                 "setup.data.partition", "setup.engine", "setup.data.place",
                 "setup.eval_batches", "setup.init_state", "setup.init.model",
                 "setup.init.server_opt", "setup.place_state",
                 "obs.executables", "compile.lower", "compile.backend"):
        assert name in names, name
    by_name = {e["name"]: e for e in record}
    exp_entry = by_name["setup.experiment"]
    children = [e for e in record if e["parent"] == "setup.experiment"]
    assert {e["name"] for e in children} >= {"setup.model", "setup.data.load",
                                             "setup.engine"}
    held = sum(e["end"] - e["start"] for e in children)
    assert exp_entry["self_s"] == pytest.approx(
        exp_entry["end"] - exp_entry["start"] - held, abs=1e-9)
    assert by_name["setup.data.load"]["args"] == {
        "dataset": "mnist", "examples": 256 + 64}
    assert by_name["setup.data.partition"]["args"]["clients"] == 2
    for name in ("setup.experiment", "setup.init_state", "setup.place_state"):
        assert by_name[name]["parent"] is None and by_name[name]["lane"] == 0
    lower, backend = by_name["compile.lower"], by_name["compile.backend"]
    assert lower["parent"] == backend["parent"] == "obs.executables"
    assert lower["args"] == {"round": 1, "program": "round.sync"}
    assert backend["args"]["program"] == "round.sync"
    assert backend["args"]["round"] == 1 and "cache" in backend["args"]
    # the first flush of a fit would carry the same phases
    assert "setup.experiment" in first.tracer.drain()
    # a second Experiment of the process has a record of its own, and the
    # module's import is only the first one's
    second = _driven(_tiny_cfg(tmp_path / "b"))
    again = [e["name"] for e in second.tracer.startup_record()]
    assert again.count("setup.experiment") == 1
    assert again.count("compile.lower") == names.count("compile.lower")
    assert "setup.import" not in again
    assert len(first.tracer.startup_record()) == len(record) + 1  # + compile


def test_spans_off_keeps_no_record(tmp_path):
    from colearn_federated_learning_tpu.obs import spans as spans_mod

    exp = _driven(_tiny_cfg(tmp_path, **{"run.obs.spans": False}))
    assert exp.tracer.span("setup.experiment") is _NULL_SPAN
    assert exp.tracer.startup_record() == [] and exp.tracer.drain() == {}
    assert exp.tracer not in spans_mod.live_tracers()
    _NULL_SPAN.note(cache="hit")  # what the registry and the loader call
    assert _NULL_SPAN.cache is None
