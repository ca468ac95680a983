"""Churn & async production traffic (run.churn + the FedBuff promotion):
hazard-model purity, churn-off bitwise identity, engine-invariant and
resume-replayable schedules, the bounded-staleness admission gate (both
ways), backpressure, the fault-injection e2e (crashing compromised
clients vs krum/reputation), the promoted store-backed FedBuff headline,
the watch/population panels, and the capability-matrix flips."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from colearn_federated_learning_tpu.config import get_named_config
from colearn_federated_learning_tpu.server.churn import (
    ChurnModel,
    build_churn_model,
)
from colearn_federated_learning_tpu.server.round_driver import Experiment

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Cfg:
    def __init__(self, **kw):
        self.diurnal_period = kw.get("diurnal_period", 8)
        self.diurnal_amplitude = kw.get("diurnal_amplitude", 0.5)
        self.base_availability = kw.get("base_availability", 0.7)
        self.min_availability = kw.get("min_availability", 0.05)
        self.dropout_hazard = kw.get("dropout_hazard", 0.1)
        self.crash_rate = kw.get("crash_rate", 0.2)


# ---------------------------------------------------------------------------
# unit: the hazard model is pure, bounded, and rate-faithful
# ---------------------------------------------------------------------------


def test_churn_model_is_pure_and_bounded():
    m = ChurnModel(_Cfg(), seed=7)
    ids = np.arange(64)
    for r in (0, 3, 17):
        p = m.availability_prob(r, ids)
        assert (p >= 0.05).all() and (p <= 1.0).all()
        np.testing.assert_array_equal(m.available(r, ids), m.available(r, ids))
        np.testing.assert_array_equal(m.dropped(r, ids), m.dropped(r, ids))
        c1, f1 = m.crashed(r, ids)
        c2, f2 = m.crashed(r, ids)
        np.testing.assert_array_equal(c1, c2)
        np.testing.assert_array_equal(f1, f2)
        assert ((f1 > 0.0) & (f1 <= 1.0)).all()
    # the diurnal wave actually moves a client's probability over a day
    probs = np.array([
        float(m.availability_prob(r, np.array([3]))[0])
        for r in range(m.period)
    ])
    assert probs.max() - probs.min() > 0.5  # amplitude 0.5 ⇒ ~1.0 swing
    # per-client phases differ (timezones): round-0 probabilities spread
    p0 = m.availability_prob(0, ids)
    assert p0.std() > 0.1
    # a different seed is a different schedule
    m2 = ChurnModel(_Cfg(), seed=8)
    assert not np.array_equal(m.available(0, ids), m2.available(0, ids))


def test_churn_model_rates_match_config():
    m = ChurnModel(_Cfg(dropout_hazard=0.15, crash_rate=0.25,
                        diurnal_amplitude=0.0, base_availability=0.6),
                   seed=0)
    ids = np.arange(20_000)
    assert abs(m.available(5, ids).mean() - 0.6) < 0.02
    assert abs(m.dropped(5, ids).mean() - 0.15) < 0.02
    crashed, frac = m.crashed(5, ids)
    assert abs(crashed.mean() - 0.25) < 0.02
    # crash fractions are ~uniform over (0, 1]
    assert abs(frac.mean() - 0.5) < 0.02


def test_churn_off_constructs_nothing():
    cfg = get_named_config("mnist_fedavg_2")
    assert build_churn_model(cfg) is None
    cfg.run.churn.enabled = True
    assert isinstance(build_churn_model(cfg), ChurnModel)


# ---------------------------------------------------------------------------
# config pairing rejections
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overrides,match", [
    ({"algorithm": "gossip", "server.cohort_size": 8,
      "server.sampling": "uniform"}, "gossip"),
    ({"run.shape_buckets.enabled": True}, "shape_buckets"),
    ({"server.sampling": "poisson"}, "streaming"),
    ({"server.sampling": "weighted"}, "streaming"),
    ({"run.churn.diurnal_period": 0}, "diurnal_period"),
    ({"run.churn.dropout_hazard": 1.0}, "dropout_hazard"),
    ({"run.churn.base_availability": 0.0}, "base_availability"),
])
def test_churn_pairing_rejections(overrides, match):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.data.num_clients = 8
    cfg.server.cohort_size = 8
    cfg.run.churn.enabled = True
    for k, v in overrides.items():
        cfg.apply_overrides({k: v})
    with pytest.raises(ValueError, match=match):
        cfg.validate()


def test_fedbuff_backpressure_knob_validation():
    cfg = get_named_config("mnist_fedavg_2")
    cfg.algorithm = "fedbuff"
    cfg.data.num_clients = 8
    cfg.server.cohort_size = 4
    cfg.server.async_overload_policy = "nonsense"
    with pytest.raises(ValueError, match="async_overload_policy"):
        cfg.validate()
    cfg.server.async_overload_policy = "reject_newest"
    cfg.server.async_backlog_cap = -1
    with pytest.raises(ValueError, match="async_backlog_cap"):
        cfg.validate()


# ---------------------------------------------------------------------------
# driver: churn-off bitwise identity, engine invariance, resume replay
# ---------------------------------------------------------------------------


def _sync_cfg(tmp_path, name="churn_sync", rounds=4, **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.name = name
    cfg.data.num_clients = 8
    cfg.server.cohort_size = 4
    cfg.server.num_rounds = rounds
    cfg.server.eval_every = 0
    cfg.data.synthetic_train_size = 256
    cfg.data.synthetic_test_size = 64
    cfg.client.batch_size = 8
    cfg.data.max_examples_per_client = 32
    cfg.run.out_dir = str(tmp_path)
    cfg.run.metrics_flush_every = 1
    for k, v in over.items():
        cfg.apply_overrides({k: v})
    return cfg.validate()


_CHURN = {
    "run.churn.enabled": True,
    "run.churn.diurnal_period": 4,
    "run.churn.base_availability": 0.7,
    "run.churn.diurnal_amplitude": 0.4,
    "run.churn.dropout_hazard": 0.1,
    "run.churn.crash_rate": 0.25,
}


def test_churn_off_is_bitwise_identical_with_stray_knobs(tmp_path):
    """enabled=false must construct nothing: a run with every churn
    knob set (but disabled) is bitwise the plain run — params AND the
    sampler's rng stream."""
    plain = Experiment(_sync_cfg(tmp_path / "a"), echo=False)
    s_plain = plain.fit()
    stray = Experiment(_sync_cfg(
        tmp_path / "b",
        **{"run.churn.enabled": False,
           "run.churn.diurnal_period": 3,
           "run.churn.base_availability": 0.2,
           "run.churn.dropout_hazard": 0.4,
           "run.churn.crash_rate": 0.4},
    ), echo=False)
    s_stray = stray.fit()
    assert stray._churn is None and stray.sampler.availability_fn is None
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        s_plain["params"], s_stray["params"],
    )


def test_churn_schedule_is_engine_invariant(tmp_path):
    """sharded vs sequential under identical churn: the realized
    cohorts and failure draws are bitwise-equal (the schedule is host
    code, pure in (seed, round, id)); params agree at engine
    tolerance."""
    runs = {}
    for engine in ("sharded", "sequential"):
        cfg = _sync_cfg(tmp_path / engine, rounds=4,
                        **dict(_CHURN, **{"run.engine": engine}))
        exp = Experiment(cfg, echo=False)
        state = exp._place_state(exp.init_state())
        cohorts = []
        for r in range(4):
            cohorts.append(np.asarray(exp.sampler.sample(r)))
            state = exp.run_round(state, r)
            state.pop("_metrics")
        runs[engine] = (exp, state, cohorts)
    (e_sh, s_sh, c_sh), (e_sq, s_sq, c_sq) = runs["sharded"], runs["sequential"]
    for a, b in zip(c_sh, c_sq):
        np.testing.assert_array_equal(a, b)
    assert e_sh._fail_stats == e_sq._fail_stats
    assert any(
        k.startswith("churn") for st in e_sh._fail_stats.values() for k in st
    ), e_sh._fail_stats  # the draws actually fired at these rates
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        s_sh["params"], s_sq["params"],
    )


def test_churn_resume_replays_bitwise_through_checkpoint(tmp_path):
    """A churn-on run resumed from a mid-run checkpoint replays the
    straight run's schedule (and params) bitwise — nothing churn-
    related rides the checkpoint because every draw is a pure function
    of (seed, round, id)."""
    def run(path, rounds, resume=False):
        cfg = _sync_cfg(path, rounds=rounds, **_CHURN)
        cfg.server.checkpoint_every = 2
        cfg.run.resume = resume
        return Experiment(cfg, echo=False).fit()

    straight = run(tmp_path / "straight", 6)
    run(tmp_path / "resumed", 4)
    resumed = run(tmp_path / "resumed", 6, resume=True)
    assert int(resumed["round"]) == 6
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        straight["params"], resumed["params"],
    )


def test_churn_counts_flow_to_records_and_summary(tmp_path):
    cfg = _sync_cfg(tmp_path, rounds=6, **_CHURN)
    exp = Experiment(cfg, echo=False)
    exp.fit()
    records = [
        json.loads(line)
        for line in open(tmp_path / f"{cfg.name}.metrics.jsonl")
    ]
    churn_ev = [r for r in records if r.get("event") == "churn"]
    assert len(churn_ev) == 1
    assert churn_ev[0]["base_availability"] == 0.7
    rounds = [r for r in records if "train_loss" in r and "round" in r
              and "event" not in r]
    assert any(
        any(k.startswith("churn_") for k in r) for r in rounds
    ), rounds
    summary = [r for r in records if r.get("event") == "run_summary"][-1]
    assert sum(
        summary.get(k, 0) for k in
        ("churn_unavailable", "churn_dropped", "churn_crashed")
    ) > 0, summary


# ---------------------------------------------------------------------------
# fedbuff under churn: admission gate (both ways) + backpressure
# ---------------------------------------------------------------------------


def _fedbuff_churn_cfg(tmp_path, rounds=24, strict=False, **over):
    # deep-trough diurnal shape (base 0.8, amplitude 0.75, period 16):
    # most clients stay online (so offline completions are rarely
    # force-popped as fill), while a client in its trough goes dark
    # for ~6 consecutive rounds — longer than the 2S = 4 staleness
    # budget, exactly what exercises the admission gate (calibrated:
    # 5 clamps, max realized staleness 6 at this geometry)
    cfg = get_named_config("mnist_fedavg_2")
    cfg.name = "fb_churn"
    cfg.algorithm = "fedbuff"
    cfg.data.num_clients = 8
    cfg.server.cohort_size = 4
    cfg.server.async_max_staleness = 2
    cfg.server.num_rounds = rounds
    cfg.server.eval_every = 0
    cfg.run.out_dir = str(tmp_path)
    cfg.run.metrics_flush_every = 2
    cfg.data.synthetic_train_size = 256
    cfg.data.synthetic_test_size = 64
    cfg.run.strict_staleness = strict
    cfg.apply_overrides({
        "run.churn.enabled": True,
        "run.churn.diurnal_period": 16,
        "run.churn.base_availability": 0.8,
        "run.churn.diurnal_amplitude": 0.75,
    })
    for k, v in over.items():
        cfg.apply_overrides({k: v})
    return cfg.validate()


def test_staleness_clamp_graceful_path(tmp_path):
    """Harsh churn defers completions past the 2S ring bound: the
    graceful gate admits them clamped + down-weighted and counts them
    (warn-once + per-round + run_summary), instead of killing the
    run."""
    cfg = _fedbuff_churn_cfg(tmp_path)
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    assert int(state["round"]) == cfg.server.num_rounds
    records = [
        json.loads(line)
        for line in open(tmp_path / f"{cfg.name}.metrics.jsonl")
    ]
    summary = [r for r in records if r.get("event") == "run_summary"][-1]
    assert summary.get("staleness_clamped", 0) > 0, summary
    warns = [r for r in records if r.get("event") == "warning"
             and r.get("warning") == "staleness_clamped"]
    assert len(warns) == 1, warns  # warn-once
    rounds = [r for r in records if "max_staleness" in r]
    assert max(r["max_staleness"] for r in rounds) > 4  # bound 2S = 4
    # the absorbed-throughput readout the bench entry consumes
    assert summary["async_staleness_bound"] == 4
    assert summary["async_updates_absorbed"] > 0
    assert summary["async_updates_per_sec"] > 0


def test_strict_staleness_escape_hatch_preserves_the_raise(tmp_path):
    cfg = _fedbuff_churn_cfg(tmp_path, strict=True)
    exp = Experiment(cfg, echo=False)
    with pytest.raises(RuntimeError, match="staleness bound violated"):
        exp.fit()


def test_no_churn_no_clamp_and_bound_still_invariant(tmp_path):
    """Churn off ⇒ the scheduler's 2S bound is an invariant again: a
    full fit never clamps and records no backpressure."""
    cfg = _fedbuff_churn_cfg(tmp_path)
    cfg.run.churn.enabled = False
    cfg.validate()
    exp = Experiment(cfg, echo=False)
    exp.fit()
    assert exp._traffic_totals.get("staleness_clamped", 0) == 0
    assert not exp._staleness_warned


@pytest.mark.parametrize("policy", ["drop_oldest", "reject_newest"])
def test_backpressure_sheds_and_counts(tmp_path, policy):
    cfg = _fedbuff_churn_cfg(
        tmp_path / policy, rounds=16,
        **{"server.async_backlog_cap": 1,
           "server.async_max_staleness": 3,
           "server.async_overload_policy": policy},
    )
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    assert int(state["round"]) == 16
    key = ("backpressure_dropped" if policy == "drop_oldest"
           else "backpressure_rejected")
    assert exp._traffic_totals.get(key, 0) > 0, exp._traffic_totals
    other = ("backpressure_rejected" if policy == "drop_oldest"
             else "backpressure_dropped")
    assert exp._traffic_totals.get(other, 0) == 0
    # queue bookkeeping stayed consistent under shedding
    assert len(np.unique(state["queue_seq"])) == len(state["queue_seq"])


# ---------------------------------------------------------------------------
# fault injection e2e: crashing compromised clients vs the defenses
# ---------------------------------------------------------------------------


def _fit_acc(tmp_path, name, **over):
    cfg = _sync_cfg(
        tmp_path, name=name, rounds=15,
        **{"data.num_clients": 16, "server.cohort_size": 8,
           "data.synthetic_train_size": 512, **over},
    )
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    return exp.evaluate(state["params"])["eval_acc"]


# sign_flip at f = 2 of 16, scale 10: the federation is 2× the cohort
# so the availability-gated sampler keeps per-round participation near
# 8 — krum's Blanchard bound 2f+2 < m stays satisfiable under churn
# (with cohort == num_clients a diurnal trough drives m below the
# bound and krum legitimately collapses — measured during calibration)
_FAULT_ATTACK = {"attack.kind": "sign_flip", "attack.fraction": 0.125}
# milder in-round churn for the fault matrix: hazard drops cost
# participation (krum's m); crashes cost only work — the scenario the
# satellite names is crash-heavy, drop-light
_FAULT_CHURN = dict(_CHURN, **{"run.churn.dropout_hazard": 0.01})


@pytest.fixture(scope="module")
def undefended_accs(tmp_path_factory):
    """(benign, attacked) accuracy of the plain weighted mean under
    churn: fitted once for the two defenses' cases below."""
    out = tmp_path_factory.mktemp("undefended")
    return (_fit_acc(out, "churn_benign", **_FAULT_CHURN),
            _fit_acc(out, "churn_attacked_mean", **_FAULT_CHURN,
                     **_FAULT_ATTACK))


_FAULT_DEFENSES = {
    "krum": {"server.aggregator": "krum", "server.krum_byzantine": 2},
    "reputation": {"run.obs.client_ledger.enabled": True,
                   "server.reputation.enabled": True,
                   "server.aggregator": "trimmed_mean",
                   "server.trim_ratio": 0.25},
}


@pytest.mark.parametrize("defense", sorted(_FAULT_DEFENSES))
def test_crashing_compromised_clients_break_mean_not_krum_or_reputation(
    tmp_path, undefended_accs, defense,
):
    """The fault-injection headline: sign_flip at f=2/16 (scale 10)
    WITH diurnal churn + mid-round crashes on everyone, compromised
    clients included. Crash-truncated Byzantine uploads still reach
    aggregation (partial work aggregates), and the undefended mean
    degrades to chance, while (a) krum and (b) the reputation-scaled
    trimmed mean — trust from the per-client ledger multiplying each
    delta BEFORE the order statistics, the composition ReputationConfig
    ships for exactly this regime — hold their own benign-under-churn
    bands. (A bare reputation-WEIGHTED mean cannot survive a scale-10
    adversary's pre-evidence rounds: the attack transform applies after
    clipping by design, so nothing bounds round 0 — robust order
    statistics are the structural answer there, and trust composes
    with them.)"""
    benign_acc, broken_acc = undefended_accs
    assert benign_acc > 0.6, benign_acc  # learnable even under churn
    assert broken_acc <= 0.35, (
        f"weighted_mean survived sign_flip under churn: {broken_acc}"
    )
    over = _FAULT_DEFENSES[defense]
    benign = _fit_acc(tmp_path, f"churn_benign_{defense}", **_FAULT_CHURN,
                      **over)
    acc = _fit_acc(tmp_path, f"churn_attacked_{defense}", **_FAULT_CHURN,
                   **_FAULT_ATTACK, **over)
    assert acc >= benign - 0.15 and acc > broken_acc + 0.2, (
        f"{defense} failed under churn+attack: {acc} vs benign "
        f"{benign}, broken mean {broken_acc}"
    )


# ---------------------------------------------------------------------------
# the promoted FedBuff headline + the ops panels (CI smoke)
# ---------------------------------------------------------------------------


def _store_fedbuff_cfg(tmp_path, store_dir, rounds=48, **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.name = "fb_headline"
    cfg.apply_overrides({
        "algorithm": "fedbuff",
        "data.num_clients": 64, "data.store.dir": str(store_dir),
        "data.placement": "stream", "server.sampling": "streaming",
        "server.cohort_size": 8, "client.batch_size": 4,
        "server.num_rounds": rounds, "server.eval_every": 0,
        "server.checkpoint_every": 0,
        "run.out_dir": str(tmp_path),
        "run.metrics_flush_every": 2,
        "server.async_max_staleness": 2,
        "server.async_backlog_cap": 8,
        "run.obs.client_ledger.enabled": True,
        "run.obs.client_ledger.log_every": 4,
        "server.reputation.enabled": True,
        "run.obs.population.enabled": True,
        "run.churn.enabled": True,
        "run.churn.diurnal_period": 8,
        "run.churn.base_availability": 0.7,
        "run.churn.dropout_hazard": 0.05,
        "run.churn.crash_rate": 0.1,
    })
    for k, v in over.items():
        cfg.apply_overrides({k: v})
    return cfg.validate()


@pytest.fixture(scope="module")
def _store_dir(tmp_path_factory):
    from colearn_federated_learning_tpu.data.store import (
        build_synthetic_store,
    )

    d = tmp_path_factory.mktemp("fb_store")
    build_synthetic_store(
        str(d), num_clients=64, examples_per_client=16, shape=(12, 12, 1),
        num_classes=4, seed=0, test_examples=64,
    )
    return d


def test_fedbuff_promoted_headline_e2e(tmp_path, _store_dir):
    """THE acceptance e2e: store-backed + streaming sampler + per-
    insert ledger + reputation merge + diurnal churn. The promoted
    plane absorbs the arrival stream with realized staleness within
    the configured bound (clamped admissions counted, never silent),
    logs the throughput readout, and lands final eval loss within the
    benign band of the synchronous twin on the same store and seed —
    while the ledger actually accumulated per-insert evidence."""
    cfg = _store_fedbuff_cfg(tmp_path / "async", _store_dir)
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    assert int(state["round"]) == cfg.server.num_rounds
    records = [
        json.loads(line)
        for line in open(tmp_path / "async" / f"{cfg.name}.metrics.jsonl")
    ]
    summary = [r for r in records if r.get("event") == "run_summary"][-1]
    # staleness stayed within the bound OR every over-bound admission
    # was clamped-and-counted — never silently included
    rounds = [r for r in records if "max_staleness" in r]
    bound = summary["async_staleness_bound"]
    over = [r for r in rounds if r["max_staleness"] > bound]
    assert all(r.get("staleness_clamped", 0) > 0 for r in over)
    assert summary["async_updates_per_sec"] > 0
    assert summary["async_updates_absorbed"] > 0
    # per-insert forensics accumulated: one count per absorbed update,
    # minus within-step duplicate pops (the same client can be in
    # flight twice; the .set scatter collapses those to one insert —
    # documented in make_async_round_fn)
    led = np.asarray(jax.device_get(state["ledger"]))
    absorbed = summary["async_updates_absorbed"]
    assert (led[:, 0] > 0).sum() >= 8
    assert 0.8 * absorbed <= led[:, 0].sum() <= absorbed
    # population panels landed
    pops = [r for r in records if r.get("event") == "population_health"]
    assert pops and any("async" in p for p in pops)
    assert any("churn" in p for p in pops)
    async_loss = float(exp.evaluate(state["params"])["eval_loss"])

    # the synchronous twin: same store, same seed, plain fedavg over
    # the same streaming sampler (churn on — the traffic, not the
    # engine, is what varies)
    sync_cfg = _store_fedbuff_cfg(
        tmp_path / "sync", _store_dir,
        **{"algorithm": "fedavg",
           "server.reputation.enabled": False,
           "server.async_backlog_cap": 0},
    )
    sync_cfg.name = "fb_sync_twin"
    sync_exp = Experiment(sync_cfg, echo=False)
    sync_state = sync_exp.fit()
    sync_loss = float(sync_exp.evaluate(sync_state["params"])["eval_loss"])
    chance = float(np.log(4))
    # both learn; async stays within the benign band of its sync twin
    assert sync_loss < chance, (sync_loss, chance)
    assert async_loss < chance, (async_loss, chance)
    assert async_loss <= sync_loss + 0.35 * chance, (async_loss, sync_loss)


def test_watch_and_population_render_async_churn_panels(tmp_path, _store_dir):
    """CI smoke for the ops story: a shrunk store-backed fedbuff-under-
    churn fit, then `colearn watch --once --json` (subprocess — the
    real CLI) exposes the async/churn panels and the text renderer
    prints them; `colearn population` folds them."""
    cfg = _store_fedbuff_cfg(tmp_path, _store_dir, rounds=8)
    Experiment(cfg, echo=False).fit()
    out = subprocess.run(
        [sys.executable, "-m", "colearn_federated_learning_tpu.cli",
         "watch", cfg.name, "--out-dir", str(tmp_path), "--once", "--json"],
        capture_output=True, text=True, cwd=_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr
    snap = json.loads(out.stdout)
    assert snap["state"] == "completed"
    assert "async" in snap and "arrival_rate" in snap["async"], snap
    assert "churn" in snap, snap
    assert snap.get("async_updates_per_sec", 0) > 0
    assert snap.get("staleness_series"), snap
    # the text frame renders the panels too
    from colearn_federated_learning_tpu.obs.population import (
        format_watch,
        population_report,
    )

    frame = format_watch(snap)
    assert "async:" in frame and "churn:" in frame, frame
    records = [
        json.loads(line)
        for line in open(tmp_path / f"{cfg.name}.metrics.jsonl")
    ]
    report = population_report(records)
    assert report["async"]["updates_absorbed"] > 0
    assert sum(report["churn"].values()) > 0


# ---------------------------------------------------------------------------
# trace-replay availability (run.churn.trace)
# ---------------------------------------------------------------------------


def _trace_cfg_obj(trace_path, **kw):
    c = _Cfg(**kw)
    c.trace = str(trace_path)
    return c


def test_trace_model_replays_the_bitmap_pure_and_wrapping(tmp_path):
    from colearn_federated_learning_tpu.server.churn import (
        TraceChurnModel,
        build_synthetic_trace,
    )

    path = build_synthetic_trace(
        str(tmp_path / "trace"), rounds=16, rows=64, seed=3,
        diurnal_period=8,
    )
    # deterministic in its arguments: a rebuild is byte-identical
    path2 = build_synthetic_trace(
        str(tmp_path / "trace2"), rounds=16, rows=64, seed=3,
        diurnal_period=8,
    )
    np.testing.assert_array_equal(np.load(path), np.load(path2))
    m = TraceChurnModel(_trace_cfg_obj(path), seed=7)
    assert (m.trace_rounds, m.trace_rows) == (16, 64)
    ids = np.arange(256)  # more clients than rows: rows are shared
    for r in (0, 5, 11):
        np.testing.assert_array_equal(
            m.available(r, ids), m.available(r, ids)
        )
        p = m.availability_prob(r, ids)
        # the prob IS the bit clipped to the exploration floor
        assert set(np.round(p, 3)) <= {0.05, 1.0}, set(p)
        # playback wraps mod trace_rounds
        np.testing.assert_array_equal(p, m.availability_prob(r + 16, ids))
    # the row mapping is stable (pure in (seed, id)) but seed-sensitive
    m2 = TraceChurnModel(_trace_cfg_obj(path), seed=8)
    assert not np.array_equal(
        m.availability_prob(0, ids), m2.availability_prob(0, ids)
    )
    # dropout/crash hazards compose unchanged (independent hash planes)
    assert abs(m.dropped(3, np.arange(20_000)).mean() - 0.1) < 0.02


def test_trace_model_rejects_missing_or_malformed_bitmaps(tmp_path):
    from colearn_federated_learning_tpu.server.churn import TraceChurnModel

    with pytest.raises(FileNotFoundError):
        TraceChurnModel(_trace_cfg_obj(tmp_path / "nope.npy"), seed=0)
    bad = tmp_path / "bad.npy"
    np.save(bad, np.zeros((4, 4), np.float32))
    with pytest.raises(ValueError, match="uint8"):
        TraceChurnModel(_trace_cfg_obj(bad), seed=0)
    flat = tmp_path / "flat.npy"
    np.save(flat, np.zeros(16, np.uint8))
    with pytest.raises(ValueError, match="2-D"):
        TraceChurnModel(_trace_cfg_obj(flat), seed=0)


def _trace_overrides(tmp_path):
    from colearn_federated_learning_tpu.server.churn import (
        build_synthetic_trace,
    )

    path = build_synthetic_trace(
        str(tmp_path / "avail_trace"), rounds=12, rows=32, seed=0,
        diurnal_period=6,
    )
    return {
        "run.churn.enabled": True,
        "run.churn.trace": path,
        "run.churn.dropout_hazard": 0.1,
        "run.churn.crash_rate": 0.2,
    }


def test_trace_schedule_is_engine_invariant(tmp_path):
    """Trace playback inherits the churn purity contract verbatim: the
    realized cohorts are bitwise-equal across engines."""
    over = _trace_overrides(tmp_path)
    cohorts = {}
    for engine in ("sharded", "sequential"):
        cfg = _sync_cfg(tmp_path / engine, rounds=4,
                        **dict(over, **{"run.engine": engine}))
        exp = Experiment(cfg, echo=False)
        from colearn_federated_learning_tpu.server.churn import (
            TraceChurnModel,
        )

        assert isinstance(exp._churn, TraceChurnModel)
        cohorts[engine] = [
            np.asarray(exp.sampler.sample(r)) for r in range(8)
        ]
    for a, b in zip(cohorts["sharded"], cohorts["sequential"]):
        np.testing.assert_array_equal(a, b)


def test_trace_resume_replays_bitwise_and_logs_provenance(tmp_path):
    """Nothing trace-related rides the checkpoint: a resumed run
    re-derives every draw from (seed, round, id) + the mmapped bitmap;
    the churn event pins the trace provenance."""
    over = _trace_overrides(tmp_path)

    def run(path, rounds, resume=False):
        cfg = _sync_cfg(path, rounds=rounds, **over)
        cfg.server.checkpoint_every = 2
        cfg.run.resume = resume
        return cfg, Experiment(cfg, echo=False).fit()

    cfg_s, straight = run(tmp_path / "straight", 6)
    run(tmp_path / "resumed", 4)
    _, resumed = run(tmp_path / "resumed", 6, resume=True)
    assert int(resumed["round"]) == 6
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        straight["params"], resumed["params"],
    )
    records = [
        json.loads(line)
        for line in open(tmp_path / "straight" / f"{cfg_s.name}.metrics.jsonl")
    ]
    churn_ev = [r for r in records if r.get("event") == "churn"]
    assert len(churn_ev) == 1
    assert churn_ev[0]["trace"].endswith("avail_trace.npy")
    assert churn_ev[0]["trace_rounds"] == 12
    assert churn_ev[0]["trace_rows"] == 32


# ---------------------------------------------------------------------------
# diurnal-trough edge case: every draw stays bounded and deterministic
# ---------------------------------------------------------------------------


def test_streaming_trough_draw_is_bounded_and_deterministic():
    """A full-depth trough (every client offline) must terminate the
    streaming rejection loop within its try budget and complete the
    cohort with the deterministic smallest-id backstop — bounded
    iterations, never an infinite loop."""
    from colearn_federated_learning_tpu.server.sampler import (
        _MAX_DRAW_TRIES_PER_SLOT,
        CohortSampler,
    )

    calls = {"n": 0}

    def all_offline(round_idx, ids):
        calls["n"] += len(ids)
        return np.zeros(len(np.atleast_1d(ids)), bool)

    k = 4
    s = CohortSampler(1000, k, seed=0, mode="streaming",
                      availability_fn=all_offline)
    out = s.sample(0)
    np.testing.assert_array_equal(out, np.arange(k))  # smallest ids
    assert calls["n"] <= _MAX_DRAW_TRIES_PER_SLOT * k  # bounded tries
    draws = s.take_draw_stats(0)
    assert draws["backstop"] == k
    assert draws["offline"] > 0
    # deterministic: the same round draws the same backstop cohort
    np.testing.assert_array_equal(out, s.sample(0))


def test_uniform_trough_fills_smallest_offline_ids():
    """The gated uniform draw under a partial trough: every online
    client participates and the smallest offline ids fill the rest —
    no rejection loop at all."""
    from colearn_federated_learning_tpu.server.sampler import CohortSampler

    online_set = {7, 11}

    def avail(round_idx, ids):
        return np.isin(np.atleast_1d(ids), list(online_set))

    s = CohortSampler(16, 4, seed=0, mode="fixed", availability_fn=avail)
    np.testing.assert_array_equal(s.sample(0), np.array([0, 1, 7, 11]))
    # full trough: deterministic smallest ids
    online_set.clear()
    np.testing.assert_array_equal(s.sample(1), np.arange(4))


def test_trough_floor_keeps_probability_at_min_availability():
    """base_availability AT the floor with a full-depth diurnal wave:
    the clip keeps every probability exactly at min_availability in
    the trough — the exploration floor never closes."""
    m = ChurnModel(
        _Cfg(base_availability=0.05, diurnal_amplitude=1.0,
             diurnal_period=8),
        seed=0,
    )
    ids = np.arange(512)
    probs = np.stack([m.availability_prob(r, ids) for r in range(8)])
    assert probs.min() >= 0.05 - 1e-12
    assert (np.isclose(probs, 0.05)).any()  # the trough actually bites


# ---------------------------------------------------------------------------
# capability-matrix flips + analyzer coverage
# ---------------------------------------------------------------------------


def test_capability_matrix_records_the_fedbuff_flips():
    with open(os.path.join(_ROOT, "capability_matrix.json")) as f:
        rejected = json.load(f)["rejected"]
    for flipped in ("client_ledger+fedbuff", "fedbuff+reputation",
                    "fedbuff+sampling_streaming_ledger",
                    "fedbuff+stream_placement"):
        assert flipped not in rejected, rejected[flipped]
    # the genuinely-unsound neighbours stayed rejected, with reasons
    for still in ("fedbuff+paged_ledger", "churn+gossip",
                  "churn+shape_buckets", "churn+sampling_poisson"):
        assert rejected[still].strip(), still


def test_seed_purity_lint_covers_churn_module():
    from colearn_federated_learning_tpu.analysis.seed_purity import (
        DEFAULT_SCOPE,
        _scope_files,
        lint_files,
    )

    pkg = os.path.join(_ROOT, "colearn_federated_learning_tpu")
    files = _scope_files(pkg, DEFAULT_SCOPE)
    churn_py = os.path.join(pkg, "server", "churn.py")
    assert churn_py in files  # covered from day one (server/ scope)
    # and the module is clean on its own: no wall-clock, no unseeded
    # rng, no bare asserts — zero allowlist entries needed
    assert lint_files([churn_py], _ROOT) == []
