"""Per-client forensic ledger (obs/ledger.py, run.obs.client_ledger):
stat/update semantics, the pure-observability contract (ledger-on
params == ledger-off params bitwise), ledger parity across
sharded↔sequential and fused↔unfused engines per aggregator × attack,
abort-path flushes, the `colearn clients` report + CLI, config pairing
rejections, and the headline cifar10_krum_byzantine CPU smoke with
detection precision/recall against the ground-truth sign_flip set."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu import cli
from colearn_federated_learning_tpu.config import get_named_config
from colearn_federated_learning_tpu.obs.ledger import (
    LEDGER_COLS,
    LEDGER_WIDTH,
    client_round_stats,
    clients_report,
    format_clients_report,
    update_ledger,
    upload_residual,
)

# ledger column indices (LEDGER_COLS order)
_COUNT, _FLAGGED = 0, 1


# ---------------------------------------------------------------------------
# unit: stats block + ledger update semantics
# ---------------------------------------------------------------------------


def test_client_round_stats_flags_the_outlier():
    # 5 honest clients near a common direction, one boosted sign-flip,
    # one dropped (must not pollute the median/MAD)
    base = np.linspace(0.9, 1.1, 8).astype(np.float32)
    rows = np.stack([base * s for s in (1.0, 1.05, 0.95, 1.02, 0.98)])
    flip = (-10.0 * base)[None]
    junk = (50.0 * base)[None]  # the dropped client: huge but excluded
    stack = {"w": jnp.asarray(np.concatenate([rows, flip, junk]))}
    n_ex = jnp.asarray([10, 10, 10, 10, 10, 10, 0], jnp.float32)
    mean = {"w": jnp.asarray(base)}
    losses = jnp.ones(7, jnp.float32)
    resid = jnp.zeros(7, jnp.float32)
    stats = np.asarray(
        client_round_stats(stack, mean, losses, resid, n_ex, zmax=3.5)
    )
    assert stats.shape == (7, 6)
    l2, cos, flag = stats[:, 0], stats[:, 1], stats[:, 5]
    np.testing.assert_allclose(
        l2[0], np.linalg.norm(base), rtol=1e-6
    )
    assert cos[:5].min() > 0.99  # honest cluster aligns with the mean
    assert cos[5] < -0.99  # the sign-flipper anti-aligns
    assert flag[5] == 1.0 and flag[:5].max() == 0.0
    assert flag[6] == 0.0  # dropped client can never be flagged


def test_upload_residual_is_blockwise_l2_of_difference():
    a = {"w": jnp.asarray([[3.0, 0.0], [0.0, 0.0]])}
    b = {"w": jnp.asarray([[0.0, 4.0], [0.0, 0.0]])}
    np.testing.assert_allclose(np.asarray(upload_residual(a, b)), [5.0, 0.0])


def test_update_ledger_counts_emas_and_oob_drop():
    rows = 4
    ledger = jnp.zeros((rows, LEDGER_WIDTH), jnp.float32)
    # cohort: clients 1 and 3, client 2 dropped, one poisson pad (id=4)
    ids = jnp.asarray([1, 3, 2, 4], jnp.int32)
    n_ex = jnp.asarray([5.0, 5.0, 0.0, 0.0])
    stats = jnp.asarray([
        # l2,  cos, resid, loss,  z, flag
        [1.0, 0.5, 0.1, 2.0, 1.0, 0.0],
        [9.0, -0.9, 0.2, 3.0, 9.0, 1.0],
        [7.0, 7.0, 7.0, 7.0, 7.0, 1.0],  # dropped: must not land
        [8.0, 8.0, 8.0, 8.0, 8.0, 1.0],  # pad: must not land
    ], jnp.float32)
    led1 = np.asarray(update_ledger(ledger, ids, n_ex, stats, ema=0.5))
    assert led1[0].sum() == 0.0 and led1[2].sum() == 0.0
    # first observation seeds the EMA with the value itself
    np.testing.assert_allclose(led1[1], [1, 0, 1.0, 0.5, 0.1, 2.0, 1.0])
    np.testing.assert_allclose(led1[3], [1, 1, 9.0, -0.9, 0.2, 3.0, 9.0])
    # second round: client 1 participates again with different stats
    ids2 = jnp.asarray([1], jnp.int32)
    stats2 = jnp.asarray([[3.0, 0.0, 0.3, 4.0, 2.0, 1.0]], jnp.float32)
    led2 = np.asarray(update_ledger(
        jnp.asarray(led1), ids2, jnp.asarray([5.0]), stats2, ema=0.5
    ))
    np.testing.assert_allclose(
        led2[1], [2, 1, 2.0, 0.25, 0.2, 3.0, 1.5]
    )  # count+1, flagged+1, ema + 0.5*(x - ema)
    np.testing.assert_allclose(led2[3], led1[3])  # untouched row


# ---------------------------------------------------------------------------
# driver e2e: pure observability + engine/fusion parity
# ---------------------------------------------------------------------------


def _cfg(out, engine="sharded", fuse=1, rounds=4, **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.apply_overrides({
        "server.num_rounds": rounds, "server.eval_every": 0,
        "data.num_clients": 8, "server.cohort_size": 4,
        "data.synthetic_train_size": 256, "data.synthetic_test_size": 64,
        "data.max_examples_per_client": 32, "client.batch_size": 16,
        "run.out_dir": str(out), "run.metrics_flush_every": 2,
        "run.engine": engine, "run.fuse_rounds": fuse,
        "run.obs.client_ledger.enabled": True,
        **over,
    })
    return cfg.validate()


def _fit(cfg):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    return exp, state


def _ledger(state):
    return np.asarray(jax.device_get(state["ledger"]))


def test_ledger_is_pure_observability(tmp_path):
    """Enabling the ledger must not move the params trajectory: the
    weighted-mean path still aggregates through its psum (the stack
    only feeds the stats), so ledger-on == ledger-off BITWISE."""
    _, on = _fit(_cfg(tmp_path / "on"))
    cfg_off = _cfg(tmp_path / "off")
    cfg_off.run.obs.client_ledger.enabled = False
    _, off = _fit(cfg_off)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        on["params"], off["params"],
    )
    led = _ledger(on)
    # 4 rounds x cohort 4 = 16 participations over the 8 clients
    assert led[:, _COUNT].sum() == 16
    assert (led[:, 2] > 0).sum() >= 1  # some ema_l2 accumulated


def _assert_ledger_parity(a, b):
    """Cross-engine ledger comparison: integer count/flagged columns
    exact; EMA columns to the engines' established cross-engine float
    tolerance (per-client deltas differ in ulps between the vmapped
    lane and the per-client oracle — the same tolerance the params
    parity tests pin); the z column looser still (it divides the ulp
    noise by a small MAD, amplifying it)."""
    np.testing.assert_array_equal(a[:, :2], b[:, :2])
    np.testing.assert_allclose(a[:, 2:6], b[:, 2:6], rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(a[:, 6], b[:, 6], rtol=1e-2, atol=1e-5)


_MATRIX = [
    ("weighted_mean", ""),
    ("weighted_mean", "sign_flip"),
    ("krum", ""),
    ("krum", "sign_flip"),
]


@pytest.mark.parametrize("aggregator,attack", _MATRIX)
def test_ledger_parity_engines_and_fusion(tmp_path, aggregator, attack):
    """The acceptance matrix: {weighted_mean, krum} x {none, sign_flip}.
    fused↔unfused ledgers are BITWISE equal (same engine, same scan
    body); sharded↔sequential ledgers agree exactly on the integer
    count/flagged columns and to the engines' established cross-engine
    float tolerance on the EMA columns (per-client deltas differ in
    ulps between the vmapped lane and the per-client oracle — the same
    tolerance the params parity tests pin)."""
    over = {"server.aggregator": aggregator}
    if attack:
        over.update({"attack.kind": attack, "attack.fraction": 0.25})
    _, sh = _fit(_cfg(tmp_path / "sh", "sharded", **over))
    _, sq = _fit(_cfg(tmp_path / "sq", "sequential", **over))
    _, fu = _fit(_cfg(tmp_path / "fu", "sharded", fuse=2, **over))
    led_sh, led_sq, led_fu = _ledger(sh), _ledger(sq), _ledger(fu)
    np.testing.assert_array_equal(led_sh, led_fu)  # fused == unfused
    _assert_ledger_parity(led_sh, led_sq)
    if attack:
        # the boosted sign-flippers that were sampled got flagged
        from colearn_federated_learning_tpu.server.attacks import (
            select_compromised,
        )

        byz = select_compromised(8, 0.25, seed=0)
        seen = led_sh[byz, _COUNT] > 0
        assert (led_sh[byz, _FLAGGED][seen] > 0).all()


def test_ledger_ef_residual_parity(tmp_path):
    """Error feedback: the resid stat is ||e_i^+|| and the ledger rides
    alongside the EF store in both engines."""
    over = {"server.compression": "qsgd", "server.error_feedback": True}
    _, sh = _fit(_cfg(tmp_path / "sh", "sharded", **over))
    _, sq = _fit(_cfg(tmp_path / "sq", "sequential", **over))
    led_sh, led_sq = _ledger(sh), _ledger(sq)
    _assert_ledger_parity(led_sh, led_sq)
    seen = led_sh[:, _COUNT] > 0
    assert (led_sh[seen, 4] > 0).all()  # ema_resid: qsgd always drops bits
    # and fused EF carries the ledger through the scan carry bitwise
    _, fu = _fit(_cfg(tmp_path / "fu", "sharded", fuse=2, **over))
    np.testing.assert_array_equal(led_sh, _ledger(fu))


def test_ledger_periodic_records_and_resume_roundtrip(tmp_path):
    cfg = _cfg(tmp_path, **{
        "run.obs.client_ledger.log_every": 2,
        "server.checkpoint_every": 2,
    })
    exp, state = _fit(cfg)
    path = os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl")
    recs = [json.loads(l) for l in open(path)]
    led_recs = [r for r in recs if r.get("event") == "client_ledger"]
    assert len(led_recs) >= 2  # periodic + final
    for r in led_recs:
        assert set(LEDGER_COLS[2:]) <= set(r)
        assert len(r["ids"]) == len(r["count"]) == len(r["flagged"])
    # counts in the FINAL record match the device ledger
    final = led_recs[-1]
    led = _ledger(state)
    np.testing.assert_array_equal(
        led[np.asarray(final["ids"], int), _COUNT],
        np.asarray(final["count"], np.float32),
    )
    # the ledger rides checkpoints: a resumed run continues the counts
    cfg2 = _cfg(tmp_path, rounds=6, **{
        "run.obs.client_ledger.log_every": 2,
        "server.checkpoint_every": 2, "run.resume": True,
    })
    _, resumed = _fit(cfg2)
    led6 = _ledger(resumed)
    assert led6[:, _COUNT].sum() == 6 * 4  # 6 rounds x cohort 4
    # and it equals the straight 6-round run bitwise (fresh dir)
    _, straight = _fit(_cfg(tmp_path / "straight", rounds=6))
    np.testing.assert_array_equal(led6, _ledger(straight))


# ---------------------------------------------------------------------------
# abort paths: partial ledgers still land in the JSONL
# ---------------------------------------------------------------------------


def test_ledger_flushed_on_health_abort(tmp_path):
    from colearn_federated_learning_tpu.obs import HealthAbortError
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cfg = _cfg(tmp_path, "sequential", **{
        "client.lr": 1e38, "run.obs.on_unhealthy": "abort",
        "run.metrics_flush_every": 1,
    })
    exp = Experiment(cfg, echo=False)
    with pytest.raises(HealthAbortError):
        exp.fit()
    recs = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl"))]
    led_recs = [r for r in recs if r.get("event") == "client_ledger"]
    assert led_recs, "partial ledger must land on HealthAbortError"
    assert led_recs[-1]["ids"], "aborted run still tracked participants"
    assert any(r.get("event") == "run_summary" for r in recs)


def test_ledger_flushed_on_keyboard_interrupt(tmp_path, monkeypatch):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cfg = _cfg(tmp_path, "sequential",
               **{"run.metrics_flush_every": 1})
    exp = Experiment(cfg, echo=False)
    orig = Experiment.run_round

    def interrupt(self, state, round_idx, **kw):
        if round_idx >= 2:
            raise KeyboardInterrupt
        return orig(self, state, round_idx, **kw)

    monkeypatch.setattr(Experiment, "run_round", interrupt)
    with pytest.raises(KeyboardInterrupt):
        exp.fit()
    recs = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl"))]
    led_recs = [r for r in recs if r.get("event") == "client_ledger"]
    assert led_recs and led_recs[-1]["round"] == 2
    assert sum(led_recs[-1]["count"]) == 2 * 4  # the two completed rounds


# ---------------------------------------------------------------------------
# the `colearn clients` report + CLI
# ---------------------------------------------------------------------------


def test_clients_report_and_cli(tmp_path, capsys):
    cfg = _cfg(tmp_path, "sharded", rounds=6, **{
        "attack.kind": "sign_flip", "attack.fraction": 0.25,
        "server.aggregator": "krum",
    })
    exp, state = _fit(cfg)
    path = os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl")
    recs = [json.loads(l) for l in open(path)]
    report = clients_report(recs)
    atk = report["attack"]
    assert atk["kind"] == "sign_flip"
    assert atk["n_compromised"] == len(exp.compromised) == 2
    assert atk["recall"] >= 0.5 and atk["precision"] >= 0.5
    # every detected client really is compromised at this attack scale
    assert set(atk["detected"]) <= set(int(c) for c in exp.compromised)
    text = format_clients_report(report, path)
    assert "precision" in text and "sign_flip" in text
    # CLI: table, --json, and clean errors
    assert cli.main(["clients", path]) == 0
    out = capsys.readouterr().out
    assert "detection precision" in out
    assert cli.main(["clients", path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["attack"]["recall"] >= 0.5
    assert cli.main(["clients", "no_such_run",
                     "--out-dir", str(tmp_path / "nope")]) == 2


def test_threshold_sweep_and_cli_flag(tmp_path, capsys):
    """`colearn clients --threshold-sweep`: precision/recall at several
    min-flag-rate cutoffs from one run's JSONL, so operators pick the
    detection threshold without re-running training."""
    from colearn_federated_learning_tpu.obs.ledger import (
        DEFAULT_SWEEP_THRESHOLDS,
        format_threshold_sweep,
        threshold_sweep,
    )

    cfg = _cfg(tmp_path, "sharded", rounds=6, **{
        "attack.kind": "sign_flip", "attack.fraction": 0.25,
    })
    _fit(cfg)
    path = os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl")
    recs = [json.loads(l) for l in open(path)]
    rows = threshold_sweep(recs)
    assert len(rows) == len(DEFAULT_SWEEP_THRESHOLDS)
    for r in rows:
        assert set(r) == {"threshold", "detected", "true_positives",
                          "false_positives", "false_negatives",
                          "precision", "recall"}
    # monotone by construction: raising the threshold never detects MORE
    dets = [r["detected"] for r in rows]
    assert dets == sorted(dets, reverse=True), dets
    text = format_threshold_sweep(rows)
    assert "min-flag-rate" in text and "precision" in text
    # CLI: table + --json carry the sweep
    assert cli.main(["clients", path, "--threshold-sweep"]) == 0
    out = capsys.readouterr().out
    assert "detection threshold sweep" in out
    assert cli.main(["clients", path, "--threshold-sweep", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["threshold_sweep"]) == len(DEFAULT_SWEEP_THRESHOLDS)
    # a benign run has no ground truth to sweep against: clean error
    benign = _cfg(tmp_path / "benign")
    _fit(benign)
    bpath = os.path.join(
        str(tmp_path / "benign"), f"{benign.name}.metrics.jsonl"
    )
    assert cli.main(["clients", bpath, "--threshold-sweep"]) == 2
    err = capsys.readouterr().err
    assert "attack" in err and "Traceback" not in err


def test_clients_cli_errors_without_ledger(tmp_path, capsys):
    p = tmp_path / "x.metrics.jsonl"
    p.write_text('{"round": 1, "train_loss": 1.0, "schema": 1}\n')
    assert cli.main(["clients", str(p)]) == 2
    err = capsys.readouterr().err
    assert "client_ledger" in err and "Traceback" not in err


# ---------------------------------------------------------------------------
# config/engine pairing rejections
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overrides,match", [
    ({"server.secure_aggregation": True, "server.clip_delta_norm": 1.0},
     "secure_aggregation"),
    ({"server.dp_client_noise_multiplier": 1.0,
      "server.clip_delta_norm": 1.0}, "client-level DP"),
    # fedbuff × dense ledger is SUPPORTED since the churn PR (per-
    # insert stats); the pager's slot remap stays synchronous-only
    ({"algorithm": "fedbuff",
      "run.obs.client_ledger.hot_capacity": 64}, "fedbuff"),
    ({"algorithm": "scaffold", "client.momentum": 0.0}, "scaffold"),
    ({"run.obs.client_ledger.ema": 0.0}, "ema"),
    ({"run.obs.client_ledger.zmax": -1.0}, "zmax"),
    ({"run.obs.client_ledger.log_every": -1}, "log_every"),
])
def test_ledger_pairing_rejections(overrides, match):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.run.obs.client_ledger.enabled = True
    for k, v in overrides.items():
        cfg.apply_overrides({k: v})
    with pytest.raises(ValueError, match=match):
        cfg.validate()


def test_gossip_rejects_ledger():
    cfg = get_named_config("cifar10_gossip_16")
    cfg.run.obs.client_ledger.enabled = True
    with pytest.raises(ValueError, match="gossip"):
        cfg.validate()


# ---------------------------------------------------------------------------
# paged ledger (run.obs.client_ledger.hot_capacity): [hot, 7] device hot
# set + host mmap cold spill — merged view bitwise-equal to dense
# ---------------------------------------------------------------------------


def _merged_ledger(exp, state):
    led = _ledger(state)
    if exp._pager is not None:
        return exp._pager.merged(led)
    return led


def _fit_merged(cfg):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    return exp, state, _merged_ledger(exp, state)


@pytest.mark.parametrize("engine", ["sharded", "sequential"])
def test_paged_ledger_merged_equals_dense(tmp_path, engine):
    """hot_capacity 5 < 8 clients with cohort 4 forces real page-ins and
    LRU evictions; the merged (hot ∪ cold) ledger must equal the dense
    run's BITWISE, and — with reputation feeding trust from the paged
    rows — the params trajectory too (paging invisible to the program)."""
    over = {
        "attack.kind": "sign_flip", "attack.fraction": 0.25,
        "server.reputation.enabled": True,
    }
    _, d_state, d_led = _fit_merged(_cfg(tmp_path / "d", engine,
                                         rounds=6, **over))
    exp, p_state, p_led = _fit_merged(_cfg(tmp_path / "p", engine, rounds=6,
                                           **{**over,
                                              "run.obs.client_ledger"
                                              ".hot_capacity": 5}))
    assert exp._pager is not None
    assert p_led.shape[0] == 8  # merged view is client-indexed
    np.testing.assert_array_equal(d_led, p_led)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        d_state["params"], p_state["params"],
    )
    # the small hot set genuinely paged (8 distinct clients through 5
    # slots over 6 rounds cannot avoid evicting)
    assert exp._pager.evictions >= 1
    assert exp._pager.page_syncs >= 1


def test_paged_ledger_fused_chunk_union(tmp_path):
    """Under fuse_rounds the whole chunk's cohort union is slot-assigned
    before dispatch — fused paged == fused dense bitwise (hot capacity
    exactly the worst-case union, the construction-check floor)."""
    _, _, d_led = _fit_merged(_cfg(tmp_path / "d", fuse=2, rounds=6))
    exp, _, p_led = _fit_merged(_cfg(
        tmp_path / "p", fuse=2, rounds=6,
        **{"run.obs.client_ledger.hot_capacity": 8}
    ))
    np.testing.assert_array_equal(d_led, p_led)


def test_paged_ledger_checkpoint_resume_roundtrip(tmp_path):
    """The page-in/page-out roundtrip through checkpoint/resume: hot
    array, slot maps, and the cold spill all ride the checkpoint, so a
    resumed run replays slot assignment and lands the same merged
    ledger (and JSONL records keep CLIENT ids, never slots)."""
    over = {
        "run.obs.client_ledger.hot_capacity": 5,
        "run.obs.client_ledger.log_every": 2,
        "server.checkpoint_every": 3,
    }
    _, s_state, s_led = _fit_merged(_cfg(tmp_path / "straight", rounds=6,
                                         **over))
    _fit_merged(_cfg(tmp_path / "resumed", rounds=3, **over))
    exp, r_state, r_led = _fit_merged(_cfg(tmp_path / "resumed", rounds=6,
                                           **{**over, "run.resume": True}))
    np.testing.assert_array_equal(s_led, r_led)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        s_state["params"], r_state["params"],
    )
    # periodic records carry client ids within [0, num_clients), with
    # counts matching the merged view
    path = os.path.join(str(tmp_path / "resumed"),
                        "mnist_fedavg_2.metrics.jsonl")
    recs = [json.loads(l) for l in open(path)]
    led_recs = [r for r in recs if r.get("event") == "client_ledger"]
    assert led_recs
    final = led_recs[-1]
    assert final["num_clients"] == 8
    assert all(0 <= i < 8 for i in final["ids"])
    np.testing.assert_array_equal(
        r_led[np.asarray(final["ids"], int), _COUNT],
        np.asarray(final["count"], np.float32),
    )
    # run_summary records the paging accounting
    rs = [r for r in recs if r.get("event") == "run_summary"][-1]
    assert "ledger_evictions" in rs and "ledger_page_syncs" in rs


def test_paged_ledger_capacity_and_pairing_rejections(tmp_path):
    # hot set smaller than one dispatch's cohort: construction-time error
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cfg = _cfg(tmp_path, **{"run.obs.client_ledger.hot_capacity": 3})
    with pytest.raises(ValueError, match="hot_capacity=3"):
        Experiment(cfg, echo=False)
    # fused: the floor is the chunk union (cohort × fuse)
    cfg = _cfg(tmp_path / "f", fuse=2,
               **{"run.obs.client_ledger.hot_capacity": 6})
    with pytest.raises(ValueError, match="fuse_rounds=2"):
        Experiment(cfg, echo=False)
    # EF shares the cohort-id input the pager remaps: rejected
    cfg = get_named_config("mnist_fedavg_2")
    cfg.apply_overrides({
        "run.obs.client_ledger.enabled": True,
        "run.obs.client_ledger.hot_capacity": 4,
        "server.compression": "qsgd", "server.error_feedback": True,
    })
    with pytest.raises(ValueError, match="error_feedback"):
        cfg.validate()
    cfg = get_named_config("mnist_fedavg_2")
    cfg.run.obs.client_ledger.hot_capacity = -1
    with pytest.raises(ValueError, match="hot_capacity"):
        cfg.validate()
    # hot_capacity >= num_clients degrades to the dense store
    cfg = _cfg(tmp_path / "dense",
               **{"run.obs.client_ledger.hot_capacity": 8})
    exp = Experiment(cfg, echo=False)
    assert exp._pager is None


# ---------------------------------------------------------------------------
# tier-1 CPU smoke: the headline adversarial config with the ledger on
# ---------------------------------------------------------------------------


def _headline_cfg(out, engine):
    """cifar10_krum_byzantine shrunk for CPU (same shrink discipline as
    tests/test_all_configs.py — the structure stays: resnet18 family,
    krum defense, live sign_flip adversary at f=2 of a 16-client
    federation, cohort 8 so the Blanchard bound 2f+2 < 8 holds)."""
    cfg = get_named_config("cifar10_krum_byzantine")
    cfg.apply_overrides({
        "data.num_clients": 16, "model.kwargs.width": 8,
        "server.cohort_size": 8, "server.num_rounds": 5,
        "server.eval_every": 0, "server.krum_byzantine": 2,
        "client.batch_size": 8, "data.max_examples_per_client": 16,
        "data.synthetic_train_size": 512, "data.synthetic_test_size": 64,
        "run.compute_dtype": "float32", "run.local_param_dtype": "",
        "run.metrics_flush_every": 2, "run.out_dir": str(out),
        "run.engine": engine,
        "run.obs.client_ledger.enabled": True,
        # this smoke pins LEDGER semantics against the layout-free
        # sequential oracle, so both engines must run the same layout:
        # the named config ships cohort_layout=megabatch (r12), whose
        # GEMM reassociation can flip krum's near-tie winner vs the
        # oracle over 5 rounds, moving every cosine EMA — layout parity
        # has its own matrix (test_round_engine.py::TestCohortLayout)
        "run.cohort_layout": "spatial",
    })
    return cfg.validate()


def test_smoke_headline_krum_byzantine_ledger(tmp_path, shallow_zoo):
    """CI smoke for the acceptance story: the headline adversarial
    config runs with the ledger on, sharded↔sequential ledgers agree,
    and the anomaly flag detects the known sign_flip set with
    precision/recall >= 0.5 through `colearn clients`' scoring.

    The engines are compared after ONE round, where both start from the
    same parameters and differ in ulps: from the second round on krum's
    pick among near-tied candidates feeds back into every cosine EMA,
    and which candidate wins follows the rounding of the machine's
    thread count (the five-round comparison passed on one machine and
    failed on the next). The sharded side of it is the first round's
    record of the five-round run that detection is scored on."""
    cfg = _headline_cfg(tmp_path / "sharded", "sharded")
    cfg.run.obs.client_ledger.log_every = cfg.run.metrics_flush_every = 1
    exp, _ = _fit(cfg)
    assert len(exp.compromised) == 2  # f = 2/16 federation, cohort 8
    path = os.path.join(
        str(tmp_path / "sharded"), "cifar10_krum_byzantine.metrics.jsonl"
    )
    recs = [json.loads(l) for l in open(path)]
    first = next(r for r in recs
                 if r.get("event") == "client_ledger" and r["round"] == 1)
    led_sh = np.zeros((16, LEDGER_WIDTH), np.float32)
    led_sh[first["ids"]] = np.stack([first[c] for c in LEDGER_COLS], 1)
    cfg = _headline_cfg(tmp_path / "sequential", "sequential")
    cfg.server.num_rounds = 1
    _assert_ledger_parity(led_sh, _ledger(_fit(cfg)[1]))
    report = clients_report(recs)
    atk = report["attack"]
    assert atk["n_compromised_seen"] >= 1
    assert atk["recall"] >= 0.5, atk
    assert atk["precision"] >= 0.5, atk
    # nonzero recall literally: at least one known sign_flip client
    # was flagged by the in-program anomaly score
    assert atk["true_positives"] >= 1
