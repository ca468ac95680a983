"""Byzantine adversary simulation (server/attacks.py + AttackConfig):
attack-transform semantics, sharded↔sequential parity on attacked
rounds, config pairing rejections, the label-flip data path, gossip
replica poisoning, and the headline end-to-end story — sign_flip at
f=2/8 destroys plain weighted_mean FedAvg while krum / median /
trimmed_mean under the identical attack hold their benign accuracy
band."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.config import (
    ClientConfig,
    DPConfig,
    ServerConfig,
    get_named_config,
    resolve_config,
)
from colearn_federated_learning_tpu.data.loader import RoundShape, make_round_indices
from colearn_federated_learning_tpu.models import build_model, init_params
from colearn_federated_learning_tpu.parallel.mesh import build_client_mesh
from colearn_federated_learning_tpu.parallel.round_engine import (
    make_sequential_round_fn,
    make_sharded_round_fn,
)
from colearn_federated_learning_tpu.server.aggregation import make_server_update_fn
from colearn_federated_learning_tpu.server.attacks import (
    UPLOAD_ATTACKS,
    apply_upload_attack,
    flip_labels,
    select_compromised,
)
from colearn_federated_learning_tpu.server.round_driver import Experiment


# ---------------------------------------------------------------------------
# unit: compromised-set selection + transform semantics
# ---------------------------------------------------------------------------


def test_select_compromised_is_deterministic_and_sized():
    a = select_compromised(100, 0.125, seed=7)
    b = select_compromised(100, 0.125, seed=7)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 12 and len(np.unique(a)) == 12
    assert a.min() >= 0 and a.max() < 100
    # a different seed compromises a different set
    c = select_compromised(100, 0.125, seed=8)
    assert not np.array_equal(a, c)
    # floor at one attacker: an attack config can never be silently benign
    assert len(select_compromised(2, 0.1, seed=0)) == 1


def _stack(k=8, shape=(5,), seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(k,) + shape).astype(np.float32))}


def test_sign_flip_and_scale_transform_only_byz_rows():
    d = _stack()
    byz = jnp.asarray([0, 1, 0, 0, 1, 0, 0, 0], jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    flipped = apply_upload_attack(d, byz, keys, "sign_flip", 10.0, 1.0)["w"]
    scaled = apply_upload_attack(d, byz, keys, "scale", 10.0, 1.0)["w"]
    w = np.asarray(d["w"])
    for i in range(8):
        if i in (1, 4):
            np.testing.assert_allclose(flipped[i], -10.0 * w[i], rtol=1e-6)
            np.testing.assert_allclose(scaled[i], 10.0 * w[i], rtol=1e-6)
        else:
            np.testing.assert_array_equal(flipped[i], w[i])
            np.testing.assert_array_equal(scaled[i], w[i])


def test_gauss_replaces_byz_rows_with_noise():
    d = _stack()
    byz = jnp.asarray([1, 0, 0, 0, 0, 0, 0, 0], jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    out = np.asarray(
        apply_upload_attack(d, byz, keys, "gauss", 10.0, 0.5)["w"]
    )
    w = np.asarray(d["w"])
    np.testing.assert_array_equal(out[1:], w[1:])
    assert not np.allclose(out[0], w[0])
    # the replacement is eps-scaled noise, independent of the old delta
    out2 = np.asarray(
        apply_upload_attack(
            {"w": jnp.asarray(w + 100.0)}, byz, keys, "gauss", 10.0, 0.5
        )["w"]
    )
    np.testing.assert_allclose(out2[0], out[0], rtol=1e-6)


def test_alie_rows_are_honest_mean_minus_eps_std():
    d = _stack(k=6)
    byz = np.array([0, 0, 1, 0, 0, 1], np.float32)
    part = np.array([1, 1, 1, 0, 1, 1], bool)  # client 3 dropped
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    out = np.asarray(apply_upload_attack(
        d, jnp.asarray(byz), keys, "alie", 10.0, 1.5,
        participation=jnp.asarray(part),
    )["w"])
    w = np.asarray(d["w"])
    honest = w[[0, 1, 4]]  # participating, not compromised
    mu, sigma = honest.mean(0), honest.std(0)
    want = mu - 1.5 * sigma
    np.testing.assert_allclose(out[2], want, rtol=1e-5)
    np.testing.assert_allclose(out[5], want, rtol=1e-5)
    np.testing.assert_array_equal(out[[0, 1, 3, 4]], w[[0, 1, 3, 4]])


def test_label_flip_poisons_only_compromised_shards():
    y = np.arange(10, dtype=np.int32) % 10
    shards = [np.array([0, 1, 2]), np.array([3, 4, 5]), np.array([6, 7, 8, 9])]
    out = flip_labels(y, shards, np.array([1]), num_classes=10)
    np.testing.assert_array_equal(out[[3, 4, 5]], 9 - y[[3, 4, 5]])
    np.testing.assert_array_equal(out[[0, 1, 2, 6, 7, 8, 9]],
                                  y[[0, 1, 2, 6, 7, 8, 9]])
    # the input corpus is untouched (flip works on a copy)
    np.testing.assert_array_equal(y, np.arange(10) % 10)


# ---------------------------------------------------------------------------
# engine parity: attacked rounds agree across sharded and sequential
# ---------------------------------------------------------------------------


def _setup(cohort=8, n=256):
    model = build_model("lenet5", num_classes=10)
    params = init_params(model, (28, 28, 1), seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))

    class _Fed:
        def __init__(self, ci):
            self.client_indices = ci

    splits = np.array_split(rng.permutation(n), cohort)
    fed = _Fed([s[: rng.integers(8, len(s) + 1)] for s in splits])
    shape = RoundShape(local_epochs=1, steps_per_epoch=4, batch_size=8, cap=32)
    idx, mask, n_ex = make_round_indices(fed, list(range(cohort)), shape, rng)
    return model, params, x, y, idx, mask, n_ex


@pytest.mark.parametrize("kind,aggregator", [
    # every attack kind through the default aggregator, plus one
    # attack × robust-defense composition (the dryrun matrix's pair)
    ("sign_flip", "weighted_mean"),
    ("gauss", "weighted_mean"),
    ("scale", "weighted_mean"),
    ("alie", "weighted_mean"),
    ("sign_flip", "krum"),
])
def test_attacked_round_sharded_matches_sequential(kind, aggregator):
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    mesh = build_client_mesh(4)
    common = dict(aggregator=aggregator, attack=kind, attack_scale=10.0,
                  attack_eps=1.0)
    if aggregator == "krum":
        common["byzantine_f"] = 2
    sharded = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, server_update,
        cohort_size=8, donate=False, **common,
    )
    sequential = make_sequential_round_fn(
        model, ccfg, DPConfig(), "classify", server_update, **common,
    )
    byz = jnp.asarray([0, 1, 0, 0, 1, 0, 0, 0], jnp.float32)
    # one dropped client so alie's honest statistics exclude it
    n_drop = n_ex.copy()
    n_drop[3] = 0.0
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_drop),
            jax.random.PRNGKey(42))
    p_sh, _, m_sh = sharded(params, init(params), *args, byz)
    p_sq, _, m_sq = sequential(params, init(params), *args, byz=byz)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6),
        p_sh, p_sq,
    )
    np.testing.assert_allclose(m_sh.train_loss, m_sq.train_loss, rtol=1e-5)


def test_attacked_round_actually_moves_params():
    """sign_flip at scale 10 must change the aggregate vs the benign
    round — the mask input is live, not a decoration."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1, momentum=0.9)
    init, server_update = make_server_update_fn(
        ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    )
    mesh = build_client_mesh(4)
    atk = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, server_update,
        cohort_size=8, donate=False, attack="sign_flip", attack_scale=10.0,
    )
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(0))
    byz0 = jnp.zeros(8, jnp.float32)
    byz2 = jnp.asarray([1, 0, 0, 1, 0, 0, 0, 0], jnp.float32)
    p0, _, _ = atk(params, init(params), *args, byz0)
    p2, _, _ = atk(params, init(params), *args, byz2)
    diff = sum(
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p2))
    )
    assert diff > 1e-4, diff


# ---------------------------------------------------------------------------
# config validation: every unsound pairing is rejected with a reason
# ---------------------------------------------------------------------------


def _attack_cfg(kind="sign_flip", **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.attack.kind = kind
    for k, v in over.items():
        cfg.apply_overrides({k: v})
    return cfg


@pytest.mark.parametrize("kind,overrides,match", [
    ("nope", {}, "unknown attack.kind"),
    ("sign_flip", {"attack.fraction": 0.0}, "fraction"),
    ("sign_flip", {"attack.fraction": 1.5}, "fraction"),
    ("sign_flip", {"attack.scale": 0.0}, "scale"),
    ("sign_flip",
     {"server.secure_aggregation": True, "server.clip_delta_norm": 1.0},
     "secure_aggregation"),
    ("sign_flip",
     {"server.dp_client_noise_multiplier": 1.0,
      "server.clip_delta_norm": 1.0},
     "client-level DP"),
    ("sign_flip", {"dp.enabled": True}, "dp.enabled"),
    ("sign_flip",
     {"algorithm": "scaffold", "client.momentum": 0.0}, "scaffold"),
    ("label_flip",
     {"algorithm": "scaffold", "client.momentum": 0.0}, "scaffold"),
    ("sign_flip", {"algorithm": "fedbuff"}, "fedbuff"),
    ("gauss",
     {"server.error_feedback": True, "server.compression": "qsgd"},
     "error_feedback"),
    ("label_flip", {"model.num_classes": 0}, "num_classes"),
])
def test_attack_pairing_rejections(kind, overrides, match):
    cfg = _attack_cfg(kind, **overrides)
    with pytest.raises(ValueError, match=match):
        cfg.validate()


def test_alie_rejected_with_gossip():
    cfg = get_named_config("cifar10_gossip_16")
    cfg.attack.kind = "alie"
    with pytest.raises(ValueError, match="alie"):
        cfg.validate()
    # the per-client kinds ARE the decentralized threat model
    cfg.attack.kind = "sign_flip"
    cfg.validate()


def test_label_flip_composes_with_fused_rounds():
    cfg = _attack_cfg("label_flip")
    cfg.data.num_clients = 8
    cfg.server.cohort_size = 4
    cfg.server.num_rounds = 8
    cfg.server.eval_every = 4
    cfg.run.fuse_rounds = 4
    cfg.validate()  # data-level attack, no engine involvement


def test_upload_attacks_compose_with_fused_rounds():
    """r6: upload attacks validate under fuse_rounds > 1 (the byzantine
    masks become a stacked [fuse, K] scan input); the fused↔unfused
    numeric parity is pinned in tests/test_round_engine.py."""
    for kind in UPLOAD_ATTACKS:
        cfg = _attack_cfg(kind)
        cfg.data.num_clients = 8
        cfg.server.cohort_size = 4
        cfg.server.num_rounds = 8
        cfg.server.eval_every = 4
        cfg.run.fuse_rounds = 4
        cfg.validate()


def test_cli_style_override_builds_attacked_experiment():
    """`--set attack.kind=sign_flip` reaches the driver: compromised set
    constructed, engines built with the attack wired in."""
    cfg = resolve_config("mnist_fedavg_2", {
        "attack.kind": "sign_flip",
        "attack.fraction": 0.5,
        "data.synthetic_train_size": 64,
        "data.synthetic_test_size": 32,
        "run.out_dir": "",
    })
    exp = Experiment(cfg, echo=False)
    assert exp._attack_upload and len(exp.compromised) == 1


# ---------------------------------------------------------------------------
# driver integration: label_flip data path, metrics, provenance
# ---------------------------------------------------------------------------


def _tiny_cfg(tmp_path, **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.data.num_clients = 8
    cfg.server.cohort_size = 8
    cfg.server.num_rounds = 3
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


def test_label_flip_poisons_exactly_the_compromised_corpus(tmp_path):
    benign = Experiment(_tiny_cfg(tmp_path), echo=False)
    cfg = _tiny_cfg(tmp_path, **{"attack.kind": "label_flip",
                                 "attack.fraction": 0.25})
    atk = Experiment(cfg, echo=False)
    comp = set(int(c) for c in atk.compromised)
    assert len(comp) == 2
    for cid in range(8):
        rows = atk.fed.client_indices[cid]
        if cid in comp:
            np.testing.assert_array_equal(
                atk.fed.train_y[rows], 9 - benign.fed.train_y[rows]
            )
        else:
            np.testing.assert_array_equal(
                atk.fed.train_y[rows], benign.fed.train_y[rows]
            )
    # the eval corpus is never poisoned
    np.testing.assert_array_equal(atk.fed.test_y, benign.fed.test_y)


def test_attack_metrics_and_provenance_logged(tmp_path):
    cfg = _tiny_cfg(tmp_path, **{"attack.kind": "sign_flip",
                                 "attack.fraction": 0.25})
    exp = Experiment(cfg, echo=False)
    exp.fit()
    records = [
        json.loads(line)
        for line in open(f"{tmp_path}/{cfg.name}.metrics.jsonl")
    ]
    attack_events = [r for r in records if r.get("event") == "attack"]
    assert len(attack_events) == 1
    ev = attack_events[0]
    assert ev["kind"] == "sign_flip" and ev["n_compromised"] == 2
    assert sorted(ev["compromised"]) == [int(c) for c in exp.compromised]
    rounds = [r for r in records if "round" in r and "train_loss" in r]
    # full participation (cohort == N): both attackers in every round
    assert [r.get("byzantine_count") for r in rounds] == [2, 2, 2]


def test_dp_two_pass_warning_logged(tmp_path):
    cfg = _tiny_cfg(tmp_path)
    cfg.name = "two_pass_warn"
    cfg.dp.enabled = True
    cfg.dp.clipping = "two_pass"
    cfg.dp.microbatch_size = 8
    cfg.server.num_rounds = 1
    exp = Experiment(cfg, echo=False)
    exp.fit()
    records = [
        json.loads(line)
        for line in open(f"{tmp_path}/{cfg.name}.metrics.jsonl")
    ]
    warns = [r for r in records if r.get("warning") == "dp_two_pass_clipping"]
    assert len(warns) == 1 and "exact" in warns[0]["detail"]


# ---------------------------------------------------------------------------
# gossip: the poisoned-replica threat model
# ---------------------------------------------------------------------------


def test_gossip_replica_poisoning_spreads_to_neighbours(tmp_path):
    cfg = get_named_config("cifar10_gossip_16")
    cfg.apply_overrides({
        "data.num_clients": 8,
        "server.cohort_size": 8,
        "server.num_rounds": 2,
        "server.eval_every": 0,
        "model.name": "lenet5",
        "model.kwargs": {},
        "data.name": "mnist",
        "client.batch_size": 8,
        "data.synthetic_train_size": 128,
        "data.synthetic_test_size": 32,
        "data.max_examples_per_client": 16,
        "run.out_dir": str(tmp_path),
        "run.metrics_flush_every": 1,
        "attack.kind": "sign_flip",
        "attack.fraction": 0.25,
    })
    cfg.validate()
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    assert np.isfinite(float(exp.evaluate(state["params"])["eval_loss"]))
    records = [
        json.loads(line)
        for line in open(f"{tmp_path}/{cfg.name}.metrics.jsonl")
    ]
    rounds = [r for r in records if "byzantine_count" in r]
    assert rounds and all(r["byzantine_count"] == 2 for r in rounds)


# ---------------------------------------------------------------------------
# the headline e2e: the attack breaks FedAvg, the defenses hold
# ---------------------------------------------------------------------------


def _fit_acc(tmp_path, name, **over):
    cfg = _tiny_cfg(tmp_path, **over)
    cfg.name = name
    # 15 rounds: enough for the slow single-update-per-round krum
    # trajectory to saturate the easy synthetic task (measured: every
    # robust aggregator reaches 1.0 benign AND attacked by round 15,
    # while the attacked mean sits at chance)
    cfg.server.num_rounds = 15
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    return exp.evaluate(state["params"])["eval_acc"]


_SIGN_FLIP = {"attack.kind": "sign_flip", "attack.fraction": 0.25}
_CHANCE = 0.1 + 0.2  # chance + margin


@pytest.fixture(scope="module")
def undefended_accs(tmp_path_factory):
    """(benign, attacked) accuracy of the plain weighted mean: fitted
    once for the defenses' cases below."""
    out = tmp_path_factory.mktemp("undefended")
    return (_fit_acc(out, "benign_mean"),
            _fit_acc(out, "attacked_mean", **_SIGN_FLIP))


_DEFENSES = {
    "krum": {"server.aggregator": "krum", "server.krum_byzantine": 2},
    "median": {"server.aggregator": "median"},
    "trimmed_mean": {"server.aggregator": "trimmed_mean",
                     "server.trim_ratio": 0.25},
}


@pytest.mark.parametrize("label", sorted(_DEFENSES))
def test_sign_flip_breaks_fedavg_but_not_robust_aggregators(
        tmp_path, undefended_accs, label):
    """THE acceptance story: sign_flip at f=2 of cohort 8 drives the
    undefended weighted mean to chance while each robust aggregator
    under the identical attack stays within ITS OWN benign-run accuracy
    band (krum converges slower than the mean by construction — it
    applies one client's update per round — so each defense is held to
    its own benign baseline, not FedAvg's)."""
    agg_over = _DEFENSES[label]
    benign_acc, broken_acc = undefended_accs
    assert benign_acc > 0.75, benign_acc  # the task is learnable
    assert broken_acc <= _CHANCE, (
        f"weighted_mean survived sign_flip: {broken_acc}"
    )
    benign = _fit_acc(tmp_path, f"benign_{label}", **agg_over)
    acc = _fit_acc(tmp_path, f"attacked_{label}", **_SIGN_FLIP, **agg_over)
    assert acc >= benign - 0.15 and acc > 2 * _CHANCE, (
        f"{label} failed to defend: attacked acc {acc} vs its "
        f"benign {benign}"
    )
    # and the defense really was under the same fire FedAvg died to
    assert acc > broken_acc + 0.2, (label, acc, broken_acc)
