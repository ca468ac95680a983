"""The distributed-without-a-cluster test (SURVEY.md §4.3): the real
shard_map/psum round engine over a clients=8 CPU mesh must match the
sequential reference loop."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.config import ClientConfig, DPConfig, ServerConfig
from colearn_federated_learning_tpu.data.loader import RoundShape, make_round_indices
from colearn_federated_learning_tpu.models import build_model, init_params
from colearn_federated_learning_tpu.parallel.mesh import build_client_mesh, largest_lane_count
from colearn_federated_learning_tpu.parallel.round_engine import (
    make_sequential_round_fn,
    make_sharded_round_fn,
)
from colearn_federated_learning_tpu.server.aggregation import make_server_update_fn


class _Fed:
    """Minimal FederatedData stand-in for index building."""

    def __init__(self, client_indices):
        self.client_indices = client_indices


class _ConvNet(nn.Module):
    """Its second 3x3 kernel is 81 % of it: the megabatch block trainer
    runs it without a shared-weight phase (client/trainer.py), where
    LeNet-5 (4 %) keeps one."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(nn.Conv(8, (3, 3), strides=(2, 2))(x))
        x = nn.relu(nn.Conv(16, (3, 3), strides=(2, 2))(x))
        return nn.Dense(10)(x.mean(axis=(1, 2)))


def _setup(cohort=8, n=256, conv_model=False):
    model = _ConvNet() if conv_model else build_model("lenet5", num_classes=10)
    params = init_params(model, (28, 28, 1), seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    # heterogeneous client sizes
    splits = np.array_split(rng.permutation(n), cohort)
    fed = _Fed([s[: rng.integers(8, len(s) + 1)] for s in splits])
    shape = RoundShape(local_epochs=2, steps_per_epoch=4, batch_size=8, cap=32)
    idx, mask, n_ex = make_round_indices(fed, list(range(cohort)), shape, rng)
    return model, params, x, y, idx, mask, n_ex


@pytest.mark.parametrize("lanes", [8, 4, 1])
def test_sharded_matches_sequential(lanes):
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    _, server_update = make_server_update_fn(scfg)
    init, _ = make_server_update_fn(scfg)

    mesh = build_client_mesh(lanes)
    sharded = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, server_update,
        cohort_size=8, donate=False,
    )
    sequential = make_sequential_round_fn(model, ccfg, DPConfig(), "classify", server_update)

    opt_state = init(params)  # placeholder init fn returns opt state
    rng = jax.random.PRNGKey(42)
    p_sh, _, m_sh = sharded(params, opt_state, x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex), rng)
    p_sq, _, m_sq = sequential(params, opt_state, x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex), rng)

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        p_sh, p_sq,
    )
    np.testing.assert_allclose(m_sh.train_loss, m_sq.train_loss, rtol=1e-5)
    np.testing.assert_allclose(m_sh.examples, m_sq.examples, rtol=1e-6)


def test_dropout_zero_weight_removes_client():
    """A client with weight 0 must not influence the aggregate (exact)."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    mesh = build_client_mesh(8)
    sharded = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, server_update,
        cohort_size=8, donate=False,
    )
    rng = jax.random.PRNGKey(0)
    opt_state = init(params)

    n_dropped = n_ex.copy()
    n_dropped[3] = 0.0
    p_drop, _, _ = sharded(params, opt_state, x, y, jnp.asarray(idx), jnp.asarray(mask),
                           jnp.asarray(n_dropped), rng)

    # corrupt client 3's data entirely: must not change the result
    idx2 = idx.copy()
    idx2[3] = 0
    p_drop2, _, _ = sharded(params, opt_state, x, y, jnp.asarray(idx2), jnp.asarray(mask),
                            jnp.asarray(n_dropped), rng)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        p_drop, p_drop2,
    )


@pytest.mark.parametrize("width", [0, 2, 4])
def test_vmap_width_matches_scan(width):
    """vmapped-clients blocks must compute the same round as pure scan."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    mesh = build_client_mesh(2)
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(3))
    opt_state = init(params)
    scan_fn = make_sharded_round_fn(model, ccfg, DPConfig(), "classify", mesh,
                                    server_update, 8, donate=False,
                                    client_vmap_width=1)
    vmap_fn = make_sharded_round_fn(model, ccfg, DPConfig(), "classify", mesh,
                                    server_update, 8, donate=False,
                                    client_vmap_width=width)
    p_scan, _, m_scan = scan_fn(params, opt_state, *args)
    p_vmap, _, m_vmap = vmap_fn(params, opt_state, *args)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        p_scan, p_vmap,
    )
    np.testing.assert_allclose(m_scan.train_loss, m_vmap.train_loss, rtol=1e-5)


def test_dp_under_sharded_engine():
    """Regression: DP-SGD inside shard_map (scan-carry vma typing)."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1)
    dcfg = DPConfig(enabled=True, l2_clip=1.0, noise_multiplier=1.0,
                    microbatch_size=4)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    mesh = build_client_mesh(4)
    fn = make_sharded_round_fn(model, ccfg, dcfg, "classify", mesh,
                               server_update, 8, donate=False)
    p, _, m = fn(params, init(params), x, y, jnp.asarray(idx),
                 jnp.asarray(mask), jnp.asarray(n_ex), jax.random.PRNGKey(0))
    assert np.isfinite(float(m.train_loss))
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(p))


def test_clip_delta_norm_bounds_update():
    """With per-client clipping at C and the plain-mean server (lr=1),
    the global update is a convex combination of ≤C-norm deltas, so
    ‖w_new − w_old‖ ≤ C."""
    from colearn_federated_learning_tpu.utils import trees

    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.5)  # hot lr → big deltas
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    clip = 0.05
    fn = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", build_client_mesh(4),
        server_update, cohort_size=8, donate=False, clip_delta_norm=clip,
    )
    p, _, _ = fn(params, init(params), x, y, jnp.asarray(idx),
                 jnp.asarray(mask), jnp.asarray(n_ex), jax.random.PRNGKey(0))
    moved = float(jnp.sqrt(trees.tree_sq_norm(trees.tree_sub(p, params))))
    assert moved <= clip * 1.001, moved
    # and without clipping the same round moves much further
    fn0 = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", build_client_mesh(4),
        server_update, cohort_size=8, donate=False,
    )
    p0, _, _ = fn0(params, init(params), x, y, jnp.asarray(idx),
                   jnp.asarray(mask), jnp.asarray(n_ex), jax.random.PRNGKey(0))
    moved0 = float(jnp.sqrt(trees.tree_sq_norm(trees.tree_sub(p0, params))))
    assert moved0 > clip * 2, moved0


def test_clip_delta_sharded_matches_sequential():
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    kw = dict(clip_delta_norm=0.02)
    sharded = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", build_client_mesh(4),
        server_update, cohort_size=8, donate=False, **kw,
    )
    sequential = make_sequential_round_fn(
        model, ccfg, DPConfig(), "classify", server_update, **kw,
    )
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(42))
    p_sh, _, m_sh = sharded(params, init(params), *args)
    p_sq, _, m_sq = sequential(params, init(params), *args)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        p_sh, p_sq,
    )
    np.testing.assert_allclose(m_sh.train_loss, m_sq.train_loss, rtol=1e-5)


def test_largest_lane_count():
    assert largest_lane_count(16, 8) == 8
    assert largest_lane_count(12, 8) == 6
    assert largest_lane_count(11, 8) == 1
    assert largest_lane_count(7, 8) == 7


@pytest.mark.parametrize("batch_shards", [2, 4])
def test_batch_sharded_matches_sequential(batch_shards):
    """The clients×batch 2D mesh (intra-client batch parallelism for big
    silo models) must reproduce the sequential oracle exactly: psum of
    per-shard weighted grad sums / psummed count == full-batch mean."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)

    mesh = build_client_mesh(8 // batch_shards, batch_shards=batch_shards)
    sharded = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, server_update,
        cohort_size=8, donate=False,
    )
    sequential = make_sequential_round_fn(model, ccfg, DPConfig(), "classify", server_update)
    opt_state = init(params)
    rng = jax.random.PRNGKey(42)
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex), rng)
    p_sh, _, m_sh = sharded(params, opt_state, *args)
    p_sq, _, m_sq = sequential(params, opt_state, *args)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        p_sh, p_sq,
    )
    np.testing.assert_allclose(m_sh.train_loss, m_sq.train_loss, rtol=1e-5)
    np.testing.assert_allclose(m_sh.examples, m_sq.examples, rtol=1e-6)


def test_batch_sharded_dp_matches_unsharded():
    """DP under the 2D mesh: per-client noise keys are replicated over
    batch shards, so the mechanism must match the 1D-mesh result
    bit-close (one logical noise draw either way)."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1)
    dcfg = DPConfig(enabled=True, l2_clip=1.0, noise_multiplier=1.0,
                    microbatch_size=2)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(7))
    fn_1d = make_sharded_round_fn(model, ccfg, dcfg, "classify",
                                  build_client_mesh(4), server_update, 8,
                                  donate=False)
    fn_2d = make_sharded_round_fn(model, ccfg, dcfg, "classify",
                                  build_client_mesh(4, batch_shards=2),
                                  server_update, 8, donate=False)
    p1, _, m1 = fn_1d(params, init(params), *args)
    p2, _, m2 = fn_2d(params, init(params), *args)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6),
        p1, p2,
    )
    np.testing.assert_allclose(m1.train_loss, m2.train_loss, rtol=1e-5)


def test_batch_shards_must_divide_batch():
    model, params, *_ = _setup(cohort=8)
    ccfg = ClientConfig(batch_size=6, lr=0.1)
    scfg = ServerConfig(optimizer="mean", cohort_size=8)
    _, server_update = make_server_update_fn(scfg)
    with pytest.raises(ValueError, match="batch shards"):
        make_sharded_round_fn(model, ccfg, DPConfig(), "classify",
                              build_client_mesh(2, batch_shards=4),
                              server_update, 8, donate=False)


class TestFusedRounds:
    """run.fuse_rounds=F: F rounds as one XLA program (lax.scan over
    the round body with the unfused loop's EXACT per-round rngs)."""

    def _run(self, fuse, rounds=6, **over):
        from colearn_federated_learning_tpu.config import get_named_config
        from colearn_federated_learning_tpu.server.round_driver import (
            Experiment,
        )

        cfg = get_named_config("mnist_fedavg_2")
        cfg.data.num_clients = 8
        cfg.server.cohort_size = 4
        cfg.server.num_rounds = rounds
        cfg.server.eval_every = 0
        cfg.server.dropout_rate = 0.2
        cfg.run.out_dir = ""
        cfg.run.fuse_rounds = fuse
        cfg.data.synthetic_train_size = 256
        cfg.data.synthetic_test_size = 64
        for k, v in over.items():
            cfg.apply_overrides({k: v})
        cfg.validate()
        exp = Experiment(cfg, echo=False)
        state = exp.fit()
        return state, exp

    @pytest.mark.parametrize("fuse", [2, 3])
    def test_fused_equals_unfused_bitwise(self, fuse):
        a, _ = self._run(1)
        b, _ = self._run(fuse)
        assert int(a["round"]) == int(b["round"]) == 6
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            a["params"], b["params"],
        )

    # the generalized fused scan (r6): every robust aggregator, with
    # and without a live upload attack, must reproduce the unfused
    # loop exactly — the per-client delta stack stays private to the
    # scan body, the byzantine masks ride a stacked [fuse, K] input
    @pytest.mark.parametrize("aggregator", [
        "weighted_mean", "median", "trimmed_mean", "krum",
    ])
    @pytest.mark.parametrize("attack", ["", "sign_flip"])
    def test_fused_robust_and_attacked_parity(self, aggregator, attack):
        over = {"server.aggregator": aggregator}
        if attack:
            over.update({"attack.kind": attack, "attack.fraction": 0.25})
        a, _ = self._run(1, rounds=4, **over)
        b, _ = self._run(2, rounds=4, **over)
        assert int(a["round"]) == int(b["round"]) == 4
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            a["params"], b["params"],
        )

    def test_fused_error_feedback_carry_parity(self):
        """EF under fusion: the residual store rides the scan carry —
        params AND the post-run store must match the unfused loop."""
        over = {"server.compression": "qsgd",
                "server.error_feedback": True}
        a, _ = self._run(1, rounds=4, **over)
        b, _ = self._run(2, rounds=4, **over)
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            a["params"], b["params"],
        )
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            a["c_clients"], b["c_clients"],
        )

    def test_unaligned_resume_runs_unfused_catchup(self, tmp_path):
        """A checkpoint at a non-chunk-aligned round no longer errors:
        the driver runs unfused rounds to the next boundary (logging a
        fuse_unaligned_resume warning), re-enters the fused loop, and
        the final params match a straight unfused run bitwise."""
        from colearn_federated_learning_tpu.config import get_named_config
        from colearn_federated_learning_tpu.server.round_driver import (
            Experiment,
        )

        def cfg_for(rounds, resume, fuse, out, ckpt):
            cfg = get_named_config("mnist_fedavg_2")
            cfg.data.num_clients = 8
            cfg.server.cohort_size = 4
            cfg.server.num_rounds = rounds
            cfg.server.eval_every = 0
            cfg.server.checkpoint_every = ckpt
            cfg.run.out_dir = out
            cfg.run.resume = resume
            cfg.run.fuse_rounds = fuse
            cfg.run.metrics_flush_every = 1
            cfg.data.synthetic_train_size = 256
            cfg.data.synthetic_test_size = 64
            return cfg.validate()

        # 3 unfused rounds with per-round checkpoints: the latest
        # checkpoint (round 3) is NOT a fuse=2 chunk boundary
        Experiment(cfg_for(3, False, 1, str(tmp_path), 1), echo=False).fit()
        exp = Experiment(cfg_for(6, True, 2, str(tmp_path), 2), echo=False)
        resumed = exp.fit()
        assert int(resumed["round"]) == 6
        warns = [r for r in exp.logger.history
                 if r.get("warning") == "fuse_unaligned_resume"]
        assert len(warns) == 1 and "1 unfused catch-up" in warns[0]["detail"]
        # per-round metrics cover the catch-up round AND the fused tail
        rounds = [r["round"] for r in exp.logger.history
                  if "train_loss" in r]
        assert rounds == [4, 5, 6]
        straight = Experiment(
            cfg_for(6, False, 1, str(tmp_path / "straight"), 0), echo=False
        ).fit()
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            straight["params"], resumed["params"],
        )

    def test_fuse_smoke_robust_attack(self):
        """Tier-1 CPU smoke for the generalized fused path (fuse=2,
        robust aggregator + live attack): the fused program must build,
        run, and report per-round metrics — a collection-time or
        trace-time regression in the fused scan fails here fast."""
        state, exp = self._run(
            2, rounds=4,
            **{"server.aggregator": "median",
               "attack.kind": "sign_flip", "attack.fraction": 0.25},
        )
        assert int(state["round"]) == 4
        rounds = [r for r in exp.logger.history if "train_loss" in r]
        assert len(rounds) == 4
        assert all(np.isfinite(r["train_loss"]) for r in rounds)
        # byzantine_count is attributed per fused sub-round
        assert all("byzantine_count" in r for r in rounds)

    def test_per_round_metrics_preserved(self):
        _, exp = self._run(3)
        losses = [r["train_loss"] for r in exp.logger.history
                  if "train_loss" in r]
        assert len(losses) == 6  # one metrics record per ROUND, not chunk
        _, exp1 = self._run(1)
        losses1 = [r["train_loss"] for r in exp1.logger.history
                   if "train_loss" in r]
        np.testing.assert_allclose(losses, losses1, rtol=1e-6)

    def test_validation_rejections(self):
        from colearn_federated_learning_tpu.config import get_named_config

        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.fuse_rounds = 4
        cfg.server.num_rounds = 10  # 4 does not divide 10
        with pytest.raises(ValueError, match="divide num_rounds"):
            cfg.validate()
        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.fuse_rounds = 2
        cfg.server.num_rounds = 4
        cfg.server.eval_every = 3
        with pytest.raises(ValueError, match="eval_every"):
            cfg.validate()
        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.fuse_rounds = 2
        cfg.server.num_rounds = 4
        cfg.server.eval_every = 2
        cfg.algorithm = "scaffold"
        cfg.client.momentum = 0.0
        with pytest.raises(ValueError, match="fedavg/fedprox"):
            cfg.validate()
        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.fuse_rounds = 2
        cfg.server.num_rounds = 4
        cfg.server.eval_every = 2
        cfg.server.secure_aggregation = True
        cfg.server.clip_delta_norm = 1.0
        with pytest.raises(ValueError, match="secure_aggregation"):
            cfg.validate()
        # the r6 generalization: robust aggregators, upload attacks and
        # error feedback VALIDATE with fuse_rounds > 1 now
        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.fuse_rounds = 2
        cfg.server.num_rounds = 4
        cfg.server.eval_every = 2
        cfg.server.aggregator = "median"
        cfg.attack.kind = "sign_flip"
        cfg.validate()
        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.fuse_rounds = 2
        cfg.server.num_rounds = 4
        cfg.server.eval_every = 2
        cfg.server.compression = "qsgd"
        cfg.server.error_feedback = True
        cfg.validate()


class TestBF16ComputeParity:
    """The bf16-compute/f32-master headline policy (r7, ROADMAP item 2
    lever a): run.compute_dtype=bfloat16 + run.local_param_dtype=
    bfloat16 with f32 server params. Local matmuls/activations and the
    per-step SGD run bf16 end-to-end (make_loss_fn normalizes inputs
    straight into the model's compute dtype); the delta upcast, the
    aggregation psum, and the server trajectory stay f32. Parity
    contract, documented here and in docs/DESIGN.md: fused↔unfused is
    BITWISE (same program, scanned); sharded↔sequential holds at
    atol 1e-4 / rtol 1e-3 — the engines accumulate the same f32 deltas
    in different orders, and each reassociation sits next to
    bf16-rounded values (measured 0.0 on this config/backend; the band
    leaves room for lane-count and backend reassociation)."""

    def _run(self, engine="sharded", fuse=1, **over):
        from colearn_federated_learning_tpu.config import get_named_config
        from colearn_federated_learning_tpu.server.round_driver import (
            Experiment,
        )

        cfg = get_named_config("mnist_fedavg_2")
        cfg.data.num_clients = 8
        cfg.server.cohort_size = 4
        cfg.server.num_rounds = 4
        cfg.server.eval_every = 0
        cfg.run.out_dir = ""
        cfg.run.engine = engine
        cfg.run.fuse_rounds = fuse
        cfg.run.compute_dtype = "bfloat16"
        cfg.run.local_param_dtype = "bfloat16"
        cfg.data.synthetic_train_size = 256
        cfg.data.synthetic_test_size = 64
        cfg.data.max_examples_per_client = 32
        for k, v in over.items():
            cfg.apply_overrides({k: v})
        cfg.validate()
        exp = Experiment(cfg, echo=False)
        return exp.fit()

    def test_master_params_stay_f32(self):
        state = self._run()
        for leaf in jax.tree.leaves(state["params"]):
            assert leaf.dtype == jnp.float32

    def test_fused_equals_unfused_bitwise_under_bf16(self):
        a = self._run(fuse=1)
        b = self._run(fuse=2)
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)
            ),
            a["params"], b["params"],
        )

    def test_sharded_matches_sequential_under_bf16(self):
        sh = self._run("sharded")
        sq = self._run("sequential")
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=1e-4, rtol=1e-3
            ),
            sh["params"], sq["params"],
        )


class TestCohortLayout:
    """run.cohort_layout='megabatch' (r12, ROADMAP item 1): collapse
    the cohort axis into the GEMM batch — a lane's whole client chunk
    trains as ONE fused block (shared-weight first step at
    [K_local·batch] GEMM rows, lane-local vmap after the per-client
    params diverge) while every wire shape is untouched. The layout is
    a pure performance knob, so the contract is PARITY — the documented
    tolerance, per docs/DESIGN.md "Cohort layout & megabatching":
    megabatch ≡ spatial at GEMM-reassociation tolerance (atol 1e-6 /
    rtol 2e-5; measured ≤ 2 ulp on this backend). Bitwise is NOT
    promised across layouts because changing the contraction shapes is
    the layout's entire mechanism — XLA fuses each program differently
    (the weighted-mean psum program happens to land bitwise here; the
    krum/EF programs differ in the last ulp). Same-layout comparisons
    (fused↔unfused via the driver, resume crossings) stay bitwise —
    those run the same per-round programs."""

    def _pair(self, cohort=8, lanes=2, fuse=1, conv_model=False,
              client=None, **kw):
        """(spatial_fn, megabatch_fn) engine twins plus shared inputs."""
        model, params, x, y, idx, mask, n_ex = _setup(
            cohort=cohort, conv_model=conv_model)
        ccfg = ClientConfig(**{"local_epochs": 2, "batch_size": 8,
                               "lr": 0.1, "momentum": 0.9, **(client or {})})
        scfg = ServerConfig(optimizer="mean", server_lr=1.0,
                            cohort_size=cohort)
        init, server_update = make_server_update_fn(scfg)
        mesh = build_client_mesh(lanes)
        fns = {
            layout: make_sharded_round_fn(
                model, ccfg, DPConfig(), "classify", mesh, server_update,
                cohort_size=cohort, donate=False, fuse_rounds=fuse,
                cohort_layout=layout, **kw,
            )
            for layout in ("spatial", "megabatch")
        }
        args = (x, y, jnp.asarray(idx), jnp.asarray(mask),
                jnp.asarray(n_ex))
        return model, params, init(params), args, fns

    @staticmethod
    def _assert_bitwise(a, b):
        jax.tree.map(
            lambda p, q: np.testing.assert_array_equal(
                np.asarray(p), np.asarray(q)
            ),
            a, b,
        )

    @staticmethod
    def _assert_layout_parity(a, b):
        # the documented cross-layout tolerance: the megabatch program
        # contracts different GEMM shapes, so XLA's reassociation can
        # move the last ulp (observed max 6e-8 on CPU)
        jax.tree.map(
            lambda p, q: np.testing.assert_allclose(
                np.asarray(p), np.asarray(q), atol=1e-6, rtol=2e-5
            ),
            a, b,
        )

    @pytest.mark.parametrize("aggregator,attack", [
        ("weighted_mean", ""),
        ("weighted_mean", "sign_flip"),
        ("krum", ""),
        ("krum", "sign_flip"),
    ])
    def test_megabatch_matches_spatial(self, aggregator, attack):
        kw = {"aggregator": aggregator}
        if aggregator == "krum":
            kw["byzantine_f"] = 1
        if attack:
            kw["attack"] = attack
        _, params, opt_state, args, fns = self._pair(**kw)
        rng = jax.random.PRNGKey(7)
        extra = ()
        if attack:
            byz = np.zeros(8, np.float32)
            byz[3] = 1.0
            extra = (jnp.asarray(byz),)
        p_sp, _, m_sp = fns["spatial"](params, opt_state, *args, rng, *extra)
        p_mb, _, m_mb = fns["megabatch"](params, opt_state, *args, rng, *extra)
        self._assert_layout_parity(p_sp, p_mb)
        np.testing.assert_allclose(
            float(m_sp.train_loss), float(m_mb.train_loss), rtol=1e-5
        )
        np.testing.assert_array_equal(
            np.asarray(m_sp.examples), np.asarray(m_mb.examples)
        )

    def test_megabatch_fused_matches_spatial_fused(self):
        """fuse_rounds=2 × megabatch: the fused scan body trains the
        megabatched block per sub-round; parity against the fused
        spatial twin (and, transitively via TestFusedRounds, against
        the unfused loop) stays bitwise."""
        _, params, opt_state, args, fns = self._pair(
            fuse=2, aggregator="krum", byzantine_f=1, attack="sign_flip",
        )
        x, y, idx, mask, n_ex = args
        f_idx = jnp.stack([idx, idx])
        f_mask = jnp.stack([mask, mask])
        f_nex = jnp.stack([n_ex, n_ex])
        rngs = jnp.stack([jax.random.PRNGKey(11), jax.random.PRNGKey(12)])
        byz = np.zeros((2, 8), np.float32)
        byz[:, 3] = 1.0
        f_byz = jnp.asarray(byz)
        p_sp, _, m_sp = fns["spatial"](
            params, opt_state, x, y, f_idx, f_mask, f_nex, rngs, f_byz
        )
        p_mb, _, m_mb = fns["megabatch"](
            params, opt_state, x, y, f_idx, f_mask, f_nex, rngs, f_byz
        )
        self._assert_layout_parity(p_sp, p_mb)
        assert m_mb.train_loss.shape == (2,)
        np.testing.assert_allclose(
            np.asarray(m_sp.train_loss), np.asarray(m_mb.train_loss),
            rtol=1e-5,
        )

    @pytest.mark.parametrize("fuse", [1, 2])
    def test_megabatch_error_feedback_matches_spatial(self, fuse):
        """EF × megabatch: training is megabatched, the compression
        memory (upload C(Δ+e), residual scatter-back) is untouched —
        params AND the post-round residual store must match the spatial
        twin bitwise, at fuse 1 and 2."""
        _, params, opt_state, args, fns = self._pair(
            fuse=fuse, compression="qsgd", error_feedback=True,
            num_clients=16,
        )
        x, y, idx, mask, n_ex = args
        store = jax.tree.map(
            lambda p: jnp.zeros((16,) + p.shape, jnp.float32), params
        )
        cohort = jnp.arange(8, dtype=jnp.int32)
        if fuse == 1:
            ins = (x, y, idx, mask, n_ex, jax.random.PRNGKey(5), store,
                   cohort)
            p_sp, _, e_sp, _ = fns["spatial"](params, opt_state, *ins)
            p_mb, _, e_mb, _ = fns["megabatch"](params, opt_state, *ins)
        else:
            rngs = jnp.stack(
                [jax.random.PRNGKey(5), jax.random.PRNGKey(6)]
            )
            ins = (x, y, jnp.stack([idx, idx]), jnp.stack([mask, mask]),
                   jnp.stack([n_ex, n_ex]), rngs, store,
                   jnp.stack([cohort, cohort]))
            p_sp, _, e_sp, _ = fns["spatial"](params, opt_state, *ins)
            p_mb, _, e_mb, _ = fns["megabatch"](params, opt_state, *ins)
        self._assert_layout_parity(p_sp, p_mb)
        self._assert_layout_parity(e_sp, e_mb)

    def test_megabatch_matches_sequential(self):
        """The oracle crossing: the megabatched sharded engine against
        the layout-free python-loop reference, at the engines'
        established tolerance."""
        model, params, opt_state, args, fns = self._pair()
        ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1,
                            momentum=0.9)
        scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
        _, server_update = make_server_update_fn(scfg)
        seq = make_sequential_round_fn(
            model, ccfg, DPConfig(), "classify", server_update,
        )
        rng = jax.random.PRNGKey(21)
        p_mb, _, m_mb = fns["megabatch"](params, opt_state, *args, rng)
        p_sq, _, m_sq = seq(params, opt_state, *args, rng)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
            ),
            p_mb, p_sq,
        )
        np.testing.assert_allclose(
            float(m_mb.train_loss), float(m_sq.train_loss), rtol=1e-5
        )

    @pytest.mark.parametrize("fuse,client", [
        (1, {}), (2, {}), (1, {"optimizer": "adamw", "lr": 1e-3}),
    ], ids=["sgd-fuse1", "sgd-fuse2", "adamw-fuse1"])
    def test_conv_dominated_block_matches_spatial_and_sequential(
            self, fuse, client):
        """A model of windowed convolutions trains its block without the
        shared-weight phase: inside the lanes' shard_map (the loop's
        carry, adam's fresh step count included, must be device-varying
        going in) and the fused scan it lands on the spatial twin, and
        at fuse 1 on the sequential engine's megabatch block."""
        from colearn_federated_learning_tpu.client.trainer import (
            shared_weight_phase,
        )

        model, params, opt_state, args, fns = self._pair(
            fuse=fuse, conv_model=True, client=client)
        assert not shared_weight_phase(params)
        x, y, idx, mask, n_ex = args
        if fuse == 1:
            ins = (x, y, idx, mask, n_ex, jax.random.PRNGKey(9))
        else:
            ins = (x, y, jnp.stack([idx, idx]), jnp.stack([mask, mask]),
                   jnp.stack([n_ex, n_ex]),
                   jnp.stack([jax.random.PRNGKey(9), jax.random.PRNGKey(10)]))
        p_sp, _, m_sp = fns["spatial"](params, opt_state, *ins)
        p_mb, _, m_mb = fns["megabatch"](params, opt_state, *ins)
        self._assert_layout_parity(p_sp, p_mb)
        np.testing.assert_allclose(
            np.asarray(m_sp.train_loss), np.asarray(m_mb.train_loss),
            rtol=1e-5,
        )
        if fuse == 1:
            ccfg = ClientConfig(**{"local_epochs": 2, "batch_size": 8,
                                   "lr": 0.1, "momentum": 0.9, **client})
            scfg = ServerConfig(optimizer="mean", server_lr=1.0,
                                cohort_size=8)
            seq = make_sequential_round_fn(
                model, ccfg, DPConfig(), "classify",
                make_server_update_fn(scfg)[1],
            )
            p_sq, _, _ = seq(params, opt_state, *ins)
            self._assert_layout_parity(p_sq, p_mb)

    def test_unaligned_resume_crossing(self, tmp_path):
        """A megabatch run resumed at a NON-chunk-aligned round (the
        fuse=1 catch-up twin is built with the same layout) must land
        bitwise on the straight megabatch run — the layout composes
        with the catch-up path, not just the steady-state loop."""
        from colearn_federated_learning_tpu.config import get_named_config
        from colearn_federated_learning_tpu.server.round_driver import (
            Experiment,
        )

        def cfg_for(rounds, resume, fuse, out, ckpt):
            cfg = get_named_config("mnist_fedavg_2")
            cfg.data.num_clients = 8
            cfg.server.cohort_size = 4
            cfg.server.num_rounds = rounds
            cfg.server.eval_every = 0
            cfg.server.checkpoint_every = ckpt
            cfg.run.out_dir = out
            cfg.run.resume = resume
            cfg.run.fuse_rounds = fuse
            cfg.run.cohort_layout = "megabatch"
            cfg.run.metrics_flush_every = 1
            cfg.data.synthetic_train_size = 256
            cfg.data.synthetic_test_size = 64
            return cfg.validate()

        Experiment(cfg_for(3, False, 1, str(tmp_path), 1), echo=False).fit()
        exp = Experiment(cfg_for(6, True, 2, str(tmp_path), 2), echo=False)
        resumed = exp.fit()
        assert int(resumed["round"]) == 6
        warns = [r for r in exp.logger.history
                 if r.get("warning") == "fuse_unaligned_resume"]
        assert len(warns) == 1
        straight = Experiment(
            cfg_for(6, False, 1, str(tmp_path / "straight"), 0), echo=False
        ).fit()
        self._assert_bitwise(straight["params"], resumed["params"])

    def test_validation_and_engine_rejections(self):
        from colearn_federated_learning_tpu.config import get_named_config

        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.cohort_layout = "megablotch"
        with pytest.raises(ValueError, match="cohort_layout"):
            cfg.validate()
        for algo in ("scaffold", "feddyn", "gossip", "fedbuff"):
            cfg = get_named_config("mnist_fedavg_2")
            cfg.run.cohort_layout = "megabatch"
            cfg.algorithm = algo
            if algo == "scaffold":
                cfg.client.momentum = 0.0
            with pytest.raises(ValueError, match="megabatch"):
                cfg.validate()
        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.cohort_layout = "megabatch"
        cfg.run.batch_shards = 2
        with pytest.raises(ValueError, match="batch_shards"):
            cfg.validate()
        cfg = get_named_config("mnist_fedavg_2")
        cfg.run.cohort_layout = "megabatch"
        cfg.run.client_vmap_width = 2
        with pytest.raises(ValueError, match="client_vmap_width"):
            cfg.validate()
        # widths 0 and 1 both mean "the layout decides"
        for w in (0, 1):
            cfg = get_named_config("mnist_fedavg_2")
            cfg.run.cohort_layout = "megabatch"
            cfg.run.client_vmap_width = w
            cfg.validate()
        # megabatch × batch-sharded mesh is rejected at construction
        model = build_model("lenet5", num_classes=10)
        ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1)
        scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=2)
        _, server_update = make_server_update_fn(scfg)
        mesh2 = build_client_mesh(2, batch_shards=2)
        with pytest.raises(ValueError, match="batch-sharded"):
            make_sharded_round_fn(
                model, ccfg, DPConfig(), "classify", mesh2, server_update,
                cohort_size=2, donate=False, cohort_layout="megabatch",
            )
