"""FedDyn (Acar et al. 2021): first-round identities, engine parity,
the h == mean(gᵢ) invariant end-to-end, and config validation."""

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
from colearn_federated_learning_tpu.parallel.mesh import build_client_mesh
from colearn_federated_learning_tpu.parallel.round_engine import (
    make_sequential_round_fn,
    make_sharded_round_fn,
)
from colearn_federated_learning_tpu.server.aggregation import make_server_update_fn
from colearn_federated_learning_tpu.server.round_driver import Experiment

ALPHA = 0.1


class _Fed:
    def __init__(self, ci):
        self.client_indices = ci


def _setup(cohort=8, n=256):
    model = build_model("lenet5", num_classes=10)
    params = init_params(model, (28, 28, 1), seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    splits = np.array_split(rng.permutation(n), cohort)
    fed = _Fed([s[: rng.integers(8, len(s) + 1)] for s in splits])
    shape = RoundShape(local_epochs=2, steps_per_epoch=4, batch_size=8, cap=32)
    idx, mask, n_ex = make_round_indices(fed, list(range(cohort)), shape, rng)
    return model, params, x, y, idx, mask, n_ex


def _zero_state(params, cohort):
    h = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    g = jax.tree.map(lambda p: jnp.zeros((cohort,) + p.shape, jnp.float32), params)
    return h, g


def test_first_round_identities():
    """From zero state: gᵢ⁺ = −α·Δᵢ, h⁺ = −α·(1/N)ΣΔᵢ, and
    w⁺ = w₀ + mean(Δ) − h⁺/α — all recoverable from the outputs."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=4)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=4)
    init, server_update = make_server_update_fn(scfg)
    fn = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", build_client_mesh(4),
        server_update, cohort_size=4, donate=False, agg="uniform",
        num_clients=8, feddyn_alpha=ALPHA,
    )
    h0, g0 = _zero_state(params, 4)
    p1, _, h1, store1, m = fn(
        params, init(params), x, y, jnp.asarray(idx), jnp.asarray(mask),
        jnp.asarray(n_ex), jax.random.PRNGKey(0), h0, g0,
        jnp.arange(4, dtype=jnp.int32),
    )
    # recover per-client deltas from g₁ = −α·Δ and check server math
    deltas = jax.tree.map(lambda g: -np.asarray(g)[:4] / ALPHA, store1)
    h_want = jax.tree.map(lambda d: -ALPHA * d.sum(0) / 8.0, deltas)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, np.asarray(b), rtol=1e-4,
                                                atol=1e-7),
        h_want, h1,
    )
    p_want = jax.tree.map(
        lambda p, d, h: np.asarray(p) + d.mean(0) - np.asarray(h) / ALPHA,
        params, deltas, h1,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, np.asarray(b), rtol=2e-4,
                                                atol=1e-6),
        p_want, p1,
    )
    # the correction term actually moved the params beyond plain FedAvg:
    # h/α = mean over ALL N of deltas ≠ 0
    assert float(sum(np.abs(np.asarray(l)).sum()
                     for l in jax.tree.leaves(h1))) > 0


@pytest.mark.parametrize("lanes", [8, 4, 1])
def test_feddyn_sharded_matches_sequential(lanes):
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    kw = dict(agg="uniform", num_clients=16, feddyn_alpha=ALPHA)
    sharded = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", build_client_mesh(lanes),
        server_update, cohort_size=8, donate=False, **kw,
    )
    sequential = make_sequential_round_fn(
        model, ccfg, DPConfig(), "classify", server_update, **kw,
    )
    rngs = np.random.default_rng(3)
    h0 = jax.tree.map(
        lambda p: jnp.asarray(0.01 * rngs.normal(size=p.shape).astype(np.float32)),
        params,
    )
    # full 16-client store for the sharded engine; the oracle gets the
    # cohort rows (clients 8..15 — exercises the in-program gather)
    store0 = jax.tree.map(
        lambda p: jnp.asarray(
            0.01 * rngs.normal(size=(16,) + p.shape).astype(np.float32)
        ),
        params,
    )
    cohort = np.arange(8, 16, dtype=np.int32)
    g0 = jax.tree.map(lambda a: a[jnp.asarray(cohort)], store0)
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(42))
    p_sh, _, h_sh, store_sh, m_sh = sharded(
        params, init(params), *args, h0, store0, jnp.asarray(cohort)
    )
    p_sq, _, h_sq, g_sq, m_sq = sequential(params, init(params), *args, h0, g0)
    g_sh = jax.tree.map(lambda a: np.asarray(a)[cohort], store_sh)
    for got, want in ((p_sh, p_sq), (h_sh, h_sq), (g_sh, g_sq)):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5),
            got, want,
        )
    np.testing.assert_allclose(m_sh.train_loss, m_sq.train_loss, rtol=1e-5)


def _feddyn_cfg(tmp_path, rounds=4):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.algorithm = "feddyn"
    cfg.data.num_clients = 4
    cfg.server.cohort_size = 2
    cfg.server.feddyn_alpha = ALPHA
    cfg.server.num_rounds = rounds
    cfg.server.eval_every = 0
    cfg.run.out_dir = str(tmp_path)
    cfg.data.synthetic_train_size = 256
    cfg.data.synthetic_test_size = 64
    return cfg


def test_feddyn_e2e_h_mean_invariant(tmp_path):
    """h and gᵢ accumulate the same Δg stream, so h == mean(gᵢ) exactly
    (both start 0) — partial participation included."""
    # 6 rounds: 4 left the accuracy sitting ON the 0.5 threshold (an XLA
    # version bump flipped it to 0.44); 6 clears it with real margin
    cfg = _feddyn_cfg(tmp_path, rounds=6)
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    assert exp.feddyn and exp.stateful
    n = cfg.data.num_clients  # ignore lane-pad rows (always zero)
    g_mean = jax.tree.map(
        lambda a: np.asarray(a)[:n].mean(0), state["c_clients"]
    )
    jax.tree.map(
        lambda h, gm: np.testing.assert_allclose(
            np.asarray(h), np.asarray(gm), rtol=1e-4, atol=1e-6
        ),
        state["c_global"], g_mean,
    )
    metrics = exp.evaluate(state["params"])
    assert np.isfinite(metrics["eval_loss"])
    assert metrics["eval_acc"] > 0.5, metrics


def test_feddyn_config_validation():
    cfg = _feddyn_cfg("unused")
    cfg.client.prox_mu = 0.01
    with pytest.raises(ValueError, match="prox_mu"):
        cfg.validate()
    cfg = _feddyn_cfg("unused")
    cfg.dp.enabled = True
    with pytest.raises(ValueError, match="dp"):
        cfg.validate()
    cfg = _feddyn_cfg("unused")
    cfg.server.optimizer = "fedadam"
    with pytest.raises(ValueError, match="server update"):
        cfg.validate()
    cfg = _feddyn_cfg("unused")
    cfg.server.compression = "qsgd"
    with pytest.raises(ValueError, match="compression"):
        cfg.validate()
    cfg = _feddyn_cfg("unused")
    cfg.server.server_lr = 0.5
    with pytest.raises(ValueError, match="server_lr"):
        cfg.validate()
    cfg = _feddyn_cfg("unused")
    cfg.run.param_dtype = "bfloat16"
    with pytest.raises(ValueError, match="f32 local"):
        cfg.validate()
