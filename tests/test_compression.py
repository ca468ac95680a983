"""Client-update compression: top-k semantics, QSGD unbiasedness,
engine parity, width-invariance, and the e2e config surface."""

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
from colearn_federated_learning_tpu.ops.compression import make_compressor
from colearn_federated_learning_tpu.parallel.mesh import build_client_mesh
from colearn_federated_learning_tpu.parallel.round_engine import (
    make_sequential_round_fn,
    make_sharded_round_fn,
)
from colearn_federated_learning_tpu.server.aggregation import make_server_update_fn
from colearn_federated_learning_tpu.server.round_driver import Experiment


def test_topk_keeps_largest_magnitudes():
    d = {"w": jnp.asarray([[0.1, -5.0, 0.2, 3.0, -0.05, 0.4]], jnp.float32)}
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    out = make_compressor("topk", topk_ratio=1 / 3)(d, keys)
    np.testing.assert_allclose(
        np.asarray(out["w"]), [[0.0, -5.0, 0.0, 3.0, 0.0, 0.0]]
    )


def test_topk_ratio_one_is_identity():
    rng = np.random.default_rng(0)
    d = {"w": jnp.asarray(rng.normal(size=(3, 17)).astype(np.float32))}
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    out = make_compressor("topk", topk_ratio=1.0)(d, keys)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(d["w"]))


def test_qsgd_unbiased():
    """E[qsgd(x)] = x — the Alistarh et al. 2017 property."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(1, 64)).astype(np.float32)
    comp = make_compressor("qsgd", qsgd_levels=4)  # coarse → visible noise
    # 2,000 seeds through one compiled program
    draws = jax.jit(jax.vmap(lambda seed: comp(
        {"w": jnp.asarray(x)},
        jax.random.split(jax.random.PRNGKey(seed), 1))["w"]))(jnp.arange(2000))
    mean = np.asarray(draws).mean(0)
    # per-coordinate dither std ≈ ‖x‖/s; the empirical mean over 2000
    # draws must sit well inside 5 standard errors
    norm = np.linalg.norm(x)
    tol = 5 * (norm / 4) / np.sqrt(2000)
    np.testing.assert_allclose(mean, x, atol=tol)


def test_qsgd_preserves_sign_and_zero():
    x = jnp.asarray([[1.5, -2.0, 0.0, 0.25]], jnp.float32)
    comp = make_compressor("qsgd", qsgd_levels=8)
    out = np.asarray(comp({"w": x}, jax.random.split(jax.random.PRNGKey(3), 1))["w"])
    assert out[0, 2] == 0.0
    assert out[0, 0] >= 0.0 and out[0, 1] <= 0.0


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
    shape = RoundShape(local_epochs=2, steps_per_epoch=4, batch_size=8, cap=32)
    idx, mask, n_ex = make_round_indices(fed, list(range(cohort)), shape, rng)
    return model, params, x, y, idx, mask, n_ex


@pytest.mark.parametrize("kind", ["topk", "qsgd"])
def test_compressed_sharded_matches_sequential(kind):
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.9)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    kw = dict(compression=kind, topk_ratio=0.25, qsgd_levels=16)
    sharded = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", build_client_mesh(4),
        server_update, cohort_size=8, donate=False, client_vmap_width=2, **kw,
    )
    sequential = make_sequential_round_fn(
        model, ccfg, DPConfig(), "classify", server_update, **kw,
    )
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(42))
    p_sh, _, m_sh = sharded(params, init(params), *args)
    p_sq, _, m_sq = sequential(params, init(params), *args)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6),
        p_sh, p_sq,
    )
    np.testing.assert_allclose(m_sh.train_loss, m_sq.train_loss, rtol=1e-5)


def test_compression_composes_with_robust_aggregation():
    """qsgd-compressed (dense) deltas can still be median-aggregated —
    the block emits compressed deltas, robust stats consume them. (The
    sparse topk × robust pairing is rejected at config level: a majority
    of exact zeros per coordinate would zero the median.)"""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, server_update = make_server_update_fn(scfg)
    fn = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", build_client_mesh(4),
        server_update, cohort_size=8, donate=False,
        aggregator="median", compression="qsgd", qsgd_levels=16,
    )
    p, _, m = fn(params, init(params), x, y, jnp.asarray(idx),
                 jnp.asarray(mask), jnp.asarray(n_ex), jax.random.PRNGKey(0))
    assert np.isfinite(float(m.train_loss))
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(p))


def test_compression_e2e_trains(tmp_path):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.compression = "topk"
    cfg.server.compression_topk_ratio = 0.25
    cfg.server.num_rounds = 8
    cfg.server.eval_every = 0
    cfg.run.out_dir = str(tmp_path)
    cfg.data.synthetic_train_size = 256
    cfg.data.synthetic_test_size = 64
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    metrics = exp.evaluate(state["params"])
    assert metrics["eval_acc"] > 0.5, metrics


def test_compression_config_validation():
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.compression = "gzip"
    with pytest.raises(ValueError, match="compression"):
        cfg.validate()
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.compression_topk_ratio = 0.0
    with pytest.raises(ValueError, match="topk_ratio"):
        cfg.validate()
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.compression = "topk"
    cfg.server.aggregator = "median"
    with pytest.raises(ValueError, match="sparse"):
        cfg.validate()


class TestDownlink:
    """Downlink broadcast quantization (ops/compression.downlink_quantize
    + server.downlink_compression)."""

    def test_unbiased_and_norm_preserving_shape(self):
        import jax

        key = jax.random.PRNGKey(0)
        p = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)),
                              jnp.float32)}
        from colearn_federated_learning_tpu.ops.compression import (
            downlink_quantize,
        )

        # unbiasedness: average over many dither draws ≈ the original
        acc = jnp.zeros_like(p["w"])
        n = 200
        for i in range(n):
            acc = acc + downlink_quantize(
                p, jax.random.fold_in(key, i), levels=8
            )["w"]
        err = np.abs(np.asarray(acc / n - p["w"])).mean()
        # dither std per coord ≈ ‖p‖/levels; mean-of-200 shrinks by √200
        bound = 3 * float(jnp.linalg.norm(p["w"])) / 8 / np.sqrt(n)
        assert err < bound, (err, bound)
        # identical key ⇒ identical broadcast (it is ONE message)
        a = downlink_quantize(p, key, levels=8)["w"]
        b = downlink_quantize(p, key, levels=8)["w"]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_engine_parity_with_downlink(self):
        import jax

        from colearn_federated_learning_tpu.config import (
            DPConfig,
        )
        from colearn_federated_learning_tpu.parallel.mesh import (
            build_client_mesh,
        )
        from colearn_federated_learning_tpu.parallel.round_engine import (
            make_sequential_round_fn,
            make_sharded_round_fn,
        )
        from tests.test_secagg import _setup

        (model, params, ccfg, server_init, server_update, tx, ty, idx, mask,
         n_ex) = _setup()
        kw = dict(downlink="qsgd", downlink_levels=64)
        mesh = build_client_mesh(8)
        sharded = make_sharded_round_fn(
            model, ccfg, DPConfig(), "classify", mesh, server_update,
            cohort_size=8, donate=False, **kw,
        )
        seq = make_sequential_round_fn(
            model, ccfg, DPConfig(), "classify", server_update, **kw,
        )
        rng = jax.random.PRNGKey(21)
        p_sh, _, m_sh = sharded(
            params, server_init(params), tx, ty, idx, mask, n_ex, rng
        )
        p_sq, _, m_sq = seq(
            params, server_init(params), tx, ty, idx, mask, n_ex, rng
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-6
            ),
            p_sh, p_sq,
        )
        np.testing.assert_allclose(
            float(m_sh.train_loss), float(m_sq.train_loss), rtol=1e-5
        )

    def test_e2e_converges_under_downlink_compression(self, tmp_path):
        from colearn_federated_learning_tpu.config import get_named_config
        from colearn_federated_learning_tpu.server.round_driver import (
            Experiment,
        )

        cfg = get_named_config("mnist_fedavg_2")
        cfg.server.downlink_compression = "qsgd"
        cfg.server.downlink_qsgd_levels = 256
        cfg.server.num_rounds = 6
        cfg.server.eval_every = 0
        cfg.run.out_dir = str(tmp_path)
        cfg.data.synthetic_train_size = 512
        cfg.data.synthetic_test_size = 256
        exp = Experiment(cfg.validate(), echo=False)
        state = exp.fit()
        metrics = exp.evaluate(state["params"])
        assert metrics["eval_acc"] > 0.9, metrics

    def test_validation_rejects_stateful(self):
        import pytest as _pytest

        from colearn_federated_learning_tpu.config import get_named_config

        cfg = get_named_config("mnist_fedavg_2")
        cfg.algorithm = "scaffold"
        cfg.client.momentum = 0.0
        cfg.server.downlink_compression = "qsgd"
        with _pytest.raises(ValueError):
            cfg.validate()


class TestTopkSampledThreshold:
    """The sampled-quantile threshold for big leaves (> _TOPK_SAMPLE
    coords): selected count within ±10% of k, invariant to client
    blocking, and identical to exact when forced."""

    def test_selected_count_within_band(self):
        from colearn_federated_learning_tpu.ops.compression import _TOPK_SAMPLE

        n = 1 << 20  # 1M coords: well past the sampling cutoff
        assert n > _TOPK_SAMPLE
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        d = jax.random.normal(jax.random.PRNGKey(7), (2, n), jnp.float32)
        for ratio in (0.1, 0.01):
            comp = make_compressor("topk", topk_ratio=ratio)
            out = comp({"w": d}, keys)["w"]
            k = round(ratio * n)
            nnz = np.count_nonzero(np.asarray(out), axis=1)
            for c in range(2):
                assert abs(nnz[c] - k) <= 0.10 * k, (ratio, c, nnz[c], k)
            # kept coordinates are a superset-by-magnitude selection:
            # every kept |value| >= every dropped |value|'s threshold
            mag = np.abs(np.asarray(d))
            outm = np.abs(np.asarray(out))
            for c in range(2):
                kept_min = outm[c][outm[c] > 0].min()
                dropped_max = mag[c][np.asarray(out)[c] == 0].max()
                assert kept_min >= dropped_max

    def test_blocking_invariance(self):
        """Per-client keys make the threshold independent of how clients
        are blocked into vmap widths (the same invariance qsgd pins)."""
        n = (1 << 17) + 13
        keys = jax.random.split(jax.random.PRNGKey(3), 4)
        d = jax.random.normal(jax.random.PRNGKey(11), (4, n), jnp.float32)
        comp = make_compressor("topk", topk_ratio=0.05)
        whole = comp({"w": d}, keys)["w"]
        parts = jnp.concatenate([
            comp({"w": d[:2]}, keys[:2])["w"],
            comp({"w": d[2:]}, keys[2:])["w"],
        ])
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(parts))

    def test_exact_flag_restores_full_sort(self):
        n = 1 << 18
        keys = jax.random.split(jax.random.PRNGKey(5), 2)
        d = jax.random.normal(jax.random.PRNGKey(13), (2, n), jnp.float32)
        comp = make_compressor("topk", topk_ratio=0.01, topk_exact=True)
        out = np.asarray(comp({"w": d}, keys)["w"])
        k = round(0.01 * n)
        np.testing.assert_array_equal(np.count_nonzero(out, axis=1), [k, k])
        # exact = the k largest magnitudes, verified against numpy
        mag = np.abs(np.asarray(d))
        for c in range(2):
            want = np.zeros(n, np.float32)
            top = np.argsort(-mag[c])[:k]
            want[top] = np.asarray(d)[c][top]
            np.testing.assert_array_equal(out[c], want)

    def test_ratio_one_keeps_everything_on_big_leaf(self):
        n = (1 << 17) + 1
        keys = jax.random.split(jax.random.PRNGKey(2), 1)
        d = jax.random.normal(jax.random.PRNGKey(4), (1, n), jnp.float32)
        comp = make_compressor("topk", topk_ratio=1.0)
        np.testing.assert_array_equal(
            np.asarray(comp({"w": d}, keys)["w"]), np.asarray(d))
