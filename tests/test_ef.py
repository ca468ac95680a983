"""Error-feedback compression (server.error_feedback — EF-SGD family,
Seide et al. 2014; Stich et al. 2018): memory semantics, lossless-case
identity, sharded-vs-sequential parity on the device-resident store,
dropout gating, the convergence advantage over plain top-k that is EF's
reason to exist, e2e/resume through the driver, and config rejections.
Spec frame: SURVEY.md §2 C6 (aggregation/compression row) — the
reference mount is empty, so citations point at the spec files."""

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


class _Fed:
    def __init__(self, client_indices):
        self.client_indices = client_indices


def _setup(cohort=8, n=256, n_clients=16, steps=RoundShape(2, 4, 8, 32), seed=0):
    model = build_model("lenet5", num_classes=10)
    params = init_params(model, (28, 28, 1), seed=0)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    splits = np.array_split(rng.permutation(n), cohort)
    fed = _Fed([s[: rng.integers(8, len(s) + 1)] for s in splits])
    idx, mask, n_ex = make_round_indices(fed, list(range(cohort)), steps, rng)
    return model, params, x, y, idx, mask, n_ex


def _e_store(params, rows, seed=None):
    if seed is None:
        return jax.tree.map(
            lambda p: jnp.zeros((rows,) + p.shape, jnp.float32), params
        )
    rngs = np.random.default_rng(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(
            0.01 * rngs.normal(size=(rows,) + p.shape).astype(np.float32)
        ),
        params,
    )


def _engines(model, mesh, compression="topk", ratio=0.3, **kw):
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.0)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, supd = make_server_update_fn(scfg)
    sh = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, supd, cohort_size=8,
        donate=False, num_clients=16, compression=compression,
        topk_ratio=ratio, error_feedback=True, **kw,
    )
    sq = make_sequential_round_fn(
        model, ccfg, DPConfig(), "classify", supd, num_clients=16,
        compression=compression, topk_ratio=ratio, error_feedback=True, **kw,
    )
    return init, sh, sq


@pytest.mark.parametrize("lanes", [8, 4, 1])
@pytest.mark.parametrize("kind", ["topk", "qsgd"])
def test_ef_sharded_matches_sequential(lanes, kind):
    """The e-store rides scaffold's gather/scatter plumbing: the sharded
    engine takes the FULL [N_pad, ...] store + cohort ids; the oracle
    takes the cohort rows host-side. Non-trivial cohort (odd clients of
    N=16) exercises the in-program gather; a seeded non-zero starting
    store exercises the memory-add path."""
    model, params, x, y, idx, mask, n_ex = _setup()
    mesh = build_client_mesh(lanes)
    init, sh, sq = _engines(model, mesh, compression=kind)
    cohort = np.arange(1, 16, 2, dtype=np.int32)
    store = _e_store(params, 16, seed=5)
    cc = jax.tree.map(lambda a: a[jnp.asarray(cohort)], store)
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(42))
    p_sh, _, store_sh, m_sh = sh(params, init(params), *args, store,
                                 jnp.asarray(cohort))
    p_sq, _, cc_sq, m_sq = sq(params, init(params), *args, None, cc)
    cc_sh = jax.tree.map(lambda a: np.asarray(a)[cohort], store_sh)
    for got, want in ((p_sh, p_sq), (cc_sh, cc_sq)):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5),
            got, want,
        )
    # rows outside the cohort are untouched
    other = np.arange(0, 16, 2)
    jax.tree.map(
        lambda new, old: np.testing.assert_array_equal(
            np.asarray(new)[other], np.asarray(old)[other]
        ),
        store_sh, store,
    )
    np.testing.assert_allclose(m_sh.train_loss, m_sq.train_loss, rtol=1e-5)


def test_ef_lossless_compressor_is_plain_fedavg():
    """topk_ratio=1.0 keeps every coordinate, so C is the identity:
    the memory must stay exactly 0 and the round must equal the plain
    no-compression engine bit-for-bit (modulo f32 accumulation order)."""
    model, params, x, y, idx, mask, n_ex = _setup()
    mesh = build_client_mesh(8)
    init, sh, _ = _engines(model, mesh, ratio=1.0)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.0)
    _, supd = make_server_update_fn(
        ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    )
    plain = make_sharded_round_fn(
        model, ccfg, DPConfig(), "classify", mesh, supd, cohort_size=8,
        donate=False,
    )
    cohort = np.arange(8, dtype=np.int32)
    store = _e_store(params, 16)
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex),
            jax.random.PRNGKey(7))
    p_ef, _, store_out, _ = sh(params, init(params), *args, store,
                               jnp.asarray(cohort))
    p_plain, _, _ = plain(params, init(params), *args)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        p_ef, p_plain,
    )
    jax.tree.map(
        lambda e: np.testing.assert_array_equal(np.asarray(e), 0.0), store_out
    )


def test_ef_memory_is_the_compression_residual():
    """One round from a zero store: eᵢ⁺ must equal Δᵢ − topk(Δᵢ) where
    Δᵢ is the client's raw delta from an identical uncompressed run —
    the defining EF recursion checked against an independent control."""
    model, params, x, y, idx, mask, n_ex = _setup(cohort=2, steps=RoundShape(1, 2, 8, 16))
    ccfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1, momentum=0.0)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=2)
    init, supd = make_server_update_fn(scfg)
    ratio = 0.25
    sq = make_sequential_round_fn(
        model, ccfg, DPConfig(), "classify", supd, num_clients=2,
        compression="topk", topk_ratio=ratio, error_feedback=True,
    )
    control = make_sequential_round_fn(model, ccfg, DPConfig(), "classify", supd)
    cc = _e_store(params, 2)
    rng = jax.random.PRNGKey(3)
    args = (x, y, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(n_ex), rng)
    _, _, new_e, _ = sq(params, init(params), *args, None, cc)
    # raw per-client deltas from the control engine: rerun local
    # training through the same rng so trajectories match, then
    # recompute the residual by hand
    from colearn_federated_learning_tpu.client.trainer import make_local_train_fn
    from colearn_federated_learning_tpu.ops.compression import make_compressor

    local = jax.jit(make_local_train_fn(model, ccfg, DPConfig(), "classify"))
    keys = jax.random.split(rng, 2)
    comp = make_compressor("topk", topk_ratio=ratio)
    for c in range(2):
        w_c, _ = local(params, x, y, jnp.asarray(idx[c]), jnp.asarray(mask[c]),
                       keys[c])
        delta_c = jax.tree.map(
            lambda w, p: w.astype(jnp.float32) - p.astype(jnp.float32), w_c, params
        )
        block = jax.tree.map(lambda a: a[None], delta_c)
        want_e = jax.tree.map(lambda d, q: (d - q)[0], block,
                              comp(block, keys[c][None]))
        jax.tree.map(
            lambda got, want: np.testing.assert_allclose(
                np.asarray(got)[c], np.asarray(want), rtol=1e-5, atol=1e-7
            ),
            new_e, want_e,
        )


def test_ef_dropout_keeps_memory_and_round_exact():
    """A dropped client (n_ex = 0 upstream zeroing) must keep its eᵢ
    bit-identical and contribute nothing: the round must equal the same
    round run with the dropped client's training data scrambled — i.e.
    its data cannot reach the aggregate through any path (ADVICE r4 #4:
    the equality claim is now actually tested)."""
    model, params, x, y, idx, mask, n_ex = _setup()
    mesh = build_client_mesh(8)
    init, sh, _ = _engines(model, mesh)
    n_drop = np.asarray(n_ex).copy()
    n_drop[3] = 0
    mask_drop = np.asarray(mask).copy()
    mask_drop[3] = 0
    cohort = np.arange(8, dtype=np.int32)
    store = _e_store(params, 16, seed=9)
    p1, _, store1, _ = sh(
        params, init(params), x, y, jnp.asarray(idx), jnp.asarray(mask_drop),
        jnp.asarray(n_drop), jax.random.PRNGKey(1), store, jnp.asarray(cohort),
    )
    # the dropped client's memory row is untouched
    jax.tree.map(
        lambda new, old: np.testing.assert_array_equal(
            np.asarray(new)[3], np.asarray(old)[3]
        ),
        store1, store,
    )
    # control: same round, but the dropped client gathers COMPLETELY
    # different corpus rows — params, server state and store must match
    # bitwise, proving the zero weight severs every data path
    idx_ctl = np.asarray(idx).copy()
    idx_ctl[3] = (idx_ctl[3] + 17) % x.shape[0]
    p2, _, store2, _ = sh(
        params, init(params), x, y, jnp.asarray(idx_ctl),
        jnp.asarray(mask_drop), jnp.asarray(n_drop), jax.random.PRNGKey(1),
        store, jnp.asarray(cohort),
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        p1, p2,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        store1, store2,
    )
    # and the aggregate is finite / sane (the garbage C(e) never ships)
    jax.tree.map(lambda p: np.testing.assert_array_equal(
        np.isfinite(np.asarray(p)), True), p1)


def test_ef_beats_plain_topk_at_aggressive_ratio():
    """EF's raison d'être: at topk_ratio=0.05 the biased compressor
    permanently starves small-magnitude coordinates; the memory retries
    them until they ship. Same data, same seeds, 12 rounds — the EF run
    must reach a strictly lower training loss."""
    model, params, x, y, idx, mask, n_ex = _setup(n=512)
    mesh = build_client_mesh(8)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=0.1, momentum=0.0)
    scfg = ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    init, supd = make_server_update_fn(scfg)

    def run(error_feedback):
        fn = make_sharded_round_fn(
            model, ccfg, DPConfig(), "classify", mesh, supd, cohort_size=8,
            donate=False, compression="topk", topk_ratio=0.05,
            error_feedback=error_feedback,
            **({"num_clients": 16} if error_feedback else {}),
        )
        p, s = params, init(params)
        store = _e_store(params, 16)
        cohort = jnp.asarray(np.arange(8, dtype=np.int32))
        loss = None
        for r in range(12):
            rng = jax.random.fold_in(jax.random.PRNGKey(0), r)
            args = (x, y, jnp.asarray(idx), jnp.asarray(mask),
                    jnp.asarray(n_ex), rng)
            if error_feedback:
                p, s, store, m = fn(p, s, *args, store, cohort)
            else:
                p, s, m = fn(p, s, *args)
            loss = float(m.train_loss)
        return loss

    loss_ef = run(True)
    loss_plain = run(False)
    assert loss_ef < loss_plain, (loss_ef, loss_plain)


def test_ef_e2e_fit_eval_resume(tmp_path):
    """Driver integration: fit + eval + checkpoint/resume-equals-
    straight-run with the e-store in the checkpoint (sharded engine)."""
    def _cfg(out, rounds):
        cfg = get_named_config("mnist_fedavg_2")
        cfg.server.compression = "topk"
        cfg.server.compression_topk_ratio = 0.25
        cfg.server.error_feedback = True
        cfg.server.num_rounds = rounds
        cfg.server.eval_every = 0
        cfg.server.checkpoint_every = 1
        cfg.run.out_dir = str(out)
        cfg.data.synthetic_train_size = 256
        cfg.data.synthetic_test_size = 64
        return cfg

    exp = Experiment(_cfg(tmp_path / "straight", 6), echo=False)
    straight = exp.fit()
    metrics = exp.evaluate(straight["params"])
    assert metrics["eval_acc"] > 0.5, metrics
    assert "c_clients" in straight and "c_global" not in straight

    Experiment(_cfg(tmp_path / "resumed", 3), echo=False).fit()
    cfg_b = _cfg(tmp_path / "resumed", 6)
    cfg_b.run.resume = True
    resumed = Experiment(cfg_b, echo=False).fit()
    assert int(resumed["round"]) == 6
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        straight["params"], resumed["params"],
    )


def test_ef_config_validation():
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.error_feedback = True
    with pytest.raises(ValueError, match="requires server.compression"):
        cfg.validate()
    cfg.server.compression = "topk"
    cfg.server.compression_topk_ratio = 0.25
    cfg.validate()  # the sound pairing passes
    for break_it, pat in [
        (lambda c: setattr(c.server, "secure_aggregation", True), "secure"),
        (lambda c: setattr(c.server, "dp_client_noise_multiplier", 1.0),
         "client-level DP"),
        (lambda c: setattr(c.server, "aggregator", "median"), "robust"),
    ]:
        cfg2 = get_named_config("mnist_fedavg_2")
        cfg2.server.compression = "qsgd"
        cfg2.server.error_feedback = True
        cfg2.server.clip_delta_norm = 1.0  # satisfy secagg/dp preconditions
        break_it(cfg2)
        with pytest.raises(ValueError, match=pat):
            cfg2.validate()
    # stateful algorithms own the store
    cfg3 = get_named_config("mnist_fedavg_2")
    cfg3.algorithm = "scaffold"
    cfg3.server.compression = "qsgd"
    cfg3.server.error_feedback = True
    cfg3.client.momentum = 0.0
    with pytest.raises(ValueError, match="error_feedback|scaffold"):
        cfg3.validate()
