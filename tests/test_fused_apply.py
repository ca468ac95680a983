"""Pallas fused server-apply chain (server.fused_apply, r7 — ROADMAP
item 2 lever b; ops/pallas_apply.py).

On this CPU host the kernel runs in pallas INTERPRET mode — exact and
jax-traceable — so these tests pin the real kernel code path against
the unfused reference for {weighted_mean, krum} × {reputation on/off}
(× error feedback on the psum path), exactly the matrix the fused path
can never be allowed to regress on a non-TPU host. Tolerance contract
(documented in ops/pallas_apply.py): the fused FMA order differs from
optax's separate passes, so parity is at f32-reassociation tolerance,
not bitwise.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from colearn_federated_learning_tpu.config import (
    ClientConfig,
    DPConfig,
    ServerConfig,
    get_named_config,
)
from colearn_federated_learning_tpu.ops.pallas_apply import (
    fused_delta_apply,
    fused_reduce_apply,
)
from colearn_federated_learning_tpu.server.aggregation import (
    make_server_update_fn,
)

# documented parity tolerance: one f32 reassociation of values O(1)
_ATOL = 1e-5
_RTOL = 1e-5


def _tree(rng, bf16_leaf=False):
    t = {
        "w": jnp.asarray(rng.normal(size=(33, 65)), jnp.float32),
        "b": {"k": jnp.asarray(rng.normal(size=(17,)), jnp.float32)},
    }
    if bf16_leaf:
        t["h"] = jnp.asarray(rng.normal(size=(9, 5)), jnp.bfloat16)
    return t


def _close(a, b, atol=_ATOL, rtol=_RTOL):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            atol=atol, rtol=rtol,
        ),
        a, b,
    )


# ---------------------------------------------------------------------------
# kernel units vs the optax reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lr,mom", [(1.0, 0.0), (0.7, 0.9)])
@pytest.mark.parametrize("bf16_leaf", [False, True])
def test_delta_apply_matches_optax(lr, mom, bf16_leaf):
    rng = np.random.default_rng(0)
    params = _tree(rng, bf16_leaf)
    delta = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), params
    )
    opt = optax.sgd(lr, momentum=mom if mom else None)
    st = opt.init(params)
    upd, st2 = opt.update(jax.tree.map(jnp.negative, delta), st, params)
    ref = optax.apply_updates(params, upd)
    trace = st[0].trace if mom else None
    p2, m2 = jax.jit(
        lambda p, m, d: fused_delta_apply(p, m, d, lr, mom)
    )(params, trace, delta)
    _close(ref, p2, atol=1e-2 if bf16_leaf else _ATOL)
    if mom:
        _close(st2[0].trace, m2)
    else:
        assert m2 is None


def test_reduce_apply_matches_weighted_mean_reference():
    rng = np.random.default_rng(1)
    params = _tree(rng)
    k = 5
    stack = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=(k,) + p.shape), jnp.float32),
        params,
    )
    w = jnp.asarray(rng.random(k), jnp.float32)
    ref_delta = jax.tree.map(
        lambda s: jnp.einsum("k,k...->...", w, s) / w.sum(), stack
    )
    opt = optax.sgd(0.5, momentum=0.9)
    st = opt.init(params)
    upd, st2 = opt.update(jax.tree.map(jnp.negative, ref_delta), st, params)
    ref_p = optax.apply_updates(params, upd)
    p2, m2, d2 = jax.jit(
        lambda s, ww, p, m: fused_reduce_apply(s, ww, p, m, 0.5, 0.9)
    )(stack, w / w.sum(), params, st[0].trace)
    _close(ref_p, p2)
    _close(st2[0].trace, m2)
    _close(ref_delta, d2)


def test_reduce_apply_one_hot_is_selection():
    """krum's winner enters the kernel as a one-hot weight row: the
    'reduction' returns exactly the selected client's delta."""
    rng = np.random.default_rng(2)
    params = _tree(rng)
    k = 4
    stack = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=(k,) + p.shape), jnp.float32),
        params,
    )
    w = jnp.zeros((k,), jnp.float32).at[2].set(1.0)
    _, _, d = fused_reduce_apply(stack, w, params, None, 1.0, 0.0)
    _close(jax.tree.map(lambda s: s[2], stack), d)


def test_fused_server_update_keeps_optax_state_structure():
    """Checkpoint interop: the fused update's opt-state pytree is
    structurally identical to the unfused one (same TraceState/
    EmptyState skeleton, same round counter advance)."""
    rng = np.random.default_rng(3)
    params = _tree(rng)
    delta = jax.tree.map(lambda p: jnp.asarray(
        rng.normal(size=p.shape), p.dtype), params)
    for optname in ("mean", "fedavgm"):
        cfg_u = ServerConfig(optimizer=optname)
        cfg_f = ServerConfig(optimizer=optname, fused_apply=True)
        init_u, upd_u = make_server_update_fn(cfg_u)
        init_f, upd_f = make_server_update_fn(cfg_f)
        su, sf = init_u(params), init_f(params)
        assert (jax.tree.structure(su) == jax.tree.structure(sf))
        pu, su2 = upd_u(params, su, delta)
        pf, sf2 = upd_f(params, sf, delta)
        assert (jax.tree.structure(su2) == jax.tree.structure(sf2))
        assert int(sf2["round"]) == 1
        _close(pu, pf)
        assert hasattr(upd_f, "fused_reduce")
        assert not hasattr(upd_u, "fused_reduce")


# ---------------------------------------------------------------------------
# rejections
# ---------------------------------------------------------------------------


def test_fused_apply_rejects_unsupported_optimizers():
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.fused_apply = True
    for optimizer in ("fedadam", "fedyogi"):
        cfg.server.optimizer = optimizer
        with pytest.raises(ValueError, match="fused_apply.*mean.*fedavgm"):
            cfg.validate()


def test_fused_apply_rejects_stateful_and_gossip():
    for algo in ("scaffold", "feddyn", "gossip"):
        cfg = get_named_config("mnist_fedavg_2")
        cfg.algorithm = algo
        cfg.client.momentum = 0.0
        cfg.server.fused_apply = True
        with pytest.raises(ValueError):
            cfg.validate()


def test_engine_rejects_fused_flag_without_fused_update():
    """A direct engine caller cannot pair fused_apply=True with a plain
    server_update — the stacked path would silently run unfused."""
    from colearn_federated_learning_tpu.parallel.round_engine import (
        make_sequential_round_fn,
    )

    _, update = make_server_update_fn(ServerConfig())
    with pytest.raises(ValueError, match="fused_apply"):
        make_sequential_round_fn(
            None, ClientConfig(), DPConfig(), "classify", update,
            fused_apply=True,
        )


def test_fused_update_lowers_for_tpu_on_a_multi_device_mesh(monkeypatch):
    """What the four-chip host said (PR 21): a Mosaic kernel left in
    the auto-partitioned region of a mesh wider than one chip does not
    lower. Cross-lowering for TPU from here reproduces that, and shows
    the engines' replicated manual region (_on_every_device) lowers to
    the custom call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from colearn_federated_learning_tpu.ops import pallas_apply
    from colearn_federated_learning_tpu.parallel.mesh import build_client_mesh
    from colearn_federated_learning_tpu.parallel.round_engine import (
        _on_every_device,
    )

    monkeypatch.setattr(pallas_apply, "_interpret", lambda interpret: False)
    mesh = build_client_mesh(4, devices=jax.devices()[:4])
    rep = NamedSharding(mesh, P())
    init, update = make_server_update_fn(
        ServerConfig(optimizer="fedavgm", fused_apply=True)
    )
    params = _tree(np.random.default_rng(3))
    args = (params, init(params), params)

    def lower(fn):
        return jax.jit(fn, in_shardings=rep).trace(*args).lower(
            lowering_platforms=("tpu",))

    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        lower(update)
    assert "tpu_custom_call" in lower(_on_every_device(update, mesh)).as_text()
    # the optax chain needs no manual region and is passed through
    _, plain = make_server_update_fn(ServerConfig(optimizer="fedavgm"))
    assert _on_every_device(plain, mesh) is plain


# ---------------------------------------------------------------------------
# e2e: the CI matrix — {weighted_mean, krum} × {reputation on/off},
# fused vs unfused, both engines, interpret mode (the tier-1 smoke that
# keeps the kernel path from regressing to collection-error off-TPU)
# ---------------------------------------------------------------------------


def _cfg(fused, engine="sharded", fuse=1, reputation=False, **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.apply_overrides({
        "server.num_rounds": 4, "server.eval_every": 0,
        "data.num_clients": 8, "server.cohort_size": 4,
        "data.synthetic_train_size": 256, "data.synthetic_test_size": 64,
        "data.max_examples_per_client": 32, "client.batch_size": 16,
        "run.out_dir": "", "run.metrics_flush_every": 2,
        "run.engine": engine, "run.fuse_rounds": fuse,
        "server.fused_apply": fused,
        "server.optimizer": "fedavgm",
        "attack.kind": "sign_flip", "attack.fraction": 0.25,
    })
    if reputation:
        cfg.apply_overrides({
            "run.obs.client_ledger.enabled": True,
            "server.reputation.enabled": True,
        })
    for k, v in over.items():
        cfg.apply_overrides({k: v})
    return cfg.validate()


def _fit(cfg):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    return exp.fit()


@pytest.mark.parametrize("aggregator", ["weighted_mean", "krum"])
@pytest.mark.parametrize("reputation", [False, True])
def test_fused_matches_unfused_per_aggregator_and_reputation(
    tmp_path, aggregator, reputation,
):
    over = {"server.aggregator": aggregator}
    ref = _fit(_cfg(False, reputation=reputation, **over))
    fused = _fit(_cfg(True, reputation=reputation, **over))
    _close(ref["params"], fused["params"])
    _close(ref["server_opt_state"]["opt"][0].trace,
           fused["server_opt_state"]["opt"][0].trace)
    if reputation:
        _close(ref["ledger"], fused["ledger"], atol=1e-4, rtol=1e-3)
    # cross-engine: the sequential oracle's fused path shares the
    # weight construction and the kernel — same tolerance again
    seq = _fit(_cfg(True, engine="sequential", reputation=reputation,
                    **over))
    _close(fused["params"], seq["params"], atol=1e-4, rtol=1e-3)


def test_fused_apply_composes_with_fusion_and_psum_path(tmp_path):
    """fuse_rounds>1: the fused apply runs inside the fused scan body;
    and the plain psum path (no attack/robust — Mode B apply-only
    fusion) matches too, composing with error feedback."""
    base = {"attack.kind": "", "attack.fraction": 0.25}
    ref = _fit(_cfg(False, **base))
    fused = _fit(_cfg(True, **base))
    fused2 = _fit(_cfg(True, fuse=2, **base))
    _close(ref["params"], fused["params"])
    _close(ref["params"], fused2["params"])
    ef = {
        "attack.kind": "", "server.compression": "qsgd",
        "server.error_feedback": True,
    }
    ref_ef = _fit(_cfg(False, **ef))
    fused_ef = _fit(_cfg(True, **ef))
    _close(ref_ef["params"], fused_ef["params"])
    _close(ref_ef["c_clients"], fused_ef["c_clients"], atol=1e-4,
           rtol=1e-3)
