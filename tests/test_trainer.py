"""Local trainer unit tests (SURVEY.md §4.1): FedProx gradient identity,
padded-step no-ops, loss masking."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.client.trainer import (
    make_local_train_fn,
    make_loss_fn,
    shared_weight_phase,
    windowed_conv_share,
)
from colearn_federated_learning_tpu.config import ClientConfig, DPConfig
from colearn_federated_learning_tpu.models import build_model, init_params
from colearn_federated_learning_tpu.utils import trees


@pytest.fixture(scope="module")
def lenet():
    model = build_model("lenet5", num_classes=10)
    params = init_params(model, (28, 28, 1), seed=0)
    return model, params


def _fake_data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    return x, y


def test_fedprox_gradient_identity(lenet):
    """∇(loss + μ/2‖w−w₀‖²) == plain ∇loss + μ(w−w₀)."""
    model, params = lenet
    x, y = _fake_data(8)
    m = jnp.ones((8,))
    mu = 0.37
    loss_fn = make_loss_fn(model, "classify")
    w = jax.tree.map(lambda p: p + 0.01, params)  # displace from w0

    plain = jax.grad(loss_fn)(w, x, y, m)

    def prox_loss(p):
        return loss_fn(p, x, y, m) + (mu / 2) * trees.tree_sq_norm(
            trees.tree_sub(p, params)
        )

    full = jax.grad(prox_loss)(w)
    manual = jax.tree.map(lambda g, p, p0: g + mu * (p - p0), plain, w, params)
    chex_close = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    jax.tree.map(chex_close, full, manual)


def test_padded_steps_are_noops(lenet):
    """A client whose mask is all-zero after step s must end with exactly
    the params it had at step s (momentum must not keep drifting)."""
    model, params = lenet
    cfg = ClientConfig(local_epochs=1, batch_size=8, lr=0.1, momentum=0.9)
    fn = jax.jit(make_local_train_fn(model, cfg, DPConfig(), "classify"))
    x, y = _fake_data(32)
    rng = jax.random.PRNGKey(0)

    # 4 steps, last 2 fully padded
    idx = jnp.arange(32).reshape(4, 8)
    mask_full = jnp.stack([jnp.ones(8), jnp.ones(8), jnp.zeros(8), jnp.zeros(8)])
    w_padded, _ = fn(params, x, y, idx, mask_full, rng)

    idx2 = idx[:2]
    mask2 = mask_full[:2]
    w_short, _ = fn(params, x, y, idx2, mask2, rng)

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        w_padded, w_short,
    )


def test_masked_loss_ignores_padding(lenet):
    model, params = lenet
    loss_fn = make_loss_fn(model, "classify")
    x, y = _fake_data(16)
    full = loss_fn(params, x[:8], y[:8], jnp.ones(8))
    # same 8 real examples + 8 garbage padded ones
    y_garbage = jnp.concatenate([y[:8], jnp.zeros(8, jnp.int32)])
    m = jnp.concatenate([jnp.ones(8), jnp.zeros(8)])
    padded = loss_fn(params, x, y_garbage, m)
    np.testing.assert_allclose(full, padded, rtol=1e-6)


def test_local_train_learns(lenet):
    """Loss goes down over one local phase on learnable data."""
    model, params = lenet
    cfg = ClientConfig(local_epochs=4, batch_size=16, lr=0.05, momentum=0.9)
    fn = jax.jit(make_local_train_fn(model, cfg, DPConfig(), "classify"))
    # template-structured data (learnable)
    rng = np.random.default_rng(0)
    templates = rng.uniform(0, 1, (10, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, 64).astype(np.int32)
    x = 0.8 * templates[y] + 0.2 * rng.uniform(0, 1, (64, 28, 28, 1)).astype(np.float32)
    x, y = jnp.asarray(x), jnp.asarray(y)
    idx = jnp.asarray(np.tile(np.arange(64), 4).reshape(16, 16))
    mask = jnp.ones((16, 16))
    loss_fn = make_loss_fn(model, "classify")
    before = float(loss_fn(params, x, y, jnp.ones(64)))
    w, metrics = fn(params, x, y, idx, mask, jax.random.PRNGKey(1))
    after = float(loss_fn(w, x, y, jnp.ones(64)))
    assert after < before * 0.7, (before, after)


# -- megabatch block trainer: with and without its shared-weight phase --

class _DenseOnly(nn.Module):
    """No convolution at all."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))


# (build, input shape, client overrides, tolerance against the spatial
# layout). _TIGHT is test_megabatch_matches_spatial's: a shared-weight
# step contracts other GEMM shapes than the per-client path, so
# reassociation moves the last ulp. MobileNetV2 amplifies that ulp a
# hundredfold per local step at any lr that trains (GroupNorm over a few
# elements, relu6 kinks): its block reads 2.5e-5 against spatial after
# two real steps, at the commit before PR 24 and here alike. ResNet-18
# runs no shared-weight step and IS the spatial layout.
_TIGHT = (1e-6, 2e-5)
_BLOCK_CASES = {
    # windowed convolutions: no shared-weight phase
    "resnet18": (
        lambda: build_model("resnet18", num_classes=10, width=8),
        (32, 32, 3), dict(momentum=0.9), (0, 0),
    ),
    # pointwise and depthwise kernels: shared-weight phase; FedProx
    # against the un-batched global
    "mobilenetv2_prox": (
        lambda: build_model("mobilenetv2", num_classes=10, width_mult=0.5),
        (28, 28, 1), dict(momentum=0.9, prox_mu=0.1, lr=1e-3), (2e-4, 2e-5),
    ),
    "dense_only": (_DenseOnly, (28, 28, 1), dict(momentum=0.9), _TIGHT),
    # optax state through the shared-weight step. Adam divides by
    # sqrt(nu): an ulp on a tiny gradient is 5e-6 on the first update
    "lenet5_adamw": (
        lambda: build_model("lenet5", num_classes=10),
        (28, 28, 1), dict(optimizer="adamw", lr=1e-3), (2e-5, 1e-4),
    ),
}
# steps == 1: nothing but the shared-weight step (lenet5), or one
# diverged step (resnet18)
_BLOCK_RUNS = [(case, 3) for case in sorted(_BLOCK_CASES)] + [
    ("lenet5_adamw", 1), ("resnet18", 1)]


def _block_inputs(shape, clients, steps, batch, seed=0):
    rng = np.random.default_rng(seed)
    n = 64
    x = jnp.asarray(rng.uniform(0, 1, (n,) + shape).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    idx = jnp.asarray(
        rng.integers(0, n, (clients, steps, batch)).astype(np.int32)
    )
    mask = np.ones((clients, steps, batch), np.float32)
    if steps > 2:
        mask[:, 1] = 0.0  # a padded (all-masked) step in the middle
    mask[0, -1, batch // 2:] = 0.0  # and a ragged last batch
    keys = jax.random.split(jax.random.PRNGKey(seed + 3), clients)
    return x, y, idx, jnp.asarray(mask), keys


@pytest.mark.parametrize("case,steps", _BLOCK_RUNS)
def test_block_trainer_matches_vmapped_local_train(case, steps):
    """The block trainer's stacked [C, ...] output equals
    ``jax.vmap(local_train)`` (the spatial layout) leaf for leaf, with
    or without a shared-weight phase."""
    build, shape, ckw, (atol, rtol) = _BLOCK_CASES[case]
    model = build()
    params = init_params(model, shape, seed=0)
    cfg = ClientConfig(**{"local_epochs": 1, "batch_size": 4, "lr": 0.02,
                          **ckw})
    args = (params,) + _block_inputs(shape, 4, steps, 4)
    w_b, m_b = jax.jit(make_local_train_fn(
        model, cfg, DPConfig(), "classify", megabatch=True))(*args)
    w_s, m_s = jax.jit(jax.vmap(
        make_local_train_fn(model, cfg, DPConfig(), "classify"),
        in_axes=(None, None, None, 0, 0, 0)))(*args)
    jax.tree.map(
        lambda p, b: np.testing.assert_equal(b.shape, (4,) + p.shape),
        params, w_b,
    )
    jax.tree.map(
        lambda p, q: np.testing.assert_allclose(
            np.asarray(p), np.asarray(q), atol=atol, rtol=rtol),
        w_s, w_b,
    )
    np.testing.assert_allclose(m_s.loss, m_b.loss, rtol=1e-5)
    np.testing.assert_array_equal(m_s.examples, m_b.examples)


# every model of the zoo, its share of windowed-convolution elements at
# the shapes the named configs use: far from the rule's one half on
# either side, and nothing between has been measured (PERF.md, PR 24)
_ZOO_SHARES = {
    "resnet18": ((32, 32, 3), {}, 0.983),
    "lenet5": ((28, 28, 1), {}, 0.04),
    "mobilenetv2": ((28, 28, 1), {}, 0.0),
    "bert_tiny": (None, {}, 0.0),
    "stacked_lstm": (None, {}, 0.0),
    "vit_b16": (None, {}, 0.007),
}


@pytest.mark.parametrize("name", sorted(_ZOO_SHARES))
def test_windowed_conv_share_of_the_zoo(name):
    from colearn_federated_learning_tpu.models import model_input_spec

    shape, kw, want = _ZOO_SHARES[name]
    model = build_model(name, num_classes=10, **kw)
    spec_shape, dtype = model_input_spec(name, **kw)
    shapes = jax.eval_shape(
        lambda: init_params(model, shape or spec_shape, input_dtype=dtype))
    share = windowed_conv_share(shapes)
    assert share == pytest.approx(want, abs=0.01)
    assert shared_weight_phase(shapes) == (name != "resnet18")
    assert not 0.1 < share < 0.9


def _scans(jaxpr):
    """Every scan equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scans(sub)


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("name", ["resnet18", "lenet5"])
def test_which_models_run_a_shared_weight_phase(name, steps):
    """ResNet-18 (windowed kernels 98 % of it) loops over every local
    step with per-client weights; LeNet-5 (4 %) over all but the
    shared-weight first, whose convolutions contract the [C·batch]
    megabatch against the un-batched kernel."""
    shape = (32, 32, 3) if name == "resnet18" else (28, 28, 1)
    model = build_model(name, num_classes=10,
                        **(dict(width=8) if name == "resnet18" else {}))
    params = init_params(model, shape, seed=0)
    shared = shared_weight_phase(params)
    assert shared == (name == "lenet5")
    cfg = ClientConfig(local_epochs=1, batch_size=2, lr=0.1, momentum=0.9)
    fn = make_local_train_fn(model, cfg, DPConfig(), "classify",
                             megabatch=True)
    clients = 4
    args = (params, jnp.zeros((8,) + shape), jnp.zeros((8,), jnp.int32),
            jnp.zeros((clients, steps, 2), jnp.int32),
            jnp.ones((clients, steps, 2)),
            jax.random.split(jax.random.PRNGKey(0), clients))
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    loops = [e.params["length"] for e in _scans(jaxpr)]
    assert loops == ([steps - shared] if steps > shared else [])
    out_shapes = jax.eval_shape(fn, *args)[0]
    jax.tree.map(lambda p, o: np.testing.assert_equal(
        o.shape, (clients,) + p.shape), params, out_shapes)
