"""Local trainer unit tests (SURVEY.md §4.1): FedProx gradient identity,
padded-step no-ops, loss masking."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.client import trainer as trainer_mod
from colearn_federated_learning_tpu.client.trainer import (
    block_group,
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
# runs no shared-weight step and IS the spatial layout, since PR 35 a
# client's step alone under a conditional: against the vmap over the
# block, whose convolutions XLA batches, it reads 1.19e-7 at most after
# three steps (one ulp of a weight between 1 and 2) and 5.96e-8 after
# one, where it was the same program and 0 before (_R18).
_TIGHT = (1e-6, 2e-5)
_R18 = (2.4e-7, 0)
_BLOCK_CASES = {
    # windowed convolutions: no shared-weight phase
    "resnet18": (
        lambda: build_model("resnet18", num_classes=10, width=8),
        (32, 32, 3), dict(momentum=0.9), _R18,
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
def test_block_trainer_matches_vmapped_local_train(case, steps, shallow_zoo):
    """The block trainer's stacked [C, ...] output equals
    ``jax.vmap(local_train)`` (the spatial layout) leaf for leaf, with
    or without a shared-weight phase. The two deep families at two
    stages (conftest's ``shallow_zoo``): whether the layouts agree is a
    question of a model's kinds of kernel, which both keep, as they keep
    their side of ``shared_weight_phase``'s threshold."""
    build, shape, ckw, (atol, rtol) = _BLOCK_CASES[case]
    model = build()
    # one program, not one for every leaf's shape
    params = jax.jit(lambda: init_params(model, shape, seed=0))()
    assert shared_weight_phase(params) == (case != "resnet18")
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
    params = jax.eval_shape(lambda: init_params(model, shape, seed=0))
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
    closed, out_shapes = jax.make_jaxpr(fn, return_shape=True)(*args)
    loops = [e.params["length"] for e in _scans(closed.jaxpr)]
    # the loop over steps and, inside it, the loop over the block's
    # groups of clients
    assert loops == ([steps - shared, clients // block_group(params, clients)]
                     if steps > shared else [])
    jax.tree.map(lambda p, o: np.testing.assert_equal(
        o.shape, (clients,) + p.shape), params, out_shapes[0])


# -- the block's step loop: a group's step under a real conditional --

# examples a client: one with none, two that fill every step (3 steps
# of 4), and no order among them
_BLOCK_EXAMPLES = [0, 12, 4, 5, 12, 1, 8, 9]
_LOOP_CASES = [(opt, path) for path in ("diverged", "shared")
               for opt in ("sgd_momentum", "adamw")]


def _loop_inputs(steps=3, batch=4):
    from colearn_federated_learning_tpu.data.loader import expand_mask_spec

    rng = np.random.default_rng(0)
    clients = len(_BLOCK_EXAMPLES)
    x, y = _fake_data()
    idx = jnp.asarray(
        rng.integers(0, 64, (clients, steps, batch)).astype(np.int32))
    spec = np.stack([_BLOCK_EXAMPLES, np.full(clients, steps)], 1)
    mask = jnp.asarray(expand_mask_spec(spec, steps, batch, 1))
    keys = jax.random.split(jax.random.PRNGKey(3), clients)
    return spec, (x, y, idx, mask, keys)


def _loop_cfg(opt):
    if opt == "adamw":
        return ClientConfig(local_epochs=1, batch_size=4, lr=1e-3,
                            optimizer="adamw")
    return ClientConfig(local_epochs=1, batch_size=4, lr=0.02, momentum=0.9)


def _assert_trees_bitwise(got, want):
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got, want)


# against jax.vmap(local_train), the largest difference of any parameter
# of any client, measured here (PR 35) and allowed: the diverged block
# under SGD with momentum is vmap(local_train) to the bit; under AdamW it
# reads 2.98e-8, one ulp of a weight between 0.25 and 0.5 (a client's
# convolution alone against the same one batched over the block, divided
# by sqrt(nu)); the shared-weight block reads 2.98e-8 / 3.54e-8 (its
# first step contracts the megabatch against one weight, as before PR 35)
_LOOP_ATOL = {("sgd_momentum", "diverged"): 0.0,
              ("adamw", "diverged"): 6e-8,
              ("sgd_momentum", "shared"): 6e-8,
              ("adamw", "shared"): 8e-8}


@pytest.mark.parametrize("opt,path", _LOOP_CASES)
def test_block_loop_skips_dead_steps_bitwise(lenet, monkeypatch, opt, path):
    """The block's step loop against ``jax.vmap(local_train)``, every
    client: the one without examples, the two that fill every step and
    the unordered rest. Parameters, loss and examples are equal to the
    bit for the diverged block (a model of windowed convolutions;
    LeNet-5 made to take that path, each client's step alone) under SGD
    with momentum, and within ``_LOOP_ATOL`` elsewhere. Skipping a dead
    step leaves what multiplying it by zero left, to the bit, on both
    paths and under both optimizers: the same block made to run every
    step of the grid (its conditional replaced by its live branch, which
    for the shared-weight block is the loop before PR 35). And the
    conditional is taken where ``obs/counters.block_step_counts`` says:
    the live predicates the program evaluates are counted beside the
    host's rule on the spec and a loop over the grid."""
    from colearn_federated_learning_tpu.obs.counters import block_step_counts

    model, params = lenet
    cfg = _loop_cfg(opt)
    spec, args = _loop_inputs()
    width, steps = args[3].shape[:2]
    shared = path == "shared"
    if not shared:
        monkeypatch.setattr(trainer_mod, "shared_weight_phase",
                            lambda params: False)
    group = block_group(params, width)
    assert group == (width if shared else 1)

    def run_block():
        block = make_local_train_fn(model, cfg, DPConfig(), "classify",
                                    megabatch=True)
        out = jax.jit(block)(params, *args)
        jax.effects_barrier()
        return out

    taken, cond = [], jax.lax.cond

    def counted_cond(live, run, skip, carry):
        jax.debug.callback(lambda p: taken.append(bool(p)), live)
        return cond(live, run, skip, carry)

    with monkeypatch.context() as counting:
        counting.setattr(jax.lax, "cond", counted_cond)
        w_b, m_b = run_block()
    with monkeypatch.context() as every_step:
        every_step.setattr(jax.lax, "cond",
                           lambda live, run, skip, carry: run(carry))
        w_r, m_r = run_block()
    _assert_trees_bitwise(w_b, w_r)
    np.testing.assert_array_equal(m_b.loss, m_r.loss)
    np.testing.assert_array_equal(m_b.examples, m_r.examples)
    # the client without examples comes back as it went in
    cast = jax.tree.map(lambda p, w: p.astype(w.dtype), params, w_b)
    _assert_trees_bitwise(jax.tree.map(lambda w: w[0], w_b), cast)

    # the spatial layout: local_train vmapped over the whole block
    w_s, m_s = jax.jit(jax.vmap(
        make_local_train_fn(model, cfg, DPConfig(), "classify"),
        in_axes=(None, None, None, 0, 0, 0)))(params, *args)
    atol = _LOOP_ATOL[opt, path]
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=atol, rtol=0), w_b, w_s)
    np.testing.assert_allclose(m_b.loss, m_s.loss, atol=0,
                               rtol=1e-6 if atol else 0)
    np.testing.assert_array_equal(m_b.examples, m_s.examples)
    np.testing.assert_array_equal(m_b.examples,
                                  1.0 * np.array(_BLOCK_EXAMPLES))

    counts = block_step_counts(spec, steps, 4, 1, width, group, shared)
    live = np.array([[s * 4 < n for s in range(steps)]
                     for n in _BLOCK_EXAMPLES])
    executed = 0
    for s in range(steps):
        for g0 in range(0, width, group):
            if (shared and s == 0) or live[g0:g0 + group, s].any():
                executed += group
    assert counts == {"client_steps": width * steps,
                      "dead_steps": int((~live).sum()),
                      "skipped_steps": width * steps - executed}
    assert (counts["skipped_steps"] > 0) == (not shared)
    # one predicate a group-step of the loop, true where it runs
    assert len(taken) == (steps - shared) * (width // group)
    assert sum(taken) * group + shared * width == executed
    # the full mask gives the same counts as the spec
    assert block_step_counts(np.asarray(args[3]), steps, 4, 1, width, group,
                             shared) == counts


def test_block_loop_is_a_conditional_and_no_select_over_the_parameters(
        lenet, monkeypatch):
    """The compiled block holds a ``conditional`` (``vmap`` over the
    predicate would have made it a select whose both sides run), and no
    ``select`` produces a parameter-shaped array: the fused SGD step has
    none of its own."""
    import re

    model, params = lenet
    _, args = _loop_inputs()
    width = args[3].shape[0]
    monkeypatch.setattr(trainer_mod, "shared_weight_phase",
                        lambda params: False)
    block = make_local_train_fn(model, _loop_cfg("sgd_momentum"), DPConfig(),
                                "classify", megabatch=True)
    text = jax.jit(block).lower(params, *args).compile().as_text()
    assert len(re.findall(r" conditional\(", text)) == 1
    shapes = {lead + tuple(p.shape) for p in jax.tree.leaves(params)
              for lead in ((), (width,), (1,))}
    selected = {tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"= \w+\[([\d,]*)\][^ ]* select\(",
                                       text)}
    assert not selected & shapes, selected & shapes
