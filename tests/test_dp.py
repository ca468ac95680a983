"""DP-SGD unit tests (SURVEY.md §4.1): clip-norm bound, masking, accountant."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.config import DPConfig
from colearn_federated_learning_tpu.privacy import dp as dp_lib
from colearn_federated_learning_tpu.utils import trees


def _quadratic_loss(params, x, y, m):
    # per-example "loss" with analytically known gradient: w·x scaled
    pred = (params["w"][None, :] * x).sum(-1)
    err = (pred - y) ** 2
    return (err * m).sum() / jnp.maximum(m.sum(), 1.0)


def test_clip_norm_bound_holds():
    """With noise off, ‖DP grad‖ ≤ clip (mean of per-example clipped grads)."""
    cfg = DPConfig(enabled=True, l2_clip=0.1, noise_multiplier=0.0, microbatch_size=4)
    fn = dp_lib.make_dp_grad_fn(_quadratic_loss, cfg)
    params = {"w": jnp.asarray(np.random.default_rng(0).normal(size=8).astype(np.float32))}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 8)).astype(np.float32) * 100)
    y = jnp.zeros(16)
    m = jnp.ones(16)
    _, grads = jax.jit(fn)(params, x, y, m, jax.random.PRNGKey(0))
    norm = float(trees.tree_global_norm(grads))
    assert norm <= cfg.l2_clip * 1.0001, norm


def test_masked_examples_contribute_nothing():
    cfg = DPConfig(enabled=True, l2_clip=1.0, noise_multiplier=0.0, microbatch_size=4)
    fn = jax.jit(dp_lib.make_dp_grad_fn(_quadratic_loss, cfg))
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=8).astype(np.float32))}
    x = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    y = jnp.ones(8)
    m_half = jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0], jnp.float32)
    _, g_half = fn(params, x, y, m_half, jax.random.PRNGKey(0))
    # same real examples, garbage in padded slots
    x2 = x.at[4:].set(999.0)
    _, g_half2 = fn(params, x2, y, m_half, jax.random.PRNGKey(0))
    np.testing.assert_allclose(g_half["w"], g_half2["w"], rtol=1e-6)


def test_noise_changes_with_key_and_scales():
    cfg = DPConfig(enabled=True, l2_clip=1.0, noise_multiplier=2.0, microbatch_size=4)
    fn = jax.jit(dp_lib.make_dp_grad_fn(_quadratic_loss, cfg))
    rng = np.random.default_rng(0)
    params = {"w": jnp.zeros(8)}
    x = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    y = jnp.zeros(8)
    m = jnp.ones(8)
    _, g1 = fn(params, x, y, m, jax.random.PRNGKey(1))
    _, g2 = fn(params, x, y, m, jax.random.PRNGKey(2))
    assert not np.allclose(np.asarray(g1["w"]), np.asarray(g2["w"]))


def test_rdp_accountant_monotonic():
    # more steps or more noise → ε moves the right way
    e1 = dp_lib.rdp_epsilon(1.0, 0.01, 100, 1e-5)
    e2 = dp_lib.rdp_epsilon(1.0, 0.01, 1000, 1e-5)
    e3 = dp_lib.rdp_epsilon(4.0, 0.01, 1000, 1e-5)
    assert e2 > e1
    assert e3 < e2
    assert dp_lib.rdp_epsilon(0.0, 0.01, 10, 1e-5) == float("inf")


# ---------------------------------------------------------------------------
# accountant validation (VERDICT r1 next-#7): the integer-order
# sampled-Gaussian RDP closed form is checked against an independent
# numerical-integration oracle, the analytic unamplified Gaussian case,
# and a published-literature ballpark.
# ---------------------------------------------------------------------------


def _numeric_renyi_sampled_gaussian(q, sigma, alpha, grid=400_000, span=60.0):
    """Oracle: D_α(mix‖p0) and D_α(p0‖mix) for mix=(1−q)N(0,σ²)+qN(1,σ²),
    by direct quadrature of ∫ P^α Q^{1−α}. Independent of the closed form."""
    x = np.linspace(-span, span, grid)
    lp0 = -0.5 * ((x / sigma) ** 2) - np.log(sigma * np.sqrt(2 * np.pi))
    lp1 = -0.5 * (((x - 1.0) / sigma) ** 2) - np.log(sigma * np.sqrt(2 * np.pi))
    lmix = np.logaddexp(np.log1p(-q) + lp0, np.log(q) + lp1)

    def d_renyi(lP, lQ):
        log_integrand = alpha * lP + (1.0 - alpha) * lQ
        shift = log_integrand.max()  # keep exp() in float64 range at high α
        val = np.trapezoid(np.exp(log_integrand - shift), x)
        return (shift + np.log(val)) / (alpha - 1.0)

    return d_renyi(lmix, lp0), d_renyi(lp0, lmix)


@pytest.mark.parametrize("q,sigma", [(0.01, 1.1), (0.1, 1.0), (0.5, 2.0), (0.02, 0.7)])
@pytest.mark.parametrize("alpha", [2, 3, 8, 32])
def test_sampled_gaussian_rdp_matches_numeric_oracle(q, sigma, alpha):
    closed = dp_lib.sampled_gaussian_rdp(q, sigma, alpha)
    d_mix_p0, d_p0_mix = _numeric_renyi_sampled_gaussian(q, sigma, alpha)
    # exact match for the computed direction...
    np.testing.assert_allclose(closed, d_mix_p0, rtol=1e-5, atol=1e-9)
    # ...and that direction dominates (Mironov et al. 2019 §3.3), so it is
    # the correct per-step RDP for add/remove adjacency
    assert closed >= d_p0_mix - 1e-7


def test_rdp_accountant_unamplified_analytic():
    """q=1, T=1: ε = min_α α/(2σ²) + log(1/δ)/(α−1); the continuous optimum
    is 1/(2σ²) + √(2·log(1/δ))/σ (Mironov 2017 Prop. 3 + conversion).
    Integer orders can only be ≥ the continuum value, and close to it."""
    import math

    sigma, delta = 1.0, 1e-5
    analytic = 1 / (2 * sigma**2) + math.sqrt(2 * math.log(1 / delta)) / sigma
    got = dp_lib.rdp_epsilon(sigma, 1.0, 1, delta)
    assert analytic <= got <= analytic * 1.02, (got, analytic)


def test_rdp_accountant_literature_value():
    """The headline number of Abadi et al. 2016 (§1/Fig. 2): q=0.01,
    σ=4, T=10⁴ steps, δ=1e-5 — the moments accountant reports ε ≈ 1.26
    (vs ≈9.34 for strong composition). Our exact integer-order RDP
    accountant must land in a tight band around it."""
    eps = dp_lib.rdp_epsilon(4.0, 0.01, 10_000, 1e-5)
    assert 1.2 < eps < 1.35, eps


def test_rdp_accountant_subsampling_never_hurts():
    """Amplified ε at q<1 must beat the unamplified Gaussian bound."""
    for q in (0.001, 0.01, 0.1, 0.9):
        amp = dp_lib.rdp_epsilon(1.5, q, 500, 1e-5)
        unamp = dp_lib.rdp_epsilon(1.5, 1.0, 500, 1e-5)
        assert amp <= unamp + 1e-9, (q, amp, unamp)


class TestTwoPassClipping:
    """dp.clipping="two_pass" (ghost-norm-style, r5): the released
    quantity must be IDENTICAL to the microbatch path — same clip
    scales, same noise stream — only the schedule of backward passes
    differs."""

    def _both(self, cfg_kw, b=16, d=8, seed=0):
        rng = np.random.default_rng(seed)
        params = {"w": jnp.asarray(rng.normal(size=d).astype(np.float32))}
        x = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32) * 10)
        y = jnp.zeros(b)
        m = jnp.asarray((rng.random(b) > 0.2).astype(np.float32))
        outs = {}
        for mode in ("microbatch", "two_pass"):
            cfg = DPConfig(enabled=True, clipping=mode, **cfg_kw)
            fn = jax.jit(dp_lib.make_dp_grad_fn(_quadratic_loss, cfg))
            outs[mode] = fn(params, x, y, m, jax.random.PRNGKey(7))
        return outs

    def test_matches_microbatch_noiseless(self):
        outs = self._both(dict(l2_clip=0.3, noise_multiplier=0.0,
                               microbatch_size=4))
        (l1, g1), (l2, g2) = outs["microbatch"], outs["two_pass"]
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7),
            g1, g2,
        )

    def test_matches_microbatch_with_noise(self):
        """Same rng ⇒ the identical noise stream on both paths: outputs
        agree to float tolerance even WITH noise."""
        outs = self._both(dict(l2_clip=0.5, noise_multiplier=1.3,
                               microbatch_size=8))
        (_, g1), (_, g2) = outs["microbatch"], outs["two_pass"]
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            g1, g2,
        )

    def test_clip_bound_still_exact(self):
        cfg = DPConfig(enabled=True, clipping="two_pass", l2_clip=0.1,
                       noise_multiplier=0.0, microbatch_size=4)
        fn = jax.jit(dp_lib.make_dp_grad_fn(_quadratic_loss, cfg))
        rng = np.random.default_rng(3)
        params = {"w": jnp.asarray(rng.normal(size=8).astype(np.float32))}
        x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32) * 100)
        _, grads = fn(params, x, jnp.zeros(16), jnp.ones(16),
                      jax.random.PRNGKey(0))
        assert float(trees.tree_global_norm(grads)) <= cfg.l2_clip * 1.0001


# -- the default path per leaf (PR 44): Gram norms and one weighted
# product for Dense / patch-embedding kernels, materialised per-example
# gradients for every other leaf — against vmap(grad) over single
# examples, written here and nowhere in the package -----------------------

import flax.linen as nn  # noqa: E402

from colearn_federated_learning_tpu.client.trainer import make_loss_fn  # noqa: E402
from colearn_federated_learning_tpu.models import build_model  # noqa: E402

GHOST_B, GHOST_MB, GHOST_CLIP = 8, 4, 0.5


class _MLP(nn.Module):
    """Dense only: every product has T = 1."""
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape(x.shape[0], -1).astype(self.compute_dtype)
        x = nn.tanh(nn.Dense(32, dtype=self.compute_dtype)(x))
        x = nn.tanh(nn.Dense(24, dtype=self.compute_dtype)(x))
        return nn.Dense(10, dtype=jnp.float32)(x)


def _ghost_model(name, dtype):
    if name == "vit":  # patch-embedding Conv, cls, positions, LayerNorm
        return build_model("vit_b16", 10, image_size=16, patch_size=4,
                           hidden=32, layers=2, heads=2, mlp_dim=64,
                           compute_dtype=dtype), (16, 16, 3)
    if name == "lenet":  # windowed convolutions: materialised leaves
        return build_model("lenet5", 10, compute_dtype=dtype), (28, 28, 1)
    return _MLP(compute_dtype=dtype), (6, 6, 1)


class _Case:
    """One model in one compute dtype: its batch, the oracle's
    per-example gradients and norms, and the jitted path."""

    def __init__(self, name, dtype):
        self.model, shape = _ghost_model(name, dtype)
        rng = np.random.default_rng(11)
        self.x = jnp.asarray(rng.integers(0, 256, (GHOST_B,) + shape), jnp.uint8)
        self.y = jnp.asarray(rng.integers(0, 10, GHOST_B))
        params = self.model.init(jax.random.PRNGKey(0),
                                 jnp.zeros((1,) + shape), train=False)["params"]
        # zero-initialised leaves (biases, cls) away from zero
        self.params = jax.tree.map(
            lambda p: p + 0.05 * jax.random.normal(
                jax.random.PRNGKey(p.size), p.shape), params)
        self.loss_fn = make_loss_fn(self.model, "classify")
        one = jnp.ones((1,), jnp.float32)
        self.losses, per_example = jax.vmap(
            lambda x1, y1: jax.value_and_grad(self.loss_fn)(
                self.params, x1[None], y1[None], one))(self.x, self.y)
        self.per_example = jax.tree.map(
            lambda g: g.astype(jnp.float32), per_example)
        self.norms = np.sqrt(sum(
            np.sum(np.square(np.asarray(g).reshape(GHOST_B, -1)), axis=1)
            for g in jax.tree.leaves(self.per_example)))
        self.fn = {sigma: jax.jit(dp_lib.make_dp_grad_fn(self.loss_fn, DPConfig(
            enabled=True, l2_clip=GHOST_CLIP, noise_multiplier=sigma,
            microbatch_size=GHOST_MB))) for sigma in (0.0, 1.3)}

    def expected(self, mask):
        """(loss, clipped mean) the mechanism owes for ``mask``."""
        scale = np.minimum(1.0, GHOST_CLIP / self.norms) * mask
        denom = max(mask.sum(), 1.0)
        return (float((np.asarray(self.losses) * mask).sum() / denom),
                jax.tree.map(lambda g: np.einsum(
                    "b,b...->...", scale, np.asarray(g)) / denom,
                    self.per_example))


_CASES = {}


def _case(name, dtype):
    key = (name, jnp.dtype(dtype).name)
    if key not in _CASES:
        _CASES[key] = _Case(name, dtype)
    return _CASES[key]


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


GHOST_MASKS = {
    "all_real": np.ones(GHOST_B, np.float32),
    "some_masked": np.asarray([1, 1, 0, 1, 0, 0, 1, 1], np.float32),
    "all_masked": np.zeros(GHOST_B, np.float32),
}
# float32: 1e-5 relative. bfloat16 compute: the oracle rounds every
# per-example kernel gradient to bfloat16 at its product's output where
# the path accumulates the same products in float32, and two programs
# of one bfloat16 model differ by which intermediate roundings the
# compiler's fusions skip (dp_grads_two_pass's docstring puts bfloat16
# reassociation at 1e-2 a product; `cls`, at the far end of the tiny
# ViT's bfloat16 backward pass, reads 2.1e-2).
GHOST_TOL = {"float32": 1e-5, "bfloat16": 5e-2}


@pytest.mark.parametrize("mask", sorted(GHOST_MASKS))
@pytest.mark.parametrize("dtype", sorted(GHOST_TOL))
@pytest.mark.parametrize("name", ["vit", "lenet", "mlp"])
def test_default_path_matches_materialised_oracle(name, dtype, mask):
    case, tol = _case(name, dtype), GHOST_TOL[dtype]
    m = GHOST_MASKS[mask]
    loss, grads = case.fn[0.0](case.params, case.x, case.y, jnp.asarray(m),
                               jax.random.PRNGKey(3))
    want_loss, want = case.expected(m)
    assert float(loss) == pytest.approx(want_loss, rel=tol, abs=1e-7)
    if not m.any():
        assert all(not np.asarray(g).any() for g in jax.tree.leaves(grads))
        return
    for (path, got), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                              jax.tree.leaves(want)):
        assert got.dtype == jnp.float32  # the parameters' dtype
        assert _rel_l2(got, w) <= tol, jax.tree_util.keystr(path)


@pytest.mark.parametrize("dtype", sorted(GHOST_TOL))
@pytest.mark.parametrize("name", ["vit", "lenet", "mlp"])
def test_per_example_norms_and_sensitivity(name, dtype):
    """One example alone (a one-hot mask, noise off) is released as
    ``s_i·g_i``: its norm is the sensitivity, ``≤ l2_clip·(1 + 1e-5)``
    in either dtype (norm and released sum come from different products
    of the same operands, both accumulated in float32); the path's
    ``s_i`` and the norm behind it are read off against the oracle's
    ``g_i``."""
    case, tol = _case(name, dtype), GHOST_TOL[dtype]
    assert (case.norms > GHOST_CLIP).all()  # every example is clipped
    for i in range(GHOST_B):
        m = np.zeros(GHOST_B, np.float32)
        m[i] = 1.0
        _, out = case.fn[0.0](case.params, case.x, case.y, jnp.asarray(m),
                              jax.random.PRNGKey(3))
        out = np.concatenate([np.asarray(g, np.float64).ravel()
                              for g in jax.tree.leaves(out)])
        g_i = np.concatenate([np.asarray(g[i], np.float64).ravel()
                              for g in jax.tree.leaves(case.per_example)])
        assert np.linalg.norm(out) <= GHOST_CLIP * (1 + 1e-5)
        s_i = out @ g_i / (g_i @ g_i)
        norm_i = GHOST_CLIP / s_i  # the norm the path clipped at
        assert norm_i == pytest.approx(case.norms[i], rel=tol)
        assert s_i * case.norms[i] <= GHOST_CLIP * (1 + tol)


@pytest.mark.parametrize("name", ["vit", "lenet"])
def test_noise_is_the_same_draw_leaf_by_leaf(name):
    """Same leaf order, same key split: with one key the noisy result is
    the clean one plus ``sigma·C·N(key_leaf) / n``."""
    case = _case(name, jnp.float32)
    m, key = jnp.ones(GHOST_B), jax.random.PRNGKey(5)
    _, clean = case.fn[0.0](case.params, case.x, case.y, m, key)
    _, noisy = case.fn[1.3](case.params, case.x, case.y, m, key)
    leaves, treedef = jax.tree.flatten(case.params)
    keys = jax.tree.unflatten(treedef, list(jax.random.split(key, len(leaves))))
    jax.tree.map(
        lambda c, n, k: np.testing.assert_allclose(
            (np.asarray(n) - np.asarray(c)) * GHOST_B / (1.3 * GHOST_CLIP),
            np.asarray(jax.random.normal(k, c.shape, jnp.float32)),
            atol=2e-5),
        clean, noisy, keys)


@pytest.mark.parametrize("shards", [2, 4])
def test_batch_axis_form_matches_unsharded(shards):
    """The batch sharded over a mesh axis (the CPU's virtual devices):
    per-shard perturbations and clipped sums, one psum, the same noise —
    beside the unsharded result."""
    from jax.sharding import PartitionSpec as P

    case = _case("vit", jnp.float32)
    cfg = DPConfig(enabled=True, l2_clip=GHOST_CLIP, noise_multiplier=1.3,
                   microbatch_size=2)
    m = jnp.asarray(GHOST_MASKS["some_masked"])
    key = jax.random.PRNGKey(9)
    want_loss, want = jax.jit(dp_lib.make_dp_grad_fn(case.loss_fn, cfg))(
        case.params, case.x, case.y, m, key)
    mesh = jax.make_mesh((shards,), ("batch",))
    sharded = jax.jit(jax.shard_map(
        dp_lib.make_dp_grad_fn(case.loss_fn, cfg, batch_axis="batch"),
        mesh=mesh, in_specs=(P(), P("batch"), P("batch"), P("batch"), P()),
        out_specs=(P(), P())))
    loss, got = sharded(case.params, case.x, case.y, m, key)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-6), got, want)


def _counts(model, shape, dtype=jnp.float32):
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1,) + shape),
                           train=False)["params"])
    return dp_lib.ghost_param_counts(
        make_loss_fn(model, "classify"), DPConfig(enabled=True), params,
        jax.ShapeDtypeStruct(shape, jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32)), params


def _ghost_paths(loss_fn, case):
    """(paths of the leaves that take the Gram form, all paths, the
    predicate's own answer)."""
    ghost = dp_lib._product_leaves(loss_fn, case.params, case.x[0], case.y[0])
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(case.params)[0]]
    return sorted(paths[i] for i in ghost), paths, ghost


def test_product_leaves_are_recognised_from_the_layer():
    """Which leaves take the Gram form: Dense kernels and the
    patch-embedding kernel, nothing else; LeNet's windowed convolutions
    do not; a loss that is no flax model has none; a kernel the loss
    also uses elsewhere (here: a penalty on it) is materialised."""
    case = _case("vit", jnp.float32)
    found, paths, ghost = _ghost_paths(case.loss_fn, case)
    kernels = sorted(p for p in paths if p.endswith("['kernel']"))
    assert found == kernels
    assert ghost[paths.index("['Conv_0']['kernel']")].shape == (1, 4, 4, 32)
    assert ghost[paths.index("['Dense_0']['kernel']")].shape == (1, 10)

    lenet = _case("lenet", jnp.float32)
    assert _ghost_paths(lenet.loss_fn, lenet)[0] == [
        f"['Dense_{j}']['kernel']" for j in range(3)]

    assert dp_lib._product_leaves(_quadratic_loss, {"w": jnp.ones(8)},
                                  jnp.ones(8), jnp.ones(())) == {}

    def penalised(params, x, y, m):
        return case.loss_fn(params, x, y, m) + 1e-3 * jnp.sum(
            jnp.square(params["Dense_0"]["kernel"]))

    assert _ghost_paths(penalised, case)[0] == [
        p for p in kernels if p != "['Dense_0']['kernel']"]


def test_long_rows_over_a_narrow_product_are_materialised():
    """``T² > d_in·d_out``: forming ``a_iᵀ δ_i`` is the cheaper way, and
    that is what the materialised form does."""

    class Narrow(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.reshape(x.shape[0], -1, 2).astype(jnp.float32)  # T = 18
            x = nn.Dense(3)(x)  # 18² > 2·3
            return nn.Dense(10)(x.reshape(x.shape[0], -1))  # T = 1

    counts, params = _counts(Narrow(), (6, 6, 1))
    assert counts == {"dp_params": 2 * 3 + 3 + 54 * 10 + 10,
                      "dp_ghost_params": 54 * 10}
    # two-pass clipping has no such form: nothing is counted under it
    assert dp_lib.ghost_param_counts(
        make_loss_fn(Narrow(), "classify"),
        DPConfig(enabled=True, clipping="two_pass"), params,
        jax.ShapeDtypeStruct((6, 6, 1), jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32))["dp_ghost_params"] == 0


def test_ghost_param_share_of_vit_b16_by_hand():
    """``dp_ghost_params / dp_params`` by the trainer's predicate, from
    ``jax.eval_shape`` of ViT-B/16 at its published widths, against the
    share counted by hand: 99.68 %."""
    model = build_model("vit_b16", 1000, compute_dtype=jnp.bfloat16)
    counts, _ = _counts(model, (224, 224, 3))
    block = 768 * 2304 + 768 * 768 + 768 * 3072 + 3072 * 768
    products = 16 * 16 * 3 * 768 + 12 * block + 768 * 1000
    others = (768 + 12 * (2304 + 768 + 3072 + 768 + 4 * 768) + 2 * 768
              + 1000 + 768 + 197 * 768)
    assert counts == {"dp_params": products + others,
                      "dp_ghost_params": products}
    assert 100.0 * products / (products + others) == pytest.approx(
        99.682, abs=1e-3)


def test_compiled_step_forms_no_per_example_kernel_gradient():
    """The disease a slow result would have: the backward pass forming
    ``[microbatch, *kernel.shape]`` for a product leaf. One DP step of a
    reduced ViT (2 layers, hidden 64), compiled here: the only arrays of
    such a shape are ``dp_clip``'s per-example products on their way
    into the weighted sum (float32; the TPU compiler fuses them away,
    ``tests/test_sparse_attention_kernel.py``), never a result of the
    model's backward pass, and none is carried from one instruction
    scope to the other; the materialised leaves' ``[microbatch, ...]``
    gradients are there."""
    import re

    mb = 4
    model = build_model("vit_b16", 10, image_size=32, patch_size=8,
                        hidden=64, layers=2, heads=2, mlp_dim=256)
    counts, params = _counts(model, (32, 32, 3))
    assert counts["dp_ghost_params"] > 0.9 * counts["dp_params"]
    loss_fn = make_loss_fn(model, "classify")
    fn = jax.jit(dp_lib.make_dp_grad_fn(loss_fn, DPConfig(
        enabled=True, l2_clip=1.0, noise_multiplier=1.0, microbatch_size=mb)))
    text = fn.lower(
        params, jax.ShapeDtypeStruct((2 * mb, 32, 32, 3), jnp.uint8),
        jax.ShapeDtypeStruct((2 * mb,), jnp.int32),
        jax.ShapeDtypeStruct((2 * mb,), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    made = {}  # shape -> op_names of the instructions that produce it
    for dims, rest in re.findall(r"= [a-z]+[0-9]+\[([0-9,]+)\]\S* (.*)", text):
        if rest.startswith("parameter("):  # of a fused computation
            continue
        name = re.search(r'op_name="([^"]*)"', rest)
        made.setdefault(tuple(int(d) for d in dims.split(",")), set()).add(
            name.group(1) if name else "")
    ghost = dp_lib._product_leaves(
        loss_fn, params, jax.ShapeDtypeStruct((32, 32, 3), jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert len(ghost) == 2 + 2 * 4
    for i in ghost:
        leaf = jax.tree.leaves(params)[i]
        d_out = leaf.shape[-1]
        for form in ((mb,) + leaf.shape, (mb, leaf.size // d_out, d_out),
                     (mb, d_out, leaf.size // d_out)):
            for name in made.get(form, ()):
                assert "dp_clip" in name and "ViT" not in name, (form, name)
    # the reader sees materialised gradients: LayerNorm's [mb, 64]
    assert any("transpose(jvp(ViT))" in n for s_ in ((mb, 64), (mb, 1, 64))
               for n in made.get(s_, ()))


@pytest.mark.parametrize("mb,t,d_in,d_out", [
    (4, 37, 256, 384),    # rows that fill no whole tile; one tile each way
    (3, 8, 1536, 128),    # two tiles of 768 over d_in
    (2, 197, 128, 896),   # ViT's 197 rows; tiles of 128 over d_out
])
def test_post_scaled_kernel_matches_the_sum_in_float64(mb, t, d_in, d_out):
    """The TPU form of the weighted product (interpret mode here; Mosaic
    takes it in ``tests/test_sparse_attention_kernel.py``) against
    ``Σ_i s_i a_iᵀ δ_i`` in float64, beside the form every backend runs,
    alone and under a ``vmap`` over clients."""
    rng = np.random.default_rng(mb)
    a = jnp.asarray(rng.normal(size=(2, mb, t, d_in)), jnp.bfloat16)
    d = jnp.asarray(rng.normal(size=(2, mb, t, d_out)), jnp.bfloat16)
    s = jnp.asarray(rng.uniform(size=(2, mb)), jnp.float32)
    want = np.einsum("cb,cbti,cbto->cio", *(np.asarray(v, np.float64)
                                            for v in (s, a, d)))
    kernel = functools.partial(dp_lib._post_scaled_product, interpret=True)
    assert _rel_l2(kernel(a[0], d[0], s[0]), want[0]) < 1e-6
    assert _rel_l2(jax.vmap(kernel)(a, d, s), want) < 1e-6
    assert _rel_l2(dp_lib._weighted_product(a[0], d[0], s[0]), want[0]) < 1e-6
