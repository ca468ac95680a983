"""models/keye.py, ops/moe.py and ops/sparse_attention.py against the plain
reference (tests/reference/keye_decoder.py) at a small size on the CPU:
hidden 64, 8 experts of which 2 are held, top-8 keys at T = 32, a
vocabulary slice of 8."""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.client.trainer import (
    make_local_train_fn,
    make_loss_fn,
)
from colearn_federated_learning_tpu.config import (
    ClientConfig,
    DPConfig,
    resolve_config,
)
from colearn_federated_learning_tpu.models import build_model, keye
from colearn_federated_learning_tpu.ops import moe, sparse_attention

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(HERE, "reference", "keye_decoder.py"), "keye_ref")

SIZES = dict(vocab_size=8, seq_len=32, layers=2, hidden=64, heads=4,
             kv_heads=2, head_dim=16, num_experts=8, experts_held=2,
             expert_offset=2, experts_per_token=3, expert_width=32,
             index_heads=4, index_head_dim=8, index_topk=8, rope_theta=1e7,
             mrope_section=(2, 3, 3), rms_eps=1e-6)
INDEXER_LEAVES = {"layers_idx_wq", "layers_idx_wk", "layers_idx_ww",
                  "layers_idx_k_norm_scale", "layers_idx_k_norm_bias"}


@pytest.fixture(scope="module")
def setup():
    model = build_model("keye_decoder", 0, q_chunk=8, moe_tile=4, **SIZES)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 8)
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 8)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # larger matrices than the init's 0.02, so that routing, selection
    # and both losses are far from their degenerate values
    params = {k: v * 5 if v.ndim >= 2 and "norm" not in k else v
              for k, v in params.items()}
    return model, params, tokens, targets


def _model_losses(model, params, tokens, targets):
    logits, aux = model.apply({"params": params}, tokens, train=True)
    logp = jax.nn.log_softmax(logits, -1)
    lm = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].mean(-1)
    return lm, aux["loss"]


@pytest.fixture(scope="module")
def model_side(setup):
    """One compiled program for the three tests below: (logits, aux,
    both losses, each loss's gradient)."""
    model, params, tokens, targets = setup

    def losses(params):
        logits, aux = model.apply({"params": params}, tokens, train=True)
        logp = jax.nn.log_softmax(logits, -1)
        lm = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0].mean(-1)
        return (lm.sum(), aux["loss"].sum()), (logits, aux, lm)

    @jax.jit
    def run(params):
        # one forward pass, and a backward pass for each loss
        _, pull, (logits, aux, lm) = jax.vjp(losses, params, has_aux=True)
        grads = [pull(ct)[0] for ct in ((1.0, 0.0), (0.0, 1.0))]
        return logits, aux, lm, aux["loss"], grads

    return run(params)


@pytest.fixture(scope="module")
def reference_side(setup):
    """The reference's forward, losses and gradient of their sum, one
    compiled program a sequence."""
    _, params, tokens, targets = setup

    @jax.jit
    def run(params, tokens, targets):
        logits, li, _ = ref.forward(params, tokens, SIZES, jnp.float32)
        (_, (lm, li2)), grads = jax.value_and_grad(
            lambda p: ref.losses(p, tokens, targets, SIZES, jnp.float32),
            has_aux=True)(params)
        return logits, lm, li, li2, grads

    return [run(params, tokens[b], targets[b]) for b in range(2)]


# reads the model's side alone, so it comes first: the two sides are
# compiled inside two tests, not one
def test_the_indexer_learns_from_its_own_loss_only(model_side, setup):
    params = setup[1]
    g_lm, g_li = model_side[4]
    for name in params:
        if name in INDEXER_LEAVES:
            assert not np.any(np.asarray(g_lm[name])), name
            assert np.any(np.asarray(g_li[name])), name
        elif name == "layers_router":
            # 2 of 8 experts held: the gates are constants (ops/moe.route)
            assert not np.any(np.asarray(g_lm[name])), name
            assert not np.any(np.asarray(g_li[name])), name
        else:
            assert not np.any(np.asarray(g_li[name])), name
            assert np.any(np.asarray(g_lm[name])), name


def test_logits_and_both_losses_match_the_reference(model_side,
                                                    reference_side, setup):
    model = setup[0]
    logits, aux, lm, li, _ = model_side
    for b, (r_logits, r_lm, r_li, r_li2, _) in enumerate(reference_side):
        np.testing.assert_allclose(logits[b], r_logits, atol=2e-4)
        np.testing.assert_allclose(lm[b], r_lm, rtol=1e-5)
        np.testing.assert_allclose(li[b], r_li, rtol=1e-5)
        np.testing.assert_allclose(r_li, r_li2, rtol=1e-6)
    assert set(aux["counters"]) == set(model.aux_counters)
    # T = 32, top-8: sum_t min(t + 1, 8) = 228 of 528 causal pairs
    np.testing.assert_allclose(aux["counters"]["selected_key_share"],
                               228 / 528, rtol=1e-6)


def test_gradients_of_every_leaf_match_the_reference(model_side,
                                                     reference_side, setup):
    params = setup[1]
    g_lm, g_li = model_side[4]
    got = jax.tree.map(jnp.add, g_lm, g_li)
    want = jax.tree.map(jnp.add, *(side[4] for side in reference_side))
    assert set(got) == set(want) == set(params)
    for name in params:
        scale = float(jnp.abs(want[name]).max())
        if name == "layers_router":  # a share's gates are constants
            assert scale == 0 and not np.any(np.asarray(got[name]))
            continue
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name],
                                   atol=3e-5 * scale, err_msg=name)


@pytest.mark.parametrize("case", ["ties", "zeros", "random", "short"])
def test_selection_is_the_exact_top_k_with_ties_to_the_lower_key(case):
    t, topk = 48, 8
    rng = np.random.default_rng(3)
    if case == "ties":  # few distinct values: every row's threshold ties
        scores = rng.integers(-2, 3, (t, t)).astype(np.float32)
    elif case == "zeros":  # relu's exact zeros, of both signs
        scores = np.where(rng.random((t, t)) < 0.7, 0.0,
                          rng.normal(size=(t, t))).astype(np.float32)
        scores[::2] *= -1.0  # -0.0 and +0.0 are one value
    elif case == "random":
        scores = rng.normal(size=(t, t)).astype(np.float32)
    else:  # no row has more than topk causal keys
        t = 8
        scores = rng.normal(size=(t, t)).astype(np.float32)
    causal = np.tril(np.ones((t, t), bool))
    got = sparse_attention.select_topk(jnp.asarray(scores) + 0.0,
                                       jnp.asarray(causal), topk)
    want = ref.selection(jnp.asarray(scores), topk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got).sum(-1) == np.minimum(np.arange(t) + 1,
                                                  topk)).all()
    # a later chunk of queries against the keys it can see
    lo = t // 2
    got = sparse_attention.select_topk(jnp.asarray(scores[lo:]) + 0.0,
                                       jnp.asarray(causal[lo:]), topk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[lo:])


def test_mrope_on_equal_streams_is_rope_and_sections_pick_their_stream():
    pos = jnp.arange(32)
    equal = keye.mrope_angles(jnp.stack([pos, pos, pos]), 16, 1e7, (2, 3, 3))
    np.testing.assert_array_equal(equal, keye.rope_angles(pos, 16, 1e7))
    np.testing.assert_allclose(
        equal, ref.mrope_angles(jnp.stack([pos] * 3), 16, 1e7, (2, 3, 3)),
        rtol=1e-6)
    streams = jnp.stack([pos, 2 * pos, 3 * pos])
    mixed = keye.mrope_angles(streams, 16, 1e7, (2, 3, 3))
    np.testing.assert_allclose(mixed[:, :2], equal[:, :2], rtol=1e-6)
    np.testing.assert_allclose(mixed[:, 2:5], 2 * equal[:, 2:5], rtol=1e-6)
    np.testing.assert_allclose(mixed[:, 5:], 3 * equal[:, 5:], rtol=1e-6)
    np.testing.assert_allclose(
        mixed, ref.mrope_angles(streams, 16, 1e7, (2, 3, 3)), rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 4, 16))
    np.testing.assert_allclose(keye.apply_rope(x, mixed),
                               ref.rotate_half(x, mixed), atol=1e-6)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(setup):
    """The share test: 4 chips with 2 experts each against the
    reference's layer with all 8."""
    _, params, _, _ = setup
    p = ref.layer_params(params, 0)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 64))
    h = ref.rms_norm(x, p["mlp_norm"], 1e-6)
    full = {k: jax.random.normal(jax.random.PRNGKey(i), (8,) + p[k].shape[1:])
            * 0.3 for i, k in enumerate(("w1", "w3", "w2"))}
    whole, _ = ref.experts({**p, **full}, x, SIZES, jnp.float32,
                           experts_held=8, expert_offset=0)
    total, held = jnp.zeros_like(whole), 0.0
    for share in range(4):
        lo = 2 * share
        y, disp = moe.expert_share(
            h, p["router"], full["w1"][lo:lo + 2], full["w3"][lo:lo + 2],
            full["w2"][lo:lo + 2], top_k=3, expert_offset=lo, tile=4)
        one, _ = ref.experts(
            {**p, **{k: v[lo:lo + 2] for k, v in full.items()}}, x, SIZES,
            jnp.float32, experts_held=2, expert_offset=lo)
        np.testing.assert_allclose(y, one, atol=1e-5)
        assert int(disp.counts.sum()) == round(float(disp.held_share) * 96)
        total, held = total + y, held + float(disp.held_share)
    np.testing.assert_allclose(total, whole, atol=1e-5)
    np.testing.assert_allclose(held, 1.0, rtol=1e-6)


@pytest.mark.parametrize("held", [2, 8])
def test_a_share_holds_its_gates_constant_and_the_whole_layer_does_not(held):
    """A chip with a share of the experts has a share of the gates'
    gradient, which only the exchange would complete: neither the router
    nor the layer's input gets any of it. A chip that holds all 8 trains
    its router. Both as the reference does."""
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    h = jax.random.normal(ks[0], (32, 16))
    router = jax.random.normal(ks[1], (16, 8))
    w = [jax.random.normal(k, s) * 0.3 for k, s in
         zip(ks[2:], ((held, 16, 12), (held, 16, 12), (held, 12, 16)))]
    sizes = dict(SIZES, rms_eps=0.0)

    def mine(h, router):
        return moe.expert_share(h, router, *w, top_k=3, expert_offset=0,
                                tile=4)[0].sum()

    def plain(h, router):
        p = {"mlp_norm": jnp.ones(16), "router": router, "w1": w[0],
             "w3": w[1], "w2": w[2]}
        # the reference normalises its input: hand it rows of unit rms
        return ref.experts(p, h, sizes, jnp.float32, experts_held=held,
                           expert_offset=0)[0].sum()

    h = ref.rms_norm(h, jnp.ones(16), 0.0)
    got = jax.grad(mine, argnums=(0, 1))(h, router)
    assert bool(np.any(np.asarray(got[1]))) == (held == 8)
    want_router = jax.grad(plain, argnums=1)(h, router)
    np.testing.assert_allclose(got[1], want_router, atol=2e-5)


def test_expert_ffn_drops_no_token_when_every_assignment_is_held():
    """All 8 experts held: every one of the T x top_k assignments is
    computed, whatever the load (no capacity)."""
    key = jax.random.PRNGKey(7)
    h = jax.random.normal(key, (16, 8))
    router = jnp.zeros((8, 4)).at[:, 0].set(9.0)  # everything prefers 0
    w = [jax.random.normal(jax.random.PRNGKey(i), s) for i, s in
         enumerate(((4, 8, 6), (4, 8, 6), (4, 6, 8)))]
    y, disp = moe.expert_share(h, router, *w, top_k=2, expert_offset=0,
                               tile=4)
    assert int(disp.counts.sum()) == 32 and float(disp.held_share) == 1.0
    sizes = dict(SIZES, experts_per_token=2)
    want, _ = ref.experts(
        {"mlp_norm": jnp.ones(8), "router": router, "w1": w[0], "w3": w[1],
         "w2": w[2]}, h, dict(sizes, rms_eps=0.0), jnp.float32,
        experts_held=4, expert_offset=0)
    # the reference normalises h; feed the op the same
    hn = ref.rms_norm(h, jnp.ones(8), 0.0)
    y, _ = moe.expert_share(hn, router, *w, top_k=2, expert_offset=0, tile=4)
    np.testing.assert_allclose(y, want, atol=1e-4)


def test_selected_attention_is_a_dense_masked_grouped_softmax():
    """ops/sparse_attention.selected_attention over the kept pairs
    equals a plain softmax over a dense masked score matrix with the
    key-value heads repeated, and its second output the heads' mean of
    those weights."""
    t, heads, kv, hd = 32, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (t, heads, hd))
    k = jax.random.normal(ks[1], (t, kv, hd))
    v = jax.random.normal(ks[2], (t, kv, hd))
    keep = ref.selection(jax.random.normal(ks[3], (t, t)), 8)
    got, weights = sparse_attention.selected_attention(q, k, v, keep)
    kr, vr = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, kr) / np.sqrt(hd)
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(got.reshape(t, heads, hd),
                               jnp.einsum("hqk,khd->qhd", p, vr), atol=2e-5)
    np.testing.assert_allclose(weights, p.mean(0), atol=2e-6)


# jaxpr digests taken at the parent commit (3ff8736): the loss and its
# gradient of every model without an auxiliary loss, and bert_tiny's
# blockwise backend, trace to the program they traced to before
# make_loss_fn learned about (logits, aux).
PARENT_JAXPRS = {
    "bert_tiny-mean": "443ac3e7252efaa0",
    "bert_tiny-sum": "21b53ce50dff517f",
    "bert_tiny-blockwise-mean": "9b3eb60a6845781f",
    "bert_tiny-blockwise-sum": "758f8efaabec4832",
    "resnet18-mean": "76ed8d893be769bf",
    "resnet18-sum": "ecf79c4f62745930",
    "vit_b16-mean": "69888abb7152fb48",
    "vit_b16-sum": "e2f4cd81786b55a1",
}
_TOKENS = jnp.zeros((2, 16), jnp.int32)
_BERT = {"vocab_size": 11, "seq_len": 16, "hidden": 16, "heads": 2,
         "layers": 1, "ff": 32}
_PROGRAMS = {
    "bert_tiny": ("bert_tiny", _BERT, "lm", _TOKENS, _TOKENS),
    "bert_tiny-blockwise": (
        "bert_tiny", dict(_BERT, attention="blockwise", block_size=8), "lm",
        _TOKENS, _TOKENS),
    "resnet18": ("resnet18", {}, "classify",
                 jnp.zeros((2, 32, 32, 3), jnp.uint8),
                 jnp.zeros((2,), jnp.int32)),
    "vit_b16": ("vit_b16", {"image_size": 16, "patch_size": 8, "hidden": 16,
                            "layers": 1, "heads": 2, "mlp_dim": 32},
                "classify", jnp.zeros((2, 16, 16, 3), jnp.uint8),
                jnp.zeros((2,), jnp.int32)),
}


@pytest.mark.parametrize("pinned", sorted(PARENT_JAXPRS))
def test_programs_of_models_without_an_auxiliary_loss_are_unchanged(pinned):
    program, reduction = pinned.rsplit("-", 1)
    name, kwargs, task, x, y = _PROGRAMS[program]
    model = build_model(name, 10, compute_dtype=jnp.bfloat16, **kwargs)
    x_init = x.astype(jnp.float32) if x.dtype == jnp.uint8 else x
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x_init,
                           train=False)["params"])
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    fn = jax.value_and_grad(make_loss_fn(model, task, reduction))
    text = str(jax.make_jaxpr(fn)(params, x, y, jnp.ones((2,), jnp.float32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_JAXPRS[pinned]


def test_local_metrics_carry_the_counters_and_only_for_this_model(setup):
    model, params, tokens, targets = setup
    fn = make_local_train_fn(
        model, ClientConfig(optimizer="adamw", lr=1e-3, batch_size=1),
        DPConfig(), "lm")
    assert fn.aux_names == model.aux_counters
    idx = jnp.arange(2).reshape(2, 1)
    _, metrics = jax.jit(fn)(params, tokens, targets, idx, jnp.ones((2, 1)),
                             jax.random.PRNGKey(0))
    assert set(metrics.aux) == set(model.aux_counters)
    lm, li = _model_losses(model, params, tokens[:1], targets[:1])
    assert float(metrics.aux["indexer_loss"]) > 0
    assert model.aux_counters[0] == "indexer_loss"  # the trainer's index
    assert model.aux_counters[-1] == "expert_tile_fill"
    assert 0.0 < float(metrics.aux["expert_tile_fill"]) <= 1.0
    plain = make_local_train_fn(
        build_model("bert_tiny", 0, **_BERT), ClientConfig(), DPConfig(),
        "lm")
    assert plain.aux_names == ()


@pytest.mark.parametrize("override,named", [
    ({"model.lora.enabled": True}, "model.lora.enabled"),
    ({"run.cohort_layout": "megabatch"}, "cohort_layout='megabatch'"),
    ({"dp.enabled": True}, "dp.enabled"),
])
def test_config_names_what_the_model_does_not_support(override, named):
    with pytest.raises(ValueError, match="does not support") as err:
        resolve_config("keye_silo_lm", override)
    assert named in str(err.value)


def test_named_config_has_the_published_widths():
    cfg = resolve_config("keye_silo_lm")
    model = build_model(cfg.model.name, 0, **cfg.model.kwargs)
    d = model.dims
    assert (d.hidden, d.heads, d.kv_heads, d.head_dim) == (2048, 32, 4, 128)
    assert (d.num_experts, d.experts_held, d.experts_per_token,
            d.expert_width) == (128, 16, 8, 768)
    assert (d.index_heads, d.index_head_dim, d.index_topk) == (16, 64, 2048)
    assert (model.layers, model.vocab_size, model.seq_len) == (4, 18992, 8192)
    assert model.rope_theta == 1e7 and model.mrope_section == (16, 24, 24)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8192), jnp.int32))["params"])
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 465_391_104


def test_the_benchmarks_reference_is_a_copy_of_this_one():
    marker = "# " + "-" * 75 + "\n"
    mine = open(os.path.join(HERE, "reference", "keye_decoder.py")).read()
    theirs = open(os.path.join(HERE, os.pardir, "benchmark", "references",
                               "fedavg_keye_lm.py")).read()
    body = mine[mine.index(marker):].rstrip("\n")
    assert body in theirs
    for text in (mine, theirs):
        assert "colearn_federated_learning_tpu.models" not in text
        assert "colearn_federated_learning_tpu.ops" not in text
        assert "build_model" not in text.split('"""', 2)[2]
