"""models/mellum2.py, ops/band_attention.py and ops/moe.py against the
plain reference (tests/reference/mellum2_decoder.py) at a small size on
the CPU: hidden 64, two periods of (sliding, full), a window of 20 at
T = 48 in tiles of 16 (longer than twice the window, so the band bites),
8 experts of which 2 are held, a vocabulary slice of 8."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.client.trainer import make_local_train_fn
from colearn_federated_learning_tpu.config import (
    ClientConfig,
    DPConfig,
    resolve_config,
)
from colearn_federated_learning_tpu.models import axk1, build_model, keye, mellum2
from colearn_federated_learning_tpu.ops import moe

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(HERE, "reference", "mellum2_decoder.py"),
            "mellum2_ref")

SIZES = dict(vocab_size=8, seq_len=48, layers=4, period=("sliding", "full"),
             hidden=64, heads=4, kv_heads=2, head_dim=16, num_experts=8,
             experts_held=2, expert_offset=2, experts_per_token=3,
             expert_width=32, sliding_window=20, rope_theta=100.0,
             rope_factor=4.0, rope_original=64, rope_beta_fast=4.0,
             rope_beta_slow=1.0, rope_attention_factor=1.2, rms_eps=1e-6)
TILES = dict(q_chunk=16, moe_tile=4)
PUBLISHED = dict(head_dim=128, rope_theta=500000.0, rope_factor=16.0,
                 rope_original=8192, rope_beta_fast=32.0, rope_beta_slow=1.0,
                 rope_attention_factor=1.2772588722239782)


@pytest.fixture(scope="module")
def setup():
    model = build_model("mellum2_decoder", 0, **TILES, **SIZES)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 8)
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 48), 0, 8)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # larger matrices than the init's 0.02, so that routing and both
    # kinds of attention are far from their degenerate values
    params = {k: v * 5 if v.ndim >= 2 and "norm" not in k else v
              for k, v in params.items()}
    return model, params, tokens, targets


@pytest.fixture(scope="module")
def model_side(setup):
    """One compiled program for the tests that read the model's side:
    (logits, aux, loss a sequence, gradient of the losses' sum, the
    counters' names in the model's order)."""
    model, params, tokens, targets = setup
    order = []  # of the counters as the model returns them: jit sorts a dict

    def loss(params):
        logits, aux = model.apply({"params": params}, tokens, train=True)
        order[:] = aux["counters"]
        logp = jax.nn.log_softmax(logits, -1)
        loss = -jnp.take_along_axis(
            logp, targets[..., None], -1)[..., 0].mean(-1)
        return loss.sum(), (logits, aux, loss)

    (_, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return (*out, grads, tuple(order))


@pytest.fixture(scope="module")
def reference_side(setup):
    """The reference's (logits, chosen experts, loss, gradient), one
    compiled program for both sequences."""
    _, params, tokens, targets = setup

    @jax.jit
    def run(params, tokens, targets):
        logits, chosen = ref.forward(params, tokens, SIZES, jnp.float32)
        loss, grads = jax.value_and_grad(ref.loss)(
            params, tokens, targets, SIZES, jnp.float32)
        return logits, chosen, loss, grads

    return [run(params, tokens[b], targets[b]) for b in range(2)]


def test_logits_loss_and_counters_match_the_reference(model_side,
                                                      reference_side, setup):
    model = setup[0]
    logits, aux, loss, _, counters = model_side
    assert "loss" not in aux
    for b, (r_logits, chosen, r_loss, _) in enumerate(reference_side):
        np.testing.assert_allclose(logits[b], r_logits, atol=2e-5)
        np.testing.assert_allclose(loss[b], r_loss, rtol=1e-5)
        held = (chosen >= 2) & (chosen < 4)
        np.testing.assert_allclose(aux["counters"]["held_assignment_share"][b],
                                   held.mean(), rtol=1e-5)
    assert counters == model.aux_counters
    # T = 48, window 20 in tiles of 16: 210 + 28 x 20 = 770 kept pairs;
    # the three query tiles visit 1 + 2 + 3 key tiles of 256 pairs
    np.testing.assert_allclose(aux["counters"]["band_pair_share"],
                               770 / (6 * 256), rtol=1e-6)


def test_gradients_of_every_leaf_match_the_reference(model_side,
                                                     reference_side, setup):
    params = setup[1]
    got = model_side[3]
    want = jax.tree.map(jnp.add, *(side[3] for side in reference_side))
    assert set(got) == set(want) == set(params)
    for name in params:
        scale = float(jnp.abs(want[name]).max())
        if name == "layers_router":  # a share's gates are constants
            assert scale == 0 and not np.any(np.asarray(got[name]))
            continue
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name],
                                   atol=3e-5 * scale, err_msg=name)


@pytest.mark.parametrize("wrong", ["triangle", "one_rope"])
def test_the_comparison_sees_a_triangle_for_a_band_and_one_rope_for_two(
        setup, model_side, wrong, monkeypatch):
    """What the benchmark's two controls change in the reference moves
    the logits far beyond the agreement above."""
    _, params, tokens, _ = setup
    logits = model_side[0]
    if wrong == "triangle":
        monkeypatch.setattr(ref, "window_of", lambda kind, sizes: None)
    else:
        by_kind = ref.rope_of
        monkeypatch.setattr(ref, "rope_of", lambda kind, pos, sizes: by_kind(
            "sliding", pos, sizes))
    r_logits, _ = ref.forward(params, tokens[0], SIZES, jnp.float32)
    assert float(jnp.abs(logits[0] - r_logits).max()) > 1e-2


def test_a_sliding_layer_does_not_see_beyond_its_window(setup):
    """Two sequences that differ only at positions more than the window
    before a query give that query the same output row in a sliding
    layer, and another in a full layer."""
    model, params, _, _ = setup
    d = model.dims
    p = ref.layer_params(params, 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (48, 64))
    other = x.at[:20].set(jax.random.normal(jax.random.PRNGKey(4), (20, 64)))
    pos = jnp.arange(48)
    angles = keye.rope_angles(pos, 16, 100.0)
    rows = slice(39, 48)  # 39 - 20 + 1 = 20: the first key row 39 reads
    out = {kind: [mellum2.attention_block(p, a, angles, d, kind)
                  for a in (x, other)] for kind in mellum2.KINDS}
    np.testing.assert_array_equal(out["sliding"][0][rows],
                                  out["sliding"][1][rows])
    assert np.abs(out["sliding"][0][38] - out["sliding"][1][38]).max() > 1e-4
    assert np.abs(out["full"][0][rows] - out["full"][1][rows]).max() > 1e-4


def test_yarn_range_and_factor_at_the_published_sizes():
    low, high, freqs = ref.yarn(PUBLISHED)
    assert (low, high) == (18, 35)
    assert axk1.yarn_range(128, 500000.0, 8192, 32.0, 1.0) == (18, 35)
    c = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(500000))
    assert round(c(32), 3) == 18.081 and round(c(1), 3) == 34.984
    assert 0.1 * math.log(16) + 1 == pytest.approx(1.2772588722239782,
                                                   rel=1e-15)
    model = build_model("mellum2_decoder", 0)
    assert model.dims.attention_factor == 1.2772588722239782
    np.testing.assert_allclose(model.full_inv_freq, freqs, rtol=1e-12)
    plain = [500000.0 ** (-2.0 * i / 128) for i in range(64)]
    # below low the published frequency, from high on a sixteenth of it
    np.testing.assert_allclose(freqs[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(freqs[35:], np.asarray(plain[35:]) / 16,
                               rtol=1e-12)
    assert all(p / 16 < f < p for f, p in zip(freqs[19:35], plain[19:35]))


def test_the_two_tables_of_a_sequence(setup):
    """The sliding layers turn by ``rope_angles``' table, the full
    layers by YaRN's; the model's and the reference's agree, and the
    attention factor multiplies cosine and sine."""
    pos = jnp.arange(48)
    sliding, one = ref.rope_of("sliding", pos, SIZES)
    full, factor = ref.rope_of("full", pos, SIZES)
    assert (one, factor) == (1.0, 1.2)
    np.testing.assert_allclose(sliding, keye.rope_angles(pos, 16, 100.0),
                               rtol=1e-6)
    low, high, freqs = ref.yarn(SIZES)
    assert (low, high) == axk1.yarn_range(16, 100.0, 64, 4.0, 1.0) == (1, 5)
    np.testing.assert_allclose(
        freqs, axk1.yarn_inv_freq(16, 100.0, 4.0, 64, 4.0, 1.0), rtol=1e-12)
    assert np.abs(np.asarray(full) - np.asarray(sliding)).max() > 1.0
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 4, 16))
    np.testing.assert_allclose(keye.apply_rope(x, full, 1.2),
                               ref.rotate_half(x, full, 1.2), atol=1e-6)
    np.testing.assert_allclose(keye.apply_rope(x, full, 1.2),
                               1.2 * keye.apply_rope(x, full), atol=1e-6)


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(setup):
    """The share test: 4 chips with 2 experts each against the
    reference's layer with all 8 (no shared expert to count once)."""
    _, params, _, _ = setup
    p = ref.layer_params(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 64))
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
        total, held = total + y, held + float(disp.held_share)
    np.testing.assert_allclose(total, whole, atol=1e-5)
    np.testing.assert_allclose(held, 1.0, rtol=1e-6)


@pytest.mark.parametrize("frozen", [False, True])
def test_experts_of_2304_by_896_over_more_tiles_than_a_call_holds(
        frozen, monkeypatch):
    """``ops/moe.py`` at the published expert shape (18 and 7 lanes wide:
    no powers of two), in interpret mode, with the tiles in use split
    over three kernel calls (an expert's gradient sums pass from call to
    call), against the plain loop over the held experts."""
    t, d, f, tile = 96, 2304, 896, 8
    cd = jnp.bfloat16  # a trained [2304, 896] expert fits VMEM in bfloat16 only
    sizes = dict(SIZES, num_experts=4, experts_per_token=2, rms_eps=0.0)
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    h = ref.rms_norm(jax.random.normal(ks[0], (t, d)), jnp.ones(d),
                     0.0).astype(cd)
    router = (jax.random.normal(ks[1], (d, 4)) * 0.05).astype(cd)
    w = [(jax.random.normal(k, s) * 0.02).astype(cd) for k, s in
         zip(ks[2:], ((2, d, f), (2, d, f), (2, f, d)))]
    # four slots a call
    monkeypatch.setattr(moe, "_ROWS_BYTES", 4 * tile * d * 2)
    disp = moe.route(h, router, top_k=2, experts_held=2, expert_offset=1,
                     tile=tile)
    tables = (disp.row_token, disp.row_gate, disp.tile_expert, disp.n_tiles)
    assert moe._calls(h, *tables[:2], disp.tile_expert,
                      disp.tile_expert)[1] == 4
    assert 8 < int(disp.n_tiles) <= 14  # three or four calls
    ffn = moe.expert_ffn_frozen if frozen else moe.expert_ffn
    gates = jnp.zeros((t, 2), jnp.float32).at[
        disp.row_token, jnp.repeat(disp.tile_expert, tile)].add(disp.row_gate)

    def mine(h, w1, w3, w2):
        return ffn(h, w1, w3, w2, *tables)

    def plain(h, w1, w3, w2):  # every token through every held expert
        y = jnp.zeros((t, d), jnp.float32)
        for e in range(2):
            a = jnp.dot(h, w1[e], preferred_element_type=jnp.float32)
            b = jnp.dot(h, w3[e], preferred_element_type=jnp.float32)
            out = jnp.dot((jax.nn.silu(a) * b).astype(cd), w2[e],
                          preferred_element_type=jnp.float32)
            y = y + gates[:, e, None] * out
        return y.astype(cd)

    # the tables are the reference's own routing
    _, chosen = ref.experts(
        {"mlp_norm": jnp.ones(d), "router": router, "w1": w[0], "w3": w[1],
         "w2": w[2]}, h, sizes, cd, experts_held=2, expert_offset=1)
    assert int(((chosen >= 1) & (chosen < 3)).sum()) == int(disp.counts.sum())

    def close(got, want, name):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=2e-2 * np.abs(want).max(),
                                   err_msg=name)

    close(mine(h, *w), plain(h, *w), "out")
    ct = jax.random.normal(jax.random.PRNGKey(18), (t, d))
    total = lambda fn: lambda *a: (fn(*a).astype(jnp.float32) * ct).sum()  # noqa: E731
    got = jax.grad(total(mine), (0, 1, 2, 3))(h, *w)
    want = jax.grad(total(plain), (0, 1, 2, 3))(h, *w)
    for name, g, wg in zip(("dh", "dw1", "dw3", "dw2"), got, want):
        if frozen and name != "dh":
            assert not np.any(np.asarray(g, np.float32)), name
            continue
        close(g, wg, name)


def test_the_width_rules_at_the_published_expert_shape():
    """What ISSUE 31 computed by ``ops/moe.py``'s own count: a trained
    expert of [2304, 896] at tile 256 needs 90.25 MiB of the kernels' 96,
    16,384 tokens' float32 result goes in two blocks of 1,152 columns,
    and a call has 56 slots."""
    assert moe._width_block(256, 2304, 896, 2, True) == 896
    assert moe._width_block(256, 2304, 896, 2, False) == 896
    assert moe._hidden_block(16384, 256, 2304, 896, 2, 1) == 1152
    assert moe._hidden_block(16384, 256, 2304, 896, 2, 2) == 1152
    assert moe._ROWS_BYTES // (256 * 2304 * 2) == 56


def test_local_metrics_carry_the_counters(setup):
    model, params, tokens, targets = setup
    fn = make_local_train_fn(
        model, ClientConfig(optimizer="adamw", lr=1e-3, batch_size=1),
        DPConfig(), "lm")
    assert fn.aux_names == model.aux_counters
    idx = jnp.arange(2).reshape(2, 1)
    _, metrics = jax.jit(fn)(params, tokens, targets, idx, jnp.ones((2, 1)),
                             jax.random.PRNGKey(0))
    assert set(metrics.aux) == set(model.aux_counters)
    assert 0.0 < float(metrics.aux["expert_tile_fill"]) <= 1.0
    np.testing.assert_allclose(metrics.aux["band_pair_share"], 770 / 1536,
                               rtol=1e-6)


@pytest.mark.parametrize("override,named", [
    ({"model.lora.enabled": True}, "model.lora.enabled"),
    ({"run.cohort_layout": "megabatch"}, "cohort_layout='megabatch'"),
    ({"dp.enabled": True}, "dp.enabled"),
    ({"run.batch_shards": 2}, "run.batch_shards > 1"),
])
def test_config_names_what_the_model_does_not_support(override, named):
    with pytest.raises(ValueError, match="does not support") as err:
        resolve_config("mellum2_silo_lm", override)
    assert named in str(err.value)


@pytest.mark.parametrize("kwargs,message", [
    (dict(layers=6), "whole periods"),
    (dict(period=("sliding", "linear")), "kinds"),
    (dict(heads=30), "multiple of kv_heads"),
    (dict(sliding_window=0), "keeps no key"),
    (dict(expert_offset=60), "not among the router's 64"),
])
def test_the_factory_refuses_sizes_that_make_no_model(kwargs, message):
    with pytest.raises(ValueError, match=message):
        build_model("mellum2_decoder", 0, **kwargs)


def test_named_config_has_the_published_widths():
    cfg = resolve_config("mellum2_silo_lm")
    model = build_model(cfg.model.name, 0, **cfg.model.kwargs)
    d = model.dims
    assert (d.hidden, d.heads, d.kv_heads, d.head_dim) == (2304, 32, 4, 128)
    assert (d.num_experts, d.experts_held, d.experts_per_token,
            d.expert_width) == (64, 8, 8, 896)
    assert (d.window, model.rope_theta) == (1024, 500000.0)
    assert model.period == ("sliding", "sliding", "sliding", "full")
    assert (model.layers, model.vocab_size, model.seq_len) == (4, 12288,
                                                               16384)
    assert model.period == build_model(
        "mellum2_decoder", 0, period="sliding,sliding,sliding,full").period
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 16384), jnp.int32))["params"])
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 340_350_208
    # the stacks stay top-level [layers, ...] leaves
    assert shapes["layers_w1"].shape == (4, 8, 2304, 896)
    assert shapes["layers_wq"].shape == (4, 2304, 4096)


def test_no_score_array_of_a_whole_sequence_exists_at_the_published_size():
    """The loss and its gradient at 16,384 tokens, lowered (nothing
    runs): no array of the program has a ``16384 x 16384`` extent, for
    one head or for all (a head's float32 scores would be 1.07 GB)."""
    model = build_model("mellum2_decoder", 0, compute_dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16384), jnp.int32))["params"])

    def loss(p, tokens):
        return model.apply({"params": p}, tokens, train=True)[0].mean()

    text = jax.jit(jax.value_and_grad(loss)).lower(params, tokens).as_text()
    assert "16384x2304xbf16" in text  # the text does hold the sequence
    assert "16384x16384" not in text


def test_the_benchmarks_reference_is_a_copy_of_this_one():
    marker = "# " + "-" * 75 + "\n"
    mine = open(os.path.join(HERE, "reference", "mellum2_decoder.py")).read()
    theirs = open(os.path.join(HERE, os.pardir, "benchmark", "references",
                               "fedavg_mellum2_lm.py")).read()
    body = mine[mine.index(marker):].rstrip("\n")
    assert body in theirs
    for text in (mine, theirs):
        assert "colearn_federated_learning_tpu.models" not in text
        assert "colearn_federated_learning_tpu.ops" not in text
        assert "build_model" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("window", [None, 20, 7])
def test_the_benchmarks_blocks_of_queries_are_the_plain_softmax(window,
                                                                monkeypatch):
    """``benchmark/references/fedavg_mellum2_lm.py`` puts attention a
    block of queries at a time in ``attention_core``'s place: the same
    values and gradients as the mask over the whole sequence."""
    theirs = _load(os.path.join(HERE, os.pardir, "benchmark", "references",
                                "fedavg_mellum2_lm.py"), "mellum2_bench_ref")
    assert theirs.QUERY_BLOCK == 2048
    monkeypatch.setattr(theirs, "QUERY_BLOCK", 16)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (48, 4, 16))
    k, v = (jax.random.normal(key, (48, 2, 16)) for key in ks[1:])
    assert theirs.attention_core is not theirs.plain_attention_core
    for fn in (lambda f: f(q, k, v, window, jnp.float32),
               lambda f: jax.grad(lambda k: f(q, k, v, window,
                                              jnp.float32).sum())(k)):
        np.testing.assert_allclose(fn(theirs.attention_core),
                                   fn(ref.attention_core), atol=2e-6)
