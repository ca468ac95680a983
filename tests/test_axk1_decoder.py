"""models/axk1.py, ops/latent_attention.py, the sigmoid routing and the
frozen form of ops/moe.py, and the frozen base as data (models/lora.py,
client/trainer.RoundData, the driver) against the plain reference
(tests/reference/axk1_decoder.py) at a small size on the CPU: hidden 64,
4 heads of 16 + 8 / 16, latent ranks 24 / 16, 16 experts in 4 groups of
which 2 are kept and 4 held, top 4, one dense and two expert layers,
T 64, adapters of rank 4."""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from colearn_federated_learning_tpu.client.trainer import RoundData
from colearn_federated_learning_tpu.config import resolve_config
from colearn_federated_learning_tpu.models import axk1, build_model
from colearn_federated_learning_tpu.models.lora import build_lora_model
from colearn_federated_learning_tpu.ops import latent_attention, moe
from colearn_federated_learning_tpu.server.round_driver import Experiment

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(HERE, "reference", "axk1_decoder.py"), "axk1_ref")

MODEL = dict(vocab_size=32, seq_len=64, layers=3, hidden=64, heads=4,
             q_rank=24, kv_rank=16, qk_nope=16, qk_rope=8, v_dim=16,
             dense_width=96, num_experts=16, experts_held=4, expert_offset=4,
             experts_per_token=4, expert_width=32, n_group=4, topk_group=2,
             gate_scale=2.5, rope_theta=10000.0, rope_factor=32.0,
             rope_original=16, rope_beta_fast=32.0, rope_beta_slow=1.0,
             rope_mscale_all_dim=1.0, rms_eps=1e-6)
SIZES = dict(MODEL, lora_rank=4, lora_alpha=8.0)
PUBLISHED = dict(qk_rope=64, rope_theta=10000.0, rope_factor=32.0,
                 rope_original=4096, rope_beta_fast=32.0, rope_beta_slow=1.0)
TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 32)
TARGETS = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 32)


@pytest.fixture(scope="module")
def setup():
    """(facade, frozen base, adapters with B != 0): matrices five times
    the init's 0.02 so that routing and attention are far from uniform."""
    model = build_lora_model(
        build_model("axk1_decoder", 0, q_chunk=16, moe_tile=4, **MODEL),
        "axk1_decoder", rank=4, alpha=8.0, target="attention")
    rng = jax.random.PRNGKey(0)
    frozen = model.init_frozen(rng, TOKENS)
    frozen = {k: v * 5 if v.ndim >= 2 and "norm" not in k and k != "embed"
              else v for k, v in frozen.items()}
    adapters = model.init(rng, TOKENS)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 64))
    adapters = jax.tree_util.tree_map_with_path(
        lambda p, l: (jax.random.normal(next(keys), l.shape) * 0.05
                      if p[-1].key == "lora_b" else l), adapters)
    return model, frozen, adapters


def _model_loss(model, adapters, frozen):
    logits, _ = model.apply({"params": adapters, "frozen": frozen}, TOKENS,
                            train=True)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, TARGETS[..., None], -1)[..., 0].mean()


@pytest.fixture(scope="module")
def model_side(setup):
    """One compiled program for the three tests below: (logits and aux
    of the forward pass, the training loss, its gradient in the
    adapters)."""
    model, frozen, adapters = setup

    @jax.jit
    def run(adapters, frozen):
        logits, aux = model.apply({"params": adapters, "frozen": frozen},
                                  TOKENS)
        loss, grads = jax.value_and_grad(
            lambda a: _model_loss(model, a, frozen))(adapters)
        return logits, aux, loss, grads

    return run(adapters, frozen)


@pytest.fixture(scope="module")
def reference_side(setup):
    """The reference's (logits, (groups, chosen), loss, gradient), one
    compiled program for both sequences."""
    _, frozen, adapters = setup

    @jax.jit
    def run(adapters, frozen, tokens, targets):
        logits, picked = ref.forward(frozen, adapters, tokens, SIZES,
                                     jnp.float32)
        loss, grads = jax.value_and_grad(ref.loss)(
            adapters, frozen, tokens, targets, SIZES, jnp.float32)
        return logits, picked, loss, grads

    return [run(adapters, frozen, TOKENS[b], TARGETS[b]) for b in range(2)]


def test_logits_and_loss_match_the_reference(model_side, reference_side):
    logits, aux, loss, _ = model_side
    assert set(aux) == {"counters"}  # no auxiliary loss
    for b, (want, _, _, _) in enumerate(reference_side):
        np.testing.assert_allclose(logits[b], want, atol=2e-4)
    np.testing.assert_allclose(
        loss, jnp.mean(jnp.stack([side[2] for side in reference_side])),
        rtol=1e-5)


def test_adapter_gradients_match_the_reference(model_side, reference_side):
    got = model_side[3]
    want = jax.tree.map(lambda *g: sum(g) / 2,
                        *(side[3] for side in reference_side))
    assert len(jax.tree.leaves(got)) == 20  # 5 projections x 2 x 2 factors
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-8, err_msg=str(path))


def test_counters_read_the_references_groups_and_experts(model_side,
                                                         reference_side):
    aux = model_side[1]
    for b, (_, (groups, chosen), _, _) in enumerate(reference_side):
        held = (chosen >= 4) & (chosen < 8)
        np.testing.assert_allclose(
            aux["counters"]["held_assignment_share"][b], held.mean(),
            rtol=1e-6)
        np.testing.assert_allclose(  # experts 4-7 are group 1
            aux["counters"]["held_group_hit_share"][b],
            (groups == 1).any(-1).mean(), rtol=1e-6)
        counts = jnp.stack([(chosen == e).sum((1, 2)) for e in range(4, 8)],
                           -1).astype(jnp.float32)  # [layers, held]
        np.testing.assert_allclose(
            aux["counters"]["expert_load_max_over_mean"][b],
            (counts.max(-1) / jnp.maximum(counts.mean(-1), 1.0)).mean(),
            rtol=1e-6)


@pytest.mark.parametrize("case", ["random", "ties"])
def test_groups_and_experts_are_the_references_ties_to_the_lower_index(case):
    h = jax.random.normal(jax.random.PRNGKey(4), (64, 16))
    router = jax.random.normal(jax.random.PRNGKey(5), (16, 16))
    if case == "ties":  # every expert scores alike: groups 0, 1; experts 0-3
        router = jnp.zeros_like(router)
    disp = moe.route(h, router, top_k=4, experts_held=4, expert_offset=4,
                     tile=4, scoring="sigmoid", n_group=4, topk_group=2,
                     gate_scale=2.5)
    gates, chosen, groups = ref.routing(h, router, SIZES)
    np.testing.assert_array_equal(np.sort(disp.groups, -1),
                                  np.sort(groups, -1))
    np.testing.assert_array_equal(disp.experts, chosen)
    if case == "ties":
        np.testing.assert_array_equal(chosen, np.tile(np.arange(4), (64, 1)))
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-6)
    # the held assignments' gates, in the dispatch table
    held = (chosen >= 4) & (chosen < 8)
    np.testing.assert_allclose(disp.row_gate.sum(), (gates * held).sum(),
                               rtol=1e-5)


def test_yarn_blends_between_pairs_10_and_23_at_the_published_sizes():
    assert axk1.yarn_range(64, 10000.0, 4096, 32.0, 1.0) == (10, 23)
    freqs, (low, high) = ref.yarn_frequencies(PUBLISHED)
    assert (low, high) == (10, 23)
    mine = axk1.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(mine, freqs, rtol=1e-12)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(mine[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(mine[23:], plain[23:] / 32.0, rtol=1e-12)
    assert np.all(np.diff(mine) < 0)
    m = axk1.yarn_attention_factor(32.0, 1.0)
    np.testing.assert_allclose(m, 0.1 * np.log(32.0) + 1.0)
    model = build_model("axk1_decoder", 0)
    np.testing.assert_allclose(model.dims.attn_scale, 192 ** -0.5 * m * m)
    np.testing.assert_allclose(model.dims.attn_scale,
                               ref.attention_scale(dict(
                                   PUBLISHED, qk_nope=128,
                                   rope_mscale_all_dim=1.0)))


def test_the_rope_key_is_one_vector_shared_by_the_heads(setup):
    """W_kva gives kv_rank + qk_rope columns, not heads x qk_rope; and
    reordering the heads (W_qb's and W_kvb's column blocks, W_o's row
    blocks) leaves the block's output where it was, which holds only if
    every head reads the same rope key."""
    _, frozen, _ = setup
    p = {k[len("dense_"):]: v for k, v in frozen.items()
         if k.startswith("dense_")}
    assert p["wkva"].shape == (64, 16 + 8)
    d = build_model("axk1_decoder", 0, q_chunk=16, moe_tile=4, **MODEL).dims
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 64))
    angles = jnp.arange(64.0)[:, None] * jnp.asarray(
        axk1.yarn_inv_freq(8, 10000.0, 32.0, 16, 32.0, 1.0), jnp.float32)
    order = jnp.asarray([2, 0, 3, 1])

    def blocks(w, axis, width):
        shape = list(w.shape)
        shape[axis:axis + 1] = [4, width]
        return jnp.take(w.reshape(shape), order, axis).reshape(w.shape)

    shuffled = dict(p, wqb=blocks(p["wqb"], 1, 24),
                    wkvb=blocks(p["wkvb"], 1, 32), wo=blocks(p["wo"], 0, 16))
    np.testing.assert_allclose(
        axk1.attention_block(shuffled, {}, x, angles, d, 0.0),
        axk1.attention_block(p, {}, x, angles, d, 0.0), atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_attention_is_a_dense_causal_softmax_and_so_are_its_gradients(dtype,
                                                                      tol):
    """The three kernels (interpret mode) over 3 x 3 tiles against dense
    [T, T] scores: nope and rope parts summed, one rope key for all
    heads, values of another width."""
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    shapes = ((48, 3, 16), (48, 3, 8), (48, 3, 16), (48, 8), (48, 3, 24))
    ops = [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, shapes)]
    w = jax.random.normal(ks[5], (48, 3, 24))

    def dense(q_n, q_r, k_n, k_r, v):
        f = lambda a: a.astype(jnp.float32)  # noqa: E731
        s = (jnp.einsum("qhd,khd->hqk", f(q_n), f(k_n))
             + jnp.einsum("qhd,kd->hqk", f(q_r), f(k_r))) * 0.3
        s = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), f(v))

    mine = lambda *a: latent_attention.causal_attention(*a, 0.3, 16)  # noqa: E731
    got = mine(*ops)
    assert got.dtype == dtype and got.shape == (48, 3, 24)
    scale = lambda a: tol * max(1.0, float(jnp.abs(a).max()))  # noqa: E731
    want = dense(*ops)
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=scale(want))
    grads = jax.grad(lambda *a: (mine(*a).astype(jnp.float32) * w).sum(),
                     argnums=(0, 1, 2, 3, 4))(*ops)
    wants = jax.grad(lambda *a: (dense(*a) * w).sum(),
                     argnums=(0, 1, 2, 3, 4))(*ops)
    for g, e, o in zip(grads, wants, ops):
        assert g.dtype == dtype and g.shape == o.shape
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   e.astype(jnp.float32), atol=scale(e))
    with pytest.raises(ValueError, match="multiple"):
        latent_attention.causal_attention(*(o[:40] for o in ops), 0.3, 16)


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(
        setup):
    """Four chips with 4 of the 16 experts each: the routed parts of all
    four shares plus the shared expert, counted once, are the reference's
    layer with all 16."""
    _, frozen, _ = setup
    p = {k[len("layers_"):]: v[0] for k, v in frozen.items()
         if k.startswith("layers_")}
    full = {k: jax.random.normal(jax.random.PRNGKey(i), (16,) + p[k].shape[1:])
            * 0.3 for i, k in enumerate(("w1", "w3", "w2"))}
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 64))
    h = ref.rms_norm(x, p["mlp_norm"], 1e-6)
    routed, _, _ = ref.experts({**p, **full}, x, SIZES, experts_held=16,
                               expert_offset=0)
    shared = ref.shared_expert(p, x, SIZES)
    total, held = jnp.zeros_like(routed), 0.0
    for share in range(4):
        lo = 4 * share
        mine = {**p, **{k: v[lo:lo + 4] for k, v in full.items()}}
        d = build_model("axk1_decoder", 0, q_chunk=16, moe_tile=4,
                        **dict(MODEL, expert_offset=lo)).dims
        y, stats = axk1.expert_block(mine, h, d, frozen=True)
        one, _, _ = ref.experts(mine, x, SIZES, experts_held=4,
                                expert_offset=lo)
        np.testing.assert_allclose(y - shared, one, atol=1e-5)
        total, held = total + (y - shared), held + float(stats[0])
    np.testing.assert_allclose(total + shared, routed + shared, atol=2e-5)
    np.testing.assert_allclose(held, 1.0, rtol=1e-6)


def test_frozen_experts_give_the_trained_forms_row_gradients_and_no_more():
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    h = jax.random.normal(ks[0], (32, 16))
    router = jax.random.normal(ks[1], (16, 8))
    w = [jax.random.normal(k, s) * 0.3 for k, s in
         zip(ks[2:], ((4, 16, 12), (4, 16, 12), (4, 12, 16)))]
    disp = moe.route(h, router, top_k=3, experts_held=4, expert_offset=2,
                     tile=4)
    tables = (disp.row_token, disp.row_gate, disp.tile_expert, disp.n_tiles)
    np.testing.assert_array_equal(moe.expert_ffn_frozen(h, *w, *tables),
                                  moe.expert_ffn(h, *w, *tables))

    def total(ffn):
        return lambda h, w1, w3, w2, gate: (ffn(
            h, w1, w3, w2, tables[0], gate, *tables[2:]) ** 2).sum()

    args = (h, *w, disp.row_gate)
    trained = jax.grad(total(moe.expert_ffn), argnums=(0, 1, 2, 3, 4))(*args)
    frozen = jax.grad(total(moe.expert_ffn_frozen),
                      argnums=(0, 1, 2, 3, 4))(*args)
    np.testing.assert_array_equal(frozen[0], trained[0])  # rows
    np.testing.assert_array_equal(frozen[4], trained[4])  # gates
    for got, had in zip(frozen[1:4], trained[1:4]):
        assert not np.any(np.asarray(got)) and np.any(np.asarray(had))
    # and its kernel runs five products a tile (two of them the forward's,
    # again) where the trained form's runs eight
    dy = jnp.ones_like(h)
    for call, products in ((moe._backward_rows, 5),
                           (moe._backward_trained, 8)):
        text = str(jax.make_jaxpr(functools.partial(call, 4))(
            h, *w, *tables[:2], tables[2], tables[2], tables[3], dy))
        assert text.count("dot_general") == products


def test_with_b_zero_the_first_forward_is_the_base_models(setup):
    model, frozen, _ = setup
    adapters = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    assert all(not np.any(np.asarray(l)) == (p[-1].key == "lora_b")
               for p, l in jax.tree_util.tree_flatten_with_path(adapters)[0])
    got, _ = model.apply({"params": adapters, "frozen": frozen}, TOKENS)
    base, _ = model.base.apply({"params": frozen}, TOKENS)
    np.testing.assert_array_equal(got, base)
    want, _ = ref.forward(frozen, None, TOKENS[0], SIZES, jnp.float32)
    np.testing.assert_allclose(base[0], want, atol=2e-4)


# ---------------------------------------------------------------------------
# through the driver: the frozen base as an argument of the round program
# ---------------------------------------------------------------------------

_SMALL = {f"model.kwargs.{k}": v for k, v in MODEL.items()}
_SMALL.update({
    "model.kwargs.q_chunk": 16, "model.kwargs.moe_tile": 4,
    "model.lora.rank": 4, "model.lora.alpha": 8.0,
    "run.compute_dtype": "float32", "run.local_param_dtype": "",
    "server.cohort_size": 2, "server.num_rounds": 4, "run.out_dir": "",
    "data.synthetic_train_size": 16, "data.synthetic_test_size": 8,
    "client.lr": 1e-2, "run.obs.executables": True,
})


@pytest.fixture(scope="module")
def one_round():
    """(experiment, adapters before, state after one run_round, the base
    as it was before the round, the round programs' compiled texts)."""
    exp = Experiment(resolve_config("axk1_silo_lora", _SMALL), echo=False)
    state = exp.init_state()
    before = jax.device_get(state["params"])
    base_before = jax.device_get(exp.frozen_base)
    state, texts = _run_round(exp, state)
    return exp, before, state, base_before, texts


def _run_round(exp, state):
    """One run_round with the executable registry installed, as fit
    installs it: (state, compiled texts of the round programs)."""
    from colearn_federated_learning_tpu.obs import executables

    executables.install(exp._exec_reg)
    try:
        state = exp.run_round(exp._place_state(state), 0)
    finally:
        executables.uninstall()
    return state, [e["compiled"].as_text()
                   for e in exp._exec_reg._cache.values()
                   if e["name"].startswith("round.")]


def test_one_round_through_run_round_is_the_references_round(one_round):
    from colearn_federated_learning_tpu.data.loader import mask_from_spec

    exp, before, state, _, _ = one_round
    frozen = exp.frozen_base
    cohort, idx, mask, n_ex, _ = exp._host_inputs(0)
    mask = np.asarray(mask_from_spec(mask, exp.shape))
    idx = np.asarray(idx)
    opt = optax.adamw(1e-2, weight_decay=0.01)
    x_all, y_all = jnp.asarray(exp.fed.train_x), jnp.asarray(exp.fed.train_y)

    def batch_loss(adapters, rows):
        return jnp.mean(jnp.stack([
            ref.loss(adapters, frozen, x_all[r], y_all[r], SIZES, jnp.float32)
            for r in rows]))

    # one compiled program for every client's every step
    step = jax.jit(jax.value_and_grad(batch_loss))
    delta = jax.tree.map(jnp.zeros_like, before)
    losses, weights = [], []
    for c in range(len(cohort)):
        local, opt_state, client_loss = before, opt.init(before), []
        for s in range(idx.shape[1]):
            assert mask[c, s].all()
            value, grads = step(local, jnp.asarray(idx[c, s]))
            updates, opt_state = opt.update(grads, opt_state, local)
            local = optax.apply_updates(local, updates)
            client_loss.append(float(value))
        w = float(n_ex[c])
        delta = jax.tree.map(lambda d, l, p: d + w * (l - p), delta, local,
                             before)
        losses.append(np.mean(client_loss))
        weights.append(w)
    want = jax.tree.map(lambda p, d: p + d / sum(weights), before, delta)
    moved = 0.0
    for (path, g), w, b in zip(
            jax.tree_util.tree_flatten_with_path(state["params"])[0],
            jax.tree.leaves(want), jax.tree.leaves(before)):
        np.testing.assert_allclose(g, w, atol=2e-5, err_msg=str(path))
        moved += float(jnp.abs(w - b).sum())
    assert moved > 0.1  # two steps: B moved, then A
    np.testing.assert_allclose(
        float(state["_metrics"].train_loss),
        np.average(losses, weights=weights), rtol=1e-5)
    aux = state["_metrics"].aux
    assert set(aux) == set(axk1.AUX_COUNTERS)
    assert 0.0 < float(aux["held_assignment_share"]) < 1.0


def _base_shapes(exp):
    return {tuple(l.shape) for l in jax.tree.leaves(exp.frozen_base)
            if l.ndim >= 2}


def test_the_base_is_unchanged_and_out_of_everything_that_handles_params(
        one_round, tmp_path):
    exp, before, state, base_before, _ = one_round
    jax.tree.map(np.testing.assert_array_equal, exp.frozen_base, base_before)
    # a pure function of run.seed: another process draws the same base
    twin = Experiment(resolve_config("axk1_silo_lora", _SMALL), echo=False)
    twin.init_state()
    jax.tree.map(np.testing.assert_array_equal, twin.frozen_base, base_before)
    # the state (params, server optimizer, metrics) holds adapters only
    names = {p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(state["params"])[0]}
    assert names == {"lora_a", "lora_b"}
    n_adapters = sum(l.size for l in jax.tree.leaves(before))
    assert n_adapters == 3 * 4 * (64 + 24 + 24 + 96 + 64 + 24 + 16 + 128
                                  + 64 + 64)
    assert sum(np.size(l) for l in jax.tree.leaves(
        {k: v for k, v in state.items() if k != "_metrics"})) < 2 * n_adapters
    # the wire stack, the client ledger and a checkpoint: a run with all
    # three on keeps every array it handles adapter-sized
    over = dict(_SMALL, **{
        "server.aggregator": "median", "run.obs.client_ledger.enabled": True,
        "server.checkpoint_every": 1, "server.num_rounds": 1,
        "run.out_dir": str(tmp_path)})
    exp2 = Experiment(resolve_config("axk1_silo_lora", over), echo=False)
    final = exp2.fit()
    shapes = _base_shapes(exp2)
    for leaf in jax.tree.leaves(final):
        assert tuple(np.shape(leaf)) not in shapes
        assert tuple(np.shape(leaf))[1:] not in shapes  # [K, ...] stacks
    from colearn_federated_learning_tpu.utils.checkpoint import (
        CheckpointStore,
    )

    store = CheckpointStore(os.path.join(exp2._run_dir(), "ckpt"))
    restored, _ = store.restore(template=exp2.init_state())
    store.close()
    assert sum(np.size(l) for l in jax.tree.leaves(restored["params"])) \
        == n_adapters
    size = sum(os.path.getsize(os.path.join(d, f)) for d, _, fs in
               os.walk(os.path.join(exp2._run_dir(), "ckpt")) for f in fs)
    assert size < sum(l.nbytes for l in jax.tree.leaves(exp2.frozen_base))


@pytest.mark.parametrize("named", ["axk1_silo_lora", "bert_lora_federated",
                                   "vit_lora_dp"])
def test_the_round_program_takes_the_base_as_an_argument(named, one_round):
    """No constant of a base kernel's shape in the lowered round program;
    the base's leaves are among its parameters."""
    if named == "axk1_silo_lora":
        exp, texts = one_round[0], one_round[4]
    else:
        over = {"server.cohort_size": 2, "server.num_rounds": 2,
                "run.out_dir": "", "data.synthetic_train_size": 64,
                "data.synthetic_test_size": 16, "data.num_clients": 4,
                "data.max_examples_per_client": 16, "client.batch_size": 4,
                "run.obs.executables": True}
        if named == "vit_lora_dp":
            # a base of two narrow layers has kernels to look for as
            # well as one of twelve at the published width
            over.update({"model.kwargs.image_size": 32,
                         "model.kwargs.patch_size": 8,
                         "model.kwargs.hidden": 64, "model.kwargs.layers": 2,
                         "model.kwargs.heads": 2, "model.kwargs.mlp_dim": 128,
                         "model.num_classes": 10, "dp.microbatch_size": 2})
        exp = Experiment(resolve_config(named, over), echo=False)
        _, texts = _run_round(exp, exp.init_state())
    assert texts
    wanted = {",".join(map(str, s)) for s in _base_shapes(exp)}
    for text in texts:
        constants = set(re.findall(
            r"= \w+\[(\d+(?:,\d+)*)\]\S* constant\(", text))
        assert constants and not constants & wanted
        parameters = set(re.findall(
            r"= \w+\[(\d+(?:,\d+)*)\]\S* parameter\(", text))
        assert wanted <= parameters


# ---------------------------------------------------------------------------
# the named config
# ---------------------------------------------------------------------------


def test_named_config_has_the_published_widths_and_both_counts():
    cfg = resolve_config("axk1_silo_lora")
    exp_model = build_model(cfg.model.name, 0, **cfg.model.kwargs)
    d = exp_model.dims
    assert (d.hidden, d.heads, d.q_rank, d.kv_rank, d.qk_nope, d.qk_rope,
            d.v_dim, d.dense_width, d.num_experts, d.experts_per_token,
            d.expert_width, d.n_group, d.topk_group, d.gate_scale) == (
        7168, 64, 1536, 512, 128, 64, 128, 18432, 192, 8, 2048, 8, 4, 2.5)
    assert (exp_model.layers, d.experts_held, exp_model.vocab_size) == (
        5, 12, 20480)
    model = build_lora_model(exp_model, cfg.model.name, cfg.model.lora.rank,
                             cfg.model.lora.alpha, cfg.model.lora.target)
    x = jnp.zeros((1, 4096), jnp.int32)
    count = lambda t: sum(int(np.prod(s.shape))  # noqa: E731
                          for s in jax.tree.leaves(t))
    assert count(jax.eval_shape(
        lambda: model.init_frozen(jax.random.PRNGKey(0), x))) == 3_491_257_344
    assert count(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x))) == 5_002_240


@pytest.mark.parametrize("override,named", [
    ({"run.cohort_layout": "megabatch"}, "cohort_layout='megabatch'"),
    ({"dp.enabled": True}, "dp.enabled"),
    ({"run.batch_shards": 2}, "batch_shards"),
])
def test_config_names_what_the_model_does_not_support(override, named):
    with pytest.raises(ValueError, match="does not support") as err:
        resolve_config("axk1_silo_lora", override)
    assert named in str(err.value)


def test_the_benchmarks_reference_is_a_copy_of_this_one():
    marker = "# " + "-" * 75 + "\n"
    mine = open(os.path.join(HERE, "reference", "axk1_decoder.py")).read()
    theirs = open(os.path.join(HERE, os.pardir, "benchmark", "references",
                               "fedavg_axk1_lora.py")).read()
    body = mine[mine.index(marker):].rstrip("\n")
    assert body in theirs
    for text in (mine, theirs):
        assert "colearn_federated_learning_tpu.models" not in text
        assert "colearn_federated_learning_tpu.ops" not in text
        assert "build_model" not in text.split('"""', 2)[2]
