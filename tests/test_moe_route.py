"""ops/moe.route: its tables against the formulation it replaced (the
per-expert counts as a scatter-add, kept here as the reference), and
what a rematerialised layer that keeps ``"moe_dispatch"`` runs a second
time: nothing of ``route`` where a share of the experts is held, in
each of the three decoders."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.models import build_model
from colearn_federated_learning_tpu.models.lora import build_lora_model
from colearn_federated_learning_tpu.ops import moe
from tests.test_axk1_decoder import MODEL as axk1_sizes
from tests.test_keye_decoder import SIZES as keye_sizes
from tests.test_mellum2_decoder import SIZES as mellum2_sizes
from tests.test_mellum2_decoder import TILES as mellum2_tiles

T, D, TILE = 48, 16, 4


def scatter_add_route(h, w_router, *, top_k, experts_held, expert_offset,
                      tile, scoring="softmax", n_group=1, topk_group=1,
                      gate_scale=1.0):
    """``moe.route`` as it was before PR 32, from the scores on."""
    t = h.shape[0]
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    groups = None
    if scoring == "softmax":
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        gates = top_p / top_p.sum(-1, keepdims=True)
    else:
        top_p, top_e, groups = moe.group_limited_top_k(
            jax.nn.sigmoid(logits), top_k, n_group, topk_group)
        gates = gate_scale * top_p / top_p.sum(-1, keepdims=True)
    n = t * top_k
    rows = n + experts_held * tile
    local = top_e.reshape(n) - expert_offset
    held = (local >= 0) & (local < experts_held)
    local = jnp.where(held, local, experts_held)
    token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    counts = jnp.zeros(experts_held + 1, jnp.int32).at[local].add(1)
    padded = -(-counts[:experts_held] // tile) * tile
    padded_end = jnp.cumsum(padded)
    plain_start = jnp.cumsum(counts) - counts
    order = jnp.argsort(local, stable=True)
    local_s = local[order]
    rank = jnp.arange(n, dtype=jnp.int32) - plain_start[local_s]
    dest = jnp.where(
        local_s < experts_held,
        (padded_end - padded)[jnp.minimum(local_s, experts_held - 1)] + rank,
        rows)
    row_token = jnp.zeros(rows, jnp.int32).at[dest].set(token[order],
                                                        mode="drop")
    row_gate = jnp.zeros(rows, jnp.float32).at[dest].set(
        gates.reshape(n)[order], mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(padded_end, jnp.arange(rows // tile) * tile,
                         side="right"),
        experts_held - 1).astype(jnp.int32)
    return moe.Dispatch(row_token, row_gate, tile_expert,
                        padded_end[-1] // tile, counts[:experts_held],
                        held.mean(dtype=jnp.float32), top_e, groups)


def _router(case):
    """(h, w_router, route's keywords) of one sequence over 8 experts
    (16 in four groups for the sigmoid scores)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    h = jax.random.normal(ks[0], (T, D))
    w = jax.random.normal(ks[1], (D, 16 if case == "sigmoid_groups" else 8))
    kw = dict(top_k=2, experts_held=3, expert_offset=2, tile=TILE)
    if case == "uniform":  # equal scores: ties go to the lower expert id
        w = jnp.zeros_like(w)
    elif case == "one_expert":  # every token's first choice is expert 3
        h = jnp.abs(h)
        w = (-jnp.abs(w)).at[:, 3].set(9.0)
        kw.update(top_k=1)
    elif case == "none_held":  # every choice below the held ones
        h = jnp.abs(h)
        w = (-jnp.abs(w)).at[:, :2].set(9.0)
    elif case == "all_held":
        kw.update(experts_held=8, expert_offset=0)
    elif case == "sigmoid_groups":
        kw.update(top_k=4, experts_held=4, expert_offset=4,
                  scoring="sigmoid", n_group=4, topk_group=2, gate_scale=2.5)
    return h, w, kw


CASES = ["uniform", "one_expert", "none_held", "all_held", "sigmoid_groups",
         "random"]


@pytest.mark.parametrize("case", CASES)
def test_the_dispatch_is_the_scatter_adds_to_the_bit(case):
    h, w, kw = _router(case)
    got = jax.jit(lambda h, w: moe.route(h, w, **kw))(h, w)
    want = jax.jit(lambda h, w: scatter_add_route(h, w, **kw))(h, w)
    for name, a, b in zip(got._fields, got, want):
        assert (a is None) == (b is None), name
        if a is not None:
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_array_equal(a, b, err_msg=name)
    n = T * kw["top_k"]
    if case == "one_expert":
        assert got.counts.tolist() == [0, n, 0]
    if case == "none_held":
        assert int(got.n_tiles) == 0 and not got.counts.any()
    if case == "all_held":
        assert int(got.counts.sum()) == n


def _layer(held, offset):
    """A layer's result as a function of (h, router, w1, w3, w2), with
    the norm and the residual of a decoder's block around the experts."""
    def layer(h, router, w1, w3, w2):
        x = h * jax.lax.rsqrt((h * h).mean(-1, keepdims=True) + 1e-6)
        disp = moe.route(x, router, top_k=2, experts_held=held,
                         expert_offset=offset, tile=TILE)
        y = moe.expert_ffn(x, w1, w3, w2, disp.row_token, disp.row_gate,
                           disp.tile_expert, disp.n_tiles)
        return h + jax.ad_checkpoint.checkpoint_name(y, "moe_out")

    return layer


@pytest.mark.parametrize("held,offset", [(3, 2), (8, 0)])
def test_a_rematerialised_layer_with_the_tables_kept_has_the_plain_gradients(
        held, offset):
    """Two layers, so that the second's cotangent is not a constant; the
    gates take a gradient where every expert is held."""
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    args = [jax.random.normal(ks[0], (T, D)), jax.random.normal(ks[1], (D, 8))]
    args += [jax.random.normal(k, s) * 0.3 for k, s in
             zip(ks[2:], ((held, D, 12), (held, D, 12), (held, 12, D)))]
    plain = _layer(held, offset)
    kept = jax.checkpoint(
        plain, policy=jax.checkpoint_policies.save_only_these_names(
            "moe_dispatch", "moe_out"))

    def grads(layer):
        def loss(*a):
            return (layer(layer(*a), *a[1:]) ** 2).sum()
        return jax.jit(jax.grad(loss, argnums=tuple(range(5))))(*args)

    got, want = grads(kept), grads(plain)
    assert bool(np.any(np.asarray(want[1]))) == (held == 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def _count(jaxpr, names, times=1, inside=False):
    """How often the primitives ``names`` run under the scope
    ``moe_route`` in ``jaxpr``: a scan's body counts ``length`` times,
    and an inner program's scopes go on from its equation's."""
    total = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        here = inside or "moe_route" in str(eqn.source_info.name_stack)
        total += times * (here and prim in names)
        inner = times * eqn.params["length"] if prim == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _count(sub, names, inner, here)
    return total


# the toy sizes of the three decoders' own tests
KEYE = dict(keye_sizes, q_chunk=8, moe_tile=4)
AXK1 = dict(axk1_sizes, q_chunk=16, moe_tile=4)
MELLUM2 = dict(mellum2_sizes, **mellum2_tiles)


def _loss_of(name, sizes):
    """(loss(trained leaves), their shapes, expert layers) of a toy
    decoder that holds a share of its experts."""
    model = build_model(name, 0, **sizes)
    tokens = jnp.zeros((2, sizes["seq_len"]), jnp.int32)
    key = jax.random.PRNGKey(0)
    rest, expert_layers = {}, sizes["layers"]
    if name == "axk1_decoder":
        model = build_lora_model(model, name, rank=4, alpha=8.0,
                                 target="attention")
        rest = {"frozen": jax.eval_shape(model.init_frozen, key, tokens)}
        expert_layers -= 1  # the leading dense layer
    params = jax.eval_shape(model.init, key, tokens)["params"]

    def loss(params, rest):
        logits, _ = model.apply({"params": params, **rest}, tokens,
                                train=True)
        return jax.nn.log_softmax(logits, -1)[..., 0].mean()

    return loss, (params, rest), expert_layers


@pytest.mark.parametrize("name,sizes", [
    ("keye_decoder", KEYE), ("axk1_decoder", AXK1),
    ("mellum2_decoder", MELLUM2)])
def test_route_runs_once_a_layer_in_a_decoders_gradient(name, sizes,
                                                        monkeypatch):
    """The gradient's program holds one sort and one selection of
    ``route`` for each expert layer: the forward pass's. Without
    ``"moe_dispatch"`` in the layer's policy it holds two, the second in
    the rematerialisation, which is what losing one field's name brings
    back."""
    loss, shapes, expert_layers = _loss_of(name, sizes)
    # group-limited routing selects three times: pairs, groups, experts
    top_ks = 3 if name == "axk1_decoder" else 1

    def runs():
        jaxpr = jax.make_jaxpr(jax.grad(loss))(*shapes).jaxpr
        return _count(jaxpr, {"sort"}), _count(jaxpr, {"top_k"})

    assert runs() == (expert_layers, top_ks * expert_layers)
    names = jax.checkpoint_policies.save_only_these_names
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *kept: names(*(k for k in kept if k != "moe_dispatch")))
    assert runs() == (2 * expert_layers, 2 * top_ks * expert_layers)
