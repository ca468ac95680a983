"""ops/sparse_attention.selected_attention: the three Pallas kernels
(forward, heads' mean, backward) in interpret mode on the CPU against the
attention equations of the plain reference (tests/reference/
keye_decoder.py: a float32 softmax over the kept pairs of each head,
rounded to the compute dtype once for its product with v; the heads'
mean of those weights); ops/sparse_attention.index_scores: its two
kernels against the ``jnp`` form they replaced, kept here as the
reference; and the compiled text of the gradient for a described v5e."""

import collections
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.models import build_model
from colearn_federated_learning_tpu.ops import sparse_attention

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "keye_ref", os.path.join(HERE, "reference", "keye_decoder.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

HD = 16


def reference_attention(q, k, v, keep, compute):
    """The reference's ``head_weights`` / ``head_output`` on given q, k,
    v: (``[Tq, H * hd]``, the heads' mean of the weights)."""
    tq, heads, hd = q.shape
    group = jnp.arange(heads) // (heads // k.shape[1])
    qh, kh, vh = (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[group],
                  v.transpose(1, 0, 2)[group])

    def head_weights(qh, kh):
        s = jnp.dot(qh, kh.T, preferred_element_type=ref.ISLAND) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(keep, s, ref.NEG), axis=-1)
        return jnp.where(keep, prob, 0.0)

    weights = jax.vmap(head_weights)(qh, kh)
    out = jnp.einsum("hqk,hkd->qhd", weights.astype(compute), vh)
    return out.reshape(tq, heads * hd), weights.mean(0)


def inputs(tq, tk, heads, kv, dtype, seed=0, topk=8):
    """A chunk of ``tq`` queries, the last of ``tk`` positions, with the
    reference's selection of ``topk`` keys per query."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (tq, heads, HD)).astype(dtype)
    k = jax.random.normal(ks[1], (tk, kv, HD)).astype(dtype)
    v = jax.random.normal(ks[2], (tk, kv, HD)).astype(dtype)
    keep = ref.selection(jax.random.normal(ks[3], (tk, tk)), topk)[tk - tq:]
    ct = jax.random.normal(ks[4], (tq, heads * HD))
    return q, k, v, keep, ct


def both(fn, q, k, v, keep, ct):
    """(out, weights, (dq, dk, dv)) for the cotangent ``ct`` of out."""
    def loss(q, k, v):
        out, weights = fn(q, k, v, keep)
        return (out.astype(jnp.float32) * ct).sum(), (out, weights)
    (_, (out, weights)), grads = jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True)(q, k, v)
    return out, weights, grads


def close(got, want, tol):
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol * max(1.0, np.abs(want).max()))


@pytest.mark.parametrize("rep", [1, 4, 8])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_forward_weights_and_gradients_match_the_reference(dtype, tol, rep):
    """Several query tiles and key tiles (blocks of 8 and 16), grouped
    heads; bfloat16 is held to the float32 reference of the same inputs."""
    q, k, v, keep, ct = inputs(16, 48, 2 * rep, 2, dtype)
    got = both(lambda *a: sparse_attention.selected_attention(*a, 8, 16),
               q, k, v, keep, ct)
    want = both(lambda *a: reference_attention(*a, jnp.float32),
                *(a.astype(jnp.float32) for a in (q, k, v)), keep, ct)
    close(got[0], want[0], tol)
    close(got[1], want[1], 1e-6 if dtype == jnp.float32 else 2e-3)
    for g, w in zip(got[2], want[2]):
        assert g.dtype == dtype
        close(g, w, tol)
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32


@pytest.mark.parametrize("tq,tk", [(16, 32), (16, 40), (12, 40), (8, 8),
                                   (12, 21)])
def test_extents_that_are_and_are_not_a_multiple_of_the_block(tq, tk):
    q, k, v, keep, ct = inputs(tq, tk, 4, 2, jnp.float32, seed=tq + tk,
                               topk=5)
    got = both(lambda *a: sparse_attention.selected_attention(*a, 8, 16),
               q, k, v, keep, ct)
    want = both(lambda *a: reference_attention(*a, jnp.float32),
                q, k, v, keep, ct)
    assert got[0].shape == (tq, 4 * HD) and got[1].shape == (tq, tk)
    close(got[0], want[0], 3e-5)
    close(got[1], want[1], 1e-6)
    for g, w in zip(got[2], want[2]):
        assert g.shape == w.shape
        close(g, w, 3e-5)


@pytest.mark.parametrize("case", ["last_tile_only", "one_key"])
def test_a_query_whose_kept_keys_come_late(case):
    """Selection is scattered: through every key tile but the last the
    running maximum of such a row is still the mask value, and
    ``exp(s - m)`` is 1 on its masked pairs."""
    tq, tk = 8, 64
    q, k, v, _, ct = inputs(tq, tk, 4, 2, jnp.float32, seed=3)
    keep = np.zeros((tq, tk), bool)
    if case == "last_tile_only":
        keep[:, 48:] = np.asarray(
            jax.random.bernoulli(jax.random.PRNGKey(4), 0.4, (tq, 16)))
        keep[:, 63] = True
    else:
        keep[np.arange(tq), 56 + np.arange(tq)] = True
        keep[3] = False
        keep[3, 2] = True  # and one whose only key lies in the first tile
    keep = jnp.asarray(keep)
    got = both(lambda *a: sparse_attention.selected_attention(*a, 8, 16),
               q, k, v, keep, ct)
    want = both(lambda *a: reference_attention(*a, jnp.float32),
                q, k, v, keep, ct)
    close(got[0], want[0], 3e-5)
    for g, w in zip(got[2], want[2]):
        close(g, w, 3e-5)
    if case == "one_key":
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(keep, np.float32))
        rows = np.asarray(v)[np.argmax(np.asarray(keep), -1)]  # [tq, G, hd]
        np.testing.assert_allclose(
            np.asarray(got[0]).reshape(tq, 2, 2, HD),
            np.broadcast_to(rows[:, :, None], (tq, 2, 2, HD)), atol=1e-6)
        # a softmax over one key is constant
        np.testing.assert_allclose(np.asarray(got[2][0]), 0.0, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weights_are_exactly_zero_off_keep_and_rows_sum_to_one(dtype):
    """``index_kl`` tests ``target > 0``: an unkept pair must read 0.0,
    whatever its score (here up to 60 above the row's kept ones)."""
    q, k, v, keep, _ = inputs(16, 48, 8, 2, dtype, seed=7)
    k = jnp.where(keep.any(0)[:, None, None], k, 4 * k)
    _, weights = sparse_attention.selected_attention(q, k, v, keep, 8, 16)
    weights, keep = np.asarray(weights), np.asarray(keep)
    assert weights.dtype == np.float32
    assert not np.any(weights[~keep])
    assert np.all(weights[keep] > 0)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-5)


def reference_index_scores(q_idx, k_idx, w_idx):
    """``index_scores`` as XLA ops, as the program held it until PR 28:
    the ``[J, Tq, Tk]`` float32 per-head scores are an array here."""
    j, d = q_idx.shape[1], q_idx.shape[2]
    dots = jnp.einsum("qjd,kd->jqk", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    scale = jnp.float32(d ** -0.5 * j ** -0.5)
    w = w_idx.astype(jnp.float32).T[:, :, None] * scale
    return (jax.nn.relu(dots) * w).sum(0) + 0.0


def index_inputs(tq, tk, heads, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (tq, heads, hd)).astype(dtype),
            jax.random.normal(ks[1], (tk, hd)).astype(dtype),
            jax.random.normal(ks[2], (tq, heads)),
            jax.random.normal(ks[3], (tq, tk)))


def index_both(fn, q_idx, k_idx, w_idx, ct):
    """(scores, (dq_idx, dk_idx, dw_idx)) for the cotangent ``ct``."""
    def loss(q_idx, k_idx, w_idx):
        scores = fn(q_idx, k_idx, w_idx)
        return (scores * ct).sum(), scores
    (_, scores), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
        q_idx, k_idx, w_idx)
    return scores, grads


def fused_index_scores(q_idx, k_idx, w_idx):
    return sparse_attention.index_scores(q_idx, k_idx, w_idx, 8, 16)


# heads x head_dim: one head a slab, two heads a 128-lane slab (the
# published shape's case), all heads in one slab
@pytest.mark.parametrize("heads,hd", [(3, 8), (4, 64), (16, 8)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6),
                                       (jnp.bfloat16, 1e-2)])
def test_index_scores_and_gradients_match_the_jnp_form(dtype, tol, heads,
                                                       hd):
    """Two query tiles and three key tiles; bfloat16 operands are held to
    the float32 reference of the same inputs (the kernel rounds the
    masked cotangent to bfloat16 once, for its two products)."""
    q_idx, k_idx, w_idx, ct = index_inputs(16, 48, heads, hd, dtype)
    scores, grads = index_both(fused_index_scores, q_idx, k_idx, w_idx, ct)
    want, want_g = index_both(
        reference_index_scores, q_idx.astype(jnp.float32),
        k_idx.astype(jnp.float32), w_idx, ct)
    assert scores.dtype == jnp.float32 and scores.shape == (16, 48)
    # the scores see the same roundings in both dtypes: bfloat16 products
    # are exact in float32
    close(scores, want, 3e-6)
    for g, w, like in zip(grads, want_g, (q_idx, k_idx, w_idx)):
        assert g.dtype == like.dtype and g.shape == like.shape
        close(g, w, tol)


def test_the_selection_reads_the_same_set_from_the_fused_scores():
    """No near-ties here (random float32 scores, 48 keys): the exact
    top-8 of the kernel's scores and of the reference's are one set."""
    q_idx, k_idx, w_idx, _ = index_inputs(16, 48, 4, 64, jnp.float32, seed=5)
    causal = jnp.arange(32, 48)[:, None] >= jnp.arange(48)[None, :]
    got = sparse_attention.select_topk(
        fused_index_scores(q_idx, k_idx, w_idx), causal, 8)
    want = sparse_attention.select_topk(
        reference_index_scores(q_idx, k_idx, w_idx), causal, 8)
    assert np.all(np.asarray(got).sum(-1) == 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tq,tk", [(16, 32), (16, 40), (12, 40), (8, 8),
                                   (12, 21)])
def test_index_extents_that_are_and_are_not_a_multiple_of_the_block(tq, tk):
    q_idx, k_idx, w_idx, ct = index_inputs(tq, tk, 4, 64, jnp.float32,
                                           seed=tq + tk)
    scores, grads = index_both(fused_index_scores, q_idx, k_idx, w_idx, ct)
    want, want_g = index_both(reference_index_scores, q_idx, k_idx, w_idx,
                              ct)
    assert scores.shape == (tq, tk)
    close(scores, want, 3e-6)
    for g, w in zip(grads, want_g):
        assert g.shape == w.shape
        close(g, w, 3e-6)


def test_a_row_whose_dots_are_all_negative_scores_plus_zero():
    """Every ``relu`` of the row is 0 and its negative weights turn the
    terms into ``-0.0``; the score must be ``+0.0``, whose bit pattern
    the selection orders above ``-0.0``'s. Its gradients: nothing for
    ``q_idx`` and ``w_idx`` of that row."""
    q_idx, k_idx, w_idx, ct = index_inputs(8, 32, 4, 64, jnp.float32, seed=9)
    k_idx = jnp.abs(k_idx)
    q_idx = q_idx.at[2].set(-jnp.abs(q_idx[2]))
    w_idx = w_idx.at[2].set(-jnp.abs(w_idx[2]))
    scores, (dq, _, dw) = index_both(fused_index_scores, q_idx, k_idx,
                                     w_idx, ct)
    scores = np.asarray(scores)
    assert np.all(scores[2] == 0) and np.any(scores[0] != 0)
    assert not np.signbit(scores[scores == 0]).any()
    assert not np.any(np.asarray(dq)[2]) and not np.any(np.asarray(dw)[2])


def test_index_scores_through_vmap_and_a_shard_map_over_clients():
    """As the model calls it (the batch mapped over) and as the round
    engine does (operands that vary over the ``clients`` lanes)."""
    from jax.sharding import Mesh, PartitionSpec as P

    args = [jnp.stack(a) for a in zip(*(
        index_inputs(8, 24, 4, 64, jnp.float32, seed=s) for s in (1, 2)))]
    want, want_g = jax.vmap(
        lambda *a: index_both(reference_index_scores, *a))(*args)
    mapped = jax.vmap(lambda *a: index_both(fused_index_scores, *a))
    mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))
    lanes = jax.jit(jax.shard_map(mapped, mesh=mesh, in_specs=P("clients"),
                                  out_specs=P("clients")))
    for got, got_g in (mapped(*args), lanes(*args)):
        close(got, want, 3e-6)
        for g, w in zip(got_g, want_g):
            close(g, w, 3e-6)


def _kernel_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    _kernel_calls(inner, found)
    return found


def test_the_forward_kernels_run_once_a_step_under_rematerialisation():
    """Under ``vmap`` and the decoder layer's policy the output, the
    log-sum-exp and the weights are kept, so the gradient's program
    holds each kernel once: nothing of attention is recomputed."""
    q, k, v, keep, ct = inputs(8, 16, 4, 2, jnp.float32)

    def block(q, k, v):
        out, weights = sparse_attention.selected_attention(q, k, v, keep)
        weights = jax.ad_checkpoint.checkpoint_name(weights, "attn_weights")
        return (out * ct).sum() + (jax.lax.stop_gradient(weights)
                                   * (q.sum() + k.sum())).sum()

    def loss(policy, q, k, v):
        layer = jax.checkpoint(block, policy=policy)
        return jax.vmap(layer)(q[None], k[None], v[None]).sum()

    names = jax.checkpoint_policies.save_only_these_names(
        "attn_out", "attn_lse", "attn_weights")
    for policy, forward in ((names, 1),
                            (jax.checkpoint_policies.nothing_saveable, 2)):
        calls = _kernel_calls(jax.make_jaxpr(jax.grad(
            lambda *a: loss(policy, *a), (0, 1, 2)))(q, k, v).jaxpr, [])
        assert sorted(calls) == sorted(
            ["attn_sparse_backward"]
            + ["attn_sparse_forward", "attn_sparse_head_mean"] * forward
        ), policy


def _dry_keye():
    sizes = dict(vocab_size=8, seq_len=32, layers=2, hidden=64, heads=4,
                 kv_heads=2, head_dim=16, num_experts=8, experts_held=2,
                 expert_offset=2, experts_per_token=3, expert_width=32,
                 index_heads=3, index_head_dim=8, index_topk=8,
                 mrope_section=(2, 3, 3), q_chunk=8, moe_tile=4)
    # 3 index heads: the indexer's [index_heads, q_chunk, keys] scores
    # must not have the shape of attention's, nor (with 2) that of the
    # interpreted forward kernel's [H/G, block_q, head_dim] accumulator
    return build_model("keye_decoder", 0, **sizes), sizes


def _loss_and_grad(model):
    def loss(params, tokens):
        logits, aux = model.apply({"params": params}, tokens, train=True)
        return logits.mean() + aux["loss"].sum()
    return jax.value_and_grad(loss)


def test_the_decoders_gradient_holds_no_per_head_score_array():
    """The regression these kernels exist to prevent, at the rehearsal
    size: no ``[G, H/G, q_chunk, keys]`` array of attention's scores and
    no ``[index_heads, q_chunk, keys]`` array of the indexer's in the
    lowered gradient (PR 25's program held one of each per chunk and
    pass)."""
    model, s = _dry_keye()
    tokens = jnp.zeros((1, s["seq_len"]), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])
    text = jax.jit(_loss_and_grad(model)).lower(params, tokens).as_text()
    g, rep = s["kv_heads"], s["heads"] // s["kv_heads"]
    keys = "(8|16|24|32)"  # a chunk sees 8 .. 32 keys
    assert not re.search(
        rf"tensor<(1x)?{g}x{rep}x{s['q_chunk']}x{keys}xf32>", text)
    assert not re.search(
        rf"tensor<(1x)?{s['heads']}x{s['q_chunk']}x{keys}xf32>", text)
    assert not re.search(
        rf"tensor<(1x)?{s['index_heads']}x{s['q_chunk']}x{keys}xf32>", text)


def test_a_chunks_index_scores_are_computed_once_a_pass():
    """The decoder's gradient holds the forward index kernel twice a
    chunk (the forward pass, where the selection and the value of the
    indexer's loss read the same scores; the backward pass, on the way to
    the loss's gradient) and the backward kernel once, beside
    attention's three; the layers are scanned, so a chunk counts once."""
    model, s = _dry_keye()
    tokens = jnp.zeros((1, s["seq_len"]), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])
    calls = collections.Counter(_kernel_calls(
        jax.make_jaxpr(_loss_and_grad(model))(params, tokens).jaxpr, []))
    chunks = s["seq_len"] // s["q_chunk"]
    assert calls == {"attn_index_forward": 2 * chunks,
                     "attn_index_backward": chunks,
                     "attn_sparse_forward": chunks,
                     "attn_sparse_head_mean": chunks,
                     "attn_sparse_backward": chunks,
                     # the held experts, a row kernel and the combining
                     # kernel a pass (ops/moe.py)
                     "moe_experts_forward": 1, "moe_experts_backward": 1,
                     "moe_experts_combine": 2}


@pytest.fixture(scope="module")
def chip_mesh():
    """A ``clients`` mesh over one described v5e chip."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.Mesh(np.array(topo.devices[:1]), ("clients",))


def _experts_meet_their_weights_in_the_kernels(text, held, d, f):
    """Under ``moe_experts`` nothing but the kernels touches the stacked
    weights: no copy, slice or relayout of ``[held, d, f]`` / ``[held,
    f, d]`` (``vmap``'s per-element slice, a loop's ``w[e]``), and no
    one expert's ``[d, f]`` cut out of them (a bitcast moves nothing)."""
    stacked = rf"{held},(?:{d},{f}|{f},{d})"
    assert re.search(rf"= bf16\[(1,)*{stacked}\]\S* parameter\(", text)
    made = re.findall(
        rf"= bf16\[(?:1,)*(?:{stacked}|{d},{f})\]\S* ([a-z\-]+)\(.*"
        r"op_name=\"[^\"]*moe_experts", text)
    assert set(made) <= {"broadcast", "get-tuple-element", "bitcast"}, made


def test_compiled_for_a_v5e_the_scores_stay_in_the_kernels(chip_mesh,
                                                           monkeypatch):
    """One decoder layer at the published widths (1,024 tokens: two
    chunks of 512 queries, so the second sees keys before its own),
    bfloat16, inside a manual ``clients`` region
    as the round engine runs it, compiled for a described v5e with the
    kernels as Mosaic calls: Mosaic accepts their tiling, the kernels'
    loop carries type-check against operands that vary over the mesh,
    every chunk has its six kernels (attention's forward and heads' mean,
    kept through the layer's rematerialisation, and its backward; the
    index scores once for the selection and the loss's value, once more
    and their backward for the loss's gradient), and no float32 buffer of
    the program has the shape of a chunk's per-head scores, attention's
    or the indexer's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    monkeypatch.setattr(sparse_attention, "_interpret", lambda: False)
    model = build_model("keye_decoder", 0, seq_len=1024, layers=1,
                        vocab_size=1024, compute_dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16)
    everywhere = NamedSharding(chip_mesh, P())
    tokens = jax.ShapeDtypeStruct(
        (1, 1024), jnp.int32, sharding=NamedSharding(chip_mesh, P("clients")))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=everywhere),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1024), jnp.int32))["params"]))

    def lane(params, tokens):
        # a client's own copy of the weights, as the trainer holds them
        params = jax.lax.pcast(params, ("clients",), to="varying")
        loss, grads = _loss_and_grad(model)(params, tokens)
        return jax.lax.psum((loss, grads), "clients")

    step = jax.jit(jax.shard_map(lane, mesh=chip_mesh,
                                 in_specs=(P(), P("clients")), out_specs=P()))
    text = step.lower(params, tokens).compile().as_text()
    # and the held experts' forward (kept through the rematerialisation)
    # and backward, a row kernel and the combining kernel each
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 6 * 2 + 4
    _experts_meet_their_weights_in_the_kernels(text, 16, 2048, 768)
    keys = "(512|1024)"  # what a chunk sees here
    assert not re.search(rf"f32\[(1,)?4,8,512,{keys}\]", text)
    assert not re.search(rf"f32\[(1,)?32,512,{keys}\]", text)
    assert not re.search(rf"f32\[(1,)?16,512,{keys}\]", text)


def test_compiled_for_a_v5e_latent_attentions_scores_stay_in_the_kernels(
        chip_mesh, monkeypatch):
    """A.X-K1's leading layer and one expert layer at the published
    widths (2,048 tokens: four tiles of 512), bfloat16, adapters on a
    frozen base, inside a manual ``clients`` region as the round engine
    runs it, compiled for a described v5e: Mosaic accepts the three
    latent-attention kernels' tiling (rope parts 64 wide, one rope key
    for all heads, the statistics as lane vectors), each layer has its
    forward kernel once (kept through the rematerialisation) and its two
    backward kernels, and no float32 buffer of the program has the shape
    of a tile row's scores over the heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from colearn_federated_learning_tpu.models.lora import build_lora_model

    monkeypatch.setattr(sparse_attention, "_interpret", lambda: False)
    model = build_lora_model(
        build_model("axk1_decoder", 0, seq_len=2048, layers=2,
                    vocab_size=1024, compute_dtype=jnp.bfloat16,
                    param_dtype=jnp.bfloat16),
        "axk1_decoder", rank=16, alpha=32.0, target="attention",
        adapter_dtype=jnp.bfloat16)
    everywhere = NamedSharding(chip_mesh, P())
    tokens = jax.ShapeDtypeStruct(
        (1, 2048), jnp.int32, sharding=NamedSharding(chip_mesh, P("clients")))
    dummy = jnp.zeros((1, 2048), jnp.int32)
    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=everywhere),
        tree)
    frozen = abstract(jax.eval_shape(
        lambda: model.init_frozen(jax.random.PRNGKey(0), dummy)))
    adapters = abstract(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dummy)["params"]))

    def lane(adapters, frozen, tokens):
        adapters = jax.lax.pcast(adapters, ("clients",), to="varying")

        def loss(adapters):
            logits, _ = model.apply({"params": adapters, "frozen": frozen},
                                    tokens, train=True)
            return logits.mean()

        return jax.lax.psum(jax.value_and_grad(loss)(adapters), "clients")

    step = jax.jit(jax.shard_map(
        lane, mesh=chip_mesh, in_specs=(P(), P(), P("clients")),
        out_specs=P()))
    text = step.lower(adapters, frozen, tokens).compile().as_text()
    # and the frozen held experts' forward and backward, a row kernel and
    # the combining kernel each
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 3 * 2 + 4
    _experts_meet_their_weights_in_the_kernels(text, 12, 7168, 2048)
    assert not re.search(r"f32\[(1,)?64,512,(512|1024|1536|2048)\]", text)
    # the base is a parameter of the program, not a constant of it
    assert not re.search(r"= bf16\[7168,18432\]\S* constant\(", text)
    assert re.search(r"= bf16\[7168,18432\]\S* parameter\(", text)


def test_compiled_for_a_v5e_banded_attentions_scores_stay_in_the_kernels(
        chip_mesh, monkeypatch):
    """One period of Mellum2's layers (sliding, full) at the published
    widths (2,048 tokens: four tiles of 512 under a window of 1,024),
    bfloat16, inside a manual ``clients`` region as the round engine
    runs it, compiled for a described v5e: Mosaic accepts the three
    banded-attention kernels' tiling in both forms (a group's eight
    query heads of 128 over one key-value head, the statistics as lane
    vectors, a grid of three band steps and of four), each layer has its
    forward kernel once (kept through the rematerialisation) and its two
    backward kernels beside the held experts' four calls, experts of
    [2304, 896] (18 and 7 lanes) fit the expert kernels' VMEM, and no
    float32 buffer of the program has the shape of a tile row's scores
    over the heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    monkeypatch.setattr(sparse_attention, "_interpret", lambda: False)
    model = build_model("mellum2_decoder", 0, seq_len=2048, layers=2,
                        period=("sliding", "full"), vocab_size=1024,
                        compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    everywhere = NamedSharding(chip_mesh, P())
    tokens = jax.ShapeDtypeStruct(
        (1, 2048), jnp.int32, sharding=NamedSharding(chip_mesh, P("clients")))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=everywhere),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2048), jnp.int32))["params"]))

    def lane(params, tokens):
        params = jax.lax.pcast(params, ("clients",), to="varying")

        def loss(params):
            logits, _ = model.apply({"params": params}, tokens, train=True)
            return logits.mean()

        return jax.lax.psum(jax.value_and_grad(loss)(params), "clients")

    step = jax.jit(jax.shard_map(lane, mesh=chip_mesh,
                                 in_specs=(P(), P("clients")), out_specs=P()))
    text = step.lower(params, tokens).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == (3 + 4) * 2
    _experts_meet_their_weights_in_the_kernels(text, 8, 2304, 896)
    assert not re.search(r"f32\[(1,)?(32|4,8),(512|2048),(512|1024|2048)\]",
                         text)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "fused"])
def test_compiled_for_a_v5e_the_dp_step_writes_no_per_example_kernel_gradient(
        chip_mesh, monkeypatch, kernel):
    """What the chip's compiler keeps of DP-SGD's clipped sum: one step
    of a ViT at the published widths (one block, 197 tokens, bfloat16, a
    microbatch of 16) inside a manual ``clients`` region, compiled for a
    described v5e. ``privacy/dp.py`` wants each example's ``a_iᵀ δ_i``
    in float32, scaled by ``s_i`` and summed over the microbatch (the
    one exact form that is a single MXU pass), in two forms. As a Pallas
    kernel (what a TPU runs where the widths are whole lanes): Mosaic
    accepts the tiling, 197 rows and a transposed left operand among it,
    and the call's type against operands that vary over the mesh; the
    patch embedding and the block's four products have their call, the
    head (1,000 classes) has not. As three XLA steps (every other
    backend, and the head): the compiler fuses them. Either way no
    instruction of the program writes ``[16, *kernel.shape]`` in any
    layout or dtype, which is what the per-example weight gradients this
    path exists to avoid would be. The patch embedding's ``[16, 768,
    768]`` is left out: a Gram matrix of 768 rows would have its shape
    (none has: T is 197)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from colearn_federated_learning_tpu.client.trainer import make_loss_fn
    from colearn_federated_learning_tpu.config import DPConfig
    from colearn_federated_learning_tpu.privacy import dp as dp_lib

    monkeypatch.setattr(dp_lib, "_on_tpu", lambda: kernel)
    mb = 16
    model = build_model("vit_b16", 1000, layers=1,
                        compute_dtype=jnp.bfloat16)
    everywhere = NamedSharding(chip_mesh, P())
    rows = NamedSharding(chip_mesh, P("clients"))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=everywhere),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))["params"]))
    dp_grads = dp_lib.make_dp_grad_fn(
        make_loss_fn(model, "classify"),
        DPConfig(enabled=True, l2_clip=1.0, noise_multiplier=0.8,
                 microbatch_size=mb))

    def lane(params, x, y, m, key):
        params = jax.lax.pcast(params, ("clients",), to="varying")
        return jax.lax.psum(dp_grads(params, x[0], y[0], m[0], key[0]),
                            "clients")

    step = jax.jit(jax.shard_map(
        lane, mesh=chip_mesh, in_specs=(P(),) + (P("clients"),) * 4,
        out_specs=P()))
    text = step.lower(
        params,
        jax.ShapeDtypeStruct((1, 2 * mb, 224, 224, 3), jnp.uint8, sharding=rows),
        jax.ShapeDtypeStruct((1, 2 * mb), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((1, 2 * mb), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=rows),
    ).compile().as_text()
    # what an instruction outside a fused computation produces is a
    # buffer; what one inside produces stays in the fusion
    fused = set(re.findall(r"fusion\(.*calls=%?([\w.\-]+)", text))
    written, inside = set(), False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            inside = head.group(1) in fused
        made = re.search(r"= [a-z]+[0-9]+\[([0-9,]+)\]\S* (?!parameter\()",
                         line)
        if made and not inside:
            written.add(made.group(1))
    assert "16,197,768" in written  # a block's input rows, as a check
    for d_in, d_out in ((768, 2304), (768, 3072), (3072, 768), (768, 1000)):
        for form in (f"{mb},{d_in},{d_out}", f"{mb},{d_out},{d_in}"):
            assert form not in written, form
    assert f"{mb},16,16,3,768" not in written
    assert text.count("custom_call_target=\"tpu_custom_call\"") == (
        5 if kernel else 0)
    if not kernel:  # the products, fused with their scale and their sum
        assert re.search(r"= f32\[768,3072\]\S* fusion\(.*bti,bto->bio",
                         text)
