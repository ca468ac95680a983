"""ops/band_attention.band_attention: the three kernels in interpret mode
on the CPU against plain masked attention (dense scores over every
pair, the mask as an array, the key-value heads repeated), forward and
the three gradients; which tiles the kernels visit; through ``vmap`` and
inside a manual mesh region; and that a rematerialised caller runs the
forward kernel once. (Compiled for a described v5e at the published
widths: tests/test_sparse_attention_kernel.py, the one file that
describes a chip.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.ops import band_attention as ba

T, KV, HD, BLOCK = 64, 2, 16, 16


def plain(q, k, v, window, scale):
    t, heads, _ = q.shape
    rep = heads // k.shape[1]
    kr, vr = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("qhd,khd->hqk", q, kr,
                   preferred_element_type=jnp.float32) * scale
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = ahead >= 0
    if window is not None:
        keep &= ahead < window
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), vr,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def inputs(rep, dtype, t=T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (t, KV * rep, HD)).astype(dtype)
    k = jax.random.normal(ks[1], (t, KV, HD)).astype(dtype)
    v = jax.random.normal(ks[2], (t, KV, HD)).astype(dtype)
    ct = jax.random.normal(ks[3], (t, KV * rep, HD))
    return q, k, v, ct


def both(fn, q, k, v, ct):
    def total(q, k, v):
        out = fn(q, k, v)
        return (out.astype(jnp.float32) * ct).sum(), out

    (_, out), grads = jax.value_and_grad(total, (0, 1, 2), has_aux=True)(
        q, k, v)
    return (out, *grads)


def close(got, want, tol):
    for g, w in zip(got, want):
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), w,
                                   atol=tol * max(1.0, np.abs(w).max()))


# none, smaller than a tile, a tile, several tiles, not a multiple of it
WINDOWS = {"none": None, "under_a_tile": 5, "a_tile": 16, "three_tiles": 48,
           "no_multiple": 23}


@pytest.mark.parametrize("rep", [1, 2, 8])
@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_output_and_the_three_gradients_match_plain_masked_attention(
        window, rep):
    w = WINDOWS[window]
    q, k, v, ct = inputs(rep, jnp.float32)
    got = both(lambda *a: ba.band_attention(*a, w, HD ** -0.5, BLOCK),
               q, k, v, ct)
    want = both(lambda *a: plain(*a, w, HD ** -0.5), q, k, v, ct)
    close(got, want, 5e-6)


@pytest.mark.parametrize("window", [None, 23])
def test_bfloat16_operands_float32_softmax(window):
    q, k, v, ct = inputs(4, jnp.bfloat16, seed=3)
    got = both(lambda *a: ba.band_attention(*a, window, HD ** -0.5, BLOCK),
               q, k, v, ct)
    want = both(lambda *a: plain(*a, window, HD ** -0.5), q, k, v, ct)
    assert got[0].dtype == jnp.bfloat16
    close(got, want, 2e-2)


def test_a_window_is_a_band_and_not_a_triangle():
    """The gradient of a query's output with respect to a key that lies
    ``window`` or more positions before it is exactly zero, and the
    output differs from the triangle's."""
    q, k, v, _ = inputs(2, jnp.float32)
    window = 23
    row = 50

    def out_row(k, v, w):
        return ba.band_attention(q, k, v, w, HD ** -0.5, BLOCK)[row].sum()

    dk, dv = jax.grad(out_row, (0, 1))(k, v, window)
    first = row - window + 1
    assert not np.any(np.asarray(dk[:first])) and np.any(np.asarray(dk[first]))
    assert not np.any(np.asarray(dv[:first])) and np.any(np.asarray(dv[first]))
    assert not np.any(np.asarray(dk[row + 1:]))
    dk_full, _ = jax.grad(out_row, (0, 1))(k, v, None)
    assert np.any(np.asarray(dk_full[:first]))


@pytest.mark.parametrize("t,block,window", [
    (64, 16, None), (64, 16, 5), (64, 16, 16), (64, 16, 17), (64, 16, 48),
    (64, 16, 23), (64, 16, 1), (64, 16, 500), (16384, 512, 1024),
    (16384, 512, None), (16384, 256, 1024),
])
def test_the_kernels_visit_every_tile_of_the_band_and_no_other(t, block,
                                                               window):
    """From the grid and the block index: query tile ``i`` meets key
    tiles ``i - c`` for ``c < band_tiles`` (those below 0 are skipped and
    repeat tile 0's index), key tile ``j`` query tiles ``j + c``. Every
    visited tile holds a kept pair and every tile that holds one is
    visited."""
    n = t // block
    reach = ba.band_tiles(n, block, window)

    def holds_a_kept_pair(i, j):  # query tile i, key tile j
        if j > i:
            return False
        nearest = (i - j - 1) * block + 1 if i > j else 0
        return window is None or nearest < window

    by_query = {(i, i - c) for i in range(n) for c in range(reach) if c <= i}
    by_key = {(j + c, j) for j in range(n) for c in range(reach) if j + c < n}
    band = {(i, j) for i in range(n)
            for j in range(max(0, i - reach - 2), i + 1)
            if holds_a_kept_pair(i, j)}
    assert by_query == by_key == band
    assert ba.visited_pairs(t, block, window) == len(band) * block * block
    w = t if window is None else min(window, t)
    assert ba.kept_pairs(t, window) == sum(min(q + 1, w) for q in range(t))


def test_pairs_at_the_published_sizes():
    assert ba.kept_pairs(16384, 1024) == 16_253_440
    assert ba.kept_pairs(16384, None) == 134_225_920
    assert ba.band_tiles(32, 512, 1024) == 3
    # 1 + 2 + 30 x 3 tiles of 512 x 512: two thirds of them kept
    assert ba.visited_pairs(16384, 512, 1024) == 93 * 512 * 512
    assert ba.visited_pairs(16384, 512, None) == 528 * 512 * 512


def test_through_vmap_and_a_shard_map_over_clients():
    from jax.sharding import Mesh, PartitionSpec as P

    q, k, v, _ = inputs(2, jnp.float32, t=32)
    batch = [jnp.stack([a, a[::-1]]) for a in (q, k, v)]
    fn = lambda q, k, v: ba.band_attention(q, k, v, 11, HD ** -0.5, 8)  # noqa: E731
    want = jnp.stack([fn(*(a[i] for a in batch)) for i in range(2)])
    np.testing.assert_allclose(jax.vmap(fn)(*batch), want, atol=1e-6)
    mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))
    lanes = jax.jit(jax.shard_map(
        lambda q, k, v: jax.vmap(fn)(q, k, v), mesh=mesh,
        in_specs=(P("clients"),) * 3, out_specs=P("clients")))
    np.testing.assert_allclose(lanes(*batch), want, atol=1e-6)
    grads = jax.jit(jax.shard_map(
        lambda q, k, v: jax.grad(
            lambda q: jax.vmap(fn)(q, k, v).sum())(q), mesh=mesh,
        in_specs=(P("clients"),) * 3, out_specs=P("clients")))(*batch)
    want_g = jax.grad(lambda q: jax.vmap(fn)(q, *batch[1:]).sum())(batch[0])
    np.testing.assert_allclose(grads, want_g, atol=1e-5)


def _kernel_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, found)
    return found


def test_the_forward_kernel_runs_once_a_step_under_rematerialisation():
    """A caller that rematerialises and keeps ``attn_out`` / ``attn_lse``
    has the forward kernel once and each backward kernel once."""
    q, k, v, _ = inputs(2, jnp.float32, t=32)

    @jax.checkpoint
    def plain_remat(q, k, v):
        return ba.band_attention(q, k, v, 11, HD ** -0.5, 8).sum()

    kept = jax.checkpoint(
        lambda q, k, v: ba.band_attention(q, k, v, 11, HD ** -0.5, 8).sum(),
        policy=jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse"))
    names = ["band_attn_forward", "band_attn_backward_dq",
             "band_attn_backward_dkv"]
    calls = _kernel_calls(
        jax.make_jaxpr(jax.grad(kept, (0, 1, 2)))(q, k, v).jaxpr, [])
    assert sorted(calls) == sorted(names)
    calls = _kernel_calls(
        jax.make_jaxpr(jax.grad(plain_remat, (0, 1, 2)))(q, k, v).jaxpr, [])
    assert sorted(calls) == sorted(names + ["band_attn_forward"])


@pytest.mark.parametrize("bad,message", [
    (dict(t=40, block=16), "no multiple"),
    (dict(window=0), "keeps no key"),
])
def test_shapes_the_kernels_do_not_take_are_refused(bad, message):
    t = bad.get("t", T)
    q, k, v, _ = inputs(2, jnp.float32, t=t)
    with pytest.raises(ValueError, match=message):
        ba.band_attention(q, k, v, bad.get("window", 8), HD ** -0.5,
                          bad.get("block", BLOCK))
    with pytest.raises(ValueError, match="no multiple of 2"):
        ba.band_attention(q[:, :3], k, v, 8, HD ** -0.5, BLOCK)
