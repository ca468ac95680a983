"""ops/moe.expert_ffn / expert_ffn_frozen: the grouped-product kernels
(forward, trained backward, frozen backward) in interpret mode on the CPU
against the per-tile loop they replaced, kept here as the oracle (a
Python loop over the used tiles: per tile the row gather, the three
forward or eight backward products with the same roundings, the row
scatter-add); the batching rule against per-element calls; and the rule
that picks the width block. (Compiled for a described v5e at the
published widths of both decoders: tests/test_sparse_attention_kernel.py,
the one file that describes a chip.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.ops import moe

TILE = 4


def oracle_forward(h, w1, w3, w2, row_token, row_gate, tile_expert, n_tiles):
    """``tile_expert`` and ``n_tiles`` are Python numbers here."""
    y = jnp.zeros(h.shape, jnp.float32)
    for i in range(n_tiles):
        e, rows = tile_expert[i], slice(i * TILE, (i + 1) * TILE)
        tok, gate = row_token[rows], row_gate[rows]
        x = h[tok]
        a = jnp.dot(x, w1[e], preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3[e], preferred_element_type=jnp.float32)
        mid = (jax.nn.silu(a) * b).astype(h.dtype)
        out = jnp.dot(mid, w2[e], preferred_element_type=jnp.float32)
        y = y.at[tok].add(out * gate[:, None])
    return y.astype(h.dtype)


def oracle_backward(h, w1, w3, w2, row_token, row_gate, tile_expert, n_tiles,
                    dy):
    """(dh, dw1, dw3, dw2, dgate) as the loop computed them."""
    cd = h.dtype
    f32 = lambda a: jnp.zeros(a.shape, jnp.float32)  # noqa: E731
    dh, dw1, dw3, dw2, dgate = f32(h), f32(w1), f32(w3), f32(w2), f32(row_gate)
    for i in range(n_tiles):
        e, rows = tile_expert[i], slice(i * TILE, (i + 1) * TILE)
        tok, gate = row_token[rows], row_gate[rows]
        x = h[tok]
        a = jnp.dot(x, w1[e], preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3[e], preferred_element_type=jnp.float32)
        sig = jax.nn.sigmoid(a)
        silu = a * sig
        mid = silu * b
        dout = dy[tok]
        dmid_pre = jnp.dot(dout, w2[e].T, preferred_element_type=jnp.float32)
        dgate = dgate.at[rows].set((mid * dmid_pre).sum(-1))
        dmid = dmid_pre * gate[:, None]
        da = (dmid * b * (sig * (1.0 + a * (1.0 - sig)))).astype(cd)
        db = (dmid * silu).astype(cd)
        dout_g = (dout.astype(jnp.float32) * gate[:, None]).astype(cd)
        dw2 = dw2.at[e].add(jnp.dot(mid.astype(cd).T, dout_g,
                                    preferred_element_type=jnp.float32))
        dw1 = dw1.at[e].add(jnp.dot(x.T, da,
                                    preferred_element_type=jnp.float32))
        dw3 = dw3.at[e].add(jnp.dot(x.T, db,
                                    preferred_element_type=jnp.float32))
        dh = dh.at[tok].add(
            jnp.dot(da, w1[e].T, preferred_element_type=jnp.float32)
            + jnp.dot(db, w3[e].T, preferred_element_type=jnp.float32))
    return (dh.astype(cd), dw1.astype(cd), dw3.astype(cd), dw2.astype(cd),
            dgate)


# tokens of each held expert, of T = 24
_CASES = {
    # no row, exactly one tile, three tiles (10 rows), a part of one
    "empty_one_three": (0, 4, 10, 3),
    # every assignment (top-2 of 24 tokens) on a held expert: the tables
    # are full but for the groups' padding
    "all_held": (9, 15, 13, 11),
    # the held experts get nothing: no tile in use
    "no_tile": (0, 0, 0, 0),
}
T, TOP_K = 24, 2


def layer(case, dtype, d=16, f=12, seed=0):
    """(h, w1, w3, w2, row_token, row_gate, tile_expert, n_tiles, dy) of
    one sequence, the dispatch tables laid out as ``moe.route`` does:
    rows sorted by expert, a group's tokens ascending, every group
    padded to whole tiles with token 0 at gate 0, ``T * top_k + held *
    tile`` rows in all, the tiles past the used ones named after the
    last expert."""
    loads = _CASES[case]
    rng = np.random.RandomState(seed)
    rows = T * TOP_K + len(loads) * TILE
    row_token, row_gate = np.zeros(rows, np.int32), np.zeros(rows, np.float32)
    tile_expert = np.full(rows // TILE, len(loads) - 1, np.int32)
    at = 0
    for e, load in enumerate(loads):
        row_token[at:at + load] = np.sort(rng.choice(T, load, replace=False))
        row_gate[at:at + load] = rng.uniform(0.1, 1.0, load)
        tile_expert[at // TILE:(at + load + TILE - 1) // TILE] = e
        at += -(-load // TILE) * TILE
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h, dy = (jax.random.normal(k, (T, d)).astype(dtype) for k in ks[:2])
    w = [(jax.random.normal(k, s) * 0.3).astype(dtype) for k, s in
         zip(ks[2:], ((len(loads), d, f), (len(loads), d, f),
                      (len(loads), f, d)))]
    return (h, *w, jnp.asarray(row_token), jnp.asarray(row_gate),
            jnp.asarray(tile_expert), jnp.int32(at // TILE), dy)


def close(got, want, tol):
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol * max(1.0, np.abs(want).max()))


def gradients(ffn, args):
    *operands, dy = args

    def total(h, w1, w3, w2, gate):
        y = ffn(h, w1, w3, w2, operands[4], gate, *operands[6:])
        return (y.astype(jnp.float32) * dy.astype(jnp.float32)).sum()

    return jax.grad(total, argnums=(0, 1, 2, 3, 4))(*operands[:4],
                                                    operands[5])


def everything(*args):
    """(y, the frozen form's y, the five gradients, the frozen form's)."""
    return (moe.expert_ffn(*args[:-1]), moe.expert_ffn_frozen(*args[:-1]),
            gradients(moe.expert_ffn, args),
            gradients(moe.expert_ffn_frozen, args))


@pytest.mark.parametrize("case,dtype,tol,chunk_tiles", [
    ("empty_one_three", jnp.float32, 2e-6, 2),
    ("empty_one_three", jnp.bfloat16, 1e-2, 1),
    ("all_held", jnp.float32, 2e-6, 8),
    ("all_held", jnp.bfloat16, 1e-2, 3),
    ("no_tile", jnp.float32, 0.0, 2),
    ("no_tile", jnp.bfloat16, 0.0, 1),
])
def test_the_kernels_are_the_loop_over_the_used_tiles(
        monkeypatch, case, dtype, tol, chunk_tiles):
    """Forward, trained backward and frozen backward against the loop.
    ``chunk_tiles`` slots a kernel call: at 1 the run of three tiles
    joins the chunk before it twice and goes on into the next twice, at
    2 it does each once."""
    args = layer(case, dtype)
    monkeypatch.setattr(moe, "_GATHER_TILES", min(chunk_tiles, 2))
    monkeypatch.setattr(moe, "_ROWS_BYTES",
                        chunk_tiles * TILE * args[0][0].nbytes)
    tiles = (tuple(np.asarray(args[6]).tolist()), int(args[7]))
    assert tiles[1] == sum(-(-load // TILE) for load in _CASES[case])
    (y, y_frozen, trained, frozen), want_y, want = jax.jit(lambda *a: (
        everything(*a), oracle_forward(*a[:6], *tiles),
        oracle_backward(*a[:6], *tiles, a[8])))(*args)
    assert y.dtype == dtype
    close(y, want_y, tol)
    np.testing.assert_array_equal(y_frozen, y)
    for got, had in zip(trained, want):
        assert got.dtype == had.dtype
        close(got, had, tol)
    for i in (0, 4):  # rows and gates: the same arithmetic
        np.testing.assert_array_equal(frozen[i], trained[i])
    assert not any(np.any(np.asarray(g)) for g in frozen[1:4])
    if case == "empty_one_three":  # expert 0 of the held ones has no row
        assert not any(np.any(np.asarray(g[0])) for g in trained[1:4])
        assert all(np.any(np.asarray(g[1:])) for g in trained[1:4])


def test_vmap_is_a_loop_that_equals_the_calls_of_its_elements():
    """Two clients that bring their own weights, each over three
    sequences that share them, as the round engine and the models map
    the layer: forward and every gradient; two kernels a pass (the rows'
    products, then the combining one), traced once for all six."""
    def both(*args):
        return moe.expert_ffn(*args[:-1]), gradients(moe.expert_ffn, args)

    shared = (0, None, None, None, 0, 0, 0, 0, 0)
    seqs = [jnp.stack(a) for a in zip(*(
        layer("empty_one_three", jnp.float32, seed=s) for s in (0, 1, 2)))]
    seqs[1:4] = [w[0] for w in seqs[1:4]]
    clients = [jnp.stack([a, a[::-1]]) if ax == 0 else jnp.stack([a, 2 * a])
               for a, ax in zip(seqs, shared)]
    mapped = jax.jit(jax.vmap(jax.vmap(both, in_axes=shared))).trace(*clients)
    y, grads = mapped.lower().compile()(*clients)
    one = jax.jit(both)
    for c in range(2):
        for i in range(3):
            want_y, want = one(*(a[c] if ax is None else a[c, i]
                                 for a, ax in zip(clients, shared)))
            close(y[c, i], want_y, 2e-6)
            # a sequence's part of the weights' gradient is its own
            for got, had in zip(grads, want):
                close(got[c, i], had, 2e-6)
    calls = str(mapped.jaxpr)
    # forward, backward, and the gradient's forward is the forward again
    assert calls.count("pallas_call") == 6


@pytest.mark.parametrize("d,f,trained,want", [
    (2048, 768, False, 768), (2048, 768, True, 768),   # Keye's experts
    (7168, 2048, False, 512),                          # A.X-K1's
    (16, 12, True, 12),                                # rehearsal widths
    (7168, 2048, True, None),
])
def test_the_width_block_follows_from_the_shapes(d, f, trained, want):
    if want is None:
        with pytest.raises(ValueError, match="accumulated whole"):
            moe._width_block(256, d, f, 2, trained)
    else:
        assert moe._width_block(256, d, f, 2, trained) == want


@pytest.mark.parametrize("t,d,f,products,want", [
    (8192, 2048, 768, 1, 2048), (8192, 2048, 768, 2, 2048),  # Keye: whole
    (4096, 7168, 2048, 1, 1792), (4096, 7168, 2048, 2, 1792),  # A.X-K1
    (65536, 2048, 768, 1, 256),                     # a longer sequence
    (64, 16, 12, 2, 16),                            # rehearsal widths
    (2 ** 20, 2048, 768, 1, None),
])
def test_the_hidden_block_follows_from_the_shapes(t, d, f, products, want):
    """The combining kernel holds every token's float32 result in VMEM,
    so many columns at a time as fit."""
    if want is None:
        with pytest.raises(ValueError, match="does not fit"):
            moe._hidden_block(t, 256, d, f, 2, products)
    else:
        assert moe._hidden_block(t, 256, d, f, 2, products) == want
