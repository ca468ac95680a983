"""A sparse-expert layer that is told which experts it holds.

One chip of an expert-parallel layer: the router scores ALL
``num_experts``, every token takes its ``top_k`` of them with the gates
renormalised over those ``top_k``, and this chip computes the part of the
layer's result that its own ``experts_held`` experts (global ids
``expert_offset .. expert_offset + experts_held``) contribute. What the
absent experts would add is left out; the shares of all chips add up to
the uncut layer (``tests/test_keye_decoder.py`` pins it); the gates'
gradient, which the deployment assembles over the chips, is left out
too where the chip holds a share (see :func:`route`). No token is
dropped and there is no capacity factor: the token-expert assignments
that fall on held experts are sorted by expert, every expert's group is
padded to a multiple of ``tile`` rows, and a loop over the *used* tiles
(a data-dependent trip count) runs the three products of each tile
against its one expert's weights and scatters the rows back. On one chip
the layer runs without its exchange.

``route`` (router, top-k, sort/dispatch tables) and ``expert_ffn`` (the
grouped products) are separate so that the model can put them under the
named scopes ``moe_route`` and ``moe_experts``.

``expert_ffn`` carries its own backward pass: reverse-mode autodiff has
no rule for a loop whose trip count is data, and a static loop over the
worst case (every assignment on a held expert) would do
``num_experts / experts_held`` times the work. Forward and backward are
``sequential_vmap`` functions: under the round engine's per-client
``vmap`` a batched trip count would otherwise turn every iteration into
a ``select`` over the whole carry. (The map's one copy of the stacked
expert weights per call, 52 ms a round in ``keye_silo_8k``, buys the
loop's products their layout: calling the loop on a reshaped view
instead made every tile's products five times slower. PERF.md, PR 25.)
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.custom_batching import sequential_vmap

from colearn_federated_learning_tpu.utils.trees import zeros_varying_like


class Dispatch(NamedTuple):
    """The sorted, tile-padded layout of the held assignments.

    ``row_token`` / ``row_gate``: ``[rows]`` token index and gate of each
    row (padding rows: token 0, gate 0); ``tile_expert``: ``[rows //
    tile]`` local expert of each tile; ``n_tiles``: tiles in use;
    ``counts``: ``[experts_held]`` assignments per held expert;
    ``held_share``: share of all assignments that fall on held experts;
    ``experts``: ``[T, top_k]`` global ids of every token's experts;
    ``groups``: ``[T, topk_group]`` the groups kept for every token
    (group-limited routing only).
    """

    row_token: jnp.ndarray
    row_gate: jnp.ndarray
    tile_expert: jnp.ndarray
    n_tiles: jnp.ndarray
    counts: jnp.ndarray
    held_share: jnp.ndarray
    experts: jnp.ndarray
    groups: Any = None


def group_limited_top_k(scores, top_k: int, n_group: int, topk_group: int):
    """Group-limited selection: the experts are ``n_group`` groups of
    consecutive ids, a group's score is the sum of its two largest
    ``scores``, the ``topk_group`` best groups are kept (ties: the lower
    group), and the ``top_k`` largest scores among their experts win
    (ties: the lower expert id). ``scores``: ``[T, num_experts]``, all
    positive. Returns (scores of the chosen, their ids, the kept
    groups ``[T, topk_group]``)."""
    t, n = scores.shape
    grouped = scores.reshape(t, n_group, n // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    _, groups = jax.lax.top_k(group_score, topk_group)
    kept = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], groups].set(True)
    masked = jnp.where(kept[:, :, None], grouped, -1.0).reshape(t, n)
    _, top_e = jax.lax.top_k(masked, top_k)
    return jnp.take_along_axis(scores, top_e, axis=-1), top_e, groups


def route(h, w_router, *, top_k: int, experts_held: int, expert_offset: int,
          tile: int, scoring: str = "softmax", n_group: int = 1,
          topk_group: int = 1, gate_scale: float = 1.0) -> Dispatch:
    """Router scores over all experts (float32), top-k with gates
    renormalised over the k, and the dispatch tables for the held ones.
    ``h``: ``[T, D]``; ``w_router``: ``[D, num_experts]``. ``scoring``
    ``"softmax"`` takes the k largest probabilities; ``"sigmoid"``
    scores every expert alone, selects within the ``topk_group`` best of
    ``n_group`` groups (:func:`group_limited_top_k`) and multiplies the
    renormalised gates by ``gate_scale``."""
    t = h.shape[0]
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    groups = None
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, top_k)  # ties: the lower expert id
        gates = top_p / top_p.sum(-1, keepdims=True)
    elif scoring == "sigmoid":
        top_p, top_e, groups = group_limited_top_k(
            jax.nn.sigmoid(logits), top_k, n_group, topk_group)
        gates = gate_scale * top_p / top_p.sum(-1, keepdims=True)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if experts_held < w_router.shape[1]:
        # A share of the layer has a share of the gates' gradient: the
        # deployment sums it over the chips, with the combine's exchange,
        # before the router sees it. This chip's part alone says "the
        # held experts are the only ones that lower the loss", and an
        # adaptive optimizer turns that into full steps of the router
        # and of everything before it: the last layer's tile loop, 40
        # tiles at 1/8 of the assignments, ran 60 and then 225 within
        # four rounds (PERF.md, PR 25). Without the exchange the gates
        # are constants of the backward pass; a chip that holds every
        # expert trains its router as usual.
        gates = jax.lax.stop_gradient(gates)

    n = t * top_k
    rows = n + experts_held * tile  # worst case, every group padded
    local = top_e.reshape(n) - expert_offset
    held = (local >= 0) & (local < experts_held)
    local = jnp.where(held, local, experts_held)  # absent: one tail group
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
        rows,  # out of bounds: dropped
    )
    row_token = jnp.zeros(rows, jnp.int32).at[dest].set(token[order],
                                                        mode="drop")
    row_gate = jnp.zeros(rows, jnp.float32).at[dest].set(
        gates.reshape(n)[order], mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(padded_end, jnp.arange(rows // tile) * tile,
                         side="right"),
        experts_held - 1,
    ).astype(jnp.int32)
    return Dispatch(row_token, row_gate, tile_expert,
                    padded_end[-1] // tile, counts[:experts_held],
                    held.mean(dtype=jnp.float32), top_e, groups)


def _tile_inputs(i, tile, h, w1, w3, row_token, row_gate, tile_expert):
    e = tile_expert[i]
    tok = jax.lax.dynamic_slice(row_token, (i * tile,), (tile,))
    gate = jax.lax.dynamic_slice(row_gate, (i * tile,), (tile,))
    x = jnp.take(h, tok, axis=0)
    a = jnp.dot(x, w1[e], preferred_element_type=jnp.float32)
    b = jnp.dot(x, w3[e], preferred_element_type=jnp.float32)
    return e, tok, gate, x, a, b


@sequential_vmap
def _experts_forward(h, w1, w3, w2, row_token, row_gate, tile_expert,
                     n_tiles):
    tile = row_token.shape[0] // tile_expert.shape[0]

    def body(i, y):
        e, tok, gate, _, a, b = _tile_inputs(i, tile, h, w1, w3, row_token,
                                             row_gate, tile_expert)
        mid = (jax.nn.silu(a) * b).astype(h.dtype)
        out = jnp.dot(mid, w2[e], preferred_element_type=jnp.float32)
        return y.at[tok].add(out * gate[:, None])

    y = jax.lax.fori_loop(0, n_tiles, body,
                          zeros_varying_like(h, dtype=jnp.float32))
    return y.astype(h.dtype)


def _tile_cotangents(i, tile, h, w1, w3, w2, row_token, row_gate, tile_expert,
                     dy):
    """One tile of the backward pass, up to the cotangents of its two
    first products: (expert, rows' tokens, gates, rows, ``silu(a) b``,
    rows of ``dy``, ``da``, ``db``, the gates' cotangent)."""
    e, tok, gate, x, a, b = _tile_inputs(i, tile, h, w1, w3, row_token,
                                         row_gate, tile_expert)
    sig = jax.nn.sigmoid(a)
    silu = a * sig
    mid = silu * b
    dout = jnp.take(dy, tok, axis=0)
    # cotangent of the ungated tile output, back through w2
    dmid_pre = jnp.dot(dout, w2[e].T, preferred_element_type=jnp.float32)
    dg = (mid * dmid_pre).sum(-1)
    dmid = dmid_pre * gate[:, None]
    da = (dmid * b * (sig * (1.0 + a * (1.0 - sig)))).astype(h.dtype)
    db = (dmid * silu).astype(h.dtype)
    return e, tok, gate, x, mid, dout, da, db, dg


def _rows_cotangent(e, da, db, w1, w3):
    return (jnp.dot(da, w1[e].T, preferred_element_type=jnp.float32)
            + jnp.dot(db, w3[e].T, preferred_element_type=jnp.float32))


@sequential_vmap
def _experts_backward(h, w1, w3, w2, row_token, row_gate, tile_expert,
                      n_tiles, dy):
    tile = row_token.shape[0] // tile_expert.shape[0]
    cd = h.dtype

    def body(i, carry):
        dh, dw1, dw3, dw2, dgate = carry
        e, tok, gate, x, mid, dout, da, db, dg = _tile_cotangents(
            i, tile, h, w1, w3, w2, row_token, row_gate, tile_expert, dy)
        dout_g = (dout.astype(jnp.float32) * gate[:, None]).astype(cd)
        dw2 = dw2.at[e].add(jnp.dot(mid.astype(cd).T, dout_g,
                                    preferred_element_type=jnp.float32))
        dw1 = dw1.at[e].add(jnp.dot(x.T, da,
                                    preferred_element_type=jnp.float32))
        dw3 = dw3.at[e].add(jnp.dot(x.T, db,
                                    preferred_element_type=jnp.float32))
        dh = dh.at[tok].add(_rows_cotangent(e, da, db, w1, w3))
        dgate = jax.lax.dynamic_update_slice(dgate, dg, (i * tile,))
        return dh, dw1, dw3, dw2, dgate

    zeros = lambda a: zeros_varying_like(h, a.shape, jnp.float32)  # noqa: E731
    dh, dw1, dw3, dw2, dgate = jax.lax.fori_loop(
        0, n_tiles, body,
        (zeros(h), zeros(w1), zeros(w3), zeros(w2), zeros(row_gate)),
    )
    return (dh.astype(h.dtype), dw1.astype(w1.dtype), dw3.astype(w3.dtype),
            dw2.astype(w2.dtype), dgate)


@sequential_vmap
def _experts_backward_rows(h, w1, w3, w2, row_token, row_gate, tile_expert,
                           n_tiles, dy):
    """:func:`_experts_backward` against weights that take no gradient:
    the rows' and the gates' cotangents only, five products a tile
    (two of them the forward's, computed again) where the trained form
    has eight. (XLA cannot drop the three weight
    accumulators itself: they are carried by a loop whose trip count is
    data.)"""
    tile = row_token.shape[0] // tile_expert.shape[0]

    def body(i, carry):
        dh, dgate = carry
        e, tok, _, _, _, _, da, db, dg = _tile_cotangents(
            i, tile, h, w1, w3, w2, row_token, row_gate, tile_expert, dy)
        dh = dh.at[tok].add(_rows_cotangent(e, da, db, w1, w3))
        dgate = jax.lax.dynamic_update_slice(dgate, dg, (i * tile,))
        return dh, dgate

    zeros = lambda a: zeros_varying_like(h, a.shape, jnp.float32)  # noqa: E731
    dh, dgate = jax.lax.fori_loop(0, n_tiles, body,
                                  (zeros(h), zeros(row_gate)))
    return dh.astype(h.dtype), dgate


@jax.custom_vjp
def expert_ffn(h, w1, w3, w2, row_token, row_gate, tile_expert, n_tiles):
    """``y[t] = sum over the held experts e of token t of gate[t, e] *
    (silu(h[t] w1[e]) * (h[t] w3[e])) w2[e]``, over the dispatch tables
    of :func:`route`. ``h``: ``[T, D]``; ``w1``, ``w3``: ``[experts_held,
    D, F]``; ``w2``: ``[experts_held, F, D]``. Products accumulate in
    float32; the result has ``h``'s dtype."""
    return _experts_forward(h, w1, w3, w2, row_token, row_gate, tile_expert,
                            n_tiles)


def _expert_ffn_fwd(h, w1, w3, w2, row_token, row_gate, tile_expert, n_tiles):
    y = _experts_forward(h, w1, w3, w2, row_token, row_gate, tile_expert,
                         n_tiles)
    return y, (h, w1, w3, w2, row_token, row_gate, tile_expert, n_tiles)


def _expert_ffn_bwd(res, dy):
    dh, dw1, dw3, dw2, dgate = _experts_backward(*res, dy)
    return dh, dw1, dw3, dw2, None, dgate, None, None


expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


@jax.custom_vjp
def expert_ffn_frozen(h, w1, w3, w2, row_token, row_gate, tile_expert,
                      n_tiles):
    """:func:`expert_ffn` against frozen experts: the same result, and a
    backward pass that computes the rows' and the gates' cotangents
    only (the weights' are ``None``, which JAX reads as zero)."""
    return _experts_forward(h, w1, w3, w2, row_token, row_gate, tile_expert,
                            n_tiles)


def _expert_ffn_frozen_bwd(res, dy):
    dh, dgate = _experts_backward_rows(*res, dy)
    return dh, None, None, None, None, dgate, None, None


expert_ffn_frozen.defvjp(_expert_ffn_fwd, _expert_ffn_frozen_bwd)


def expert_share(h, w_router, w1, w3, w2, *, top_k: int, expert_offset: int,
                 tile: int):
    """Route and compute in one call: (this chip's part of the layer's
    result, the :class:`Dispatch` it was computed over)."""
    d = route(h, w_router, top_k=top_k, experts_held=w1.shape[0],
              expert_offset=expert_offset, tile=tile)
    y = expert_ffn(h, w1, w3, w2, d.row_token, d.row_gate, d.tile_expert,
                   d.n_tiles)
    return y, d
