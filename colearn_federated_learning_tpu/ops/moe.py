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
padded to a multiple of ``tile`` rows, so that a tile has exactly one
expert, and the *used* tiles (a data-dependent count) meet their
expert's weights in grouped products. On one chip the layer runs
without its exchange.

``route`` (router, top-k, sort/dispatch tables) and ``expert_ffn`` (the
grouped products) are separate so that the model can put them under the
named scopes ``moe_route`` and ``moe_experts``. The four tables that
``expert_ffn`` reads leave ``route`` under the name ``moe_dispatch``
(``checkpoint_name``): a model that rematerialises its layers and
keeps that name (a megabyte a layer and sequence at 16,384 tokens) runs
``route`` once a step and layer, in the forward pass, where it holds a
share of the experts; without the name the backward pass runs all of
it again to rebuild them.

``expert_ffn`` is Pallas TPU kernels (off the chip they run in
interpret mode), two a pass. The row kernels, ``moe_experts_forward``,
``moe_experts_backward`` and, for frozen experts,
``moe_experts_backward_rows``: a tile's expert is a scalar-prefetched
operand that the weights' block index reads, so the DMA engine fetches
``w1[e]`` / ``w3[e]`` / ``w2[e]`` from the stacked arrays and
consecutive tiles of one expert find them in VMEM; ``a``, ``b`` and
their activations never leave VMEM, what leaves is ``expert_width``
wide (``silu(a) * b``; ``da`` and ``db``), and a trained expert's three
weight gradients accumulate in float32 VMEM scratch over its
consecutive tiles and are written once, rounded once. The combining
kernel, ``moe_experts_combine``, runs the products that are ``hidden``
wide (with ``w2``; with ``w1`` and ``w3`` transposed) and adds every
row of a tile to its token's row of the layer's float32 result, which
stays in VMEM while the tiles go by, a block of columns at a time where
it does not fit whole: the gate goes on where a tile's rows are added,
and no row is scattered through HBM (XLA's scatter-add of 256 rows
takes 23 us with its target in VMEM, which no kernel beside it leaves
room for, and 63 to 99 in HBM; the kernel's takes 3: PERF.md, PR 30).
The widths of the blocks follow from the shapes (:func:`_width_block`,
:func:`_hidden_block`). Rows enter through XLA, for the tiles in use
only: a loop gathers ``_GATHER_TILES`` tiles' rows at a time into the
operand of one kernel call, which holds as many tiles as
``_ROWS_BYTES`` allow (64 at hidden 2,048, 16 at 7,168; the tiles in
use beyond them, if the held experts ever draw that many, take further
calls); no array has the worst-case row count times ``hidden``.

Reverse-mode autodiff has no rule for a data-dependent trip count, so
``expert_ffn`` carries its own backward pass; and ``vmap`` has one for
a kernel with batched scalar operands that slices every operand per
element, the stacked weights too, so the passes carry their own
batching rule (:func:`_batched`): a loop over the elements that cuts
out an element's rows and tables and never a weight.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from colearn_federated_learning_tpu.ops.sparse_attention import (
    _LANES,
    _NT,
    _VMEM_LIMIT,
    _call,
)
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
    renormalised gates by ``gate_scale``.

    ``row_token``, ``row_gate``, ``tile_expert`` and ``n_tiles`` carry
    the ``checkpoint_name`` ``"moe_dispatch"``; the other fields feed
    counters of the forward pass only. Where every expert is held the
    gates take a gradient, whose backward pass needs the scores, the
    selection and the sort's order again: a rematerialisation recomputes
    those there whatever it keeps."""
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
    # compared against every bin and summed, which XLA vectorises: a
    # scatter-add of n colliding updates into these few bins runs them
    # one after another (8.7 ns an update on a v5e: PERF.md, PR 32)
    counts = (jnp.arange(experts_held + 1, dtype=jnp.int32)[:, None]
              == local[None, :]).sum(-1, dtype=jnp.int32)
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
    # what expert_ffn's two passes read, by name: a rematerialisation
    # that keeps "moe_dispatch" runs none of the above a second time
    # (one field without the name keeps the chain it hangs on alive)
    row_token, row_gate, tile_expert, n_tiles = (
        checkpoint_name(a, "moe_dispatch")
        for a in (row_token, row_gate, tile_expert, padded_end[-1] // tile))
    return Dispatch(row_token, row_gate, tile_expert, n_tiles,
                    counts[:experts_held], held.mean(dtype=jnp.float32),
                    top_e, groups)


_ROWS_BYTES = 64 * 2 ** 20  # of one operand's gathered rows in a kernel call
_GATHER_TILES = 4  # tiles of rows XLA gathers at a time
_NN = (((1,), (0,)), ((), ()))  # a [m, k] . b [k, n] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # a [k, m] . b [k, n] -> [m, n]
_SEQUENTIAL = ("arbitrary", "arbitrary")


def _lane_blocks(n: int):
    """The divisors of ``n`` in whole lanes, largest first (``n`` itself
    where it has none: a block may always span its dimension)."""
    return [b for b in range(n, 0, -_LANES) if n % b == 0] or [n]


def _width_block(tile: int, d: int, f: int, itemsize: int, trained: bool):
    """How many of an expert's ``f`` columns a grid step of the row
    kernels holds: the largest divisor of ``f`` in whole lanes (``f``
    itself where it has none) at which the step's blocks fit
    ``_VMEM_LIMIT``: the rows in (two operands, two buffers each), three
    weight blocks in two buffers, a tile's intermediates and results,
    and for trained experts the three gradient blocks, their float32
    accumulators and one product's result. The accumulators hold an
    expert's whole gradient, so trained experts take ``f`` or nothing."""
    def need(bf):
        grads = 3 * d * bf * (2 * itemsize + 4) + d * bf * 4
        return (4 * tile * d * itemsize + 6 * d * bf * itemsize
                + 8 * tile * bf * 4 + (grads if trained else 0))

    blocks = _lane_blocks(f)
    for bf in blocks[:1] if trained else blocks:
        if need(bf) <= _VMEM_LIMIT:
            return bf
    raise ValueError(
        f"experts of [{d}, {f}] do not fit the kernels' VMEM "
        f"({need(blocks[0]) >> 20} MiB of {_VMEM_LIMIT >> 20})"
        + ": a trained expert's gradient is accumulated whole" * trained)


def _hidden_block(t: int, tile: int, d: int, f: int, itemsize: int,
                  products: int):
    """How many of the ``d`` columns of the layer's result the combining
    kernel holds at a time: the largest divisor of ``d`` in whole lanes
    at which the float32 result of all ``t`` tokens, a tile's products
    and ``products`` weight blocks and row tiles in two buffers fit
    ``_VMEM_LIMIT``."""
    def need(bd):
        return (t * bd * 4 + 3 * tile * bd * 4
                + 2 * products * (f * bd + tile * f) * itemsize)

    for bd in _lane_blocks(d):
        if need(bd) <= _VMEM_LIMIT:
            return bd
    raise ValueError(
        f"{t} tokens' float32 result does not fit the kernels' VMEM at "
        f"{_lane_blocks(d)[-1]} of its {d} columns "
        f"({need(_lane_blocks(d)[-1]) >> 20} MiB of {_VMEM_LIMIT >> 20})")


def _silu_products(x, w1_ref, w3_ref):
    a = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    b = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
    return a, b, jax.nn.sigmoid(a)


def _forward_kernel(tw_ref, to_ref, live_ref, joins_ref, x_ref, w1_ref,
                    w3_ref, mid_ref):
    """One tile of rows against one block of its expert's columns:
    ``silu(x w1) * (x w3)``, rounded as the product with ``w2`` takes
    it. Slots past the live ones do nothing (their index maps repeat the
    last live slot's blocks, so nothing is fetched for them either)."""
    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        a, b, sig = _silu_products(x_ref[...], w1_ref, w3_ref)
        mid_ref[...] = (a * sig * b).astype(mid_ref.dtype)


def _backward_kernel(tw_ref, to_ref, live_ref, joins_ref, x_ref, dout_ref,
                     gate_ref, w1_ref, w3_ref, w2_ref, *refs, trained: bool):
    """One tile of rows and of the result's cotangent against one block
    of its expert's columns: the two first products again, the
    cotangent back through ``w2``, the gates' cotangent (summed over the
    width blocks, the innermost grid axis), and ``da`` and ``db``,
    rounded as the products with ``w1`` and ``w3`` take them. Three
    products; frozen experts stop there (the combining kernel runs the
    other two).

    ``trained``: three more, the weight gradients, which accumulate in
    float32 scratch over the consecutive tiles of a result group's run
    and leave, rounded once, where the run ends. A run that the call
    before left unfinished (``joins_ref[0]``) starts from that call's
    sums, and one that goes on in the next call (``joins_ref[1]``)
    hands its sums on, both through float32 arrays in HBM."""
    if trained:
        (_, _, _, c1_in, c3_in, c2_in, da_ref, db_ref, dg_ref, dw1_ref,
         dw3_ref, dw2_ref, c1_out, c3_out, c2_out, acc1, acc3, acc2,
         sem) = refs
        sums = ((acc1, c1_in, c1_out, dw1_ref), (acc3, c3_in, c3_out, dw3_ref),
                (acc2, c2_in, c2_out, dw2_ref))
    else:
        da_ref, db_ref, dg_ref = refs
    t, f = pl.program_id(0), pl.program_id(1)
    live = live_ref[0]

    def copy_all(pairs):
        copies = [pltpu.make_async_copy(src, dst, sem.at[i])
                  for i, (src, dst) in enumerate(pairs)]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    @pl.when(t < live)
    def _():
        x, dout, gate = x_ref[...], dout_ref[...], gate_ref[...]
        cd = x.dtype
        a, b, sig = _silu_products(x, w1_ref, w3_ref)
        silu = a * sig
        mid = silu * b
        # cotangent of the ungated tile output, back through w2
        dmid_pre = jax.lax.dot_general(dout, w2_ref[...], _NT,
                                       preferred_element_type=jnp.float32)
        part = (mid * dmid_pre).sum(-1, keepdims=True)

        @pl.when(f == 0)
        def _():
            dg_ref[...] = part

        @pl.when(f > 0)
        def _():
            dg_ref[...] += part

        dmid = dmid_pre * gate
        da = (dmid * b * (sig * (1.0 + a * (1.0 - sig)))).astype(cd)
        db = (dmid * silu).astype(cd)
        da_ref[...] = da
        db_ref[...] = db
        if not trained:
            return
        here = to_ref[t]
        last = t == live - 1
        starts = (t == 0) | (to_ref[jnp.maximum(t - 1, 0)] != here)
        ends = last | (to_ref[jnp.minimum(t + 1, to_ref.shape[0] - 1)] != here)
        joined = (t == 0) & (joins_ref[0] != 0)

        @pl.when(starts & ~joined)
        def _():
            for acc, *_ in sums:
                acc[...] = jnp.zeros_like(acc)

        @pl.when(joined)
        def _():
            copy_all([(c_in, acc) for acc, c_in, _, _ in sums])

        dout_g = (dout.astype(jnp.float32) * gate).astype(cd)
        for acc, left, right in ((acc1, x, da), (acc3, x, db),
                                 (acc2, mid.astype(cd), dout_g)):
            acc[...] += jax.lax.dot_general(
                left, right, _TN, preferred_element_type=jnp.float32)
        goes_on = last & (joins_ref[1] != 0)

        @pl.when(ends & ~goes_on)
        def _():
            for acc, _, _, dw_ref in sums:
                dw_ref[...] = acc[...].astype(dw_ref.dtype)

        @pl.when(goes_on)
        def _():
            copy_all([(acc, c_out) for acc, _, c_out, _ in sums])


def _combine_kernel(tok_ref, tw_ref, fill_ref, live_ref, first_ref, *refs,
                    products: int, gated: bool):
    """The layer's result (or its rows' cotangent) for one block of its
    columns, float32 and whole in VMEM while the tiles go by: a tile's
    rows are ``products`` products of ``[tile, f]`` operands with their
    expert's weight blocks, summed, times the rows' gates where
    ``gated``, and each of the tile's filled rows is added to the row of
    its token, which the scalar-prefetched ``tok_ref`` names: no row
    scatter goes through HBM. The block starts from zero where
    ``first_ref`` says so and from what the call before left otherwise,
    and goes to HBM once, after the last slot."""
    lefts, refs = refs[:products], refs[products:]
    gate_ref, refs = (refs[0], refs[1:]) if gated else (None, refs)
    ws, (y_in, y_out, acc, rows, sem) = refs[:products], refs[products:]
    j, t = pl.program_id(0), pl.program_id(1)
    tile, bd = rows.shape
    column = pl.ds(pl.multiple_of(j * bd, bd), bd)

    @pl.when(t == 0)
    def _():
        @pl.when(first_ref[0] != 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(first_ref[0] == 0)
        def _():
            copy = pltpu.make_async_copy(y_in.at[:, column], acc, sem.at[0])
            copy.start()
            copy.wait()

    @pl.when(t < live_ref[0])
    def _():
        # forward: rows [tile, f] . w2's block [f, bd]; backward: . the
        # blocks [bd, f] of w1 and w3
        out = sum(jax.lax.dot_general(left[...], w[...],
                                      _NN if gated else _NT,
                                      preferred_element_type=jnp.float32)
                  for left, w in zip(lefts, ws))
        rows[...] = out * gate_ref[...] if gated else out

        def add(r, carry):
            at = pl.ds(tok_ref[t * tile + r], 1)
            acc[at, :] += rows[pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(0, fill_ref[t], add, 0)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        copy = pltpu.make_async_copy(acc, y_out.at[:, column], sem.at[0])
        copy.start()
        copy.wait()


def _grid_spec(scalars: int, grid, in_specs, out_specs, scratch=()):
    return dict(grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=scalars, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch))


class _Call(NamedTuple):
    """One kernel call's share of the dispatch tables: ``slots``
    consecutive tiles from tile ``first``, of which ``live`` are in use."""

    first: Any
    live: Any     # [1]
    token: Any    # [slots * tile] the rows' tokens
    gate: Any     # [slots * tile, 1]
    fill: Any     # [slots] rows of each tile up to its last real one
    scalars: Any  # (weight group, result group, live, joins) of the slots


def _calls(h, row_token, row_gate, tile_w, tile_out):
    """How a pass walks the tiles in use: (rows a tile, slots a kernel
    call, a function from a call's number to its :class:`_Call`). A call
    takes as many slots as ``_ROWS_BYTES`` of gathered rows hold, whole
    ``_GATHER_TILES``; the tiles in use fit one call unless the held
    experts draw far more than their share."""
    nt = tile_w.shape[0]
    tile = row_token.shape[0] // nt
    g = min(nt, _GATHER_TILES)
    slots = max(g, min(nt, _ROWS_BYTES // (tile * h.shape[1]
                                            * h.dtype.itemsize)) // g * g)
    pad = -(-nt // slots) * slots + 1 - nt  # whole calls, and a slot after
    token = jnp.pad(row_token, (0, pad * tile))
    gate = jnp.pad(row_gate, (0, pad * tile))
    groups = jnp.pad(jnp.stack([tile_w, tile_out]), ((0, 0), (1, pad)),
                     mode="edge")  # and a slot before

    def call(c, n_tiles):
        first = c * slots
        live = jnp.clip(n_tiles - first, 0, slots)
        tok = jax.lax.dynamic_slice(token, (first * tile,), (slots * tile,))
        gat = jax.lax.dynamic_slice(gate, (first * tile,), (slots * tile,))
        w, out = jax.lax.dynamic_slice(groups, (0, first), (2, slots + 2))
        # a padding row has gate 0 (and so has a real row that adds nothing)
        fill = ((gat.reshape(slots, tile) != 0)
                * jnp.arange(1, tile + 1)).max(-1).astype(jnp.int32)
        joins = jnp.stack([(first > 0) & (out[0] == out[1]),
                           (first + slots < n_tiles)
                           & (out[slots + 1] == out[slots])])
        return _Call(first, live.reshape(1), tok, gat.reshape(-1, 1), fill,
                     (w[1:-1], out[1:-1], live.reshape(1),
                      joins.astype(jnp.int32)))

    return tile, slots, call


def _gather(a, token, live, tile: int):
    """``a[token]`` for the ``live`` first tiles of ``token``'s rows,
    ``_GATHER_TILES`` at a time; the rows of the other tiles are not
    read."""
    n = min(_GATHER_TILES, token.shape[0] // tile) * tile

    def body(c, rows):
        idx = jax.lax.dynamic_slice(token, (c * n,), (n,))
        return jax.lax.dynamic_update_slice(
            rows, jnp.take(a, idx, axis=0, mode="clip"), (c * n, 0))

    return jax.lax.fori_loop(
        0, -(-live[0] * tile // n), body,
        zeros_varying_like(a, (token.shape[0], a.shape[1])))


def _row_specs(tile: int, d: int, f: int, bf: int):
    """The block specs of the row kernels, on a grid of (slots, width
    blocks): a tile's rows ``[tile, d]``, its column ``[tile, 1]``, its
    ``[tile, bf]`` block of an ``f``-wide operand, and the blocks of
    ``w1`` / ``w3`` and of ``w2`` that the tile's scalar-prefetched
    weight group names. Every index map clamps to the last live slot,
    at whose blocks the pipeline then stays."""
    def at(t, s):
        return jnp.minimum(t, s[2][0] - 1)

    def block(t, fi, s):
        return jnp.where(t < s[2][0], fi, f // bf - 1)

    rows = pl.BlockSpec((tile, d), lambda t, fi, *s: (at(t, s), 0))
    column = pl.BlockSpec((tile, 1), lambda t, fi, *s: (at(t, s), 0))
    wide = pl.BlockSpec((tile, bf),
                        lambda t, fi, *s: (at(t, s), block(t, fi, s)))
    w13 = pl.BlockSpec((None, d, bf),
                       lambda t, fi, *s: (s[0][at(t, s)], 0, block(t, fi, s)))
    w2 = pl.BlockSpec((None, bf, d),
                      lambda t, fi, *s: (s[0][at(t, s)], block(t, fi, s), 0))
    return rows, column, wide, w13, w2


def _combine(y, call: _Call, tile: int, lefts, weights, gated: bool):
    """``y`` (float32 ``[tokens, d]``) plus the rows of ``call``'s live
    tiles, each added to its token's row: :func:`_combine_kernel` over
    the blocks of ``d`` that :func:`_hidden_block` allows."""
    t, d = y.shape
    f = lefts[0].shape[1]
    slots = call.fill.shape[0]
    n = len(lefts)
    bd = _hidden_block(t, tile, d, f, lefts[0].dtype.itemsize, n)

    def at(ti, s):
        return jnp.minimum(ti, s[3][0] - 1)

    left = pl.BlockSpec((tile, f), lambda j, ti, *s: (at(ti, s), 0))
    column = pl.BlockSpec((tile, 1), lambda j, ti, *s: (at(ti, s), 0))
    if gated:  # w2 [groups, f, d]
        w = pl.BlockSpec((None, f, bd),
                         lambda j, ti, *s: (s[1][at(ti, s)], 0, j))
    else:  # w1, w3 [groups, d, f]
        w = pl.BlockSpec((None, bd, f),
                         lambda j, ti, *s: (s[1][at(ti, s)], j, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    ins = (*lefts, *((call.gate,) if gated else ()), *weights, y)
    y, = _call(
        "moe_experts_combine",
        functools.partial(_combine_kernel, products=n, gated=gated),
        (call.token, call.scalars[0], call.fill, call.live,
         (call.first == 0).astype(jnp.int32).reshape(1), *ins),
        [(y.shape, y.dtype)], _SEQUENTIAL,
        input_output_aliases={5 + len(ins) - 1: 0},
        **_grid_spec(
            5, (d // bd, slots),
            [left] * n + [column] * gated + [w] * n + [anywhere], [anywhere],
            [pltpu.VMEM((t, bd), jnp.float32),
             pltpu.VMEM((tile, bd), jnp.float32),
             pltpu.SemaphoreType.DMA((1,))]))
    return y


def _forward_call(n_out, h, w1, w3, w2, row_token, row_gate, tile_w, tile_out,
                  n_tiles):
    del n_out
    d, f = w1.shape[-2:]
    tile, slots, call_at = _calls(h, row_token, row_gate, tile_w, tile_out)
    bf = _width_block(tile, d, f, h.dtype.itemsize, False)
    rows, _, wide, w13, _ = _row_specs(tile, d, f, bf)

    def body(c, y):
        call = call_at(c, n_tiles)
        x = _gather(h, call.token, call.live, tile)
        mid, = _call(
            "moe_experts_forward", _forward_kernel, (*call.scalars, x, w1, w3),
            [((slots * tile, f), h.dtype)], _SEQUENTIAL,
            **_grid_spec(4, (slots, f // bf), [rows, w13, w13], [wide]))
        return _combine(y, call, tile, (mid,), (w2,), True)

    y = jax.lax.fori_loop(0, -(-n_tiles // slots), body,
                          zeros_varying_like(h, dtype=jnp.float32))
    return y.astype(h.dtype)


def _backward_call(trained: bool, n_out, h, w1, w3, w2, row_token, row_gate,
                   tile_w, tile_out, n_tiles, dy):
    """The backward pass: (the rows' cotangent, for ``trained`` experts
    the three weights' as ``[n_out, ...]``, one per result group and
    zero for a group no tile names, the gates' cotangent)."""
    d, f = w1.shape[-2:]
    tile, slots, call_at = _calls(h, row_token, row_gate, tile_w, tile_out)
    bf = _width_block(tile, d, f, h.dtype.itemsize, trained)
    rows, column, wide, w13, w2_spec = _row_specs(tile, d, f, bf)
    shapes = ((d, f), (d, f), (f, d)) if trained else ()
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grads = [pl.BlockSpec((None,) + s,
                          lambda t, fi, *s: (s[1][jnp.minimum(t, s[2][0] - 1)],
                                             0, 0)) for s in shapes]
    n_rows = -(-row_gate.shape[0] // (slots * tile)) * slots * tile

    def body(c, carry):
        # held: a trained layer's three gradients and the three float32
        # sums that a call hands on to the next, updated in place
        dh, dgate, held = carry
        call = call_at(c, n_tiles)
        x = _gather(h, call.token, call.live, tile)
        dout = _gather(dy, call.token, call.live, tile)
        da, db, dg, *held = _call(
            "moe_experts_backward" + "_rows" * (not trained),
            functools.partial(_backward_kernel, trained=trained),
            (*call.scalars, x, dout, call.gate, w1, w3, w2, *held),
            [((slots * tile, f), h.dtype)] * 2
            + [((slots * tile, 1), jnp.float32)]
            + [(a.shape, a.dtype) for a in held],
            _SEQUENTIAL,
            input_output_aliases={10 + i: 3 + i for i in range(len(held))},
            **_grid_spec(
                4, (slots, f // bf),
                [rows, rows, column, w13, w13, w2_spec]
                + [anywhere] * len(held),
                [wide, wide, column] + grads + [anywhere] * len(shapes),
                [pltpu.VMEM(s, jnp.float32) for s in shapes]
                + [pltpu.SemaphoreType.DMA((3,))] * trained))
        alive = jnp.arange(slots * tile) < call.live * tile
        dgate = jax.lax.dynamic_update_slice(
            dgate, jnp.where(alive, dg.reshape(-1), 0.0),
            (call.first * tile,))
        return (_combine(dh, call, tile, (da, db), (w1, w3), False), dgate,
                held)

    dh, dgate, held = jax.lax.fori_loop(
        0, -(-n_tiles // slots), body,
        (zeros_varying_like(h, dtype=jnp.float32),
         zeros_varying_like(h, (n_rows,), jnp.float32),
         [zeros_varying_like(h, (n_out,) + s, w1.dtype) for s in shapes]
         + [zeros_varying_like(h, s, jnp.float32) for s in shapes]))
    return (dh.astype(h.dtype), *held[:3], dgate[:row_gate.shape[0]])


_backward_trained = functools.partial(_backward_call, True)
_backward_rows = functools.partial(_backward_call, False)


@functools.cache
def _batched(call, n_out: int):
    """``call(n_out, h, w1, w3, w2, row_token, row_gate, tile_w, tile_out,
    n_tiles[, dy])`` as a function that ``vmap`` turns into a loop over
    the elements in which nothing but an element's rows and tables is
    cut out per element (jax's own rule for a kernel with batched scalar
    operands slices every operand per element, the stacked weights
    too). Elements that share the weights keep naming the same blocks;
    where every element brings its own, the weights become more groups
    of one array (``[C, E, d, f] -> [C * E, d, f]``) and an element's
    tiles name its own among them. One element at a time, because the
    layer's float32 result has to fit VMEM whole."""
    fn = custom_vmap(functools.partial(call, n_out))

    @fn.def_vmap
    def rule(size, in_batched, *args):
        own = any(in_batched[1:4])
        args = [a if b or (not own and 1 <= i <= 3)
                else jnp.broadcast_to(a, (size,) + a.shape)
                for i, (a, b) in enumerate(zip(args, in_batched))]
        h, w1, w3, w2, row_token, row_gate, tile_w, *rest = args
        weights = (w1, w3, w2)
        if own:
            tile_w = tile_w + w1.shape[1] * jnp.arange(size)[:, None]
            weights = tuple(w.reshape((-1,) + w.shape[2:]) for w in weights)
        outs = jax.lax.map(
            lambda x: fn(x[0], *weights, *x[1:]),
            (h, row_token, row_gate, tile_w, *rest))
        return outs, jax.tree.map(lambda _: True, outs)

    return fn


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ffn(trained: bool, n_out: int, h, own, read, row_token, row_gate, tile_w,
         tile_out, n_tiles):
    """The layer over the weights ``read`` (three arrays of ``[groups,
    ...]``, ``tile_w`` naming each tile's group in them), differentiated
    as a function of ``own``, the layer's ``n_out`` experts themselves:
    the same values, which nothing here reads."""
    del own
    return _batched(_forward_call, n_out)(
        h, *read, row_token, row_gate, tile_w, tile_out, n_tiles)


def _ffn_fwd(trained, n_out, h, own, read, row_token, row_gate, tile_w,
             tile_out, n_tiles):
    y = _ffn(trained, n_out, h, own, read, row_token, row_gate, tile_w,
             tile_out, n_tiles)
    return y, (h, read, row_token, row_gate, tile_w, tile_out, n_tiles)


def _ffn_bwd(trained, n_out, res, dy):
    h, read, row_token, row_gate, tile_w, tile_out, n_tiles = res
    dh, *dws, dgate = _batched(
        _backward_trained if trained else _backward_rows, n_out)(
            h, *read, row_token, row_gate, tile_w, tile_out, n_tiles, dy)
    return dh, tuple(dws) or None, None, None, dgate, None, None, None


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def expert_ffn(h, w1, w3, w2, row_token, row_gate, tile_expert, n_tiles,
               stack=None, trained: bool = True):
    """``y[t] = sum over the held experts e of token t of gate[t, e] *
    (silu(h[t] w1[e]) * (h[t] w3[e])) w2[e]``, over the dispatch tables
    of :func:`route`. ``h``: ``[T, D]``; ``w1``, ``w3``: ``[experts_held,
    D, F]``; ``w2``: ``[experts_held, F, D]``. Products accumulate in
    float32; the result has ``h``'s dtype.

    ``stack``: ``(w1s, w3s, w2s, layer)`` where the three weights are
    ``w1s[layer]`` ... of arrays stacked over the layers. The kernels
    then take the layer's blocks out of the stacks by their block index
    and ``w1`` / ``w3`` / ``w2`` are never read: what a scan over the
    layers would cut out of the stacks for them, 151 MB a pass in
    ``keye_silo_8k``, is dead code. They still take the gradient."""
    read, tile_w = (w1, w3, w2), tile_expert
    if stack is not None:
        *stacks, layer = stack
        read = tuple(w.reshape((-1,) + w.shape[2:]) for w in stacks)
        tile_w = tile_expert + layer * w1.shape[0]
    return _ffn(trained, w1.shape[0], h, (w1, w3, w2), read, row_token,
                row_gate, tile_w, tile_expert, n_tiles)


def expert_ffn_frozen(h, w1, w3, w2, row_token, row_gate, tile_expert,
                      n_tiles, stack=None):
    """:func:`expert_ffn` against frozen experts: the same result, and a
    backward pass that computes the rows' and the gates' cotangents
    only (the weights' are ``None``, which JAX reads as zero)."""
    return expert_ffn(h, w1, w3, w2, row_token, row_gate, tile_expert,
                      n_tiles, stack, trained=False)


def tile_fill(d: Dispatch, tile: int):
    """Held assignments over the rows of the tiles in use: how much of
    what the kernels compute is not a group's padding (1 where no tile
    is in use)."""
    return jnp.where(d.n_tiles > 0, d.counts.sum(dtype=jnp.float32)
                     / jnp.maximum(d.n_tiles * tile, 1), 1.0)


def expert_share(h, w_router, w1, w3, w2, *, top_k: int, expert_offset: int,
                 tile: int):
    """Route and compute in one call: (this chip's part of the layer's
    result, the :class:`Dispatch` it was computed over)."""
    d = route(h, w_router, top_k=top_k, experts_held=w1.shape[0],
              expert_offset=expert_offset, tile=tile)
    y = expert_ffn(h, w1, w3, w2, d.row_token, d.row_gate, d.tile_expert,
                   d.n_tiles)
    return y, d
