"""Fused server-apply chain as a pallas TPU kernel (ROADMAP item 2,
lever b; ``server.fused_apply``).

The tail of every round program is a chain of separate XLA ops over the
full parameter set: trust/weight scaling of the upload stack → weighted
reduction → negate (pseudo-gradient) → optax trace (server momentum) →
scale by −lr → apply. Each link is an HBM round trip of |params| bytes
(the profiled headline round spends its non-conv time in exactly this
kind of memory-bound elementwise traffic — BASELINE.md r2 profile), and
on the stacked paths the reduction additionally materializes weighted
``[K, |params|]`` intermediates. This module collapses the chain into
ONE VMEM-resident pass over the flat param vector:

- :func:`fused_reduce_apply` — the stacked-path kernel: per tile it
  loads the ``[K, tile]`` wire-upload block, contracts it with the
  combined ``[K]`` weights (FedAvg weight × reputation trust ×
  1/denominator — or krum's one-hot selection row), and applies the
  server optimizer update to the params (and momentum) tile in the
  same pass. One read of the stack, one read-modify-write of
  params/momentum — no weighted ``[K, N]`` intermediate ever lands in
  HBM.
- :func:`fused_delta_apply` — the psum-path kernel: the reduction
  already happened inside the lane psum, so the kernel fuses
  pseudo-grad → momentum trace → lr scale → apply (four XLA passes →
  one read-modify-write).

Semantics match ``optax.sgd(server_lr, momentum)`` exactly in exact
arithmetic: ``m ← β·m − Δ̄;  p ← p − lr·m`` (β = 0 collapses to
``p ← p + lr·Δ̄``). The kernel computes in f32 like the reference; the
only divergence is floating-point reassociation (the fused FMA orders
differ from optax's separate passes), so the engines pin the fused path
against the unfused reference at a documented tolerance
(tests/test_fused_apply.py) rather than bitwise.

Like ``ops/pallas_attention.py``, the kernel runs in pallas INTERPRET
mode on non-TPU backends — exact, slow, and jax-traceable (so GSPMD and
the CPU CI cover the real kernel code path). Only ``mean`` / ``fedavgm``
server optimizers are supported (config.validate enforces it): fedadam/
fedyogi carry second-moment state the one-pass kernel does not model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one kernel tile of the flat param vector: [_SUB, _LANE] f32 = 32 KiB
# VMEM per operand (the [K, _SUB, _LANE] stack block stays ≤ 2 MiB at
# cohort 64) — the (8, 128)-aligned shape the TPU vector unit wants
_SUB = 64
_LANE = 128
_TILE = _SUB * _LANE


def _flatten_tree(tree):
    """Ravel a pytree into one flat f32 vector. Returns
    ``(flat [N], unflatten)`` where ``unflatten(vec)`` splits a flat
    vector back into the tree's leaf shapes, cast per leaf to the
    ORIGINAL leaf dtypes (handles mixed-dtype trees)."""
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [int(l.size) for l in leaves]
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])

    def unflatten(vec):
        out, off = [], 0
        for sz, shp, dt in zip(sizes, shapes, dtypes):
            out.append(vec[off:off + sz].reshape(shp).astype(dt))
            off += sz
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten


def _flatten_stack(tree, k: int):
    """Ravel a ``[K, ...]`` stacked pytree into one ``[K, N]`` f32
    matrix (row c = client c's flat upload)."""
    return jnp.concatenate(
        [l.reshape(k, -1).astype(jnp.float32) for l in jax.tree.leaves(tree)],
        axis=1,
    )


def _pad_tiles(flat):
    """Pad a flat (or [K, N]) array to a tile multiple on its last dim
    and reshape it to the kernel's ``[..., G·_SUB, _LANE]`` layout —
    every kernel block is then a natively (8, 128)-tileable
    ``[_SUB, _LANE]`` (or ``[K, _SUB, _LANE]``) slab, the shape the TPU
    vector unit wants. Returns (tiled, n, grid)."""
    n = flat.shape[-1]
    g = max(1, -(-n // _TILE))
    pad = g * _TILE - n
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    return flat.reshape(flat.shape[:-1] + (g * _SUB, _LANE)), n, g


def _interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def out_struct(shape, dtype, inputs):
    """``ShapeDtypeStruct`` for a kernel output, carrying the union of
    the inputs' vma sets: inside a shard_map the output varies over the
    mesh axes its inputs vary over (none, for the replicated server
    update; ``clients`` for attention in the client lanes), and
    shard_map's vma checker requires that stated explicitly."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _tile_struct(g, inputs):
    return out_struct((g * _SUB, _LANE), jnp.float32, inputs)


_TILE_SPEC = pl.BlockSpec((_SUB, _LANE), lambda i: (i, 0))


def _delta_apply_kernel(d_ref, p_ref, m_ref, po_ref, mo_ref, *,
                        lr: float, beta: float):
    delta = d_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    if mo_ref is not None:
        # optax.sgd: trace m′ = β·m + grad with grad = −Δ̄; then −lr·m′
        m_new = beta * m_ref[...].astype(jnp.float32) - delta
        po_ref[...] = p - lr * m_new
        mo_ref[...] = m_new
    else:
        po_ref[...] = p + lr * delta


def _reduce_apply_kernel(w_ref, s_ref, p_ref, m_ref, po_ref, mo_ref, do_ref,
                         *, lr: float, beta: float):
    # [K] ∙ [K, _SUB, _LANE] → [_SUB, _LANE]: the trust/weight-scaled
    # reduction; the weights already carry the 1/denominator, so the
    # contraction IS the finished weighted mean. The [K] weights live
    # in SMEM and are read as SCALARS: one scalar × tile FMA per client
    # (K is a cohort — tiny — and the pass is bandwidth-bound). A [K]
    # lane vector broadcast against the tile stack needs a
    # vector<1xK> → vector<Kx1x1> shape cast, which Mosaic refuses
    # ("infer-vector-layout: unsupported shape cast", libtpu 0.0.34).
    delta = w_ref[0] * s_ref[0].astype(jnp.float32)
    for c in range(1, s_ref.shape[0]):
        delta = delta + w_ref[c] * s_ref[c].astype(jnp.float32)
    do_ref[...] = delta
    p = p_ref[...].astype(jnp.float32)
    if mo_ref is not None:
        m_new = beta * m_ref[...].astype(jnp.float32) - delta
        po_ref[...] = p - lr * m_new
        mo_ref[...] = m_new
    else:
        po_ref[...] = p + lr * delta


def fused_delta_apply(params, momentum, mean_delta, server_lr: float,
                      server_momentum: float = 0.0, interpret=None):
    """Apply the already-reduced mean delta to the params in one fused
    pass: ``(params, momentum, Δ̄) → (params′, momentum′)``.

    ``momentum`` is the optax trace tree (None when the server optimizer
    is plain ``mean``); ``momentum′`` is None in the same case. Trees
    come back in the input leaves' dtypes; kernel math is f32.
    """
    has_mom = momentum is not None
    flat_d, _ = _flatten_tree(mean_delta)
    flat_p, unflat_p = _flatten_tree(params)
    d_t, n, g = _pad_tiles(flat_d)
    p_t = _pad_tiles(flat_p)[0]
    if has_mom:
        flat_m, unflat_m = _flatten_tree(momentum)
        m_t = _pad_tiles(flat_m)[0]
        kernel = functools.partial(
            _delta_apply_kernel, lr=float(server_lr),
            beta=float(server_momentum),
        )
        p_out, m_out = pl.pallas_call(
            kernel, grid=(g,),
            in_specs=[_TILE_SPEC, _TILE_SPEC, _TILE_SPEC],
            out_specs=[_TILE_SPEC, _TILE_SPEC],
            out_shape=[_tile_struct(g, (d_t, p_t, m_t))] * 2,
            interpret=_interpret(interpret),
        )(d_t, p_t, m_t)
        return unflat_p(p_out.reshape(-1)[:n]), unflat_m(m_out.reshape(-1)[:n])

    def kernel(d_ref, p_ref, po_ref):
        _delta_apply_kernel(d_ref, p_ref, None, po_ref, None,
                            lr=float(server_lr), beta=0.0)

    p_out = pl.pallas_call(
        kernel, grid=(g,),
        in_specs=[_TILE_SPEC, _TILE_SPEC],
        out_specs=_TILE_SPEC,
        out_shape=_tile_struct(g, (d_t, p_t)),
        interpret=_interpret(interpret),
    )(d_t, p_t)
    return unflat_p(p_out.reshape(-1)[:n]), None


def fused_reduce_apply(wire_stack, weights, params, momentum,
                       server_lr: float, server_momentum: float = 0.0,
                       interpret=None):
    """The full stacked-path chain in one pass: ``[K, ...]`` wire
    uploads × combined ``[K]`` weights → Δ̄ → server optimizer → params.

    ``weights`` must already fold in EVERYTHING multiplicative — FedAvg
    example/participation weight, reputation trust, and the reciprocal
    of the weight sum (or krum's one-hot winner row) — so the kernel's
    contraction is the finished aggregate. Returns
    ``(params′, momentum′, mean_delta)``; the delta is emitted as a
    kernel output (one extra tile write) because the client-ledger
    cosine statistic reads the aggregated delta.
    """
    has_mom = momentum is not None
    k = jax.tree.leaves(wire_stack)[0].shape[0]
    flat_s = _flatten_stack(wire_stack, k)  # [K, N]
    flat_p, unflat_p = _flatten_tree(params)
    s_t, n, g = _pad_tiles(flat_s)  # [K, G*_SUB, _LANE]
    p_t = _pad_tiles(flat_p)[0]
    w = weights.astype(jnp.float32).reshape(k)
    stack_spec = pl.BlockSpec((k, _SUB, _LANE), lambda i: (0, i, 0))
    w_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    if has_mom:
        flat_m, unflat_m = _flatten_tree(momentum)
        m_t = _pad_tiles(flat_m)[0]
        kernel = functools.partial(
            _reduce_apply_kernel, lr=float(server_lr),
            beta=float(server_momentum),
        )
        p_out, m_out, d_out = pl.pallas_call(
            kernel, grid=(g,),
            in_specs=[w_spec, stack_spec, _TILE_SPEC, _TILE_SPEC],
            out_specs=[_TILE_SPEC, _TILE_SPEC, _TILE_SPEC],
            out_shape=[_tile_struct(g, (w, s_t, p_t, m_t))] * 3,
            interpret=_interpret(interpret),
        )(w, s_t, p_t, m_t)
        new_mom = unflat_m(m_out.reshape(-1)[:n])
    else:
        def kernel(w_ref, s_ref, p_ref, po_ref, do_ref):
            _reduce_apply_kernel(w_ref, s_ref, p_ref, None, po_ref, None,
                                 do_ref, lr=float(server_lr), beta=0.0)

        p_out, d_out = pl.pallas_call(
            kernel, grid=(g,),
            in_specs=[w_spec, stack_spec, _TILE_SPEC],
            out_specs=[_TILE_SPEC, _TILE_SPEC],
            out_shape=[_tile_struct(g, (w, s_t, p_t))] * 2,
            interpret=_interpret(interpret),
        )(w, s_t, p_t)
        new_mom = None
    new_params = unflat_p(p_out.reshape(-1)[:n])
    # unflat_p casts per leaf to the params dtypes — exactly the dtype
    # the unfused paths' mean_delta carries (the psum accumulator's)
    mean_delta = unflat_p(d_out.reshape(-1)[:n])
    return new_params, new_mom, mean_delta
