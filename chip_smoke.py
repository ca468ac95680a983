"""On-chip smoke: the headline round program, start to finish, on the TPU.

    python chip_smoke.py        # no arguments, one process, no children

The quickest proof that the system still starts on the chip. It drives
the main path once through the entry points a user calls —
``resolve_config`` → ``Experiment`` → ``fit`` → ``evaluate_checkpoint``,
the calls ``colearn fit`` / ``colearn evaluate`` make — on the shipped
``cifar10_fedavg_100`` config (ResNet-18 at full width, cohort 16,
batch 64, bf16, megabatch layout) in the driver's bench shape
(``run.fuse_rounds=4`` + ``server.fused_apply``) over synthetic data at
CIFAR's cardinality, then runs every Pallas kernel the repo ships
natively (``interpret=False``) against its plain-jnp reference at the
shapes the zoo gives it. Weights and data are random from a seed; only
the round count is cut.

It never continues on CPU: without a TPU it exits non-zero before
compiling anything and prints no result. No step is wrapped in a
``try`` that lets the script reach exit 0 — any failed check raises.
On success the LAST line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One process per chip: a chip belongs to one process at a time, so
never run this next to another chip process (benchmark/run.py,
colearn fit).
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# the repo's one table of device peaks, keyed by ``device_kind``
PEAKS_FILE = os.path.join(ROOT, "benchmark", "harness", "peaks.json")

SMOKE_CONFIG = "cifar10_fedavg_100"
SMOKE_ROUNDS = 8
# parity tolerances: the apply kernels' is tests/test_fused_apply.py's
# (one f32 reassociation of O(1) values — the kernels are pure f32 VPU
# work, so the chip owes the same bound the CPU interpreter meets)
APPLY_TOL = 1e-5
# flash attention vs blockwise_attention. Both accumulate in f32, and on
# this chip both run their f32 matmuls at the MXU's default precision
# (bf16 products): measured in PR 21, the kernel and the XLA reference
# sit at the SAME distance (4e-3..1.6e-2) from a precision="highest"
# reference, and up to 1.0e-3 (f32 in) / 3.9e-3 (bf16 in) from each
# other — the online softmax rescales by block, so the two round
# different p values to bf16. The kernel is held to the
# default-precision reference at one bf16 ulp of an O(1) value for f32
# inputs and at tests/test_pallas_attention.py's bound for bf16 inputs
# (a wrong mask, block or scale is O(0.1)), and to the "highest"
# reference at the bf16-product bound.
ATTN_TOL = {"float32": 4e-3, "bfloat16": 3e-2}
ATTN_TOL_VS_HIGHEST = 5e-2
# (name, batch, T, heads, model dim): bert_tiny (T=80 is one unpadded
# 80-row block) and ViT-B/16 (T=197 pads to two 128-row blocks)
ATTN_SHAPES = (("bert_tiny", 4, 80, 2, 128), ("vit_b16", 2, 197, 12, 768))


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def smoke_overrides(out_dir: str) -> dict:
    """The headline cell's shape of the smoke config (``r18_c16_k8``:
    four fused rounds, the fused apply, a CIFAR-cardinality synthetic
    corpus), cut to ``SMOKE_ROUNDS`` rounds with one eval and one
    checkpoint at the end."""
    return {
        "run.fuse_rounds": 4,
        "server.fused_apply": True,
        "data.synthetic_train_size": 50_000,
        "data.synthetic_test_size": 1_000,
        "server.num_rounds": SMOKE_ROUNDS,
        "server.eval_every": SMOKE_ROUNDS,
        "server.checkpoint_every": SMOKE_ROUNDS,
        # one flush per fused chunk: the first round record's timestamp
        # then marks the end of the first dispatch
        "run.metrics_flush_every": 4,
        "run.out_dir": out_dir,
    }


def require_known_device_kind(kind: str) -> None:
    """A device the benchmark has no peaks for is refused here too."""
    with open(PEAKS_FILE) as f:
        known = [k for k in json.load(f) if not k.startswith("_")]
    if kind not in known:
        raise RuntimeError(
            f"device_kind {kind!r} is not a key of {PEAKS_FILE} "
            f"(it has {known}); add its published peaks there first"
        )


def require_live_buffers(devices) -> None:
    """Every device of the mesh must hold buffers once rounds have run
    — a mesh whose work all landed on device 0 fails here."""
    for d in devices:
        in_use = (d.memory_stats() or {}).get("bytes_in_use", 0)
        say(f"device {d.id}: bytes_in_use={in_use}")
        if not in_use > 0:
            raise RuntimeError(f"device {d} holds no live buffers after fit")


def run_fit(config: str, overrides: dict, t_start: float) -> dict:
    """fit + evaluate-from-checkpoint through the CLI's own calls;
    raises on any broken expectation, returns the facts worth printing."""
    import jax
    import numpy as np

    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.parallel import mesh as mesh_lib
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cfg = resolve_config(config, overrides)
    exp = Experiment(cfg, echo=False)
    lanes = int(exp.mesh.shape[mesh_lib.CLIENT_AXIS])
    want_lanes = mesh_lib.largest_lane_count(
        cfg.server.cohort_size, len(jax.devices())
    )
    say(f"mesh shape={dict(exp.mesh.shape)} n_chips={exp.n_chips} "
        f"lanes={lanes} clients_per_lane={cfg.server.cohort_size // lanes}")
    if lanes != want_lanes or exp.n_chips != want_lanes:
        raise RuntimeError(
            f"mesh uses {lanes} lanes / {exp.n_chips} chips; "
            f"{len(jax.devices())} devices allow {want_lanes}"
        )

    state = exp.fit()
    records = exp.logger.history
    require_live_buffers(exp.mesh.devices.flat)

    rounds = sorted((r for r in records if "event" not in r),
                    key=lambda r: r["round"])
    losses = [float(r["train_loss"]) for r in rounds]
    say("train_loss by round: " + " ".join(f"{x:.4f}" for x in losses))
    if len(rounds) != cfg.server.num_rounds:
        raise RuntimeError(f"{len(rounds)} round records, expected "
                           f"{cfg.server.num_rounds}")
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite train_loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(
            f"train_loss did not fall: round 1 {losses[0]} -> "
            f"round {len(losses)} {losses[-1]}"
        )

    compiled = [r for r in records if r.get("event") == "executable_compiled"]
    if not compiled:
        raise RuntimeError("no executable_compiled records (registry off?)")
    for r in compiled:
        say(f"compiled {r['name']}: {r['compile_ms'] / 1e3:.1f}s "
            f"backend={r['backend']} peak_bytes={r['peak_bytes']}")
    off_chip = [r["name"] for r in compiled if r["backend"] != "tpu"]
    if off_chip:
        raise RuntimeError(f"programs not compiled for tpu: {off_chip}")
    warnings = [r for r in records if r.get("event") == "warning"]
    for r in warnings:
        say(f"warning record: {r.get('warning')}: {r.get('detail')}")
    bad = [r for r in warnings
           if str(r.get("warning", "")).startswith("executable_")]
    if bad:
        raise RuntimeError(f"executable registry warnings: {bad}")

    final_eval = [r for r in rounds if "eval_loss" in r][-1]
    # `colearn evaluate` is a fresh Experiment reading the checkpoint
    restored = Experiment(cfg, echo=False).evaluate_checkpoint()
    say(f"fit eval_loss={final_eval['eval_loss']!r} "
        f"checkpoint eval_loss={restored['eval_loss']!r} "
        f"(round {restored['round']})")
    if restored["round"] != cfg.server.num_rounds:
        raise RuntimeError(f"checkpoint is of round {restored['round']}")
    if restored["eval_loss"] != final_eval["eval_loss"]:
        raise RuntimeError(
            "evaluate from checkpoint does not reproduce the final "
            f"eval_loss bit for bit: {restored['eval_loss']!r} != "
            f"{final_eval['eval_loss']!r}"
        )

    summary = next(r for r in records if r.get("event") == "run_summary")
    return {
        "first_round_sec": round(rounds[0]["time"] - t_start, 1),
        "fit_wall_sec": summary["wall_time_sec"],
        "compile_sec": round(summary["compile_ms"] / 1e3, 1),
        "compiles": summary["compiles"],
        "host_pipeline": "native" if exp._native else "numpy",
        "n_chips": exp.n_chips,
        "final_train_loss": losses[-1],
        "final_eval_loss": final_eval["eval_loss"],
        "rounds": int(state["round"]),
    }


def _require_close(label: str, got, want, tol: float) -> None:
    import jax
    import numpy as np

    pairs = [
        (np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True)
    ]
    err = max(float(np.max(np.abs(a - b))) for a, b in pairs)
    say(f"{label}: max abs err {err:.3e} (tol {tol:g})")
    for a, b in pairs:
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol, err_msg=label)


def check_pallas_apply(params, k: int = 16) -> None:
    """Both server-apply kernels, natively, on ``params``-shaped trees
    and a ``k``-client stack, with and without momentum, against the
    optax / jnp chain."""
    import jax
    import jax.numpy as jnp
    import optax

    from colearn_federated_learning_tpu.ops.pallas_apply import (
        fused_delta_apply,
        fused_reduce_apply,
    )

    @functools.partial(jax.jit, static_argnums=1)
    def rand_like(key, lead=()):
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            jax.random.normal(kk, lead + p.shape, jnp.float32)
            for kk, p in zip(keys, leaves)
        ])

    key = jax.random.PRNGKey(0)
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    delta = rand_like(jax.random.fold_in(key, 1))
    stack = rand_like(jax.random.fold_in(key, 2), (k,))
    w = jax.random.uniform(jax.random.fold_in(key, 3), (k,), jnp.float32)
    w = w / w.sum()
    n = sum(int(p.size) for p in jax.tree.leaves(params))
    say(f"pallas apply: {n} coordinates, K={k}")

    @jax.jit
    def ref_mean(stack, w):
        # the jnp chain, elementwise in f32 (a dot would run its
        # products in bf16 on the MXU and blur the comparison)
        return jax.tree.map(
            lambda s: jnp.sum(
                w.reshape((k,) + (1,) * (s.ndim - 1)) * s, axis=0
            ), stack,
        )

    for lr, mom in ((1.0, 0.0), (0.7, 0.9)):
        opt = optax.sgd(lr, momentum=mom if mom else None)
        st = opt.init(params)
        trace = st[0].trace if mom else None
        if mom:
            # a non-zero momentum so the beta*m term is exercised
            trace = rand_like(jax.random.fold_in(key, 4))
            st = (st[0]._replace(trace=trace),) + tuple(st[1:])

        @jax.jit
        def ref_apply(params, st, delta):
            upd, st2 = opt.update(jax.tree.map(jnp.negative, delta), st,
                                  params)
            return optax.apply_updates(params, upd), st2

        ref_p, ref_st = ref_apply(params, st, delta)
        got_p, got_m = jax.jit(
            lambda p, m, d: fused_delta_apply(p, m, d, lr, mom,
                                              interpret=False)
        )(params, trace, delta)
        _require_close(f"fused_delta_apply mom={mom} params", got_p, ref_p,
                       APPLY_TOL)
        if mom:
            _require_close(f"fused_delta_apply mom={mom} momentum", got_m,
                           ref_st[0].trace, APPLY_TOL)
        elif got_m is not None:
            raise RuntimeError("momentum returned without server momentum")

        mean = ref_mean(stack, w)
        ref_p, ref_st = ref_apply(params, st, mean)
        got_p, got_m, got_d = jax.jit(
            lambda s, ww, p, m: fused_reduce_apply(s, ww, p, m, lr, mom,
                                                   interpret=False)
        )(stack, w, params, trace)
        _require_close(f"fused_reduce_apply mom={mom} delta", got_d, mean,
                       APPLY_TOL)
        _require_close(f"fused_reduce_apply mom={mom} params", got_p, ref_p,
                       APPLY_TOL)
        if mom:
            _require_close(f"fused_reduce_apply mom={mom} momentum", got_m,
                           ref_st[0].trace, APPLY_TOL)


def check_flash_attention() -> None:
    """``flash_attention``, natively, at the zoo's shapes, causal and
    not, f32 and bf16 inputs, against ``blockwise_attention``."""
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from colearn_federated_learning_tpu.ops.ring_attention import (
        blockwise_attention,
    )

    for name, b, t, heads, d in ATTN_SHAPES:
        for dtype in (jnp.float32, jnp.bfloat16):
            ks = jax.random.split(jax.random.PRNGKey(t), 3)
            q, k, v = (jax.random.normal(kk, (b, t, d), dtype) for kk in ks)
            for causal in (True, False):
                ref = jax.jit(lambda q, k, v: blockwise_attention(
                    q, k, v, heads, block_size=t, causal=causal))
                want = ref(q, k, v)
                with jax.default_matmul_precision("highest"):
                    want_hi = ref(q, k, v)  # retraced: precision is keyed
                got = jax.jit(
                    lambda q, k, v: flash_attention(
                        q, k, v, heads, causal, 128, 128, False
                    )
                )(q, k, v)
                if got.dtype != dtype or got.shape != (b, t, d):
                    raise RuntimeError(
                        f"flash_attention {name}: got {got.dtype}{got.shape}"
                    )
                label = (f"flash_attention {name} T={t} {heads}x{d // heads} "
                         f"{jnp.dtype(dtype).name} causal={causal}")
                _require_close(label, got, want,
                               ATTN_TOL[jnp.dtype(dtype).name])
                _require_close(label + " vs highest", got, want_hi,
                               ATTN_TOL_VS_HIGHEST)


def check_selected_attention() -> None:
    """``selected_attention``'s three kernels, natively, at the Keye
    decoder's widths (a chunk of 512 queries over 2,048 keys, 32 / 4
    heads of 128, bf16): output, the heads' mean and the three gradients
    against a dense masked softmax in XLA on the same bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from colearn_federated_learning_tpu.ops.sparse_attention import (
        selected_attention,
    )

    tq, tk, heads, kv, hd = 512, 2048, 32, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(26), 5)
    q = jax.random.normal(ks[0], (tq, heads, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (tk, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (tk, kv, hd), jnp.bfloat16)
    keep = (jax.random.uniform(ks[3], (tq, tk)) < 0.25).at[:, -1].set(True)
    ct = jax.random.normal(ks[4], (tq, heads * hd), jnp.float32)

    def dense(q, k, v, keep):
        kr, vr = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, kr,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        out = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), vr,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype).reshape(tq, heads * hd), p.mean(0)

    def both(fn):
        def loss(q, k, v):
            out, weights = fn(q, k, v, keep)
            return (out.astype(jnp.float32) * ct).sum(), (out, weights)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    (_, (out, weights)), grads = both(selected_attention)(q, k, v)
    (_, (want, want_w)), want_g = both(dense)(q, k, v)
    if np.any(np.asarray(weights)[~np.asarray(keep)]):
        raise RuntimeError("selected_attention: weight off the kept set")
    _require_close("selected_attention out", out, want, ATTN_TOL["bfloat16"])
    _require_close("selected_attention heads' mean", weights, want_w, 1e-4)
    for name, g, w in zip(("dq", "dk", "dv"), grads, want_g):
        scale = float(jnp.abs(w.astype(jnp.float32)).max())
        _require_close(f"selected_attention {name} / {scale:.3g}",
                       g.astype(jnp.float32) / scale,
                       w.astype(jnp.float32) / scale,
                       ATTN_TOL["bfloat16"])


def check_index_scores() -> None:
    """``index_scores``' two kernels, natively, at the Keye decoder's
    widths (a chunk of 512 queries over 8,192 keys, 16 heads of 64, one
    key head, bf16): the scores and the three gradients against the
    dense ``jnp`` form, whose ``[16, 512, 8192]`` float32 per-head
    scores are an array, on the same bf16 inputs; and the sign of a
    score whose terms are all ``-0.0``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from colearn_federated_learning_tpu.ops.sparse_attention import (
        index_scores,
    )

    tq, tk, heads, hd = 512, 8192, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(28), 4)
    q_idx = jax.random.normal(ks[0], (tq, heads, hd), jnp.bfloat16)
    k_idx = jax.random.normal(ks[1], (tk, hd), jnp.bfloat16)
    w_idx = jax.random.normal(ks[2], (tq, heads), jnp.float32)
    ct = jax.random.normal(ks[3], (tq, tk), jnp.float32)

    def dense(q_idx, k_idx, w_idx):
        dots = jnp.einsum("qjd,kd->jqk", q_idx, k_idx,
                          preferred_element_type=jnp.float32)
        w = w_idx.T[:, :, None] * jnp.float32(hd ** -0.5 * heads ** -0.5)
        return (jax.nn.relu(dots) * w).sum(0) + 0.0

    def both(fn):
        def loss(q_idx, k_idx, w_idx):
            scores = fn(q_idx, k_idx, w_idx)
            return (scores * ct).sum(), scores
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    (_, scores), grads = both(index_scores)(q_idx, k_idx, w_idx)
    (_, want), want_g = both(dense)(q_idx, k_idx, w_idx)
    # every dot negative, every weight negative: each term is -0.0, and
    # the selection's bit patterns need the sum to be +0.0
    zeros = np.asarray(jax.jit(index_scores)(
        -jnp.abs(q_idx), jnp.abs(k_idx), -jnp.abs(w_idx)))
    if zeros.any() or np.signbit(zeros).any():
        raise RuntimeError("index_scores: a score of -0.0, or not 0 at all")
    _require_close("index_scores", scores, want, 1e-5)
    for name, g, w in zip(("dq_idx", "dk_idx", "dw_idx"), grads, want_g):
        scale = float(jnp.abs(w.astype(jnp.float32)).max())
        _require_close(f"index_scores {name} / {scale:.3g}",
                       g.astype(jnp.float32) / scale,
                       w.astype(jnp.float32) / scale,
                       ATTN_TOL["bfloat16"])


def check_expert_ffn() -> None:
    """``ops/moe.expert_ffn``'s kernels, natively, bf16, tiles of 256
    rows: the result and the four gradients, and the frozen form's rows'
    gradient, against every held expert computed densely over every
    token and weighted by a ``[tokens, held]`` matrix of gates, in XLA on
    the same bf16 inputs. At the Keye decoder's widths (2,048 tokens, 16
    held of 64 experts of ``[2048, 768]``, 8 a token) once as the
    decoders run it and once with the tiles in use split over several
    kernel calls, as they are when the held experts draw many times
    their share; and at Mellum2's (16,384 tokens, 8 held of 64 experts
    of ``[2304, 896]``: widths of 18 and 7 lanes, the float32 result in
    two blocks of 1,152 columns, and 64 to 72 tiles in use where a call
    has 56 slots, so every pass takes a second call as it is)."""
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.ops import moe

    tile = 256
    for t, d, f, held, offset, split in ((2048, 2048, 768, 16, 8, True),
                                         (16384, 2304, 896, 8, 0, False)):
        ks = jax.random.split(jax.random.PRNGKey(30), 6)
        h = jax.random.normal(ks[0], (t, d), jnp.bfloat16)
        router = jax.random.normal(ks[1], (d, 64), jnp.bfloat16) * 0.02
        w = [jax.random.normal(k, s, jnp.bfloat16) * 0.02 for k, s in
             zip(ks[2:5], ((held, d, f), (held, d, f), (held, f, d)))]
        ct = jax.random.normal(ks[5], (t, d), jnp.float32)
        disp = moe.route(h, router, top_k=8, experts_held=held,
                         expert_offset=offset, tile=tile)
        tables = (disp.row_token, disp.row_gate, disp.tile_expert,
                  disp.n_tiles)
        gates = jnp.zeros((t, held), jnp.float32).at[
            disp.row_token, jnp.repeat(disp.tile_expert, tile)].add(
                disp.row_gate)
        slots = moe._calls(h, *tables[:2], disp.tile_expert,
                           disp.tile_expert)[1]
        say(f"expert_ffn [{d}, {f}] x {held}, {t} tokens: "
            f"{int(disp.n_tiles)} tiles in use, {slots} slots a call")
        if not split and int(disp.n_tiles) <= slots:
            raise RuntimeError("the tiles in use fit one call: this case "
                               "is there for the second call")

        def dense(h, w1, w3, w2):
            def one(y, e):
                mid = (jax.nn.silu(jnp.dot(
                    h, w1[e], preferred_element_type=jnp.float32))
                    * jnp.dot(h, w3[e], preferred_element_type=jnp.float32))
                out = jnp.dot(mid.astype(h.dtype), w2[e],
                              preferred_element_type=jnp.float32)
                return y + out * gates[:, e, None], None
            y, _ = jax.lax.scan(one, jnp.zeros((t, d), jnp.float32),
                                jnp.arange(held))
            return y.astype(h.dtype)

        def both(fn):
            def loss(*a):
                out = fn(*a)
                return (out.astype(jnp.float32) * ct).sum(), out
            return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3),
                                              has_aux=True))

        (_, want), want_g = both(dense)(h, *w)
        # in one kernel call a pass (the tiles in use fit it), and in
        # calls of four tiles, where an expert's sums pass from call to
        # call; at Mellum2's sizes the calls as they come
        cases = [(moe._ROWS_BYTES, "as it is")]
        if split:
            cases = [(moe._ROWS_BYTES, "one call"),
                     (4 * tile * d * 2, "calls of 4 tiles")]
        for rows_bytes, how in cases:
            moe._ROWS_BYTES, kept = rows_bytes, moe._ROWS_BYTES
            try:
                (_, out), grads = both(
                    lambda *a: moe.expert_ffn(*a, *tables))(h, *w)
                (_, _), frozen = both(
                    lambda *a: moe.expert_ffn_frozen(*a, *tables))(h, *w)
            finally:
                moe._ROWS_BYTES = kept
            for name, g, wg in zip(
                    ("out", "dh", "dw1", "dw3", "dw2", "frozen dh"),
                    (out, *grads, frozen[0]), (want, *want_g, want_g[0])):
                size = float(jnp.abs(wg.astype(jnp.float32)).max())
                _require_close(
                    f"expert_ffn [{d}, {f}], {how}: {name} / {size:.3g}",
                    g.astype(jnp.float32) / size,
                    wg.astype(jnp.float32) / size, ATTN_TOL["bfloat16"])


def check_band_attention() -> None:
    """``ops/band_attention.band_attention``'s three kernels, natively,
    at Mellum2's widths (16,384 positions in tiles of 512, 32 query heads
    over 4 key-value heads of 128, bf16), under the sliding layers' band
    of 1,024 and over the whole triangle: the output and the three
    gradients against a softmax over float32 scores under a mask that is
    an array, a block of 1,024 queries at a time, in XLA on the same
    bf16 inputs."""
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.ops.band_attention import (
        band_attention,
    )

    t, heads, kv, hd, rows = 16384, 32, 4, 128, 1024
    ks = jax.random.split(jax.random.PRNGKey(31), 4)
    q = jax.random.normal(ks[0], (t, heads, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (t, kv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (t, kv, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (t, heads, hd), jnp.float32)

    def blockwise(window):
        def dense(q, k, v):
            kr, vr = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))

            @jax.checkpoint
            def block(q_b, lo):
                s = jnp.einsum("qhd,khd->hqk", q_b, kr,
                               preferred_element_type=jnp.float32) * hd ** -0.5
                ahead = (lo + jnp.arange(rows))[:, None] - jnp.arange(t)
                keep = ahead >= 0
                if window is not None:
                    keep &= ahead < window
                p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
                return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), vr,
                                  preferred_element_type=jnp.float32
                                  ).astype(v.dtype)

            out = jax.lax.map(lambda a: block(*a), (
                q.reshape(t // rows, rows, heads, hd),
                jnp.arange(0, t, rows)))
            return out.reshape(t, heads, hd)
        return dense

    def both(fn):
        def loss(*a):
            out = fn(*a)
            return (out.astype(jnp.float32) * ct).sum(), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    for window in (1024, None):
        (_, out), grads = both(
            lambda *a: band_attention(*a, window, hd ** -0.5, 512))(q, k, v)
        (_, want), want_g = both(blockwise(window))(q, k, v)
        label = f"band attention, window {window}"
        _require_close(f"{label}: out", out, want, ATTN_TOL["bfloat16"])
        for name, g, w in zip(("dq", "dk", "dv"), grads, want_g):
            size = float(jnp.abs(w.astype(jnp.float32)).max())
            _require_close(f"{label}: {name} / {size:.3g}",
                           g.astype(jnp.float32) / size,
                           w.astype(jnp.float32) / size, ATTN_TOL["bfloat16"])


def check_latent_attention() -> None:
    """``ops/latent_attention.causal_attention``'s three kernels,
    natively, at A.X-K1's widths (2,048 positions in tiles of 512, 8 of
    the 64 heads of 128 + 64 / 128, one rope key for all heads, bf16):
    the output and the five gradients against dense causal scores in XLA
    on the same bf16 inputs."""
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.ops.latent_attention import (
        causal_attention,
    )

    t, heads, scale = 2048, 8, 192 ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(29), 6)
    shapes = ((t, heads, 128), (t, heads, 64), (t, heads, 128), (t, 64),
              (t, heads, 128))
    ops = [jax.random.normal(k, s, jnp.bfloat16) for k, s in zip(ks, shapes)]
    ct = jax.random.normal(ks[5], shapes[4], jnp.float32)

    def dense(q_n, q_r, k_n, k_r, v):
        s = (jnp.einsum("qhd,khd->hqk", q_n, k_n,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhd,kd->hqk", q_r, k_r,
                          preferred_element_type=jnp.float32)) * scale
        p = jax.nn.softmax(
            jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(v.dtype)

    def both(fn):
        def loss(*a):
            out = fn(*a)
            return (out.astype(jnp.float32) * ct).sum(), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4),
                                          has_aux=True))

    (_, out), grads = both(lambda *a: causal_attention(*a, scale, 512))(*ops)
    (_, want), want_g = both(dense)(*ops)
    _require_close("latent attention out", out, want, ATTN_TOL["bfloat16"])
    for name, g, w in zip(("dq_n", "dq_r", "dk_n", "dk_r", "dv"), grads,
                          want_g):
        size = float(jnp.abs(w.astype(jnp.float32)).max())
        _require_close(f"latent attention {name} / {size:.3g}",
                       g.astype(jnp.float32) / size,
                       w.astype(jnp.float32) / size, ATTN_TOL["bfloat16"])


def main() -> int:
    t_start = time.time()
    # (a) the compile cache, before the first compile
    from colearn_federated_learning_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()

    import jax
    import jaxlib

    # (b) a TPU or nothing
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: jax.default_backend() is {backend!r}, not 'tpu' "
              f"— this script only runs on the chip", file=sys.stderr)
        return 1
    from importlib import metadata

    dev = jax.devices()[0]
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {metadata.version('libtpu')}")
    say(f"device_kind={dev.device_kind!r} count={len(jax.devices())} "
        f"compile_cache={cache_dir} "
        f"(entries at start: "
        f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0})")
    require_known_device_kind(dev.device_kind)

    # (c) the main path
    out_dir = os.path.join(ROOT, "runs", "chip_smoke")
    shutil.rmtree(out_dir, ignore_errors=True)
    facts = run_fit(SMOKE_CONFIG, smoke_overrides(out_dir), t_start)
    # (f) the start-up facts
    say("fit: " + json.dumps(facts))

    # (d) + (e) every Pallas kernel, natively
    from colearn_federated_learning_tpu.models import build_model, init_params

    params = init_params(build_model("resnet18", num_classes=10),
                         (32, 32, 3), seed=0)
    check_pallas_apply(params, k=16)
    check_flash_attention()
    check_selected_attention()
    check_index_scores()
    check_latent_attention()
    check_band_attention()
    check_expert_ffn()

    say(f"total wall {time.time() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
