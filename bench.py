"""Benchmark harness (BASELINE.json:2): FL rounds/sec and
client-updates/sec/chip, plus MFU accounting (XLA-counted FLOPs vs the
chip's bf16 peak).

Default (what the driver runs): the headline config
``cifar10_fedavg_100`` — prints ONE JSON line::

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Matrix mode (VERDICT r2 missing-#4 — a perf record for every TPU
config, so regressions in those paths are measurable)::

    python bench.py --config femnist_fedprox_500   # one line, that config
    python bench.py --matrix                        # one line per config

``vs_baseline`` is relative to the first recorded TPU measurement of the
same config (the reference publishes no numbers — BASELINE.json:13
``"published": {}``); a config measured for the first time reports
vs_baseline=1.0 and its number becomes the baseline. The pinned
baselines below were taken on an earlier installation (``git show
6c506b7:BASELINE.md``); ROADMAP S1 re-measures them on the local chip
and moves them into the ledger.

One process per chip: importing this module initializes no jax backend,
so the ``--matrix`` parent never holds the chip its per-config children
need (pinned in tests/test_bring_up.py).
"""

from __future__ import annotations

import argparse
import json
import os
import time

# First recorded rounds/sec per config on 1× TPU v5 lite, taken on an
# earlier installation (git show 6c506b7:BASELINE.md, measurement
# tables); confirm on the local chip (ROADMAP S1). The headline
# baseline is the 2026-07-29 first light-up; the other configs'
# baselines are their round-3 first measurements.
BASELINES = {
    "cifar10_fedavg_100": 2.22,
    # round-3 first measurements through THIS bench path; the
    # dispatch-bound configs varied ~2× run to run on that installation
    "cifar10_fedavg_1000": 3.05,
    # femnist/shakespeare RE-PINNED at the r5-adopted shapes (cohort 32;
    # shakespeare also fuse_rounds=10) — BASELINE.md r5 sweep table. The
    # old-shape values (5.90 / 6.71 at cohorts 16 / 8) are kept there;
    # client-updates/sec/chip improved 337→405 and 381→801.
    "femnist_fedprox_500": 12.66,
    "shakespeare_fedavg": 13.42,
    "imagenet_silo_dp": 0.31,
}

# Device-side ms/round baselines (from the round-4 profiled measurement
# on an earlier installation; confirm on the local chip). Wall r/s of a
# dispatch-bound config (MFU < 5%) is mostly host time — a 2× real
# device regression could hide inside its run-to-run swing — so any
# config with a pinned device baseline gates vs_baseline on the round
# program's measured DEVICE time instead (VERDICT r3 weak-#5). Under
# run.fuse_rounds the fused chunk's device time is divided by fuse, so
# the per-round pin survives shape re-pins.
DEVICE_MS_BASELINES = {
    # RE-PINNED r6 at the fused shapes (fuse adopted for the
    # dispatch-sensitive bench shapes this round): femnist cohort 32
    # (per-round device time is fusion-invariant — the scan body IS the
    # round program; r5 pin kept), shakespeare cohort 32 + fuse 10.
    "femnist_fedprox_500": 64.6,
    "shakespeare_fedavg": 29.5,
    # north-star config, pinned from the r4 profiled measurement
    # (~310 ms device/round): its wall r/s swung run to run even at 37%
    # MFU on that installation, so it gates on device time too
    "cifar10_fedavg_1000": 310.0,
}

# MFU floor below which a config counts as dispatch-bound (reported in
# the JSON; the device-time pass runs for every pinned config)
DISPATCH_BOUND_MFU_PCT = 5.0

# Chip peaks + the MFU-basis rule live in obs/roofline.py now (r8): the
# bench, the driver's `phase_cost_model` records, and `colearn mfu`'s
# waterfall all divide by the SAME denominators — a drifted copy here
# would make the waterfall's components stop summing to this headline.
# Re-exported under the established names (tests pin them).
from colearn_federated_learning_tpu.obs.roofline import (  # noqa: E402
    PEAK_BF16_FLOPS,
    PEAK_F32_FLOPS,
    mfu_basis as _roofline_mfu_basis,
)


def _mfu_basis(cfg):
    """(basis name, peak FLOP/s) from the config's effective compute
    precision: the matmuls run bf16 when either the model compute dtype
    or the effective local-param dtype is bfloat16 (the shared
    obs/roofline.py rule — `mfu_basis` in every result's extra records
    which denominator produced the number)."""
    return _roofline_mfu_basis(
        cfg.run.compute_dtype, cfg.run.local_param_dtype,
        cfg.run.param_dtype,
    )

# Per-config bench shape: (warmup rounds, timed rounds, extra overrides).
# Overrides only bound BENCH COST (round count, per-client caps, eval
# off) — engine, algorithm, model family, partition kind, and DP are the
# config's own. The imagenet cap keeps a ViT-B/16 DP round at seconds,
# not minutes; recorded in the JSON so the number is honest.
_SHAPES = {
    # r7 (ROADMAP item 2 — the 41% MFU plateau): the headline config
    # adopts all three levers at once. fuse_rounds=4 amortizes the
    # ~13 ms host dispatch the r2 profile measured (the r2 R=8
    # fusion attempt predated the generalized fused engine; r6 proved
    # fuse=4 compiles fine for this exact model at cohort 64);
    # server.fused_apply collapses the round tail into one pallas
    # pass; run.double_buffer (default-on) hides host_inputs/placement
    # under dispatch. bf16-compute/f32-master was already the config's
    # dtype policy — now recorded via compute_dtype/mfu_basis extras.
    "cifar10_fedavg_100": (4, 16, {"run.fuse_rounds": 4,
                                   "server.fused_apply": True}),
    # ISSUE 18: the headline config's device-control-plane twin —
    # identical workload + fusion, but cohort/churn/slab derivation is
    # lowered into the round program (server/device_plane.py) so host
    # I/O collapses to flush boundaries. Bench-report's mode column
    # reads the two entries side by side.
    "cifar10_fedavg_100_device": (4, 16, {"run.fuse_rounds": 4,
                                          "server.fused_apply": True,
                                          "run.control_plane": "device"}),
    # r6: round fusion adopted for the dispatch-sensitive shapes — the
    # generalized fused scan now covers robust/attack/EF paths, and the
    # plain configs take the dispatch amortization directly (warmup and
    # timed are fused-chunk multiples; fuse divides num_rounds)
    "cifar10_fedavg_1000": (4, 8, {"run.fuse_rounds": 4,
                                   "server.fused_apply": True}),
    # r7: femnist's natural-partition (power-law) client sizes make the
    # federation-max pad mostly dead steps for the median cohort —
    # shape buckets trim them per chunk (bitwise-equal; the grid is
    # recorded in extra.shape_bucket_steps so the number stays honest)
    "femnist_fedprox_500": (4, 8, {"run.fuse_rounds": 4,
                                   "run.shape_buckets.enabled": True}),
    # shakespeare runs fused via its named config (run.fuse_rounds=10)
    "shakespeare_fedavg": (10, 20, {}),
    "imagenet_silo_dp": (1, 3, {"data.max_examples_per_client": 128}),
}


def _base_shape_name(name: str) -> str:
    # the *_device twins bench a named config under the device control
    # plane — same workload, the mode override rides in the entry's
    # overrides dict
    return name[: -len("_device")] if name.endswith("_device") else name


def _round_flops(exp, state):
    """Analytic FLOPs of one round: XLA-counted FLOPs of a single
    SCAN-FREE train step (value_and_grad on one batch) × local steps ×
    cohort size. The whole-round program cannot be cost-analyzed
    directly — XLA's cost model counts a ``lax.scan`` body ONCE, not
    ×trip-count, under-reporting a 128-step round by ~128×. Optimizer
    + psum + server-update FLOPs are elementwise (≪1% of fwd+bwd) and
    ignored; DP's per-example gradients cost the same matmul FLOPs as
    the batched backward. Raises when the backend has no cost model —
    every MFU extra divides by this number, so a bench without it is a
    failed bench, not one that quietly drops the fields."""
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.client.trainer import make_loss_fn

    bs = exp.cfg.client.batch_size
    x = jnp.asarray(exp.fed.train_x[:bs])
    y = jnp.asarray(exp.fed.train_y[:bs])
    m = jnp.ones((bs,), jnp.float32)
    step = jax.value_and_grad(make_loss_fn(exp.model, exp.task))
    compiled = jax.jit(step).lower(state["params"], x, y, m).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    if not ca or "flops" not in ca:
        raise RuntimeError(
            f"backend {jax.default_backend()!r} reports no cost_analysis "
            f"flops for the train step; the bench's MFU numbers need it"
        )
    return float(ca["flops"]) * exp.shape.steps * exp.cfg.server.cohort_size


def _parse_device_ms(profile_dir: str, fn_prefix: str = "jit_round_fn"):
    """Mean duration (ms) of the round program's DEVICE executions in a
    ``jax.profiler`` trace directory.

    The perfetto trace contains ``jit_round_fn`` spans on both the host
    (dispatch, ~ms) and the device (execution, the number we want); the
    device track is identified as the pid whose spans carry the most
    total time — dispatch spans are orders of magnitude shorter than
    executions for every config benched here. Returns None when no
    trace or no matching spans exist."""
    import glob
    import gzip
    import json as _json

    events = []
    for pattern in ("*.trace.json.gz", "*.trace.json"):
        for path in glob.glob(
            os.path.join(profile_dir, "**", pattern), recursive=True
        ):
            opener = gzip.open if path.endswith(".gz") else open
            try:
                with opener(path, "rt") as f:
                    events.extend(_json.load(f).get("traceEvents", []))
            except Exception:
                continue
    by_pid = {}
    for e in events:
        if e.get("ph") == "X" and str(e.get("name", "")).startswith(fn_prefix):
            by_pid.setdefault(e.get("pid"), []).append(float(e.get("dur", 0)))
    if not by_pid:
        return None
    durs = max(by_pid.values(), key=sum)
    return sum(durs) / len(durs) / 1000.0  # µs → ms


def _measure_device_ms(exp, state, start_round: int, rounds: int = 4):
    """Trace ``rounds`` dispatched rounds and return (state, mean device
    ms/round). The drain inside the trace waits for every round, so the
    trace contains the device work. Raises when the trace holds no
    round-program device spans: the configs that come here gate on this
    number, and a missing one must not switch the gate's basis."""
    import shutil
    import tempfile

    import jax

    tmp = tempfile.mkdtemp(prefix="bench_profile_")
    fuse = exp.cfg.run.fuse_rounds
    try:
        jax.profiler.start_trace(tmp)
        try:
            pending = []
            for r in range(start_round, start_round + rounds * fuse, fuse):
                state = exp.run_round(state, r)
                pending.append(state.pop("_metrics"))
            jax.device_get(pending)
        finally:
            jax.profiler.stop_trace()
        ms = _parse_device_ms(tmp)
        if ms is None:
            wrote = sorted(
                os.path.relpath(os.path.join(d, f), tmp)
                for d, _, files in os.walk(tmp) for f in files
            )
            raise RuntimeError(
                f"profiler trace holds no 'jit_round_fn' spans in a "
                f"*.trace.json(.gz) file; the profiler wrote: {wrote}"
            )
        # ``rounds`` DISPATCHES; under fusion each carries fuse rounds
        return state, ms / fuse
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _gate(name: str, rounds_per_sec: float, device_ms, mfu_pct):
    """(vs_baseline, basis): baseline_ms / measured_ms whenever a
    device-time baseline is pinned (the device pass then ran, or the
    bench raised) — device time regresses independently of host load,
    so it is the honest basis for every pinned config (dispatch-bound
    or not; ``mfu_pct`` is reported but no longer gates the basis).
    Wall-clock r/s against BASELINES otherwise. Pure function so the
    2×-regression-trips-the-gate property is unit-testable."""
    if device_ms and name in DEVICE_MS_BASELINES:
        return DEVICE_MS_BASELINES[name] / device_ms, "device_ms"
    baseline = BASELINES.get(name)
    return (rounds_per_sec / baseline if baseline else 1.0), "rounds_per_sec"


_STATIC_CHECK_CACHE = None


def _static_check_extra():
    """Static-analyzer provenance for every bench entry's extra
    (ISSUE 13): the analyzer version + whether `colearn check` passed
    clean on the repo producing this number. Computed once per process
    (the capability extraction runs ~600 validate() calls); best-effort
    — a broken analyzer must never take the bench down."""
    global _STATIC_CHECK_CACHE
    if _STATIC_CHECK_CACHE is None:
        from colearn_federated_learning_tpu.analysis.check import (
            bench_provenance,
        )

        _STATIC_CHECK_CACHE = bench_provenance()
    return _STATIC_CHECK_CACHE


def _peak_host_rss_mb():
    """Peak resident set size of THIS process (ru_maxrss; KiB on
    Linux). Recorded in every result's extra so the BENCH trajectory
    carries the clients-scale axis next to rounds/sec — the ROADMAP
    item-1 acceptance (`store_scale_1m` flat vs `store_scale_1k`) is
    read directly off these numbers. Matrix mode runs one subprocess
    per config, so each peak is that config's own."""
    import resource
    import sys

    kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform == "darwin":  # ru_maxrss is bytes on macOS
        kb /= 1024.0
    return round(kb / 1024.0, 1)


def _hbm_stats():
    """Peak/in-use device memory if the backend exposes it (HBM headroom
    for the north-star scale record); None otherwise."""
    import jax

    try:
        stats = jax.devices()[0].memory_stats() or {}
    except Exception:
        return None
    out = {}
    if "bytes_in_use" in stats:
        out["hbm_in_use_gib"] = round(stats["bytes_in_use"] / 2**30, 2)
    if "peak_bytes_in_use" in stats:
        out["hbm_peak_gib"] = round(stats["peak_bytes_in_use"] / 2**30, 2)
    if "bytes_limit" in stats:
        out["hbm_limit_gib"] = round(stats["bytes_limit"] / 2**30, 2)
    return out or None


def bench_config(name: str):
    import jax

    from colearn_federated_learning_tpu.config import get_named_config
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    warmup, timed, overrides = _SHAPES[name]
    base_name = _base_shape_name(name)
    cfg = get_named_config(base_name)
    cfg.server.num_rounds = warmup + timed
    cfg.server.eval_every = 0
    cfg.server.checkpoint_every = 0
    cfg.run.out_dir = ""
    # synthetic corpora at the real datasets' cardinality (zero egress —
    # real files absent); the per-config synthetic sizes already match
    # except the 100-client config, pinned at CIFAR's 50k here
    if base_name == "cifar10_fedavg_100":
        cfg.data.synthetic_train_size = 50_000
        cfg.data.synthetic_test_size = 1_000
    cfg.apply_overrides(overrides)
    cfg.validate()

    exp = Experiment(cfg, echo=False)
    state = exp.init_state()
    state = exp._place_state(state)
    flops_per_round = _round_flops(exp, state)

    # Rounds are dispatched asynchronously (the driver's production mode:
    # run.metrics_flush_every batches metric fetches); the timed region
    # ends with ONE metrics drain, which waits for every round (each
    # depends on the previous round's params) AND moves the scalars the
    # result needs to the host. On the local chip block_until_ready
    # syncs just as well (CHANGES.md PR 21); the drain stays because it
    # is the driver's own flush.
    fuse = cfg.run.fuse_rounds
    # each dispatch executes exactly `fuse` rounds — misaligned shape
    # constants would silently mis-count rounds_per_sec
    assert warmup % fuse == 0 and timed % fuse == 0, (name, warmup, timed, fuse)
    # the executable registry intercepts lowerings only while installed
    # (fit() does this for real runs); bench drives run_round directly,
    # so install around the round loops to get the HLO-derived flop
    # truth behind the flop_model_drift_pct extra — production runs
    # have it on too, so the timed region stays representative
    from colearn_federated_learning_tpu.obs import executables as _exec_mod

    if exp._exec_reg is not None:
        _exec_mod.install(exp._exec_reg)
    try:
        for r in range(0, warmup, fuse):
            state = exp.run_round(state, r)
            m = state.pop("_metrics")
            last_loss = float(
                m.train_loss if fuse == 1 else m.train_loss[-1]
            )

        # reset the phase-span aggregates so the breakdown below covers
        # the TIMED region only (the warmup window holds the compiles)
        exp.tracer.drain()
        t0 = time.perf_counter()
        pending = []
        for r in range(warmup, warmup + timed, fuse):
            state = exp.run_round(state, r)
            m = state.pop("_metrics")
            if fuse == 1:
                pending.append(m)
            else:
                pending.extend(
                    jax.tree.map(lambda a, j=j: a[j], m) for j in range(fuse)
                )
        fetched = jax.device_get(pending)
        last_loss = float(fetched[-1].train_loss)
        dt = time.perf_counter() - t0
    finally:
        if exp._exec_reg is not None:
            _exec_mod.uninstall()

    rounds_per_sec = timed / dt
    updates_per_sec_per_chip = (
        timed * cfg.server.cohort_size / dt / exp.n_chips
    )
    mfu_basis, peak_flops = _mfu_basis(cfg)
    flops_pct = (
        100.0 * flops_per_round * rounds_per_sec / (peak_flops * exp.n_chips)
    )
    # per-phase host-side timing of the timed region (obs/spans.py):
    # localizes a wall-clock regression to host inputs / placement /
    # dispatch (or a mid-bench retrace) without a profiler rerun —
    # drained BEFORE the device-time pass dispatches extra rounds
    timed_compiles = exp.tracer.compile_stats()[0]
    phase_ms = {
        k: v["total_ms"] for k, v in exp.tracer.drain().items()
    }
    # device-time pass for gating: every config with a pinned device
    # baseline gets the weather-independent basis (4 profiled dispatches
    # — cheap next to the timed region)
    device_ms = None
    if name in DEVICE_MS_BASELINES:
        state, device_ms = _measure_device_ms(exp, state, warmup + timed)
    vs, vs_basis = _gate(name, rounds_per_sec, device_ms, flops_pct)
    # measured-vs-analytic flop drift (run.obs.executables): the XLA
    # cost_analysis flops of the dominant compiled round program vs the
    # analytic model — None (n/a in bench-report) when the registry is
    # off or the backend reports no cost analysis, gated against
    # flop_drift_pct_max
    drift_pct = None
    reg = getattr(exp, "_exec_reg", None)
    if reg is not None:
        measured = reg.measured_round_flops()
        if measured is not None:
            drift_pct = round(
                100.0 * (measured[1] - flops_per_round) / flops_per_round, 2
            )
    extra = {
        "static_check": _static_check_extra(),
        "vs_baseline_basis": vs_basis,
        "phase_ms": phase_ms,
        "flop_model_drift_pct": drift_pct,
        "client_updates_per_sec_per_chip": round(updates_per_sec_per_chip, 4),
        "n_chips": exp.n_chips,
        "timed_rounds": timed,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "data_source": exp.fed.meta.get("source"),
        # clients-scale axis (ROADMAP item 1): every result records the
        # federation size and this process's peak host RSS, so the
        # BENCH trajectory shows host memory tracking O(cohort), not
        # O(num_clients), as the store-backed entries scale up
        "num_clients": cfg.data.num_clients,
        "peak_host_rss_mb": _peak_host_rss_mb(),
        "final_train_loss": round(last_loss, 4),
        "param_dtype": cfg.run.param_dtype,
        # precision provenance (r7, ROADMAP item 2): which dtype the
        # matmuls ran in and which peak the MFU divides by — a bf16
        # number silently compared against an f32 denominator (or vice
        # versa) is the exact hygiene failure mfu_basis exists to stop
        "compute_dtype": cfg.run.compute_dtype,
        "mfu_basis": mfu_basis,
        "peak_tflops": round(peak_flops / 1e12, 1),
        "fused_apply": bool(cfg.server.fused_apply),
        "double_buffer": bool(cfg.run.double_buffer),
        # shape provenance (r6): fuse_rounds and the local-training
        # dtype change the meaning of every throughput number — record
        # them in each result so the BENCH_*.json trajectory stays
        # interpretable across shape re-pins
        "fuse_rounds": cfg.run.fuse_rounds,
        "local_param_dtype": cfg.run.local_param_dtype,
        # cohort layout (r12): megabatch collapses the cohort axis into
        # the GEMM batch — throughput/MFU numbers under the two layouts
        # are different machines, so every result records which one ran
        "cohort_layout": cfg.run.cohort_layout,
        # control plane (ISSUE 18): device mode derives cohorts/churn in
        # the round program, so the host-exposed share is a different
        # machine — every result records which plane produced it
        "control_plane": cfg.run.control_plane,
        # the per-client forensic ledger adds an in-program stats block
        # + scatter to every round — throughput numbers with it on are
        # not comparable to ledger-off pins, so record the switch
        "client_ledger": bool(cfg.run.obs.client_ledger.enabled),
        # cohort-selection mode and reputation weighting (r8): adaptive
        # sampling changes which clients (and so which shard shapes) the
        # timed rounds draw, and reputation adds the in-program trust
        # computation — both shift throughput semantics, so every result
        # records them next to the ledger switch
        "sampler": cfg.server.sampling,
        "reputation": bool(cfg.server.reputation.enabled),
        # federation health observatory (run.obs.population): per-window
        # population_health records add small host-side accounting to
        # every round — record the switch so throughput numbers stay
        # comparable across BENCH entries
        "population": bool(cfg.run.obs.population.enabled),
        # LoRA adapter plane (model.lora): adapter-only uploads change
        # both the wire story and the per-round compute — every result
        # records the switch and the analytic full÷adapter upload-byte
        # ratio (exactly 1.0 with lora off)
        "lora": bool(cfg.model.lora.enabled),
        "wire_reduction_vs_full": round(exp.wire_reduction_vs_full(), 2),
        # trace-shaped churn (run.churn): availability gating + failure
        # injection change which clients (and how much work) the timed
        # rounds see — every result records the switch
        "churn": bool(cfg.run.churn.enabled),
    }
    for k, v in overrides.items():
        extra[f"override:{k}"] = v
    if device_ms is not None:
        extra["device_ms_per_round"] = round(device_ms, 3)
    extra["dispatch_bound"] = bool(flops_pct < DISPATCH_BOUND_MFU_PCT)
    # Shape-waste accounting (r7): which step grids the timed rounds
    # actually dispatched on, and how much of the padded grid was dead
    # work — so a BENCH_* trajectory can attribute a throughput move to
    # shape waste (or a bucket re-pin) rather than the kernels.
    import numpy as _np

    shape_stats = [
        exp._comm_stats.get(r) for r in range(warmup, warmup + timed)
    ]
    shape_stats = [s for s in shape_stats if s]
    if shape_stats and "padded_step_fraction" in shape_stats[0]:
        extra["padded_step_fraction"] = round(float(_np.mean(
            [s["padded_step_fraction"] for s in shape_stats]
        )), 4)
        extra["host_input_bytes_per_round"] = int(_np.mean(
            [s["host_input_bytes"] for s in shape_stats]
        ))
    extra["shape_bucket_steps"] = sorted({
        int(s["shape_bucket_steps"]) for s in shape_stats
        if "shape_bucket_steps" in s
    }) or [exp.shape.steps]
    if exp._bucket_ladder is not None:
        # compile budget: ≤ ladder-size retraces per engine; a NONZERO
        # timed-region compile count means a rung first realized inside
        # the timed window — visible here and as phase_ms["compile"]
        extra["shape_bucket_ladder_steps"] = [
            r * cfg.client.local_epochs for r in exp._bucket_ladder
        ]
        extra["timed_region_compiles"] = int(timed_compiles)
        assert len(exp._seen_buckets) <= len(exp._bucket_ladder), (
            exp._seen_buckets, exp._bucket_ladder
        )
    # raw MFU counts the FULL padded federation-max grid as useful
    # work (the legacy accounting); effective MFU mask-weights it —
    # only real examples' step FLOPs count, so the gap between the
    # two IS the padded-FLOP waste shape buckets reclaim
    step_flops = flops_per_round / (exp.shape.steps * cfg.server.cohort_size)
    mean_examples = float(_np.mean([float(m.examples) for m in fetched]))
    useful_flops = step_flops * mean_examples / cfg.client.batch_size
    extra.update({
        "model_tflops_per_round": round(flops_per_round / 1e12, 3),
        "achieved_tflops": round(flops_per_round * rounds_per_sec / 1e12, 2),
        "mfu_pct": round(flops_pct, 2),
        "effective_mfu_pct": round(
            100.0 * useful_flops * rounds_per_sec
            / (peak_flops * exp.n_chips), 2
        ),
    })
    if name == "cifar10_fedavg_100":
        # ROADMAP item 2's stated goal for the headline config — the
        # measured step above it (or short of it) is the honest record
        extra["roadmap_target"] = {"mfu_pct": 50.0, "vs_baseline": 2.0}
    hbm = _hbm_stats()
    if hbm:
        extra.update(hbm)
    d = cfg.data
    return {
        "metric": (
            f"FL rounds/sec ({d.num_clients}-client {d.name}, "
            f"{cfg.model.name}, cohort {cfg.server.cohort_size})"
        ),
        "value": round(rounds_per_sec, 4),
        "unit": "rounds/sec",
        "vs_baseline": round(vs, 4),
        "extra": extra,
    }


# Clients-scale entries (ROADMAP item 1 acceptance): the same tiny
# store-backed workload at 10³ and 10⁶ clients — streaming sampler,
# stream placement, mmap store — so the BENCH trajectory records host
# RSS staying flat (within 1.5×) while num_clients grows 1000×. Built
# on the fly into a temp dir (a 10⁶-client store of 2×(12,12,1)-uint8
# records is ~290 MB of DISK, a few MB of touched pages).
_STORE_SCALE = {
    "store_scale_1k": 1_000,
    "store_scale_1m": 1_000_000,
}

# Weak-scaling entries (ROADMAP item 1 follow-on / ISSUE 12): the SAME
# per-chip workload — the headline ResNet-18 family under the megabatch
# cohort layout, K_local clients per chip — run at however many chips
# are visible, so the BENCH trajectory finally gets an `n_chips` axis.
# The realized cohort is per_chip × n_chips (cohort-in-the-hundreds on
# a multi-chip slice; on 1 chip the entry IS the 1-chip pin the
# `colearn bench-report` weak-scaling-efficiency line divides by).
# Ideal weak scaling holds updates/sec/chip flat as chips grow.
_WEAK_SCALE = {
    "weak_scale_64": 64,
    "weak_scale_128": 128,
    "weak_scale_256": 256,
}


def _weak_scale_cfg(per_chip: int, n_chips: int, warmup: int, timed: int):
    """The weak-scale workload for one (per-chip cohort, chip count)
    point — factored out so CI can validate every entry's config
    without paying for a ResNet run."""
    from colearn_federated_learning_tpu.config import get_named_config

    cohort = per_chip * n_chips
    cfg = get_named_config("cifar10_fedavg_100")
    cfg.apply_overrides({
        # federation sized 2× the cohort so sampling stays a real draw;
        # the 50k corpus keeps shards non-degenerate up to 2048 clients
        "data.num_clients": 2 * cohort,
        "data.synthetic_train_size": 50_000,
        "data.synthetic_test_size": 1_000,
        # bounded per-chip step grid: 2 steps × batch 32 per client —
        # the megabatch block still sees K_local·32 GEMM rows per chip
        "data.max_examples_per_client": 64,
        "client.batch_size": 32,
        "server.cohort_size": cohort,
        "server.num_rounds": warmup + timed,
        "server.eval_every": 0,
        "server.checkpoint_every": 0,
        "run.out_dir": "",
        "run.fuse_rounds": 1,
        "run.cohort_layout": "megabatch",
        "server.fused_apply": True,
    })
    return cfg.validate()


def bench_weak_scale(name: str):
    import jax

    from colearn_federated_learning_tpu.server.round_driver import Experiment

    per_chip = _WEAK_SCALE[name]
    n_chips = len(jax.devices())
    cohort = per_chip * n_chips
    warmup, timed = 2, 4
    cfg = _weak_scale_cfg(per_chip, n_chips, warmup, timed)
    exp = Experiment(cfg, echo=False)
    state = exp._place_state(exp.init_state())
    flops_per_round = _round_flops(exp, state)
    for r in range(warmup):
        state = exp.run_round(state, r)
        state.pop("_metrics")
    t0 = time.perf_counter()
    pending = []
    for r in range(warmup, warmup + timed):
        state = exp.run_round(state, r)
        pending.append(state.pop("_metrics"))
    fetched = jax.device_get(pending)
    dt = time.perf_counter() - t0
    rounds_per_sec = timed / dt
    ups_chip = timed * cohort / dt / exp.n_chips
    basis, peak_flops = _mfu_basis(cfg)
    extra = {
        "static_check": _static_check_extra(),
        "weak_scale_per_chip_cohort": per_chip,
        "cohort_size": cohort,
        "n_chips": exp.n_chips,
        "client_updates_per_sec_per_chip": round(ups_chip, 4),
        "cohort_layout": cfg.run.cohort_layout,
        "control_plane": cfg.run.control_plane,
        "fused_apply": bool(cfg.server.fused_apply),
        "num_clients": cfg.data.num_clients,
        "timed_rounds": timed,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "compute_dtype": cfg.run.compute_dtype,
        "local_param_dtype": cfg.run.local_param_dtype,
        "mfu_basis": basis,
        "peak_host_rss_mb": _peak_host_rss_mb(),
        "final_train_loss": round(float(fetched[-1].train_loss), 4),
        "lora": False,
        "wire_reduction_vs_full": round(exp.wire_reduction_vs_full(), 2),
        "churn": bool(cfg.run.churn.enabled),
    }
    extra["model_tflops_per_round"] = round(flops_per_round / 1e12, 3)
    extra["mfu_pct"] = round(
        100.0 * flops_per_round * rounds_per_sec
        / (peak_flops * exp.n_chips), 2
    )
    hbm = _hbm_stats()
    if hbm:
        extra.update(hbm)
    return {
        "metric": (
            f"FL rounds/sec (weak scaling: {per_chip} clients/chip x "
            f"{exp.n_chips} chip(s), resnet18, megabatch cohort {cohort})"
        ),
        "value": round(rounds_per_sec, 4),
        "unit": "rounds/sec",
        # a weak-scale entry's regression basis is the efficiency line
        # in `colearn bench-report`, not a scalar baseline ratio
        "vs_baseline": 1.0,
        "extra": extra,
    }


# Async-throughput entry (ROADMAP item 4 acceptance): the promoted
# FedBuff plane under production traffic — 10³-client mmap store,
# stream placement, streaming-sampler arrivals, per-insert ledger +
# reputation merge, diurnal churn + dropout hazard + crash injection.
# The headline number is updates/sec ABSORBED at the configured
# staleness bound (clamped admissions counted, never silently
# included as bounded), recorded next to rounds/sec. BENCH_BUDGETS.json
# carries its floor (`async_updates_per_sec_min`); the entry records
# whether it was met so the trajectory gates on it.
_ASYNC_SCALE = {
    "async_throughput_1k": 1_000,
}


def bench_async_throughput(name: str):
    import shutil
    import tempfile

    import jax

    from colearn_federated_learning_tpu.config import get_named_config
    from colearn_federated_learning_tpu.data.store import (
        build_synthetic_store,
    )
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    n = _ASYNC_SCALE[name]
    warmup, timed = 2, 8
    s_max = 2
    tmp = tempfile.mkdtemp(prefix=f"bench_{name}_")
    try:
        t_build0 = time.perf_counter()
        build_synthetic_store(
            tmp, num_clients=n, examples_per_client=2, shape=(12, 12, 1),
            num_classes=10, seed=0, test_examples=64,
        )
        build_sec = time.perf_counter() - t_build0
        cfg = get_named_config("mnist_fedavg_2")
        cfg.apply_overrides({
            "algorithm": "fedbuff",
            "data.num_clients": n, "data.store.dir": tmp,
            "data.placement": "stream", "server.sampling": "streaming",
            "server.cohort_size": 16, "client.batch_size": 2,
            "server.num_rounds": warmup + timed, "server.eval_every": 0,
            "server.checkpoint_every": 0, "run.out_dir": "",
            "server.async_max_staleness": s_max,
            "server.async_backlog_cap": 8,
            # per-insert ledger stats feed the reputation-weighted merge
            # and the streaming sampler's arrival sketch
            "run.obs.client_ledger.enabled": True,
            "run.obs.client_ledger.log_every": 2,
            "server.reputation.enabled": True,
            "run.obs.population.enabled": True,
            # trace-shaped production traffic: diurnal wave + dropout
            # hazard + crash injection (seed-pure, resume-replayable)
            "run.churn.enabled": True,
            "run.churn.diurnal_period": 8,
            "run.churn.base_availability": 0.7,
            "run.churn.dropout_hazard": 0.02,
            "run.churn.crash_rate": 0.05,
        })
        cfg.validate()
        exp = Experiment(cfg, echo=False)
        state = exp._place_state(exp.init_state())
        for r in range(warmup):
            state = exp.run_round(state, r)
            exp._ledger_ref = state.get("ledger")
            state.pop("_metrics")
        absorbed0 = exp._async_absorbed
        t0 = time.perf_counter()
        pending = []
        for r in range(warmup, warmup + timed):
            state = exp.run_round(state, r)
            exp._ledger_ref = state.get("ledger")
            pending.append(state.pop("_metrics"))
        fetched = jax.device_get(pending)
        dt = time.perf_counter() - t0
        absorbed = exp._async_absorbed - absorbed0
        astats = [exp._async_stats[r] for r in range(warmup, warmup + timed)
                  if r in exp._async_stats]
        max_stale = max((a["max"] for a in astats), default=0)
        clamped = sum(a["clamped"] for a in astats)
        bp = sum(a["bp_dropped"] + a["bp_rejected"] for a in astats)
        updates_per_sec = absorbed / dt if dt > 0 else 0.0
        # the BENCH_BUDGETS floor for this entry (satellite: the async
        # throughput number is trajectory-gated like rounds/sec)
        floor = None
        try:
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "BENCH_BUDGETS.json")) as f:
                floor = json.load(f).get("async_updates_per_sec_min")
        except (OSError, json.JSONDecodeError):
            pass
        pop_totals = exp._population.summary_totals(
            None, (exp.fed.train_x, exp.fed.train_y)
        )
        return {
            "metric": (
                f"async updates/sec absorbed at staleness <= {2 * s_max} "
                f"({n}-client mmap store, fedbuff + churn, buffer "
                f"{cfg.server.cohort_size}, streaming sampler)"
            ),
            "value": round(updates_per_sec, 4),
            "unit": "updates/sec",
            "vs_baseline": 1.0,
            "extra": {
                "static_check": _static_check_extra(),
                "num_clients": n,
                "store_backed": True,
                "store_build_sec": round(build_sec, 2),
                "placement": "stream",
                "sampler": "streaming",
                "client_ledger": True,
                "reputation": True,
                "population": True,
                "churn": True,
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "timed_rounds": timed,
                "rounds_per_sec": round(timed / dt, 4) if dt > 0 else 0.0,
                "updates_absorbed": int(absorbed),
                "staleness_bound": 2 * s_max,
                "max_realized_staleness": int(max_stale),
                # pooled per-update staleness quantiles over the timed
                # window (exact — the driver keeps a value → count
                # histogram, no sampling)
                "staleness_p50": exp._staleness_percentiles()[0],
                "staleness_p90": exp._staleness_percentiles()[1],
                "staleness_clamped": int(clamped),
                "backpressure_shed": int(bp),
                "async_overload_policy": cfg.server.async_overload_policy,
                "final_train_loss": round(
                    float(fetched[-1].train_loss), 4
                ),
                "peak_host_rss_mb": _peak_host_rss_mb(),
                "coverage_pct": pop_totals.get("population_coverage_pct"),
                "gather_workers": pop_totals.get("store_gather_workers"),
                "store_gather_mbps": pop_totals.get("store_gather_mbps"),
                "budget_floor_updates_per_sec": floor,
                "meets_budget": (
                    bool(updates_per_sec >= float(floor))
                    if floor is not None else None
                ),
                "lora": False,
                "cohort_layout": cfg.run.cohort_layout,
                "control_plane": cfg.run.control_plane,
                "wire_reduction_vs_full": round(
                    exp.wire_reduction_vs_full(), 2
                ),
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# Hierarchical multi-version async entry (ISSUE 16 acceptance): the
# FedBuff plane at 10⁶ store-backed clients with TWO concurrent model
# versions (server.async_versions), FOUR edge aggregators grouping the
# popped buffer (server.hierarchy, reputation-trust core, 10% edge
# dropout), and trace-replay availability (run.churn.trace) instead of
# the analytic diurnal model. Headline: updates/sec ABSORBED at the
# staleness bound; extras break the absorbed count down per tier (edge)
# and per version. BENCH_BUDGETS.json gates it TWICE — the throughput
# floor (`async_updates_per_sec_min`) and the realized-staleness
# ceiling (`hier_async_staleness_bound`) — so a regression that keeps
# throughput by letting staleness run away still fails the report.
_HIER_ASYNC_SCALE = {
    "hier_async_1m": 1_000_000,
}


def bench_hier_async(name: str):
    import shutil
    import tempfile

    import jax

    from colearn_federated_learning_tpu.config import get_named_config
    from colearn_federated_learning_tpu.data.store import (
        build_synthetic_store,
    )
    from colearn_federated_learning_tpu.server.churn import (
        build_synthetic_trace,
    )
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    n = _HIER_ASYNC_SCALE[name]
    warmup, timed = 2, 8
    s_max, versions, edges = 2, 2, 4
    tmp = tempfile.mkdtemp(prefix=f"bench_{name}_")
    try:
        t_build0 = time.perf_counter()
        build_synthetic_store(
            tmp, num_clients=n, examples_per_client=2, shape=(12, 12, 1),
            num_classes=10, seed=0, test_examples=64,
        )
        build_sec = time.perf_counter() - t_build0
        trace = build_synthetic_trace(
            os.path.join(tmp, "avail_trace"), rounds=64, rows=4096,
            seed=0, diurnal_period=8,
        )
        cfg = get_named_config("mnist_fedavg_2")
        cfg.apply_overrides({
            "algorithm": "fedbuff",
            "data.num_clients": n, "data.store.dir": tmp,
            "data.placement": "stream", "server.sampling": "streaming",
            "server.cohort_size": 16, "client.batch_size": 2,
            "server.num_rounds": warmup + timed, "server.eval_every": 0,
            "server.checkpoint_every": 0, "run.out_dir": "",
            "server.async_max_staleness": s_max,
            "server.async_backlog_cap": 8,
            # the tentpole knobs: concurrent model lines + edge tier
            "server.async_versions": versions,
            "server.async_retire_rounds": 6,
            "server.hierarchy.num_edges": edges,
            "server.hierarchy.core_aggregator": "reputation",
            "server.hierarchy.edge_dropout_rate": 0.1,
            "run.obs.population.enabled": True,
            # availability from a recorded on/off trace, not the
            # analytic diurnal wave (seed-pure row hash, O(cohort))
            "run.churn.enabled": True,
            "run.churn.trace": trace,
            "run.churn.dropout_hazard": 0.02,
        })
        cfg.validate()
        exp = Experiment(cfg, echo=False)
        state = exp._place_state(exp.init_state())
        for r in range(warmup):
            state = exp.run_round(state, r)
            state.pop("_metrics")
        absorbed0 = exp._async_absorbed
        t0 = time.perf_counter()
        pending = []
        for r in range(warmup, warmup + timed):
            state = exp.run_round(state, r)
            pending.append(state.pop("_metrics"))
        fetched = jax.device_get(pending)
        dt = time.perf_counter() - t0
        absorbed = exp._async_absorbed - absorbed0
        astats = [exp._async_stats[r] for r in range(warmup, warmup + timed)
                  if r in exp._async_stats]
        max_stale = max((a["max"] for a in astats), default=0)
        p50, p90, _hist_max = exp._staleness_percentiles()
        updates_per_sec = absorbed / dt if dt > 0 else 0.0
        floor = bound = None
        try:
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "BENCH_BUDGETS.json")) as f:
                budgets = json.load(f)
            floor = budgets.get("async_updates_per_sec_min")
            bound = budgets.get("hier_async_staleness_bound")
        except (OSError, json.JSONDecodeError):
            pass
        meets = None
        if floor is not None or bound is not None:
            meets = bool(
                (floor is None or updates_per_sec >= float(floor))
                and (bound is None or max_stale <= int(bound))
            )
        pop_totals = exp._population.summary_totals(
            None, (exp.fed.train_x, exp.fed.train_y)
        )
        return {
            "metric": (
                f"hier async updates/sec absorbed at staleness <= "
                f"{2 * s_max} ({n}-client mmap store, fedbuff × "
                f"{versions} versions × {edges} edges, trace churn)"
            ),
            "value": round(updates_per_sec, 4),
            "unit": "updates/sec",
            "vs_baseline": 1.0,
            "extra": {
                "static_check": _static_check_extra(),
                "num_clients": n,
                "store_backed": True,
                "store_build_sec": round(build_sec, 2),
                "placement": "stream",
                "sampler": "streaming",
                "population": True,
                "churn": True,
                "churn_trace": True,
                "async_versions": versions,
                "hier_edges": edges,
                "edge_dropout_rate": 0.1,
                "core_aggregator": "reputation",
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "timed_rounds": timed,
                "rounds_per_sec": round(timed / dt, 4) if dt > 0 else 0.0,
                "updates_absorbed": int(absorbed),
                "staleness_bound": 2 * s_max,
                "max_realized_staleness": int(max_stale),
                "staleness_p50": p50,
                "staleness_p90": p90,
                # per-tier / per-version absorbed breakdown — the
                # ISSUE 16 acceptance readout (a starved version or a
                # dead edge reads ~0 in its bucket)
                "per_version_absorbed": {
                    str(v): int(c)
                    for v, c in enumerate(exp._per_version_absorbed[:versions])
                },
                "per_edge_absorbed": {
                    str(e): int(c) for e, c in enumerate(exp._edge_absorbed)
                },
                "version_readmitted": int(exp._version_readmitted),
                "final_train_loss": round(
                    float(fetched[-1].train_loss), 4
                ),
                "peak_host_rss_mb": _peak_host_rss_mb(),
                "coverage_pct": pop_totals.get("population_coverage_pct"),
                "gather_workers": pop_totals.get("store_gather_workers"),
                "store_gather_mbps": pop_totals.get("store_gather_mbps"),
                "budget_floor_updates_per_sec": floor,
                "budget_staleness_bound": bound,
                "meets_budget": meets,
                "lora": False,
                "cohort_layout": cfg.run.cohort_layout,
                "control_plane": cfg.run.control_plane,
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# LoRA × store-scale entries (ROADMAP item 3 acceptance): BERT-tiny
# transformer federation over the mmap client store at 10³ and 10⁶
# clients, adapter-only uploads (rank-2 attention LoRA ⇒ ~133× fewer
# upload bytes than the full-delta twin at this geometry — recorded as
# extra.wire_reduction_vs_full), streaming sampler + paged ledger +
# population tracking. The acceptance bar mirrors PR 9's: the
# 10⁶-client entry's peak_host_rss_mb must stay within 1.5× the
# 10³-client twin's in the same BENCH_r*.json.
_LORA_SCALE = {
    "bert_lora_1k": 1_000,
    "bert_lora_1m": 1_000_000,
}


def bench_store_scale(name: str):
    import shutil
    import tempfile

    import jax

    from colearn_federated_learning_tpu.config import get_named_config
    from colearn_federated_learning_tpu.data.store import (
        build_synthetic_store,
    )
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    n = _STORE_SCALE[name]
    warmup, timed = 2, 6
    tmp = tempfile.mkdtemp(prefix=f"bench_{name}_")
    try:
        t_build0 = time.perf_counter()
        build_synthetic_store(
            tmp, num_clients=n, examples_per_client=2, shape=(12, 12, 1),
            num_classes=10, seed=0, test_examples=64,
        )
        build_sec = time.perf_counter() - t_build0
        cfg = get_named_config("mnist_fedavg_2")
        cfg.apply_overrides({
            "data.num_clients": n, "data.store.dir": tmp,
            "data.placement": "stream", "server.sampling": "streaming",
            "server.cohort_size": 16, "client.batch_size": 2,
            "server.num_rounds": warmup + timed, "server.eval_every": 0,
            "server.checkpoint_every": 0, "run.out_dir": "",
            # the 1M-scale data-plane baseline (run.obs.population):
            # population tracking + the paged ledger feeding the
            # streaming sampler's sketch, so these entries record
            # coverage % and pager hit rate next to rounds/sec — the
            # numbers the federation health observatory gets judged by
            "run.obs.population.enabled": True,
            "run.obs.client_ledger.enabled": True,
            "run.obs.client_ledger.log_every": 2,
            "run.obs.client_ledger.hot_capacity": 64,
        })
        cfg.validate()
        exp = Experiment(cfg, echo=False)
        state = exp._place_state(exp.init_state())
        for r in range(warmup):
            state = exp.run_round(state, r)
            # the fit loop's per-round rebind: the ledger input is
            # donated, so snapshot refreshes must read the new array
            exp._ledger_ref = state.get("ledger")
            state.pop("_metrics")
        t0 = time.perf_counter()
        pending = []
        for r in range(warmup, warmup + timed):
            state = exp.run_round(state, r)
            exp._ledger_ref = state.get("ledger")
            pending.append(state.pop("_metrics"))
        fetched = jax.device_get(pending)
        dt = time.perf_counter() - t0
        rss = _peak_host_rss_mb()
        # end-of-run data-plane readout off the live tracker (the same
        # totals a full fit() would land in run_summary)
        pop_totals = exp._population.summary_totals(
            exp._pager, (exp.fed.train_x, exp.fed.train_y)
        )
        return {
            "metric": (
                f"FL rounds/sec ({n}-client mmap store, lenet5, "
                f"cohort {cfg.server.cohort_size}, streaming sampler)"
            ),
            "value": round(timed / dt, 4),
            "unit": "rounds/sec",
            "vs_baseline": 1.0,
            "extra": {
                "static_check": _static_check_extra(),
                "num_clients": n,
                "peak_host_rss_mb": rss,
                "store_backed": True,
                "store_build_sec": round(build_sec, 2),
                "placement": "stream",
                "sampler": "streaming",
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "timed_rounds": timed,
                "final_train_loss": round(
                    float(fetched[-1].train_loss), 4
                ),
                # the acceptance readout: compare this config's
                # peak_host_rss_mb against store_scale_1k's in the same
                # BENCH_r*.json — flat (≤1.5×) across the 1000× scale
                # step is ROADMAP item 1's bar
                "rss_budget_vs_1k": 1.5,
                # 1M-scale data-plane baseline (run.obs.population):
                # how much of the federation the timed run touched and
                # how the paged ledger's hot set behaved at this scale
                "population": True,
                "coverage_pct": pop_totals.get("population_coverage_pct"),
                "unique_clients_est": pop_totals.get(
                    "population_unique_clients"
                ),
                "pager_hit_rate": pop_totals.get("pager_hit_rate"),
                # store data plane (PR 19): resolved pool width + wall
                # gather throughput — BENCH_BUDGETS gates the floor
                "gather_workers": pop_totals.get("store_gather_workers"),
                "store_gather_mbps": pop_totals.get("store_gather_mbps"),
                "lora": False,
                "cohort_layout": cfg.run.cohort_layout,
                "control_plane": cfg.run.control_plane,
                "wire_reduction_vs_full": round(
                    exp.wire_reduction_vs_full(), 2
                ),
                "churn": bool(cfg.run.churn.enabled),
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_lora_scale(name: str):
    """The transformer twin of :func:`bench_store_scale`: a BERT-tiny
    LoRA federation over an on-the-fly synthetic LM store — adapter
    uploads, stream placement, streaming sampler fed by the paged
    ledger, population tracking. Records rounds/sec plus the three
    numbers the ROADMAP item-3 acceptance reads: peak_host_rss_mb
    (≤1.5× the 1k twin at 10⁶ clients), coverage_pct, and
    wire_reduction_vs_full."""
    import shutil
    import tempfile

    import jax

    from colearn_federated_learning_tpu.config import get_named_config
    from colearn_federated_learning_tpu.data.store import (
        build_synthetic_lm_store,
    )
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    n = _LORA_SCALE[name]
    warmup, timed = 2, 6
    seq_len, vocab = 32, 64
    tmp = tempfile.mkdtemp(prefix=f"bench_{name}_")
    try:
        t_build0 = time.perf_counter()
        build_synthetic_lm_store(
            tmp, num_clients=n, examples_per_client=2, seq_len=seq_len,
            vocab_size=vocab, seed=0, test_examples=64,
        )
        build_sec = time.perf_counter() - t_build0
        cfg = get_named_config("bert_lora_federated")
        cfg.apply_overrides({
            "data.num_clients": n, "data.store.dir": tmp,
            "data.placement": "stream",
            "model.kwargs.seq_len": seq_len,
            "model.kwargs.vocab_size": vocab,
            "server.cohort_size": 16, "client.batch_size": 2,
            "server.num_rounds": warmup + timed, "server.eval_every": 0,
            "server.checkpoint_every": 0, "run.out_dir": "",
            "run.client_vmap_width": 1,
            "run.obs.population.enabled": True,
            "run.obs.client_ledger.enabled": True,
            "run.obs.client_ledger.log_every": 2,
            "run.obs.client_ledger.hot_capacity": 64,
        })
        cfg.validate()
        exp = Experiment(cfg, echo=False)
        state = exp._place_state(exp.init_state())
        for r in range(warmup):
            state = exp.run_round(state, r)
            exp._ledger_ref = state.get("ledger")
            state.pop("_metrics")
        t0 = time.perf_counter()
        pending = []
        for r in range(warmup, warmup + timed):
            state = exp.run_round(state, r)
            exp._ledger_ref = state.get("ledger")
            pending.append(state.pop("_metrics"))
        fetched = jax.device_get(pending)
        dt = time.perf_counter() - t0
        rss = _peak_host_rss_mb()
        pop_totals = exp._population.summary_totals(
            exp._pager, (exp.fed.train_x, exp.fed.train_y)
        )
        return {
            "metric": (
                f"FL rounds/sec ({n}-client mmap LM store, bert_tiny "
                f"rank-{cfg.model.lora.rank} LoRA, cohort "
                f"{cfg.server.cohort_size}, streaming sampler)"
            ),
            "value": round(timed / dt, 4),
            "unit": "rounds/sec",
            "vs_baseline": 1.0,
            "extra": {
                "static_check": _static_check_extra(),
                "num_clients": n,
                "peak_host_rss_mb": rss,
                "store_backed": True,
                "store_build_sec": round(build_sec, 2),
                "placement": "stream",
                "sampler": "streaming",
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "timed_rounds": timed,
                "final_train_loss": round(
                    float(fetched[-1].train_loss), 4
                ),
                # the PR 9 budget the acceptance reads: the 1m entry's
                # peak RSS vs the 1k twin's in the same BENCH_r*.json
                "rss_budget_vs_1k": 1.5,
                "population": True,
                "coverage_pct": pop_totals.get("population_coverage_pct"),
                "unique_clients_est": pop_totals.get(
                    "population_unique_clients"
                ),
                "pager_hit_rate": pop_totals.get("pager_hit_rate"),
                "gather_workers": pop_totals.get("store_gather_workers"),
                "store_gather_mbps": pop_totals.get("store_gather_mbps"),
                # the adapter-plane headline: full-delta ÷ adapter
                # upload bytes at this geometry (analytic, config-pure)
                "lora": True,
                "lora_rank": cfg.model.lora.rank,
                "lora_target": cfg.model.lora.target,
                "cohort_layout": cfg.run.cohort_layout,
                "control_plane": cfg.run.control_plane,
                "wire_reduction_vs_full": round(
                    exp.wire_reduction_vs_full(), 2
                ),
                "churn": bool(cfg.run.churn.enabled),
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="cifar10_fedavg_100",
                    choices=(sorted(_SHAPES) + sorted(_STORE_SCALE)
                             + sorted(_LORA_SCALE) + sorted(_WEAK_SCALE)
                             + sorted(_ASYNC_SCALE)
                             + sorted(_HIER_ASYNC_SCALE)))
    ap.add_argument("--matrix", action="store_true",
                    help="bench every config; one JSON line each")
    args = ap.parse_args(argv)
    # before the first compile; the --matrix children inherit the same
    # directory, so a re-run (or a later call on a machine that keeps
    # it) starts warm
    from colearn_federated_learning_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    if not args.matrix:
        if args.config in _WEAK_SCALE:
            print(json.dumps(bench_weak_scale(args.config)), flush=True)
        elif args.config in _LORA_SCALE:
            print(json.dumps(bench_lora_scale(args.config)), flush=True)
        elif args.config in _STORE_SCALE:
            print(json.dumps(bench_store_scale(args.config)), flush=True)
        elif args.config in _ASYNC_SCALE:
            print(json.dumps(bench_async_throughput(args.config)), flush=True)
        elif args.config in _HIER_ASYNC_SCALE:
            print(json.dumps(bench_hier_async(args.config)), flush=True)
        else:
            print(json.dumps(bench_config(args.config)), flush=True)
        return
    # Matrix mode re-execs one subprocess per config: each gets a clean
    # process (allocator stats aren't cumulative across configs, no
    # cross-config executable-cache contamination of HBM numbers).
    import subprocess
    import sys

    for name in (sorted(_SHAPES) + sorted(_STORE_SCALE)
                 + sorted(_LORA_SCALE) + sorted(_WEAK_SCALE)
                 + sorted(_ASYNC_SCALE) + sorted(_HIER_ASYNC_SCALE)):
        proc = subprocess.run(
            [sys.executable, __file__, "--config", name],
            capture_output=True, text=True,
        )
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        if proc.returncode != 0 or not line.startswith("{"):
            record = {"config": name, "error": proc.stderr[-500:]}
        else:
            record = dict(json.loads(line), config=name)
        print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
