"""Performance observatory (obs/roofline.py): the analytic per-phase
FLOP/HBM-byte cost model's unit semantics (fused-path byte saving,
aggregator costs, attack/ledger phases), the waterfall identity —
components sum to the headline/100% within the documented tolerance —
pinned across sharded↔sequential and fused↔unfused engines per
{weighted_mean, krum} × {bf16, f32} on the tier-1 CPU smoke, the
`colearn mfu` CLI (incl. clean errors on pre-observatory logs), and the
ops/pallas_apply.py cost annotation staying wired to the shared model."""

import json
import os

import pytest

from colearn_federated_learning_tpu import cli
from colearn_federated_learning_tpu.config import get_named_config
from colearn_federated_learning_tpu.obs.roofline import (
    PEAK_BF16_FLOPS,
    PEAK_F32_FLOPS,
    PEAK_HBM_BYTES_PER_SEC,
    SERVER_APPLY_PASSES_FUSED,
    SERVER_APPLY_PASSES_UNFUSED,
    WATERFALL_COMPONENTS,
    WATERFALL_TOL_PCT,
    analytic_step_flops,
    check_waterfall_identity,
    classify_phase,
    format_mfu_report,
    mfu_basis,
    mfu_report,
    phase_time_s,
    round_phase_costs,
    waterfall,
)

# ---------------------------------------------------------------------------
# unit: basis, cost model, roofline classification
# ---------------------------------------------------------------------------


def test_mfu_basis_follows_effective_compute_dtype():
    assert mfu_basis("float32", None, "float32") == (
        "f32_peak", PEAK_F32_FLOPS)
    assert mfu_basis("bfloat16", None, "float32") == (
        "bf16_peak", PEAK_BF16_FLOPS)
    # bf16 LOCAL params make the matmuls bf16 even under f32 compute cfg
    assert mfu_basis("float32", "bfloat16", "float32")[0] == "bf16_peak"
    assert PEAK_F32_FLOPS == PEAK_BF16_FLOPS / 2


def _costs(**over):
    base = dict(k=8, steps=16, batch=32, n_coords=10_000, compute_bytes=4,
                step_flops=analytic_step_flops(10_000, 32))
    base.update(over)
    return round_phase_costs(**base)


def test_cost_model_phase_presence_follows_config():
    c = _costs()
    assert set(c) == {"local_train", "aggregation", "server_apply"}
    c = _costs(attack=True, ledger=True)
    assert "attack_transform" in c and "ledger_stats" in c
    # local train scales with the padded grid: steps × K × step_flops
    assert c["local_train"]["flops"] == _costs()["local_train"]["flops"]
    assert (_costs(steps=32)["local_train"]["flops"]
            == 2 * _costs(steps=16)["local_train"]["flops"])


def test_cost_model_krum_dominates_weighted_mean():
    wm = _costs()["aggregation"]
    km = _costs(aggregator="krum")["aggregation"]
    # pairwise distances are O(K²·n) vs the mean's O(K·n)
    assert km["flops"] > wm["flops"] and km["bytes"] > wm["bytes"]


def test_cost_model_fused_apply_byte_saving_is_exact():
    """The Pallas fused path's whole point, in the byte model: the
    mean-delta intermediate (2 params-sized passes) disappears from
    aggregation and server_apply drops from 6 to 4 passes."""
    n = 10_000
    unfused, fused = _costs(), _costs(fused_apply=True)
    assert (unfused["aggregation"]["bytes"] - fused["aggregation"]["bytes"]
            == 2 * n * 4)
    assert (unfused["server_apply"]["bytes"] - fused["server_apply"]["bytes"]
            == (SERVER_APPLY_PASSES_UNFUSED - SERVER_APPLY_PASSES_FUSED)
            * n * 4)
    # FLOPs are invariant — fusion moves bytes, not arithmetic
    assert fused["aggregation"]["flops"] == unfused["aggregation"]["flops"]
    # median has no fused kernel: fused_apply must change nothing there
    assert (_costs(aggregator="median", fused_apply=True)
            == _costs(aggregator="median"))


def test_cost_model_reputation_adds_one_multiply_per_stack_coord():
    k, n = 8, 10_000
    assert (_costs(reputation=True)["aggregation"]["flops"]
            - _costs()["aggregation"]["flops"]) == k * n


def test_classify_and_time_against_roofline():
    peak, bw = PEAK_BF16_FLOPS, PEAK_HBM_BYTES_PER_SEC
    hot = {"flops": 10**12, "bytes": 10**6}   # intensity 1e6 ≫ ridge
    cold = {"flops": 10**6, "bytes": 10**9}   # intensity 1e-3 ≪ ridge
    assert classify_phase(hot, peak, bw) == "compute"
    assert classify_phase(cold, peak, bw) == "memory"
    assert phase_time_s(hot, peak, bw) == hot["flops"] / peak
    assert phase_time_s(cold, peak, bw) == cold["bytes"] / bw
    assert classify_phase({"flops": 5, "bytes": 0}, peak, bw) == "compute"


def test_pallas_apply_cost_annotation_stays_wired_to_the_model():
    """ops/pallas_apply.py's annotation delegates to the shared model —
    a drifted local copy would let the kernel and the phase_cost records
    disagree about what fusion saves."""
    from colearn_federated_learning_tpu.ops.pallas_apply import (
        delta_apply_cost,
        reduce_apply_cost,
    )

    k, n = 8, 10_000
    ra = reduce_apply_cost(k, n)
    fused = round_phase_costs(
        k=k, steps=1, batch=1, n_coords=n, compute_bytes=4, step_flops=0,
        fused_apply=True,
    )
    assert ra["flops"] == (fused["aggregation"]["flops"]
                           + fused["server_apply"]["flops"])
    assert ra["bytes"] == (fused["aggregation"]["bytes"]
                           + fused["server_apply"]["bytes"])
    da = delta_apply_cost(n)
    assert da["bytes"] == SERVER_APPLY_PASSES_FUSED * n * 4


# ---------------------------------------------------------------------------
# unit: waterfall identity
# ---------------------------------------------------------------------------


def test_waterfall_identity_on_synthetic_costs():
    costs = _costs(attack=True, ledger=True)
    wf = waterfall(costs, rounds_per_sec=3.4, peak_flops=PEAK_BF16_FLOPS,
                   padded_step_fraction=0.3,
                   host_exposed_ms_per_round=20.0)
    comp = wf["components"]
    total = sum(comp[c] for c in WATERFALL_COMPONENTS)
    assert abs(total - 100.0) < WATERFALL_TOL_PCT
    assert abs(comp["effective_compute"] + comp["padding"]
               - wf["headline_mfu_pct"]) < WATERFALL_TOL_PCT
    assert comp["padding"] == pytest.approx(0.3 * wf["headline_mfu_pct"])
    assert check_waterfall_identity(wf) == []


def test_waterfall_flags_over_accounting_instead_of_clamping():
    # host "measured" at 2× the wall: residual goes hard negative and
    # the identity check must SAY so, not hide it
    wf = waterfall(_costs(), rounds_per_sec=10.0,
                   peak_flops=PEAK_BF16_FLOPS,
                   host_exposed_ms_per_round=200.0)
    problems = check_waterfall_identity(wf)
    assert any("over-accounts" in p for p in problems)


def test_waterfall_rejects_nonpositive_throughput():
    with pytest.raises(ValueError):
        waterfall(_costs(), rounds_per_sec=0.0, peak_flops=PEAK_BF16_FLOPS)


# ---------------------------------------------------------------------------
# e2e: engine-parity pin + waterfall identity on the tier-1 CPU smoke
# ---------------------------------------------------------------------------


def _cfg(out, engine="sharded", fuse=1, **over):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.apply_overrides({
        "server.num_rounds": 4, "server.eval_every": 0,
        "server.checkpoint_every": 0,
        "data.num_clients": 8, "server.cohort_size": 4,
        "data.synthetic_train_size": 256, "data.synthetic_test_size": 64,
        "data.max_examples_per_client": 32, "client.batch_size": 16,
        "run.out_dir": str(out), "run.metrics_flush_every": 2,
        "run.engine": engine, "run.fuse_rounds": fuse,
        **over,
    })
    return cfg.validate()


def _fit_records(cfg):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    Experiment(cfg, echo=False).fit()
    path = os.path.join(cfg.run.out_dir, f"{cfg.name}.metrics.jsonl")
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()], path


def _phase_cost_rounds(records):
    return {
        r["round"]: r["phases"]
        for r in records if r.get("event") == "phase_cost"
    }


_MATRIX = [
    ("weighted_mean", "float32"),
    ("weighted_mean", "bfloat16"),
    ("krum", "float32"),
    ("krum", "bfloat16"),
]


@pytest.mark.parametrize("aggregator,dtype", _MATRIX)
def test_phase_cost_parity_and_waterfall_identity(tmp_path, aggregator,
                                                  dtype):
    """The acceptance pin: the analytic per-phase FLOP/byte model is
    IDENTICAL across sharded↔sequential and fused↔unfused engines
    (same discipline as the wire counters — the model is a pure
    function of config + grid, so any drift is a bug), and each run's
    waterfall satisfies the documented identity: components sum to
    100% of wall within WATERFALL_TOL_PCT with effective + padding
    reconstructing the headline."""
    over = {"server.aggregator": aggregator, "run.compute_dtype": dtype}
    recs_sh, path_sh = _fit_records(_cfg(tmp_path / "sh", "sharded", **over))
    recs_sq, _ = _fit_records(_cfg(tmp_path / "sq", "sequential", **over))
    recs_fu, _ = _fit_records(
        _cfg(tmp_path / "fu", "sharded", fuse=2, **over)
    )
    pc_sh, pc_sq, pc_fu = (
        _phase_cost_rounds(r) for r in (recs_sh, recs_sq, recs_fu)
    )
    assert pc_sh and set(pc_sh) == {1, 2, 3, 4}
    assert pc_sh == pc_sq == pc_fu  # engine/fusion parity, exact
    # the static model halves agree too (incl. the dtype-aware basis)
    model = {}
    for recs in (recs_sh, recs_sq, recs_fu):
        m = next(r for r in recs if r.get("event") == "phase_cost_model")
        cur = {k: m[k] for k in ("step_flops", "n_coords", "mfu_basis",
                                 "peak_flops", "compute_bytes")}
        assert not model or cur == model
        model = cur
    assert model["mfu_basis"] == (
        "bf16_peak" if dtype == "bfloat16" else "f32_peak"
    )
    assert model["compute_bytes"] == (2 if dtype == "bfloat16" else 4)
    # krum's pairwise-distance phase must be visible in the record
    agg_flops = pc_sh[1]["aggregation"]["flops"]
    if aggregator == "krum":
        assert agg_flops > 2 * 4 * model["n_coords"]
    # waterfall identity per engine, from the logged records alone
    for recs in (recs_sh, recs_sq, recs_fu):
        report = mfu_report(recs)
        assert report["identity_violations"] == [], report["waterfall"]
        comp = report["waterfall"]["components"]
        total = sum(comp[c] for c in WATERFALL_COMPONENTS)
        assert abs(total - 100.0) < WATERFALL_TOL_PCT
    # and the CLI renders it
    assert cli.main(["mfu", path_sh]) == 0


def test_mfu_report_includes_attack_and_ledger_phases(tmp_path):
    recs, _ = _fit_records(_cfg(
        tmp_path / "atk",
        **{"server.aggregator": "krum", "attack.kind": "sign_flip",
           "attack.fraction": 0.25, "run.obs.client_ledger.enabled": True},
    ))
    pc = _phase_cost_rounds(recs)
    assert set(pc[1]) == {"local_train", "attack_transform", "aggregation",
                          "server_apply", "ledger_stats"}
    report = mfu_report(recs)
    assert set(report["roofline"]) == set(pc[1])
    assert report["identity_violations"] == []
    text = format_mfu_report(report)
    assert "attack_transform" in text and "ledger_stats" in text


def test_phase_cost_off_knob_and_clean_cli_error(tmp_path):
    cfg = _cfg(tmp_path / "off", **{"run.obs.phase_cost": False})
    recs, path = _fit_records(cfg)
    assert not any(r.get("event") == "phase_cost" for r in recs)
    with pytest.raises(ValueError, match="phase_cost"):
        mfu_report(recs)
    assert cli.main(["mfu", path]) == 2  # clean error, not a traceback


def test_phase_cost_flops_validation():
    cfg = get_named_config("mnist_fedavg_2")
    cfg.run.obs.phase_cost_flops = "magic"
    with pytest.raises(ValueError, match="phase_cost_flops"):
        cfg.validate()


def test_xla_flop_source_falls_back_or_counts(tmp_path):
    """`run.obs.phase_cost_flops=xla` uses the backend cost model when
    it exists and falls back to the analytic count otherwise — either
    way the record says which, and the run completes."""
    recs, _ = _fit_records(_cfg(
        tmp_path / "xla", **{"run.obs.phase_cost_flops": "xla"}
    ))
    m = next(r for r in recs if r.get("event") == "phase_cost_model")
    assert m["flop_source"] in ("xla", "analytic")
    assert m["step_flops"] > 0


# ---------------------------------------------------------------------------
# cohort-layout GEMM geometry + adapter-aware LoRA step FLOPs (r12)
# ---------------------------------------------------------------------------


def test_cohort_layout_gemm_geometry_units():
    from colearn_federated_learning_tpu.obs.roofline import (
        MXU_TILE_ROWS,
        layout_gemm_rows,
        mxu_tile_pad_fraction,
    )

    assert MXU_TILE_ROWS == 128
    # spatial: per-GEMM rows are ONE client's batch — batched dot dims
    # do not merge into M, which is exactly why the layout is the lever
    assert layout_gemm_rows("spatial", 16, 32) == 32
    assert layout_gemm_rows("megabatch", 16, 32) == 512
    with pytest.raises(ValueError, match="cohort_layout"):
        layout_gemm_rows("ring", 4, 32)
    assert mxu_tile_pad_fraction(128) == 0.0
    assert mxu_tile_pad_fraction(512) == 0.0
    assert mxu_tile_pad_fraction(32) == 0.75
    assert mxu_tile_pad_fraction(130) == pytest.approx(1.0 - 130.0 / 256.0)
    with pytest.raises(ValueError, match="gemm_rows"):
        mxu_tile_pad_fraction(0)


def test_lora_step_flops_model():
    from colearn_federated_learning_tpu.obs.roofline import (
        analytic_lora_step_flops,
    )

    # frozen-base fwd + activation-gradient bwd (4·P_full·B) + factor
    # weight-gradients (2·P_adapter·B)
    assert analytic_lora_step_flops(100, 10, 32) == (4 * 100 + 2 * 10) * 32
    # strictly between full training and the naive adapter-only count
    assert (analytic_lora_step_flops(100, 10, 32)
            < analytic_step_flops(100, 32))
    assert (analytic_lora_step_flops(100, 10, 32)
            > analytic_step_flops(10, 32))


def test_megabatch_smoke_roofline_padding_drop(tmp_path):
    """Tier-1 CPU megabatch smoke (ISSUE 12 acceptance): the layout's
    phase_cost_model attribution — gemm_rows grows by K_local and the
    MXU row-tile padding fraction DROPS vs the spatial twin — while
    the two layouts train the same federation (per-round losses agree;
    the bitwise params pin lives in tests/test_round_engine.py)."""
    import numpy as _np

    over = {"run.num_lanes": 1}  # K_local = the whole cohort of 4
    recs_sp, _ = _fit_records(_cfg(tmp_path / "sp", **over))
    recs_mb, path_mb = _fit_records(_cfg(
        tmp_path / "mb", **{**over, "run.cohort_layout": "megabatch"}
    ))
    m_sp = next(r for r in recs_sp if r.get("event") == "phase_cost_model")
    m_mb = next(r for r in recs_mb if r.get("event") == "phase_cost_model")
    assert m_sp["cohort_layout"] == "spatial"
    assert m_mb["cohort_layout"] == "megabatch"
    assert m_sp["n_coords_full"] == m_sp["n_coords"]  # no lora here
    assert m_mb["clients_per_lane"] == 4
    assert m_mb["gemm_rows"] == 4 * m_sp["gemm_rows"]
    # THE smoke assertion: megabatch reclaims MXU row-tile padding
    assert (m_mb["mxu_tile_pad_fraction"]
            < m_sp["mxu_tile_pad_fraction"])
    # lenet5 (windowed kernels 4 % of it) keeps the block trainer's
    # shared-weight phase; the spatial layout has none
    assert m_sp["windowed_conv_share"] == m_mb["windowed_conv_share"] < 0.5
    assert (m_sp["shared_weight_phase"], m_mb["shared_weight_phase"]) == (
        False, True)
    # batch 16 spatial → 1 - 16/128; megabatch 64 rows → 1 - 64/128
    assert m_sp["mxu_tile_pad_fraction"] == pytest.approx(0.875)
    assert m_mb["mxu_tile_pad_fraction"] == pytest.approx(0.5)
    # same federation, same trajectory: per-round losses agree
    loss_sp = [r["train_loss"] for r in recs_sp
               if r.get("event") is None and "train_loss" in r]
    loss_mb = [r["train_loss"] for r in recs_mb
               if r.get("event") is None and "train_loss" in r]
    assert loss_sp and len(loss_sp) == len(loss_mb)
    _np.testing.assert_allclose(loss_sp, loss_mb, rtol=1e-5)
    # per-phase analytic costs are layout-INVARIANT (same math, new
    # shapes) — the attribution lives in the model record, not the costs
    assert _phase_cost_rounds(recs_sp) == _phase_cost_rounds(recs_mb)
    # `colearn mfu` surfaces the layout line
    report = mfu_report(recs_mb)
    assert report["layout"]["cohort_layout"] == "megabatch"
    assert report["layout"]["gemm_rows"] == 64
    text = format_mfu_report(report)
    assert "megabatch" in text and "gemm rows" in text
    assert cli.main(["mfu", path_mb]) == 0


def test_lora_phase_cost_model_counts_adapter_step(tmp_path):
    """Under model.lora the analytic local_train step cost follows the
    frozen-base structure — 4·P_full·B + 2·P_adapter·B — instead of
    either the full-model 6·P_full·B or the adapter-only 6·P_adapter·B
    (ROADMAP item 3 follow-up, ISSUE 12 satellite)."""
    from colearn_federated_learning_tpu.obs.roofline import (
        analytic_lora_step_flops,
    )
    from colearn_federated_learning_tpu.server.round_driver import (
        Experiment,
    )

    cfg = get_named_config("bert_lora_federated")
    cfg.apply_overrides({
        "data.num_clients": 8, "server.cohort_size": 4,
        "model.kwargs.seq_len": 16,
        "server.num_rounds": 2, "server.eval_every": 0,
        "server.checkpoint_every": 0,
        "data.synthetic_train_size": 256, "data.synthetic_test_size": 64,
        "data.max_examples_per_client": 32, "client.batch_size": 8,
        "run.out_dir": str(tmp_path), "run.metrics_flush_every": 1,
        "run.compute_dtype": "float32", "run.local_param_dtype": "",
        "run.cohort_layout": "spatial",
    })
    cfg.validate()
    Experiment(cfg, echo=False).fit()
    path = os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl")
    with open(path) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    m = next(r for r in recs if r.get("event") == "phase_cost_model")
    assert m["flop_source"] == "analytic_lora"
    assert m["n_coords_full"] > m["n_coords"]  # adapters ≪ full model
    units = 8 * 16  # batch × seq_len (token corpora count tokens)
    assert m["step_flops"] == analytic_lora_step_flops(
        m["n_coords_full"], m["n_coords"], units
    )
