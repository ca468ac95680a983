"""The Keye decoder's FLOP family against totals computed by hand, its
configuration file against the program's model, the expert layer's
reader, and the rehearsal presets of ``keye_silo_8k`` as processes."""

import json
import os

import pytest

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from bench_paths import BENCH_DIR, bench_line, run_benchmark
from harness import catalog, flops

# keye_vl2_30b_a3b_ep8 (MACs, forward, one sequence of T = 8,192), per layer:
#   q, o, k, v      8192 * 2048 * (2*32 + 2*4) * 128          = 154,618,822,656
#   indexer         8192 * 2048 * (16*64 + 64 + 16)           =  18,522,046,464
#   index scores    (8192*8193/2 = 33,558,528 causal pairs) * 16*64
#                                                             =  34,363,932,672
#   selected pairs  2048*2049/2 + 6144*2048 = 14,681,088;  * 2 * 32*128
#                                                             = 120,267,472,896
#   router          8192 * 2048 * 128                         =   2,147,483,648
#   held experts    8192 * 8 * 16/128 = 8192 rows * 3*2048*768
#                                                             =  38,654,705,664
PER_LAYER = (154_618_822_656 + 18_522_046_464 + 34_363_932_672
             + 120_267_472_896 + 2_147_483_648 + 38_654_705_664)
#   head            8192 * 2048 * 18992                       = 318,632,886,272
KEYE_MACS = 4 * PER_LAYER + 318_632_886_272
# parameters per layer: 18,874,368 (q, o, k, v) + 2,260,992 (indexer)
#   + 262,144 (router) + 16*3*2048*768 = 75,497,472 (held experts)
#   + 2*2048 + 2*128 + 2*64 = 4,480 (norms)                   =  96,899,456
KEYE_PARAMS = 4 * 96_899_456 + 2 * 18992 * 2048 + 2048

CONFIG = catalog.load_config("keye_vl2_30b_a3b_ep8")
KEYE = CONFIG["flops"]
FAMILY = catalog.load_flops_family("keye_moe_lm")


@pytest.mark.parametrize("got,want", [
    (PER_LAYER, 368_574_464_000),
    (KEYE_MACS, 1_792_930_742_272),
    (flops.forward_macs(KEYE), 1_792_930_742_272),
    (flops.forward_macs(KEYE) // 8192, 218_863_616),  # per token
    (flops.parameters(KEYE), 465_391_104),
    (KEYE_PARAMS, 465_391_104),
    (flops.train_flops_per_example(KEYE), 6 * 1_792_930_742_272),
    (FAMILY.expert_macs(**KEYE["args"]), 38_654_705_664),
    (FAMILY.selected_pairs(8192, 2048), 14_681_088),
    (FAMILY.selected_pairs(32, 8), 228),    # 36 + 24 * 8
    (FAMILY.selected_pairs(16, 64), 136),   # never selects: all causal pairs
])
def test_hand_computed_totals(got, want):
    assert got == want


def test_selected_work_is_below_dense_masked_work():
    """What the program computes (every causal pair, masked) is not what
    counts: attention reads the selected pairs only."""
    args = KEYE["args"]
    dense = dict(args, index_topk=args["seq_len"])
    assert FAMILY.forward_macs(**dense) - FAMILY.forward_macs(**args) == \
        4 * 2 * (33_558_528 - 14_681_088) * 32 * 128


@pytest.mark.parametrize("name", ["keye_vl2_30b_a3b_ep8", "dry_keye"])
def test_the_family_counts_the_programs_own_model(name):
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.models import build_model

    config = catalog.load_config(name)
    sizes = {k: v for k, v in config["model"].items()
             if k not in ("name", "parameters")}
    model = build_model(config["model"]["name"], 0, **sizes)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, sizes["seq_len"]), jnp.int32))["params"])
    n = sum(int(s.size) for s in jax.tree.leaves(shapes))
    assert n == flops.parameters(config["flops"])
    if "parameters" in config["model"]:
        assert n == config["model"]["parameters"]


def test_configuration_file_states_the_cut_and_the_source():
    assert CONFIG["reduced"] == ["layers", "experts_held", "vocab_size"]
    assert (CONFIG["layers"], CONFIG["experts_held"], CONFIG["vocab_size"]) \
        == (4, 16, 18992)
    assert CONFIG["published"]["num_hidden_layers"] == 48 == \
        CONFIG["num_hidden_layers"]
    assert CONFIG["published"]["num_experts"] == 128 == CONFIG["num_experts"]
    assert CONFIG["published"]["vocab_size"] == 151936
    assert "8 chips share each layer" in CONFIG["deployment"]
    m = CONFIG["model"]
    # no width differs from the source's keys
    assert (m["hidden"], m["heads"], m["kv_heads"], m["head_dim"]) == (
        CONFIG["hidden_size"], CONFIG["num_attention_heads"],
        CONFIG["num_key_value_heads"], CONFIG["head_dim"])
    assert (m["num_experts"], m["experts_per_token"], m["expert_width"]) == (
        CONFIG["num_experts"], CONFIG["num_experts_per_tok"],
        CONFIG["moe_intermediate_size"])
    sa = CONFIG["sa_config"]
    assert (m["index_heads"], m["index_head_dim"], m["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert m["rope_theta"] == CONFIG["rope_theta"]
    assert m["mrope_section"] == CONFIG["rope_scaling"]["mrope_section"]
    assert m["rms_eps"] == CONFIG["rms_norm_eps"]
    for item in ("q_k_norm", "indexer", "indexer_loss", "load_balancing"):
        assert CONFIG["assumed"][item]
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog_file):
        return
    with open(catalog_file) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_moe_experts_mxu_pct_reader(monkeypatch):
    read = catalog.load_reader("moe_experts_mxu_pct")
    calls = []

    def fake_loader(name, bench_dir=BENCH_DIR):
        """Stands in for ``inner_scope_ms_round``: 20 ms per round."""
        calls.append(name)
        return lambda ctx, scopes: 20.0 if scopes == ["moe_experts"] else None

    monkeypatch.setattr(catalog, "load_reader", fake_loader)
    ctx = {"bench_dir": BENCH_DIR, "config": CONFIG,
           "counters": {"examples_per_round": 4.0},
           "peaks": {"bf16_flops_per_s": 197e12}}
    got = read(ctx, scopes=["moe_experts"])
    assert calls == ["inner_scope_ms_round"]
    assert got == pytest.approx(
        100 * 6 * 38_654_705_664 * 4 / 0.020 / 197e12)
    assert 0 < got < 100
    assert read(ctx, scopes=["nothing"]) is None
    # a configuration whose family counts no experts reports nothing
    vit = dict(ctx, config=catalog.load_config("vit_b16_silo"))
    assert read(vit, scopes=["moe_experts"]) is None


@pytest.mark.parametrize("metric", [
    "attn_indexer_ms_round", "attn_select_ms_round", "attn_sparse_ms_round",
    "moe_route_ms_round", "moe_experts_ms_round", "lm_head_ms_round",
    "moe_experts_mxu_pct"])
def test_new_metrics_read_nothing_from_a_trace_without_their_scopes(metric):
    """On the parent's program (no such scope) the reader returns None
    and does not raise: the recorded ResNet trace stands in for it."""
    import gzip

    from harness import trace_reduce

    fixtures = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fixtures")
    with gzip.open(os.path.join(
            fixtures, "chip1_dry_r18_fused.op_names.json.gz"), "rt") as f:
        op_names = json.load(f)
    raw = os.path.join(fixtures, "chip1_dry_r18_fused.xplane.pb.gz")
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "t.xplane.pb")
        with gzip.open(raw, "rb") as src, open(path, "wb") as dst:
            dst.write(src.read())
        trace = trace_reduce.load(path, op_names)
    windows = trace_reduce.steady_windows(trace, "jit_round_fn")
    spec = catalog.load_layer_metric(metric)
    ctx = {"bench_dir": BENCH_DIR, "config": CONFIG, "reduce": trace_reduce,
           "windows": windows, "trace": trace, "fuse": 2,
           "scopes": ("round_local_train", "round_aggregate",
                      "round_server_apply", "round_fused_reduce_apply",
                      "round_control_plane", "round_attack_transform",
                      "round_client_ledger"),
           "counters": {"examples_per_round": 4.0},
           "peaks": {"bf16_flops_per_s": 197e12}}
    assert windows
    assert catalog.load_reader(spec["reader"])(ctx, **spec["args"]) is None


def test_dry_keye_agrees_with_its_reference_round(tmp_path):
    """One round through ``Experiment.run_round`` (the normal path: the
    trainer, the round engine's scan over the cohort, aggregation, the
    server step) equals the plain reference's round, in float32."""
    proc = run_benchmark(["--workload", "dry_keye_silo", "--seed", "5",
                          "--seconds", "1", "--dry"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    ref = bench_line(proc.stdout, "reference")
    assert ref["agrees"]
    assert ref["loss_rel_errs"][0] < 1e-6 and ref["delta_rel_l2_err"] < 1e-3


def test_dry_keye_in_a_lower_precision_than_stated_is_not_correct(tmp_path):
    proc = run_benchmark(["--workload", "dry_keye_lowered", "--seed", "5",
                          "--seconds", "1", "--dry"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    ref = bench_line(proc.stdout, "reference")
    assert not ref["agrees"]
    # by at least one of the preset's limits (here both)
    assert ref["loss_rel_errs"][0] > 1e-6 or ref["delta_rel_l2_err"] > 1e-3


def test_dry_keye_against_a_reference_with_lowered_islands_is_not_correct(tmp_path):
    """The control of ``keye_silo_8k_lowered`` at a size the CPU runs: the
    reference's router softmax, index scores, selection, attention
    softmax and logits in bfloat16, the losses' own arithmetic float32."""
    proc = run_benchmark(["--workload", "dry_keye_islands", "--seed", "5",
                          "--seconds", "1", "--dry"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    ref = bench_line(proc.stdout, "reference")
    assert not ref["agrees"]
    assert ref["loss_rel_errs"][0] > 1e-6 or ref["delta_rel_l2_err"] > 1e-3
    # the cell-sized control differs from the cell in its reference only
    cell = catalog.load_workload("keye_silo_8k")
    control = catalog.load_workload("keye_silo_8k_lowered")
    assert control["reference"]["impl"] == "fedavg_keye_lm_lowered"
    for key in ("loss_rel_tols", "state_rel_l2_tol", "rounds"):
        assert control["reference"][key] == cell["reference"][key]
    for key in ("config", "named_config", "overrides", "loss_check",
                "warmup_dispatches"):
        assert control[key] == cell[key]
