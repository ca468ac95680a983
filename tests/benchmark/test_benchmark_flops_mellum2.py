"""flops/gqa_band_moe_lm.py against hand-computed totals of Mellum2's cut
(ISSUE 31), attention's pairs by layer kind, the new reader's arithmetic,
the new configuration's file against the catalog's config and the
program's named config, and the rehearsal presets of ``mellum2_silo_16k``
as processes: the stated reference agrees, its three controls (islands
lowered, triangle for band, one rope for two) and the system run in a
lower precision do not."""

import json
import os

import pytest

from bench_paths import BENCH_DIR, ROOT, bench_line, run_benchmark
from harness import catalog, flops

family = catalog.load_flops_family("gqa_band_moe_lm")
CONFIG = catalog.load_config("mellum2_12b_a2p5b_ep8")
ARGS = CONFIG["flops"]["args"]
T = 16384

# mellum2_12b_a2p5b_ep8 (MACs, forward, one sequence of T = 16,384):
#   q, o, k, v a layer   16384 * 2304 * (2*32 + 2*4) * 128    = 347,892,350,976
#   a sliding layer      1024*1025/2 + 15360*1024 = 16,253,440 pairs
#                        * 2 * 32 * 128                        = 133,148,180,480
#   the full layer       16384*16385/2 = 134,225,920 pairs * 8192
#                                                            = 1,099,578,736,640
#   router a layer       16384 * 2304 * 64                    =   2,415,919,104
#   held experts a layer 16384 * 8 * 8/64 = 16384 rows * 3*2304*896
#                                                              = 101,468,602,368
#   head                 16384 * 2304 * 12288                 = 463,856,467,968
PARTS = {
    "attention_window": 3 * 133_148_180_480,
    "attention_full": 1_099_578_736_640,
    "projections": 4 * 347_892_350_976,
    "experts": 4 * 101_468_602_368,
    "router": 4 * 2_415_919_104,
    "head": 463_856_467_968,
}
# parameters a layer: 21,233,664 (q, o, k, v) + 147,456 (router)
#   + 8 * 3*2304*896 = 49,545,216 (held experts) + 2*2304 + 2*128 (norms)
PER_LAYER = 21_233_664 + 147_456 + 49_545_216 + 4_864


@pytest.mark.parametrize("got,want", [
    (family.band_pairs(T, 1024), 16_253_440),
    (family.band_pairs(T, None), 134_225_920),
    (family.band_pairs(48, 20), 770),          # 210 + 28 x 20
    (family.band_pairs(16, 64), 136),          # a window wider than T
    (PER_LAYER, 70_931_200),
    (4 * PER_LAYER + 2 * 12288 * 2304 + 2304, 340_350_208),
    (family.parameters(**ARGS), 340_350_208),
    (flops.parameters(CONFIG["flops"]), CONFIG["model"]["parameters"]),
    (sum(PARTS.values()), 3_769_987_235_840),
    (family.forward_macs(**ARGS), 3_769_987_235_840),
    (flops.train_flops_per_example(CONFIG["flops"]),
     6 * 3_769_987_235_840),
])
def test_hand_computed_totals(got, want):
    assert got == want


def test_macs_by_part_and_their_shares():
    parts = family.macs_by_part(**ARGS)
    assert parts == PARTS
    share = {k: round(100 * v / sum(parts.values()), 1)
             for k, v in parts.items()}
    # ISSUE 31: the one full layer 29.2 %, the three sliding layers 10.6,
    # projections 36.9, experts 10.8, head 12.3, router 0.3
    assert share == {"attention_full": 29.2, "attention_window": 10.6,
                     "projections": 36.9, "experts": 10.8, "head": 12.3,
                     "router": 0.3}
    core = parts["attention_full"] + parts["attention_window"]
    assert round(100 * parts["attention_full"] / core) == 73
    assert round(100 * core / sum(parts.values())) == 40
    # a step: 22.6 TFLOP of needed work
    assert round(flops.train_flops_per_example(CONFIG["flops"]) / 1e12,
                 1) == 22.6


def test_the_layer_kinds_follow_the_period():
    assert family.layer_kinds(4, family.PERIOD) == {"sliding": 3, "full": 1}
    assert family.layer_kinds(28, family.PERIOD) == {"sliding": 21, "full": 7}
    assert family.layer_kinds(4, ("sliding", "full")) == {"sliding": 2,
                                                          "full": 2}
    # a masked dense kernel would run the window layers at the triangle's cost
    dense = dict(ARGS, sliding_window=T)
    assert (family.forward_macs(**dense) - family.forward_macs(**ARGS)
            == 3 * (134_225_920 - 16_253_440) * 8192)
    assert round(134_225_920 / 16_253_440, 1) == 8.3


class _Op:
    def __init__(self, scope, start, dur):
        self.scope, self.start, self.dur = scope, start, dur
        self.end, self.self_ns, self.parent = start + dur, dur, None


class _Reduce:
    @staticmethod
    def scope_of_op(op, scopes):
        return "round_local_train"


@pytest.mark.parametrize("metric,pairs,layers", [
    ("attn_window_mxu_pct", 16_253_440, 3),
    ("attn_full_mxu_pct", 134_225_920, 1),
])
def test_the_reader_counts_the_kept_pairs_of_its_kind_once(metric, pairs,
                                                           layers):
    read = catalog.load_reader("band_attn_mxu_pct")
    spec = catalog.load_layer_metric(metric)
    assert spec["reader"] == "band_attn_mxu_pct"
    assert spec["workloads"] == ["mellum2_silo_16k"]
    scope = spec["args"]["scopes"][0]

    class Dev:
        ops = [_Op(f"jit(round_fn)/round_local_train/local_grad/{scope}/call",
                   0, 300_000_000),
               # the other kind's time is not this kind's
               _Op("jit(round_fn)/round_local_train/local_grad/attn_proj/dot",
                   300_000_000, 500_000_000)]

    ctx = {"reduce": _Reduce, "windows": [(Dev, 0, 10**9, 1)], "fuse": 1,
           "scopes": ("round_local_train",), "bench_dir": BENCH_DIR,
           "config": CONFIG, "peaks": {"bf16_flops_per_s": 197e12},
           "counters": {"examples_per_round": 4.0}}
    macs = layers * pairs * 32 * 256
    got = read(ctx, **spec["args"])
    assert got == pytest.approx(100 * 6 * macs * 4 / 0.3 / 197e12)
    assert 0 < got < 105
    # the kernels at the MXU's whole rate over the tiles they visit, nine
    # products a pair where six count, cannot pass 100 %
    visited = {"attn_window_mxu_pct": 93, "attn_full_mxu_pct": 528}[metric]
    least_s = layers * visited * 512 * 512 * 32 * 256 * 2 * 9 * 4 / 197e12
    Dev.ops = [_Op(Dev.ops[0].scope, 0, int(least_s * 1e9))]
    assert read(ctx, **spec["args"]) < 67
    Dev.ops = [_Op("jit(round_fn)/round_local_train/local_grad/dot", 0, 10)]
    assert read(ctx, **spec["args"]) is None  # a program without the scope
    Dev.ops = [_Op(f"jit(round_fn)/round_local_train/local_grad/{scope}/call",
                   0, 10)]
    for other in ("keye_vl2_30b_a3b_ep8", "axk1_519b_ep16", "vit_b16_silo"):
        ctx_other = dict(ctx, config=catalog.load_config(other))
        assert read(ctx_other, **spec["args"]) is None  # no such part


@pytest.mark.parametrize("metric", [
    "attn_window_ms_round", "attn_full_ms_round", "attn_window_mxu_pct",
    "attn_full_mxu_pct", "attn_proj_ms_round", "moe_route_m2_ms_round",
    "moe_experts_m2_ms_round", "lm_head_m2_ms_round"])
def test_new_metrics_list_the_cell_and_read_nothing_without_their_scopes(
        metric):
    """On a program without the scope the reader returns None and does
    not raise: the recorded ResNet trace stands in for it."""
    import gzip
    import tempfile

    from harness import trace_reduce

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    assert len(entry) == 1 and entry[0]["workloads"] == ["mellum2_silo_16k"]
    assert entry[0]["moves"] == "rounds_per_s"
    fixtures = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fixtures")
    with gzip.open(os.path.join(
            fixtures, "chip1_dry_r18_fused.op_names.json.gz"), "rt") as f:
        op_names = json.load(f)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "t.xplane.pb")
        with gzip.open(os.path.join(
                fixtures, "chip1_dry_r18_fused.xplane.pb.gz"), "rb") as src, \
                open(path, "wb") as dst:
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


def test_config_file_holds_the_catalogs_config_and_names_its_cuts():
    assert CONFIG["reduced"] == ["layers", "experts_held", "vocab_size"]
    assert (CONFIG["layers"], CONFIG["experts_held"], CONFIG["vocab_size"]) \
        == (4, 8, 12288)
    published = CONFIG["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (28, 64, 98304)
    assert CONFIG["num_hidden_layers"] == 28 and CONFIG["num_experts"] == 64
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "8 x 4 stages of 7 layers" in CONFIG["deployment"]
    assert set(CONFIG["assumed"]) >= {"q_k_norm", "load_balancing",
                                      "mtp_head", "gates_gradient"}
    assert (CONFIG["yarn"]["low"], CONFIG["yarn"]["high"]) == (18, 35)
    m = CONFIG["model"]
    # no width differs from the source's keys
    assert (m["hidden"], m["heads"], m["kv_heads"], m["head_dim"]) == (
        CONFIG["hidden_size"], CONFIG["num_attention_heads"],
        CONFIG["num_key_value_heads"], CONFIG["head_dim"])
    assert (m["num_experts"], m["experts_per_token"], m["expert_width"]) == (
        CONFIG["num_experts"], CONFIG["num_experts_per_tok"],
        CONFIG["moe_intermediate_size"])
    assert m["sliding_window"] == CONFIG["sliding_window"] == 1024
    assert m["rms_eps"] == CONFIG["rms_norm_eps"]
    rope = CONFIG["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert sliding == {"rope_type": "default", "rope_theta": 500000}
    assert (m["rope_theta"], m["rope_factor"], m["rope_original"],
            m["rope_beta_fast"], m["rope_beta_slow"],
            m["rope_attention_factor"]) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"])
    # the period is the source's layer_types, one period of it
    kinds = {"sliding_attention": "sliding", "full_attention": "full"}
    assert [kinds[k] for k in CONFIG["layer_types"][:4]] == m["period"]
    assert CONFIG["layer_types"] == CONFIG["layer_types"][:4] * 7
    assert set(CONFIG["mlp_layer_types"]) == {"sparse"}
    policy = CONFIG["dtype_policy"]
    assert (policy["compute"], policy["local_params"],
            policy["master_params"]) == ("bfloat16", "bfloat16", "float32")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "mellum2_12b_a2p5b_ep8"][0]
    assert entry["reduced"] == CONFIG["reduced"]
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog_file):
        return
    with open(catalog_file) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert CONFIG["source"] == entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


@pytest.mark.parametrize("name", ["mellum2_12b_a2p5b_ep8", "dry_mellum2"])
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


def test_the_program_builds_what_the_file_states():
    import inspect

    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.models import model_registry

    cell = catalog.load_workload("mellum2_silo_16k")
    cfg = resolve_config(cell["named_config"],
                         catalog.experiment_overrides(cell, CONFIG, seed=1))
    defaults = {
        n: p.default for n, p in inspect.signature(
            model_registry.get("mellum2_decoder")).parameters.items()}
    built = {**defaults, **cfg.model.kwargs}
    model = CONFIG["model"]
    for key in set(model) - {"name", "parameters", "period"}:
        assert built[key] == model[key], key
    assert list(built["period"]) == model["period"]
    assert not cfg.model.lora.enabled and not cfg.dp.enabled
    assert (cfg.server.cohort_size, cfg.data.num_clients, cfg.data.name,
            cfg.client.optimizer, cfg.client.lr, cfg.client.weight_decay,
            cfg.client.batch_size, cfg.data.max_examples_per_client,
            cfg.server.optimizer, cfg.run.fuse_rounds,
            cfg.run.cohort_layout) == (
        2, 8, "synthetic_text", "adamw", 1e-4, 0.01, 1, 2, "mean", 1,
        "spatial")


@pytest.mark.parametrize("preset,verdict", [
    ("dry_mellum2_silo", True), ("dry_mellum2_islands", False),
    ("dry_mellum2_lowered", False)])
def test_dry_mellum2_against_its_reference_and_the_controls(preset, verdict,
                                                            tmp_path):
    """One round through ``Experiment.run_round`` (two periods, so the
    scan over periods is a loop) equals the plain reference's round in
    float32; a reference with its islands in bfloat16 and the system run
    in bfloat16 do not. (The wrong-mask and wrong-rope references move
    the logits by more than 1e-2 at this size:
    tests/test_mellum2_decoder.py; at the cell's size they run on the
    chip.)"""
    proc = run_benchmark(["--workload", preset, "--seed", "5", "--dry",
                          "--seconds", "1"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is verdict and line["failed"] == 0
    ref = bench_line(proc.stdout, "reference")
    assert ref["agrees"] is verdict
    cell = catalog.load_workload(preset)["reference"]
    # a control fails by at least one of the preset's limits
    assert (ref["loss_rel_errs"][0] <= cell["loss_rel_tols"][0]
            and ref["delta_rel_l2_err"] <= cell["state_rel_l2_tol"]) is verdict


def test_each_control_changes_one_thing_in_the_stated_reference(monkeypatch):
    """The three control modules load the stated reference and replace
    one name in it before they run its rounds: the island dtype, the
    window of a layer kind, the rope of a layer kind."""
    import jax.numpy as jnp

    stated = catalog.load_reference("fedavg_mellum2_lm")
    seen = {}
    monkeypatch.setattr(stated, "run_rounds",
                        lambda *a: seen.setdefault("args", a))
    monkeypatch.setattr(catalog, "load_reference",
                        lambda name, *a: stated)
    sizes = CONFIG["model"]
    pos = jnp.arange(8)
    plain = stated.rope_of("sliding", pos, sizes)
    assert stated.ISLAND == jnp.float32
    assert stated.window_of("sliding", sizes) == 1024
    assert stated.rope_of("full", pos, sizes)[1] == 1.2772588722239782
    for control, changed in (
            ("lowered", lambda: stated.ISLAND == jnp.bfloat16),
            ("triangle", lambda: stated.window_of("sliding", sizes) is None),
            ("one_rope", lambda: stated.rope_of("full", pos, sizes)[1] == 1.0
             and bool((stated.rope_of("full", pos, sizes)[0]
                       == plain[0]).all()))):
        module = catalog.load_module(
            "references", f"fedavg_mellum2_lm_{control}", ("run_rounds",))
        assert module.run_rounds("exp", "config", 3, 1) == (
            "exp", "config", 3, 1)
        assert changed(), control
    assert stated.window_of("full", sizes) is None


@pytest.mark.parametrize("control", ["lowered", "triangle", "one_rope"])
def test_a_cell_sized_control_differs_from_the_cell_in_its_reference_only(
        control):
    cell = catalog.load_workload("mellum2_silo_16k")
    twin = catalog.load_workload(f"mellum2_silo_16k_{control}")
    assert twin["reference"]["impl"] == f"fedavg_mellum2_lm_{control}"
    for key in ("loss_rel_tols", "state_rel_l2_tol", "rounds"):
        assert twin["reference"][key] == cell["reference"][key]
    for key in ("config", "named_config", "overrides", "loss_check",
                "warmup_dispatches", "chips"):
        assert twin[key] == cell[key]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {w["name"] for w in json.load(f)["workloads"]}
    assert "mellum2_silo_16k" in listed and twin["name"] not in listed
