"""flops/mla_moe_lm.py against hand-computed totals of A.X-K1's cut
(ISSUE 29), the 2/3 rule, the new reader, and the new configuration's
file against the catalog's config and the program's named config."""

import json
import os

import pytest

from bench_paths import BENCH_DIR, ROOT, bench_line, run_benchmark
from harness import catalog, flops

family = catalog.load_flops_family("mla_moe_lm")
CONFIG = catalog.load_config("axk1_519b_ep16")
ARGS = CONFIG["flops"]["args"]
T = 4096


def test_parameters_a_layer_by_hand():
    shapes = family.projection_shapes()
    mla = {n: i * o for n, (i, o) in shapes.items()}
    assert mla == {"wqa": 11_010_048, "wqb": 18_874_368, "wkva": 4_128_768,
                   "wkvb": 8_388_608, "wo": 58_720_256}
    assert sum(mla.values()) == 101_122_048
    assert 3 * 7168 * 18432 == 396_361_728       # the dense layer's MLP
    assert 3 * 7168 * 2048 == 44_040_192         # one expert, shared or routed
    assert 7168 * 192 == 1_376_256               # the router
    norms = 2 * 7168 + 1536 + 512
    assert norms == 16_384
    dense_layer = 101_122_048 + norms + 396_361_728
    expert_layer = 101_122_048 + norms + 1_376_256 + 13 * 44_040_192
    frozen = dense_layer + 4 * expert_layer + 2 * 20480 * 7168 + 7168
    assert frozen == 3_491_257_344
    adapters = sum(16 * (i + o) for i, o in shapes.values())
    assert adapters == 1_000_448
    assert family.parameters(**ARGS) == {"frozen": 3_491_257_344,
                                         "trained": 5_002_240}
    assert flops.parameters(CONFIG["flops"]) == CONFIG["model"]["parameters"]


def test_macs_a_token_by_part_at_4096_tokens():
    kinds = family.macs_by_part(**ARGS)
    per_token = {k: v / T for k, v in kinds["parts"].items()}
    assert per_token == {
        "mla_proj": 5 * 101_122_048, "adapters": 5 * 1_000_448,
        # causal pairs a token x 64 heads x (192 + 128)
        "mla_attn": 5 * (T + 1) / 2 * 64 * 320,
        "dense_mlp": 396_361_728, "moe_route": 4 * 1_376_256,
        "moe_shared": 4 * 44_040_192,
        # 8 x 12 / 192 = half an assignment a token falls on a held expert
        "moe_experts": 4 * 44_040_192 / 2,
        "lm_head": 7168 * 20480,
    }
    assert 5 * (T + 1) / 2 * 64 * 320 / 5 == 41_953_280  # a layer
    assert kinds["trained"] == kinds["parts"]["adapters"]
    assert kinds["attention"] == kinds["parts"]["mla_attn"]
    assert (kinds["frozen"] + kinds["trained"] + kinds["attention"]
            == sum(kinds["parts"].values()))
    # ISSUE 29's 1,528 M a token: everything but the adapters' side products
    assert round((kinds["frozen"] + kinds["attention"]) / T / 1e6) == 1528
    shares = {k: v / (kinds["frozen"] + kinds["attention"])
              for k, v in kinds["parts"].items()}
    assert round(100 * (shares["mla_proj"] + shares["mla_attn"]), 1) == 46.8
    assert round(100 * shares["dense_mlp"], 1) == 25.9
    assert round(100 * (shares["moe_shared"] + shares["moe_experts"]), 1) == 17.3
    assert round(100 * shares["lm_head"], 1) == 9.6


def test_forward_macs_counts_a_frozen_product_at_two_thirds():
    kinds = family.macs_by_part(**ARGS)
    macs = family.forward_macs(**ARGS)
    assert macs == (kinds["trained"] + kinds["attention"]
                    + 2 * kinds["frozen"] // 3)
    # what the generic mfu_pct multiplies by 6: 6 FLOPs a trained or
    # weightless MAC, 4 a frozen one (to the rounding of one division)
    step = flops.train_flops_per_example(CONFIG["flops"])
    honest = (6 * (kinds["trained"] + kinds["attention"])
              + 4 * kinds["frozen"])
    assert 0 <= honest - step < 6
    assert round(step / 1e12, 1) == 26.9
    # a cut with nothing frozen would count as any trained model does
    assert family.forward_macs(**dict(ARGS, lora_rank=0)) < macs


def test_the_reader_counts_the_causal_pairs_once_and_returns_none_without():
    read = catalog.load_reader("mla_attn_mxu_pct")
    spec = catalog.load_layer_metric("mla_attn_mxu_pct")
    assert spec["workloads"] == ["axk1_silo_lora_4k"]

    class Op:
        def __init__(self, scope, start, dur):
            self.scope, self.start, self.dur = scope, start, dur
            self.end, self.self_ns, self.parent = start + dur, dur, None

    class Dev:
        ops = [Op("jit(round_fn)/round_local_train/local_grad/mla_attn/dot",
                  0, 400_000_000)]

    class Reduce:
        @staticmethod
        def scope_of_op(op, scopes):
            return "round_local_train"

    ctx = {"reduce": Reduce, "windows": [(Dev, 0, 10**9, 1)], "fuse": 1,
           "scopes": ("round_local_train",), "bench_dir": BENCH_DIR,
           "config": CONFIG, "peaks": {"bf16_flops_per_s": 197e12},
           "counters": {"examples_per_round": 4.0}}
    macs = 5 * (T * (T + 1) // 2) * 64 * 320
    assert read(ctx, **spec["args"]) == pytest.approx(
        100 * 6 * macs * 4 / 0.4 / 197e12)
    Dev.ops = [Op("jit(round_fn)/round_local_train/local_grad/dot", 0, 10)]
    assert read(ctx, **spec["args"]) is None  # a program without the scope
    keye = dict(ctx, config=catalog.load_config("keye_vl2_30b_a3b_ep8"))
    Dev.ops = [Op("jit(round_fn)/round_local_train/local_grad/mla_attn/dot",
                  0, 10)]
    assert read(keye, **spec["args"]) is None  # a family without the count


def test_config_file_holds_the_catalogs_config_and_names_its_cuts():
    source = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840,
    }
    differs = {k for k, v in source.items() if CONFIG.get(k) != v}
    assert differs == {"vocab_size"} and CONFIG["vocab_size"] == 20480
    assert CONFIG["reduced"] == ["layers", "experts_held", "vocab_size"]
    assert (CONFIG["layers"], CONFIG["experts_held"]) == (5, 12)
    assert CONFIG["published"]["vocab_size"] == 163840
    assert set(CONFIG["assumed"]) >= {"topk_method", "rope_pairing", "yarn",
                                      "load_balancing", "gates_gradient"}
    assert "16 chips share each layer" in CONFIG["deployment"]
    model = CONFIG["model"]
    for key, name in (("hidden_size", "hidden"), ("num_attention_heads", "heads"),
                      ("q_lora_rank", "q_rank"), ("kv_lora_rank", "kv_rank"),
                      ("qk_nope_head_dim", "qk_nope"),
                      ("qk_rope_head_dim", "qk_rope"), ("v_head_dim", "v_dim"),
                      ("intermediate_size", "dense_width"),
                      ("n_routed_experts", "num_experts"),
                      ("num_experts_per_tok", "experts_per_token"),
                      ("moe_intermediate_size", "expert_width"),
                      ("n_group", "n_group"), ("topk_group", "topk_group"),
                      ("routed_scaling_factor", "gate_scale"),
                      ("rope_theta", "rope_theta")):
        assert model[name] == source[key], name
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "axk1_519b_ep16"][0]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_program_builds_what_the_file_states():
    from colearn_federated_learning_tpu.config import resolve_config

    cell = catalog.load_workload("axk1_silo_lora_4k")
    cfg = resolve_config(cell["named_config"],
                         catalog.experiment_overrides(cell, CONFIG, seed=1))
    import inspect

    from colearn_federated_learning_tpu.models import model_registry

    defaults = {
        n: p.default for n, p in inspect.signature(
            model_registry.get("axk1_decoder")).parameters.items()}
    built = {**defaults, **cfg.model.kwargs}
    model = CONFIG["model"]
    for key in set(model) - {"name", "parameters", "lora_rank", "lora_alpha"}:
        assert built[key] == model[key], key
    assert cfg.model.lora.enabled
    assert (cfg.model.lora.rank, cfg.model.lora.alpha) == (
        model["lora_rank"], model["lora_alpha"])
    assert (cfg.server.cohort_size, cfg.data.num_clients,
            cfg.client.optimizer, cfg.client.lr, cfg.client.weight_decay,
            cfg.client.batch_size, cfg.data.max_examples_per_client) == (
        2, 8, "adamw", 1e-4, 0.01, 1, 2)


@pytest.mark.parametrize("preset,verdict", [("dry_axk1_silo", True),
                                            ("dry_axk1_islands", False)])
def test_dry_axk1_against_its_reference_and_the_lowered_control(
        preset, verdict, tmp_path):
    proc = run_benchmark(["--workload", preset, "--seed", "5", "--dry",
                          "--seconds", "1"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is verdict and line["failed"] == 0
    ref = bench_line(proc.stdout, "reference")
    assert ref["agrees"] is verdict
    cell = catalog.load_workload(preset)["reference"]
    # the control fails by at least one of the preset's limits
    assert (ref["loss_rel_errs"][0] <= cell["loss_rel_tols"][0]
            and ref["delta_rel_l2_err"] <= cell["state_rel_l2_tol"]) is verdict


def test_the_device_plans_reference_agrees_where_the_hosts_cannot(tmp_path):
    """dry_r18_device (run.control_plane=device): references/
    fedavg_device_plan.py reads the schedule the round program derives."""
    proc = run_benchmark(["--workload", "dry_r18_device", "--seed", "5",
                          "--dry", "--seconds", "1"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["correct"] is True
