"""BENCHMARK.json and every data file it names, against the contract's
limits; and that a new cell, configuration and per-layer metric need new
files and appended entries only."""

import glob
import hashlib
import json
import os
import re
import shutil

import pytest

from bench_paths import BENCH_DIR, BENCHMARK_JSON, ROOT
from harness import catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(BENCHMARK_JSON) as _f:
    BENCH = json.load(_f)


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(BENCHMARK_JSON) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert len(BENCH["command"]) <= 32 and all(map(_one_line, BENCH["command"]))
    # the command names a file under paths and nothing outside them
    assert any(BENCH["command"][1].startswith(p + "/") for p in BENCH["paths"])
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for section in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert _one_line(entry["source"]) and _one_line(entry["why"])
    assert len(entry["reduced"]) <= 16 and all(NAME.match(k) for k in entry["reduced"])
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    cfg = catalog.load_config(entry["name"])
    assert os.path.samefile(os.path.join(ROOT, entry["file"]),
                            os.path.join(BENCH_DIR, "configs", entry["name"] + ".json"))
    assert cfg["reduced"] == entry["reduced"]
    # the sizes the file states are the sizes the FLOP count is made from
    from harness import flops

    assert flops.parameters(cfg["flops"]) == cfg["model"]["parameters"]
    for key, value in cfg["flops"]["args"].items():
        if key in cfg["model"]:
            assert cfg["model"][key] == value, key


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_workload_entry_and_file(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(entry[k]) for k in ("name", "config", "traffic"))
    assert entry["chips"] in (1, 4) and _one_line(entry["why"])
    assert entry["config"] in {c["name"] for c in BENCH["configs"]}
    cell = catalog.load_workload(entry["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == entry[key], key
    band = cell["loss_check"]["band"]
    assert band is not None and band[0] < band[1], "loss band not measured"
    assert cell["loss_check"]["reason"]
    ref = cell["reference"]
    assert callable(catalog.load_reference(ref["impl"]).run_rounds)
    # one tolerance per compared round, none left unmeasured
    assert len(ref["loss_rel_tols"]) == ref["rounds"]
    assert all(t is not None and 0 < t < 1 for t in ref["loss_rel_tols"])
    assert 0 < ref["state_rel_l2_tol"] < 1
    assert ref["reason"]
    # an untraced run reaches the check round inside the window, and a
    # traced one early enough to start its profiler after it
    warm = cell["warmup_dispatches"] * ref["rounds"]
    assert cell["loss_check"]["round"] > warm


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_workload_overrides_validate(entry):
    from colearn_federated_learning_tpu.config import resolve_config

    cell = catalog.load_workload(entry["name"])
    config = catalog.load_config(cell["config"])
    cfg = resolve_config(cell["named_config"],
                         catalog.experiment_overrides(cell, config, seed=3))
    # who trains when: the cell's file where it fixes it, else --seed
    # (the rule itself: test_benchmark_traffic_seed.py)
    assert cfg.run.seed == cell.get("traffic_seed", 3)
    assert cfg.run.num_lanes == entry["chips"]
    assert cfg.server.eval_every == 0 and cfg.server.checkpoint_every == 0
    assert cfg.run.out_dir == ""
    assert cfg.model.name == config["model"]["name"]
    # the run's precision is the one the configuration states (and the
    # reference follows)
    policy = config["dtype_policy"]
    assert cfg.run.compute_dtype == policy["compute"]
    assert (cfg.run.local_param_dtype or cfg.run.param_dtype) == policy["local_params"]
    assert cfg.run.param_dtype == policy["master_params"]
    assert cfg.server.cohort_size % entry["chips"] == 0
    assert cell["reference"]["rounds"] == cfg.run.fuse_rounds


@pytest.mark.parametrize("entry", BENCH["end_to_end"], ids=lambda e: e["name"])
def test_end_to_end_entry(entry):
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("host_clock", "device_trace")
    assert 0.01 <= entry["bound"] <= 0.1


def test_setup_s_is_an_end_to_end_metric():
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda e: e["name"])
def test_per_layer_entry_and_reader(entry):
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher") and entry["source"] in SOURCES
    assert _one_line(entry["layer"])
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    if entry["name"].endswith("_roofline"):
        assert entry["unit"] == "%"
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    spec = catalog.load_layer_metric(entry["name"])
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert spec.get("workloads") == entry.get("workloads")
    assert callable(catalog.load_reader(spec["reader"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_enough(cell):
    e2e = {m["name"] for m in catalog.cell_metrics(BENCH, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = catalog.cell_metrics(BENCH, cell, "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize(
    "path", sorted(
        os.path.relpath(p, ROOT)
        for d in BENCH["paths"]
        for p in glob.glob(os.path.join(ROOT, d, "**", "*"), recursive=True)
        if os.path.isfile(p) and "__pycache__" not in p and "/out/" not in p
    ),
)
def test_file_names_under_paths(path):
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", path), path
    if path.endswith(".json"):
        with open(os.path.join(ROOT, path)) as f:
            json.load(f)


def test_peaks_table_is_keyed_by_device_kind():
    with open(os.path.join(BENCH_DIR, "harness", "peaks.json")) as f:
        peaks = json.load(f)
    assert "Google Cloud" in peaks["_source"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def _tree_digest(root):
    digest = hashlib.sha256()
    for p in sorted(glob.glob(os.path.join(root, "**", "*"), recursive=True)):
        if os.path.isfile(p) and "__pycache__" not in p:
            digest.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                digest.update(f.read())
    return digest.hexdigest()


def test_a_new_cell_config_family_reference_and_metric_are_new_files_only(tmp_path):
    """A fifth cell, a third configuration of a model family the harness
    has never seen, the plain reference of another algorithm and one more
    per-layer metric: new files and appended BENCHMARK.json entries, no
    edit to a file that was there."""
    bench = tmp_path / "benchmark"
    kinds = ("configs", "workloads", "layer_metrics", "readers", "flops",
             "references")
    for sub in kinds:
        shutil.copytree(os.path.join(BENCH_DIR, sub), bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree_digest(str(bench))

    new_files = {
        # a family of its own: counts from its own shapes
        "flops/toy_mlp.py": (
            "def forward_macs(inputs, hidden, num_classes):\n"
            "    return inputs * hidden + hidden * num_classes\n\n"
            "def parameters(inputs, hidden, num_classes):\n"
            "    return (inputs + 1) * hidden + (hidden + 1) * num_classes\n"),
        # the reference of another algorithm: one entry point
        "references/toy_fedprox.py": (
            "def run_rounds(exp, config, seed, n_rounds):\n"
            "    assert config['name'] == 'toy_mlp_femnist'\n"
            "    return exp.initial, exp.after, exp.losses[:n_rounds]\n"),
        "configs/toy_mlp_femnist.json": json.dumps({
            "name": "toy_mlp_femnist", "source": "test",
            "named_config": "femnist_fedprox_500",
            "model": {"name": "mlp", "parameters": 784 * 64 + 64 + 64 * 62 + 62},
            "dtype_policy": {"compute": "float32", "local_params": "float32",
                             "master_params": "float32"},
            "overrides": {}, "reduced": [], "assumed": {},
            "flops": {"fn": "toy_mlp", "args": {"inputs": 784, "hidden": 64,
                                                "num_classes": 62}},
        }),
        "layer_metrics/dispatch_ms_round.json": json.dumps({
            "layer": "placement and server loop", "unit": "ms", "better": "lower",
            "source": "program_span", "moves": "rounds_per_s",
            "reader": "span_ms_round", "args": {"spans": ["round.dispatch"]},
        }),
        "layer_metrics/window_rounds.json": json.dumps({
            "layer": "entry", "unit": "rounds", "better": "higher",
            "source": "program_counter", "moves": "rounds_per_s",
            "reader": "window_rounds",
        }),
        "readers/window_rounds.py":
            "def read(ctx):\n    return ctx['window']['completed']\n",
    }
    cell = catalog.load_workload("r18_c16_k8")
    cell.update(name="toy_c8", config="toy_mlp_femnist", traffic="c8",
                named_config="femnist_fedprox_500",
                why="throw-away cell of a test")
    cell["overrides"] = dict(cell["overrides"], **{"server.cohort_size": 8})
    cell["reference"] = dict(cell["reference"], impl="toy_fedprox")
    new_files["workloads/toy_c8.json"] = json.dumps(cell)
    for rel, text in new_files.items():
        (bench / rel).write_text(text)

    appended = json.loads(json.dumps(BENCH))
    appended["configs"].append({"name": "toy_mlp_femnist", "source": "test",
                                "file": "benchmark/configs/toy_mlp_femnist.json",
                                "reduced": [], "why": "test"})
    appended["workloads"].append({"name": "toy_c8", "config": "toy_mlp_femnist",
                                  "traffic": "c8", "chips": 1, "why": "test"})
    for name, unit in (("dispatch_ms_round", "ms"), ("window_rounds", "rounds"),
                       ("mfu_pct_toy", "%")):
        appended["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "entry",
            "moves": "rounds_per_s", "workloads": ["toy_c8"]})
    # mfu_pct lists no cells, so it covers the new one as it is; the copy
    # under another name only lets this test pick it out
    shutil.copy(bench / "layer_metrics" / "mfu_pct.json",
                bench / "layer_metrics" / "mfu_pct_toy.json")
    new_files["layer_metrics/mfu_pct_toy.json"] = ""

    from colearn_federated_learning_tpu.config import resolve_config

    new_cell = catalog.load_workload("toy_c8", str(bench))
    new_cfg = catalog.load_config(new_cell["config"], str(bench))
    resolved = resolve_config(
        new_cell["named_config"],
        catalog.experiment_overrides(new_cell, new_cfg, seed=0),
    )
    assert resolved.algorithm == "fedprox" and resolved.server.cohort_size == 8

    # the family's counts are found by name, in the new file
    from harness import flops, reference

    spec = new_cfg["flops"]
    assert flops.parameters(spec, str(bench)) == new_cfg["model"]["parameters"]
    assert flops.forward_macs(spec, str(bench)) == 784 * 64 + 64 * 62
    with pytest.raises(catalog.CatalogError):
        flops.forward_macs(spec)  # not among the files that were there

    import run as bench_run

    entries = [e for e in catalog.cell_metrics(appended, "toy_c8", "per_layer")
               if e["name"] in ("dispatch_ms_round", "window_rounds", "mfu_pct_toy")]
    assert len(entries) == 3
    second = 1_000_000_000
    ctx = {"window": {"completed": 40}, "bench_dir": str(bench),
           "spans": {"round.dispatch": {"total_ms": 80.0}},
           "cell": new_cell, "config": new_cfg, "flops": flops, "fuse": 4,
           "peaks": {"bf16_flops_per_s": 6.0 * (784 * 64 + 64 * 62) * 100},
           "counters": {"examples_per_round": 10.0},
           "windows": [(None, 0, second, 1)]}
    got = bench_run.layer_metrics(entries, ctx, str(bench))
    assert got["dispatch_ms_round"] == {"value": 2.0, "unit": "ms"}
    assert got["window_rounds"] == {"value": 40.0, "unit": "rounds"}
    # 10 examples x 6 FLOPs/MAC x 4 rounds/s over a peak of 100 examples/s
    assert got["mfu_pct_toy"]["value"] == pytest.approx(40.0)

    # the reference is found by the cell's reference.impl, in the new file
    import numpy as np

    class FakeRun:
        seed, fuse = 0, 4
        rounds = {r: {"train_loss": 2.0} for r in range(1, 5)}
        first_dispatch_params = {"w": np.full(3, 2.0)}

        class exp:
            initial = {"w": np.ones(3)}
            after = {"w": np.full(3, 2.0)}
            losses = [2.0, 2.0, 2.0, 2.0]

    verdict = bench_run.check_reference(new_cell, new_cfg, FakeRun, str(bench))
    assert verdict["agrees"] and verdict["delta_rel_l2_err"] == 0.0
    with pytest.raises(catalog.CatalogError):
        bench_run.check_reference(new_cell, new_cfg, FakeRun)
    assert reference.compare(
        {"w": np.full(3, 3.0)}, [2.0] * 4, FakeRun.exp.initial,
        FakeRun.exp.after, [2.0] * 4, new_cell["reference"],
    )["agrees"] is False  # a delta twice the reference's

    # the old cells see none of it, and no old file changed
    old = {e["name"] for e in catalog.cell_metrics(appended, "r18_c16_k8", "per_layer")}
    assert not {"dispatch_ms_round", "window_rounds", "mfu_pct_toy"} & old
    for rel in new_files:
        os.remove(bench / rel)
    assert _tree_digest(str(bench)) == before
