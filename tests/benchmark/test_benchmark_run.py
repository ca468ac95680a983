"""``benchmark/run.py`` as a process: the rehearsal prints the contract's
keys and counts only, and without a chip there is no result."""

import json
import os
import shutil

import pytest

from bench_paths import (BENCH_DIR, BENCHMARK_JSON, bench_line,
                         run_benchmark as _run)

with open(BENCHMARK_JSON) as _f:
    BENCH = json.load(_f)
METRIC_NAMES = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}


@pytest.mark.parametrize("preset", ["dry_r18_fused", "dry_vit_dp"])
def test_dry_run_prints_the_contracts_keys_and_counts_only(preset, tmp_path):
    """Also the CPU test of ``references/fedavg.py``: the system's first
    dispatch (fused FedAvg in float32; example-level DP-SGD with AdamW in
    bf16) agrees with the plain reference round inside the preset's
    tolerances, the noise drawn as the program draws it."""
    proc = _run(["--workload", preset, "--seed", "2", "--seconds", "1",
                 "--dry"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]  # the numbers compared, last
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= last["metrics"]["rounds_completed"]["value"] > 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert not set(last["metrics"]) & METRIC_NAMES  # counts only
    assert last["device"]["platform"] == "cpu"
    ref = bench_line(proc.stdout, "reference")
    assert ref["agrees"] and ref["delta_rel_l2_err"] < 0.05
    assert ref["loss_rel_errs"][0] < 1e-3
    setup = bench_line(proc.stdout, "setup")
    for key in ("import_and_runtime_s", "data_partition_engine_s",
                "init_and_placement_s", "first_dispatch_s", "further_warmup_s",
                "compile_s", "host_prep_s"):
        assert setup[key] >= 0, key


def test_without_a_tpu_there_is_no_result(tmp_path):
    """Exits non-zero before building or compiling anything."""
    proc = _run(["--workload", "r18_c16_k8", "--seed", "0", "--seconds", "1",
                 "--trace", "0"], tmp_path)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert '"correct"' not in proc.stdout and "[bench]" not in proc.stdout
    assert not os.path.exists(tmp_path / "jax_cache")  # nothing compiled


def test_an_unlisted_preset_measures_nothing(tmp_path):
    proc = _run(["--workload", "dry_r18_fused", "--seconds", "1"], tmp_path)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    """With only BENCHMARK.json and the files under ``paths`` (no
    program) the command exits non-zero and prints no result."""
    lone = tmp_path / "lone"
    shutil.copytree(BENCH_DIR, lone / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(BENCHMARK_JSON, lone / "BENCHMARK.json")
    proc = _run(["--workload", "dry_r18_fused", "--seconds", "1", "--dry"],
                tmp_path, cwd=str(lone),
                script=str(lone / "benchmark" / "run.py"))
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
