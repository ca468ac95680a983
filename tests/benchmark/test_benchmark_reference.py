"""The reference comparison as a check: it passes over a four-device
mesh, and it fails a system that runs in a lower precision than its
configuration states."""

import json

from bench_paths import bench_line, run_benchmark
from harness import catalog


def _last(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_device_mesh_agrees_with_the_reference(tmp_path):
    """``dry_r18_x4``: the fused FedAvg round over a clients=4 mesh (one
    lane per virtual device, aggregate by psum) against the reference's
    loop on one device."""
    proc = run_benchmark(["--workload", "dry_r18_x4", "--seed", "2",
                          "--seconds", "1", "--dry"], tmp_path, devices=4)
    last = _last(proc)
    assert last["correct"] is True and last["device"]["count"] == 4
    ref = bench_line(proc.stdout, "reference")
    tols = catalog.load_workload("dry_r18_x4")["reference"]
    assert ref["agrees"] and len(ref["loss_rel_errs"]) == tols["rounds"] == 2


def test_a_lower_precision_than_stated_fails_the_reference(tmp_path):
    """``dry_r18_lowered`` runs the system in bfloat16 where
    ``configs/dry_resnet.json`` states float32; the reference follows the
    stated policy, so ``correct`` is false under ``dry_r18_fused``'s own
    tolerances although every loss is finite and nothing failed."""
    proc = run_benchmark(["--workload", "dry_r18_lowered", "--seed", "2",
                          "--seconds", "1", "--dry"], tmp_path)
    last = _last(proc)
    assert last["correct"] is False and last["failed"] == 0
    assert bench_line(proc.stdout, "loss_check")["ok"]
    ref = bench_line(proc.stdout, "reference")
    lowered = catalog.load_workload("dry_r18_lowered")["reference"]
    honest = catalog.load_workload("dry_r18_fused")["reference"]
    assert lowered["loss_rel_tols"] == honest["loss_rel_tols"]
    assert lowered["state_rel_l2_tol"] == honest["state_rel_l2_tol"]
    assert not ref["agrees"]
    assert ref["loss_rel_errs"][0] > 5 * lowered["loss_rel_tols"][0]
    assert ref["delta_rel_l2_err"] > lowered["state_rel_l2_tol"]
