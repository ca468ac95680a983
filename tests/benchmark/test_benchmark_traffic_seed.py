"""``traffic_seed`` (PR 43): a cell whose file carries the key fixes who
trains when, and ``--seed`` draws the weights alone; a cell without it
gives ``--seed`` both. The rule over every listed cell, the catalog's
refusals, a dry r18 preset on two seeds, the numbers a run prints beside
their limits, and the limits of the cells that were read against the
readings their files keep."""

import json
import math
import os
import statistics
import types

import numpy as np
import pytest

import bench_paths
from bench_paths import BENCHMARK_JSON
from harness import catalog
from startup_probe import load_run

with open(BENCHMARK_JSON) as _f:
    BENCH = json.load(_f)
# PR 22's two configurations: timing (r18) or limits (ViT) followed the draw
FIXED = {"r18_c16_k8": 43004, "r18_c64_k2_x4": 43006,
         "vit_silo_dp": 43001, "vit_silo_plain": 43001}
SEEDS = (3, 2147484001)


@pytest.fixture(scope="module")
def bench_run():
    return load_run()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_run_seed_is_the_files_traffic_seed_or_the_command_lines(entry, seed):
    cell = catalog.load_workload(entry["name"])
    config = catalog.load_config(cell["config"])
    got = catalog.experiment_overrides(cell, config, seed)["run.seed"]
    if entry["name"] in FIXED:
        assert cell["traffic_seed"] == FIXED[entry["name"]] == got
        assert len(cell["traffic_seed_reason"]) > 40
    else:  # the decoder cells: --seed decides everything, as before
        assert "traffic_seed" not in cell and got == seed


def _bench_dir_with(tmp_path, name, **changes):
    """A bench_dir that holds one workload file: ``name``'s, changed."""
    cell = catalog.load_workload(name)
    for key, value in changes.items():
        if value is None:
            cell.pop(key, None)
        else:
            cell[key] = value
    os.makedirs(tmp_path / "workloads", exist_ok=True)
    with open(tmp_path / "workloads" / f"{name}.json", "w") as f:
        json.dump(cell, f)
    return str(tmp_path)


@pytest.mark.parametrize("changes,says", [
    ({"traffic_seed_reason": None}, "traffic_seed_reason"),
    ({"traffic_seed_reason": ""}, "traffic_seed_reason"),
    ({"traffic_seed": "43001"}, "whole number"),
    ({"traffic_seed": 4.5}, "whole number"),
    ({"traffic_seed": True}, "whole number"),
    ({"traffic_seed": -1}, "whole number"),
])
def test_catalog_refuses_a_traffic_seed_it_cannot_stand_behind(
        tmp_path, changes, says):
    bench_dir = _bench_dir_with(tmp_path, "dry_r18_fused", **changes)
    with pytest.raises(catalog.CatalogError, match=says):
        catalog.load_workload("dry_r18_fused", bench_dir)


def test_catalog_takes_the_key_with_its_reason_and_a_file_without_it(tmp_path):
    assert catalog.load_workload("dry_r18_fused")["traffic_seed"] == 43001
    bench_dir = _bench_dir_with(tmp_path, "dry_r18_fused", traffic_seed=None,
                                traffic_seed_reason=None)
    cell = catalog.load_workload("dry_r18_fused", bench_dir)
    config = catalog.load_config(cell["config"])
    assert catalog.experiment_overrides(cell, config, 7)["run.seed"] == 7


def test_two_seeds_one_schedule_other_weights():
    """What the harness builds for two ``--seed``s of a cell with the
    key: the same cohorts, example order and block-step counts over
    eight rounds, and other initial parameters."""
    import jax

    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.obs import block_step_counts
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cell = catalog.load_workload("dry_r18_fused")
    config = catalog.load_config(cell["config"])
    seen = []
    for seed in SEEDS:
        exp = Experiment(resolve_config(
            cell["named_config"],
            catalog.experiment_overrides(cell, config, seed)), echo=False)
        try:
            rounds = []
            for r in range(8):
                cohort, idx, mask, n_ex, _ = exp._host_inputs(r)
                shape = exp._round_shape(r)
                counts = block_step_counts(
                    mask, shape.steps, shape.batch_size, shape.local_epochs,
                    *exp._block)
                rounds.append((np.asarray(cohort), np.asarray(idx),
                               np.asarray(n_ex), counts))
            params = jax.device_get(exp.init_state(seed)["params"])
        finally:
            exp._stop_prefetch()
        seen.append((rounds, params))
    (rounds_a, params_a), (rounds_b, params_b) = seen
    for (ca, ia, na, ka), (cb, ib, nb, kb) in zip(rounds_a, rounds_b):
        assert np.array_equal(ca, cb) and np.array_equal(ia, ib)
        assert np.array_equal(na, nb) and ka == kb
    # a schedule, not one cohort over and over
    assert len({tuple(c) for c, _, _, _ in rounds_a}) > 1
    leaves_a, leaves_b = jax.tree.leaves(params_a), jax.tree.leaves(params_b)
    assert any(not np.array_equal(a, b) for a, b in zip(leaves_a, leaves_b))


def test_sweep_reads_what_a_run_reads_and_both_seeds_are_correct(tmp_path):
    """``limit_sweep.py`` on the CPU, two seeds in one process: each is
    ``correct``, the round-1 losses differ (the weights follow the seed),
    and the second seed's numbers are those of a process of its own
    (``run.py --dry``), so a sweep's readings may set a run's limits."""
    common = ["--workload", "dry_r18_fused", "--dry"]
    proc = bench_paths.run_benchmark(
        [*common, "--seeds", ",".join(map(str, SEEDS))], tmp_path,
        script=os.path.join(bench_paths.ROOT, "tests", "benchmark",
                            "limit_sweep.py"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    head = "[bench] sweep: "
    rows = [json.loads(l[len(head):]) for l in proc.stdout.splitlines()
            if l.startswith(head)]
    assert [r["seed"] for r in rows] == list(SEEDS)
    assert all(r["correct"] and r["run_seed"] == 43001 for r in rows)
    assert rows[0]["first_rounds"]["1"] != rows[1]["first_rounds"]["1"]
    summary = bench_paths.bench_line(proc.stdout, "sweep_summary")
    assert summary["seeds"] == 2 and summary["not_correct"] == []
    alone = bench_paths.run_benchmark(
        [*common, "--seed", str(SEEDS[1]), "--seconds", "1"], tmp_path)
    assert alone.returncode == 0, alone.stderr[-2000:]
    last = json.loads(alone.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    for name, check in last["checks"].items():
        if name.startswith(("loss_round_", "ref_")):
            assert check == rows[1]["checks"][name], name
    # the numbers compared close the error stream, in the line's order
    tail = [l for l in alone.stderr.splitlines() if l.startswith("[check] ")]
    assert alone.stderr.rstrip().splitlines()[-len(tail):] == tail
    assert [l.split(":")[0][len("[check] "):] for l in tail] == list(
        last["checks"])


RESULT = {"failed": 0, "completed": 40, "attempted": 44, "error": None}
LOSS = {"round": 8, "band": [7.0, 9.0], "train_loss": 7.9, "ok": True}
REF = {"loss_rel_errs": [1e-5], "delta_rel_l2_err": 0.12, "agrees": True}
CELL = {"reference": {"loss_rel_tols": [1e-3], "state_rel_l2_tol": 0.25}}


def _compared(bench_run, compiles=0, result=None, loss=None, ref=None,
              cell=None):
    return bench_run.compared(compiles, {**RESULT, **(result or {})},
                              {**LOSS, **(loss or {})},
                              {**REF, **(ref or {})}, cell or CELL)


def test_compared_lists_every_number_beside_its_limit(bench_run):
    checks = _compared(bench_run)
    assert checks == {
        "compiles_in_window": [0, 0], "rounds_failed": [0, 0],
        "rounds_completed": [40, 1, 44], "loss_round_8": [7.9, 7.0, 9.0],
        "ref_loss_rel_err_round_1": [1e-5, 1e-3],
        "ref_delta_rel_l2_err": [0.12, 0.25],
    }
    assert all(bench_run.inside(c) for c in checks.values())


@pytest.mark.parametrize("broken,name", [
    ({"compiles": 1}, "compiles_in_window"),
    ({"result": {"failed": 4}}, "rounds_failed"),
    ({"result": {"completed": 0}}, "rounds_completed"),
    ({"loss": {"train_loss": 6.908}}, "loss_round_8"),
    ({"loss": {"train_loss": float("nan")}}, "loss_round_8"),
    ({"loss": {"train_loss": None}}, "loss_round_8"),
    ({"loss": {"band": None}}, "loss_round_8"),
    ({"ref": {"loss_rel_errs": [2e-3]}}, "ref_loss_rel_err_round_1"),
    ({"ref": {"delta_rel_l2_err": float("inf")}}, "ref_delta_rel_l2_err"),
    ({"cell": {"reference": {"loss_rel_tols": [],
                             "state_rel_l2_tol": 0.25}}},
     "ref_loss_rel_err_round_1"),
    ({"cell": {"reference": {"loss_rel_tols": [1e-3],
                             "state_rel_l2_tol": None}}},
     "ref_delta_rel_l2_err"),
])
def test_one_number_outside_its_limit_is_the_one_that_fails(
        bench_run, broken, name):
    checks = _compared(bench_run, **broken)
    assert [n for n, c in checks.items() if not bench_run.inside(c)] == [name]
    json.dumps(checks, allow_nan=False)  # the result's line stays JSON


# seeds on which the accepted program once read `correct: false` (ledger,
# PRs 28, 34, 42; builders', PR 25): a limit is set with them among its seeds
ON_RECORD = {"vit_silo_plain": {2045012331, 2147484001},
             "r18_c16_k8": {912000542, 2147488612}}


def _numbers(cell):
    """{compared number: (its readings, its limit(s))} of a cell's file."""
    readings, ref = cell["readings"], cell["reference"]
    out = {f"loss_round_{cell['loss_check']['round']}":
           tuple(cell["loss_check"]["band"])}
    for k, tol in enumerate(ref["loss_rel_tols"]):
        out[f"ref_loss_rel_err_round_{k + 1}"] = (tol,)
    out["ref_delta_rel_l2_err"] = (ref["state_rel_l2_tol"],)
    return {name: (readings[name], limit) for name, limit in out.items()}


# r18_c64_k2_x4 keeps PR 22's limits: no four chips were free in PR 43
READ = sorted(set(FIXED) - {"r18_c64_k2_x4"})


@pytest.mark.parametrize("name", READ)
def test_a_fixed_cells_limits_follow_from_the_readings_it_keeps(name):
    """benchmark/README.md, "How a limit is set", rules 1-4."""
    cell = catalog.load_workload(name)
    seeds = cell["readings"]["seeds"]
    assert len(set(seeds)) == len(seeds) >= 20
    assert sum(s > 10**9 for s in seeds) >= 10
    assert ON_RECORD.get(name, set()) <= set(seeds)
    assert cell["readings"]["source"]
    for number, (values, limit) in _numbers(cell).items():
        assert len(values) == len(seeds), number
        assert all(math.isfinite(v) for v in values), number
        if len(limit) == 1:  # an error against the reference
            assert 1.5 * max(values) <= limit[0] < 1, number
            continue
        lower, upper = limit
        if upper > 10 * lower > 0:
            # readings over a decade under a ceiling (chance): no sd
            # rule holds; three times beyond the extremes
            assert 3 * lower <= min(values), number
            assert 3 * max(values) <= upper, number
            continue
        room = 4 * statistics.stdev(values)
        assert lower <= min(values) - room, number
        assert max(values) + room <= upper, number


@pytest.mark.parametrize("name,classes", [("vit_silo_plain", 1000),
                                          ("vit_silo_dp", 1000),
                                          ("r18_c16_k8", 10),
                                          ("r18_c64_k2_x4", 10)])
def test_a_collapsed_or_broken_run_leaves_the_loss_band(
        bench_run, name, classes):
    """The uniform answer reads ln C in every round: it has to stay
    outside a classifier's band, as NaN, infinity and a run that never
    reached the round do."""
    cell = catalog.load_workload(name)
    at = int(cell["loss_check"]["round"])

    def ok(loss):
        rounds = {} if loss is None else {at: {"train_loss": loss}}
        run = types.SimpleNamespace(rounds=rounds)
        return bench_run.check_loss(cell, run)["ok"]

    lower, upper = cell["loss_check"]["band"]
    if name == "r18_c64_k2_x4":
        # back AT chance by round 24 (its file's reason): this cell's
        # band holds ln 10, and the reference's rounds are its check
        assert lower < math.log(classes) < upper
    else:
        assert not ok(math.log(classes))
    assert not ok(float("nan")) and not ok(float("inf")) and not ok(None)
    assert not ok(upper * 1.01) and not ok(lower * 0.99)
    if name in READ:
        assert all(ok(v) for v in cell["readings"][f"loss_round_{at}"])
