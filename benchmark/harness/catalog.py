"""Finds the benchmark's data files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, so that a later PR adds a cell, a
configuration or a metric by adding files and appending one entry to
``BENCHMARK.json`` — it never edits a file that is already there:

    configs/<config>.json          sizes as run, source, reduced, assumed,
                                   dtype policy, FLOPs family (+ arguments)
    workloads/<cell>.json          named config + dotted-key overrides,
                                   warm-up, loss band, reference
                                   implementation and tolerances; with
                                   ``traffic_seed`` the cell fixes who
                                   trains when and ``--seed`` draws the
                                   weights only
    layer_metrics/<metric>.json    layer, unit, moves, reader (+ arguments)
    readers/<reader>.py            one ``read(ctx, **args)`` per file
    flops/<family>.py              ``forward_macs(**args)`` and
                                   ``parameters(**args)`` of a model family
    references/<impl>.py           ``run_rounds(exp, config, seed, n)``: the
                                   plain reference of an algorithm

``bench_dir`` is the directory that holds those six; ``benchmark_json``
is the contract file at the root of the checkout.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class CatalogError(ValueError):
    """A data file is missing, malformed or names something unknown."""


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        raise CatalogError(f"no such benchmark file: {path}") from None
    except json.JSONDecodeError as e:
        raise CatalogError(f"{path}: not JSON: {e}") from None
    if not isinstance(data, dict):
        raise CatalogError(f"{path}: expected a JSON object")
    return data


def _named_file(bench_dir: str, kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise CatalogError(f"{kind} name {name!r} has characters a name "
                           f"may not have")
    return os.path.join(bench_dir, kind, name + ext)


def load_benchmark(benchmark_json: str = BENCHMARK_JSON) -> Dict[str, Any]:
    return _load_json(benchmark_json)


def load_workload(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    cell = _load_json(_named_file(bench_dir, "workloads", name, ".json"))
    for key in ("config", "chips", "named_config", "overrides",
                "warmup_dispatches", "loss_check", "reference", "why"):
        if key not in cell:
            raise CatalogError(f"workload {name!r} lacks {key!r}")
    if cell.get("name", name) != name:
        raise CatalogError(f"workload file {name!r} names itself "
                           f"{cell['name']!r}")
    if cell["chips"] not in (1, 4):
        raise CatalogError(f"workload {name!r}: chips must be 1 or 4")
    for key in ("impl", "rounds", "loss_rel_tols", "state_rel_l2_tol"):
        if key not in cell["reference"]:
            raise CatalogError(f"workload {name!r}: reference lacks {key!r}")
    if "traffic_seed" in cell:
        seed = cell["traffic_seed"]
        if isinstance(seed, bool) or not isinstance(seed, int) or seed < 0:
            raise CatalogError(f"workload {name!r}: traffic_seed must be a "
                               f"whole number, not {seed!r}")
        if not cell.get("traffic_seed_reason"):
            raise CatalogError(f"workload {name!r} fixes its traffic_seed "
                               f"and gives no traffic_seed_reason")
    return cell


def load_config(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    cfg = _load_json(_named_file(bench_dir, "configs", name, ".json"))
    for key in ("source", "named_config", "model", "dtype_policy",
                "overrides", "reduced", "assumed", "flops"):
        if key not in cfg:
            raise CatalogError(f"config {name!r} lacks {key!r}")
    return cfg


def load_layer_metric(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    spec = _load_json(_named_file(bench_dir, "layer_metrics", name, ".json"))
    for key in ("layer", "unit", "better", "source", "moves", "reader"):
        if key not in spec:
            raise CatalogError(f"layer metric {name!r} lacks {key!r}")
    return spec


def load_module(kind: str, name: str, needs, bench_dir: str = BENCH_DIR):
    """The module ``<kind>/<name>.py``, which has to define every
    function named in ``needs``."""
    path = _named_file(bench_dir, kind, name, ".py")
    if not os.path.exists(path):
        raise CatalogError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for fn in needs:
        if not callable(getattr(module, fn, None)):
            raise CatalogError(f"{kind}/{name}.py defines no {fn}()")
    return module


def load_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``readers/<name>.py``."""
    return load_module("readers", name, ("read",), bench_dir).read


def load_flops_family(name: str, bench_dir: str = BENCH_DIR):
    """``flops/<name>.py``: a model family's counts from shapes."""
    return load_module("flops", name, ("forward_macs", "parameters"), bench_dir)


def load_reference(name: str, bench_dir: str = BENCH_DIR):
    """``references/<name>.py``: an algorithm's plain reference rounds."""
    return load_module("references", name, ("run_rounds",), bench_dir)


def experiment_overrides(cell: Dict[str, Any], config: Dict[str, Any],
                         seed: int) -> Dict[str, Any]:
    """Dotted-key overrides for ``resolve_config(cell["named_config"])``:
    the configuration's own, then the cell's, then what the command line
    fixes (the seed, and one lane per chip the cell asks for).

    ``run.seed`` draws the partition, the cohort schedule and the example
    order. A cell whose file carries ``traffic_seed`` fixes them there and
    leaves ``--seed`` the initial weights and the run's key (the harness
    hands ``--seed`` to ``Experiment.init_state`` itself, ``window.Run``);
    a cell without the key gives ``--seed`` both."""
    out = dict(config["overrides"])
    out.update(cell["overrides"])
    out["run.seed"] = int(cell.get("traffic_seed", seed))
    out["run.num_lanes"] = int(cell["chips"])
    return out


def cell_metrics(benchmark: Dict[str, Any], workload: str,
                 section: str) -> List[Dict[str, Any]]:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: all that list no ``workloads``, and those that list it."""
    return [
        m for m in benchmark.get(section, [])
        if "workloads" not in m or workload in m["workloads"]
    ]


def benchmark_cell(benchmark: Dict[str, Any],
                   workload: str) -> Optional[Dict[str, Any]]:
    for w in benchmark.get("workloads", []):
        if w["name"] == workload:
            return w
    return None
