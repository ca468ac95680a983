"""``dp_ghost_param_pct`` (PR 44): the reader on a hand-made ``ctx``,
the data file against its BENCHMARK.json entry, and the rehearsal
preset ``dry_vit_dp`` built as the benchmark builds it: the counts ride
``round.host_inputs.slab_build`` once a round, the metric reads the
share the model's shapes give, and ``dp_example_grad`` / ``dp_clip`` /
``dp_noise`` still hold all of DP's work under ``local_grad``."""

import pytest

from bench_paths import BENCH_DIR
from harness import catalog
from harness import trace_reduce as rd

NAME = "dp_ghost_param_pct"
SPAN = "round.host_inputs.slab_build"
DP_SCOPES = ("dp_example_grad", "dp_clip", "dp_noise")
INNER = catalog.load_module("readers", "inner_scope_ms_round", ("read",),
                            BENCH_DIR)


def _read(spans):
    spec = catalog.load_layer_metric(NAME)
    return catalog.load_reader(spec["reader"])({"spans": spans},
                                               **spec["args"])


def test_reader_divides_ghost_by_trained_parameters():
    span = {"count": 6, "total_ms": 1.0, "max_ms": 0.2, "self_ms": 1.0}
    # six rounds of ViT-B/16: the counts add up, the share stays
    assert _read({SPAN: {**span, "dp_params": 6 * 86567656,
                         "dp_ghost_params": 6 * 86292480}}) == pytest.approx(
        99.682, abs=1e-3)
    # a DP model without a product leaf: a number, not nothing
    assert _read({SPAN: {**span, "dp_params": 1000,
                         "dp_ghost_params": 0}}) == 0.0
    # the parent's tracer (no such count), DP off, no spans at all
    assert _read({SPAN: {**span, "client_steps": 128}}) is None
    assert _read({SPAN: span}) is None
    assert _read({}) is None


def test_data_file_and_benchmark_entry_agree():
    bench = catalog.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    spec = catalog.load_layer_metric(NAME)
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == spec[key], key
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert (entry["layer"], entry["moves"]) == ("round engine",
                                                "rounds_per_s")
    assert entry["workloads"] == ["vit_silo_dp"]
    assert spec["reader"] == "span_count_pct"
    assert spec["args"] == {"span": SPAN, "count": "dp_ghost_params",
                            "of": "dp_params"}
    # appended after everything the benchmark already had, and reported
    # in the one cell that trains with DP-SGD
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("skipped_steps_pct")
    for cell in bench["workloads"]:
        reported = {m["name"] for m in catalog.cell_metrics(
            bench, cell["name"], "per_layer")}
        assert (NAME in reported) == (cell["name"] == "vit_silo_dp")


@pytest.fixture(scope="module")
def dry_vit_dp():
    """The rehearsal preset's experiment after one round, as the
    benchmark builds it: (experiment, the tracer's spans, {op: scope
    path} of the compiled round programs)."""
    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.obs import executables as exec_mod
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cell = catalog.load_workload("dry_vit_dp")
    config = catalog.load_config(cell["config"])
    cfg = resolve_config(cell["named_config"],
                         catalog.experiment_overrides(cell, config, 0))
    exp = Experiment(cfg, echo=False)
    exec_mod.install(exp._exec_reg)
    try:
        exp.run_round(exp._place_state(exp.init_state(0)), 0)
        names = {}
        for entry in exp._exec_reg._cache.values():
            if entry["name"].startswith("round."):
                names.update(rd.scopes_from_hlo(entry["compiled"].as_text()))
    finally:
        exec_mod.uninstall()
        exp._stop_prefetch()
    return exp, exp.tracer.drain(), names


def test_metric_reads_from_a_dry_vit_dp_line(dry_vit_dp):
    """The counts are on the span once for every round whose inputs
    were built, and the reader's share is the one the preset's shapes
    give by hand: every ``kernel`` of the reduced ViT is a product
    leaf, nothing else is."""
    import jax

    exp, spans, _ = dry_vit_dp
    span = spans[SPAN]
    leaves = jax.tree_util.tree_flatten_with_path(exp._param_shapes())[0]
    sizes = {jax.tree_util.keystr(p): int(l.size) for p, l in leaves}
    kernels = sum(n for p, n in sizes.items() if p.endswith("['kernel']"))
    assert span["dp_params"] == span["count"] * sum(sizes.values())
    assert span["dp_ghost_params"] == span["count"] * kernels
    assert _read(spans) == pytest.approx(
        100.0 * kernels / sum(sizes.values()))
    assert 90.0 < _read(spans) < 100.0


def test_dp_scopes_still_partition_dps_local_grad(dry_vit_dp):
    """Every named op beneath ``local_grad`` is in exactly one of the
    three DP scopes, but for the microbatch loop's own plumbing (its
    counter, slices and the loss accumulator); none of the model's ops
    and no product is outside them."""
    _, _, names = dry_vit_dp
    paths = {p for p in names.values()
             if INNER.inner_in_path(p, ("local_grad",))}
    assert paths
    inside = {s: {p for p in paths if INNER.inner_in_path(p, (s,))}
              for s in DP_SCOPES}
    assert all(inside.values())
    for a in DP_SCOPES:
        for b in DP_SCOPES:
            assert a == b or not inside[a] & inside[b]
    for path in paths - set().union(*inside.values()):
        assert "ViT" not in path and "dot_general" not in path, path
        assert "conv_general_dilated" not in path, path
    # the weighted products are dp_clip's (on the CPU the Gram products
    # fuse under their sum's name), the model's forward and the
    # activations' backward dp_example_grad's
    assert any("bti,bto->bio" in p for p in inside["dp_clip"])
    assert not any("bti,bto->bio" in p or "b,b...->..." in p
                   for p in inside["dp_example_grad"] | inside["dp_noise"])
    assert any("transpose(jvp(ViT))" in p for p in inside["dp_example_grad"])
