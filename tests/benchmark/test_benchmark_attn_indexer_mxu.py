"""``attn_indexer_mxu_pct``: the reader against the numbers ISSUE 28
states (5.08 TFLOP a round over 218 ms: 11.8 %), what it reads from a
program without the scope, and its data file against BENCHMARK.json."""

import gzip
import json
import os

import pytest

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from bench_paths import BENCH_DIR
from harness import catalog, trace_reduce

CONFIG = catalog.load_config("keye_vl2_30b_a3b_ep8")
SPEC = catalog.load_layer_metric("attn_indexer_mxu_pct")
# per sequence and layer: projections 8,192 x 2,048 x (16 x 64 + 64 + 16)
# and index scores 33,558,528 causal pairs x 16 x 64 MACs
LAYER_MACS = 8192 * 2048 * (16 * 64 + 64 + 16) + 33_558_528 * 16 * 64
ROUND_FLOPS = 6 * LAYER_MACS * 4 * 4  # 4 layers, 4 sequences
SCOPES = ("round_local_train", "round_aggregate", "round_server_apply",
          "round_fused_reduce_apply", "round_control_plane",
          "round_attack_transform", "round_client_ledger")


def _ctx(**over):
    ctx = {"bench_dir": BENCH_DIR, "config": CONFIG,
           "counters": {"examples_per_round": 4.0},
           "peaks": {"bf16_flops_per_s": 197e12}}
    return dict(ctx, **over)


@pytest.mark.parametrize("ms_round,want", [
    (218.0, 11.82),   # ledger, PR 27: the jnp form, per-head scores in HBM
    (130.0, 19.82),
    (90.0, 28.63),
])
def test_reader_divides_the_indexers_work_by_the_scopes_time(
        monkeypatch, ms_round, want):
    assert ROUND_FLOPS == pytest.approx(5.08e12, rel=1e-3)
    read = catalog.load_reader(SPEC["reader"])
    calls = []

    def fake_loader(name, bench_dir=BENCH_DIR):
        """Stands in for ``inner_scope_ms_round``."""
        calls.append(name)
        return lambda ctx, scopes: (ms_round if scopes == ["attn_indexer"]
                                    else None)

    monkeypatch.setattr(catalog, "load_reader", fake_loader)
    got = read(_ctx(), **SPEC["args"])
    assert calls == ["inner_scope_ms_round"]
    assert got == pytest.approx(100 * ROUND_FLOPS / (ms_round / 1e3) / 197e12)
    assert got == pytest.approx(want, abs=0.01)
    assert read(_ctx(), scopes=["nothing"]) is None
    # a configuration whose family counts no indexer reports nothing
    vit = _ctx(config=catalog.load_config("vit_b16_silo"))
    assert read(vit, **SPEC["args"]) is None


def test_the_work_is_the_familys_own_count_of_the_indexer():
    """The reader takes ``forward_macs`` with and without the indexer;
    the difference is the two lines ISSUE 28 writes out."""
    family = catalog.load_flops_family(CONFIG["flops"]["fn"])
    args = CONFIG["flops"]["args"]
    without = dict(args, index_heads=0, index_head_dim=0)
    assert (family.forward_macs(**args) - family.forward_macs(**without)
            == args["layers"] * LAYER_MACS)


def test_reads_nothing_from_a_trace_without_the_scope(tmp_path):
    """On a program from before PR 25 the reader returns None and does
    not raise: the recorded ResNet trace stands in for it."""
    fixtures = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fixtures")
    with gzip.open(os.path.join(
            fixtures, "chip1_dry_r18_fused.op_names.json.gz"), "rt") as f:
        op_names = json.load(f)
    path = str(tmp_path / "t.xplane.pb")
    with gzip.open(os.path.join(fixtures, "chip1_dry_r18_fused.xplane.pb.gz"),
                   "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())
    trace = trace_reduce.load(path, op_names)
    windows = trace_reduce.steady_windows(trace, "jit_round_fn")
    assert windows
    ctx = _ctx(reduce=trace_reduce, windows=windows, trace=trace, fuse=2,
               scopes=SCOPES)
    assert catalog.load_reader(SPEC["reader"])(ctx, **SPEC["args"]) is None


@pytest.mark.parametrize("name,ms_metric", [
    ("attn_indexer_mxu_pct", "attn_indexer_ms_round"),
    ("attn_sparse_mxu_pct", "attn_sparse_ms_round"),
])
def test_data_file_and_benchmark_entry_agree(name, ms_metric):
    """By name, wherever the entry stands in ``per_layer``: a later PR
    appends behind it (``test_benchmark_attn_sparse_mxu.py`` looks at the
    last entry, which PR 28's took from it; its checks are made here)."""
    entry, = (e for e in catalog.load_benchmark()["per_layer"]
              if e["name"] == name)
    spec = catalog.load_layer_metric(name)
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == spec[key], key
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "device_trace")
    assert entry["workloads"] == ["keye_silo_8k"]
    # the time it divides by is the scope's own metric's
    ms = catalog.load_layer_metric(ms_metric)
    assert spec["args"]["scopes"] == ms["args"]["scopes"]
    assert entry["layer"] == ms["layer"] and entry["moves"] == ms["moves"]
