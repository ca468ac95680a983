"""``attn_sparse_mxu_pct``: the reader against the numbers ISSUE 26
states (11.55 TFLOP a round over 909.9 ms: 6.4 %), what it reads from a
program without the scope, and its data file against BENCHMARK.json."""

import gzip
import json
import os

import pytest

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from bench_paths import BENCH_DIR
from harness import catalog, trace_reduce

CONFIG = catalog.load_config("keye_vl2_30b_a3b_ep8")
SPEC = catalog.load_layer_metric("attn_sparse_mxu_pct")
# per sequence and layer 2 x 14,681,088 selected pairs x 32 x 128 MACs
ROUND_FLOPS = 6 * 2 * 14_681_088 * 32 * 128 * 4 * 4  # 4 layers, 4 sequences
SCOPES = ("round_local_train", "round_aggregate", "round_server_apply",
          "round_fused_reduce_apply", "round_control_plane",
          "round_attack_transform", "round_client_ledger")


def _ctx(**over):
    ctx = {"bench_dir": BENCH_DIR, "config": CONFIG,
           "counters": {"examples_per_round": 4.0},
           "peaks": {"bf16_flops_per_s": 197e12}}
    return dict(ctx, **over)


@pytest.mark.parametrize("ms_round,want", [
    (909.9, 6.44),    # ledger, PR 25: the dense-masked jnp form
    (400.0, 14.65),
    (190.0, 30.85),   # 8 products for 6 over 2.43 x the pairs, at peak
])
def test_reader_divides_the_selected_pairs_work_by_the_scopes_time(
        monkeypatch, ms_round, want):
    assert ROUND_FLOPS == pytest.approx(11.55e12, rel=1e-3)
    read = catalog.load_reader(SPEC["reader"])
    calls = []

    def fake_loader(name, bench_dir=BENCH_DIR):
        """Stands in for ``inner_scope_ms_round``."""
        calls.append(name)
        return lambda ctx, scopes: (ms_round if scopes == ["attn_sparse"]
                                    else None)

    monkeypatch.setattr(catalog, "load_reader", fake_loader)
    got = read(_ctx(), **SPEC["args"])
    assert calls == ["inner_scope_ms_round"]
    assert got == pytest.approx(100 * ROUND_FLOPS / (ms_round / 1e3) / 197e12)
    assert got == pytest.approx(want, abs=0.01)
    assert read(_ctx(), scopes=["nothing"]) is None
    # a configuration whose family counts no selection reports nothing
    vit = _ctx(config=catalog.load_config("vit_b16_silo"))
    assert read(vit, **SPEC["args"]) is None


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


def test_data_file_and_benchmark_entry_agree():
    # looked up by name: it was the last entry only until PR 28 appended
    names = [m["name"] for m in catalog.load_benchmark()["per_layer"]]
    assert SPEC["name"] == "attn_sparse_mxu_pct"
    assert names.count(SPEC["name"]) == 1
    assert names.index(SPEC["name"]) > names.index("attn_sparse_ms_round")
    entry = catalog.load_benchmark()["per_layer"][names.index(SPEC["name"])]
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entry[key] == SPEC[key], key
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "device_trace")
    assert entry["workloads"] == ["keye_silo_8k"]
    # the time it divides by is attn_sparse_ms_round's
    ms = catalog.load_layer_metric("attn_sparse_ms_round")
    assert SPEC["args"]["scopes"] == ms["args"]["scopes"]
    assert entry["layer"] == ms["layer"] and entry["moves"] == ms["moves"]
