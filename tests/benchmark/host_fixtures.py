"""The fixtures that keep the program's host spans (PR 23), for the
tests beside this file: ``fixtures/host_*.xplane.pb.gz`` with their
``.op_names.json.gz``, cut by ``fixtures/make_host_fixture.py`` from
traces this PR recorded on the chip."""

import gzip
import json
import os
import shutil

import run as bench_run
from bench_paths import FIXTURES
from harness import trace_reduce

# fixture name -> (the rehearsal preset it is a chip trace of, rounds
# per dispatch)
RECORDED = {
    "host_chip1_dry_r18_fused": ("dry_r18_fused", 2),
    "host_chip1_dry_vit_dp": ("dry_vit_dp", 1),
}


def unpack(name, bench_dir):
    """Unpacks a fixture where a traced run of its cell would have left
    its trace under ``bench_dir`` (the layout
    ``harness/host_spans.find_trace`` looks in) and returns the readers'
    ``ctx`` for it."""
    cell, fuse = RECORDED[name]
    trace_dir = os.path.join(str(bench_dir), "out", "trace", cell + ".0")
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, name + ".xplane.pb")
    with gzip.open(os.path.join(FIXTURES, name + ".xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(FIXTURES, name + ".op_names.json.gz"), "rt") as f:
        trace = trace_reduce.load(path, json.load(f))
    return {
        "cell": {"name": cell}, "bench_dir": str(bench_dir), "trace": trace,
        "windows": trace_reduce.steady_windows(trace, bench_run.ROUND_PROGRAM),
        "fuse": fuse, "scopes": bench_run.SCOPES, "reduce": trace_reduce,
    }
