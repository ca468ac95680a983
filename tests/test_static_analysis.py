"""Static invariant analyzer (`colearn check`, analysis/): seed-purity
lint positives/negatives on fixture snippets + the allowlist contract,
capability-matrix golden pin, feature by feature, + a tampered matrix
(exit 1 names the pairing), JSONL schema registry static cross-checks + seeded
emitter/consumer violations (file:line), registry completeness against
a live tiny-fit run's JSONL, the converted bare-assert pin, and the
tier-1 `colearn check` CLI smoke (ISSUE 13)."""

import json
import os
import subprocess
import sys

import pytest

from colearn_federated_learning_tpu.analysis import capability
from colearn_federated_learning_tpu.analysis import check as check_mod
from colearn_federated_learning_tpu.analysis import schema, seed_purity

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# seed-purity lint: fixture positives / negatives / allowlist contract
# ---------------------------------------------------------------------------

_DIRTY_SNIPPET = '''\
import os
import random
import time

import numpy as np


def draw(n):
    noise = np.random.rand(n)          # unseeded module-level draw
    tok = os.urandom(8)                # unseeded by construction
    t0 = time.time()                   # wall clock
    assert n > 0, "positive"           # bare assert
    return noise, tok, t0, random.random()
'''

_CLEAN_SNIPPET = '''\
import jax
import numpy as np


def draw(seed, n, key):
    rng = np.random.default_rng((seed, 0x51))
    a = rng.normal(size=n)
    b = jax.random.normal(key, (n,))
    if n <= 0:
        raise ValueError(f"n must be positive, got {n}")
    return a, b
'''


def _lint_snippet(tmp_path, source):
    path = tmp_path / "fixture_mod.py"
    path.write_text(source)
    return seed_purity.lint_files([str(path)], str(tmp_path))


def test_lint_flags_each_rule_with_location(tmp_path):
    findings = _lint_snippet(tmp_path, _DIRTY_SNIPPET)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f["rule"], []).append(f)
    # import random + random.random() reference... the import is the
    # flagged site; np.random.rand and os.urandom are call sites
    rng_symbols = {f["symbol"] for f in by_rule["unseeded_rng"]}
    assert "np.random.rand" in rng_symbols
    assert "os.urandom" in rng_symbols
    assert "import random" in rng_symbols
    wall = by_rule["wallclock"]
    assert wall[0]["symbol"] == "time.time"
    assert wall[0]["file"] == "fixture_mod.py"
    assert wall[0]["line"] == 11  # exact file:line in the violation
    assert wall[0]["qualname"] == "draw"
    assert by_rule["bare_assert"][0]["line"] == 12


def test_lint_negatives_stay_clean(tmp_path):
    assert _lint_snippet(tmp_path, _CLEAN_SNIPPET) == []


def test_allowlist_suppresses_only_with_reason_and_flags_stale(tmp_path):
    findings = _lint_snippet(tmp_path, _DIRTY_SNIPPET)
    wall = [f for f in findings if f["rule"] == "wallclock"]
    allowlist = [
        # valid entry: suppresses the wallclock finding
        {"rule": "wallclock", "file": "fixture_mod.py", "qualname": "draw",
         "symbol": "time.time", "reason": "fixture timing site"},
        # reason-less entry: suppresses nothing, is itself a problem
        {"rule": "bare_assert", "file": "fixture_mod.py",
         "qualname": "draw", "reason": ""},
        # stale entry: matches nothing
        {"rule": "wallclock", "file": "other.py", "qualname": "gone",
         "reason": "moved long ago"},
    ]
    kept, problems, suppressed = seed_purity.apply_allowlist(
        findings, allowlist
    )
    assert suppressed == len(wall)
    assert all(f["rule"] != "wallclock" for f in kept)
    assert any(f["rule"] == "bare_assert" for f in kept)
    kinds = {p["kind"] for p in problems}
    assert kinds == {"allowlist_missing_reason", "allowlist_stale_entry"}


def test_repo_lint_is_clean_with_shipped_allowlist():
    result = seed_purity.lint_repo(_ROOT)
    assert result["violations"] == [], result["violations"]
    assert result["allowlist_problems"] == []
    # the allowlist is live documentation, not a no-op
    assert result["suppressed"] >= 10


def test_converted_assert_raises_typed_exception():
    """Satellite pin: the bare-assert conversions survive `python -O` —
    blockwise_attention's shape invariant is now a ValueError."""
    jnp = pytest.importorskip("jax.numpy")
    from colearn_federated_learning_tpu.ops.ring_attention import (
        blockwise_attention,
    )

    q = jnp.zeros((1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="block_size multiple"):
        blockwise_attention(q, q, q, heads=2, block_size=3)


# ---------------------------------------------------------------------------
# capability matrix: golden pin, artifact contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("feature", sorted(capability.FEATURES))
def test_capability_matrix_golden_pin(feature):
    """The checked-in artifact IS what validate() says, feature by
    feature: ``feature`` alone validates, and the partners validate()
    refuses with it — and each reason, to the letter — are the golden's.
    A changed pairing rule fails the cases of the features it touches
    (and must land with its regenerated matrix diff)."""
    golden = capability.load_matrix(_ROOT)
    spec = capability.FEATURES[feature]
    assert golden["features"][feature] == {
        "overrides": spec.overrides, "note": spec.note}
    alone, refused = capability.feature_verdicts(feature)
    assert alone is None, f"{feature} alone does not validate: {alone}"
    expected = {}
    for pair, reason in golden["rejected"].items():
        a, b = pair.split("+")
        if feature in (a, b):
            expected[b if a == feature else a] = reason
    assert refused == expected
    # a rejection has a reason
    assert all(reason.strip() for reason in refused.values()), refused


def test_capability_matrix_names_only_catalog_features():
    """The other direction of the pin: the golden holds no feature and
    no pairing the catalog has dropped."""
    golden = capability.load_matrix(_ROOT)
    assert sorted(golden["features"]) == sorted(capability.FEATURES)
    for pair in golden["rejected"]:
        a, b = pair.split("+")
        assert a < b and {a, b} <= set(capability.FEATURES), pair
    counts = golden["counts"]
    assert counts["rejected"] == len(golden["rejected"])
    assert counts["supported"] + counts["rejected"] == counts["pairs"]


def test_tampered_matrix_fails_naming_the_pairing(tmp_path):
    """A checked-in matrix that disagrees with validate() — one pairing
    flipped each way — exits 1 through the CLI, naming both pairings.
    The tmp repo root symlinks the real package so all three analyzers
    run for real."""
    matrix = capability.load_matrix(_ROOT)
    victim = next(iter(matrix["rejected"]))
    del matrix["rejected"][victim]  # the golden now says: supported
    planted = "attack_alie+batch_shards"
    assert planted not in matrix["rejected"]
    matrix["rejected"][planted] = "no such rule"
    os.symlink(os.path.join(_ROOT, "colearn_federated_learning_tpu"),
               tmp_path / "colearn_federated_learning_tpu")
    with open(tmp_path / capability.MATRIX_FILENAME, "w") as f:
        json.dump(matrix, f)
    report = check_mod.run_check(str(tmp_path))
    assert not report["clean"]
    drift = [v for v in report["violations"] if v["kind"] == "matrix_drift"]
    assert len(drift) == 1
    assert victim in drift[0]["message"]
    assert planted in drift[0]["message"]

    from colearn_federated_learning_tpu import cli

    assert cli.main(["check", "--root", str(tmp_path)]) == 1


# ---------------------------------------------------------------------------
# schema registry: static cross-checks + seeded violations
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def emit_sites():
    return schema.check_emit_sites(_ROOT)


def test_schema_repo_emit_and_consume_clean(emit_sites):
    emit_violations, _ = emit_sites
    assert emit_violations == [], emit_violations
    consume_violations, summary = schema.check_consumers(_ROOT)
    assert consume_violations == [], consume_violations
    assert "client_ledger" in summary["consumed_types"]
    assert "rounds_per_sec" in summary["consumed_fields"]


# the families ISSUE 13 names must all be statically visible
@pytest.mark.parametrize("record_type", [
    "round", "spans", "client_ledger", "population_health", "run_summary",
    "precision", "health", "attack"])
def test_schema_emit_site_resolves(emit_sites, record_type):
    _, sites = emit_sites
    assert record_type in {s["type"] for s in sites if s["resolved"]}


_BAD_EMITTER = '''\
class Driver:
    def flush(self):
        self.logger.log({"event": "round_trip", "round": 1})
        self.logger.log({"event": "spans", "round": 1, "phases": {},
                         "process_index": 0, "bogus_field": 2})
        self.logger.log({"event": "health", "round": 1})
'''


def test_seeded_emitter_violations_carry_file_line(tmp_path):
    path = tmp_path / "bad_emitter.py"
    path.write_text(_BAD_EMITTER)
    violations, _ = schema.check_emit_sites(
        str(tmp_path), log_modules=("bad_emitter.py",), dict_modules=()
    )
    by_kind = {v["kind"]: v for v in violations}
    assert by_kind["emit_unregistered_type"]["where"] == "bad_emitter.py:3"
    assert "round_trip" in by_kind["emit_unregistered_type"]["message"]
    assert by_kind["emit_unregistered_field"]["where"] == "bad_emitter.py:4"
    assert "bogus_field" in by_kind["emit_unregistered_field"]["message"]
    assert by_kind["emit_missing_required"]["where"] == "bad_emitter.py:6"
    assert "'kind'" in by_kind["emit_missing_required"]["message"]


_BAD_CONSUMER = '''\
def report(records):
    out = []
    for rec in records:
        if rec.get("event") == "wombat_census":
            out.append(rec.get("wombats_per_cohort"))
    return out
'''


def test_seeded_consumer_violations_carry_file_line(tmp_path):
    path = tmp_path / "bad_consumer.py"
    path.write_text(_BAD_CONSUMER)
    violations, _ = schema.check_consumers(
        str(tmp_path), modules=("bad_consumer.py",)
    )
    kinds = {v["kind"]: v for v in violations}
    assert kinds["consume_unregistered_type"]["where"] == "bad_consumer.py:4"
    assert "wombat_census" in kinds["consume_unregistered_type"]["message"]
    assert kinds["consume_unregistered_field"]["where"] == "bad_consumer.py:5"
    assert "wombats_per_cohort" in (
        kinds["consume_unregistered_field"]["message"]
    )


def test_validate_records_runtime_rules():
    ok = [
        {"round": 1, "train_loss": 0.5, "examples": 64.0,
         "upload_bytes": 10, "time": 1.0, "schema": 1},
        {"event": "health", "kind": "divergence", "round": 2,
         "loss": 9.9, "time": 1.0, "schema": 1},
    ]
    assert schema.validate_records(ok) == []
    bad = [
        {"event": "never_registered", "time": 1.0, "schema": 1},
        {"round": 3, "examples": 1.0, "time": 1.0, "schema": 1},
        {"event": "spans", "round": 1, "phases": {}, "process_index": 0,
         "surprise": 1, "time": 1.0, "schema": 1},
        {"free": "form"},
    ]
    kinds = [v["kind"] for v in schema.validate_records(bad)]
    assert kinds == ["record_unregistered_type", "record_missing_required",
                     "record_unregistered_field", "record_untyped"]


def test_live_tiny_fit_jsonl_is_fully_registered(tmp_path):
    """Registry completeness (ISSUE 13 satellite): every record type
    AND field a real fit emits — attack provenance, forensic ledger,
    population health, spans/phase costs, run_summary — validates
    against the registry, dynamic keys included."""
    from colearn_federated_learning_tpu.config import get_named_config
    from colearn_federated_learning_tpu.obs.summary import load_records
    from colearn_federated_learning_tpu.server.round_driver import (
        Experiment,
    )

    cfg = get_named_config("mnist_fedavg_2")
    cfg.apply_overrides({
        "data.num_clients": 8,
        "data.synthetic_train_size": 256,
        "data.synthetic_test_size": 64,
        "server.cohort_size": 4,
        "server.num_rounds": 4,
        "server.eval_every": 2,
        "run.engine": "sequential",
        "run.metrics_flush_every": 2,
        "run.out_dir": str(tmp_path),
        "run.obs.client_ledger.enabled": True,
        "run.obs.client_ledger.log_every": 2,
        "run.obs.population.enabled": True,
        "attack.kind": "sign_flip",
        "attack.fraction": 0.25,
    })
    exp = Experiment(cfg.validate())
    exp.fit()
    records = load_records(
        os.path.join(str(tmp_path), f"{cfg.name}.metrics.jsonl")
    )
    assert records, "fit produced no JSONL"
    emitted_types = {
        r.get("event", "round" if "round" in r else None) for r in records
    }
    for t in ("round", "spans", "precision", "attack", "client_ledger",
              "population_health", "run_summary"):
        assert t in emitted_types, (t, sorted(emitted_types))
    violations = schema.validate_records(records)
    assert violations == [], violations


# ---------------------------------------------------------------------------
# the orchestrated check + CLI smoke (tier-1 gate)
# ---------------------------------------------------------------------------


def test_run_check_clean_on_repo():
    report = check_mod.run_check(_ROOT)
    assert report["clean"], report["violations"]
    assert report["analyzer_version"] == check_mod.ANALYZER_VERSION
    text = check_mod.format_report(report)
    assert "OK — no violations" in text


def test_check_cli_smoke_json():
    """`colearn check --json` runs clean on the repo itself — the
    tier-1 gate that makes every future exclusion-matrix / schema /
    purity drift fail the suite."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "colearn_federated_learning_tpu.cli",
         "check", "--json", "--root", _ROOT],
        capture_output=True, text=True, env=env, cwd=_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["clean"] is True
    assert report["capability"]["pairs"] > 500
    assert report["seed_purity"]["files_scanned"] >= 20

