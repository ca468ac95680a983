"""Static invariant analysis (`colearn check` — docs/DESIGN.md
"Static invariants & capability matrix").

Three pure-host analyzers turn the repo's hand-maintained correctness
disciplines into checked artifacts:

- :mod:`analysis.capability` — enumerates the config pairing space,
  runs ``config.validate()`` (the one place that refuses a pairing) on
  every pairing, and fails where the verdicts or reasons differ from
  the checked-in ``capability_matrix.json`` golden, or on a reason-less
  rejection.
- :mod:`analysis.seed_purity` — AST lint of the program-path and
  record-producing modules for wall-clock reads, unseeded RNG, and
  bare ``assert`` in library code, against the checked-in
  ``seed_purity_allowlist.json`` that documents each genuine timing
  site.
- :mod:`analysis.schema` — the JSONL record-type registry, statically
  cross-checked against the MetricsLogger emit sites and the
  summarize/watch/population/clients consumers (plus a runtime
  validator the tier-1 tests run over a live fit's JSONL).

:mod:`analysis.check` orchestrates all three; ``colearn check`` is the
CLI entry (exit 1 names each violation, ``--json`` for tooling).
"""

from colearn_federated_learning_tpu.analysis.check import (  # noqa: F401
    ANALYZER_VERSION,
    run_check,
)
