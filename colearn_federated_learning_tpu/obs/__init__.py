"""Observability layer (round-lifecycle telemetry).

Three pillars, each its own module, all host-side and engine-agnostic:

- :mod:`spans` — a low-overhead context-manager tracer for the round
  lifecycle (host inputs → placement → dispatch → fetch → eval →
  checkpoint, plus engine sub-phases), with per-phase aggregation into
  the metrics JSONL and an optional Chrome-trace/Perfetto export.
  Retraces are attributed via ``jax.monitoring`` compile hooks.
- :mod:`counters` — per-round communication byte accounting (pre/post
  compression, uplink + downlink) and device-memory polling.
- :mod:`health` — NaN/Inf + divergence monitoring over the per-round
  loss with configurable abort / checkpoint-and-abort actions.
- :mod:`ledger` — the per-client forensic ledger
  (``run.obs.client_ledger``): in-program cohort statistics + anomaly
  flags scattered into a device-resident per-client store, periodic
  ``client_ledger`` JSONL records, and the ``colearn clients``
  attack-attribution report.
- :mod:`population` — the federation health observatory
  (``run.obs.population``): population/data-plane telemetry for the
  million-client structures — HLL-style unique-client coverage,
  exploration/exploitation draw split, cohort staleness, ledger-pager
  and store-I/O health, participation fairness — as per-flush-window
  ``population_health`` records (count columns engine-parity pinned),
  plus the pure-host ``colearn watch`` live tailer and ``colearn
  population`` report.

Everything is configured through the ``run.obs`` config block
(:class:`~colearn_federated_learning_tpu.config.ObsConfig`); the
``colearn summarize`` CLI subcommand (:mod:`summary`) aggregates a
run's JSONL into a per-phase timing/throughput table.
"""

from colearn_federated_learning_tpu.obs.counters import (  # noqa: F401
    block_step_counts,
    device_memory_stats,
    gossip_round_bytes,
    round_comm_bytes,
    round_host_input_bytes,
    round_shape_stats,
)
from colearn_federated_learning_tpu.obs.health import (  # noqa: F401
    HealthAbortError,
    HealthMonitor,
)
from colearn_federated_learning_tpu.obs.ledger import (  # noqa: F401
    LEDGER_COLS,
    LEDGER_WIDTH,
    STAT_COLS,
    client_round_stats,
    update_ledger,
)
from colearn_federated_learning_tpu.obs.population import (  # noqa: F401
    HLLCounter,
    PopulationTracker,
    SpaceSavingSketch,
)
from colearn_federated_learning_tpu.obs.spans import Tracer  # noqa: F401
