"""Federation health observatory: population-scale data-plane telemetry
(``run.obs.population``) and the ``colearn watch`` / ``colearn
population`` CLIs.

PR 9 made every round-loop structure O(cohort); this module is the
observability half that scale story was missing. The structures that
carry a 10⁶-client federation — the streaming score sketch, the ledger
pager, the mmap client store — were nearly blind: run_summary held two
pager totals and nothing else, so a cold-start pager thrash, a sketch
that never covers the attacker population, or a store gather stall were
indistinguishable from "slow". The :class:`PopulationTracker` closes
that gap with one ``population_health`` JSONL record per metrics-flush
window covering four planes:

- **sampler health** — cumulative unique-client coverage via an
  O(1)-memory probabilistic counter (:class:`HLLCounter`, an
  HLL-style register sketch over a fixed splitmix64 hash — seed-pure:
  the same cohort schedule always produces the same estimate),
  the per-window exploration/exploitation draw split (the streaming
  sampler tallies which pool each accepted draw came from), streaming-
  sketch occupancy / refresh age / sketch-vs-universe flag-rate
  coverage, and the cohort staleness distribution (rounds since each
  member's last participation, over a bounded recency map).
- **ledger-pager health** — per-window hit/miss/page-in/eviction/
  page-sync counts and page-sync stall ms, extending the PR 9
  run_summary *totals* into a time series.
- **store I/O** — bytes gathered, gather wall ms, per-shard touch
  counts from ``ShardedRecordArray``, and the union-slab dedup ratio
  under stream placement (rows indexed vs unique rows gathered).
- **participation fairness** — Gini / max-share over a bounded top-k
  participation sketch (:class:`SpaceSavingSketch`), never a dense
  ``[num_clients]`` histogram.

Purity discipline (the wire counters' contract): every tracked
quantity is a pure function of host-side facts that are identical
across the sharded, sequential, and fused engines (the cohort schedule,
the pager's slot bookkeeping, the slab index tensors), so the
count-based columns of ``population_health`` records are engine-parity
PINNED — only wall-clock fields (every key ends in ``_ms``) may differ.
Every structure is O(cohort) per round or fixed-size (HLL registers,
sketch capacity, recency map), so the records themselves survive the
10⁶-client smoke; tracking never touches the device, the rng streams,
or anything the round program consumes.

The CLI half is pure stdlib (importable without a jax backend, like
``obs/summary.py``): :func:`read_complete_records` tails a metrics
JSONL incrementally — a torn (unterminated or mid-record truncated)
tail line is left for the next poll, never crashes the tailer —
:func:`watch_snapshot` / :func:`format_watch` render the live view
(rounds/sec, loss, health/divergence state, pager hit rate, coverage %,
phase-ms sparklines), and :func:`population_report` /
:func:`format_population_report` are the post-hoc twin.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# the O(1)-memory probabilistic unique-client counter
# ---------------------------------------------------------------------------

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer: the fixed, seed-free hash the
    coverage counter buckets client ids with. Fixed constants ⇒ the
    same id always lands in the same register with the same rank, on
    every engine and every run — the counter's seed-purity contract."""
    x = (x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)) & _M64
    x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _M64
    x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _M64
    return x ^ (x >> np.uint64(31))


def _clz64(x: np.ndarray) -> np.ndarray:
    """Vectorized count-leading-zeros over uint64 (binary search —
    exact, unlike float log2 at 64-bit precision)."""
    x = x.astype(np.uint64)
    zero = x == 0
    clz = np.zeros(x.shape, np.int64)
    for s in (32, 16, 8, 4, 2, 1):
        top = x >> np.uint64(64 - s)
        empty = top == 0
        clz += np.where(empty, s, 0)
        x = np.where(empty, x << np.uint64(s), x)
    return np.where(zero, 64, clz)


class HLLCounter:
    """HyperLogLog-style distinct counter: ``2**bits`` one-byte
    registers (4 KiB at the default 12 bits), ~1.04/√m relative error.
    ``add`` is O(batch); memory never grows with the population —
    exactly the structure that lets "how many of the 10⁶ clients has
    this run ever touched" ride every flush window for free."""

    def __init__(self, bits: int = 12):
        if not 4 <= bits <= 18:
            raise ValueError(f"hll bits must be in [4, 18], got {bits}")
        self.bits = int(bits)
        self.m = 1 << self.bits
        self.registers = np.zeros(self.m, np.uint8)

    def add(self, ids) -> None:
        ids = np.asarray(ids, np.uint64).reshape(-1)
        if ids.size == 0:
            return
        h = _splitmix64(ids)
        bucket = (h >> np.uint64(64 - self.bits)).astype(np.int64)
        w = (h << np.uint64(self.bits)) & _M64
        rho = np.minimum(_clz64(w) + 1, 64 - self.bits + 1).astype(np.uint8)
        np.maximum.at(self.registers, bucket, rho)

    def estimate(self) -> int:
        m = float(self.m)
        if m == 16:
            alpha = 0.673
        elif m == 32:
            alpha = 0.697
        elif m == 64:
            alpha = 0.709
        else:
            alpha = 0.7213 / (1.0 + 1.079 / m)
        raw = alpha * m * m / float(
            np.sum(np.exp2(-self.registers.astype(np.float64)))
        )
        zeros = int(np.count_nonzero(self.registers == 0))
        if raw <= 2.5 * m and zeros:
            # small-range (linear counting) correction — near-exact for
            # populations well under the register count
            raw = m * np.log(m / zeros)
        return int(round(raw))


# ---------------------------------------------------------------------------
# the bounded participation sketch (fairness without a dense histogram)
# ---------------------------------------------------------------------------


class SpaceSavingSketch:
    """Metwally et al. space-saving heavy-hitter sketch, capacity-k:
    the top participating clients by (over-)estimated count. At
    capacity the minimum-count row (ties broken by smallest id —
    deterministic) is replaced and inherits its count, so heavy
    participants can never be evicted by light ones. Memory is O(k)
    regardless of how many distinct clients participate."""

    def __init__(self, capacity: int = 64):
        if capacity < 1:
            raise ValueError(f"sketch capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.counts: Dict[int, int] = {}
        self.total = 0

    def add(self, ids) -> None:
        for i in np.asarray(ids, np.int64).reshape(-1):
            i = int(i)
            self.total += 1
            if i in self.counts:
                self.counts[i] += 1
            elif len(self.counts) < self.capacity:
                self.counts[i] = 1
            else:
                victim = min(self.counts, key=lambda c: (self.counts[c], c))
                self.counts[i] = self.counts.pop(victim) + 1

    def top(self, k: int) -> List[Tuple[int, int]]:
        return sorted(
            self.counts.items(), key=lambda kv: (-kv[1], kv[0])
        )[: max(0, int(k))]

    def gini(self) -> float:
        """Gini coefficient over the SKETCH rows (documented: the
        fairness view of the top-k participants, not the full — and
        deliberately never materialized — [num_clients] histogram)."""
        x = np.sort(np.asarray(list(self.counts.values()), np.float64))
        n = len(x)
        s = x.sum()
        if n == 0 or s <= 0:
            return 0.0
        i = np.arange(1, n + 1, dtype=np.float64)
        return float(round(2.0 * np.sum(i * x) / (n * s) - (n + 1.0) / n, 6))

    def max_share(self) -> float:
        if not self.counts or not self.total:
            return 0.0
        return float(round(max(self.counts.values()) / self.total, 6))


# ---------------------------------------------------------------------------
# the per-fit tracker the driver feeds
# ---------------------------------------------------------------------------


class PopulationTracker:
    """Per-fit accumulator behind ``population_health`` records.

    The driver feeds it host-side facts it already has — the realized
    cohort (:meth:`observe_cohort`, pads and zero-weight dropouts
    excluded), the stream-slab dedup shape (:meth:`observe_slab`), the
    streaming sketch refresh (:meth:`observe_sketch_refresh`) — and at
    every metrics-flush boundary :meth:`window_record` folds the window
    plus pager/store deltas into one JSONL record and resets. Coverage,
    fairness, and the pager/store lifetime totals are cumulative;
    everything else is per-window. All structures are fixed-size or
    O(cohort) per round, and all mutation happens on the fit thread —
    the worker-thread paths (store gathers) count inside the
    instrumented objects themselves and are only *read* here."""

    def __init__(self, num_clients: int, top_k: int = 64,
                 hll_bits: int = 12, recency_capacity: int = 8192):
        self.num_clients = int(num_clients)
        self.coverage = HLLCounter(hll_bits)
        self.fairness = SpaceSavingSketch(top_k)
        # bounded last-participation-round map (LRU by insertion order
        # refresh): cohort members absent from it — first-timers, or
        # evicted long-agos — count in `staleness.unknown` rather than
        # skewing the distribution
        from collections import OrderedDict

        self._recency: "OrderedDict[int, int]" = OrderedDict()
        self._recency_cap = max(1, int(recency_capacity))
        # window accumulators (reset by window_record)
        self._w_rounds = 0
        self._w_participants = 0
        self._w_draws: Dict[str, int] = {}
        self._w_stale: List[int] = []
        self._w_first_seen = 0
        self._w_unknown = 0
        self._w_slab_indexed = 0
        self._w_slab_unique = 0
        self._sketch_flag_cov: Optional[float] = None
        # async (fedbuff) window accumulators: realized staleness
        # distribution, admitted-update count, clamp + backpressure
        # totals — fed by the scheduler, folded as the "async" section
        self._w_async_stale: List[float] = []
        self._w_async_max_stale = 0
        self._w_async_steps = 0
        self._w_async_absorbed = 0
        self._w_async_clamped = 0
        self._w_bp_dropped = 0
        self._w_bp_rejected = 0
        # multi-version / hierarchy window accumulators: per-version
        # absorbed counts (server.async_versions > 1), retired-
        # generation re-admissions, and crashed-edge exclusions
        # (server.hierarchy under fedbuff)
        self._w_async_versions: Dict[int, int] = {}
        self._w_async_readmitted = 0
        self._w_edge_crashed = 0
        # churn window accumulators (run.churn realized failures) —
        # fed at flush from the per-round failure stats
        self._w_churn = {"unavailable": 0, "dropped": 0, "crashed": 0}
        self._w_churn_seen = False
        # lifetime baselines for delta-ing the instrumented objects
        self._pager_base = {
            "hits": 0, "misses": 0, "page_ins": 0, "evictions": 0,
            "page_syncs": 0, "sync_ms": 0.0,
        }
        self._store_base: Optional[Dict[str, Any]] = None

    # ---- feeds -------------------------------------------------------

    def observe_cohort(self, round_idx: int, cohort, n_ex,
                       draw_counts: Optional[Dict[str, int]] = None) -> None:
        """One dispatched round's realized participants: ``cohort`` may
        carry poisson pad slots (id == num_clients) and ``n_ex`` zeros
        for dropouts — both are excluded, so "participation" means a
        row that carried aggregation weight."""
        ids = np.asarray(cohort, np.int64).reshape(-1)
        w = np.asarray(n_ex).reshape(-1)
        real = ids[(ids >= 0) & (ids < self.num_clients) & (w > 0)]
        self._w_rounds += 1
        self._w_participants += int(real.size)
        if draw_counts:
            for k, v in draw_counts.items():
                self._w_draws[k] = self._w_draws.get(k, 0) + int(v)
        self.coverage.add(real)
        self.fairness.add(real)
        r = int(round_idx)
        for c in real:
            c = int(c)
            last = self._recency.pop(c, None)
            if last is None:
                if len(self._recency) >= self._recency_cap:
                    self._recency.popitem(last=False)
                    self._w_unknown += 1
                else:
                    self._w_first_seen += 1
            else:
                self._w_stale.append(r - last)
            self._recency[c] = r

    def observe_slab(self, rows_indexed: int, rows_unique: int) -> None:
        """One round's (or fused chunk's) stream-slab gather shape: how
        many grid slots indexed the corpus vs how many unique example
        rows were actually gathered — the dedup ratio is the fraction
        of gather I/O the union slab saved."""
        self._w_slab_indexed += int(rows_indexed)
        self._w_slab_unique += int(rows_unique)

    def observe_async(self, round_idx: int, staleness, *, absorbed: int,
                      clamped: int = 0, bp_dropped: int = 0,
                      bp_rejected: int = 0, readmitted: int = 0,
                      edge_crashed: int = 0,
                      version: Optional[int] = None) -> None:
        """One fedbuff server step's scheduler facts: the popped
        buffer's realized staleness values, how many updates carried
        weight (arrival-rate numerator), and the clamp/backpressure
        counts. ``version`` is the model line this step drove
        (server.async_versions > 1), ``readmitted`` late completions
        folded back from a retired generation, ``edge_crashed`` edge
        aggregators lost this step (server.hierarchy). Pure
        observation on the fit thread (the async scheduler is never
        double-buffered)."""
        s = np.asarray(staleness, np.float64).reshape(-1)
        self._w_async_steps += 1
        self._w_async_absorbed += int(absorbed)
        self._w_async_clamped += int(clamped)
        self._w_bp_dropped += int(bp_dropped)
        self._w_bp_rejected += int(bp_rejected)
        self._w_async_readmitted += int(readmitted)
        self._w_edge_crashed += int(edge_crashed)
        if version is not None:
            v = int(version)
            self._w_async_versions[v] = (
                self._w_async_versions.get(v, 0) + int(absorbed)
            )
        if s.size:
            self._w_async_stale.append(float(s.mean()))
            self._w_async_max_stale = max(
                self._w_async_max_stale, int(s.max())
            )

    def observe_churn(self, unavailable: int, dropped: int,
                      crashed: int) -> None:
        """One round's realized churn failures (run.churn): offline at
        dispatch, hazard-dropped, crashed mid-round — counts only, fed
        at metrics-flush from the per-round failure stats (fit
        thread)."""
        self._w_churn_seen = True
        self._w_churn["unavailable"] += int(unavailable)
        self._w_churn["dropped"] += int(dropped)
        self._w_churn["crashed"] += int(crashed)

    def observe_sketch_refresh(self, total_flagged: float,
                               kept_flagged: float) -> None:
        """Streaming-mode sketch refresh: what fraction of the ledger's
        total flagged mass the retained sketch rows carry — 1.0 means
        the sketch covers every flag-bearing (attacker-evidence) client,
        low values mean the flag suppression cannot see the attackers."""
        self._sketch_flag_cov = (
            round(float(kept_flagged) / float(total_flagged), 6)
            if total_flagged > 0 else None
        )

    # ---- window fold -------------------------------------------------

    @staticmethod
    def _pager_counters(pager) -> Dict[str, float]:
        return {
            "hits": int(pager.hits), "misses": int(pager.misses),
            "page_ins": int(pager.page_ins),
            "evictions": int(pager.evictions),
            "page_syncs": int(pager.page_syncs),
            "sync_ms": float(pager.sync_ms),
        }

    def window_record(self, last_round: int, *, pager=None,
                      store_arrays=(), sketch_ids=None,
                      refresh_age: Optional[int] = None,
                      ) -> Optional[Dict[str, Any]]:
        """Fold the window into one ``population_health`` record (None
        when the window saw no rounds — tail flushes must not emit
        empty records). Count-based fields are engine-parity material;
        wall-clock fields all end in ``_ms``."""
        if self._w_rounds == 0:
            return None
        est = self.coverage.estimate()
        rec: Dict[str, Any] = {
            "event": "population_health",
            "round": int(last_round),
            "window_rounds": self._w_rounds,
            "participants": self._w_participants,
            "coverage": {
                "unique_clients_est": est,
                "coverage_pct": round(
                    100.0 * min(est, self.num_clients) / self.num_clients, 2
                ),
                "num_clients": self.num_clients,
            },
            "fairness": {
                "total_participations": self.fairness.total,
                "tracked": len(self.fairness.counts),
                "gini": self.fairness.gini(),
                "max_share": self.fairness.max_share(),
                "top_clients": [
                    [int(c), int(n)] for c, n in self.fairness.top(5)
                ],
            },
        }
        if self._w_draws:
            rec["draws"] = dict(sorted(self._w_draws.items()))
        stale = {
            "first_seen": self._w_first_seen,
            "known": len(self._w_stale),
        }
        if self._w_unknown:
            stale["unknown"] = self._w_unknown
        if self._w_stale:
            s = np.asarray(self._w_stale, np.float64)
            stale.update({
                "mean": round(float(s.mean()), 3),
                "p50": round(float(np.median(s)), 1),
                "max": int(s.max()),
            })
        rec["staleness"] = stale
        if sketch_ids is not None:
            live = int(np.count_nonzero(np.asarray(sketch_ids) >= 0))
            rec["sketch"] = {
                "rows": live,
                "occupancy": round(live / max(1, len(sketch_ids)), 4),
            }
            if refresh_age is not None:
                rec["sketch"]["refresh_age"] = int(refresh_age)
            if self._sketch_flag_cov is not None:
                rec["sketch"]["flag_coverage"] = self._sketch_flag_cov
        if pager is not None:
            cur = self._pager_counters(pager)
            delta = {k: cur[k] - self._pager_base[k] for k in cur}
            self._pager_base = cur
            looked = delta["hits"] + delta["misses"]
            rec["pager"] = {
                "hits": int(delta["hits"]),
                "misses": int(delta["misses"]),
                "hit_rate": round(delta["hits"] / looked, 4) if looked else 1.0,
                "page_ins": int(delta["page_ins"]),
                "evictions": int(delta["evictions"]),
                "page_syncs": int(delta["page_syncs"]),
                "sync_stall_ms": round(delta["sync_ms"], 3),
            }
        store_stats = [
            a.gather_stats() for a in store_arrays
            if hasattr(a, "gather_stats")
        ]
        if store_stats:
            cur_s = {
                "calls": sum(s["calls"] for s in store_stats),
                "rows": sum(s["rows"] for s in store_stats),
                "bytes": sum(s["bytes"] for s in store_stats),
                "ms": sum(s["ms"] for s in store_stats),
                "io_ms": sum(s.get("io_ms", 0.0) for s in store_stats),
                "pool_gathers": sum(
                    s.get("pool_gathers", 0) for s in store_stats
                ),
                "replica_rows": sum(
                    s.get("replica_rows", 0) for s in store_stats
                ),
            }
            touches = [np.asarray(s["shard_touches"]) for s in store_stats]
            width = max(len(t) for t in touches)
            tot_touch = np.zeros(width, np.int64)
            for t in touches:
                tot_touch[: len(t)] += t
            if self._store_base is None:
                self._store_base = {
                    "calls": 0, "rows": 0, "bytes": 0, "ms": 0.0,
                    "io_ms": 0.0, "pool_gathers": 0, "replica_rows": 0,
                    "touches": np.zeros(width, np.int64),
                }
            base = self._store_base
            rec["store"] = {
                "gather_calls": int(cur_s["calls"] - base["calls"]),
                "rows_gathered": int(cur_s["rows"] - base["rows"]),
                "bytes_gathered": int(cur_s["bytes"] - base["bytes"]),
                "gather_ms": round(cur_s["ms"] - base["ms"], 3),
                # summed per-shard copy time vs the wall gather_ms: the
                # pool's overlap factor reads directly off the pair
                # (io_ms ≈ gather_ms → serial; io_ms >> gather_ms →
                # the worker pool is hiding shard I/O)
                "gather_io_ms": round(
                    cur_s["io_ms"] - base.get("io_ms", 0.0), 3
                ),
                "gather_workers": max(
                    int(s.get("workers", 1)) for s in store_stats
                ),
                "pool_gathers": int(
                    cur_s["pool_gathers"] - base.get("pool_gathers", 0)
                ),
                "shard_touches": [
                    int(v) for v in (tot_touch - base["touches"])
                ],
            }
            replica = int(
                cur_s["replica_rows"] - base.get("replica_rows", 0)
            )
            if replica:
                # multi-host ownership: rows served from NON-owned
                # shards via read-replica fallback this window
                rec["store"]["replica_rows"] = replica
            self._store_base = dict(cur_s, touches=tot_touch)
        if self._w_slab_indexed:
            rec.setdefault("store", {}).update({
                "slab_rows_indexed": self._w_slab_indexed,
                "slab_rows_unique": self._w_slab_unique,
                "slab_dedup_ratio": round(
                    self._w_slab_unique / self._w_slab_indexed, 4
                ),
            })
        if self._w_async_steps:
            # the fedbuff production-traffic panel: arrival rate
            # (absorbed updates per server step), the realized
            # staleness distribution, and clamp/backpressure counts
            a: Dict[str, Any] = {
                "server_steps": self._w_async_steps,
                "updates_absorbed": self._w_async_absorbed,
                "arrival_rate": round(
                    self._w_async_absorbed / self._w_async_steps, 3
                ),
                "staleness_max": self._w_async_max_stale,
            }
            if self._w_async_stale:
                s = np.asarray(self._w_async_stale, np.float64)
                a["staleness_mean"] = round(float(s.mean()), 3)
                a["staleness_p90"] = round(float(np.percentile(s, 90)), 3)
            if self._w_async_clamped:
                a["staleness_clamped"] = self._w_async_clamped
            if self._w_bp_dropped:
                a["backpressure_dropped"] = self._w_bp_dropped
            if self._w_bp_rejected:
                a["backpressure_rejected"] = self._w_bp_rejected
            if self._w_async_versions:
                # per-model-line absorbed counts for this window — the
                # multi-version health panel (a starved line shows up
                # as a near-zero bucket here long before its loss does)
                a["per_version_absorbed"] = {
                    str(v): int(n)
                    for v, n in sorted(self._w_async_versions.items())
                }
            if self._w_async_readmitted:
                a["version_readmitted"] = self._w_async_readmitted
            if self._w_edge_crashed:
                a["edge_crashed"] = self._w_edge_crashed
            rec["async"] = a
        if self._w_churn_seen:
            rec["churn"] = {k: int(v) for k, v in self._w_churn.items()}
        # reset the window
        self._w_rounds = 0
        self._w_participants = 0
        self._w_draws = {}
        self._w_stale = []
        self._w_first_seen = 0
        self._w_unknown = 0
        self._w_slab_indexed = 0
        self._w_slab_unique = 0
        self._w_async_stale = []
        self._w_async_max_stale = 0
        self._w_async_steps = 0
        self._w_async_absorbed = 0
        self._w_async_clamped = 0
        self._w_bp_dropped = 0
        self._w_bp_rejected = 0
        self._w_async_versions = {}
        self._w_async_readmitted = 0
        self._w_edge_crashed = 0
        self._w_churn = {"unavailable": 0, "dropped": 0, "crashed": 0}
        self._w_churn_seen = False
        return rec

    def summary_totals(self, pager=None, store_arrays=()) -> Dict[str, Any]:
        """The population keys ``run_summary`` carries (and ``colearn
        summarize`` renders): lifetime coverage and participation, plus
        the LIVE pager hit rate and store gather bytes (read from the
        instrumented objects directly — the last flush window may have
        folded before the final round landed)."""
        est = self.coverage.estimate()
        out: Dict[str, Any] = {
            "population_unique_clients": est,
            "population_coverage_pct": round(
                100.0 * min(est, self.num_clients) / self.num_clients, 2
            ),
            "population_participations": int(self.fairness.total),
        }
        if pager is not None:
            looked = int(pager.hits) + int(pager.misses)
            if looked:
                out["pager_hit_rate"] = round(int(pager.hits) / looked, 4)
        stats = [
            a.gather_stats() for a in store_arrays
            if hasattr(a, "gather_stats")
        ]
        total_bytes = sum(s["bytes"] for s in stats)
        if total_bytes:
            out["store_gather_bytes"] = int(total_bytes)
            total_ms = sum(s["ms"] for s in stats)
            if total_ms:
                # wall-clock store throughput — the data-plane
                # headline
                out["store_gather_mbps"] = round(
                    total_bytes / (1 << 20) / (total_ms / 1e3), 1
                )
            out["store_gather_workers"] = max(
                int(s.get("workers", 1)) for s in stats
            )
        return out


# ---------------------------------------------------------------------------
# incremental JSONL tailing (`colearn watch` — pure host, no backend)
# ---------------------------------------------------------------------------


def read_complete_records(path: str, offset: int = 0
                          ) -> Tuple[List[Dict[str, Any]], int]:
    """Read every COMPLETE record line past ``offset``; return
    ``(records, new_offset)``. A live writer's torn tail — the final
    line without a terminating newline, possibly truncated mid-record —
    is left unconsumed (the offset stays before it) so the next poll
    rereads it whole; an unparsable *terminated* line (a crash artifact)
    is skipped, matching ``summary.load_records``."""
    with open(path, "rb") as f:
        f.seek(offset)
        data = f.read()
    end = data.rfind(b"\n")
    if end < 0:
        return [], offset
    records: List[Dict[str, Any]] = []
    for line in data[: end + 1].splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except (json.JSONDecodeError, UnicodeDecodeError):
            continue
    return records, offset + end + 1


_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def sparkline(values, width: int = 24) -> str:
    """Unicode block sparkline of the TAIL of a numeric series (empty
    string for no data; a flat series renders mid-blocks)."""
    vals = [float(v) for v in values][-max(1, int(width)):]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK_BLOCKS[3] * len(vals)
    scale = (len(_SPARK_BLOCKS) - 1) / (hi - lo)
    return "".join(
        _SPARK_BLOCKS[int(round((v - lo) * scale))] for v in vals
    )


def watch_snapshot(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold a run's records (complete or mid-fit) into the live-view
    state ``colearn watch`` renders: run state, loss / rounds-per-sec
    series, health + divergence counts, the latest population-health
    coverage and pager hit rate, and per-phase ms series for the
    sparklines. Pure host; tolerant of every historical record shape
    (missing families render as absent keys, never KeyError)."""
    snap: Dict[str, Any] = {
        "state": "running",
        "rounds": 0,
        "loss_series": [],
        "rps_series": [],
        "health": {},
        "phase_ms": {},
    }
    phase_totals: Dict[str, float] = {}
    last_pop = None
    for rec in records:
        ev = rec.get("event")
        if ev == "run_summary":
            snap["state"] = "completed"
            snap["rounds"] = max(snap["rounds"], int(rec.get("rounds", 0)))
            if "wall_time_sec" in rec:
                snap["wall_time_sec"] = float(rec["wall_time_sec"])
            for k in ("population_coverage_pct", "population_unique_clients",
                      "pager_hit_rate", "ledger_evictions",
                      "ledger_page_syncs", "async_updates_per_sec",
                      "async_updates_absorbed", "staleness_clamped",
                      "backpressure_dropped", "backpressure_rejected",
                      "async_staleness_p50", "async_staleness_p90",
                      "async_staleness_max", "async_per_version",
                      "version_readmitted", "hier_edges",
                      "hier_edge_absorbed", "hier_edge_crashed"):
                if k in rec:
                    snap[k] = rec[k]
            continue
        if ev == "health":
            kind = rec.get("kind", "?")
            snap["health"][kind] = snap["health"].get(kind, 0) + 1
            continue
        if ev == "spans":
            for name, agg in (rec.get("phases") or {}).items():
                cnt = int(agg.get("count", 0)) or 1
                mean = float(agg.get("total_ms", 0.0)) / cnt
                snap["phase_ms"].setdefault(name, []).append(round(mean, 3))
                phase_totals[name] = (
                    phase_totals.get(name, 0.0)
                    + float(agg.get("total_ms", 0.0))
                )
            continue
        if ev == "population_health":
            last_pop = rec
            continue
        if ev == "precision":
            snap["precision"] = {
                k: rec.get(k) for k in
                ("param_dtype", "compute_dtype", "local_param_dtype")
                if k in rec
            }
            continue
        if ev is None and "round" in rec:
            snap["rounds"] = max(snap["rounds"], int(rec["round"]))
            if "train_loss" in rec:
                snap["loss_series"].append(float(rec["train_loss"]))
                snap["last_train_loss"] = float(rec["train_loss"])
            if "rounds_per_sec" in rec:
                snap["rps_series"].append(float(rec["rounds_per_sec"]))
                snap["rounds_per_sec"] = float(rec["rounds_per_sec"])
            if "mean_staleness" in rec:
                # the fedbuff staleness-distribution panel's series
                snap.setdefault("staleness_series", []).append(
                    float(rec["mean_staleness"])
                )
            for k in ("eval_loss", "eval_acc"):
                if k in rec:
                    snap.setdefault("eval", {})[k] = float(rec[k])
    if last_pop is not None:
        cov = last_pop.get("coverage") or {}
        if "coverage_pct" in cov:
            snap["coverage_pct"] = cov["coverage_pct"]
            snap["unique_clients_est"] = cov.get("unique_clients_est")
        pager = last_pop.get("pager")
        if pager:
            snap["pager_window"] = {
                k: pager.get(k) for k in
                ("hit_rate", "page_ins", "evictions", "page_syncs")
                if k in pager
            }
        sketch = last_pop.get("sketch")
        if sketch:
            snap["sketch"] = sketch
        asy = last_pop.get("async")
        if asy:
            # arrival-rate / staleness-distribution / backpressure
            # panel (fedbuff under production traffic)
            snap["async"] = asy
        chn = last_pop.get("churn")
        if chn:
            snap["churn"] = chn
    # keep the series bounded for --json consumers and the sparklines
    snap["loss_series"] = snap["loss_series"][-64:]
    snap["rps_series"] = snap["rps_series"][-64:]
    if "staleness_series" in snap:
        snap["staleness_series"] = snap["staleness_series"][-64:]
    # top phases by cumulative time, round-loop family first
    top = sorted(phase_totals, key=lambda n: -phase_totals[n])[:5]
    snap["phase_ms"] = {
        n: snap["phase_ms"][n][-32:] for n in top
    }
    # determinism flight recorder status (run.obs.digest): last
    # verified digest round, chain OK/broken, and any failed resume
    # verification — absent key when the run logs no digests
    from colearn_federated_learning_tpu.obs.digest import (
        watch_digest_status,
    )
    dg = watch_digest_status(records)
    if dg is not None:
        snap["digest"] = dg
    return snap


def format_watch(snap: Dict[str, Any], path: str = "") -> str:
    """Render one watch frame as aligned text with sparklines."""
    lines = []
    state = snap.get("state", "running").upper()
    head = f"watch: {path}" if path else "watch"
    head += f"  [{state}]  round {snap.get('rounds', 0)}"
    if "rounds_per_sec" in snap:
        head += f"  rounds/sec {snap['rounds_per_sec']:.3f}"
    if "wall_time_sec" in snap:
        head += f"  wall {snap['wall_time_sec']:.1f}s"
    lines.append(head)
    if "last_train_loss" in snap:
        line = (
            f"loss  {snap['last_train_loss']:<10.4g}"
            f"{sparkline(snap.get('loss_series', ()))}"
        )
        ev = snap.get("eval")
        if ev:
            line += "   " + "  ".join(
                f"{k}={v:.4f}" for k, v in sorted(ev.items())
            )
        lines.append(line)
    if snap.get("rps_series"):
        lines.append(
            f"r/s   {snap.get('rounds_per_sec', 0.0):<10.3f}"
            f"{sparkline(snap['rps_series'])}"
        )
    health = snap.get("health") or {}
    lines.append(
        "health: " + (
            ", ".join(f"{k}×{v}" for k, v in sorted(health.items()))
            if health else "ok"
        )
    )
    dg = snap.get("digest")
    if dg:
        # flight-recorder status line: the chain verdict is recomputed
        # from the log every frame, so tampering/truncation shows up
        # live, not only at the next resume
        line = (
            f"digest: chain {'OK' if dg.get('chain_ok') else 'BROKEN'}"
            f" through round {dg.get('last_round', 0)}"
        )
        if not dg.get("chain_ok") and dg.get("problems"):
            line += f"  [{dg['problems'][0]}]"
        rf = dg.get("resume_fail")
        if rf:
            line += (
                f"  RESUME-VERIFY FAILED @ round {rf.get('round')}"
                f" ({rf.get('detail', '')})"
            )
        lines.append(line)
    asy = snap.get("async")
    if asy or snap.get("staleness_series"):
        # production-traffic panel: arrival rate, staleness
        # distribution (+ sparkline of the per-round means), clamp and
        # backpressure counters — the fedbuff ops view under churn
        parts = []
        if asy and "arrival_rate" in asy:
            parts.append(f"arrivals {asy['arrival_rate']:.1f} upd/step")
        if asy and "staleness_mean" in asy:
            line = f"staleness {asy['staleness_mean']:.2f}"
            if "staleness_p90" in asy:
                line += f"/p90 {asy['staleness_p90']:.2f}"
            if "staleness_max" in asy:
                line += f"/max {asy['staleness_max']}"
            parts.append(line)
        clamped = (asy or {}).get(
            "staleness_clamped", snap.get("staleness_clamped")
        )
        if clamped:
            parts.append(f"clamped {clamped}")
        bp = ((asy or {}).get("backpressure_dropped", 0)
              + (asy or {}).get("backpressure_rejected", 0)) or (
            (snap.get("backpressure_dropped") or 0)
            + (snap.get("backpressure_rejected") or 0)
        )
        if bp:
            parts.append(f"backpressure {bp}")
        if "async_updates_per_sec" in snap:
            parts.append(f"{snap['async_updates_per_sec']:.1f} upd/s")
        line = "async: " + ("  ".join(parts) if parts else "ok")
        series = snap.get("staleness_series")
        if series:
            line += "  " + sparkline(series)
        lines.append(line)
        # multi-version lines: absorbed per model line this window
        # (a starved line reads ~0 here) plus retired-generation
        # re-admissions; hierarchy: crashed-edge exclusions
        pv = (asy or {}).get(
            "per_version_absorbed", snap.get("async_per_version")
        )
        if pv:
            vparts = [
                f"v{v} {n}" for v, n in sorted(
                    pv.items(), key=lambda kv: int(kv[0])
                )
            ]
            readmit = (asy or {}).get(
                "version_readmitted", snap.get("version_readmitted")
            )
            if readmit:
                vparts.append(f"readmitted {readmit}")
            lines.append("versions: " + "  ".join(vparts))
        crashed_e = (asy or {}).get(
            "edge_crashed", snap.get("hier_edge_crashed")
        )
        if crashed_e:
            lines.append(f"edges: crashed {crashed_e}")
    chn = snap.get("churn")
    if chn:
        lines.append(
            "churn: " + "  ".join(
                f"{k} {v}" for k, v in sorted(chn.items()) if v
            )
        )
    bits = []
    if "coverage_pct" in snap:
        bits.append(f"coverage {snap['coverage_pct']:.1f}%")
    pw = snap.get("pager_window")
    if pw and "hit_rate" in pw:
        bits.append(f"pager hit rate {100.0 * pw['hit_rate']:.1f}%")
    elif "pager_hit_rate" in snap:
        bits.append(f"pager hit rate {100.0 * snap['pager_hit_rate']:.1f}%")
    sk = snap.get("sketch")
    if sk and "occupancy" in sk:
        bits.append(f"sketch occupancy {100.0 * sk['occupancy']:.1f}%")
    if bits:
        lines.append("population: " + "  ".join(bits))
    phases = snap.get("phase_ms") or {}
    if phases:
        lines.append("phase ms (per-window mean):")
        for name, series in phases.items():
            last = series[-1] if series else 0.0
            lines.append(f"  {name:<24}{last:>9.2f}  {sparkline(series)}")
    return "\n".join(lines)


def watch_follow(path: str, interval: float = 2.0, out=None,
                 max_refreshes: Optional[int] = None,
                 clear_screen: Optional[bool] = None) -> int:
    """The live loop behind ``colearn watch``: incremental-tail the
    JSONL, re-render each ``interval`` seconds, stop when the run
    completes (a ``run_summary`` record lands) or after
    ``max_refreshes`` frames (tests / bounded watches). Returns the
    process exit code — 2 when the log never produced a record,
    matching the ``summarize`` empty-log contract."""
    out = out or sys.stdout
    if clear_screen is None:
        clear_screen = hasattr(out, "isatty") and out.isatty()
    offset = 0
    records: List[Dict[str, Any]] = []
    frames = 0
    while True:
        try:
            new, offset = read_complete_records(path, offset)
        except FileNotFoundError:
            new = []
        records.extend(new)
        frames += 1
        if records:
            frame = format_watch(watch_snapshot(records), path)
            if clear_screen:
                out.write("\x1b[2J\x1b[H")
            out.write(frame + "\n")
            out.flush()
            if watch_snapshot(records)["state"] == "completed":
                return 0
        if max_refreshes is not None and frames >= max_refreshes:
            return 0 if records else 2
        try:
            time.sleep(interval)
        except KeyboardInterrupt:
            return 0 if records else 2


# ---------------------------------------------------------------------------
# `colearn population` — the post-hoc report twin
# ---------------------------------------------------------------------------


def population_report(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold a run's ``population_health`` records into the post-hoc
    data-plane report: coverage trajectory, draw-split totals, pager
    and store totals with overall rates, slab dedup, staleness, and the
    final fairness view. Raises ValueError (→ CLI exit 2) when the run
    carried no population records."""
    recs = [r for r in records if r.get("event") == "population_health"]
    if not recs:
        raise ValueError(
            "no population_health records in this run — enable the "
            "federation health observatory with "
            "run.obs.population.enabled=true"
        )
    draws: Dict[str, int] = {}
    pager = {"hits": 0, "misses": 0, "page_ins": 0, "evictions": 0,
             "page_syncs": 0, "sync_stall_ms": 0.0}
    store = {"gather_calls": 0, "rows_gathered": 0, "bytes_gathered": 0,
             "gather_ms": 0.0, "gather_io_ms": 0.0, "pool_gathers": 0,
             "replica_rows": 0, "slab_rows_indexed": 0,
             "slab_rows_unique": 0}
    shard_touches: List[int] = []
    rounds = participants = 0
    gather_workers = 0
    cov_series: List[float] = []
    saw_pager = saw_store = False
    asy = {"server_steps": 0, "updates_absorbed": 0, "staleness_max": 0,
           "staleness_clamped": 0, "backpressure_dropped": 0,
           "backpressure_rejected": 0}
    stale_means: List[float] = []
    churn = {"unavailable": 0, "dropped": 0, "crashed": 0}
    saw_async = saw_churn = False
    for r in recs:
        rounds += int(r.get("window_rounds", 0))
        participants += int(r.get("participants", 0))
        a = r.get("async")
        if a:
            saw_async = True
            for k in ("server_steps", "updates_absorbed",
                      "staleness_clamped", "backpressure_dropped",
                      "backpressure_rejected"):
                asy[k] += int(a.get(k, 0))
            asy["staleness_max"] = max(
                asy["staleness_max"], int(a.get("staleness_max", 0))
            )
            if "staleness_mean" in a:
                stale_means.append(float(a["staleness_mean"]))
        c = r.get("churn")
        if c:
            saw_churn = True
            for k in churn:
                churn[k] += int(c.get(k, 0))
        for k, v in (r.get("draws") or {}).items():
            draws[k] = draws.get(k, 0) + int(v)
        cov = r.get("coverage") or {}
        if "coverage_pct" in cov:
            cov_series.append(float(cov["coverage_pct"]))
        p = r.get("pager")
        if p:
            saw_pager = True
            for k in pager:
                pager[k] += p.get(k, 0)
        s = r.get("store")
        if s:
            saw_store = True
            for k in store:
                store[k] += s.get(k, 0)
            gather_workers = max(
                gather_workers, int(s.get("gather_workers", 0))
            )
            for i, t in enumerate(s.get("shard_touches") or []):
                while len(shard_touches) <= i:
                    shard_touches.append(0)
                shard_touches[i] += int(t)
    last = recs[-1]
    report: Dict[str, Any] = {
        "windows": len(recs),
        "rounds": rounds,
        "participants": participants,
        "coverage": last.get("coverage") or {},
        "coverage_pct_series": cov_series,
        "fairness": last.get("fairness") or {},
        "staleness": last.get("staleness") or {},
    }
    if draws:
        report["draws"] = dict(sorted(draws.items()))
    if saw_async:
        if asy["server_steps"]:
            asy["arrival_rate"] = round(
                asy["updates_absorbed"] / asy["server_steps"], 3
            )
        if stale_means:
            asy["staleness_mean"] = round(
                float(np.mean(stale_means)), 3
            )
        report["async"] = asy
    if saw_churn:
        report["churn"] = churn
    if "sketch" in last:
        report["sketch"] = last["sketch"]
    if saw_pager:
        looked = pager["hits"] + pager["misses"]
        report["pager"] = dict(
            pager,
            hit_rate=round(pager["hits"] / looked, 4) if looked else 1.0,
        )
    if saw_store:
        report["store"] = dict(store)
        if gather_workers:
            report["store"]["gather_workers"] = gather_workers
        if store["gather_ms"]:
            # wall-clock gather throughput — the data-plane headline
            # (`store_gather_mbps`)
            report["store"]["store_gather_mbps"] = round(
                store["bytes_gathered"] / (1 << 20)
                / (store["gather_ms"] / 1e3), 1
            )
        if shard_touches:
            report["store"]["shard_touches"] = shard_touches
        if store["slab_rows_indexed"]:
            report["store"]["slab_dedup_ratio"] = round(
                store["slab_rows_unique"] / store["slab_rows_indexed"], 4
            )
    return report


def _fmt_bytes(n) -> str:
    v = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if v < 1024.0 or unit == "TiB":
            return f"{v:.1f} {unit}" if unit != "B" else f"{int(v)} B"
        v /= 1024.0


def format_population_report(report: Dict[str, Any], path: str = "") -> str:
    """Render the population report as aligned text."""
    lines = []
    head = f"run: {path}" if path else "population health"
    head += (
        f"  windows: {report['windows']}  rounds: {report['rounds']}"
        f"  participations: {report['participants']}"
    )
    lines.append(head)
    cov = report.get("coverage") or {}
    if cov:
        lines.append(
            f"coverage: {cov.get('unique_clients_est', 0)} of "
            f"{cov.get('num_clients', 0)} clients "
            f"({cov.get('coverage_pct', 0.0):.1f}%)  "
            f"{sparkline(report.get('coverage_pct_series', ()))}"
        )
    draws = report.get("draws")
    if draws:
        total = sum(draws.values()) or 1
        lines.append("draw split: " + "  ".join(
            f"{k} {v} ({100.0 * v / total:.0f}%)"
            for k, v in draws.items()
        ))
    sk = report.get("sketch")
    if sk:
        bits = [f"rows {sk.get('rows', 0)}",
                f"occupancy {100.0 * sk.get('occupancy', 0.0):.1f}%"]
        if "refresh_age" in sk:
            bits.append(f"refresh age {sk['refresh_age']} rounds")
        if "flag_coverage" in sk:
            bits.append(f"flag coverage {100.0 * sk['flag_coverage']:.1f}%")
        lines.append("score sketch: " + "  ".join(bits))
    st = report.get("staleness")
    if st and st.get("known"):
        lines.append(
            f"staleness (rounds since last participation): mean "
            f"{st.get('mean', 0.0):.1f}  p50 {st.get('p50', 0.0):.0f}  max "
            f"{st.get('max', 0)}  (+{st.get('first_seen', 0)} first-time)"
        )
    asy = report.get("async")
    if asy:
        line = (
            f"async traffic: {asy.get('updates_absorbed', 0)} updates "
            f"over {asy.get('server_steps', 0)} server steps"
        )
        if "arrival_rate" in asy:
            line += f" ({asy['arrival_rate']:.1f} upd/step)"
        if "staleness_mean" in asy:
            line += (
                f"  staleness mean {asy['staleness_mean']:.2f} "
                f"max {asy.get('staleness_max', 0)}"
            )
        bits = []
        if asy.get("staleness_clamped"):
            bits.append(f"clamped {asy['staleness_clamped']}")
        if asy.get("backpressure_dropped"):
            bits.append(f"bp-dropped {asy['backpressure_dropped']}")
        if asy.get("backpressure_rejected"):
            bits.append(f"bp-rejected {asy['backpressure_rejected']}")
        if bits:
            line += "  " + "  ".join(bits)
        lines.append(line)
    chn = report.get("churn")
    if chn:
        lines.append(
            "churn: " + "  ".join(
                f"{k} {v}" for k, v in sorted(chn.items())
            )
        )
    pg = report.get("pager")
    if pg:
        lines.append(
            f"ledger pager: hit rate {100.0 * pg['hit_rate']:.1f}% "
            f"({pg['hits']} hits / {pg['misses']} misses)  page-ins "
            f"{pg['page_ins']}  evictions {pg['evictions']}  syncs "
            f"{pg['page_syncs']} ({pg['sync_stall_ms']:.1f} ms stalled)"
        )
    st = report.get("store")
    if st:
        line = (
            f"store I/O: {_fmt_bytes(st.get('bytes_gathered', 0))} gathered "
            f"in {st.get('gather_calls', 0)} gathers "
            f"({st.get('gather_ms', 0.0):.1f} ms)"
        )
        if "store_gather_mbps" in st:
            line += f"  {st['store_gather_mbps']:.0f} MiB/s"
        if st.get("gather_workers", 0) > 1:
            line += (
                f"  pool x{st['gather_workers']} "
                f"(io {st.get('gather_io_ms', 0.0):.1f} ms summed)"
            )
        if st.get("replica_rows"):
            line += f"  replica rows {st['replica_rows']}"
        if "slab_dedup_ratio" in st:
            line += (
                f"  slab dedup {st['slab_dedup_ratio']:.2f} "
                f"({st['slab_rows_unique']}/{st['slab_rows_indexed']} rows)"
            )
        lines.append(line)
        touches = st.get("shard_touches")
        if touches:
            lines.append(
                "shard touches: "
                + " ".join(f"s{i}:{t}" for i, t in enumerate(touches))
            )
    fair = report.get("fairness") or {}
    if fair:
        lines.append(
            f"fairness (top-{fair.get('tracked', 0)} sketch): gini "
            f"{fair.get('gini', 0.0):.3f}  max share "
            f"{100.0 * fair.get('max_share', 0.0):.2f}%  top clients "
            + ", ".join(
                f"{c}×{n}" for c, n in (fair.get("top_clients") or [])
            )
        )
    return "\n".join(lines)


def strip_timing_keys(obj):
    """Recursively drop every ``*_ms`` key — the parity tests' helper
    for comparing population records across engines (wall-clock is the
    ONE record family allowed to differ; counts must be identical)."""
    if isinstance(obj, dict):
        return {
            k: strip_timing_keys(v) for k, v in obj.items()
            if not (isinstance(k, str) and k.endswith("_ms"))
        }
    if isinstance(obj, list):
        return [strip_timing_keys(v) for v in obj]
    return obj
