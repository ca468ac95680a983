"""The indexer's products against the chip's peak, over the time the
device spends under the indexer's scope.

Work: what the configuration's FLOP family counts for the indexer in
``forward_macs`` (per layer and sequence ``seq_len x hidden x
(index_heads x index_head_dim + index_head_dim + index_heads)`` MACs of
projections and ``causal pairs x index_heads x index_head_dim`` of index
scores), read as the difference between ``forward_macs`` with the
indexer and without it, so that the two cannot drift apart, x 6 (two
FLOPs per product; the backward pass costs twice the forward) x the real
sequences of a round. Time: op self time per round under ``scopes``
(``inner_scope_ms_round``'s reading, on the chip it reads). The index
scores are computed more than once a step (for the selection, and again
on the way to their gradient) and a chunk scores the non-causal pairs of
its own diagonal block too: both are time and not work, and every one of
these products is 64 deep or 64 wide, half of what a 128 x 128 MXU
holds, so the share stays far under what a full-width product achieves.
None where the trace has no such scope (a program from before PR 25) or
the family counts no indexer."""

from harness import catalog


def read(ctx, scopes):
    scope_ms = catalog.load_reader("inner_scope_ms_round", ctx["bench_dir"])
    ms_round = scope_ms(ctx, scopes=scopes)
    sequences = ctx["counters"]["examples_per_round"]
    spec = ctx["config"]["flops"]
    args = spec["args"]
    if not ms_round or not sequences or "index_heads" not in args:
        return None
    family = catalog.load_flops_family(spec["fn"], ctx["bench_dir"])
    without = dict(args, index_heads=0, index_head_dim=0)
    macs = family.forward_macs(**args) - family.forward_macs(**without)
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * 6.0 * macs * sequences / (ms_round / 1e3) / peak
