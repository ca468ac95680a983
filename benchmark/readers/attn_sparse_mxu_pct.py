"""Attention's products over the selected pairs against the chip's peak,
over the time the device spends under selected attention's scope.

Work: scores and values over the *selected* pairs, exactly what the
configuration's FLOP family counts in ``forward_macs`` (2 x
``selected_pairs(seq_len, index_topk)`` x ``heads`` x ``head_dim`` MACs
per layer and sequence) x ``layers`` x 6 (two FLOPs per product; the
backward pass costs twice the forward) x the real sequences of a round.
Time: op self time per round under ``scopes`` (``inner_scope_ms_round``'s
reading, on the chip it reads). The masked pairs a tile holds beside the
selected ones, the scores recomputed in the backward pass and the second
pass for the heads' mean are time and not work, so the share stays far
under what the products alone achieve. None where the trace has no such
scope (a program from before PR 25) or the family counts no selection."""

from harness import catalog


def read(ctx, scopes):
    scope_ms = catalog.load_reader("inner_scope_ms_round", ctx["bench_dir"])
    ms_round = scope_ms(ctx, scopes=scopes)
    sequences = ctx["counters"]["examples_per_round"]
    spec = ctx["config"]["flops"]
    family = catalog.load_flops_family(spec["fn"], ctx["bench_dir"])
    if not ms_round or not sequences or not hasattr(family, "selected_pairs"):
        return None
    a = spec["args"]
    macs = (2 * family.selected_pairs(a["seq_len"], a["index_topk"])
            * a["heads"] * a["head_dim"] * a["layers"])
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * 6.0 * macs * sequences / (ms_round / 1e3) / peak
