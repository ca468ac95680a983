"""Banded attention's products over the kept pairs of one kind of layer
against the chip's peak, over the time the device spends under that
kind's scope.

Work: scores and values over the pairs inside the band, what the
configuration's FLOP family counts in ``macs_by_part`` under ``part``
(``attention_window``: ``min(t + 1, window)`` keys for query ``t``;
``attention_full``: the causal triangle; pairs x ``heads`` x 2 x
``head_dim`` MACs per layer of that kind and sequence) x 6 (two FLOPs
per product; the backward pass costs twice the forward) x the real
sequences of a round. Time: op self time per round under ``scopes``
(``inner_scope_ms_round``'s reading, on the chip it reads). The masked
pairs of the tiles on the band's two edges and the scores recomputed in
both backward kernels are time and not work. None where the trace has no
such scope (a program from before PR 31) or the family counts no such
part."""

from harness import catalog


def read(ctx, scopes, part):
    scope_ms = catalog.load_reader("inner_scope_ms_round", ctx["bench_dir"])
    ms_round = scope_ms(ctx, scopes=scopes)
    sequences = ctx["counters"]["examples_per_round"]
    spec = ctx["config"]["flops"]
    family = catalog.load_flops_family(spec["fn"], ctx["bench_dir"])
    if not ms_round or not sequences or not hasattr(family, "macs_by_part"):
        return None
    macs = family.macs_by_part(**spec["args"]).get(part)
    if not macs:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * 6.0 * macs * sequences / (ms_round / 1e3) / peak
