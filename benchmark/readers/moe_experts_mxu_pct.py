"""The held experts' products against the chip's peak, over the time the
device spends under the expert layer's scope.

Work: ``expert_macs`` of the configuration's FLOP family (the held
experts' three products of one sequence's forward pass, by expectation)
x 6 (two FLOPs per product; the backward pass costs twice the forward) x
the real sequences of a round. Time: op self time per round under
``scopes`` (``inner_scope_ms_round``'s reading, on the chip it reads).
The forward pass the layer's rematerialisation repeats and the padding
rows of the expert tiles are time and not work, so the share stays
under what the products alone achieve. None where the trace has no such
scope (a program from before PR 25) or the family counts no experts."""

from harness import catalog


def read(ctx, scopes):
    scope_ms = catalog.load_reader("inner_scope_ms_round", ctx["bench_dir"])
    ms_round = scope_ms(ctx, scopes=scopes)
    sequences = ctx["counters"]["examples_per_round"]
    spec = ctx["config"]["flops"]
    family = catalog.load_flops_family(spec["fn"], ctx["bench_dir"])
    if not ms_round or not sequences or not hasattr(family, "expert_macs"):
        return None
    flops = 6.0 * family.expert_macs(**spec["args"]) * sequences
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (ms_round / 1e3) / peak
