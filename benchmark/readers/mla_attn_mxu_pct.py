"""Latent attention's products over the causal pairs against the chip's
peak, over the time the device spends under latent attention's scope.

Work: scores and values over the causal pairs, what the configuration's
FLOP family counts as ``attention`` in ``macs_by_part`` (pairs x
``heads`` x (``qk_nope + qk_rope + v_dim``) MACs per layer and sequence,
x ``layers``) x 6 (two FLOPs per product; the backward pass costs twice
the forward) x the real sequences of a round. Time: op self time per
round under ``scopes`` (``inner_scope_ms_round``'s reading, on the chip
it reads). The masked half of the diagonal tiles and the scores
recomputed in the backward pass are time and not work. None where the
trace has no such scope (a program from before PR 29) or the family
counts no attention of its own."""

from harness import catalog


def read(ctx, scopes):
    scope_ms = catalog.load_reader("inner_scope_ms_round", ctx["bench_dir"])
    ms_round = scope_ms(ctx, scopes=scopes)
    sequences = ctx["counters"]["examples_per_round"]
    spec = ctx["config"]["flops"]
    family = catalog.load_flops_family(spec["fn"], ctx["bench_dir"])
    if not ms_round or not sequences or not hasattr(family, "macs_by_part"):
        return None
    macs = family.macs_by_part(**spec["args"])["attention"]
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * 6.0 * macs * sequences / (ms_round / 1e3) / peak
