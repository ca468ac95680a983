"""Model FLOP/s utilisation from the device trace's own round rate.

Useful FLOPs per round: forward and backward of every real (unmasked)
example once, from shapes (``harness/flops.py`` and the
configuration's ``flops/<family>.py``); padded steps and DP's
recomputation do not count. The rate is rounds per second of the steady
window (``fuse`` rounds per execution of the round program)."""


def read(ctx):
    windows = ctx["windows"]
    examples = ctx["counters"]["examples_per_round"]
    if not windows or not examples:
        return None
    _, lo, hi, periods = windows[0]
    rounds_per_s = periods * ctx["fuse"] / ((hi - lo) / 1e9)
    useful = ctx["flops"].useful_round_flops(ctx["config"]["flops"], examples,
                                             ctx["bench_dir"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["cell"]["chips"]
    return 100.0 * useful * rounds_per_s / peak
