"""The fused server-apply kernel against the HBM roofline.

The least time the chip could take is the kernel's bytes over the HBM
peak (it is a bandwidth-bound elementwise pass); the share is that over
the kernel's measured time per call. The kernel is found by its
``op_name`` (``.../round_server_apply/pallas_call``): XLA names the
instruction after the innermost scope, ``round_server_apply.<n>`` on one
chip and ``shard_map.<n>`` inside the four-chip manual region."""

import re


def read(ctx, op_name):
    windows = ctx["windows"]
    if not windows:
        return None
    pattern = re.compile(op_name)
    times = [op.dur for dev, lo, hi, _ in windows for op in dev.ops
             if lo <= op.start < hi and pattern.search(op.scope)]
    if not times:
        return None
    flops = ctx["flops"]
    n_params = flops.parameters(ctx["config"]["flops"], ctx["bench_dir"])
    need = flops.apply_kernel_bytes(n_params, ctx["counters"]["server_momentum"])
    least_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(times) / len(times) / 1e9)
