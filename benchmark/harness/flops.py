"""Operations, parameters and bytes from shapes — the benchmark's own count.

The count is what the algorithm needs, not what the compiler emitted:
one multiply-accumulate of every convolution and matrix product counts 2
FLOPs; a backward pass costs twice the forward's products (one for the
input gradient, one for the weight gradient), so training one example is
3x the forward. Normalisation, activations, softmax, pooling, the loss
and the optimizer are elementwise and left out (under 1 % here). Work
the program repeats or wastes does not count: padded (masked-out) steps,
DP's per-example re-evaluation of the loss, rematerialisation.

A configuration file names its model family and the family's arguments
under ``"flops": {"fn": <family>, "args": {...}}``; the family is the
file ``flops/<family>.py`` with ``forward_macs(**args)`` and
``parameters(**args)``, found by name (``catalog.load_flops_family``),
so a new family is a new file.
"""

from __future__ import annotations

from typing import Any, Dict

from harness import catalog

BENCH_DIR = catalog.BENCH_DIR


def forward_macs(spec: Dict[str, Any], bench_dir: str = BENCH_DIR) -> int:
    family = catalog.load_flops_family(spec["fn"], bench_dir)
    return family.forward_macs(**spec["args"])


def parameters(spec: Dict[str, Any], bench_dir: str = BENCH_DIR) -> int:
    family = catalog.load_flops_family(spec["fn"], bench_dir)
    return family.parameters(**spec["args"])


def train_flops_per_example(spec: Dict[str, Any],
                            bench_dir: str = BENCH_DIR) -> int:
    """Forward and backward of one example: 2 FLOPs per MAC, backward
    twice the forward."""
    return 3 * 2 * forward_macs(spec, bench_dir)


def useful_round_flops(spec: Dict[str, Any], real_examples: float,
                       bench_dir: str = BENCH_DIR) -> float:
    """What one federated round has to compute: every real (unmasked)
    example once through forward and backward."""
    return train_flops_per_example(spec, bench_dir) * float(real_examples)


def apply_kernel_bytes(n_params: int, momentum: bool = False) -> int:
    """HBM traffic the fused server-apply kernel needs (float32):
    read the mean delta and the parameters, write the parameters; with
    server momentum also read and write the trace. The flatten and
    unflatten copies around the kernel are XLA's and are not counted."""
    passes = 5 if momentum else 3
    return passes * 4 * int(n_params)
