"""The control of ``fedavg_mellum2_lm``: the same plain reference with
the islands that the configuration states as float32 (router softmax,
attention softmax, logits) computed in bfloat16, the nearest precision
below. The loss's own arithmetic stays float32.

The cell ``mellum2_silo_16k_lowered`` (unlisted: ``run.py --dry``) puts
it in the stated reference's place at the cell's own size; the
comparison that decides ``correct`` has to print ``agrees: false`` there
by one of ``mellum2_silo_16k``'s limits. PERF.md section 6 holds the
readings."""

import jax.numpy as jnp

from harness import catalog


def run_rounds(exp, config, seed, n_rounds):
    stated = catalog.load_reference("fedavg_mellum2_lm")
    stated.ISLAND = jnp.bfloat16
    return stated.run_rounds(exp, config, seed, n_rounds)
