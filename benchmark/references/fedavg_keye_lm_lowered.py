"""The control of ``fedavg_keye_lm``: the same plain reference with the
islands that the configuration states as float32 (router softmax, index
scores and selection, attention softmax, logits) computed in bfloat16,
the nearest precision below. Both losses' own arithmetic stays float32.

The cell ``keye_silo_8k_lowered`` (unlisted: ``run.py --dry``) puts it
in the stated reference's place at the cell's own size; the comparison
that decides ``correct`` has to print ``agrees: false`` there by one of
``keye_silo_8k``'s limits. PERF.md section 6 holds the readings."""

import jax.numpy as jnp

from harness import catalog


def run_rounds(exp, config, seed, n_rounds):
    stated = catalog.load_reference("fedavg_keye_lm")
    stated.ISLAND = jnp.bfloat16
    return stated.run_rounds(exp, config, seed, n_rounds)
