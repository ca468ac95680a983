"""The control of ``fedavg_axk1_lora``: the same plain reference with the
islands that the configuration states as float32 (the router's sigmoid
scores and the selection, the attention softmax, the logits) computed in
bfloat16, the nearest precision below. The loss's own arithmetic stays
float32.

The cell ``axk1_silo_lora_4k_lowered`` (unlisted: ``run.py --dry``) puts
it in the stated reference's place at the cell's own size; the
comparison that decides ``correct`` has to print ``agrees: false`` there
by one of ``axk1_silo_lora_4k``'s limits. PERF.md section 6 holds the
readings."""

import jax.numpy as jnp

from harness import catalog


def run_rounds(exp, config, seed, n_rounds):
    stated = catalog.load_reference("fedavg_axk1_lora")
    stated.ISLAND = jnp.bfloat16
    return stated.run_rounds(exp, config, seed, n_rounds)
