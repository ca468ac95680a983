"""A control of ``fedavg_mellum2_lm``: the same plain reference with the
sliding layers' band widened to the whole causal triangle, which is what
a program that forgot the window would compute.

The cell ``mellum2_silo_16k_triangle`` (unlisted: ``run.py --dry``) puts
it in the stated reference's place at the cell's own size; the
comparison that decides ``correct`` has to print ``agrees: false`` there
by one of ``mellum2_silo_16k``'s limits. PERF.md section 6 holds the
readings."""

from harness import catalog


def run_rounds(exp, config, seed, n_rounds):
    stated = catalog.load_reference("fedavg_mellum2_lm")
    stated.window_of = lambda kind, sizes: None
    return stated.run_rounds(exp, config, seed, n_rounds)
