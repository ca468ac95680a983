"""A control of ``fedavg_mellum2_lm``: the same plain reference with the
full layers turned by the sliding layers' rope (the published
frequencies, no attention factor), which is what a program with one
angle table would compute.

The cell ``mellum2_silo_16k_one_rope`` (unlisted: ``run.py --dry``) puts
it in the stated reference's place at the cell's own size; the
comparison that decides ``correct`` has to print ``agrees: false`` there
by one of ``mellum2_silo_16k``'s limits. PERF.md section 6 holds the
readings."""

from harness import catalog


def run_rounds(exp, config, seed, n_rounds):
    stated = catalog.load_reference("fedavg_mellum2_lm")
    by_kind = stated.rope_of
    stated.rope_of = lambda kind, positions, sizes: by_kind(
        "sliding", positions, sizes)
    return stated.run_rounds(exp, config, seed, n_rounds)
