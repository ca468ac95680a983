"""``references/fedavg.py``'s plain FedAvg rounds for a cell whose
schedule is derived in the round program (``run.control_plane=device``).

``fedavg.py`` takes each round's cohort and example order from
``Experiment._host_inputs``, the host pipeline's. Under the device
control plane the program derives them itself from (seed, round): the
same cohorts (the plan's table is built by the unchanged host sampler),
another example order (a seed-pure rotation of each client's shard in
place of the host's permutation). Both are inputs of the run, not
results, so this reference reads the plan's schedule
(``Experiment._schedule_fn`` over the placed plan arrays, one jitted
call a round) where ``fedavg.py`` reads the host's, and leaves the
rounds themselves (clients, steps, optimizer, weighting, dtype policy)
to ``fedavg.run_rounds``, unchanged."""

import jax
import jax.numpy as jnp

from harness import catalog


class _DevicePlanInputs:
    """The experiment, with ``_host_inputs`` answered from the device
    plan: (cohort, idx, [K, 2] spec, n_ex, None) of round ``r``."""

    def __init__(self, exp):
        if exp._device_plan is None:
            raise NotImplementedError(
                "references/fedavg_device_plan.py covers "
                "run.control_plane=device only")
        self._exp = exp
        self._schedule = jax.jit(exp._schedule_fn)

    def __getattr__(self, name):
        return getattr(self._exp, name)

    def _host_inputs(self, r):
        s = jax.device_get(
            self._schedule(self._exp._device_arrays, jnp.int32(r)))
        return s["cohort"], s["idx"], s["spec"], s["n_ex"], None


def run_rounds(exp, config, seed, n_rounds):
    fedavg = catalog.load_reference("fedavg")
    return fedavg.run_rounds(_DevicePlanInputs(exp), config, seed, n_rounds)
