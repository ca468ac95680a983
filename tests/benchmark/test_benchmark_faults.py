"""A run with the timed path broken underneath has to print ``correct:
false``. ``benchmark/run.py``'s ``main`` is driven in this process on a
rehearsal preset (``--dry`` is all that is skipped: the look for a chip),
with one fault planted beneath the harness, where the reference cannot
see it: the reference reads the honest inputs (``_host_inputs``) and
computes on one device. The faults a training cell can have: a dispatch
that hands back the state it was given; half of every client's examples
left out, the mean taken over the rest; the other lanes' sums never
arriving in the exchange between chips. The same driver without a fault
prints ``correct: true``."""

import json

import numpy as np
import pytest

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from startup_probe import load_run


def _state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.server.round_driver import Experiment

    real = Experiment.run_round

    def run_round(self, state, round_idx, *args, **kwargs):
        kept = jax.tree.map(jnp.copy, state["params"])  # the input is donated
        out = real(self, state, round_idx, *args, **kwargs)
        out["params"] = kept
        return out

    monkeypatch.setattr(Experiment, "run_round", run_round)


def _half_the_examples(monkeypatch):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    real = Experiment._round_inputs

    def round_inputs(self, round_idx, place=True, shape=None):
        cohort, idx, mask, n_ex, x, y, n_host = real(
            self, round_idx, place, shape)
        assert not place and np.ndim(mask) == 2  # the fused path's host spec
        mask = np.array(mask)
        mask[:, 0] //= 2  # (examples, valid steps) per client
        return cohort, idx, mask, n_ex, x, y, n_host

    monkeypatch.setattr(Experiment, "_round_inputs", round_inputs)


def _no_exchange(monkeypatch):
    """Only lane 0's sums come out of the psum over the client lanes."""
    import jax
    import jax.numpy as jnp

    real = jax.lax.psum

    def psum(x, axis_name, **kwargs):
        if axis_name != "clients":
            return real(x, axis_name, **kwargs)
        first = jax.lax.axis_index(axis_name) == 0
        mine = jax.tree.map(lambda a: jnp.where(first, a, jnp.zeros_like(a)), x)
        return real(mine, axis_name, **kwargs)

    monkeypatch.setattr(jax.lax, "psum", psum)


# (preset, fault, the compared numbers that have to leave their limits)
CASES = [
    ("dry_r18_fused", None, ()),
    ("dry_r18_fused", _state_unchanged, ("ref_delta_rel_l2_err",)),
    ("dry_r18_fused", _half_the_examples, ("ref_loss_rel_err_round_1",
                                            "ref_delta_rel_l2_err")),
    ("dry_r18_x4", None, ()),
    ("dry_r18_x4", _no_exchange, ("ref_loss_rel_err_round_1",
                                  "ref_delta_rel_l2_err")),
]


@pytest.mark.parametrize(
    "preset,fault,fails", CASES,
    ids=[f"{p}-{f.__name__.strip('_') if f else 'sound'}" for p, f, _ in CASES])
def test_a_fault_beneath_the_harness_reads_not_correct(
        preset, fault, fails, monkeypatch, capsys):
    bench = load_run()
    if fault is not None:
        fault(monkeypatch)
    assert bench.main(["--workload", preset, "--seed", "2147484001",
                       "--seconds", "1", "--dry"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    outside = [n for n, c in last["checks"].items() if not bench.inside(c)]
    assert last["correct"] is (fault is None), last["checks"]
    assert set(fails) <= set(outside) and bool(outside) is (fault is not None)
    if fault is _state_unchanged:
        # a state handed back unchanged reads 1 by this measure
        assert last["checks"]["ref_delta_rel_l2_err"][0] == pytest.approx(1.0)
    # nothing failed and every loss is finite: only the comparison sees it
    assert last["failed"] == 0
    assert bench.inside(last["checks"]["loss_round_4"])
