"""The comparison that decides whether system and plain reference agree.

The reference itself is a file per algorithm under ``references/``
(``references/fedavg.py`` for the first four cells), named by the cell's
``reference.impl`` and found by ``catalog.load_reference``; every one
returns ``(initial params, params after n rounds, train loss per
round)`` from the seeded initial state. This module holds what is common
to all of them: the two error measures and the verdict.

Tolerances live in each cell's file with their reason, one per compared
round (``loss_rel_tols``) and one for the state (``state_rel_l2_tol``);
the reasoning common to all cells:

- round 1's train loss is a forward pass at identical weights on
  identical examples (then the mean over the round's local steps): it
  differs only by the order of bf16 products inside XLA's fusions
  (measured on the chip, PERF.md section 6).
- round k+1's loss is a forward pass at the state after round k, so
  round 2's loss checks the first server step much more sharply than
  the parameter delta can be checked; it has a tolerance of its own,
  near its own readings.
- the state after n rounds differs by reassociation of bf16 sums in
  every local step, compounded over steps and rounds (PR 21 saw bf16
  trajectories of ResNet-18 drift by percents within four rounds), so
  the relative L2 error of the global delta is held to a bound sized by
  the readings; a wrong clip, a missing momentum term or a wrong
  weighting are O(1) and fail it.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np


def _rel_l2(a_tree, b_tree) -> float:
    """||a - b|| / ||b|| over every leaf, in float64 on the host."""
    num = den = 0.0
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree),
                    strict=True):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        num += float(np.sum((a - b) ** 2))
        den += float(np.sum(b ** 2))
    return float(np.sqrt(num / den)) if den > 0 else float("inf")


def compare(system_params, system_losses: List[float], initial, ref_params,
            ref_losses: List[float], tolerances: Dict[str, Any]) -> Dict[str, Any]:
    """Relative error of each compared round's train loss and relative
    L2 error of the global delta (state after the compared rounds minus
    the seeded initial state). ``agrees`` only if every round's loss is
    inside its own tolerance (``loss_rel_tols[k]``) and the delta inside
    ``state_rel_l2_tol``; a tolerance left ``null`` never agrees."""
    def delta(params):
        return jax.tree.map(
            lambda s, i: np.asarray(s, np.float64) - np.asarray(i, np.float64),
            params, initial,
        )

    delta_err = _rel_l2(delta(system_params), delta(ref_params))
    loss_errs = [abs(s - r) / abs(r)
                 for s, r in zip(system_losses, ref_losses, strict=True)]
    out = {
        "losses_system": list(system_losses),
        "losses_reference": list(ref_losses),
        "loss_rel_errs": loss_errs,
        "delta_rel_l2_err": delta_err,
    }
    loss_tols = tolerances.get("loss_rel_tols") or []
    state_tol = tolerances.get("state_rel_l2_tol")
    out["agrees"] = bool(
        len(loss_tols) == len(loss_errs) and state_tol is not None
        and all(t is not None for t in loss_tols)
        and all(np.isfinite(e) for e in loss_errs + [delta_err])
        and all(e <= t for e, t in zip(loss_errs, loss_tols))
        and delta_err <= state_tol
    )
    return out
