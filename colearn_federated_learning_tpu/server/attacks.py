"""Byzantine adversary simulation (the attack half of the robustness
story; the defenses live in server/aggregation.py).

The robust aggregation stack (krum / median / trimmed_mean) exists to
survive *adversarial* clients, but without an attacker in the loop those
code paths can only be unit-tested against hand-crafted tensors. This
module puts a live adversary inside ``fit``: ``AttackConfig``
(config.py) selects a deterministic ``(seed)``-pure set of compromised
client ids, and per round the engines transform those clients' uploads
*inside the round program* — a ``[K]`` byzantine-mask input rides
alongside ``n_ex``, so nothing retraces and the sharded and sequential
engines stay in exact parity on attacked rounds.

Attack models, placed where a real attacker sits:

**Upload attacks** (``UPLOAD_ATTACKS``) — the compromised client
controls its wire message. Applied to the per-client delta stack after
clipping/compression (which an honest client performs as part of its
update rule) and immediately before aggregation:

- ``sign_flip`` — ``Δ ← −scale·Δ``: the scaled sign-flipping /
  gradient-reversal attack (the classic baseline in Blanchard et al.
  2017). ``scale = 1`` is the pure flip; the default boost makes
  ``f = 2/8`` reliably destroy an undefended weighted mean.
- ``gauss``     — ``Δ ← eps·N(0, I)``: noise *replacement* (the
  "Gaussian" Byzantine worker of Blanchard et al. 2017) — the upload
  carries no signal at all.
- ``scale``     — ``Δ ← scale·Δ``: model-replacement boosting
  (Bagdasaryan et al. 2020): the attacker amplifies its local update
  so it dominates the mean.
- ``alie``      — "a little is enough" (Baruch et al. 2019): the
  colluding attackers estimate the per-coordinate mean μ and std σ of
  the *honest* cohort updates and all upload ``μ − eps·σ`` — a
  perturbation small enough to hide inside the empirical spread (defeats
  naive outlier filters) yet consistently biased.

**Data attack** — ``label_flip``: the compromised clients' *training
labels* are flipped ``y → (C−1) − y`` in the host data path before the
corpus is placed (data poisoning; the upload itself is an honest
gradient of poisoned data). No engine involvement.

Pairing rules (which combinations are rejected and why) live in
config.validate(), and nowhere else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# attacks applied to the upload (engine-side); label_flip is host-side
UPLOAD_ATTACKS = ("sign_flip", "gauss", "scale", "alie")
ATTACK_KINDS = UPLOAD_ATTACKS + ("label_flip",)

# fold constant separating the gauss-attack noise streams from every
# other per-client stream family (trainer steps, qsgd dither) — MUST be
# identical in both engines (the noise is part of the parity contract)
_ATTACK_FOLD = 0xBAD5EED


def select_compromised(num_clients: int, fraction: float, seed: int) -> np.ndarray:
    """The run's compromised client ids: a deterministic pure function
    of ``(seed, num_clients, fraction)`` — the same federation attacked
    twice is attacked identically, and the sharded/sequential engines
    (and any resumed run) agree on who the adversary owns.

    ``round(fraction · N)`` clients, floored at 1 (an attack config
    with zero attackers would silently be a benign run), drawn without
    replacement and sorted for stable logging."""
    n_byz = max(1, int(round(fraction * num_clients)))
    n_byz = min(n_byz, num_clients)
    rng = np.random.default_rng((seed, 0xB12A))
    ids = rng.choice(num_clients, size=n_byz, replace=False)
    return np.sort(ids).astype(np.int64)


def flip_labels(train_y: np.ndarray, client_indices, compromised: np.ndarray,
                num_classes: int) -> np.ndarray:
    """Label-flip data poisoning: ``y → (C−1) − y`` on the compromised
    clients' shards only. Client shards are disjoint example-id sets,
    so flipping their rows in a COPY of the corpus poisons exactly the
    attackers' local datasets — honest clients (and the test set) are
    untouched."""
    out = np.array(train_y, copy=True)
    for cid in compromised:
        rows = client_indices[int(cid)]
        out[rows] = (num_classes - 1) - out[rows]
    return out


def apply_upload_attack(deltas, byz, keys, kind: str, scale: float,
                        eps: float, participation=None):
    """Transform the compromised rows of a ``[K, ...]`` stacked delta
    tree (f32). ``byz``: ``[K]`` 0/1 mask of compromised cohort slots;
    ``keys``: the cohort's ``[K]`` per-round PRNG keys (the same array
    both engines derive via ``jax.random.split(rng, K)`` — the gauss
    streams fold from them per (client, leaf), so the result is
    independent of lane/vmap blocking); ``participation``: ``[K]`` bool
    (``n_ex > 0``), required by ``alie`` to estimate honest statistics.

    Shared verbatim by the sharded engine (on the client-sharded stack,
    under jit — GSPMD inserts any cross-lane collectives), the
    sequential oracle, and the gossip engine (on local-update deltas) —
    one implementation is the parity argument."""
    if kind not in UPLOAD_ATTACKS:
        raise ValueError(f"unknown upload attack {kind!r}")
    b = (byz > 0).astype(jnp.float32)  # [K]

    def bshape(v, d):
        return v.reshape((v.shape[0],) + (1,) * (d.ndim - 1))

    if kind == "sign_flip":
        # Δ·(1 − b·(1 + scale)) == Δ honest, −scale·Δ compromised
        return jax.tree.map(
            lambda d: d * (1.0 - bshape(b, d) * (1.0 + scale)), deltas
        )
    if kind == "scale":
        return jax.tree.map(
            lambda d: d * (1.0 + bshape(b, d) * (scale - 1.0)), deltas
        )
    if kind == "gauss":
        leaves, treedef = jax.tree.flatten(deltas)
        out = []
        for i, d in enumerate(leaves):
            ks = jax.vmap(
                lambda k, i=i: jax.random.fold_in(
                    jax.random.fold_in(k, _ATTACK_FOLD), i
                )
            )(keys)
            noise = jax.vmap(
                lambda kk, s=d.shape[1:]: jax.random.normal(kk, s, jnp.float32)
            )(ks)
            out.append(jnp.where(bshape(b, d) > 0, eps * noise, d))
        return jax.tree.unflatten(treedef, out)
    # alie: per-coordinate honest mean/std → μ − eps·σ on every
    # compromised row (the colluders all send the identical message)
    part = (
        jnp.ones_like(b) if participation is None
        else (participation > 0).astype(jnp.float32)
    )
    h = part * (1.0 - b)  # honest participants
    n_h = jnp.maximum(h.sum(), 1.0)

    def leaf(d):
        hb = bshape(h, d)
        mu = (hb * d).sum(0) / n_h
        sigma = jnp.sqrt((hb * (d - mu[None]) ** 2).sum(0) / n_h)
        poisoned = mu - eps * sigma
        return jnp.where(bshape(b, d) > 0, poisoned[None], d)

    return jax.tree.map(leaf, deltas)


def stack_weighted_mean(deltas, n_ex, mode: str, params, trust=None):
    """FedAvg weighted mean over a ``[K, ...]`` stacked delta tree —
    the stacked-path twin of the engines' in-lane psum accumulation,
    used on attacked rounds (the attack transform needs the per-client
    stack, so the weighted mean runs after it). Identical jnp ops in
    both engines ⇒ attacked-round aggregation parity is exact given
    identical stacks. Result cast to the params dtype, matching the
    psum path's accumulator. ``trust``: optional ``[K]`` reputation
    weights (server/aggregation.py ``reputation_weights``) folded
    multiplicatively into the FedAvg weights — numerator and
    denominator, a true reweighted mean."""
    w = (
        n_ex.astype(jnp.float32) if mode == "examples"
        else (n_ex > 0).astype(jnp.float32)
    )
    if trust is not None:
        w = w * trust.astype(jnp.float32)
    w_sum = w.sum()
    denom = jnp.where(w_sum > 0, w_sum, 1.0)
    return jax.tree.map(
        lambda d, p: (
            jnp.einsum("k,k...->...", w, d.astype(jnp.float32)) / denom
        ).astype(p.dtype),
        deltas, params,
    )
