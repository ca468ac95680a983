"""Hypothesis property tests for the pure-math core: partitioners,
robust aggregation, compression, and the DP accountant. These sweep the
input space the example-based tests sample pointwise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed in this environment"
)
from hypothesis import given, settings, strategies as st  # noqa: E402

from colearn_federated_learning_tpu.data.partition import (
    dirichlet_partition,
    iid_partition,
    silo_partition,
)
from colearn_federated_learning_tpu.ops.compression import make_compressor
from colearn_federated_learning_tpu.privacy.dp import rdp_epsilon
from colearn_federated_learning_tpu.server.aggregation import robust_reduce

# keep per-example budgets small: every example compiles/executes jax
_SETTINGS = dict(max_examples=25, deadline=None)


def _clients_and_examples(max_clients, min_n, max_n):
    """(clients, n) with at least one example per client: iid_partition
    (and silo_partition through it) refuses fewer, by design — an empty
    shard would only surface rounds later."""
    return st.integers(1, max_clients).flatmap(
        lambda c: st.tuples(st.just(c), st.integers(max(min_n, c), max_n)))


@settings(**_SETTINGS)
@given(clients_n=_clients_and_examples(16, 8, 400),
       seed=st.integers(0, 2**31 - 1))
def test_iid_partition_is_a_partition(clients_n, seed):
    clients, n = clients_n
    shards = iid_partition(n, clients, seed)
    allv = np.concatenate(shards)
    assert len(allv) == n
    assert len(np.unique(allv)) == n  # disjoint + complete


@settings(**_SETTINGS)
@given(
    clients=st.integers(2, 10),
    classes=st.integers(2, 10),
    alpha=st.floats(0.05, 100.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_dirichlet_partition_is_a_partition(clients, classes, alpha, seed):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, clients * 40)
    shards = dirichlet_partition(labels, clients, classes, alpha, seed)
    allv = np.concatenate(shards)
    assert len(np.unique(allv)) == len(allv) == len(labels)
    assert all(len(s) >= 1 for s in shards)


@settings(**_SETTINGS)
@given(clients_n=_clients_and_examples(8, 4, 300),
       seed=st.integers(0, 2**31 - 1))
def test_silo_partition_is_balanced_partition(clients_n, seed):
    clients, n = clients_n
    shards = silo_partition(n, clients, seed)
    allv = np.concatenate(shards)
    assert len(np.unique(allv)) == len(allv) == n
    sizes = [len(s) for s in shards]
    assert max(sizes) - min(sizes) <= 1  # cross-silo equal split


@settings(**_SETTINGS)
@given(
    k=st.integers(1, 12),
    dim=st.integers(1, 20),
    seed=st.integers(0, 2**31 - 1),
    mode=st.sampled_from(["median", "trimmed_mean"]),
    ratio=st.floats(0.0, 0.45),
    data=st.data(),
)
def test_robust_reduce_matches_numpy_oracle(k, dim, seed, mode, ratio, data):
    rng = np.random.default_rng(seed)
    d = rng.normal(size=(k, dim)).astype(np.float32)
    part = data.draw(
        st.lists(st.booleans(), min_size=k, max_size=k).map(np.asarray)
    )
    if not part.any():
        part[rng.integers(k)] = True
    got = np.asarray(
        robust_reduce({"w": jnp.asarray(d)}, jnp.asarray(part), mode, ratio)["w"]
    )
    alive = d[part]
    if mode == "median":
        want = np.median(alive, axis=0)
    else:
        m = len(alive)
        t = int(np.floor(ratio * m))
        s = np.sort(alive, axis=0)
        want = s[t : m - t].mean(0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@settings(**_SETTINGS)
@given(
    dim=st.integers(2, 64),
    seed=st.integers(0, 2**31 - 1),
    ratio=st.floats(0.05, 1.0),
)
def test_topk_keeps_at_least_k_and_only_extremes(dim, seed, ratio):
    rng = np.random.default_rng(seed)
    d = rng.normal(size=(1, dim)).astype(np.float32)
    out = np.asarray(
        make_compressor("topk", topk_ratio=ratio)(
            {"w": jnp.asarray(d)}, jax.random.split(jax.random.PRNGKey(0), 1)
        )["w"]
    )
    k = max(1, int(round(ratio * dim)))
    kept = np.nonzero(out[0])[0]
    # at least k kept (ties at the threshold keep extras), all survivors
    # at least as large as every zeroed coordinate
    assert len(kept) >= min(k, np.count_nonzero(d))
    if len(kept) < dim:
        zeroed = np.setdiff1d(np.arange(dim), kept)
        assert np.abs(d[0][kept]).min() >= np.abs(d[0][zeroed]).max() - 1e-6
    # kept coordinates pass through exactly
    np.testing.assert_array_equal(out[0][kept], d[0][kept])


@settings(**_SETTINGS)
@given(
    sigma=st.floats(0.6, 5.0),
    q=st.floats(0.001, 0.5),
    steps=st.integers(1, 5000),
)
def test_rdp_epsilon_monotone_in_steps_and_noise(sigma, q, steps):
    delta = 1e-5
    e1 = rdp_epsilon(sigma, q, steps, delta)
    e2 = rdp_epsilon(sigma, q, steps + 100, delta)
    assert e2 >= e1 - 1e-9  # more steps, more spend
    e3 = rdp_epsilon(sigma + 0.5, q, steps, delta)
    assert e3 <= e1 + 1e-9  # more noise, less spend
    assert np.isfinite(e1) and e1 >= 0
