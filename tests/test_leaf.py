"""LEAF loaders against synthetic on-disk fixtures (real-file code path)."""

import json

import numpy as np
import pytest

from colearn_federated_learning_tpu.config import DataConfig
from colearn_federated_learning_tpu.data import build_federated_data
from colearn_federated_learning_tpu.data.leaf import (
    build_char_vocab,
    load_shakespeare_text,
)


def _write_femnist_fixture(root, n_users=6, per_user=30):
    d = root / "femnist"
    d.mkdir(parents=True)
    rng = np.random.default_rng(0)
    users = [f"writer_{i}" for i in range(n_users)]
    blob = {
        "users": users,
        "num_samples": [per_user] * n_users,
        "user_data": {
            u: {
                "x": rng.uniform(0, 1, (per_user, 784)).round(3).tolist(),
                "y": rng.integers(0, 62, per_user).tolist(),
            }
            for u in users
        },
    }
    (d / "all_data_0.json").write_text(json.dumps(blob))


def test_femnist_real_loader_natural_split(tmp_path):
    _write_femnist_fixture(tmp_path)
    cfg = DataConfig(name="femnist", num_clients=3, partition="natural",
                     data_dir=str(tmp_path))
    fed = build_federated_data(cfg, seed=0)
    assert fed.meta["source"] == "real"
    assert fed.num_clients == 3
    assert fed.train_x.shape[1:] == (28, 28, 1)
    # every example lands on exactly one client
    allidx = np.concatenate(fed.client_indices)
    assert len(np.unique(allidx)) == len(allidx) == len(fed.train_x)


def test_shakespeare_text_loader(tmp_path):
    text = "\n\n".join(
        f"SPEAKER {i}: " + "to be or not to be that is the question " * 8
        for i in range(5)
    )
    p = tmp_path / "shakespeare.txt"
    p.write_text(text)
    tx, ty, ex, ey, meta = load_shakespeare_text(str(p), vocab_size=90, seq_len=20)
    assert tx.shape[1] == 20 and ty.shape == tx.shape
    # next-token alignment: y[t] == x[t+1] within each window
    np.testing.assert_array_equal(tx[0, 1:], ty[0, :-1])
    assert meta["natural_groups"]
    cfg = DataConfig(name="shakespeare", num_clients=4, partition="natural",
                     data_dir=str(tmp_path))
    fed = build_federated_data(cfg, seed=0, vocab_size=90, seq_len=20)
    assert fed.task == "lm" and fed.meta["source"] == "real"


def test_char_vocab_reserves_unk():
    v = build_char_vocab("aaabbc", 3)
    assert 0 not in v.values()  # 0 is <unk>
    assert v["a"] == 1  # most frequent first


@pytest.mark.parametrize("name", [
    "mnist_fedavg_2", "cifar10_fedavg_100", "femnist_fedprox_500",
    "shakespeare_fedavg", "imagenet_silo_dp"])
def test_named_config_builds_data(name):
    """Every advertised BASELINE config must produce a usable federation
    (regression: femnist_fedprox_500 used to crash at partition time)."""
    from colearn_federated_learning_tpu.config import get_named_config

    cfg = get_named_config(name)
    kwargs = dict(cfg.model.kwargs)
    if "image_size" in kwargs:
        # the partition is what is asserted, and it does not read the
        # pixels: 2,048 images of 224 x 224 are half a minute to draw
        kwargs["image_size"] = 32
    fed = build_federated_data(cfg.data, seed=0, **kwargs)
    assert fed.num_clients == cfg.data.num_clients
    assert min(len(ix) for ix in fed.client_indices) >= 1
