"""Ops-mode coverage (SURVEY.md §5 tracing/sanitize): the --profile and
--sanitize paths must actually execute, including the benchmark's configuration
where out_dir is empty (profile falls back to cwd-relative)."""

import os

import jax
import pytest

from colearn_federated_learning_tpu.config import get_named_config
from colearn_federated_learning_tpu.server.round_driver import Experiment


@pytest.fixture(autouse=True)
def _restore_debug_nans():
    """Experiment(sanitize=True) flips the global jax_debug_nans flag;
    don't leak it into the rest of the session."""
    before = jax.config.jax_debug_nans
    yield
    jax.config.update("jax_debug_nans", before)


def _tiny_cfg(tmp_path, **run_overrides):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.num_rounds = 3
    cfg.server.eval_every = 0
    cfg.run.out_dir = str(tmp_path) if tmp_path is not None else ""
    cfg.data.synthetic_train_size = 256
    cfg.data.synthetic_test_size = 128
    for k, v in run_overrides.items():
        setattr(cfg.run, k, v)
    return cfg


def test_profile_round_writes_trace(tmp_path):
    cfg = _tiny_cfg(tmp_path, profile_round=1)
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    assert int(state["round"]) == 3
    profile_dir = os.path.join(str(tmp_path), cfg.name, "profile")
    assert os.path.isdir(profile_dir) and os.listdir(profile_dir)


def test_profile_round_with_empty_out_dir(tmp_path, monkeypatch):
    """benchmark/run.py runs with out_dir=''; the trace must land under cwd, not '/'."""
    monkeypatch.chdir(tmp_path)
    cfg = _tiny_cfg(None, profile_round=0)
    exp = Experiment(cfg, echo=False)
    exp.fit()
    assert os.path.isdir(os.path.join(str(tmp_path), cfg.name, "profile"))


def test_sanitize_mode_clean_run(tmp_path):
    cfg = _tiny_cfg(tmp_path, sanitize=True)
    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    assert int(state["round"]) == 3


def test_sanitize_mode_catches_nonfinite(tmp_path):
    cfg = _tiny_cfg(tmp_path, sanitize=True)
    cfg.client.lr = 1e38  # guaranteed float32 overflow → non-finite params
    exp = Experiment(cfg, echo=False)
    with pytest.raises(FloatingPointError):
        exp.fit()
