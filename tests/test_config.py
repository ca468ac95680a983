import pytest

from colearn_federated_learning_tpu.config import (
    ExperimentConfig,
    get_named_config,
    list_named_configs,
    resolve_config,
)


# BASELINE.json:7-11 — the five capability configs, plus the
# 1000-client north-star scale config (BASELINE.json:5) and the
# beyond-reference decentralized / adversarial / adapter-plane
# showcases (vit_lora_dp: the ViT injection map under example-DP)
NAMED_CONFIGS = sorted([
    "mnist_fedavg_2",
    "cifar10_fedavg_100",
    "cifar10_fedavg_1000",
    "femnist_fedprox_500",
    "shakespeare_fedavg",
    "imagenet_silo_dp",
    "cifar10_gossip_16",
    "cifar10_krum_byzantine",
    "bert_lora_federated",
    "vit_lora_dp",
    "keye_silo_lm",  # PR 25: the sparse-expert language decoder
    "axk1_silo_lora",  # PR 29: adapters on a frozen latent-attention base
    "mellum2_silo_lm",  # PR 31: layers in periods, banded attention
])


def test_named_configs_exist():
    assert list_named_configs() == NAMED_CONFIGS


@pytest.mark.parametrize("name", NAMED_CONFIGS)
def test_named_config_validates(name):
    cfg = get_named_config(name)
    assert cfg.name == name
    cfg.validate()


def test_yaml_roundtrip(tmp_path):
    cfg = get_named_config("cifar10_fedavg_100")
    path = tmp_path / "exp.yaml"
    cfg.to_yaml(str(path))
    back = ExperimentConfig.from_yaml(str(path))
    assert back.to_dict() == cfg.to_dict()


def test_overrides():
    cfg = resolve_config("mnist_fedavg_2", {"server.num_rounds": 3, "client.lr": 0.5})
    assert cfg.server.num_rounds == 3
    assert cfg.client.lr == 0.5
    with pytest.raises(KeyError):
        resolve_config("mnist_fedavg_2", {"server.bogus": 1})


def test_validation_rejects_bad_cohort():
    cfg = get_named_config("mnist_fedavg_2")
    cfg.server.cohort_size = 99
    with pytest.raises(ValueError):
        cfg.validate()


def test_fedprox_requires_mu():
    cfg = get_named_config("femnist_fedprox_500")
    cfg.client.prox_mu = 0.0
    with pytest.raises(ValueError):
        cfg.validate()


def test_dtype_typos_rejected_with_allowed_values():
    """r7 satellite: a dtype typo must fail at validate() with the
    allowed values listed — not as a deep jnp.dtype/KeyError later."""
    for field in ("param_dtype", "compute_dtype", "local_param_dtype"):
        cfg = get_named_config("mnist_fedavg_2")
        setattr(cfg.run, field, "bf16")
        with pytest.raises(ValueError, match="bfloat16"):
            cfg.validate()
    # local_param_dtype additionally allows "" (inherit)
    cfg = get_named_config("mnist_fedavg_2")
    cfg.run.local_param_dtype = ""
    cfg.validate()


def test_bf16_off_tpu_warns_once(caplog):
    """r7 satellite: requesting bf16 compute on a backend without
    native bf16 matmuls (this CPU host) warns exactly once."""
    import logging

    from colearn_federated_learning_tpu.server import round_driver

    round_driver._BF16_BACKEND_WARNED = False
    cfg = get_named_config("mnist_fedavg_2")
    cfg.run.compute_dtype = "bfloat16"
    with caplog.at_level(logging.WARNING, logger=round_driver.__name__):
        round_driver._warn_bf16_backend(cfg)
        round_driver._warn_bf16_backend(cfg)
    hits = [r for r in caplog.records if "bf16" in r.getMessage()]
    assert len(hits) == 1
    # pure-f32 configs never warn
    round_driver._BF16_BACKEND_WARNED = False
    caplog.clear()
    f32 = get_named_config("mnist_fedavg_2")
    with caplog.at_level(logging.WARNING, logger=round_driver.__name__):
        round_driver._warn_bf16_backend(f32)
    assert not caplog.records
