"""colearn_federated_learning_tpu — a TPU-native federated-learning simulation framework.

Capability rebuild of ``pooyadav/CoLearn_Federated_Learning`` (the reference
mount was empty this round; the capability spec is reconstructed in
``SURVEY.md`` from ``BASELINE.json``, the driver-written north star).

Design (TPU-first, not a port):

- The per-client local trainer is a pure ``jax.jit``'d function with
  ``lax.scan`` over local steps — the whole local phase stays on device.
- FedAvg/FedProx aggregation (the reference's NCCL allreduce,
  BASELINE.json:5) is an XLA ``psum`` over a ``jax.sharding.Mesh`` axis
  named ``"clients"`` inside ``jax.shard_map`` — one chip == one virtual
  client lane, and the entire FL round is ONE compiled XLA program.
- Datasets live in HBM; per-round client batches are on-device gathers
  driven by tiny host-generated index tensors, so the host never touches
  example data inside the round loop.
"""

__version__ = "0.1.0"

from colearn_federated_learning_tpu.config import (  # noqa: F401
    ExperimentConfig,
    get_named_config,
    list_named_configs,
)
