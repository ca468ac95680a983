"""Model zoo (SURVEY.md §2 C9, layer L0a).

Capability parity targets (BASELINE.json:7-11): LeNet-5, ResNet-18,
MobileNetV2, BERT-tiny (causal LM), ViT-B/16. All are ``flax.linen``
modules with pure-pytree params so FedAvg's weighted-sum is plain tree
arithmetic, and all use static shapes + GroupNorm-style normalization
(no batch statistics crossing client boundaries — BatchNorm is both bad
FL practice and a running-stats headache for functional aggregation).

What ``model.apply({"params": p}, x, train=...)`` may return: the logits
(classify ``[B, C]``, lm ``[B, T, V]``, float32), or ``(logits, aux)``
where ``aux["loss"]`` is a ``[B]`` auxiliary loss the trainer adds to
each example's cross-entropy and ``aux["counters"]`` a dict of ``[B]``
counters named by the module's ``aux_counters`` (``keye.py``: the
indexer's loss; ``client/trainer.make_loss_fn`` says how they reach the
round's metrics). Its registered factory carries ``aux_counters`` too
(:func:`returns_aux_loss`), which is how ``config.validate()`` knows.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu.utils.registry import Registry

model_registry = Registry("model")


def _allowed_kwargs(factory) -> set:
    """Named parameters of a zoo factory (its real knob surface — every
    builder also takes a ``**_`` sink so shared driver kwargs like
    ``compute_dtype`` flow everywhere, which is exactly why a TYPO'd
    kwarg used to vanish silently and surface as a shape error deep in
    Flax init)."""
    import inspect

    return {
        p.name
        for p in inspect.signature(factory).parameters.values()
        if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)
    }


def build_model(name: str, num_classes: int, **kwargs):
    """Instantiate a model module from the zoo.

    Unknown ``name`` and unknown ``kwargs`` both raise a ValueError
    naming the allowed set — a config typo fails at construction with
    the fix in the message, not minutes later inside Flax init."""
    try:
        factory = model_registry.get(name)
    except KeyError:
        raise ValueError(
            f"unknown model.name {name!r}; known models: "
            f"{', '.join(model_registry.names())}"
        ) from None
    unknown = set(kwargs) - _allowed_kwargs(factory)
    if unknown:
        allowed = sorted(
            _allowed_kwargs(factory) - {"num_classes"}
        )
        raise ValueError(
            f"unknown model.kwargs for {name!r}: "
            f"{', '.join(sorted(unknown))}; allowed kwargs: "
            f"{', '.join(allowed)}"
        )
    return factory(num_classes=num_classes, **kwargs)


def returns_aux_loss(name: str) -> bool:
    """Whether model ``name`` returns ``(logits, aux)``: its registered
    factory is marked with the ``aux_counters`` its module reports."""
    return name in model_registry.names() and bool(
        getattr(model_registry.get(name), "aux_counters", ()))


def model_input_spec(name: str, **kwargs) -> Tuple[Tuple[int, ...], Any]:
    """(example input shape without batch dim, dtype) for a model family."""
    try:
        spec = _INPUT_SPECS[name]
    except KeyError:
        raise ValueError(
            f"unknown model.name {name!r}; known models: "
            f"{', '.join(sorted(_INPUT_SPECS))}"
        ) from None
    if callable(spec):
        return spec(**kwargs)
    return spec


def init_params(model, input_shape, seed: int = 0, input_dtype=jnp.float32):
    """Initialize a params pytree with a dummy batch of one."""
    rng = jax.random.PRNGKey(seed)
    dummy = jnp.zeros((1,) + tuple(input_shape), input_dtype)
    variables = model.init(rng, dummy, train=False)
    return variables["params"]


# populated by the module imports below
_INPUT_SPECS = {}

from colearn_federated_learning_tpu.models import lenet  # noqa: E402,F401
from colearn_federated_learning_tpu.models import resnet  # noqa: E402,F401
from colearn_federated_learning_tpu.models import mobilenet  # noqa: E402,F401
from colearn_federated_learning_tpu.models import bert  # noqa: E402,F401
from colearn_federated_learning_tpu.models import vit  # noqa: E402,F401
from colearn_federated_learning_tpu.models import lstm  # noqa: E402,F401
from colearn_federated_learning_tpu.models import keye  # noqa: E402,F401
from colearn_federated_learning_tpu.models import axk1  # noqa: E402,F401
from colearn_federated_learning_tpu.models import mellum2  # noqa: E402,F401
