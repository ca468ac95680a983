"""Model zoo forward-shape and param-purity checks (SURVEY.md §2 C9)."""

import jax
import jax.numpy as jnp
import pytest

from colearn_federated_learning_tpu.models import build_model, init_params


@pytest.mark.parametrize(
    "name,kwargs,in_shape,in_dtype,out_shape",
    [
        ("lenet5", {"num_classes": 10}, (28, 28, 1), jnp.float32, (2, 10)),
        ("resnet18", {"num_classes": 10}, (32, 32, 3), jnp.float32, (2, 10)),
        ("mobilenetv2", {"num_classes": 62}, (28, 28, 1), jnp.float32, (2, 62)),
        ("bert_tiny", {"num_classes": 0, "vocab_size": 90, "seq_len": 16},
         (16,), jnp.int32, (2, 16, 90)),
        ("vit_b16", {"num_classes": 10, "image_size": 32}, (32, 32, 3),
         jnp.float32, (2, 10)),
        ("stacked_lstm", {"num_classes": 0, "vocab_size": 90, "seq_len": 16,
                          "hidden": 32}, (16,), jnp.int32, (2, 16, 90)),
    ],
)
def test_forward_shapes(name, kwargs, in_shape, in_dtype, out_shape):
    # shapes and dtypes are what is asserted: traced at the published
    # depth and width, never executed
    model = build_model(name.split(":")[0], **kwargs)
    params = jax.eval_shape(
        lambda: init_params(model, in_shape, seed=0, input_dtype=in_dtype))
    out = jax.eval_shape(
        lambda p, x: model.apply({"params": p}, x, train=False),
        params, jax.ShapeDtypeStruct((2,) + in_shape, in_dtype))
    assert out.shape == out_shape
    assert out.dtype == jnp.float32  # logits always f32 for stable CE
    # params must be a pure pytree of inexact arrays (aggregatable)
    for leaf in jax.tree.leaves(params):
        assert jnp.issubdtype(leaf.dtype, jnp.inexact)


def test_unknown_model_name_raises_clear_valueerror():
    """Registry hardening: a model.name typo must fail at construction
    naming the known set, not as an opaque KeyError."""
    with pytest.raises(ValueError, match="known models.*lenet5"):
        build_model("lenet6", num_classes=10)


def test_unknown_model_kwargs_raise_clear_valueerror():
    """A kwargs typo (every builder has a **_ sink for shared driver
    kwargs, so it used to vanish silently and surface deep in Flax
    init) must fail at construction listing the allowed knobs."""
    with pytest.raises(ValueError, match="seq_length.*allowed.*seq_len"):
        build_model("bert_tiny", num_classes=0, seq_length=16)
    with pytest.raises(ValueError, match="withd.*allowed.*width"):
        build_model("resnet18", num_classes=10, withd=16)


def test_known_model_kwargs_still_flow():
    model = build_model("resnet18", num_classes=10, width=16,
                        compute_dtype=jnp.bfloat16)
    assert model.width == 16


def test_unknown_input_spec_name_raises():
    from colearn_federated_learning_tpu.models import model_input_spec

    with pytest.raises(ValueError, match="known models"):
        model_input_spec("no_such_model")
    shape, dtype = model_input_spec("bert_tiny", seq_len=16)
    assert shape == (16,) and dtype == jnp.int32


def test_no_batch_stats_collections():
    """FL invariant: no mutable batch statistics (GroupNorm everywhere)."""
    for name, kwargs, shape, dtype in [
        ("resnet18", {"num_classes": 10}, (32, 32, 3), jnp.float32),
        ("mobilenetv2", {"num_classes": 62}, (28, 28, 1), jnp.float32),
    ]:
        model = build_model(name, **kwargs)
        variables = jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.ones((1,) + shape, dtype), train=True
            )
        )
        assert set(variables.keys()) == {"params"}, name


def test_bfloat16_compute_dtype():
    model = build_model("resnet18", num_classes=10, compute_dtype=jnp.bfloat16)
    out = jax.eval_shape(
        lambda: model.apply(
            {"params": init_params(model, (32, 32, 3), seed=0)},
            jnp.ones((2, 32, 32, 3)), train=False))
    assert out.dtype == jnp.float32


def test_stacked_lstm_trains_in_engine():
    """The LEAF-canonical recurrent model runs through the real round
    engine (lm task) and one round reduces the next-token loss on a
    learnable periodic sequence."""
    import numpy as np

    from colearn_federated_learning_tpu.config import (
        ClientConfig,
        DPConfig,
        ServerConfig,
    )
    from colearn_federated_learning_tpu.data.loader import (
        RoundShape,
        make_round_indices,
    )
    from colearn_federated_learning_tpu.parallel.mesh import build_client_mesh
    from colearn_federated_learning_tpu.parallel.round_engine import (
        make_sharded_round_fn,
    )
    from colearn_federated_learning_tpu.server.aggregation import (
        make_server_update_fn,
    )

    model = build_model("stacked_lstm", num_classes=0, vocab_size=16,
                        seq_len=16, hidden=32)
    params = init_params(model, (16,), seed=0, input_dtype=jnp.int32)
    rng = np.random.default_rng(0)
    # periodic text: perfectly learnable next-token structure
    base = np.arange(256 * 17) % 16
    x = jnp.asarray(base.reshape(-1, 17)[:, :16].astype(np.int32))[:256]
    y = jnp.asarray(base.reshape(-1, 17)[:, 1:].astype(np.int32))[:256]

    class _Fed:
        client_indices = list(np.array_split(np.arange(256), 8))

    idx, mask, n_ex = make_round_indices(
        _Fed(), list(range(8)), RoundShape(2, 4, 8, 32), rng
    )
    # char-LSTM at plain SGD wants a hot lr (measured: lr=2.0 reaches
    # ~0.8 by round 8 on this task; lr=0.5 barely moves in-window)
    ccfg = ClientConfig(local_epochs=2, batch_size=8, lr=2.0, momentum=0.0)
    init, supd = make_server_update_fn(
        ServerConfig(optimizer="mean", server_lr=1.0, cohort_size=8)
    )
    mesh = build_client_mesh(8)
    fn = make_sharded_round_fn(
        model, ccfg, DPConfig(), "lm", mesh, supd, cohort_size=8,
        donate=False,
    )
    p, s = params, init(params)
    losses = []
    for r in range(8):
        p, s, m = fn(p, s, x, y, jnp.asarray(idx), jnp.asarray(mask),
                     jnp.asarray(n_ex), jax.random.fold_in(jax.random.PRNGKey(0), r))
        losses.append(float(m.train_loss))
    assert losses[-1] < losses[0] * 0.5, losses
