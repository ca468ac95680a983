"""The FLOPs-from-shapes functions against totals computed by hand."""

import pytest

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from harness import flops

# ResNet-18, CIFAR stem, 32x32x3, widths 64/128/256/512 (MACs, forward):
#   stem 3x3 3->64 at 32x32:            32*32*27*64        =   1,769,472
#   stage 1: four 3x3 64->64 at 32x32:  4 * 9*64*64*1024   = 150,994,944
#   stages 2-4, each: 3x3 c/2->c (18,874,368) + three 3x3 c->c
#     (3 * 37,748,736) + 1x1 projection (2,097,152)        = 134,217,728
#   head 512->10                                           =       5,120
R18_MACS = 1_769_472 + 150_994_944 + 3 * 134_217_728 + 5_120
# ViT-B/16 at 224: 196 patches + CLS = 197 tokens (MACs, forward):
#   patchify 196 * (16*16*3) * 768                         =   115,605,504
#   per layer: QKV 197*768*2304 (348,585,984) + scores and weighted sum
#     2*197*197*768 (59,610,624) + projection 197*768*768 (116,195,328)
#     + MLP 2*197*768*3072 (929,562,624)                   = 1,453,954,560
#   head 768*1000                                          =       768,000
VIT_MACS = 115_605_504 + 12 * 1_453_954_560 + 768_000

R18 = {"fn": "resnet18", "args": {"image_size": 32, "in_channels": 3, "width": 64,
                                  "stage_sizes": [2, 2, 2, 2], "num_classes": 10,
                                  "small_inputs": True}}
VIT = {"fn": "vit", "args": {"image_size": 224, "patch_size": 16, "in_channels": 3,
                             "hidden": 768, "layers": 12, "heads": 12,
                             "mlp_dim": 3072, "num_classes": 1000}}


@pytest.mark.parametrize("got,want", [
    (flops.forward_macs(R18), 555_422_720),
    (R18_MACS, 555_422_720),
    (flops.forward_macs(VIT), 17_563_828_224),
    (VIT_MACS, 17_563_828_224),
    (flops.parameters(R18), 11_173_962),
    (flops.parameters(VIT), 86_567_656),
    (flops.train_flops_per_example(R18), 6 * 555_422_720),
    (flops.train_flops_per_example(VIT), 6 * 17_563_828_224),
    (flops.useful_round_flops(VIT, 1024), 6 * 17_563_828_224 * 1024),
    (flops.apply_kernel_bytes(11_173_962), 3 * 4 * 11_173_962),
    (flops.apply_kernel_bytes(11_173_962, momentum=True), 5 * 4 * 11_173_962),
], ids=["r18_macs", "r18_by_hand", "vit_macs", "vit_by_hand", "r18_params",
        "vit_params", "r18_train", "vit_train", "vit_round", "apply_bytes",
        "apply_bytes_momentum"])
def test_counts_from_shapes(got, want):
    assert got == want


def test_parameters_match_the_models_the_repo_builds():
    """The count from shapes equals the flax models' own parameter count
    (tiny sizes, so that init is instant)."""
    import jax
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.models import build_model

    for name, kwargs, spec, shape in (
        ("resnet18", {"width": 8},
         {"fn": "resnet18", "args": {"width": 8, "num_classes": 10}},
         (1, 32, 32, 3)),
        ("vit_b16", {"image_size": 32, "patch_size": 8, "hidden": 32,
                     "layers": 2, "heads": 2, "mlp_dim": 64},
         {"fn": "vit", "args": {"image_size": 32, "patch_size": 8, "hidden": 32,
                                "layers": 2, "heads": 2, "mlp_dim": 64,
                                "num_classes": 10}},
         (1, 32, 32, 3)),
    ):
        model = build_model(name, 10, **kwargs)
        params = jax.eval_shape(
            lambda m=model, s=shape: m.init(jax.random.PRNGKey(0),
                                            jnp.zeros(s), train=False)
        )["params"]
        n = sum(int(jnp.prod(jnp.asarray(p.shape))) for p in jax.tree.leaves(params))
        assert flops.parameters(spec) == n, name
