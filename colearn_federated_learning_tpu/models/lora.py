"""LoRA adapter plane (ROADMAP item 3): parameter-efficient transformer
federation over the million-client store.

Low-rank adaptation (Hu et al. 2021): every targeted dense kernel
``W [d_in, d_out]`` gains a rank-r adapter pair ``A [d_in, r]``,
``B [r, d_out]`` and the effective weight becomes ``W + (alpha/r)·A·B``.
The base model is FROZEN; clients train, upload, and the server
aggregates ONLY the adapter factors — which is what makes transformer
federation wire-feasible at population scale (the per-client upload
drops by ``|W| / |A|+|B| ≈ d/(2r)`` per target, 100–1000× end to end;
the analytic wire counters log the realized ratio as
``wire_reduction_vs_full``).

Design: the whole round stack (engines, aggregation, compression,
attacks, ledger, reputation, checkpointing, wire counters) operates on
ONE opaque params pytree. :class:`LoRAModel` therefore makes the
adapters BE that pytree — ``model.init`` returns adapters only,
``model.apply`` merges them into the frozen base before the underlying
forward — so every subsystem runs in adapter space *by construction*:
the ``[K, ·]`` wire stack carries adapter deltas, krum/median order
statistics rank flattened factors, the forensic ledger's norm/cosine
stats are adapter-space, and eval/checkpoints see the merged
``W + (alpha/r)·BA`` model through the same ``apply``. No engine code
knows LoRA exists; with ``model.lora.enabled=false`` no wrapper is
constructed anywhere and runs are bitwise the pre-LoRA build
(test-pinned).

The frozen base is DATA, not part of the model object: ``init_frozen``
draws it (a pure function of the init rng, every leaf in the dtype the
base module was built with), the driver places it once, and ``apply``
reads it from the variable collection ``"frozen"`` beside ``"params"``.
Inside a round program it is an argument (``client/trainer.RoundData``),
so a base of gigabytes is never a constant of the compiled program;
nothing that handles "the params pytree" ever sees it.

Targets: the dense kernels inside the repeated transformer blocks of
the two transformer families (``bert_tiny``'s ``TransformerBlock_*``,
``vit_b16``'s ``ViTBlock_*``). Within a block, ``Dense_0`` (the fused
qkv projection) and ``Dense_1`` (the attention output projection) are
the ``"attention"`` target set; ``Dense_2``/``Dense_3`` (the MLP
in/out projections) are ``"mlp"``; ``"all"`` is both. Embeddings, the
weight-tied LM head, LayerNorms, patchify conv, and the classifier
head stay frozen — the Hu et al. recipe. ``axk1_decoder`` keeps its
weights as top-level leaves, the repeated layers' stacked ``[layers,
d_in, d_out]``: its ``"attention"`` targets are the five projections of
latent attention (``_MLA_LEAVES``) under ``dense_`` and ``layers_``, a
stacked leaf's adapters carry the same leading axis, and the module
applies them itself as side products (``applies_adapters``; it has no
``"mlp"`` targets: the experts stay frozen). Zoo members without an
injection map are rejected with a clear error (``LORA_SUPPORTED``).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

# model families with a defined injection map (config.validate() and
# the wrapper both check against this)
LORA_SUPPORTED = ("bert_tiny", "vit_b16", "axk1_decoder")

LORA_TARGETS = ("attention", "mlp", "all")

# block-module prefixes whose Dense kernels are adapter targets, and
# which Dense index within a block belongs to which target set
_BLOCK_PREFIXES = ("TransformerBlock_", "ViTBlock_")
_ATTENTION_DENSE = ("Dense_0", "Dense_1")  # qkv proj, attention out
_MLP_DENSE = ("Dense_2", "Dense_3")  # MLP in, MLP out
# top-level leaves ``<prefix><name>`` of a module that holds plain
# (possibly layer-stacked) matrices: latent attention's projections
_LEAF_PREFIXES = ("dense_", "layers_")
_MLA_LEAVES = ("wqa", "wqb", "wkva", "wkvb", "wo")

Path = Tuple[str, ...]


def lora_target_paths(base_params, target: str) -> List[Path]:
    """Paths (tuples of pytree keys down to the matrix) of every kernel
    the configured ``target`` set adapts, in deterministic sorted order:
    ``[d_in, d_out]`` Dense kernels of transformer blocks, and named
    top-level leaves ``[d_in, d_out]`` or ``[layers, d_in, d_out]``.
    ``base_params`` may hold arrays or their shapes. Raises with a clear
    message when the model has no injection map or the target set is
    empty."""
    if target not in LORA_TARGETS:
        raise ValueError(
            f"unknown model.lora.target {target!r}; "
            f"allowed: {', '.join(LORA_TARGETS)}"
        )
    wanted = set()
    if target in ("attention", "all"):
        wanted.update(_ATTENTION_DENSE)
        wanted.update(p + n for p in _LEAF_PREFIXES for n in _MLA_LEAVES)
    if target in ("mlp", "all"):
        wanted.update(_MLP_DENSE)
    paths: List[Path] = []
    flat = jax.tree_util.tree_flatten_with_path(base_params)[0]
    for keypath, leaf in flat:
        keys = tuple(
            k.key if hasattr(k, "key") else str(k) for k in keypath
        )
        ndim = getattr(leaf, "ndim", 0)
        if len(keys) == 1:
            if keys[0] in wanted and ndim in (2, 3):
                paths.append(keys)
            continue
        if len(keys) < 3 or keys[-1] != "kernel":
            continue
        block, dense = keys[-3], keys[-2]
        if not block.startswith(_BLOCK_PREFIXES):
            continue
        if dense in wanted and ndim == 2:
            paths.append(keys)
    if not paths:
        raise ValueError(
            "model.lora found no adapter targets: the model has no "
            f"kernels of the target set (LoRA supports "
            f"{', '.join(LORA_SUPPORTED)}; target={target!r})"
        )
    return sorted(paths)


def _get_path(tree, path: Path):
    for k in path:
        tree = tree[k]
    return tree


def _adapter_path(path: Path) -> Path:
    """Where a target's factors live in the adapter tree: beside a Dense
    module's ``kernel``, or under a top-level leaf's own name."""
    return path[:-1] if path[-1] == "kernel" else path


def init_lora_params(base_params, rank: int, target: str, rng,
                     dtype=None) -> Dict:
    """Build the adapter pytree for ``base_params`` (arrays or shapes):
    a nested dict mirroring the targets, each kernel ``W [..., d_in,
    d_out]`` contributing ``{"lora_a": [..., d_in, r], "lora_b": [...,
    r, d_out]}`` (a layer-stacked kernel's factors are stacked alike).
    ``A ~ N(0, 1/d_in)`` (per-path key folded from ``rng``), ``B = 0`` —
    so the merged model INITIALLY equals the base exactly (the standard
    LoRA init; the first update moves ``B`` only, because ∂/∂A ∝ Bᵀ = 0
    until then). ``dtype``: the factors' stored dtype
    (``run.param_dtype``); default, the kernels' own."""
    if rank < 1:
        raise ValueError(f"model.lora.rank must be >= 1, got {rank}")
    paths = lora_target_paths(base_params, target)
    adapters: Dict = {}
    for i, path in enumerate(paths):
        w = _get_path(base_params, path)
        *lead, d_in, d_out = (int(n) for n in w.shape)
        if rank >= min(d_in, d_out):
            raise ValueError(
                f"model.lora.rank={rank} is not low-rank for kernel "
                f"{'/'.join(path)} [{d_in}, {d_out}] (needs rank < "
                f"{min(d_in, d_out)}) — the adapter would be as large "
                f"as the weight it replaces"
            )
        k = jax.random.fold_in(rng, i)
        a = jax.random.normal(k, (*lead, d_in, rank), jnp.float32) * (
            1.0 / np.sqrt(d_in)
        )
        node = adapters
        for key in _adapter_path(path):
            node = node.setdefault(key, {})
        leaf_dtype = w.dtype if dtype is None else dtype
        node["lora_a"] = a.astype(leaf_dtype)
        node["lora_b"] = jnp.zeros((*lead, rank, d_out), leaf_dtype)
    return adapters


def merge_lora_params(base_params, adapters, alpha: float, rank: int):
    """The eval/train-time merge: a copy of ``base_params`` where every
    adapted kernel becomes ``W + (alpha/rank)·A·B``. The product is
    computed at the ADAPTER dtype (bf16 under run.local_param_dtype —
    the local-training cast applies to the factors like any other
    leaf) and added at the base kernel's dtype. Non-adapted leaves are
    returned by reference (zero copy)."""
    scale = float(alpha) / float(rank)

    def walk(base, ad):
        if not isinstance(ad, dict):
            return base
        if "lora_a" in ad:
            delta = (ad["lora_a"] @ ad["lora_b"]) * jnp.asarray(
                scale, ad["lora_a"].dtype
            )
            if not isinstance(base, dict):  # a top-level matrix
                return base + delta.astype(base.dtype)
            return {
                k: (v + delta.astype(v.dtype) if k == "kernel" else v)
                for k, v in base.items()
            }
        out = dict(base)
        for k, sub in ad.items():
            out[k] = walk(base[k], sub)
        return out

    return walk(base_params, adapters)


def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


class LoRAModel:
    """Model-like facade whose params pytree IS the adapter set.

    Presents the zoo contract the trainer/driver/engines consume —
    ``init(rng, x, train=...) -> {"params": adapters}``,
    ``apply({"params": adapters, "frozen": base}, x, ...)``, a
    ``compute_dtype`` attribute — and holds no array itself: the frozen
    base comes from :meth:`init_frozen` and travels as the variable
    collection ``"frozen"`` (an argument of every program that applies
    the model; never shipped, aggregated, donated, or checkpointed).

    Binding contract: the base params are a pure function of the rng
    passed to ``init_frozen`` (exactly ``base.init``'s output for the
    rng ``init`` is given), so they are NOT checkpointed — a
    resume/restore re-derives them from ``run.seed`` and gets the
    identical base (``Experiment.init_state`` draws and places them).
    ``init`` itself reads the base's SHAPES only (``jax.eval_shape``).
    """

    def __init__(self, base, rank: int, alpha: float, target: str,
                 adapter_dtype=None):
        if rank < 1:
            raise ValueError(f"model.lora.rank must be >= 1, got {rank}")
        if alpha <= 0.0:
            raise ValueError(
                f"model.lora.alpha must be > 0, got {alpha}"
            )
        if target not in LORA_TARGETS:
            raise ValueError(
                f"unknown model.lora.target {target!r}; "
                f"allowed: {', '.join(LORA_TARGETS)}"
            )
        self.base = base
        self.rank = int(rank)
        self.alpha = float(alpha)
        self.target = target
        self.adapter_dtype = adapter_dtype
        # the trainer reads the model's compute dtype at factory time
        self.compute_dtype = getattr(base, "compute_dtype", jnp.float32)
        # a base that reports counters does so through the facade too
        self.aux_counters = tuple(getattr(base, "aux_counters", ()))

    def init_frozen(self, rng, x, train: bool = False):
        """The frozen base for init rng ``rng``: ``base.init``'s params,
        every leaf drawn in the base module's own ``param_dtype``."""
        return self.base.init(rng, x, train=train)["params"]

    def init(self, rng, x, train: bool = False):
        base_shapes = jax.eval_shape(
            lambda r, d: self.init_frozen(r, d, train=train), rng, x)
        adapters = init_lora_params(
            base_shapes, self.rank, self.target,
            jax.random.fold_in(rng, 0x10_8A), dtype=self.adapter_dtype,
        )
        return {"params": adapters}

    @staticmethod
    def _frozen(variables):
        try:
            return variables["frozen"]
        except KeyError:
            raise ValueError(
                "LoRAModel.apply needs the frozen base as the variable "
                "collection 'frozen' beside 'params' (init_frozen draws "
                "it; Experiment.frozen_base holds the placed copy)"
            ) from None

    def apply(self, variables, *args, **kwargs):
        frozen, adapters = self._frozen(variables), variables["params"]
        if getattr(self.base, "applies_adapters", False):
            # the module adds each target's side product itself and
            # never forms a merged weight
            return self.base.apply(
                {"params": frozen}, *args, adapters=adapters,
                lora_scale=self.alpha / self.rank, **kwargs)
        merged = merge_lora_params(frozen, adapters, self.alpha, self.rank)
        return self.base.apply({"params": merged}, *args, **kwargs)

    def apply_decomposed(self, variables, *args, **kwargs):
        """The merge-free forward: run the FROZEN base with its own
        params and add each target's low-rank side-path ``(x·A)·B ·
        (alpha/r)`` to that Dense's output via a method interceptor —
        ``W·x + s·(x·A)·B`` instead of ``(W + s·A·B)·x``. Same map up
        to GEMM reassociation (distributivity; test-pinned tolerance),
        but the base kernels stay un-batched: under the megabatch
        layout's per-client ``vmap`` only A/B batch, so the dominant
        base contractions see the flattened ``[C·batch, ·]`` rows
        against ONE weight in EVERY local step — the merged ``apply``
        would materialize C merged kernels and batch every GEMM. The
        trainer routes the megabatch block through this when present
        (client/trainer.py); every other consumer keeps the merged
        ``apply`` bitwise-unchanged."""
        frozen, adapters = self._frozen(variables), variables["params"]
        # module paths of the adapted Dense layers — the kernel paths
        # minus the trailing "kernel" key are exactly flax's
        # context.module.path tuples
        targets = {p[:-1] for p in lora_target_paths(frozen, self.target)}
        scale = self.alpha / self.rank

        def interceptor(next_fun, iargs, ikwargs, context):
            if context.method_name != "__call__":
                return next_fun(*iargs, **ikwargs)
            path = tuple(context.module.path)
            if path not in targets:
                return next_fun(*iargs, **ikwargs)
            x = iargs[0]
            y = next_fun(*iargs, **ikwargs)
            node = _get_path(adapters, path)
            # rank-r side path in full f32 (the factors' stored dtype):
            # under bf16 compute the merged apply folds s·A·B into W at
            # f32 BEFORE the one cast, so a low-precision residual here
            # would drift the trajectory well past reassociation level.
            # The r-wide GEMMs are negligible next to the base
            # contraction, so the upcast costs nothing that matters.
            a = node["lora_a"].astype(jnp.float32)
            b = node["lora_b"].astype(jnp.float32)
            r = (x.astype(jnp.float32) @ a) @ b * jnp.float32(scale)
            return (y.astype(jnp.float32) + r).astype(y.dtype)

        with nn.intercept_methods(interceptor):
            return self.base.apply({"params": frozen}, *args, **kwargs)

    def merged_params(self, adapters, frozen):
        """The deployable full-model params: ``W + (alpha/r)·A·B`` over
        the base ``frozen`` — what ``colearn export`` writes for a LoRA
        run so downstream consumers never need the adapter structure."""
        return merge_lora_params(frozen, adapters, self.alpha, self.rank)


def build_lora_model(base, model_name: str, rank: int, alpha: float,
                     target: str, adapter_dtype=None) -> LoRAModel:
    """Wrap a zoo model for adapter-space federation, rejecting model
    families with no injection map (clear error at construction, not a
    silent no-adapter run)."""
    if model_name not in LORA_SUPPORTED:
        raise ValueError(
            f"model.lora is not supported for model {model_name!r}: no "
            f"transformer-block injection map; supported: "
            f"{', '.join(LORA_SUPPORTED)}"
        )
    return LoRAModel(base, rank=rank, alpha=alpha, target=target,
                     adapter_dtype=adapter_dtype)
