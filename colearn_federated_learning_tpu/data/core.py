"""Dataset registry and loaders (SURVEY.md §2 C10).

Capability parity targets (BASELINE.json:7-11): MNIST, CIFAR-10, LEAF
FEMNIST, LEAF Shakespeare, federated ImageNet.

Each loader first looks for real data files under ``data_dir`` (the
formats a user would naturally drop in: keras-style ``mnist.npz``,
CIFAR-10 python pickles, LEAF ``all_data.json``); this sandbox has zero
egress so when files are absent and ``synthetic_fallback`` is enabled a
**deterministic, learnable synthetic stand-in** with identical shapes,
dtypes and class structure is generated instead — class-template images
(or a fixed Markov chain for text) plus noise, so convergence tests are
meaningful, not vacuous. The provenance is recorded in ``meta.source``
so benchmarks/logs can never silently confuse the two.
"""

from __future__ import annotations

import os
import pickle
from dataclasses import dataclass, field
from typing import Any, Dict, List

import numpy as np

from colearn_federated_learning_tpu.config import DataConfig
from colearn_federated_learning_tpu.data import partition as partition_lib
from colearn_federated_learning_tpu.obs.spans import _NULL_SPAN
from colearn_federated_learning_tpu.utils.registry import Registry

dataset_registry = Registry("dataset")


@dataclass
class FederatedData:
    """A dataset plus its federated structure.

    ``train_x``/``train_y`` are flat example arrays; the federation is the
    ``client_indices`` list (one int array of example ids per client) —
    partitioning is metadata, the bytes are stored once.

    task: "classify" (y: [N] int labels) or "lm" (x: [N,T] tokens,
    y: [N,T] next-token targets).
    """

    train_x: np.ndarray
    train_y: np.ndarray
    test_x: np.ndarray
    test_y: np.ndarray
    client_indices: List[np.ndarray]
    num_classes: int
    task: str = "classify"
    meta: Dict[str, Any] = field(default_factory=dict)

    @property
    def num_clients(self) -> int:
        return len(self.client_indices)

    def client_sizes(self) -> np.ndarray:
        # store-backed federations (data/store.py ClientIndexView) carry
        # the per-client counts directly — the O(num_clients)-aranges
        # loop below would materialize what the lazy view exists to avoid
        sizes = getattr(self.client_indices, "sizes", None)
        if sizes is not None:
            return np.asarray(sizes, np.int64)
        return np.array([len(ix) for ix in self.client_indices], np.int64)


# ---------------------------------------------------------------------------
# synthetic generators (deterministic, learnable)
# ---------------------------------------------------------------------------


def _synthetic_images(rng: np.random.Generator, n: int, templates: np.ndarray,
                      template_weight: float = 0.7):
    """Class-template images + noise: x = w·template[y] + (1−w)·noise
    with w = ``template_weight`` (DataConfig.synthetic_template_weight).

    The SAME templates generate train and test (only noise and label draws
    differ), so the task is learnable by a small convnet in a handful of
    rounds — what the convergence smoke tests (SURVEY.md §4.2) need. The
    default w=0.7 saturates (acc → 1.0); the convergence REGRESSION
    (tests/test_convergence.py) lowers w so the task plateaus strictly
    below 1.0 and a pinned mid-curve band can detect subtle aggregation
    math drift, not just outright breakage (VERDICT r3 weak-#3).

    Stored as RAW uint8 (like the real datasets' on-disk form): 4× less
    HBM and 4× less host→device transfer than f32; the [0,1] scaling is
    fused on device (client/trainer.py ``normalize_input``).
    """
    num_classes, shape = templates.shape[0], templates.shape[1:]
    w = float(template_weight)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    noise = rng.uniform(0.0, 1.0, size=(n,) + tuple(shape)).astype(np.float32)
    x = w * templates[y] + (1.0 - w) * noise
    return np.clip(np.rint(x * 255.0), 0, 255).astype(np.uint8), y


def _synthetic_template_pair(rng: np.random.Generator, n: int,
                             templates: np.ndarray, w: float,
                             label_noise: float = 0.0):
    """Second synthetic task family (VERDICT r4 weak-#4): each image
    superposes TWO class templates, x = w·(T_a + T_b)/2 + (1−w)·noise
    with a ≠ b, and the label is y = (a + b) mod C. Any LINEAR pixel
    score decomposes additively over the two strokes (s·x ≈ (s·T_a +
    s·T_b)/2), but the modular-sum label is not additively separable —
    a linear model is capped far below the ceiling (measured: linear
    probe ~0.2) while a convnet that detects the strokes and learns the
    nonlinear readout is not. Unlike a random-pixel teacher (measured:
    unlearnable by a small convnet — no spatial structure), the strokes
    keep the task inside what the model family can actually fit, so the
    regression band stays tight. Label noise sets a strict ceiling.

    Same template sharing as the first family: train and test differ
    only in draws, never in templates."""
    num_classes = templates.shape[0]
    a = rng.integers(0, num_classes, n)
    b = (a + rng.integers(1, num_classes, n)) % num_classes
    noise = rng.uniform(0.0, 1.0,
                        size=(n,) + templates.shape[1:]).astype(np.float32)
    x = w * (templates[a] + templates[b]) / 2.0 + (1.0 - w) * noise
    x_u8 = np.clip(np.rint(x * 255.0), 0, 255).astype(np.uint8)
    y = ((a + b) % num_classes).astype(np.int32)
    if label_noise > 0.0:
        flip = rng.random(n) < label_noise
        y[flip] = rng.integers(0, num_classes, flip.sum()).astype(np.int32)
    return x_u8, y


def _synthetic_text(rng: np.random.Generator, n: int, seq_len: int, vocab: int,
                    successors: np.ndarray):
    """Sequences from a fixed sparse Markov chain → next-token prediction is
    learnable well above chance (each symbol has ~4 plausible successors).

    ``successors`` is REQUIRED (no convenient default): the caller draws
    the transition table ONCE and shares it between the train and test
    calls — drawing it per call (the pre-r5 behavior) gave the two
    splits DIFFERENT chains, so eval accuracy sat at chance (with
    worse-than-uniform loss) no matter how well the model learned the
    train chain."""
    seqs = np.empty((n, seq_len + 1), np.int32)
    state = rng.integers(0, vocab, size=n)
    seqs[:, 0] = state
    for t in range(1, seq_len + 1):
        choice = rng.integers(0, 4, size=n)
        state = successors[seqs[:, t - 1], choice]
        seqs[:, t] = state
    return seqs[:, :-1].copy(), seqs[:, 1:].copy()


# ---------------------------------------------------------------------------
# loaders — real files when present, synthetic stand-in otherwise
# ---------------------------------------------------------------------------


def _stable_seed(name: str) -> int:
    # abs(hash()) is salted per-process; datasets must be reproducible
    return int.from_bytes(name.encode(), "little") % (2**31)


def _scaled_train_size(cfg: DataConfig) -> int:
    """Synthetic corpora must be big enough to partition: ≥32 examples per
    client on average, or the Dirichlet/natural min_size retry can't succeed
    (e.g. 500 FEMNIST clients over the 2048-example default)."""
    return max(cfg.synthetic_train_size, cfg.num_clients * 32)


def _image_loader(name: str, shape, num_classes: int, real_fn, size_kwarg=None):
    def load(cfg: DataConfig, **kwargs):
        # Geometry-flexible datasets (federated ImageNet) take their edge
        # size from the model kwargs so the config and the executed shapes
        # always agree — a config saying image_size=224 runs 224, real or
        # synthetic.
        shp = tuple(shape)
        if size_kwarg is not None and kwargs.get(size_kwarg):
            s = int(kwargs[size_kwarg])
            shp = (s, s, shape[-1])
        data_dir = os.path.expanduser(cfg.data_dir)
        real = real_fn(data_dir) if real_fn else None
        extra_meta = {}
        if real is not None:
            if len(real) == 5:  # loader supplies meta (e.g. natural_groups)
                tx, ty, ex, ey, extra_meta = real
            else:
                tx, ty, ex, ey = real
            source = "real"
            shp = tuple(tx.shape[1:])
        elif cfg.synthetic_fallback:
            rng = np.random.default_rng(_stable_seed(name))
            n_train = _scaled_train_size(cfg)
            if cfg.synthetic_task == "template_pair":
                templates = rng.uniform(
                    0.0, 1.0, size=(num_classes,) + shp
                ).astype(np.float32)
                w = cfg.synthetic_template_weight
                tx, ty = _synthetic_template_pair(
                    rng, n_train, templates, w,
                    label_noise=cfg.synthetic_label_noise,
                )
                ex, ey = _synthetic_template_pair(
                    rng, cfg.synthetic_test_size, templates, w,
                    label_noise=cfg.synthetic_label_noise,
                )
            else:
                templates = rng.uniform(
                    0.0, 1.0, size=(num_classes,) + shp
                ).astype(np.float32)
                w = cfg.synthetic_template_weight
                tx, ty = _synthetic_images(rng, n_train, templates, w)
                ex, ey = _synthetic_images(
                    rng, cfg.synthetic_test_size, templates, w
                )
            source = "synthetic"
        else:
            raise FileNotFoundError(
                f"{name}: no data under {data_dir} and synthetic_fallback=False"
            )
        meta = {"source": source, "input_shape": shp, **extra_meta}
        return tx, ty, ex, ey, meta, num_classes, "classify"

    return load


def _try_mnist_real(data_dir: str):
    path = os.path.join(data_dir, "mnist.npz")
    if not os.path.exists(path):
        return None
    with np.load(path) as d:
        # kept as raw uint8 — normalization happens on device
        tx = d["x_train"].astype(np.uint8)[..., None]
        ex = d["x_test"].astype(np.uint8)[..., None]
        return tx, d["y_train"].astype(np.int32), ex, d["y_test"].astype(np.int32)


def _try_cifar10_real(data_dir: str):
    base = os.path.join(data_dir, "cifar-10-batches-py")
    if not os.path.isdir(base):
        return None
    def read(fname):
        with open(os.path.join(base, fname), "rb") as f:
            d = pickle.load(f, encoding="bytes")
        x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        # raw uint8 — normalization happens on device
        return np.ascontiguousarray(x), np.array(d[b"labels"], np.int32)
    xs, ys = zip(*[read(f"data_batch_{i}") for i in range(1, 6)])
    tx, ty = np.concatenate(xs), np.concatenate(ys)
    ex, ey = read("test_batch")
    return tx, ty, ex, ey


def _try_femnist_real(data_dir: str):
    if not os.path.isdir(os.path.join(data_dir, "femnist")):
        return None
    from colearn_federated_learning_tpu.data.leaf import load_femnist

    return load_femnist(data_dir)


def _try_imagenet_real(data_dir: str, test_fraction: float = 0.05):
    """Federated ImageNet, directory-of-silos layout: ``data_dir/
    imagenet_federated/silo_*.npz`` (each an institution's shard with
    ``x`` [n,H,W,3] uint8/float and ``y`` [n] labels) plus an optional
    ``test.npz``; without one, the last ~5% of each silo is held out.
    Silo membership is returned as ``natural_groups`` so the ``silo``
    partitioner preserves real institutional boundaries.
    """
    base = os.path.join(data_dir, "imagenet_federated")
    if not os.path.isdir(base):
        return None
    silo_files = sorted(
        f for f in os.listdir(base) if f.startswith("silo_") and f.endswith(".npz")
    )
    if not silo_files:
        return None

    def to_float(x):
        # uint8 silos stay raw (normalized on device); float silos are
        # assumed pre-normalized by the institution and pass through
        return x if x.dtype == np.uint8 else x.astype(np.float32)

    test_path = os.path.join(base, "test.npz")
    has_test = os.path.exists(test_path)
    xs, ys, groups, test_xs, test_ys = [], [], [], [], []
    offset = 0
    for fname in silo_files:
        with np.load(os.path.join(base, fname)) as d:
            x, y = to_float(d["x"]), d["y"].astype(np.int32)
        if not has_test and len(x) > 1:
            n_test = max(1, int(len(x) * test_fraction))
            test_xs.append(x[-n_test:])
            test_ys.append(y[-n_test:])
            x, y = x[:-n_test], y[:-n_test]
        xs.append(x)
        ys.append(y)
        groups.append(np.arange(offset, offset + len(x), dtype=np.int64))
        offset += len(x)
    if has_test:
        with np.load(test_path) as d:
            ex, ey = to_float(d["x"]), d["y"].astype(np.int32)
    else:
        ex, ey = np.concatenate(test_xs), np.concatenate(test_ys)
    return (
        np.concatenate(xs), np.concatenate(ys), ex, ey,
        {"natural_groups": groups},
    )


dataset_registry.register("mnist")(_image_loader("mnist", (28, 28, 1), 10, _try_mnist_real))
dataset_registry.register("cifar10")(_image_loader("cifar10", (32, 32, 3), 10, _try_cifar10_real))
dataset_registry.register("femnist")(
    _image_loader("femnist", (28, 28, 1), 62, _try_femnist_real)
)
# Federated ImageNet (cross-silo): geometry follows model.kwargs.image_size
# (default 64 keeps the sandbox light); real silo files override everything.
dataset_registry.register("imagenet_federated")(
    _image_loader(
        "imagenet_federated", (64, 64, 3), 1000, _try_imagenet_real,
        size_kwarg="image_size",
    )
)


@dataset_registry.register("shakespeare")
def _load_shakespeare(cfg: DataConfig, vocab_size: int = 90, seq_len: int = 80, **kwargs):
    data_dir = os.path.expanduser(cfg.data_dir)
    txt = os.path.join(data_dir, "shakespeare.txt")
    if os.path.exists(txt):
        from colearn_federated_learning_tpu.data.leaf import load_shakespeare_text
        tx, ty, ex, ey, meta = load_shakespeare_text(txt, vocab_size, seq_len)
        return tx, ty, ex, ey, meta, vocab_size, "lm"
    if not cfg.synthetic_fallback:
        raise FileNotFoundError(f"shakespeare: no data under {data_dir}")
    rng = np.random.default_rng(1207)
    successors = rng.integers(0, vocab_size, size=(vocab_size, 4))
    tx, ty = _synthetic_text(rng, _scaled_train_size(cfg), seq_len, vocab_size,
                             successors)
    ex, ey = _synthetic_text(rng, cfg.synthetic_test_size, seq_len, vocab_size,
                             successors)
    return tx, ty, ex, ey, {"source": "synthetic", "input_shape": (seq_len,)}, vocab_size, "lm"


@dataset_registry.register("synthetic_text")
def _load_synthetic_text(cfg: DataConfig, vocab_size: int = 90,
                         seq_len: int = 80, **kwargs):
    """Documents of ``seq_len`` tokens over ``vocab_size`` ids from the
    sparse Markov chain of :func:`_synthetic_text`, one document per
    sequence (no packing): the corpus of the long-document language
    configs (``keye_silo_lm``), which have no real files to find."""
    rng = np.random.default_rng(_stable_seed("synthetic_text"))
    successors = rng.integers(0, vocab_size, size=(vocab_size, 4))
    tx, ty = _synthetic_text(rng, _scaled_train_size(cfg), seq_len, vocab_size,
                             successors)
    ex, ey = _synthetic_text(rng, cfg.synthetic_test_size, seq_len, vocab_size,
                             successors)
    return tx, ty, ex, ey, {"source": "synthetic", "input_shape": (seq_len,)}, vocab_size, "lm"


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _no_span(name: str, **args):
    return _NULL_SPAN


def build_federated_data(cfg: DataConfig, seed: int = 0, *, span=_no_span,
                         **model_kwargs) -> FederatedData:
    """Load a dataset and partition it into ``cfg.num_clients`` shards.

    ``span`` is the caller's tracer's ``span`` (``Experiment`` passes
    its own): the load and the partition are bracketed as
    ``setup.data.load`` and ``setup.data.partition``. Without one
    nothing is timed.

    With ``cfg.store.dir`` set the corpus comes from an on-disk client
    store instead (data/store.py): example bytes stay memory-mapped, the
    partition IS the store's per-client index (loader/partition config
    fields are ignored — they were baked in at ``colearn store build``
    time), and only the sampled cohort's records ever touch host RAM.
    """
    if cfg.store.dir:
        from colearn_federated_learning_tpu.data.store import open_store

        with span("setup.data.load", dataset="store") as load:
            fed = open_store(
                cfg.store.dir, gather_workers=cfg.store.gather_workers
            ).as_federated_data(
                expected_clients=cfg.num_clients,
                materialize=cfg.store.materialize,
            )
            load.note(examples=int(len(fed.train_x)) + int(len(fed.test_x)))
        return fed
    loader = dataset_registry.get(cfg.name)
    with span("setup.data.load", dataset=cfg.name) as load:
        tx, ty, ex, ey, meta, num_classes, task = loader(cfg, **model_kwargs)
        load.note(examples=int(len(tx)) + int(len(ex)))
    labels_for_partition = ty if task == "classify" else ty[:, 0]
    part_info: dict = {}
    with span("setup.data.partition", partition=cfg.partition,
              clients=cfg.num_clients):
        client_indices = partition_lib.partition(
            cfg.partition,
            labels=labels_for_partition,
            num_clients=cfg.num_clients,
            num_classes=num_classes if task == "classify" else int(labels_for_partition.max()) + 1,
            alpha=cfg.dirichlet_alpha,
            seed=seed,
            natural_groups=meta.get("natural_groups"),
            info=part_info,
        )
    meta = dict(meta, partition=cfg.partition, **part_info)
    if part_info.get("repair_used"):
        # the deterministic extreme-α repair changed the effective
        # label-skew distribution — say so where the user will see it
        import logging

        logging.getLogger(__name__).warning(
            "%s partition (dirichlet alpha=%s) needed deterministic repair: "
            "%d example(s) moved from the largest shards to starved ones; "
            "the realized label skew is milder than the drawn one",
            cfg.partition, part_info.get("repair_alpha"),
            part_info.get("repair_moved", 0),
        )
    return FederatedData(
        train_x=tx, train_y=ty, test_x=ex, test_y=ey,
        client_indices=client_indices, num_classes=num_classes, task=task, meta=meta,
    )
