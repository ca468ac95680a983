"""LoRA adapter plane (model.lora, models/lora.py — ROADMAP item 3):
merge semantics, target selection, config/injection rejections, the
lora-off bitwise-identity contract, engine/fusion parity in adapter
space, the adapter-space robustness matrix (sign_flip f=2/8:
weighted_mean degrades, krum and the reputation-weighted mean hold the
benign band), the analytic wire-reduction accounting, the
`bert_lora_federated` convergence band, and the store-backed streaming
smoke (the PR 9 plane end to end on adapter uploads)."""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu.config import get_named_config
from colearn_federated_learning_tpu.models import build_model, init_params
from colearn_federated_learning_tpu.models.lora import (
    LoRAModel,
    build_lora_model,
    init_lora_params,
    lora_target_paths,
    merge_lora_params,
)

# ---------------------------------------------------------------------------
# units: target selection, init, merge semantics
# ---------------------------------------------------------------------------


def _tiny_bert(**kw):
    kw.setdefault("vocab_size", 32)
    kw.setdefault("seq_len", 16)
    kw.setdefault("hidden", 32)
    kw.setdefault("ff", 64)
    return build_model("bert_tiny", num_classes=0, **kw)


def _base_params(model, in_shape=(16,), dtype=jnp.int32):
    return model.init(
        jax.random.PRNGKey(0), jnp.zeros((1,) + in_shape, dtype),
        train=False,
    )["params"]


def test_target_paths_attention_mlp_all():
    base = _base_params(_tiny_bert())
    att = lora_target_paths(base, "attention")
    mlp = lora_target_paths(base, "mlp")
    both = lora_target_paths(base, "all")
    # 2 blocks x {Dense_0 (qkv), Dense_1 (attn out)} / {Dense_2, Dense_3}
    assert len(att) == 4 and len(mlp) == 4 and len(both) == 8
    assert all(p[-2] in ("Dense_0", "Dense_1") for p in att)
    assert all(p[-2] in ("Dense_2", "Dense_3") for p in mlp)
    assert set(both) == set(att) | set(mlp)
    # embeddings / layernorms / the weight-tied head are never targets
    assert all(p[-1] == "kernel" for p in both)


def test_target_paths_rejects_non_transformer():
    model = build_model("lenet5", num_classes=10)
    base = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)), train=False
    )["params"]
    with pytest.raises(ValueError, match="no adapter targets"):
        lora_target_paths(base, "all")


def test_init_is_a_normal_b_zero():
    base = _base_params(_tiny_bert())
    ad = init_lora_params(base, 2, "attention", jax.random.PRNGKey(1))
    a_leaves = [
        l for p, l in jax.tree_util.tree_flatten_with_path(ad)[0]
        if p[-1].key == "lora_a"
    ]
    b_leaves = [
        l for p, l in jax.tree_util.tree_flatten_with_path(ad)[0]
        if p[-1].key == "lora_b"
    ]
    assert len(a_leaves) == 4 and len(b_leaves) == 4
    assert all(float(jnp.abs(l).max()) > 0 for l in a_leaves)
    assert all(float(jnp.abs(l).max()) == 0 for l in b_leaves)
    assert all(l.shape == (32, 2) or l.shape[1] == 2 for l in a_leaves)


def test_merge_is_identity_at_init_and_matches_manual_update():
    base = _base_params(_tiny_bert())
    ad = init_lora_params(base, 2, "attention", jax.random.PRNGKey(1))
    merged = merge_lora_params(base, ad, alpha=8.0, rank=2)
    # B = 0 => merged == base EXACTLY, on every leaf
    jax.tree.map(
        lambda m, b: np.testing.assert_array_equal(
            np.asarray(m), np.asarray(b)
        ),
        merged, base,
    )
    # perturb one B: exactly that kernel moves, by (alpha/r)*A@B
    ad = jax.tree.map(lambda x: x, ad)  # copy
    blk = ad["TransformerBlock_0"]["Dense_0"]
    blk["lora_b"] = jnp.ones_like(blk["lora_b"]) * 0.01
    merged2 = merge_lora_params(base, ad, alpha=8.0, rank=2)
    want = np.asarray(
        base["TransformerBlock_0"]["Dense_0"]["kernel"]
    ) + 4.0 * np.asarray(blk["lora_a"] @ blk["lora_b"])
    np.testing.assert_allclose(
        np.asarray(merged2["TransformerBlock_0"]["Dense_0"]["kernel"]),
        want, rtol=1e-6,
    )
    # every other leaf untouched
    np.testing.assert_array_equal(
        np.asarray(merged2["TransformerBlock_0"]["Dense_1"]["kernel"]),
        np.asarray(base["TransformerBlock_0"]["Dense_1"]["kernel"]),
    )


def test_rank_must_be_low_rank_for_every_target():
    base = _base_params(_tiny_bert())  # hidden 32 => min dim 32
    with pytest.raises(ValueError, match="rank"):
        init_lora_params(base, 32, "attention", jax.random.PRNGKey(0))


def _frozen(model, shape=(1, 16), seed=0):
    """The base the driver would draw for ``seed`` (init_params' rng)."""
    return model.init_frozen(jax.random.PRNGKey(seed),
                             jnp.zeros(shape, jnp.int32))


def test_wrapper_params_are_adapters_and_apply_merges():
    model = build_lora_model(_tiny_bert(), "bert_tiny", rank=2,
                             alpha=8.0, target="attention")
    params = init_params(model, (16,), seed=0, input_dtype=jnp.int32)
    names = {
        p[-1].key for p in
        (kp for kp, _ in jax.tree_util.tree_flatten_with_path(params)[0])
    }
    assert names == {"lora_a", "lora_b"}
    x = jnp.zeros((2, 16), jnp.int32)
    base_params = _frozen(model)
    out = model.apply({"params": params, "frozen": base_params}, x,
                      train=False)
    assert out.shape == (2, 16, 32)
    # B = 0 at init => the merged model IS the base model
    out_base = model.base.apply({"params": base_params}, x, train=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_base))
    # merged_params exports the full-model tree
    merged = model.merged_params(params, base_params)
    assert set(merged.keys()) == set(base_params.keys())


def test_apply_without_the_frozen_base_raises():
    model = LoRAModel(_tiny_bert(), rank=2, alpha=8.0, target="attention")
    with pytest.raises(ValueError, match="frozen"):
        model.apply({"params": {}}, jnp.zeros((1, 16), jnp.int32))


def test_eval_shape_init_counts_adapters_and_holds_no_base():
    model = LoRAModel(_tiny_bert(), rank=2, alpha=8.0, target="attention")
    shapes = jax.eval_shape(
        lambda d: model.init(jax.random.PRNGKey(0), d, train=False)[
            "params"
        ],
        jax.ShapeDtypeStruct((1, 16), jnp.int32),
    )
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    # 4 attention kernels at hidden 32: qkv (32x2 + 2x96) x2 blocks,
    # attn-out (32x2 + 2x32) x2 blocks
    assert n == 2 * ((32 * 2 + 2 * 96) + (32 * 2 + 2 * 32))
    # the facade holds no array, before or after a concrete init: the
    # base is data (init_frozen), never a constant of apply
    model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    assert not any(isinstance(v, jax.Array) or isinstance(v, dict)
                   for v in vars(model).values())


def test_build_lora_model_rejects_unsupported_family():
    with pytest.raises(ValueError, match="supported"):
        build_lora_model(
            build_model("lenet5", num_classes=10), "lenet5",
            rank=2, alpha=8.0, target="all",
        )


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("key,value,match", [
    ("rank", 0, "rank"),
    ("alpha", 0.0, "alpha"),
    ("target", "attn", "target"),
])
def test_lora_config_knob_validation(key, value, match):
    cfg = get_named_config("bert_lora_federated")
    setattr(cfg.model.lora, key, value)
    with pytest.raises(ValueError, match=match):
        cfg.validate()


def test_lora_config_rejects_non_transformer_model():
    cfg = get_named_config("mnist_fedavg_2")
    cfg.model.lora.enabled = True
    with pytest.raises(ValueError, match="lenet5"):
        cfg.validate()


# ---------------------------------------------------------------------------
# driver e2e: shared shrunk config
# ---------------------------------------------------------------------------


def _cfg(out, engine="sharded", fuse=1, rounds=4, **over):
    cfg = get_named_config("bert_lora_federated")
    cfg.apply_overrides({
        "data.num_clients": 8, "server.cohort_size": 4,
        "server.sampling": "uniform",
        "model.kwargs.seq_len": 16, "model.kwargs.vocab_size": 32,
        "model.kwargs.hidden": 32, "model.kwargs.ff": 64,
        "data.synthetic_train_size": 256, "data.synthetic_test_size": 64,
        "data.max_examples_per_client": 32, "client.batch_size": 8,
        "server.num_rounds": rounds, "server.eval_every": 0,
        "run.out_dir": str(out), "run.metrics_flush_every": 2,
        "run.engine": engine, "run.fuse_rounds": fuse,
        "run.compute_dtype": "float32", "run.local_param_dtype": "",
        "run.client_vmap_width": 1, "run.host_pipeline": "numpy",
        **over,
    })
    return cfg.validate()


def _fit(cfg):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    return exp, exp.fit()


def _params_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)),
        a, b,
    )


def test_lora_off_is_bitwise_identical_to_default_build(tmp_path):
    """The off-switch contract: with enabled=false no wrapper is
    constructed anywhere, so a config carrying arbitrary (ignored) lora
    knobs builds the exact pre-LoRA program — params bitwise-equal to
    the untouched-default run."""
    cfg_a = _cfg(tmp_path / "a")
    cfg_a.model.lora.enabled = False
    cfg_a.model.lora.rank = 7
    cfg_a.model.lora.alpha = 3.0
    cfg_a.model.lora.target = "mlp"
    _, a = _fit(cfg_a)
    cfg_b = _cfg(tmp_path / "b")
    cfg_b.model.lora.enabled = False
    exp_b, b = _fit(cfg_b)
    _params_equal(a["params"], b["params"])
    # full-model params throughout, and the wire ratio degenerates to 1
    assert exp_b.wire_reduction_vs_full() == 1.0


def test_lora_parity_fused_and_engines(tmp_path):
    """Adapter space rides the established parity contract: fused ≡
    unfused BITWISE (adapters are just params to the scan carry) and
    sharded ≡ sequential at the engines' documented float tolerance."""
    _, sh = _fit(_cfg(tmp_path / "sh"))
    _, fu = _fit(_cfg(tmp_path / "fu", fuse=2))
    _, sq = _fit(_cfg(tmp_path / "sq", engine="sequential"))
    _params_equal(sh["params"], fu["params"])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4),
        sh["params"], sq["params"],
    )


def test_lora_composes_with_compression_and_ef(tmp_path):
    """topk/qsgd (and qsgd+EF) act on adapter leaves like any other
    params pytree — the runs complete with finite losses and the wire
    model reflects compression ON TOP of the adapter reduction."""
    for i, over in enumerate((
        {"server.compression": "qsgd"},
        {"server.compression": "topk",
         "server.compression_topk_ratio": 0.1},
        {"server.compression": "qsgd", "server.error_feedback": True},
    )):
        exp, state = _fit(_cfg(tmp_path / f"c{i}", **over))
        ev = exp.evaluate(state["params"])
        assert math.isfinite(ev["eval_loss"])


def test_apply_decomposed_matches_merged_apply():
    """The all-steps megabatch path never materializes per-client
    merged kernels: base GEMMs run on frozen (un-batched) weights and
    the adapter residual s·(x@A)@B is added at each target. Same map
    as the merged apply up to GEMM reassociation."""
    model = build_lora_model(_tiny_bert(), "bert_tiny", rank=2,
                             alpha=8.0, target="all")
    params = init_params(model, (16,), seed=0, input_dtype=jnp.int32)
    # B = 0 at init would make the residual vanish; bump it so the
    # adapters actually contribute
    params = jax.tree_util.tree_map_with_path(
        lambda p, l: l + 0.02 if p[-1].key == "lora_b" else l, params
    )
    x = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 32)
    variables = {"params": params, "frozen": _frozen(model)}
    merged = model.apply(variables, x, train=False)
    dec = model.apply_decomposed(variables, x, train=False)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(merged), atol=1e-6, rtol=2e-5
    )


def test_lora_megabatch_all_steps_matches_spatial(tmp_path):
    """All-steps LoRA megabatch: the frozen base contracts the
    flattened [K_local*batch] megabatch un-batched in EVERY local step
    (only the rank-r adapter GEMMs stay per-client), and the result
    still matches spatial training at the layouts' documented
    GEMM-reassociation tolerance."""
    _, sp = _fit(_cfg(tmp_path / "sp"))
    _, mb = _fit(_cfg(tmp_path / "mb",
                      **{"run.cohort_layout": "megabatch"}))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=2e-5),
        sp["params"], mb["params"],
    )


# ---------------------------------------------------------------------------
# wire accounting (satellite: the 100-1000x claim is a logged number)
# ---------------------------------------------------------------------------


def test_named_config_wire_reduction_exceeds_100x():
    """The shipped `bert_lora_federated` geometry (bert-tiny, rank-2
    attention adapters): full-delta ÷ adapter upload bytes ≥ 100× —
    computed from the same analytic wire model the counters log, no fit
    needed (pure function of the config)."""
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cfg = get_named_config("bert_lora_federated")
    cfg.apply_overrides({
        "data.num_clients": 8, "server.cohort_size": 4,
        "data.synthetic_train_size": 64, "data.synthetic_test_size": 32,
        "run.out_dir": "",
    })
    exp = Experiment(cfg, echo=False)
    assert exp.wire_reduction_vs_full() >= 100.0, (
        exp.wire_reduction_vs_full()
    )
    # the adapter subspace really is what the counters meter
    coords, nbytes = exp._param_stats()
    f_coords, f_bytes = exp._full_param_stats()
    assert coords * 100 <= f_coords


def test_wire_reduction_logged_per_round_and_in_run_summary(tmp_path):
    """Every round record carries upload_bytes (adapter), its full-delta
    twin upload_bytes_full, and wire_reduction_vs_full; run_summary
    carries the totals + the ratio — so the communication claim is a
    logged number, not prose."""
    cfg = _cfg(tmp_path, rounds=4)
    exp, _ = _fit(cfg)
    path = os.path.join(str(tmp_path), cfg.name + ".metrics.jsonl")
    recs = [json.loads(l) for l in open(path)]
    rounds = [r for r in recs if "round" in r and "upload_bytes" in r]
    assert rounds
    _, p_bytes = exp._param_stats()
    _, f_bytes = exp._full_param_stats()
    red = exp.wire_reduction_vs_full()
    assert red > 1.0
    for r in rounds:
        k = r["upload_bytes"] // p_bytes
        assert r["upload_bytes"] == k * p_bytes  # adapter-only uploads
        assert r["upload_bytes_full"] == k * f_bytes
        assert r["wire_reduction_vs_full"] == round(red, 2)
    summary = [r for r in recs if r.get("event") == "run_summary"]
    assert summary and summary[-1]["wire_reduction_vs_full"] == round(red, 2)
    assert summary[-1]["upload_bytes_full"] == sum(
        r["upload_bytes_full"] for r in rounds
    )


def test_wire_reduction_is_one_without_lora(tmp_path):
    cfg = _cfg(tmp_path, rounds=2)
    cfg.model.lora.enabled = False
    exp, _ = _fit(cfg)
    path = os.path.join(str(tmp_path), cfg.name + ".metrics.jsonl")
    recs = [json.loads(l) for l in open(path)]
    rounds = [r for r in recs if "round" in r and "upload_bytes" in r]
    assert rounds
    for r in rounds:
        assert r["wire_reduction_vs_full"] == 1.0
        assert r["upload_bytes_full"] == r["upload_bytes"]


# ---------------------------------------------------------------------------
# adapter-space robustness (satellite: the PR 6 headline matrix in
# adapter space)
# ---------------------------------------------------------------------------


def _robust_cfg(out, name, **over):
    """8-client full-participation cohort under sign_flip at fraction
    0.25 => exactly f = 2 of 8 compromised slots — the PR 6 headline
    shape, now with the wire stack carrying ONLY low-rank factors."""
    cfg = get_named_config("bert_lora_federated")
    cfg.name = name
    cfg.apply_overrides({
        "data.num_clients": 8, "server.cohort_size": 8,
        "server.sampling": "uniform",
        "model.kwargs.seq_len": 16, "model.kwargs.vocab_size": 32,
        "data.synthetic_train_size": 512, "data.synthetic_test_size": 128,
        "data.max_examples_per_client": 64, "client.batch_size": 8,
        "server.num_rounds": 16, "server.eval_every": 0,
        "run.out_dir": str(out), "run.metrics_flush_every": 8,
        "run.compute_dtype": "float32", "run.local_param_dtype": "",
        "run.client_vmap_width": 1, "run.host_pipeline": "numpy",
        **over,
    })
    return cfg.validate()


# measured on this config (seed 0): benign 3.32, krum-under-attack 3.31,
# reputation-under-attack 3.33 — all inside the band; plain
# weighted_mean under attack 3.83, above chance ln(32) = 3.47
_BAND = 3.42
_ATTACK = {"attack.kind": "sign_flip", "attack.fraction": 0.25,
           "attack.scale": 10.0}


def _robust_run(out, name, **over):
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(_robust_cfg(out, name, **over), echo=False)
    state = exp.fit()
    return exp, state, exp.evaluate(state["params"])


@pytest.fixture(scope="module")
def undefended_losses(tmp_path_factory):
    """(benign, attacked) eval loss of the plain weighted mean: fitted
    once for the two defenses' cases below."""
    out = tmp_path_factory.mktemp("undefended")
    return (_robust_run(out, "lr_benign")[2]["eval_loss"],
            _robust_run(out, "lr_mean_atk", **_ATTACK)[2]["eval_loss"])


_LOWRANK_DEFENSES = {
    "krum": {"server.aggregator": "krum", "server.krum_byzantine": 2},
    "reputation": {"run.obs.client_ledger.enabled": True,
                   "server.reputation.enabled": True},
}


@pytest.mark.parametrize("defense", sorted(_LOWRANK_DEFENSES))
def test_signflip_on_lowrank_factors_matrix(tmp_path, undefended_losses,
                                            defense):
    """sign_flip on the adapter factors at f = 2/8: the plain weighted
    mean degrades past chance while krum — ranking FLATTENED FACTORS —
    and the reputation-weighted mean (ledger norm/cosine computed in
    adapter space) hold the benign band; the in-program flags identify
    the compromised set."""
    benign, mean_atk = undefended_losses
    assert benign < _BAND, benign
    assert mean_atk > math.log(32), (
        f"weighted_mean survived sign_flip on low-rank factors: "
        f"{mean_atk} (benign {benign})"
    )
    exp_r, state_r, defended = _robust_run(
        tmp_path, f"lr_{defense}_atk", **_ATTACK,
        **_LOWRANK_DEFENSES[defense],
    )
    assert defended["eval_loss"] < _BAND, (
        f"{defense} lost the benign band in adapter space: {defended}"
    )
    if defense != "reputation":
        return
    # the adapter-space forensics found the attackers
    led = np.asarray(jax.device_get(state_r["ledger"]))
    byz = np.asarray(exp_r.compromised)
    assert len(byz) == 2
    rate = led[:, 1] / np.maximum(led[:, 0], 1.0)
    assert (rate[byz] > 0.5).all(), rate
    honest = np.setdiff1d(np.arange(8), byz)
    assert (rate[honest] < 0.3).all(), rate


# ---------------------------------------------------------------------------
# convergence band for the named config (shrunk to CPU budget)
# ---------------------------------------------------------------------------


def test_bert_lora_federated_converges_in_band(tmp_path):
    """The shipped config's convergence contract, shrunk to CPU scale
    (same model family, adapter geometry, streaming sampler, natural
    partition): adapter-only training moves the merged model measurably
    below the chance floor ln(vocab) within the smoke window — the
    checked-in band. The full-scale band lands via the driver's BENCH
    runs. 24 rounds: the plateau escape at this geometry sits near
    round 16, where the band was trajectory-sensitive at GEMM-
    reassociation level (the all-steps decomposed megabatch apply is
    such a reassociation); by 24 the margin is ~3x the band for either
    trajectory."""
    cfg = get_named_config("bert_lora_federated")
    cfg.apply_overrides({
        "data.num_clients": 16, "server.cohort_size": 8,
        "model.kwargs.seq_len": 16, "model.kwargs.vocab_size": 32,
        "data.synthetic_train_size": 512, "data.synthetic_test_size": 128,
        "data.max_examples_per_client": 64, "client.batch_size": 8,
        "server.num_rounds": 24, "server.eval_every": 0,
        "run.out_dir": str(tmp_path), "run.metrics_flush_every": 8,
        "run.compute_dtype": "float32", "run.local_param_dtype": "",
        "run.client_vmap_width": 1,
    })
    cfg.validate()
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    exp = Experiment(cfg, echo=False)
    state = exp.fit()
    ev = exp.evaluate(state["params"])
    chance = math.log(32)
    assert ev["eval_loss"] < chance - 0.04, (ev, chance)
    # and the trained tree really is adapters only
    names = {
        kp[-1].key for kp, _ in
        jax.tree_util.tree_flatten_with_path(state["params"])[0]
    }
    assert names == {"lora_a", "lora_b"}


# ---------------------------------------------------------------------------
# the PR 9 plane end to end: store-backed, streaming sampler, paged
# ledger — on adapter uploads (tier-1 CPU smoke)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lora_store(tmp_path_factory):
    """A small on-disk LM client store built from the SAME federation
    the in-memory shrunk config generates (write_store conversion), so
    store-backed and in-memory runs see identical shards."""
    from colearn_federated_learning_tpu.data import build_federated_data
    from colearn_federated_learning_tpu.data.store import write_store

    out = str(tmp_path_factory.mktemp("lora_store") / "store")
    cfg = get_named_config("bert_lora_federated")
    cfg.apply_overrides({
        "data.num_clients": 8,
        "model.kwargs.seq_len": 16, "model.kwargs.vocab_size": 32,
        "data.synthetic_train_size": 256, "data.synthetic_test_size": 64,
    })
    fed = build_federated_data(cfg.data, seed=cfg.run.seed,
                               **cfg.model.kwargs)
    write_store(out, fed)
    return out


def _store_cfg(out, store_dir, engine="sharded", **over):
    return _cfg(
        out, engine=engine, rounds=4,
        **{
            "data.store.dir": store_dir, "data.placement": "stream",
            "server.sampling": "streaming",
            "run.obs.client_ledger.enabled": True,
            "run.obs.client_ledger.log_every": 2,
            **over,
        },
    )


def test_store_backed_streaming_lora_smoke(tmp_path, lora_store):
    """The tentpole's end-to-end composition: mmap LM store + stream
    placement + O(cohort·log) streaming sampler + periodic ledger — all
    carrying ONLY adapter factors on the wire. Sharded ≡ sequential at
    the engines' float tolerance on the same store; the paged-ledger
    variant (hot_capacity) lands the same count/flag columns."""
    exp_sh, sh = _fit(_store_cfg(tmp_path / "sh", lora_store))
    assert exp_sh.wire_reduction_vs_full() > 1.0
    _, sq = _fit(_store_cfg(tmp_path / "sq", lora_store,
                            engine="sequential"))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4),
        sh["params"], sq["params"],
    )
    # paged ledger: the hot set + cold spill merge to the dense rows
    _, pg = _fit(_store_cfg(
        tmp_path / "pg", lora_store,
        **{"run.obs.client_ledger.hot_capacity": 4},
    ))
    _params_equal(sh["params"], pg["params"])


def test_store_backed_lora_bitwise_vs_materialized_twin(tmp_path,
                                                        lora_store):
    """PR 9's store contract survives the adapter plane: the
    store-backed streaming-mmap run is BITWISE-equal to the
    materialized in-memory twin over the same store on the same seed
    (host pipeline pinned to numpy on both sides)."""
    _, st = _fit(_store_cfg(tmp_path / "st", lora_store))
    _, tw = _fit(_store_cfg(
        tmp_path / "tw", lora_store,
        **{"data.store.materialize": True, "data.placement": "hbm"},
    ))
    _params_equal(st["params"], tw["params"])
