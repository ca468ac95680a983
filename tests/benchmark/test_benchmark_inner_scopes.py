"""The scopes beneath ``round_local_train`` (PR 23): that the round
programs carry them, and that ``readers/inner_scope_ms_round.py`` splits
the outer scope's self time by them — on hand-made events, then on a
trace recorded on the chip (``fixtures/host_*.xplane.pb.gz``, cut down
by ``fixtures/make_host_fixture.py``)."""

import pytest

from bench_paths import BENCH_DIR
from harness import catalog
from harness import trace_reduce as rd
from harness.trace_reduce import DeviceTrace, Op

LT, SA = "round_local_train", "round_server_apply"
SCOPES = (LT, "round_aggregate", SA)
INNER = catalog.load_module("readers", "inner_scope_ms_round", ("read",),
                            BENCH_DIR)
SCOPE_MS = catalog.load_reader("scope_ms_round", BENCH_DIR)


def _ctx(ops, fuse=1):
    """One device, one period [0, 1e6) of the round program."""
    dev = DeviceTrace(0, [Op(*o) for o in ops])
    dev.ops.sort(key=lambda o: (o.start, -o.end))
    rd._fill_self_times(dev.ops)
    return {"windows": [(dev, 0.0, 1e6, 1)], "fuse": fuse, "scopes": SCOPES,
            "reduce": rd}


@pytest.mark.parametrize("component,want", [
    ("local_grad", ("local_grad", [])),
    ("vmap(local_grad)", ("local_grad", ["vmap"])),
    ("vmap(transpose(jvp(local_grad)))",
     ("local_grad", ["vmap", "transpose", "jvp"])),
    ("transpose(jvp(ResNet18))", ("ResNet18", ["transpose", "jvp"])),
    ("vmap()", ("", ["vmap"])),
    ("make_dp_grad_fn.<locals>._noise_and_mean",
     ("make_dp_grad_fn.<locals>._noise_and_mean", [])),
], ids=["plain", "vmap", "nested", "other_name", "empty", "not_wrapped"])
def test_transform_wrappers_are_stripped(component, want):
    assert INNER.unwrap(component) == want


P = f"jit(round_fn)/while/body/closed_call/{LT}/while/body/closed_call"
# 1 ms of one chip. The step loop (while.1, self 20) holds: the batch
# gather (30), the forward (100) and backward (200 + 50 named by the
# transposed region itself) under vmap(local_grad), a reduce whose path
# lost its prefix (40, forward), a DP microbatch loop inside local_grad
# (while.2: per-example backward 60, clip 70, a compiler copy without
# op_name 10, own 5), the noise (25), the update (80) — then the Pallas
# apply outside local training (90).
HAND = [
    ("while.1", 0, 800_000, f"jit(round_fn)/while/body/closed_call/{LT}/while"),
    ("fusion.1", 0, 30_000, f"{P}/vmap(local_gather)/gather"),
    ("fusion.2", 30_000, 130_000, f"{P}/vmap(local_grad)/jvp(Net)/Conv_0/conv"),
    ("fusion.3", 130_000, 330_000,
     f"{P}/vmap(local_grad)/transpose(jvp(Net))/Conv_0/conv"),
    ("fusion.4", 330_000, 380_000,
     f"{P}/vmap(local_grad)/transpose(vmap(local_grad))/jvp(Net)/select_n"),
    ("reduce.5", 380_000, 420_000, "vmap(local_grad)/jvp(Net)/GroupNorm_0/reduce_sum"),
    ("while.2", 420_000, 565_000, f"{P}/local_grad/while"),
    ("fusion.6", 420_000, 480_000,
     f"{P}/local_grad/while/body/dp_example_grad/vmap(transpose(jvp(Net)))/mul"),
    ("fusion.7", 480_000, 550_000, f"{P}/local_grad/while/body/dp_clip/mul"),
    ("copy.8", 550_000, 560_000, ""),
    ("fusion.9", 565_000, 590_000, f"{P}/local_grad/dp_noise/jit(_normal)/erf_inv"),
    ("fusion.10", 590_000, 670_000, f"{P}/vmap(local_opt)/sub"),
    ("custom-call.11", 800_000, 890_000, f"jit(round_fn)/{SA}/pallas_call"),
]


@pytest.mark.parametrize("args,want_ns", [
    ({"scopes": ["local_grad"]}, 100 + 200 + 50 + 40 + 5 + 60 + 70 + 10 + 25),
    ({"scopes": ["local_grad"], "part": "backward"}, 200 + 50 + 60),
    ({"scopes": ["local_opt"]}, 80),
    # the loop's own time: 800 - 30 - 100 - 200 - 50 - 40 - 145 - 25 - 80
    ({"scopes": ["local_grad", "local_opt"], "complement": True}, 30 + 130),
    ({"scopes": ["dp_clip", "dp_noise"]}, 70 + 25),
    ({"scopes": ["dp_example_grad"]}, 60),
    ({"scopes": ["pallas_call"], "outer": SA}, 90),
], ids=["grad", "backward", "opt", "other", "dp_clip_noise", "dp_example",
        "another_outer_scope"])
def test_inner_scopes_of_hand_made_events(args, want_ns):
    assert INNER.read(_ctx(HAND), **args) == pytest.approx(want_ns / 1e3)


def test_the_inner_readings_partition_the_outer_scope():
    ctx = _ctx(HAND, fuse=4)
    parts = [INNER.read(ctx, scopes=["local_grad"]),
             INNER.read(ctx, scopes=["local_opt"]),
             INNER.read(ctx, scopes=["local_grad", "local_opt"],
                        complement=True)]
    assert sum(parts) == pytest.approx(SCOPE_MS(ctx, scopes=[LT]))
    assert sum(parts) == pytest.approx(0.8 / 4)
    assert INNER.read(ctx, scopes=["local_grad"], part="backward") < parts[0]


def test_inner_readings_are_of_the_chip_scope_ms_round_reads():
    """Several chips: every inner reading comes from the chip with most
    self time under the outer scope, so that the parts still add up to
    ``scope_ms_round`` (its maximum over chips)."""
    slow = [("while.1", 0, 900_000, f"jit(round_fn)/{LT}/while"),
            ("fusion.2", 0, 500_000, f"{P}/vmap(local_grad)/jvp(Net)/conv"),
            ("fusion.10", 500_000, 600_000, f"{P}/vmap(local_opt)/sub")]
    ctx = _ctx(HAND)
    other = _ctx(slow)
    ctx["windows"] = ctx["windows"] + [
        (DeviceTrace(1, other["windows"][0][0].ops), 0.0, 1e6, 1)]
    parts = [INNER.read(ctx, scopes=["local_grad"]),
             INNER.read(ctx, scopes=["local_opt"]),
             INNER.read(ctx, scopes=["local_grad", "local_opt"],
                        complement=True)]
    assert parts == pytest.approx([0.5, 0.1, 0.3])  # chip 1's, not chip 0's
    assert sum(parts) == pytest.approx(SCOPE_MS(ctx, scopes=[LT]))


def test_a_program_without_the_scopes_reads_nothing():
    """The parent's round program: the reader returns None, it neither
    raises nor calls all of local training 'other'."""
    old = [("while.1", 0, 800_000, f"jit(round_fn)/{LT}/while"),
           ("fusion.1", 0, 500_000, f"jit(round_fn)/{LT}/while/body/vmap()/mul")]
    for args in ({"scopes": ["local_grad"]},
                 {"scopes": ["local_grad", "local_opt"], "complement": True}):
        assert INNER.read(_ctx(old), **args) is None
    assert INNER.read({**_ctx(HAND), "windows": None}, scopes=["local_grad"]) is None
    with pytest.raises(ValueError):
        INNER.read(_ctx(HAND), scopes=["local_grad"], part="forward")


@pytest.mark.parametrize("preset,wanted", [
    ("dry_r18_fused", ("local_gather", "local_grad", "local_opt")),
    ("dry_vit_dp", ("local_gather", "local_grad", "local_opt",
                    "dp_example_grad", "dp_clip", "dp_noise")),
])
def test_round_programs_carry_the_inner_scopes(preset, wanted):
    """Lowers and compiles the rehearsal presets' round programs as the
    benchmark does and reads the compiled text: every inner scope is
    there, each only beneath ``round_local_train``, the DP scopes only
    inside ``local_grad``; the outer scopes are intact."""
    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.obs import executables as exec_mod
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cell = catalog.load_workload(preset)
    config = catalog.load_config(cell["config"])
    cfg = resolve_config(cell["named_config"],
                         catalog.experiment_overrides(cell, config, 0))
    exp = Experiment(cfg, echo=False)
    exec_mod.install(exp._exec_reg)
    try:
        exp.run_round(exp._place_state(exp.init_state(0)), 0)
        names = {}
        for entry in exp._exec_reg._cache.values():
            if entry["name"].startswith("round."):
                names.update(rd.scopes_from_hlo(entry["compiled"].as_text()))
    finally:
        exec_mod.uninstall()
        exp._stop_prefetch()
    paths = set(names.values())
    assert any(f"/{LT}/" in p for p in paths) and any(f"/{SA}/" in p for p in paths)
    for scope in wanted:
        mine = [p for p in paths if INNER.inner_in_path(p, (scope,))]
        assert mine, scope
        for path in mine:
            parts = path.split("/")
            at = next(i for i, c in enumerate(parts)
                      if INNER.unwrap(c)[0] == scope)
            # a path that kept its head names the outer scope before it
            if parts[0] == "jit(round_fn)":
                assert LT in parts[:at], path
            assert not set(parts) & (set(SCOPES) - {LT}), path
            if scope.startswith("dp_") and parts[0] == "jit(round_fn)":
                assert "local_grad" in [INNER.unwrap(c)[0] for c in parts[:at]], path


# -- traces recorded on the chip -----------------------------------------

import host_fixtures  # noqa: E402


@pytest.fixture(scope="module", params=sorted(host_fixtures.RECORDED))
def recorded(request, tmp_path_factory):
    return request.param, host_fixtures.unpack(
        request.param, tmp_path_factory.mktemp("bench"))


def test_recorded_inner_readings_add_up_to_local_training(recorded):
    """``local_grad + local_opt + local_other`` is ``round_local_train``'s
    self time as ``scope_ms_round`` reads it, on the chip's own events
    (transform-wrapped names, ops that lost their path's head, copies
    without ``op_name``) — to 0.1 %, and in fact to rounding."""
    _, ctx = recorded
    grad = INNER.read(ctx, scopes=["local_grad"])
    opt = INNER.read(ctx, scopes=["local_opt"])
    other = INNER.read(ctx, scopes=["local_grad", "local_opt"], complement=True)
    whole = SCOPE_MS(ctx, scopes=[LT])
    assert min(grad, opt, other) > 0
    assert grad + opt + other == pytest.approx(whole, rel=1e-3)
    assert grad + opt + other == pytest.approx(whole, rel=1e-9)
    assert grad > opt  # forward and backward outweigh the update


def test_recorded_backward_is_a_part_of_the_gradient(recorded):
    _, ctx = recorded
    grad = INNER.read(ctx, scopes=["local_grad"])
    backward = INNER.read(ctx, scopes=["local_grad"], part="backward")
    assert 0 < backward < grad


def test_recorded_dp_scopes_are_in_the_dp_program_only(recorded):
    name, ctx = recorded
    dp = INNER.read(ctx, scopes=["dp_clip", "dp_noise"])
    if "dp" in name:
        assert 0 < dp < INNER.read(ctx, scopes=["local_grad"])
        assert INNER.read(ctx, scopes=["dp_example_grad"]) > 0
    else:
        assert dp is None


def test_recorded_wrapped_names_resolve(recorded):
    """What the chip's compiler leaves of the names: the megabatch
    program's components are ``vmap(local_grad)`` and
    ``transpose(jvp(...))``, never the bare name."""
    name, ctx = recorded
    paths = {op.scope for dev, *_ in ctx["windows"] for op in dev.ops}
    hits = {p for p in paths if INNER.inner_in_path(p, ("local_grad",))}
    assert hits
    assert any(INNER.inner_in_path(p, ("local_grad",))[1] for p in hits)
    if "r18" in name:
        assert not any("local_grad" in p.split("/") for p in hits)
        assert any("vmap(local_grad)" in p.split("/") for p in hits)
