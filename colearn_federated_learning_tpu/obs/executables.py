"""Compiled-program observatory (``run.obs.executables``,
obs/executables.py): the executable registry that makes XLA's own view
of every compiled program — FLOPs, HBM bytes, donation, retraces — a
first-class run artifact.

The engines' jit sites are wrapped with :func:`instrument`, which is a
no-op passthrough until a registry is installed (the driver installs
one per fit when ``run.obs.executables`` is on). With a registry
active, each wrapped call routes through the registry's AOT executable
cache: the first call for a given (name, avals, shardings, statics)
fingerprint lowers and compiles the program explicitly
(``fn.lower(*args).compile()``) — the SAME lowering ``jax.jit`` would
produce, so execution is bitwise-identical — and harvests, per
compiled program:

* ``cost_analysis()`` FLOPs / bytes-accessed (XLA's cost model of the
  optimized HLO: the FLOP count inside the program, beside the
  benchmark's shape count in ``benchmark/flops/``),
* ``memory_analysis()`` argument / output / temp / generated-code
  bytes (the predicted HBM working set; donation-aliased bytes are
  counted once),
* the donation map (which inputs the program consumes in place),
* a stable hex fingerprint (name + per-leaf aval/sharding descriptors
  + statics + backend), and the compile wall-ms,

queued as ``executable_compiled`` JSONL records the driver logs at
flush boundaries. Recompiles of an already-seen program name diff the
new fingerprint's per-argument descriptors against the cached ones and
queue a ``retrace`` record naming exactly which argument changed
shape/dtype/sharding. A live HBM ledger tracks the high-water mark
over the programs called in each flush window (``hbm_watermark``
records + run peak in ``run_summary``).

Failure contract: a backend that lacks an ANALYSIS (cost/memory) still
trains — those fields degrade to null. A program that fails to lower
or compile, or an AOT executable that rejects its call, does not: the
registry queues a ``warning`` record (``executable_lower_failed`` /
``executable_call_failed``) and re-raises. It never re-dispatches
through plain ``jit`` — that would compile the program a second time
and let a run that could not use what it built finish as if nothing
happened. The driver logs the queued records on every exit path.

OOM preflight: with ``preflight=True`` the registry lowers and
compiles but NEVER executes — wrapped calls return abstract
``jax.ShapeDtypeStruct`` outputs — so ``colearn preflight`` can walk
one round of the driver's dispatch path and report the predicted peak
HBM (naming the dominant buffers) without binding output or temp
buffers. With ``run.obs.hbm_budget_mb`` set, a newly compiled
program whose predicted peak exceeds the budget raises
:class:`HbmBudgetError` BEFORE the program executes — the driver's
pre-fit/over-budget abort (not retried by ``run.max_retries``).
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from colearn_federated_learning_tpu.obs.spans import _NULL_SPAN

__all__ = [
    "ExecutableRegistry",
    "HbmBudgetError",
    "current",
    "device_hbm_capacity",
    "install",
    "instrument",
    "uninstall",
]


def device_hbm_capacity() -> int:
    """``bytes_limit`` of device 0's allocator — the capacity the
    over-capacity warning compares against. 0 when the backend doesn't
    report memory stats (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
        return int((stats or {}).get("bytes_limit", 0))
    except Exception:
        return 0

# the process-global active registry (installed by the driver per fit,
# or by `colearn preflight` around its dry round). A module-level slot
# — not a contextvar — on purpose: the engines' wrappers are built once
# at factory time and must see a registry installed AFTER they were
# created.
_ACTIVE: Optional["ExecutableRegistry"] = None

# retrace records cap the per-argument diff list: a resharded state
# pytree would otherwise name hundreds of leaves for one cause
_MAX_CHANGED = 8
# dominant-buffer lists in preflight reports / budget errors
_TOP_BUFFERS = 3


class HbmBudgetError(RuntimeError):
    """A newly compiled program's predicted peak HBM exceeds
    ``run.obs.hbm_budget_mb``. Raised BEFORE the program executes;
    deliberately not retried by ``run.max_retries`` (recompiling the
    same program predicts the same peak)."""


def install(registry: "ExecutableRegistry") -> None:
    global _ACTIVE
    _ACTIVE = registry


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def current() -> Optional["ExecutableRegistry"]:
    return _ACTIVE


def instrument(name: str, fn: Callable, *,
               static_argnums: Tuple[int, ...] = (),
               rounds_per_call: int = 1) -> Callable:
    """Wrap a jitted callable so an installed registry intercepts its
    lowerings. Without a registry (or under tracing — e.g. the sharded
    round_fn inlined inside the device-plane program) the wrapper is a
    plain passthrough to ``fn``. ``rounds_per_call`` declares how many
    federated rounds one call advances (``run.fuse_rounds`` for the
    fused programs) so per-round FLOP joins normalize correctly."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        reg = _ACTIVE
        if reg is None:
            return fn(*args, **kwargs)
        return reg.call(name, fn, args, kwargs,
                        static_argnums=static_argnums,
                        rounds_per_call=rounds_per_call)

    wrapper.__wrapped__ = fn
    return wrapper


# ---------------------------------------------------------------------------
# fingerprinting


def _on_roomy_stack(fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` with room on the interpreter's frame
    stack: see the assignment below."""
    return fn(*args, **kwargs)


# CPython (3.11 to 3.13) keeps Python frames on a per-thread stack of
# 16 KiB chunks and unmaps a chunk the moment the frame that opened it
# returns. Tracing the ViT round makes 14.7 M calls at a depth of 60 to
# 100 frames, which is about 16 KiB: where the busiest calls straddle a
# chunk's end, every one of them maps a chunk, takes a page fault in it
# and unmaps it again, 50 times the cost of a call (7 us against 0.15).
# Which calls straddle it is decided by the BYTES of every frame
# beneath, so a new local in the trainer's step, or one more frame
# under run_round, moved the ViT cells' warm set-up by 17-24 % with an
# identical program (PERF.md, PR 23 and PR 25; on the CPU the same
# lowering takes 6.0 to 12.2 s and 12 k to 378 k page faults as frames
# are put beneath it, period 16 KiB). A frame too large for a chunk is
# given a chunk of its own that is twice its size: this one asks for
# 1 MiB, so every frame the trace pushes after it has a second MiB of
# room and crosses nothing (on the chip's host the ViT round traces in
# 6.2 s instead of 39.5). The frame's slots are never touched, so the
# memory is never resident.
_on_roomy_stack.__code__ = _on_roomy_stack.__code__.replace(
    co_stacksize=(1 << 20) // 8)


def _leaf_desc(x) -> tuple:
    """Hashable per-leaf descriptor with exactly jit's cache-key
    granularity: aval (shape/dtype/weak_type) + sharding for arrays,
    dtype-kind only for python scalars (jit keys them by weak dtype,
    not value)."""
    aval = getattr(x, "aval", None)
    if aval is not None:
        return ("a", tuple(aval.shape), str(aval.dtype),
                bool(getattr(aval, "weak_type", False)),
                getattr(x, "sharding", None))
    if isinstance(x, jax.ShapeDtypeStruct):
        return ("s", tuple(x.shape), str(x.dtype),
                getattr(x, "sharding", None))
    if isinstance(x, (np.ndarray, np.generic)):
        return ("n", tuple(x.shape), str(x.dtype))
    if isinstance(x, (bool, int, float, complex)):
        return ("p", type(x).__name__)
    # non-array leaf the jit would treat structurally — repr-keyed
    return ("o", repr(x)[:120])


def _leaf_is_tracer(x) -> bool:
    try:
        return isinstance(x, jax.core.Tracer)
    except Exception:
        return False


def _cache_key(args, kwargs, static_argnums):
    """(statics, treedef, leaf descriptors) — hashable, computed on
    every registry call, so it must stay allocation-light. Returns
    (key, leaves) or (None, None) when a leaf is a tracer (the wrapper
    is being inlined inside an outer program)."""
    statics = tuple(
        repr(args[i]) if i < len(args) else None for i in static_argnums
    )
    dyn = tuple(
        a for i, a in enumerate(args) if i not in static_argnums
    )
    leaves, treedef = jax.tree_util.tree_flatten((dyn, kwargs))
    for leaf in leaves:
        if _leaf_is_tracer(leaf):
            return None, None
    return (statics, treedef, tuple(_leaf_desc(x) for x in leaves)), leaves


def _arg_paths(fn, args, kwargs, static_argnums):
    """Per-leaf (path, {shape, dtype, sharding}) descriptors with
    signature-derived names — the retrace diff and dominant-buffer
    naming read these. Best-effort: positional ``arg<i>`` names when
    the signature can't be bound."""
    names: List[Tuple[str, Any]] = []
    try:
        sig = inspect.signature(fn)
        bound = sig.bind(*args, **kwargs)
        items = list(bound.arguments.items())
    except Exception:
        items = [(f"arg{i}", a) for i, a in enumerate(args)]
        items += sorted(kwargs.items())
    static_names = set()
    try:
        params = list(inspect.signature(fn).parameters)
        static_names = {params[i] for i in static_argnums
                        if i < len(params)}
    except Exception:
        static_names = {f"arg{i}" for i in static_argnums}
    out: Dict[str, Dict[str, Any]] = {}
    for pname, val in items:
        if pname in static_names:
            out[pname] = {"shape": None, "dtype": None,
                          "sharding": None, "static": repr(val)[:120]}
            continue
        try:
            flat = jax.tree_util.tree_flatten_with_path(val)[0]
        except Exception:
            continue
        for path, leaf in flat:
            key = pname + jax.tree_util.keystr(path)
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            sharding = getattr(leaf, "sharding", None)
            out[key] = {
                "shape": None if shape is None else list(shape),
                "dtype": None if dtype is None else str(dtype),
                "sharding": None if sharding is None else repr(sharding),
            }
    _ = names
    return out


def _fingerprint_hex(name: str, key) -> str:
    """Stable hex fingerprint: name + statics + tree structure + leaf
    descriptors + backend/compile-option bits. Deterministic across
    runs of the same config (test-pinned)."""
    statics, treedef, descs = key
    h = hashlib.sha256()
    h.update(name.encode())
    h.update(repr(statics).encode())
    h.update(str(treedef).encode())
    for d in descs:
        h.update(repr(d).encode())
    h.update(jax.default_backend().encode())
    h.update(str(jax.device_count()).encode())
    h.update(str(bool(jax.config.jax_enable_x64)).encode())
    return h.hexdigest()[:16]


def _leaf_bytes(desc: Dict[str, Any]) -> int:
    if not desc.get("shape") and desc.get("shape") != []:
        return 0
    try:
        n = 1
        for d in desc["shape"]:
            n *= int(d)
        return n * np.dtype(desc["dtype"]).itemsize
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# the registry


class ExecutableRegistry:
    """Per-fit AOT executable cache + record queue. See module
    docstring for the full contract. Not thread-safe by design: the
    driver's dispatch loop is single-threaded."""

    def __init__(self, *, preflight: bool = False,
                 hbm_budget_bytes: int = 0,
                 device_capacity_bytes: int = 0,
                 tracer=None, backend: Optional[str] = None):
        self.preflight = preflight
        self.hbm_budget_bytes = int(hbm_budget_bytes)
        self.device_capacity_bytes = int(device_capacity_bytes)
        self.tracer = tracer
        self.backend = backend or jax.default_backend()
        self.round = 0  # the driver advances this before each dispatch
        # fingerprint-key -> {"compiled", "fingerprint", "name",
        #                     "abstract_out", "stats"}
        self._cache: Dict[Any, Dict[str, Any]] = {}
        # name -> {"fingerprint", "paths", "compiles", "peak_bytes"}
        self._programs: Dict[str, Dict[str, Any]] = {}
        self._records: List[Dict[str, Any]] = []
        # flush-window program names (for the hbm_watermark record)
        self._window: set = set()
        self.peak_bytes = 0
        self.peak_program: Optional[str] = None
        self.total_compiles = 0
        self.total_compile_ms = 0.0

    # -- spans ----------------------------------------------------------
    def _span(self, label: str, **args):
        if self.tracer is None:
            return _NULL_SPAN
        try:
            return self.tracer.span(label, **args)
        except Exception:
            return _NULL_SPAN

    # -- the wrapped-call entry point -----------------------------------
    def call(self, name: str, fn: Callable, args: tuple, kwargs: dict,
             *, static_argnums: Tuple[int, ...] = (),
             rounds_per_call: int = 1):
        try:
            key, _ = _cache_key(args, kwargs, static_argnums)
        except Exception:
            key = None
        if key is None:
            # tracer leaves (inlined inside an outer program) or an
            # unfingerprintable input: stay out of the way
            return fn(*args, **kwargs)
        hit = self._cache.get(key)
        if hit is not None:
            self._window.add(name)
            if self.preflight:
                return hit["abstract_out"]
            return self._run(name, hit["compiled"], args, kwargs)
        return self._compile_and_call(name, fn, args, kwargs, key,
                                      static_argnums, rounds_per_call)

    def _warn(self, kind: str, name: str, e: BaseException) -> None:
        self._records.append({
            "event": "warning",
            "warning": kind,
            "detail": f"{name}: {type(e).__name__}: {e}"[:300],
            "round": int(self.round),
        })

    def _run(self, name, compiled, args, kwargs):
        """Call the AOT executable. A rejected call (an input sharding
        or layout the fingerprint missed) is recorded and re-raised —
        see the module docstring's failure contract."""
        try:
            return compiled(*args, **kwargs)
        except Exception as e:
            self._warn("executable_call_failed", name, e)
            raise

    # -- slow path: first sight of a fingerprint ------------------------
    def _compile_and_call(self, name, fn, args, kwargs, key,
                          static_argnums, rounds_per_call):
        span = "obs.preflight" if self.preflight else "obs.executables"
        with self._span(span):
            fingerprint = _fingerprint_hex(name, key)
            t0 = time.perf_counter()
            try:
                # trace + lower, then compile or load from the persistent
                # cache: two spans of the start-up record (obs/spans.py),
                # which inherit the dispatch's ``round``
                with self._span("compile.lower", program=name):
                    lowered = _on_roomy_stack(fn.lower, *args, **kwargs)
                with self._span("compile.backend", program=name) as backend:
                    compiled = lowered.compile()
                    backend.note(cache=backend.cache)
            except Exception as e:
                compile_ms = (time.perf_counter() - t0) * 1e3
                self._emit_compiled(name, fingerprint, None, compile_ms,
                                    rounds_per_call)
                self._warn("executable_lower_failed", name, e)
                raise
            compile_ms = (time.perf_counter() - t0) * 1e3
            stats = self._harvest(lowered, compiled)
            paths = self._paths_or_none(fn, args, kwargs, static_argnums)
            prev = self._programs.get(name)
            if prev is not None and prev["fingerprint"] != fingerprint:
                self._emit_retrace(name, prev, fingerprint, paths)
            self._programs[name] = {
                "fingerprint": fingerprint,
                "paths": paths,
                "compiles": (prev["compiles"] + 1) if prev else 1,
                "peak_bytes": stats.get("peak_bytes"),
                "rounds_per_call": int(rounds_per_call),
                "stats": stats,
            }
            abstract_out = self._abstract_out(lowered)
            self._cache[key] = {
                "compiled": compiled,
                "fingerprint": fingerprint,
                "name": name,
                "abstract_out": abstract_out,
                "stats": stats,
            }
            self._window.add(name)
            self.total_compiles += 1
            self.total_compile_ms += compile_ms
            peak = stats.get("peak_bytes")
            if peak is not None and peak > self.peak_bytes:
                self.peak_bytes = int(peak)
                self.peak_program = name
            self._emit_compiled(name, fingerprint, stats, compile_ms,
                                rounds_per_call)
            self._check_budget(name, stats, paths)
        if self.preflight:
            return abstract_out
        return self._run(name, compiled, args, kwargs)

    # -- harvesting ------------------------------------------------------
    @staticmethod
    def _paths_or_none(fn, args, kwargs, static_argnums):
        try:
            target = getattr(fn, "__wrapped__", fn)
            return _arg_paths(target, args, kwargs, static_argnums)
        except Exception:
            return None

    @staticmethod
    def _harvest(lowered, compiled) -> Dict[str, Any]:
        """Pull cost/memory analysis off the compiled executable.
        Availability varies by backend and jax version — every field
        degrades to None independently, never raises (test-pinned)."""
        stats: Dict[str, Any] = {
            "flops": None, "bytes_accessed": None,
            "argument_bytes": None, "output_bytes": None,
            "temp_bytes": None, "generated_code_bytes": None,
            "alias_bytes": None, "peak_bytes": None,
            "donated_args": None,
        }
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if ca:
                flops = ca.get("flops")
                ba = ca.get("bytes accessed")
                stats["flops"] = None if flops is None else float(flops)
                stats["bytes_accessed"] = None if ba is None else float(ba)
        except Exception:
            pass
        try:
            mem = compiled.memory_analysis()
            if mem is not None:
                stats["argument_bytes"] = int(mem.argument_size_in_bytes)
                stats["output_bytes"] = int(mem.output_size_in_bytes)
                stats["temp_bytes"] = int(mem.temp_size_in_bytes)
                stats["generated_code_bytes"] = int(
                    mem.generated_code_size_in_bytes
                )
                stats["alias_bytes"] = int(mem.alias_size_in_bytes)
                # donation-aliased output bytes reuse their argument's
                # buffer — count the resident set once
                stats["peak_bytes"] = (
                    stats["argument_bytes"] + stats["output_bytes"]
                    - stats["alias_bytes"] + stats["temp_bytes"]
                    + stats["generated_code_bytes"]
                )
        except Exception:
            pass
        try:
            flat = jax.tree_util.tree_flatten(lowered.args_info)[0]
            stats["donated_args"] = sum(
                1 for a in flat if getattr(a, "donated", False)
            )
        except Exception:
            pass
        return stats

    @staticmethod
    def _abstract_out(lowered):
        """ShapeDtypeStruct pytree mirroring the program's outputs —
        what preflight-mode calls return instead of executing."""
        try:
            return jax.tree.map(
                lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype),
                lowered.out_info,
            )
        except Exception:
            return None

    # -- record construction --------------------------------------------
    def _emit_compiled(self, name, fingerprint, stats, compile_ms,
                       rounds_per_call):
        stats = stats or {}
        self._records.append({
            "event": "executable_compiled",
            "round": int(self.round),
            "name": name,
            "fingerprint": fingerprint,
            "compile_ms": round(float(compile_ms), 3),
            "flops": stats.get("flops"),
            "bytes_accessed": stats.get("bytes_accessed"),
            "argument_bytes": stats.get("argument_bytes"),
            "output_bytes": stats.get("output_bytes"),
            "temp_bytes": stats.get("temp_bytes"),
            "generated_code_bytes": stats.get("generated_code_bytes"),
            "peak_bytes": stats.get("peak_bytes"),
            "donated_args": stats.get("donated_args"),
            "rounds_per_call": int(rounds_per_call),
            "backend": self.backend,
            "preflight": bool(self.preflight),
        })

    def _emit_retrace(self, name, prev, fingerprint, paths):
        changed = []
        old = prev.get("paths") or {}
        new = paths or {}
        for arg in sorted(set(old) | set(new)):
            if old.get(arg) != new.get(arg):
                changed.append({
                    "arg": arg,
                    "before": old.get(arg),
                    "after": new.get(arg),
                })
        self._records.append({
            "event": "retrace",
            "round": int(self.round),
            "name": name,
            "fingerprint": fingerprint,
            "prev_fingerprint": prev["fingerprint"],
            "n_changed": len(changed),
            "changed": changed[:_MAX_CHANGED],
        })

    def _check_budget(self, name, stats, paths):
        peak = stats.get("peak_bytes")
        if peak is None:
            return
        cap = self.device_capacity_bytes
        if cap and peak > cap and not self.hbm_budget_bytes:
            self._records.append({
                "event": "warning",
                "warning": "hbm_over_capacity",
                "detail": (
                    f"{name}: predicted peak "
                    f"{peak / 2**20:.1f} MiB exceeds device capacity "
                    f"{cap / 2**20:.1f} MiB"
                ),
                "round": int(self.round),
            })
        budget = self.hbm_budget_bytes
        if budget and peak > budget:
            dom = self.dominant_buffers(name)
            dom_s = ", ".join(
                f"{a} ({b / 2**20:.1f} MiB)" for a, b in dom
            ) or "n/a"
            raise HbmBudgetError(
                f"program {name!r}: predicted peak HBM "
                f"{peak / 2**20:.1f} MiB exceeds run.obs.hbm_budget_mb="
                f"{budget // 2**20} ({budget / 2**20:.1f} MiB); "
                f"dominant buffers: {dom_s}"
            )

    # -- reporting -------------------------------------------------------
    def dominant_buffers(self, name: str) -> List[Tuple[str, int]]:
        """Largest input leaves of a program by bytes (+ the temp
        scratch as a pseudo-buffer when it dominates)."""
        entry = self._programs.get(name)
        if entry is None:
            return []
        paths = entry.get("paths") or {}
        sized = sorted(
            ((arg, _leaf_bytes(d)) for arg, d in paths.items()),
            key=lambda t: -t[1],
        )
        out = [(a, b) for a, b in sized[:_TOP_BUFFERS] if b > 0]
        stats = entry.get("stats") or {}
        temp = stats.get("temp_bytes")
        if temp and (not out or temp > out[-1][1]):
            out.append(("(temp scratch)", int(temp)))
            out.sort(key=lambda t: -t[1])
            out = out[:_TOP_BUFFERS]
        return out

    def drain_records(self) -> List[Dict[str, Any]]:
        recs, self._records = self._records, []
        return recs

    def watermark(self, last_round: int) -> Optional[Dict[str, Any]]:
        """One flush window's HBM high-water record: the max predicted
        peak over the programs called since the previous watermark.
        None when nothing ran (or nothing had memory analysis)."""
        names, self._window = self._window, set()
        best: Tuple[int, Optional[str]] = (0, None)
        for n in names:
            entry = self._programs.get(n)
            peak = (entry or {}).get("peak_bytes")
            if peak is not None and peak > best[0]:
                best = (int(peak), n)
        if best[1] is None:
            return None
        stats = self._programs[best[1]].get("stats") or {}
        arg_b = stats.get("argument_bytes") or 0
        out_b = stats.get("output_bytes") or 0
        alias_b = stats.get("alias_bytes") or 0
        return {
            "event": "hbm_watermark",
            "round": int(last_round),
            "watermark_bytes": best[0],
            "program": best[1],
            "resident_bytes": int(arg_b + out_b - alias_b),
            "temp_bytes": stats.get("temp_bytes"),
            "programs": len(names),
            "peak_bytes": int(self.peak_bytes),
        }

    def preflight_report(self) -> Dict[str, Any]:
        programs = []
        for name, entry in sorted(self._programs.items()):
            stats = entry.get("stats") or {}
            programs.append({
                "name": name,
                "fingerprint": entry["fingerprint"],
                "flops": stats.get("flops"),
                "argument_bytes": stats.get("argument_bytes"),
                "output_bytes": stats.get("output_bytes"),
                "temp_bytes": stats.get("temp_bytes"),
                "generated_code_bytes": stats.get("generated_code_bytes"),
                "peak_bytes": stats.get("peak_bytes"),
                "donated_args": stats.get("donated_args"),
                "dominant": [
                    {"arg": a, "bytes": b}
                    for a, b in self.dominant_buffers(name)
                ],
            })
        return {
            "backend": self.backend,
            "predicted_peak_bytes": int(self.peak_bytes),
            "predicted_peak_program": self.peak_program,
            "hbm_budget_bytes": int(self.hbm_budget_bytes),
            "device_capacity_bytes": int(self.device_capacity_bytes),
            "programs": programs,
        }


def _mib(n: Optional[int]) -> str:
    if n is None:
        return "n/a"
    return f"{n / 2**20:,.1f}"


def format_preflight_report(report: Dict[str, Any]) -> str:
    """Human table for `colearn preflight`: per-program predicted HBM
    footprint with the dominant buffers, then the peak vs the budget /
    device capacity verdict."""
    lines = [f"preflight ({report['backend']})"]
    lines.append(
        f"{'program':<22} {'peak MiB':>10} {'args MiB':>10} "
        f"{'temp MiB':>10} {'flops':>14}  dominant"
    )
    for prog in report["programs"]:
        dom = ", ".join(
            f"{d['arg']} ({_mib(d['bytes'])} MiB)" for d in prog["dominant"][:2]
        ) or "n/a"
        flops = prog.get("flops")
        lines.append(
            f"{prog['name']:<22} {_mib(prog.get('peak_bytes')):>10} "
            f"{_mib(prog.get('argument_bytes')):>10} "
            f"{_mib(prog.get('temp_bytes')):>10} "
            f"{flops if flops is None else format(int(flops), ','):>14}  {dom}"
        )
    peak = report["predicted_peak_bytes"]
    prog = report["predicted_peak_program"] or "n/a"
    lines.append(f"predicted peak: {_mib(peak)} MiB ({prog})")
    budget = report["hbm_budget_bytes"]
    cap = report["device_capacity_bytes"]
    if budget:
        verdict = "OK" if peak <= budget else "OVER BUDGET"
        lines.append(f"budget:         {_mib(budget)} MiB -> {verdict}")
    if cap:
        verdict = "OK" if peak <= cap else "OVER CAPACITY"
        lines.append(f"capacity:       {_mib(cap)} MiB -> {verdict}")
    if not budget and not cap:
        lines.append("budget:         none (set run.obs.hbm_budget_mb "
                     "to gate; CPU backend reports no capacity)")
    return "\n".join(lines)
