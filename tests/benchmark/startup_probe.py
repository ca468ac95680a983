"""``benchmark/run.py`` with the start-up record made visible.

    python tests/benchmark/startup_probe.py --workload <cell> --seed <n> ...

takes ``run.py``'s arguments and runs its ``main`` unchanged, in this
process. After the window, behind ``check_reference`` (which calls
``init_state`` a second time), it prints two more ``[bench]`` lines:

- ``startup``: the value of every per-layer metric that
  ``readers/startup_span_s.py`` reads, through the metric's own data
  file, as a traced run would report it, and the names in the program's
  start-up record, before the window and after it. ``--dry`` runs print
  it too: the rehearsal of the reader off the chip.
- ``round_programs``: sha256 of each compiled round program's text, to
  lay a parent's program against a change's, without what a change to
  host code moves: the checkout's path, and the text's index of source
  locations (``FileNames`` ... ``StackFrames``: which line of which file
  an instruction was traced from, the driver's own lines among them),
  and the same locations inside each Pallas kernel's serialized module
  (a ``tpu_custom_call``'s ``body``, MLIR bytecode in base64: it is
  parsed and printed again without them). With
  ``STARTUP_PROBE_OUT=<dir>`` the text goes there, gzipped.
"""

import base64
import glob
import gzip
import hashlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
READER = "startup_span_s"


def load_run():
    spec = importlib.util.spec_from_file_location(
        "run", os.path.join(BENCH_DIR, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)  # puts ROOT and BENCH_DIR on sys.path
    return module


def startup_metrics(setup):
    """{metric: value or None} for every data file that names the
    reader, and the record's names on both sides of the window."""
    from harness import catalog

    module = catalog.load_module("readers", READER, ("read",), BENCH_DIR)
    ctx = {"setup": setup}
    out = {"metrics": {}}
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == READER:
            out["metrics"][spec["name"]] = module.read(ctx, **spec["args"])
    before = module.startup_record(ctx) or []
    out["before_window"] = [e["name"] for e in before]
    try:
        from colearn_federated_learning_tpu.obs import spans
        everything = [e for t in spans.live_tracers()
                      for e in t.startup_record()]
    except (ImportError, AttributeError):  # a program without the record
        everything = []
    out["after_window"] = [e["name"] for e in everything[len(before):]]
    out["record"] = everything
    return out


SOURCE_INDEX = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def without_source_index(text):
    """The compiled text minus its tables of source locations (each runs
    from its heading to the next empty line)."""
    kept, skipping = [], False
    for line in text.split("\n"):
        if line in SOURCE_INDEX:
            skipping = True
        elif skipping and not line:
            skipping = False
            continue
        if not skipping:
            kept.append(line)
    return "\n".join(kept)


def without_kernel_locations(text):
    """Each ``tpu_custom_call``'s serialized module as its assembly
    without debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    lines = text.split("\n")
    marker = '"custom_call_config":{"body":"'
    for i, line in enumerate(lines):
        at = line.find(marker)
        if 'custom_call_target="tpu_custom_call"' not in line or at < 0:
            continue
        start = at + len(marker)
        end = line.index('"', start)
        with mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(line[start:end]))
            asm = module.operation.get_asm(enable_debug_info=False)
        lines[i] = (line[:start] + "sha256:"
                    + hashlib.sha256(asm.encode()).hexdigest() + line[end:])
    return "\n".join(lines)


def round_program_hashes(exp):
    out = {}
    keep = os.environ.get("STARTUP_PROBE_OUT", "")
    if exp._exec_reg is None:
        return out
    for entry in exp._exec_reg._cache.values():
        if not entry["name"].startswith("round."):
            continue
        text = without_kernel_locations(without_source_index(
            entry["compiled"].as_text().replace(ROOT, "")))
        key = f"{entry['name']}.{entry['fingerprint']}"
        out[key] = hashlib.sha256(text.encode()).hexdigest()
        if keep:
            os.makedirs(keep, exist_ok=True)
            with gzip.open(os.path.join(keep, key + ".hlo.txt.gz"), "wt") as f:
                f.write(text)
    return out


def main(argv=None) -> int:
    run = load_run()
    said = {}
    say, check_reference = run.say, run.check_reference

    def keep_say(tag, payload):
        said[tag] = payload
        say(tag, payload)

    def check_then_probe(cell, config, the_run, *args, **kwargs):
        out = check_reference(cell, config, the_run, *args, **kwargs)
        say("startup", startup_metrics(said["setup"]))
        say("round_programs", round_program_hashes(the_run.exp))
        return out

    run.say, run.check_reference = keep_say, check_then_probe
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
