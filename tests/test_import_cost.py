"""What importing the driver costs (PERF.md PR 46): the checkpoint
library loads with the first checkpoint store a process builds, not with
``server/round_driver.py``. ``sys.modules`` is the process's, so each
test asks a fresh interpreter."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what `import orbax.checkpoint` brings, by the names that cost the most
# (google.cloud.logging is 12.0 s of its 12.9 on the chip's host). With
# its dot: a .pth file of the installation puts the empty namespace
# package `google.cloud` into every interpreter at start-up
_HEAVY = ("orbax", "google.cloud.", "google.api_core")

_PRELUDE = """
import json, sys
sys.path.insert(0, {root!r})
HEAVY = {heavy!r}
def heavy():
    return sorted(m for m in sys.modules if m.startswith(HEAVY))
"""

_PROBES = {
    "driver_import": """
import colearn_federated_learning_tpu.server.round_driver
print(json.dumps({"heavy": heavy()}))
""",
    "store": """
from colearn_federated_learning_tpu.config import get_named_config
from colearn_federated_learning_tpu.server.round_driver import Experiment

def probe(out_dir):
    cfg = get_named_config("mnist_fedavg_2")
    cfg.data.synthetic_train_size = 128
    cfg.data.synthetic_test_size = 64
    cfg.run.out_dir = out_dir
    exp = Experiment(cfg, echo=False)
    store = exp._ckpt_store()
    built = store is not None
    if built:
        store.close()
    spans = [e["name"] for e in exp.tracer.startup_record()]
    return {"built": built, "heavy": heavy(),
            "orbax": "orbax.checkpoint" in sys.modules,
            "store_spans": spans.count("setup.checkpoint_store"),
            "import_spans": spans.count("setup.import")}

# the run without an out_dir first: what is imported stays imported
print(json.dumps({"without": probe(""), "with": probe(sys.argv[1])}))
""",
    "export": """
import os
import numpy as np
from colearn_federated_learning_tpu.utils.checkpoint import (
    export_params, load_params)

tree = {"dense": {"kernel": np.arange(6, dtype=np.float32).reshape(2, 3),
                  "bias": np.ones(3, np.float16)}}
path = export_params(tree, os.path.join(sys.argv[1], "deep", "params.msgpack"))
raw = load_params(path)
like = load_params(path, template=tree)
same = all(
    np.array_equal(tree["dense"][k], got["dense"][k])
    and tree["dense"][k].dtype == got["dense"][k].dtype
    for got in (raw, like) for k in ("kernel", "bias"))
print(json.dumps({"same": same, "heavy": heavy()}))
""",
}


def _probe(name, *argv, timeout):
    """Runs one probe in a fresh interpreter on the CPU and returns the
    JSON of its last line; ``timeout`` seconds is the test's own limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = _PRELUDE.format(root=_ROOT, heavy=_HEAVY) + _PROBES[name]
    done = subprocess.run(
        [sys.executable, "-c", code, *argv], env=env, cwd=_ROOT,
        capture_output=True, text=True, timeout=timeout)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1])


def test_driver_import_loads_no_checkpoint_library():
    assert _probe("driver_import", timeout=120)["heavy"] == []


def test_checkpoint_library_loads_with_the_first_store(tmp_path):
    got = _probe("store", str(tmp_path), timeout=240)
    without, with_dir = got["without"], got["with"]
    # no out_dir: no store, nothing imported, no span
    assert without == {"built": False, "heavy": [], "orbax": False,
                       "store_spans": 0, "import_spans": 1}
    # an out_dir: the store is built inside the span, and brings orbax
    assert with_dir["built"] and with_dir["orbax"]
    assert with_dir["store_spans"] == 1
    # setup.import belongs to the process's first Experiment alone
    assert with_dir["import_spans"] == 0


def test_export_and_load_params_need_no_orbax(tmp_path):
    got = _probe("export", str(tmp_path), timeout=120)
    assert got == {"same": True, "heavy": []}
