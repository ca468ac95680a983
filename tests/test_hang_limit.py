"""The suite's per-test limit (tests/conftest.py): a test that never
returns to Python is ended by its own process, with a traceback."""

import os
import subprocess
import sys

_HUNG = """
import threading
from conftest import hang_limit

def wait_for_ever():
    threading.Event().wait()

with hang_limit(1):
    wait_for_ever()
"""


def test_a_hung_test_is_ended_by_its_own_limit_with_a_traceback():
    child = subprocess.run(
        [sys.executable, "-c", _HUNG], cwd=os.path.dirname(__file__),
        capture_output=True, text=True, timeout=120,
    )
    assert child.returncode == 1, child.stderr
    assert "Timeout (0:00:01)!" in child.stderr
    assert "wait_for_ever" in child.stderr
