"""When the import of ``server/round_driver.py`` began.

The driver module imports this before anything that costs time, and
nothing else imports it: ``STARTED`` is read once, as the interpreter
first executes this file. The driver's other imports (flax, optax, the
models' Pallas kernels) take seconds of every process's set-up, and the
driver puts them into its tracer's start-up record as ``setup.import``.
The checkpoint library is not among them: orbax loads when a run first
builds a checkpoint store, inside ``setup.checkpoint_store``.
"""

import time

STARTED = time.perf_counter()
