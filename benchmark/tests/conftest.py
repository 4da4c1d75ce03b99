"""The benchmark's own tests run on the CPU: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests -q`` from the root of the repo. They call the harness's
functions on tiny fixture cells (``fixtures/``); the command line itself has
no CPU mode."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
