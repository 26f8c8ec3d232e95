"""CPU tests of the benchmark: `python -m pytest benchmark/tests`.

They run without a chip. Where a test drives a runner, the verify
engine in the program's registry is a stand-in that computes the same
statuses on the host (cpu_engine.py), so the rest of the run (traffic,
rings, tiles, window, reference, result line) is the real one at a
small batch.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The leader cells' verify tile resolves its mode from the platform; on
# the CPU it must be told rlc, the mode the configuration states.
os.environ.setdefault("FD_VERIFY_MODE", "rlc")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
