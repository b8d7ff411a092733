"""Tests of the benchmark's own yardstick; run with ``pytest
benchmark/tests`` from the root of the repository (they are not part
of ``pytest tests/``).  They run on the CPU at toy sizes: nothing here
is a measurement."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
