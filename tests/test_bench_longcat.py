"""Tier-1 twin of ``benchmarks/tests/test_bench_longcat.py``: the LongCat
cell's control flow on the CPU (tiny config and traffic, a ``harness.Run``
built by hand, the closed-loop driver to its end, the new per-layer
readers), collected here so that the driver's test command runs it."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests.test_bench_longcat import *  # noqa: E402,F401,F403
from benchmarks.tests.test_bench_longcat import traced_run  # noqa: E402,F401
