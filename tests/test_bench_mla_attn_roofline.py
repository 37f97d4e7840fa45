"""Tier-1 twin of ``benchmarks/tests/test_bench_mla_attn_roofline.py``
(the ``mla_attn_roofline`` reader and its kernel's counts), collected
here so that the driver's test command runs it."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests.test_bench_mla_attn_roofline import *  # noqa: E402,F401,F403
