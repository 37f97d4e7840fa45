"""Tier-1 twin of ``benchmarks/tests/test_bench_trace_reduce_sweep.py``
(PR 43's sorted sweep over a traced slice's idle gaps)."""
from benchmarks.tests.test_bench_trace_reduce_sweep import *  # noqa: F401,F403
