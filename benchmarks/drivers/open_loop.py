"""Open loop: Poisson arrivals from ``--seed`` at the fixed ``rate_rps`` of
the traffic file; each request is timed from when it was DUE. Emits
``served_tokens_s`` and ``tpot_p50_ms``; the p95 tails are per-layer metrics.

Traffic parameters: ``rate_rps``, ``prompt_len``, ``output_len`` (length
specs of lib/arrivals.py), ``server`` (the deployment: bucket grid, pages,
warm-up groups), ``check_requests``, ``trace_start_frac``, ``trace_len_s``.
"""
from benchmarks.lib import harness, serve_loop


def run(run: harness.Run) -> harness.Result:
    return serve_loop.run_serving(run, "open")
