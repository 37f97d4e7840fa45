"""Closed loop: ``clients`` callers that each send their next request when
the last was answered. Emits ``served_tokens_s`` and
``tpot_p50_ms``; the p95 tails of TTFT and TPOT are per-layer metrics.

Traffic parameters: ``clients``, ``max_rps_per_client`` (sizes the seeded
request lists; a client that runs out fails the run), ``prompt_len``,
``output_len``, ``server``, ``check_requests``, ``trace_start_frac``,
``trace_len_s``.
"""
from benchmarks.lib import harness, serve_loop


def run(run: harness.Run) -> harness.Result:
    return serve_loop.run_serving(run, "closed")
