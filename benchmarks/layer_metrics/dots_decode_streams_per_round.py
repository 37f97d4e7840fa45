"""Live streams of a decode round of the traced slice (the engine's
``moe.picks:`` annotations over ``num_experts_per_tok`` and the
expert-layer executions): how many of the 8 callers decode at once while
the others wait for an encode or a prefill chunk."""
from benchmarks.layer_metrics.glm_decode_streams_per_round import read  # noqa: F401
