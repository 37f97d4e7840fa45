"""XLA backend compiles (jax.monitoring) plus the compilation service's
``mxnet_jit_cache_total{result=miss}`` between window start and end."""
from benchmarks.lib.readers import compiles_in_window as read  # noqa: F401
