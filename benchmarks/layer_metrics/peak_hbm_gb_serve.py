"""``device.memory_stats()["peak_bytes_in_use"]`` after the window, on the
fullest chip."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
