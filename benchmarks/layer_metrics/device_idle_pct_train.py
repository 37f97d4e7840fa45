"""1 - union of device-operation intervals / traced slice, on the
lowest-numbered chip."""
from benchmarks.lib.readers import device_idle_pct as read  # noqa: F401
