"""Sum of ``mxnet_pallas_dispatch_total`` over kernels at the end of the
run: how many call sites the op routing gave to a Pallas kernel."""
from benchmarks.lib.readers import pallas_sites as read  # noqa: F401
