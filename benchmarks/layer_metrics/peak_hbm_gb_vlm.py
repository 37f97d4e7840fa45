"""``peak_hbm_gb_serve`` for the dots.vlm1 cell: the tower whole (2.6 GB)
beside the language layers' 9.1 GB, the latent arena, the embedding
buffers of the requests between encode and prefill, a chunk's expanded
keys and values and a 12,288-patch encode's activations on one chip."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
