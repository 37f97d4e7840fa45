"""``moe_tokens_per_held_expert`` for the dots.vlm1 cell (that entry's
list is pinned to LongCat's cell by its own test): picks that went to
held experts per expert-layer execution of the slice's decode forwards,
over the experts held. An even router gives streams x 8 / 256 a round
(0.19 at 6 streams); the deployment (16 chips a layer, a per-chip batch of
8: 128 streams a layer) gives a held expert 4."""
from benchmarks.layer_metrics.moe_tokens_per_held_expert import read  # noqa: F401
