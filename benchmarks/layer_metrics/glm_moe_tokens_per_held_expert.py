"""``moe_tokens_per_held_expert`` for the GLM-5 cell (that entry's list is
pinned to LongCat's cell by its own test): picks that went to held
experts per expert-layer execution of the slice's decode forwards, over
the experts held. 0.25 at 8 streams where the deployment gives 4."""
from benchmarks.layer_metrics.moe_tokens_per_held_expert import read  # noqa: F401
