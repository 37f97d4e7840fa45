"""Chunks a long prompt was prefilled in, mean over the requests whose
first chunk the run saw (the ``chunks`` tag of the ``prefill`` spans)."""
from benchmarks.lib import glm_dsa_scopes


def read(inputs):
    firsts = [s["tags"]["chunks"] for s in glm_dsa_scopes.chunk_spans(inputs)
              if s["tags"].get("chunk") == 0]
    return sum(firsts) / len(firsts) if firsts else None
