"""The server's ``prefill`` span of ONE chunk of a long prompt (host and
device): what every live stream's next token waits behind while a prompt
is prefilled."""
from benchmarks.lib import glm_dsa_scopes, stats


def read(inputs):
    return stats.median([s["dur"] / 1e3
                         for s in glm_dsa_scopes.chunk_spans(inputs)])
