"""Paged one-token differential attention: every query head of a stream
against the stream's live cached rows, a key row and a value row of
``n_kv_heads * head_dim`` lanes a token. Two kinds of site a decode
round: the window layers read a ring of at most ``sliding_window`` rows,
the full-attention layer and the cross-attention layers read the whole
shared cache. Bound by the bytes of the live rows (3 FLOP a byte needed
against the v5e's 240)."""
# the Pallas kernel carries its name into the HLO instruction and into the
# operation's metadata (``.../yoco.attend/.../diff_paged_decode/...``)
PATTERN = r"diff_paged_decode"
DTYPE_BYTES = 2


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    n = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    dim = config["hidden_size"] // heads
    return {"heads": heads, "head_dim": dim,
            "row": config["num_key_value_heads"] * dim,
            "window": config["sliding_window"],
            # window layers; the full layer + the cross-attention layers
            "ring_sites": n // 4, "shared_sites": 1 + (n // 2 - 2) // 2}


def site_tokens(s: dict, contexts) -> float:
    """Live rows read over all sites for decode calls of streams given as
    ``contexts``: (live tokens, the share of the stream's round that is
    counted) each; a ring holds at most the window."""
    return sum(w * (s["shared_sites"] * c
                    + s["ring_sites"] * min(c, s["window"]))
               for c, w in contexts)


def flops(s: dict, contexts) -> float:
    """Every head's scores over its key head's lanes and its
    probabilities times its value pair's lanes: 2 x heads x (d + 2d) a
    live row."""
    return 2.0 * site_tokens(s, contexts) * s["heads"] * 3 * s["head_dim"]


def bytes_moved(s: dict, contexts) -> float:
    """Every live row read ONCE as key and once as value per site, and
    per stream and site the queries in and the paired outputs out."""
    n = sum(w for _, w in contexts)
    ends = n * (s["ring_sites"] + s["shared_sites"]) * s["heads"] \
        * 3 * s["head_dim"]
    return (2 * site_tokens(s, contexts) * s["row"] + ends) * DTYPE_BYTES
