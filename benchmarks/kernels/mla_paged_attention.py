"""Paged latent (MLA) decode attention in the absorbed form: the query
heads of a stream, one token each, against the stream's live cached rows,
where a row ``[c' | rotated k_rope | lane padding]`` is key (all lanes)
and value (the latent's lanes) at once. Bound by the bytes of the live
rows at short contexts and by the FLOPs of 64 heads over one shared row
beyond them (115 FLOP a byte against the v5e's 240)."""
# the Pallas kernel carries its name into the HLO instruction and into the
# operation's metadata (``%mla_paged_decode.N = ... custom-call(...)``,
# ``.../mla.decode/mla_paged_decode/pallas_call``)
PATTERN = r"mla_paged_decode"
DTYPE_BYTES = 2
LANES = 128


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return {"heads": config["num_attention_heads"], "latent": rank,
            # a cached row as the arena holds it, padded to whole lanes
            "row": -(-(rank + rope) // LANES) * LANES,
            # a double layer has two attention sublayers
            "sites": 2 * config["num_layers"]}


def flops(s: dict, context_tokens: float) -> float:
    """For decode calls that attend to ``context_tokens`` live rows in
    total (summed over streams and rounds): every head's scores over a
    row's lanes and its probabilities times the latent's."""
    return (s["sites"] * 2.0 * context_tokens * s["heads"]
            * (s["row"] + s["latent"]))


def bytes_moved(s: dict, context_tokens: float, stream_rounds: float
                ) -> float:
    """Every live row read ONCE per sublayer (a kernel that fetches it as
    key and again as value scores at most half), and per stream and round
    the padded query in and the latent output out."""
    rows = context_tokens * s["row"]
    ends = stream_rounds * s["heads"] * (s["row"] + s["latent"])
    return s["sites"] * (rows + ends) * DTYPE_BYTES
