"""Paged decode attention: one query token per stream against the
stream's live cache. Bound by the bytes of the live keys and values."""
# the kernel has no name of its own in the trace today (``%_unknown_.N``):
# it is the custom call whose first operands are the int32 page table
# (streams, pages) and the int32 lengths (streams,)
PATTERN = r"custom-call\(s32\[\d+,\d+\] [^,]*, s32\[\d+\] "
DTYPE_BYTES = 2


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    h = config["num_attention_heads"]
    return {"heads": h, "kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim") or config["hidden_size"] // h,
            "sites": config["num_hidden_layers"]}


def flops(s: dict, context_tokens: float) -> float:
    """For decode calls that attend to ``context_tokens`` live tokens in
    total (summed over streams and rounds): QK^T and PV."""
    return s["sites"] * 2 * 2.0 * context_tokens * s["heads"] * s["head_dim"]


def bytes_moved(s: dict, context_tokens: float) -> float:
    """The live keys and values, read once per layer; q and o are
    left out (one token per stream)."""
    return (s["sites"] * 2.0 * context_tokens * s["kv_heads"]
            * s["head_dim"] * DTYPE_BYTES)
