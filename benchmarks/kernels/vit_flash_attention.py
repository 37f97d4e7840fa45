"""The key-length-bounded flash forward as the vision tower runs it: one
call a layer over ONE image's patches, every head of a patch attending to
the image's LIVE patches (the bucket's padding is skipped, so it is not
counted). At head dim 128 over thousands of keys it is bound by the
products (a key block is read once per query block of 1,024 rows: 128
FLOP a byte and more against the v5e's 240)."""
# pallas_call(name=) in mxnet_tpu/pallas_kernels/flash_attention.py:
# ``%flash_fwd_bounded.N = ... custom-call(...)``
PATTERN = r"flash_fwd_bounded"
DTYPE_BYTES = 2
# rows of a query block: a key block is fetched once for each
QUERY_BLOCK = 1024


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    vc = config["vision_config"]
    return {"heads": vc["num_attention_heads"],
            "head_dim": vc["embed_dim"] // vc["num_attention_heads"],
            "sites": vc["num_hidden_layers"]}


def flops(s: dict, patches_squared: float) -> float:
    """For images whose live patch counts' squares sum to
    ``patches_squared``: QK^T and PV over every pair of live patches, each
    2 x H x N x N x D, in every layer."""
    return s["sites"] * 4.0 * s["heads"] * s["head_dim"] * patches_squared


def bytes_moved(s: dict, patches: float) -> float:
    """The least: q, k and v of the live patches read once and o written
    once, per layer (what a kernel re-reads per query block is its own
    cost and scores lower)."""
    return (s["sites"] * 4.0 * s["heads"] * s["head_dim"] * patches
            * DTYPE_BYTES)
