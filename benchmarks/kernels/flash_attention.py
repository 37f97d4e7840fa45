"""Flash attention (forward + backward) as one training step runs it:
what the algorithm needs, from shapes. The kernel's recomputation of
QK^T in the backward pass is not needed work and is not counted."""
# the custom calls made inside the jitted ``_contrib_sdp_attention`` op,
# forward (``jvp_jit__contrib_sdp_attention``) and backward (``transpose_``)
PATTERN = r"^%\w*sdp_attention|^%\w*flash"
DTYPE_BYTES = 2


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    return {"batch": traffic["batch"] // chips, "seq": traffic["seq"],
            "heads": config["num_attention_heads"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "sites": config["num_hidden_layers"]}


def flops(s: dict) -> float:
    """Per step: forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK),
    each 2 x B x H x S x S x D."""
    one = 2.0 * s["batch"] * s["heads"] * s["seq"] * s["seq"] * s["head_dim"]
    return s["sites"] * 6 * one


def bytes_moved(s: dict) -> float:
    """Per step: forward reads q, k, v and writes o; backward reads q, k,
    v, o, do and writes dq, dk, dv. Statistics rows are left out."""
    one = s["batch"] * s["heads"] * s["seq"] * s["head_dim"] * DTYPE_BYTES
    return s["sites"] * 12.0 * one
