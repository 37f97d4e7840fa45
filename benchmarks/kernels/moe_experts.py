"""The held experts' grouped SwiGLU (``moe.experts`` in a device trace):
gate/up and down projections of the (token, held expert) pairs routed
here. At decode widths it is bound by the bytes of the experts that got a
token; in a large prefill by the FLOPs of the pairs."""
DTYPE_BYTES = 2


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    return {"hidden": config["hidden_size"],
            "expert_hidden": config["expert_ffn_hidden_size"],
            "held": config["n_routed_experts"]}


def flops(s: dict, pairs: float) -> float:
    """``pairs`` (token, held expert) pairs through gate, up and down:
    3 matmuls of hidden x expert_hidden each, 2 FLOPs a multiply-add."""
    return 6.0 * s["hidden"] * s["expert_hidden"] * pairs


def bytes_moved(s: dict, pairs: float, experts_touched: float) -> float:
    """The least any implementation reads and writes: the weights of the
    held experts that got a token, once each (one that reads all held
    experts scores lower), and per pair the token's row in, the
    expert_hidden-wide activation out and in again, the row out."""
    weights = 3.0 * s["hidden"] * s["expert_hidden"] * experts_touched
    acts = (2.0 * s["hidden"] + 2.0 * s["expert_hidden"]) * pairs
    return (weights + acts) * DTYPE_BYTES
