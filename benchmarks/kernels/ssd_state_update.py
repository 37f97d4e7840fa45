"""The SSD recurrence's decode step on an engine's slot array: one token a
stream, each stream's matrix scan state (``heads x d_state x head_dim``
float32) advanced and read out. Bound by the bytes of the states: the
count is each live stream's state READ ONCE AND WRITTEN ONCE a layer,
whatever implements the update (5 FLOP an 8 bytes moved against the
v5e's 240 FLOP a byte)."""
# the Pallas kernel carries its name into the HLO instruction
# (``%ssd_state_update.N = (...) custom-call(...)``) and into the
# operation's metadata (``.../ssd.scan/.../ssd_state_update/...``)
PATTERN = r"ssd_state_update"
STATE_BYTES = 4


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    heads = config["mamba_n_heads"]
    return {"heads": heads, "d_state": config["mamba_d_state"],
            "head_dim": config["mamba_d_ssm"] // heads,
            "groups": config["mamba_n_groups"],
            "sites": config["num_hidden_layers"]}


def state_values(s: dict) -> int:
    return s["heads"] * s["d_state"] * s["head_dim"]


def flops(s: dict, updates: float) -> float:
    """For ``updates`` (stream, layer) state updates: decay and outer
    product into the state (3 a value), the read-out ``S^T C`` (2)."""
    return 5.0 * updates * state_values(s)


def bytes_moved(s: dict, updates: float) -> float:
    """Each state once in and once out; beside it a stream's rows ``dt
    x``, the decay and ``y`` (heads x head_dim each) and its ``B`` and
    ``C`` (groups x d_state each)."""
    rows = 3 * s["heads"] * s["head_dim"] + 2 * s["groups"] * s["d_state"]
    return updates * (2 * state_values(s) + rows) * STATE_BYTES
