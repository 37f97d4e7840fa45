"""The delta rule's decode step on an engine's slot array (Kimi Delta
Attention): one token a stream, each stream's matrix state (``heads x d_k
x d_v`` float32) decayed a key channel at a time, corrected by a rank-one
update of itself and read out. Bound by the bytes of the states: the count
is each live stream's state READ ONCE AND WRITTEN ONCE a layer, whatever
implements the update (8 FLOP an 8 bytes moved against the v5e's 240 FLOP
a byte)."""
# the Pallas kernel carries its name into the HLO instruction
# (``%kda_state_update.N = (...) custom-call(...)``) and into the
# operation's metadata (``.../kda.update/.../kda_state_update/...``)
PATTERN = r"kda_state_update"
STATE_BYTES = 4


def shapes(config: dict, traffic: dict, chips: int) -> dict:
    return {"heads": config["num_attention_heads"],
            "d_k": config["head_dim"], "d_v": config["head_dim"],
            "sites": list(config["layer_kinds"]).count("kda")}


def state_values(s: dict) -> int:
    return s["heads"] * s["d_k"] * s["d_v"]


def flops(s: dict, updates: float) -> float:
    """For ``updates`` (stream, layer) state updates: the decay (1 a
    value), ``k^T S'`` (2), the outer product into the state (2), the
    read-out ``S^T q`` (2), the correction's row (1 a value of v, left
    out)."""
    return 7.0 * updates * state_values(s)


def bytes_moved(s: dict, updates: float) -> float:
    """Each state once in and once out; beside it a stream's columns
    ``alpha``, ``k``, ``q`` (heads x d_k each) and its rows ``beta v``,
    ``beta`` and ``o`` (heads x d_v each), as dense arrays."""
    rows = 3 * s["heads"] * s["d_k"] + 3 * s["heads"] * s["d_v"]
    return updates * (2 * state_values(s) + rows) * STATE_BYTES
