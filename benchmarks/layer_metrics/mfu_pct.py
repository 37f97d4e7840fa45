"""Model FLOP/s utilization: trained tokens per second x operations per
token from shapes (forward + backward, attention included, no
recomputation) over chips x peak."""


def read(inputs):
    if not inputs.get("steps") or inputs.get("peaks") is None:
        return None
    tokens_s = inputs["steps"] * inputs["tokens_per_step"] / inputs["window_s"]
    peak = inputs["cell"]["chips"] * inputs["peaks"]["bf16_flops"]
    return 100.0 * tokens_s * inputs["flops_per_token"] / peak
