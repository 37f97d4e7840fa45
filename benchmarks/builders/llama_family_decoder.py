"""A Llama-family decoder (RMSNorm, rotary GQA attention, SwiGLU, untied
head) served by ``serving.Server`` + ``LlamaDecodeEngine`` + ``PagePool``,
built from a config file. The family's code is ``LlamaModel``; the sizes
are the config's own (Mistral-7B-v0.3 in the benchmark)."""
from __future__ import annotations

import math

KIND = "serve"


def _shapes(config: dict) -> dict:
    """Every parameter's shape, by the path of attributes that reaches it
    (``layer`` entries repeat per layer)."""
    u, f = config["hidden_size"], config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config.get("head_dim") or u // h
    v = config["vocab_size"]
    return {
        "embed": (v, u), "lm_head": (v, u), "norm": (u,),
        "layer": {"attn_norm": (u,), "q": (h * d, u), "kv": (2 * kv * d, u),
                  "out": (u, h * d), "mlp_norm": (u,),
                  "gate_up": (2 * f, u), "down": (u, f)}}


def make_weights(config: dict, seed: int, device):
    """All weights in ONE jitted call on the device, in the served dtype:
    matrices uniform with Xavier's bound sqrt(6 / (fan_in + fan_out)) so
    activations stay O(1) through the stack, norm gains at 1."""
    import jax
    import jax.numpy as jnp

    shapes = _shapes(config)
    dtype = jnp.dtype(config["dtype"])
    n_layers = config["num_hidden_layers"]

    def draw(key, shape):
        if len(shape) == 1:
            return jnp.ones(shape, dtype)
        bound = math.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    def gen(key):
        keys = jax.random.split(key, 3 + n_layers)
        out = {name: draw(keys[i], shapes[name])
               for i, name in enumerate(("embed", "lm_head", "norm"))}
        out["layers"] = []
        for li in range(n_layers):
            lk = jax.random.split(keys[3 + li], len(shapes["layer"]))
            out["layers"].append(
                {name: draw(lk[j], shape) for j, (name, shape)
                 in enumerate(sorted(shapes["layer"].items()))})
        return out

    # the hardware generator: 3.8 B draws of threefry would be seconds
    key = jax.random.key(seed, impl="rbg")
    with jax.default_device(device):
        return jax.jit(gen)(key)


def _params_by_path(net) -> dict:
    out = {"embed": net.embed.weight, "lm_head": net.lm_head.weight,
           "norm": net.norm.weight, "layers": []}
    for blk in net.blocks:
        out["layers"].append({
            "attn_norm": blk.attn_norm.weight,
            "q": blk.attention.q_proj.weight,
            "kv": blk.attention.kv_proj.weight,
            "out": blk.attention.out_proj.weight,
            "mlp_norm": blk.mlp_norm.weight,
            "gate_up": blk.mlp.gate_up.weight, "down": blk.mlp.down.weight})
    return out


def build_net(config: dict, seed: int):
    """The net with seeded weights on the device. Public API only: the
    cheapest ``initialize`` (zeros), then ``Parameter.set_data`` with
    arrays made on the device (a deferred parameter takes its shape from
    the array). The float32 host copy ``initialize`` makes of every
    parameter is the program's; see PERF.md, Open questions."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import LlamaModel

    ctx = mx.tpu(0)
    net = LlamaModel(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        units=config["hidden_size"],
        hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
        tie_weights=config["tie_word_embeddings"])
    # a server holds no gradients: without this every weight gets a
    # gradient buffer of its own size on the device
    net.collect_params().setattr("grad_req", "null")
    net.cast(config["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    weights = make_weights(config, seed, ctx.jax_device())

    def put(param, value):
        param.set_data(mx.nd.NDArray(data=value, ctx=ctx))

    params = _params_by_path(net)
    for name in ("embed", "lm_head", "norm"):
        put(params[name], weights[name])
    for lp, lw in zip(params["layers"], weights["layers"]):
        for name, param in lp.items():
            put(param, lw[name])
    jax.block_until_ready(weights)
    return net, ctx


def build(config: dict, traffic: dict, seed: int, devices) -> dict:
    from mxnet_tpu import serving

    net, ctx = build_net(config, seed)
    s = traffic["server"]
    srv = serving.Server(
        net, batch_buckets=tuple(s["batch_buckets"]), dtype="int32", ctx=ctx,
        slo_ms=60000.0, decode_pages=s["decode_pages"],
        page_size=s["page_size"], len_buckets=tuple(s["len_buckets"]),
        max_generate_tokens=s["max_generate_tokens"],
        defrag_threshold=s["defrag_threshold"], name="bench")
    srv.start()
    return {"net": net, "server": srv, "ctx": ctx}


def export_weights(built: dict) -> dict:
    """The net's weights under the reference's names, as device arrays in
    the dtype they are served in."""
    params = _params_by_path(built["net"])

    def w(p):
        return p.data().data

    return {"embed": w(params["embed"]), "lm_head": w(params["lm_head"]),
            "norm": w(params["norm"]),
            "layers": [{k: w(p) for k, p in lp.items()}
                       for lp in params["layers"]]}


def flops_per_token(config: dict, traffic: dict) -> int:
    from benchmarks.lib import flops

    return flops.decoder_forward_flops_per_token(config, 1)
