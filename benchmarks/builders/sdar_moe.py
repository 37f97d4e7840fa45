"""SDAR-MoE (a Qwen3-MoE-shaped decoder, every routed expert held, that
generates by diffusion over blocks of positions) served by
``serving.Server`` + ``SdarMoeDecodeEngine`` + ``PagePool``, built from a
config file under the published key names."""
from __future__ import annotations

import math

# a checkout without the model cannot run this configuration: the import
# fails when the builder is imported, before anything is built
import mxnet_tpu.gluon.model_zoo.nlp.sdar_moe  # noqa: F401

from benchmarks.builders.falcon_h1 import start_server, warm_widest_decode

KIND = "serve"
ROW_BLOCKS = 8


def _model_kwargs(config: dict) -> dict:
    return dict(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        units=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_hidden_size=config["moe_intermediate_size"],
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
        block_length=config["block_length"],
        denoising_steps=config["denoising_steps"],
        confidence_threshold=config["confidence_threshold"],
        mask_token_id=config["mask_token_id"])


def _layer_shapes(config: dict) -> dict:
    """A layer's weights under the reference's names."""
    u, f = config["hidden_size"], config["moe_intermediate_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, e = config["head_dim"], config["num_experts"]
    return {"ln1": (u,), "ln2": (u,), "q_norm": (hd,), "k_norm": (hd,),
            "q": (hq * hd, u), "k": (hkv * hd, u), "v": (hkv * hd, u),
            "o": (u, hq * hd), "router": (e, u), "router_bias": (e,),
            "gate_up": (e, u, 2 * f), "down": (e, f, u)}


def _draw(key, name, shape, dtype, config, fans=None):
    """Norm gains 1 but the query heads' (``seeded.q_norm_gain``: with
    unit gains on both sides the attention scores have a standard
    deviation of 1 and every position of a block reads much the same
    average of the values), the selection bias 0, the router normal with the
    standard deviation that gives its logits ``seeded.router_logit_std``
    over a unit-RMS input, the embedding normal with
    ``seeded.embed_std``, every other matrix uniform with Xavier's bound
    sqrt(6 / (fan_in + fan_out)) (an expert's own two fans; ``fans``: the
    whole matrix's where ``shape`` is a block of its rows). The config's
    ``assumed.weights`` says why."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if name == "router_bias":
        return jnp.zeros(shape, dtype)
    if name == "q_norm":
        return jnp.full(shape, config["seeded"]["q_norm_gain"], dtype)
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    if name == "router":
        std = config["seeded"]["router_logit_std"] / math.sqrt(shape[1])
        return (jax.random.normal(key, shape, f32) * std).astype(dtype)
    if name == "embed":
        return (jax.random.normal(key, shape, f32)
                * config["seeded"]["embed_std"]).astype(dtype)
    bound = math.sqrt(6.0 / sum(fans or shape[-2:]))
    if name == "down":
        bound *= config["seeded"].get("expert_out_gain", 1.0)
    return jax.random.uniform(key, shape, f32, -bound, bound).astype(dtype)


def make_layer(config: dict, key):
    """One layer's weights under the reference's names; jitted once,
    called per layer."""
    import jax
    import jax.numpy as jnp

    shapes = _layer_shapes(config)
    dtype = jnp.dtype(config["dtype"])
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, name, shape, dtype, config)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def make_rows(config: dict, name: str, keys):
    """The embedding (``embed``) or the head (``head``), one block of
    rows a key: drawn whole, their float32 random bits are 1.2 GB."""
    import jax
    import jax.numpy as jnp

    vocab, u = config["vocab_size"], config["hidden_size"]
    dtype = jnp.dtype(config["dtype"])
    return jax.lax.map(
        lambda k: _draw(k, name, (vocab // keys.shape[0], u), dtype, config,
                        fans=(vocab, u)), keys).reshape(vocab, u)


def _layer_params(blk) -> dict:
    a, m = blk.attention, blk.moe
    return {"ln1": blk.norm1.weight, "ln2": blk.norm2.weight,
            "q_norm": a.q_norm.weight, "k_norm": a.k_norm.weight,
            "q": a.q_proj.weight, "k": a.k_proj.weight,
            "v": a.v_proj.weight, "o": a.out_proj.weight,
            "router": m.router_weight, "router_bias": m.router_bias,
            "gate_up": m.gate_up_weight, "down": m.down_weight}


def build_net(config: dict, seed: int, ctx=None):
    """The net with seeded weights on the device, made there one layer at
    a time in the served dtype (hardware RNG) and put with
    ``Parameter.set_data``; the cheapest ``initialize`` first, as in the
    Falcon-H1 builder (the large matrices are deferred, so it allocates
    none of them)."""
    import functools

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import SdarMoeModel

    ctx = mx.tpu(0) if ctx is None else ctx
    net = SdarMoeModel(**_model_kwargs(config))
    net.collect_params().setattr("grad_req", "null")
    net.cast(config["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    device = ctx.jax_device()

    def put(params, values):
        for name, p in params.items():
            p.set_data(mx.nd.NDArray(data=values[name], ctx=ctx))

    blocks = ROW_BLOCKS if config["vocab_size"] % ROW_BLOCKS == 0 else 1
    keys = jax.random.split(jax.random.key(seed, impl="rbg"),
                            len(net.blocks) + 2 * blocks)
    ones = jnp.ones((config["hidden_size"],), jnp.dtype(config["dtype"]))
    with jax.default_device(device):
        layer = jax.jit(functools.partial(make_layer, config))
        for blk, k in zip(net.blocks, keys[2 * blocks:]):
            put(_layer_params(blk), layer(k))
        ends = {"embed": net.embed.weight, "head": net.lm_head.weight}
        for i, (name, p) in enumerate(ends.items()):
            put({name: p}, {name: jax.jit(functools.partial(
                make_rows, config, name))(keys[i * blocks:(i + 1) * blocks])})
        put({"norm": net.norm.weight}, {"norm": ones})
    jax.block_until_ready(net.lm_head.weight.data().data)
    return net, ctx


def build(config: dict, traffic: dict, seed: int, devices) -> dict:
    net, ctx = build_net(config, seed)
    srv = start_server(net, ctx, traffic)
    warm_widest_decode(srv, traffic, config["vocab_size"], seed)
    return {"net": net, "server": srv, "ctx": ctx}


def export_weights(built: dict) -> dict:
    """The net's weights under the reference's names, as device arrays in
    the dtype they are served in."""
    net = built["net"]

    def w(p):
        return p.data().data

    return {"embed": w(net.embed.weight), "norm": w(net.norm.weight),
            "head": w(net.lm_head.weight),
            "layers": [{k: w(p) for k, p in _layer_params(blk).items()}
                       for blk in net.blocks]}


def flops_per_token(config: dict, traffic: dict) -> int:
    """Forward FLOPs of ONE position of one forward at context 1: the
    attention's matrices, the router, ``num_experts_per_tok`` experts and
    the untied head. A token of an answer costs ``forwards per token``
    of these (1.25 under the static schedule)."""
    s = _layer_shapes(config)
    attn = sum(2 * s[k][0] * s[k][1] for k in ("q", "k", "v", "o", "router"))
    expert = 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return int(config["num_hidden_layers"]
               * (attn + config["num_experts_per_tok"] * expert)
               + 2 * config["hidden_size"] * config["vocab_size"])
