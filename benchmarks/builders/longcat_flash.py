"""One chip's share of LongCat-Flash (latent attention, shortcut-connected
routed experts of which ``n_routed_experts`` are held here, zero-compute
experts) served by ``serving.Server`` + ``LongcatFlashDecodeEngine`` +
``PagePool``, built from a config file under the published key names."""
from __future__ import annotations

import importlib.util
import math

KIND = "serve"

# a checkout without the model cannot run this configuration: say so when
# the builder is imported, before anything is built
if importlib.util.find_spec(
        "mxnet_tpu.gluon.model_zoo.nlp.longcat_flash") is None:
    raise ImportError("this checkout's mxnet_tpu has no LongCat-Flash "
                      "(gluon/model_zoo/nlp/longcat_flash.py)")


def _model_kwargs(config: dict) -> dict:
    return dict(
        vocab_size=config["vocab_size"], num_layers=config["num_layers"],
        units=config["hidden_size"],
        ffn_hidden_size=config["ffn_hidden_size"],
        expert_ffn_hidden_size=config["expert_ffn_hidden_size"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["router_outputs"] - config["zero_expert_num"],
        zero_expert_num=config["zero_expert_num"],
        moe_topk=config["moe_topk"],
        routed_scaling_factor=config["routed_scaling_factor"],
        first_held=config.get("first_held_expert", 0),
        held_experts=config["n_routed_experts"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"])


def _sub_shapes(config: dict) -> dict:
    u, f, h = (config["hidden_size"], config["ffn_hidden_size"],
               config["num_attention_heads"])
    qr, kr = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    return {"in_norm": (u,), "qa": (qr, u), "qnorm": (qr,),
            "qb": (h * (nope + rope), qr), "kva": (kr + rope, u),
            "kvnorm": (kr,), "kvb": (h * (nope + v), kr), "out": (u, h * v),
            "post_norm": (u,), "ffn_gate_up": (2 * f, u), "ffn_down": (u, f)}


def _moe_shapes(config: dict) -> dict:
    u, e = config["hidden_size"], config["expert_ffn_hidden_size"]
    held, outs = config["n_routed_experts"], config["router_outputs"]
    return {"router": (outs, u), "router_bias": (outs,),
            "gate_up": (held, u, 2 * e), "down": (held, e, u)}


def _draw(key, name, shape, dtype, config):
    """Matrices (and each expert of a stack) uniform with Xavier's bound
    sqrt(6 / (fan_in + fan_out)), norm gains 1; the ROUTER normal with std
    ``init_router_logit_std`` / sqrt(hidden), which is then the standard
    deviation of its logits (with Xavier's bound the 768 scores are
    nearly equal); the selection bias uniform in
    +-``init_router_bias_range``. Both are the config file's, with the
    readings that chose them."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if name == "router":
        std = config["init_router_logit_std"] / math.sqrt(shape[1])
        return (jax.random.normal(key, shape, f32) * std).astype(dtype)
    if name == "router_bias":
        r = config["init_router_bias_range"]
        return jax.random.uniform(key, shape, f32, -r, r).astype(dtype)
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    bound = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, f32, -bound, bound).astype(dtype)


def _draw_all(key, shapes: dict, config: dict) -> dict:
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["dtype"])
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, name, shape, dtype, config)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def make_layer(config: dict, key):
    """One double layer's weights; jitted once, called per layer."""
    import jax

    k0, k1, k2 = jax.random.split(key, 3)
    return {"sub": (_draw_all(k0, _sub_shapes(config), config),
                    _draw_all(k1, _sub_shapes(config), config)),
            "moe": _draw_all(k2, _moe_shapes(config), config)}


def make_ends(config: dict, key):
    u, v = config["hidden_size"], config["vocab_size"]
    return _draw_all(key, {"embed": (v, u), "lm_head": (v, u),
                           "norm": (u,)}, config)


def _layer_params(blk) -> dict:
    def sub(i):
        a, f = blk.attns[i], blk.ffns[i]
        return {"in_norm": blk.in_norms[i].weight, "qa": a.q_a.weight,
                "qnorm": a.q_norm.weight, "qb": a.q_b.weight,
                "kva": a.kv_a.weight, "kvnorm": a.kv_norm.weight,
                "kvb": a.kvb_weight, "out": a.out_proj.weight,
                "post_norm": blk.post_norms[i].weight,
                "ffn_gate_up": f.gate_up.weight, "ffn_down": f.down.weight}

    m = blk.moe
    return {"sub": (sub(0), sub(1)),
            "moe": {"router": m.router_weight, "router_bias": m.router_bias,
                    "gate_up": m.gate_up_weight, "down": m.down_weight}}


def build_net(config: dict, seed: int):
    """The net with seeded weights on the device, made there one double
    layer at a time in the served dtype (hardware RNG) and put with
    ``Parameter.set_data``; the cheapest ``initialize`` first, as in the
    Llama-family builder. Layer by layer, so that never more than one
    layer's weights exist twice."""
    import functools

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import LongcatFlashModel

    ctx = mx.tpu(0)
    net = LongcatFlashModel(**_model_kwargs(config))
    net.collect_params().setattr("grad_req", "null")
    net.cast(config["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    device = ctx.jax_device()

    def put(params, values):
        # a Parameter is a leaf: the two trees have one shape
        jax.tree_util.tree_map(
            lambda p, v: p.set_data(mx.nd.NDArray(data=v, ctx=ctx)),
            params, values)

    keys = jax.random.split(jax.random.key(seed, impl="rbg"),
                            len(net.blocks) + 1)
    with jax.default_device(device):
        layer = jax.jit(functools.partial(make_layer, config))
        for blk, k in zip(net.blocks, keys[1:]):
            put(_layer_params(blk), layer(k))
        put({"embed": net.embed.weight, "lm_head": net.lm_head.weight,
             "norm": net.norm.weight},
            jax.jit(functools.partial(make_ends, config))(keys[0]))
    jax.block_until_ready(net.lm_head.weight.data().data)
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
        defrag_threshold=s["defrag_threshold"],
        max_prefill_tokens=s.get("max_prefill_tokens"), name="bench")
    srv.start()
    return {"net": net, "server": srv, "ctx": ctx}


def export_weights(built: dict) -> dict:
    """The net's weights under the reference's names, as device arrays in
    the dtype they are served in."""
    import jax

    net = built["net"]

    def w(p):
        return p.data().data

    return {"embed": w(net.embed.weight), "lm_head": w(net.lm_head.weight),
            "norm": w(net.norm.weight),
            "layers": [jax.tree_util.tree_map(w, _layer_params(blk))
                       for blk in net.blocks]}


def flops_per_token(config: dict, traffic: dict) -> int:
    """Forward FLOPs of ONE token on this chip at context 1: attention
    and dense parts whole, the held experts at their mean load
    (top_k x held / router_outputs picks a token), the head's slice."""
    u, e = config["hidden_size"], config["expert_ffn_hidden_size"]
    sub = sum(2 * s[0] * s[1] for s in _sub_shapes(config).values()
              if len(s) == 2)
    picks = (config["moe_topk"] * config["n_routed_experts"]
             / config["router_outputs"])
    layer = 2 * sub + 2 * config["router_outputs"] * u + picks * 6 * u * e
    return int(config["num_layers"] * layer + 2 * u * config["vocab_size"])
