"""One chip's share of Ling-3.0-flash's language model (Kimi Delta
Attention layers beside one latent-attention layer in six,
group-limited sigmoid routing over experts of which ``num_experts`` are
held here, a shared expert) served by ``serving.Server`` +
``LingLinearDecodeEngine`` + ``PagePool`` + its state slots; a config
file under the published key names says what is built."""
from __future__ import annotations

import math

# a checkout without the model cannot run this configuration: the import
# fails when the builder is imported, before anything is built
import mxnet_tpu.gluon.model_zoo.nlp.ling_linear  # noqa: F401

from benchmarks.builders.falcon_h1 import (start_server,  # noqa: F401
                                           warm_widest_decode)

KIND = "serve"


def _model_kwargs(config: dict) -> dict:
    c = config
    return dict(
        vocab_size=c["vocab_size"], layer_kinds=tuple(c["layer_kinds"]),
        first_k_dense=c["first_k_dense_replace"], units=c["hidden_size"],
        ffn_hidden_size=c["intermediate_size"],
        moe_ffn_hidden_size=c["moe_intermediate_size"],
        num_heads=c["num_attention_heads"], head_dim=c["head_dim"],
        conv_kernel=c["short_conv_kernel_size"],
        kda_lower_bound=c["kda_lower_bound"],
        kda_safe_gate=c["kda_safe_gate"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=c["rope_theta"], rope_interleaved=False,
        n_routed_experts=c["router_outputs"],
        num_experts_per_tok=c["num_experts_per_tok"],
        n_shared_experts=c["moe_shared_expert_intermediate_size"]
        // c["moe_intermediate_size"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        routed_scaling_factor=c["routed_scaling_factor"],
        first_held=c.get("first_held_expert", 0),
        held_experts=c["num_experts"], eps=c["rms_norm_eps"])


def _layer_shapes(config: dict, kind: str, moe: bool) -> dict:
    """A layer's weights under the reference's names."""
    c = config
    u, h, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    shapes = {"in_norm": (u,), "post_norm": (u,)}
    if kind == "kda":
        shapes.update(qkv=(3 * h * d, u),
                      conv=(3 * h * d, c["short_conv_kernel_size"]),
                      f=(h * d, u), dt_b=(h * d,), a_log=(h,), b=(h, u),
                      g=(h * d, u), o_norm=(d,), o=(u, h * d))
    else:
        kr, nope, rope, v = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                             c["qk_rope_head_dim"], c["v_head_dim"])
        shapes.update(q=(h * (nope + rope), u), kva=(kr + rope, u),
                      kvnorm=(kr,), kvb=(h * (nope + v), kr), gate=(h, u),
                      out=(u, h * v))
    if not moe:
        f = c["intermediate_size"]
        return dict(shapes, ffn_gate_up=(2 * f, u), ffn_down=(u, f))
    e, s = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    held, outs = c["num_experts"], c["router_outputs"]
    return dict(shapes, router=(outs, u), router_bias=(outs,),
                gate_up=(held, u, 2 * e), down=(held, e, u),
                shared_gate_up=(2 * s, u), shared_down=(u, s))


_ONES = ("in_norm", "post_norm", "o_norm", "kvnorm", "norm")


def _draw(key, name, shape, dtype, config):
    """Matrices (each expert) uniform with Xavier's bound sqrt(6 /
    (fan_in + fan_out)), norm gains 1; the scales the config file gives
    (``assumed.weights`` says why each): the embedding normal with std
    ``init_embed_std``; the router normal with std 1 / sqrt(hidden), its
    selection bias uniform in +-``init_router_bias_range``; the held
    routed experts' down-projection at ``init_expert_down_scale`` of
    Xavier's bound; MLA's query projection times ``init_attn_q_gain``;
    the convolutions uniform in +-taps^-0.5; KDA's ``dt_bias`` uniform a
    channel in ``init_kda_dt_bias_range`` and ``A_log`` uniform a head in
    ``init_kda_a_log_range``, so that the per-token decay spans channels
    that hold thousands of tokens and channels that hold two."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def uniform(lo, hi):
        return jax.random.uniform(key, shape, f32, lo, hi).astype(dtype)

    if name == "router":
        return (jax.random.normal(key, shape, f32)
                / math.sqrt(shape[1])).astype(dtype)
    if name == "embed":
        return (jax.random.normal(key, shape, f32)
                * config.get("init_embed_std", 1.0)).astype(dtype)
    if name == "router_bias":
        r = config["init_router_bias_range"]
        return uniform(-r, r)
    if name == "dt_b":
        return uniform(*config["init_kda_dt_bias_range"])
    if name == "a_log":
        return uniform(*config["init_kda_a_log_range"])
    if name == "conv":
        bound = shape[1] ** -0.5
        return uniform(-bound, bound)
    if name in _ONES:
        return jnp.ones(shape, dtype)
    bound = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    w = jax.random.uniform(key, shape, f32, -bound, bound)
    if name == "down":                  # the held routed experts' own
        w = w * config.get("init_expert_down_scale", 1.0)
    elif name == "q":
        w = w * config.get("init_attn_q_gain", 1.0)
    return w.astype(dtype)


def _draw_all(key, shapes: dict, config: dict) -> dict:
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["dtype"])
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, name, shape, dtype, config)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def make_layer(config: dict, kind: str, moe: bool, key):
    """One layer's weights under the reference's names; jitted once per
    kind, called per layer."""
    flat = _draw_all(key, _layer_shapes(config, kind, moe), config)
    if moe:
        flat["moe"] = {k: flat.pop(k) for k in
                       ("router", "router_bias", "gate_up", "down")}
    return flat


def make_ends(config: dict, key):
    u, v = config["hidden_size"], config["vocab_size"]
    return _draw_all(key, {"embed": (v, u), "lm_head": (v, u),
                           "norm": (u,)}, config)


def _layer_params(blk) -> dict:
    m = blk.mixer
    out = {"in_norm": blk.in_norm.weight, "post_norm": blk.post_norm.weight}
    if blk.kind == "kda":
        out.update(qkv=m.qkv_weight, conv=m.conv_weight, f=m.f_weight,
                   dt_b=m.dt_bias, a_log=m.a_log, b=m.b_weight,
                   g=m.g_weight, o_norm=m.norm_weight, o=m.out_weight)
    else:
        out.update(q=m.q_proj.weight, kva=m.kv_a.weight,
                   kvnorm=m.kv_norm.weight, kvb=m.kvb_weight,
                   gate=m.gate.weight, out=m.out_proj.weight)
    if not blk.is_moe:
        return dict(out, ffn_gate_up=blk.ffn.gate_up.weight,
                    ffn_down=blk.ffn.down.weight)
    r, s = blk.ffn.routed, blk.ffn.shared
    return dict(out, moe={"router": r.router_weight,
                          "router_bias": r.router_bias,
                          "gate_up": r.gate_up_weight,
                          "down": r.down_weight},
                shared_gate_up=s.gate_up.weight,
                shared_down=s.down.weight)


def build_net(config: dict, seed: int, ctx=None):
    """The net with seeded weights on the device, made there a layer at a
    time in the served dtype (hardware RNG) and put with
    ``Parameter.set_data``; the cheapest ``initialize`` first, as in the
    dots.vlm1 builder."""
    import functools

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import LingLinearModel

    ctx = mx.tpu(0) if ctx is None else ctx
    net = LingLinearModel(**_model_kwargs(config))
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
        layer = functools.lru_cache(maxsize=None)(
            lambda kind, moe: jax.jit(functools.partial(
                make_layer, config, kind, moe)))
        for blk, k in zip(net.blocks, keys[1:]):
            put(_layer_params(blk), layer(blk.kind, blk.is_moe)(k))
        put({"embed": net.embed.weight, "lm_head": net.lm_head.weight,
             "norm": net.norm.weight},
            jax.jit(functools.partial(make_ends, config))(keys[0]))
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
    import jax

    net = built["net"]

    def w(p):
        return p.data().data

    return {"embed": w(net.embed.weight), "lm_head": w(net.lm_head.weight),
            "norm": w(net.norm.weight),
            "layers": [jax.tree_util.tree_map(w, _layer_params(blk))
                       for blk in net.blocks]}


def flops_per_token(config: dict, traffic: dict) -> int:
    """Forward FLOPs of ONE token on this chip at context 1: mixers,
    shared expert and dense layer whole, the held experts at their mean
    load (top_k x held / router_outputs picks a token), the head's slice,
    the delta rule's state update (8 a state value: decay, k^T S, the
    outer product, q^T S)."""
    u, e = config["hidden_size"], config["moe_intermediate_size"]
    h, d = config["num_attention_heads"], config["head_dim"]
    total = 0
    for i, kind in enumerate(config["layer_kinds"]):
        moe = i >= config["first_k_dense_replace"]
        total += sum(2 * s[-2] * s[-1]
                     for s in _layer_shapes(config, kind, moe).values()
                     if len(s) == 2)
        if kind == "kda":
            total += 8 * h * d * d
        if moe:
            total += (config["num_experts_per_tok"] * config["num_experts"]
                      / config["router_outputs"]) * 6 * u * e
    return int(total + 2 * u * config["vocab_size"])
