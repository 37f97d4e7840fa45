"""One chip's share of GLM-5 (latent attention over a learned selection
of the cached tokens, sigmoid-routed experts of which ``n_routed_experts``
are held here, a shared expert) served by ``serving.Server`` +
``GlmDsaDecodeEngine`` + ``PagePool``, built from a config file under the
published key names."""
from __future__ import annotations

import importlib.util
import math

KIND = "serve"

# a checkout without the model cannot run this configuration: say so when
# the builder is imported, before anything is built
if importlib.util.find_spec(
        "mxnet_tpu.gluon.model_zoo.nlp.glm_moe_dsa") is None:
    raise ImportError("this checkout's mxnet_tpu has no GLM-5 "
                      "(gluon/model_zoo/nlp/glm_moe_dsa.py)")


def _model_kwargs(config: dict) -> dict:
    return dict(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        first_k_dense=config["first_k_dense_replace"],
        units=config["hidden_size"],
        ffn_hidden_size=config["intermediate_size"],
        moe_ffn_hidden_size=config["moe_intermediate_size"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        n_routed_experts=config["router_outputs"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        first_held=config.get("first_held_expert", 0),
        held_experts=config["n_routed_experts"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        eps=config["rms_norm_eps"])


def _layer_shapes(config: dict, moe: bool) -> dict:
    u, h = config["hidden_size"], config["num_attention_heads"]
    qr, kr = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    n_j, d_j = config["index_n_heads"], config["index_head_dim"]
    shapes = {"in_norm": (u,), "qa": (qr, u), "qnorm": (qr,),
              "qb": (h * (nope + rope), qr), "kva": (kr + rope, u),
              "kvnorm": (kr,), "kvb": (h * (nope + v), kr),
              "out": (u, h * v), "iq": (n_j * d_j, qr), "ik": (d_j, u),
              "ik_gain": (d_j,), "ik_bias": (d_j,), "iw": (n_j, u),
              "post_norm": (u,)}
    if not moe:
        f = config["intermediate_size"]
        return dict(shapes, ffn_gate_up=(2 * f, u), ffn_down=(u, f))
    e = config["moe_intermediate_size"]
    s = config["n_shared_experts"] * e
    held, outs = config["n_routed_experts"], config["router_outputs"]
    return dict(shapes, router=(outs, u), router_bias=(outs,),
                gate_up=(held, u, 2 * e), down=(held, e, u),
                shared_gate_up=(2 * s, u), shared_down=(u, s))


def _draw(key, name, shape, dtype, config):
    """Matrices (and each expert of a stack) uniform with Xavier's bound
    sqrt(6 / (fan_in + fan_out)), norm gains 1, the index LayerNorm's
    bias 0; the ROUTER and the indexer's ``weights_proj`` normal with std
    1 / sqrt(hidden), so that the router's logits have unit standard
    deviation and the picks follow the token; the selection bias uniform
    in +-``init_router_bias_range``; the embedding normal with std
    ``init_embed_std`` and the routed experts' down-projection at
    ``init_expert_down_scale`` of Xavier's bound where the config file
    gives them, with the readings that chose them."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if name in ("router", "iw"):
        return (jax.random.normal(key, shape, f32)
                / math.sqrt(shape[1])).astype(dtype)
    if name == "embed" and "init_embed_std" in config:
        return (jax.random.normal(key, shape, f32)
                * config["init_embed_std"]).astype(dtype)
    if name == "router_bias":
        r = config["init_router_bias_range"]
        return jax.random.uniform(key, shape, f32, -r, r).astype(dtype)
    if name == "ik_bias":
        return jnp.zeros(shape, dtype)
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    bound = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    if name == "down":                  # the held routed experts' own
        bound *= config.get("init_expert_down_scale", 1.0)
    return jax.random.uniform(key, shape, f32, -bound, bound).astype(dtype)


def _draw_all(key, shapes: dict, config: dict) -> dict:
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["dtype"])
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, name, shape, dtype, config)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def make_layer(config: dict, moe: bool, key):
    """One layer's weights under the reference's names; jitted once per
    kind, called per layer."""
    flat = _draw_all(key, _layer_shapes(config, moe), config)
    if moe:
        flat["moe"] = {k: flat.pop(k) for k in
                       ("router", "router_bias", "gate_up", "down")}
    return flat


def make_ends(config: dict, key):
    u, v = config["hidden_size"], config["vocab_size"]
    return _draw_all(key, {"embed": (v, u), "lm_head": (v, u),
                           "norm": (u,)}, config)


def _layer_params(blk) -> dict:
    a = blk.attn
    out = {"in_norm": blk.in_norm.weight, "qa": a.q_a.weight,
           "qnorm": a.q_norm.weight, "qb": a.q_b.weight,
           "kva": a.kv_a.weight, "kvnorm": a.kv_norm.weight,
           "kvb": a.kvb_weight, "out": a.out_proj.weight,
           "iq": a.index_q.weight, "ik": a.index_k.weight,
           "ik_gain": a.index_k_norm.gamma, "ik_bias": a.index_k_norm.beta,
           "iw": a.index_w.weight, "post_norm": blk.post_norm.weight}
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
    """The net with seeded weights on the device, made there one layer at
    a time in the served dtype (hardware RNG) and put with
    ``Parameter.set_data``; the cheapest ``initialize`` first, as in the
    LongCat builder. Layer by layer, so that never more than one layer's
    weights exist twice."""
    import functools

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import GlmDsaModel

    ctx = mx.tpu(0) if ctx is None else ctx
    net = GlmDsaModel(**_model_kwargs(config))
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
        layer = {moe: jax.jit(functools.partial(make_layer, config, moe))
                 for moe in (False, True)}
        for blk, k in zip(net.blocks, keys[1:]):
            put(_layer_params(blk), layer[blk.is_moe](k))
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
    """Forward FLOPs of ONE token on this chip at context 1: attention,
    indexer, shared expert and dense parts whole, the held experts at
    their mean load (top_k x held / router_outputs picks a token), the
    head's slice."""
    u, e = config["hidden_size"], config["moe_intermediate_size"]
    total = 0
    for i in range(config["num_hidden_layers"]):
        moe = i >= config["first_k_dense_replace"]
        total += sum(2 * s[-2] * s[-1]
                     for k, s in _layer_shapes(config, moe).items()
                     if len(s) == 2)
        if moe:
            total += (config["num_experts_per_tok"]
                      * config["n_routed_experts"]
                      / config["router_outputs"]) * 6 * u * e
    return int(total + 2 * u * config["vocab_size"])
