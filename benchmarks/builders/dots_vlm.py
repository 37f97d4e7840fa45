"""One chip's share of dots.vlm1 (a NaViT vision tower, whole, in front of
DeepSeek-V3 layers: dense latent attention with YaRN rotary,
group-limited sigmoid routing over experts of which ``n_routed_experts``
are held here, a shared expert) served by ``serving.Server`` +
``DotsVlmDecodeEngine`` + its ``NavitEncodeEngine`` + ``PagePool``; a
config file under the published key names says what is built."""
from __future__ import annotations

import math

# a checkout without the model cannot run this configuration: the import
# fails when the builder is imported, before anything is built
import mxnet_tpu.gluon.model_zoo.nlp.dots_vlm  # noqa: F401

KIND = "serve"


def _vision_kwargs(config: dict) -> dict:
    vc = config["vision_config"]
    return dict(embed_dim=vc["embed_dim"], num_layers=vc["num_hidden_layers"],
                num_heads=vc["num_attention_heads"],
                intermediate_size=vc["intermediate_size"],
                patch_dim=vc["num_channels"] * vc["patch_size"] ** 2,
                eps=vc["rms_norm_eps"], rope_theta=vc["rope_theta"])


def _model_kwargs(config: dict) -> dict:
    rs = config["rope_scaling"]
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
        n_routed_experts=config["router_outputs"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=config["routed_scaling_factor"],
        first_held=config.get("first_held_expert", 0),
        held_experts=config["n_routed_experts"],
        rope_theta=config["rope_theta"],
        yarn=(rs["factor"], rs["beta_fast"], rs["beta_slow"],
              rs["original_max_position_embeddings"]),
        yarn_mscale_all_dim=rs["mscale_all_dim"],
        eps=config["rms_norm_eps"], image_token_id=config["image_token_id"],
        vision=_vision_kwargs(config))


def _layer_shapes(config: dict, moe: bool) -> dict:
    u, h = config["hidden_size"], config["num_attention_heads"]
    qr, kr = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    shapes = {"in_norm": (u,), "qa": (qr, u), "qnorm": (qr,),
              "qb": (h * (nope + rope), qr), "kva": (kr + rope, u),
              "kvnorm": (kr,), "kvb": (h * (nope + v), kr),
              "out": (u, h * v), "post_norm": (u,)}
    if not moe:
        f = config["intermediate_size"]
        return dict(shapes, ffn_gate_up=(2 * f, u), ffn_down=(u, f))
    e = config["moe_intermediate_size"]
    s = config["n_shared_experts"] * e
    held, outs = config["n_routed_experts"], config["router_outputs"]
    return dict(shapes, router=(outs, u), router_bias=(outs,),
                gate_up=(held, u, 2 * e), down=(held, e, u),
                shared_gate_up=(2 * s, u), shared_down=(u, s))


def _vision_shapes(config: dict) -> tuple:
    """The tower's weights under the reference's names: (the ends', the
    blocks' with their leading layer axis)."""
    vc = config["vision_config"]
    e, f, n = vc["embed_dim"], vc["intermediate_size"], vc["num_hidden_layers"]
    p = vc["num_channels"] * vc["patch_size"] ** 2
    u = config["hidden_size"]
    ends = {"patch_w": (e, p), "patch_b": (e,), "patch_norm": (e,),
            "post_norm": (e,), "ln_g": (e,), "ln_b": (e,),
            "merger_a": (4 * e, 4 * e), "merger_a_b": (4 * e,),
            "merger_b": (u, 4 * e), "merger_b_b": (u,)}
    blocks = {"norm1": (n, e), "qkv": (n, 3 * e, e), "proj": (n, e, e),
              "norm2": (n, e), "fc13": (n, 2 * f, e), "fc2": (n, e, f)}
    return ends, blocks


_ZEROS = ("patch_b", "ln_b", "merger_a_b", "merger_b_b")
_ONES = ("in_norm", "qnorm", "kvnorm", "post_norm", "norm", "patch_norm",
         "ln_g", "norm1", "norm2")


def _draw(key, name, shape, dtype, config):
    """Matrices (each expert, each tower layer of a stack) uniform with
    Xavier's bound sqrt(6 / (fan_in + fan_out)), norm gains 1, biases 0;
    the ROUTER normal with std 1 / sqrt(hidden), so that its logits have
    unit standard deviation and the picks follow the token; the selection
    bias uniform in +-``init_router_bias_range``. The scales the config
    file gives with the readings that chose them: the embedding normal
    with std ``init_embed_std``; the merger's output matrix times
    ``init_merger_out_scale``, so that an image's rows have the
    embedding's rms; the routed experts' down-projection at
    ``init_expert_down_scale`` of Xavier's bound; the query halves of the
    attentions (the tower's ``q`` rows of ``qkv``, the language model's
    ``qb``) times ``init_vit_q_gain`` / ``init_attn_q_gain``, so that a
    query's softmax is not uniform and the output depends on WHICH keys
    it read (what the 2-D rotary, the bound on an image's keys and YaRN
    decide)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if name == "router":
        return (jax.random.normal(key, shape, f32)
                / math.sqrt(shape[1])).astype(dtype)
    if name == "embed" and "init_embed_std" in config:
        return (jax.random.normal(key, shape, f32)
                * config["init_embed_std"]).astype(dtype)
    if name == "router_bias":
        r = config["init_router_bias_range"]
        return jax.random.uniform(key, shape, f32, -r, r).astype(dtype)
    if name in _ZEROS:
        return jnp.zeros(shape, dtype)
    if name in _ONES:
        return jnp.ones(shape, dtype)
    bound = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    w = jax.random.uniform(key, shape, f32, -bound, bound)
    if name == "down":                  # the held routed experts' own
        w = w * config.get("init_expert_down_scale", 1.0)
    elif name == "merger_b":
        w = w * config.get("init_merger_out_scale", 1.0)
    elif name == "qb":
        w = w * config.get("init_attn_q_gain", 1.0)
    elif name == "qkv":
        third = shape[-2] // 3
        gain = jnp.where(jnp.arange(shape[-2]) < third,
                         config.get("init_vit_q_gain", 1.0), 1.0)
        w = w * gain[:, None]
    return w.astype(dtype)


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


def make_vision(config: dict, key):
    """The tower's weights; a stack is drawn a layer at a time, so that
    the largest temporary is one layer's random bits and not 42 layers'
    (2.2 GB for the SwiGLU stack: it would stand in the device's peak)."""
    import jax
    import jax.numpy as jnp

    ends, blocks = _vision_shapes(config)
    k_ends, k_blocks = jax.random.split(key)
    dtype = jnp.dtype(config["dtype"])
    stacks = {
        name: jax.lax.map(
            lambda k, name=name, shape=shape: _draw(k, name, shape[1:],
                                                    dtype, config),
            jax.random.split(k, shape[0]))
        for k, (name, shape) in zip(jax.random.split(k_blocks, len(blocks)),
                                    sorted(blocks.items()))}
    return dict(_draw_all(k_ends, ends, config), blocks=stacks)


def _layer_params(blk) -> dict:
    a = blk.attn
    out = {"in_norm": blk.in_norm.weight, "qa": a.q_a.weight,
           "qnorm": a.q_norm.weight, "qb": a.q_b.weight,
           "kva": a.kv_a.weight, "kvnorm": a.kv_norm.weight,
           "kvb": a.kvb_weight, "out": a.out_proj.weight,
           "post_norm": blk.post_norm.weight}
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
    """The net with seeded weights on the device, made there a layer (and
    the tower) at a time in the served dtype (hardware RNG) and put with
    ``Parameter.set_data``; the cheapest ``initialize`` first, as in the
    GLM-5 builder."""
    import functools

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import DotsVlmModel

    ctx = mx.tpu(0) if ctx is None else ctx
    net = DotsVlmModel(**_model_kwargs(config))
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
                            len(net.blocks) + 2)
    with jax.default_device(device):
        # the tower first: its stacks are the largest single draws
        put(net.vision.weights,
            jax.jit(functools.partial(make_vision, config))(keys[1]))
        layer = {moe: jax.jit(functools.partial(make_layer, config, moe))
                 for moe in (False, True)}
        for blk, k in zip(net.blocks, keys[2:]):
            put(_layer_params(blk), layer[blk.is_moe](k))
        put({"embed": net.embed.weight, "lm_head": net.lm_head.weight,
             "norm": net.norm.weight},
            jax.jit(functools.partial(make_ends, config))(keys[0]))
    jax.block_until_ready(net.lm_head.weight.data().data)
    return net, ctx


def start_server(net, ctx, traffic: dict):
    from mxnet_tpu import serving

    s = traffic["server"]
    srv = serving.Server(
        net, batch_buckets=tuple(s["batch_buckets"]), dtype="int32", ctx=ctx,
        slo_ms=60000.0, decode_pages=s["decode_pages"],
        page_size=s["page_size"], len_buckets=tuple(s["len_buckets"]),
        max_generate_tokens=s["max_generate_tokens"],
        defrag_threshold=s["defrag_threshold"],
        max_prefill_tokens=s.get("max_prefill_tokens"),
        patch_buckets=tuple(s["patch_buckets"]),
        max_image_tokens=s["max_image_tokens"], name="bench")
    return srv.start()


def build(config: dict, traffic: dict, seed: int, devices) -> dict:
    net, ctx = build_net(config, seed)
    return {"net": net, "server": start_server(net, ctx, traffic),
            "ctx": ctx}


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
                       for blk in net.blocks],
            "vision": jax.tree_util.tree_map(w, net.vision.weights)}


def flops_per_token(config: dict, traffic: dict) -> int:
    """Forward FLOPs of ONE language token on this chip at context 1:
    attention, shared expert and dense parts whole, the held experts at
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
