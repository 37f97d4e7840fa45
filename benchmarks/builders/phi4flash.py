"""Phi-4-mini-flash-reasoning whole (Mamba + windowed attention, one
full-attention K/V cache read by the cross-attention layers, gated memory
units, differential attention) served by ``serving.Server`` +
``Phi4FlashDecodeEngine`` + ``PagePool`` + its state slots, built from a
config file under the published key names."""
from __future__ import annotations

import importlib.util
import math

KIND = "serve"

# a checkout without the model cannot run this configuration: say so when
# the builder is imported, before anything is built
if importlib.util.find_spec(
        "mxnet_tpu.gluon.model_zoo.nlp.phi4flash") is None:
    raise ImportError("this checkout's mxnet_tpu has no Phi-4-mini-flash "
                      "(gluon/model_zoo/nlp/phi4flash.py)")


def _sizes(config: dict) -> dict:
    a = config["assumed_sizes"]
    u = config["hidden_size"]
    d = a["head_dim"]
    return dict(u=u, f=config["intermediate_size"], d_in=a["expand"] * u,
                n=a["d_state"], k=a["d_conv"], r=a["dt_rank"], d=d,
                hq=config["num_attention_heads"],
                hkv=config["num_key_value_heads"])


def _model_kwargs(config: dict) -> dict:
    a = config["assumed_sizes"]
    return dict(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        units=config["hidden_size"],
        ffn_hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        sliding_window=config["sliding_window"], d_state=a["d_state"],
        d_conv=a["d_conv"], expand=a["expand"], dt_rank=a["dt_rank"],
        eps=config["layer_norm_eps"])


def _layer_shapes(config: dict, kind: str) -> dict:
    """A layer's weights under the reference's names (``kind``: mamba,
    window, full, gmu or cross)."""
    s = _sizes(config)
    u, d_in = s["u"], s["d_in"]
    shapes = {"ln1_g": (u,), "ln1_b": (u,), "ln2_g": (u,), "ln2_b": (u,),
              "gate_up": (2 * s["f"], u), "down": (u, s["f"])}
    heads = {"lq1": (s["d"],), "lk1": (s["d"],), "lq2": (s["d"],),
             "lk2": (s["d"],), "subln": (2 * s["d"],), "o": (u, u),
             "o_b": (u,)}
    if kind == "mamba":
        return dict(shapes, **{
            "in": (2 * d_in, u), "conv_w": (d_in, s["k"]),
            "conv_b": (d_in,), "x": (s["r"] + 2 * s["n"], d_in),
            "dt_w": (d_in, s["r"]), "dt_b": (d_in,),
            "a_log": (d_in, s["n"]), "d": (d_in,), "out": (u, d_in)})
    if kind in ("window", "full"):
        n_qkv = (s["hq"] + 2 * s["hkv"]) * s["d"]
        return dict(shapes, **heads, qkv=(n_qkv, u), qkv_b=(n_qkv,))
    if kind == "gmu":
        return dict(shapes, gmu_in=(d_in, u), gmu_out=(u, d_in))
    return dict(shapes, **heads, q=(u, u), q_b=(u,))


def _draw(key, name, shape, dtype, config):
    """Mamba's published initialisation where the recurrence depends on
    it (``A_log = log(1..N)`` a row, ``D = 1``, ``b_dt`` so that
    ``softplus(b_dt)`` is log-uniform in [1e-3, 0.1], ``W_dt`` uniform in
    +-``dt_rank^-0.5``, the depthwise convolution uniform in
    +-``d_conv^-0.5``: its fan-in is its 4 taps, and Xavier's bound over
    (d_inner, d_conv) would make the scan's input and the memory 15x too
    small to matter), lambda vectors normal with std 0.1, norm gains
    (``*_g``, ``subln``) 1, biases 0, the embedding normal with std
    ``init_embed_std`` where the config file gives it, every other matrix
    uniform with Xavier's bound sqrt(6 / (fan_in + fan_out)); the rows of
    ``Wqkv`` that make queries and keys, and a cross layer's ``Wq``,
    times ``init_qk_gain`` where the config file gives it (the config's
    ``assumed.weights`` says why each scale is what it is)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if name == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32)),
            shape).astype(dtype)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name in ("dt_w", "conv_w"):
        bound = shape[1] ** -0.5
        return jax.random.uniform(key, shape, f32, -bound,
                                  bound).astype(dtype)
    if name in ("lq1", "lk1", "lq2", "lk2"):
        return (0.1 * jax.random.normal(key, shape, f32)).astype(dtype)
    if name == "embed" and "init_embed_std" in config:
        return (jax.random.normal(key, shape, f32)
                * config["init_embed_std"]).astype(dtype)
    if name in ("d", "subln") or name.endswith("_g"):
        return jnp.ones(shape, dtype)
    if len(shape) == 1:                                 # biases
        return jnp.zeros(shape, dtype)
    bound = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    w = jax.random.uniform(key, shape, f32, -bound, bound)
    if name in ("qkv", "q") and "init_qk_gain" in config:
        s = _sizes(config)
        n_qk = (s["hq"] + s["hkv"]) * s["d"]      # Wqkv's value rows keep 1
        w = w * jnp.where(jnp.arange(shape[0]) < n_qk,
                          f32(config["init_qk_gain"]), f32(1.0))[:, None]
    return w.astype(dtype)


def _draw_all(key, shapes: dict, config: dict) -> dict:
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["dtype"])
    keys = jax.random.split(key, len(shapes))
    return {name: _draw(k, name, shape, dtype, config)
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def make_layer(config: dict, kind: str, key):
    """One layer's weights under the reference's names; jitted once per
    kind, called per layer."""
    return _draw_all(key, _layer_shapes(config, kind), config)


def make_ends(config: dict, key):
    u = config["hidden_size"]
    return _draw_all(key, {"embed": (config["vocab_size"], u),
                           "norm_g": (u,), "norm_b": (u,)}, config)


def _layer_params(blk) -> dict:
    m = blk.mixer
    out = {"ln1_g": blk.norm1.gamma, "ln1_b": blk.norm1.beta,
           "ln2_g": blk.norm2.gamma, "ln2_b": blk.norm2.beta,
           "gate_up": blk.mlp.gate_up.weight, "down": blk.mlp.down.weight}
    if blk.kind == "mamba":
        return dict(out, **{
            "in": m.in_weight, "conv_w": m.conv_weight,
            "conv_b": m.conv_bias, "x": m.x_weight, "dt_w": m.dt_weight,
            "dt_b": m.dt_bias, "a_log": m.a_log, "d": m.d,
            "out": m.out_weight})
    if blk.kind == "gmu":
        return dict(out, gmu_in=m.in_weight, gmu_out=m.out_weight)
    heads = {"lq1": m.lq1, "lk1": m.lk1, "lq2": m.lq2, "lk2": m.lk2,
             "subln": m.subln, "o": m.out_weight, "o_b": m.out_bias}
    if blk.kind == "cross":
        return dict(out, **heads, q=m.q_weight, q_b=m.q_bias)
    return dict(out, **heads, qkv=m.qkv_weight, qkv_b=m.qkv_bias)


def build_net(config: dict, seed: int, ctx=None):
    """The net with seeded weights on the device, made there one layer at
    a time in the served dtype (hardware RNG) and put with
    ``Parameter.set_data``; the cheapest ``initialize`` first, as in the
    GLM-5 builder. Layer by layer, so that never more than one layer's
    weights exist twice."""
    import functools

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import Phi4FlashModel

    ctx = mx.tpu(0) if ctx is None else ctx
    net = Phi4FlashModel(**_model_kwargs(config))
    net.collect_params().setattr("grad_req", "null")
    net.cast(config["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    device = ctx.jax_device()

    def put(params, values):
        for name, p in params.items():
            p.set_data(mx.nd.NDArray(data=values[name], ctx=ctx))

    keys = jax.random.split(jax.random.key(seed, impl="rbg"),
                            len(net.blocks) + 1)
    with jax.default_device(device):
        layer = {}
        for blk, k in zip(net.blocks, keys[1:]):
            if blk.kind not in layer:
                layer[blk.kind] = jax.jit(functools.partial(
                    make_layer, config, blk.kind))
            put(_layer_params(blk), layer[blk.kind](k))
        put({"embed": net.embed.weight, "norm_g": net.norm.gamma,
             "norm_b": net.norm.beta},
            jax.jit(functools.partial(make_ends, config))(keys[0]))
    jax.block_until_ready(net.embed.weight.data().data)
    return net, ctx


def start_server(net, ctx, traffic: dict):
    """``net`` behind a started ``serving.Server`` as the traffic file's
    ``server`` group sizes it."""
    from mxnet_tpu import serving

    s = traffic["server"]
    srv = serving.Server(
        net, batch_buckets=tuple(s["batch_buckets"]), dtype="int32", ctx=ctx,
        slo_ms=60000.0, decode_pages=s["decode_pages"],
        page_size=s["page_size"], len_buckets=tuple(s["len_buckets"]),
        max_generate_tokens=s["max_generate_tokens"],
        defrag_threshold=s["defrag_threshold"],
        max_prefill_tokens=s.get("max_prefill_tokens"), name="bench")
    return srv.start()


def build(config: dict, traffic: dict, seed: int, devices) -> dict:
    net, ctx = build_net(config, seed)
    return {"net": net, "server": start_server(net, ctx, traffic),
            "ctx": ctx}


def export_weights(built: dict) -> dict:
    """The net's weights under the reference's names, as device arrays in
    the dtype they are served in."""
    net = built["net"]

    def w(p):
        return p.data().data

    return {"embed": w(net.embed.weight), "norm_g": w(net.norm.gamma),
            "norm_b": w(net.norm.beta),
            "layers": [{k: w(p) for k, p in _layer_params(blk).items()}
                       for blk in net.blocks]}


def flops_per_token(config: dict, traffic: dict) -> int:
    """Forward FLOPs of ONE decode token at context 1: every matrix of
    every layer and the tied head."""
    from mxnet_tpu.gluon.model_zoo.nlp.phi4flash import layer_kinds

    total = 0
    for kind in layer_kinds(config["num_hidden_layers"]):
        total += sum(2 * s[-2] * s[-1]
                     for s in _layer_shapes(config, kind).values()
                     if len(s) == 2)
    return int(total + 2 * config["hidden_size"] * config["vocab_size"])
