"""Falcon-H1 (parallel hybrid blocks: a Mamba-2 / SSD mixer and GQA
attention side by side in every layer, muP multipliers) served by
``serving.Server`` + ``FalconH1DecodeEngine`` + ``PagePool`` + its state
slots, built from a config file under the published key names."""
from __future__ import annotations

import math

# a checkout without the model cannot run this configuration: the import
# fails when the builder is imported, before anything is built
import mxnet_tpu.gluon.model_zoo.nlp.falcon_h1  # noqa: F401

KIND = "serve"


def _model_kwargs(config: dict) -> dict:
    return dict(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        units=config["hidden_size"],
        ffn_hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ssm=config["mamba_d_ssm"],
        ssm_heads=config["mamba_n_heads"], d_state=config["mamba_d_state"],
        n_groups=config["mamba_n_groups"], d_conv=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"], rope_theta=config["rope_theta"],
        eps=config["rms_norm_eps"],
        **{k: config[k] for k in (
            "embedding_multiplier", "lm_head_multiplier",
            "ssm_in_multiplier", "ssm_out_multiplier",
            "attention_in_multiplier", "attention_out_multiplier",
            "key_multiplier", "ssm_multipliers", "mlp_multipliers")})


def _segments(config: dict) -> tuple:
    """Rows of the in-projection's ``z | x | B | C | dt`` segments."""
    d = config["mamba_d_ssm"]
    gn = config["mamba_n_groups"] * config["mamba_d_state"]
    return (d, d, gn, gn, config["mamba_n_heads"])


def _layer_shapes(config: dict) -> dict:
    """A layer's weights under the reference's names."""
    u, f = config["hidden_size"], config["intermediate_size"]
    d, h = config["mamba_d_ssm"], config["mamba_n_heads"]
    width = d + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    return {"ln1": (u,), "ln2": (u,), "in": (sum(_segments(config)), u),
            "conv_w": (width, config["mamba_d_conv"]), "conv_b": (width,),
            "dt_b": (h,), "a_log": (h,), "d": (h,), "norm": (d,),
            "out": (u, d), "q": (hq * hd, u), "k": (hkv * hd, u),
            "v": (hkv * hd, u), "o": (u, hq * hd), "gate_up": (2 * f, u),
            "down": (u, f)}


def _gain(name: str, config: dict):
    """What a seeded matrix is divided by: the muP multiplier(s) its
    product meets in the forward, per output row where they differ
    (``in``: the mixer's input multiplier times the segment's; ``gate_up``:
    the gate rows' alone), so that WITH the published multipliers every
    product has the statistics of a Xavier-initialised layer without them
    (the config's ``assumed.weights`` says why)."""
    import numpy as np

    c = config
    if name == "in":
        return c["ssm_in_multiplier"] * np.repeat(
            np.asarray(c["ssm_multipliers"], np.float32), _segments(c))
    if name == "gate_up":
        f = c["intermediate_size"]
        return np.repeat(np.asarray([c["mlp_multipliers"][0], 1.0],
                                    np.float32), (f, f))
    return {"out": c["ssm_out_multiplier"],
            "q": c["attention_in_multiplier"],
            "k": c["attention_in_multiplier"] * c["key_multiplier"],
            "v": c["attention_in_multiplier"],
            "o": c["attention_out_multiplier"],
            "down": c["mlp_multipliers"][1],
            "head": c["lm_head_multiplier"]}.get(name)


def _draw(key, name, shape, dtype, config, fans=None):
    """Mamba-2's published initialisation where the recurrence depends on
    it (``A`` uniform in [1, 16] a head, ``D = 1``, ``dt_bias`` so that
    ``softplus(dt_bias)`` is log-uniform in [1e-3, 0.1], the depthwise
    convolution and its bias uniform in +-``d_conv^-0.5``: its fan-in is
    its taps), norm gains 1, the embedding normal with std ``1 /
    embedding_multiplier`` (a unit residual stream at layer 0), every
    other matrix uniform with Xavier's bound sqrt(6 / (fan_in + fan_out))
    DIVIDED by the muP multipliers its product meets (:func:`_gain`);
    ``fans``: the whole matrix's (fan_out, fan_in) where ``shape`` is a
    block of its rows."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0,
                                          16.0)).astype(dtype)
    if name == "dt_b":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name in ("conv_w", "conv_b"):
        bound = config["mamba_d_conv"] ** -0.5
        return jax.random.uniform(key, shape, f32, -bound,
                                  bound).astype(dtype)
    if name == "embed":
        return (jax.random.normal(key, shape, f32)
                / config["embedding_multiplier"]).astype(dtype)
    if len(shape) == 1:                     # d, norm gains
        return jnp.ones(shape, dtype)
    bound = math.sqrt(6.0 / sum(fans or shape[-2:]))
    w = jax.random.uniform(key, shape, f32, -bound, bound)
    gain = _gain(name, config)
    if gain is not None:
        w = w / (jnp.asarray(gain, f32)[:, None] if jnp.ndim(gain) else gain)
    return w.astype(dtype)


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
    rows a key: the two are 1.34 B values each, and drawn whole their
    float32 random bits would not fit beside the weights already made."""
    import jax
    import jax.numpy as jnp

    vocab, u = config["vocab_size"], config["hidden_size"]
    dtype = jnp.dtype(config["dtype"])
    return jax.lax.map(
        lambda k: _draw(k, name, (vocab // keys.shape[0], u), dtype, config,
                        fans=(vocab, u)), keys).reshape(vocab, u)


def _layer_params(blk) -> dict:
    m, a = blk.mixer, blk.attention
    return {"ln1": blk.norm1.weight, "ln2": blk.norm2.weight,
            "in": m.in_weight, "conv_w": m.conv_weight,
            "conv_b": m.conv_bias, "dt_b": m.dt_bias, "a_log": m.a_log,
            "d": m.d, "norm": m.norm_weight, "out": m.out_weight,
            "q": a.q_proj.weight, "k": a.k_proj.weight,
            "v": a.v_proj.weight, "o": a.out_proj.weight,
            "gate_up": blk.mlp.gate_up.weight, "down": blk.mlp.down.weight}


ROW_BLOCKS = 8


def build_net(config: dict, seed: int, ctx=None):
    """The net with seeded weights on the device, made there one layer at
    a time in the served dtype (hardware RNG) and put with
    ``Parameter.set_data``; the cheapest ``initialize`` first, as in the
    Phi-4-mini-flash builder (the large matrices are deferred, so it
    allocates none of them). The embedding and the head are drawn
    ``ROW_BLOCKS`` row blocks at a time."""
    import functools

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nlp import FalconH1Model

    ctx = mx.tpu(0) if ctx is None else ctx
    net = FalconH1Model(**_model_kwargs(config))
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


def warm_widest_decode(srv, traffic: dict, vocab: int, seed: int) -> int:
    """Run the widest decode bucket once where the harness's warm-up
    cannot: it reaches a decode bucket through ONE prefill batch of that
    many prompts, and ``max_prefill_tokens`` closes a batch of the
    shortest length bucket below the widest batch bucket (128 x 64 >
    4,096 in the cell). Prompts of that length bucket are submitted a
    prefill batch at a time from the scheduler thread (each batch in the
    ``on_token`` of the last FIRST token of the batch before, as the
    harness submits its groups), as many as pass the second widest
    bucket, each living until the last batch decodes beside it. Returns
    the requests sent (0: the harness's groups reach the bucket)."""
    import threading
    import time

    import numpy as np

    s = traffic["server"]
    buckets, length = s["batch_buckets"], s["len_buckets"][0]
    bound = s.get("max_prefill_tokens")
    if bound is None or len(buckets) < 2 or buckets[-1] * length <= bound:
        return 0
    batch = max(b for b in buckets if b * length <= bound)
    # one request first: every later submit is on the scheduler thread,
    # so a batch is admitted whole in one tick
    total = 1 + batch * (buckets[-2] // batch + 1)
    rs = np.random.RandomState(seed % (2 ** 31))
    done = threading.Event()
    handles, errors = [], []
    awaited = {"first_tokens": 0}

    def submit_next():
        n = batch if handles else 1
        awaited["first_tokens"] = n
        for _ in range(n):
            prompt = rs.randint(1, vocab, (length,)).astype(np.int32)
            # a token a tick: alive until the last batch's decode round
            handles.append(srv.submit_generate(
                prompt, total // batch + 2, on_token=on_token))

    def on_token(i, _token):
        if i:
            return
        awaited["first_tokens"] -= 1
        if awaited["first_tokens"]:
            return
        try:
            if len(handles) < total:
                submit_next()
            else:
                done.set()
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)
            done.set()

    submit_next()
    deadline = time.perf_counter() + 1100.0
    while not done.wait(timeout=0.5):
        for h in list(handles):
            if h.future.done():
                h.result()              # a failed request's error, now
        if time.perf_counter() > deadline:
            raise RuntimeError("warm-up of the widest decode bucket stalled")
    if errors:
        raise errors[0]
    for h in handles:
        h.result(timeout=600.0)
    return len(handles)


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
    """Forward FLOPs of ONE decode token at context 1: every matrix of
    every layer and the untied head."""
    per_layer = sum(2 * s[-2] * s[-1]
                    for s in _layer_shapes(config).values() if len(s) == 2)
    return int(config["num_hidden_layers"] * per_layer
               + 2 * config["hidden_size"] * config["vocab_size"])
