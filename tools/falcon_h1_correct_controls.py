"""What the benchmark's ``correct`` can see of Falcon-H1: the cell's own
server and engine (``benchmarks/builders/falcon_h1.py``, the server group
of the cell's traffic file, the Pallas kernels, the 128-stream decode
bucket with its padding rows) answer a few requests, and
``benchmarks/lib/serve_loop.py::check_outputs`` - the comparison that
decides ``correct`` in the cell, with its own limit - judges them. Once
as the program is, which has to come out ``ok``, and once with each of
these planted, which has to come out NOT ok:

* ``tail``: the convolution tail is not carried from one dispatch to the
  next (read as zeros).
* ``key_multiplier``: the keys' muP multiplier left out.
* ``ssm_multipliers``: two segments' multipliers swapped (``x`` and
  ``C``).
* ``group_norm``: the gated norm's statistics taken over all 4096
  channels and not a group's 2048.
* ``lower_precision``: the nearest precision below the configuration's
  everywhere it states one: the residual stream rounded to an 8-bit float
  (e4m3) after every layer where the configuration's products take
  bfloat16, the scan state kept in bfloat16 where it says float32.

READINGS, not controls (``assumed.what_correct_cannot_see`` of the
configuration file has them): ``residual_bf16`` (the residual stream
rounded to bfloat16 after every layer, where the configuration says
float32) and ``scan_bf16`` (the scan state alone kept in bfloat16 between
dispatches): each costs less than the bfloat16 operands of every product
already do, so no limit on greedy tokens tells them apart;
``tools/falcon_h1_chip_check.py`` compares logits and single mixers for
those.

    chiprun -- python3 tools/falcon_h1_correct_controls.py
    JAX_PLATFORMS=cpu python3 tools/falcon_h1_correct_controls.py \
        --config tiny_falcon_h1 --traffic tiny_chat_closed \
        --prompts 21,37 --new 12

Exit code 0 only if the sound program is ok and every control is not.
Prints one JSON line with every reading (``worst_gap_in_tolerances``: 1.0
is the limit).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROLS = ("tail", "key_multiplier", "ssm_multipliers", "group_norm",
            "lower_precision")
READINGS = ("residual_bf16", "scan_bf16")


def _load(kind: str, name: str) -> dict:
    path = name if name.endswith(".json") else os.path.join(
        ROOT, "benchmarks", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


@contextlib.contextmanager
def planted(fault, net):
    """The model's serving functions with ``fault`` in them, for the
    programs traced inside (an engine built inside has a program cache
    entry of its own: the fault's name is in its ``cfg``)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.nlp import falcon_h1 as model
    from mxnet_tpu.ops import ssm

    engine = model.FalconH1DecodeEngine
    sound = (model._mixer, model._layer_forward, ssm.gated_group_norm,
             engine._make_arenas)
    mixer, layer_forward, group_norm, make_arenas = sound
    cfg = net._decode_cfg
    kept = {k: cfg[k] for k in ("key_multiplier", "ssm_multipliers")}
    low = jnp.dtype(jnp.bfloat16)

    def no_tail(u, p, tails, *rest):
        return mixer(u, p, jnp.zeros_like(tails), *rest)

    def low_state(u, p, tails, states, *rest):
        out, tails, new = mixer(u, p, tails, states.astype(jnp.float32),
                                *rest)
        return out, tails, new.astype(states.dtype)

    def low_state_arenas(self, pool):
        arenas = make_arenas(self, pool)
        states = self.slot_arrays["states"]
        for i in range(len(states)):        # one float32 copy at a time
            states[i] = states[i].astype(low)
        return arenas

    def one_group(y, z, gain, n_groups, eps):
        return group_norm(y, z, gain, 1, eps)

    def rounded(exponent_bits, mantissa_bits):
        # a convert there and back is one the compiler may drop (excess
        # precision); reduce_precision is not
        def layer(*args, **kw):
            x, *rest = layer_forward(*args, **kw)
            return (jax.lax.reduce_precision(x, exponent_bits,
                                             mantissa_bits), *rest)
        return layer

    if fault is not None:
        cfg["planted"] = fault
    if fault == "tail":
        model._mixer = no_tail
    elif fault == "key_multiplier":
        cfg["key_multiplier"] = 1.0
    elif fault == "ssm_multipliers":
        z, x, b, c, dt = cfg["ssm_multipliers"]
        cfg["ssm_multipliers"] = (z, c, b, x, dt)
    elif fault == "group_norm":
        ssm.gated_group_norm = one_group
    elif fault == "residual_bf16":
        model._layer_forward = rounded(8, 7)
    elif fault in ("scan_bf16", "lower_precision"):
        model._mixer = low_state
        engine._make_arenas = low_state_arenas
        if fault == "lower_precision":
            model._layer_forward = rounded(4, 3)
    try:
        yield
    finally:
        (model._mixer, model._layer_forward, ssm.gated_group_norm,
         engine._make_arenas) = sound
        cfg.pop("planted", None)
        cfg.update(kept)


def judge(config: dict, traffic: dict, seed: int, prompt_lens, n_new: int,
          faults, log=lambda msg: None) -> dict:
    """``check_outputs`` on the answers of the cell's own server, once per
    entry of ``faults`` (None: the program as it is)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.builders import falcon_h1 as builder
    from benchmarks.lib import arrivals, serve_loop
    from benchmarks.references import falcon_h1 as reference

    on_cpu = jax.devices()[0].platform == "cpu"
    net, ctx = builder.build_net(config, seed,
                                 ctx=mx.cpu() if on_cpu else None)
    weights = builder.export_weights({"net": net})
    log("weights made")
    rs = np.random.RandomState(seed % (2 ** 31))
    prompts = [rs.randint(1, config["vocab_size"], (n,)).astype(np.int32)
               for n in prompt_lens]
    run = types.SimpleNamespace(seed=seed, config=config,
                                reference=reference)
    got = {}
    for fault in faults:
        with planted(fault, net):
            srv = builder.start_server(net, ctx, traffic)
            gen = serve_loop.Generator(run, srv, traced=False)
            for i, prompt in enumerate(prompts):
                gen.send(serve_loop.Rec(arrivals.Request(
                    i, 0.0, prompt, n_new, i)), time.perf_counter())
            gen.drain(serve_loop.DRAIN_TIMEOUT_S * 4)
            srv.stop(timeout=60.0)
            gen.srv = None
            del srv
            gc.collect()
        check = serve_loop.check_outputs(run, weights, gen.records,
                                         len(prompts))
        errors = [repr(r.error) for r in gen.records if r.error]
        if errors:
            check = dict(check, ok=False, errors=errors)
        # how many different tokens an answer holds: one would mean the
        # greedy token no longer depends on the layers
        check["distinct_tokens"] = [
            int(np.unique(r.handle.result(timeout=1.0)).size)
            for r in gen.records if r.error is None]
        log(f"{fault or 'sound'}: {check}")
        got[fault or "sound"] = check
    return got


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="falcon_h1_34b_l6")
    ap.add_argument("--traffic", default="chat_closed_c128")
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--prompts", default="131,384,64",
                    help="the requests' prompt lengths")
    ap.add_argument("--new", type=int, default=384,
                    help="tokens every request generates")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of sound and the faults")
    args = ap.parse_args()
    t0 = time.perf_counter()

    def log(msg):
        print(f"[controls +{time.perf_counter() - t0:6.1f}s] {msg}",
              flush=True)

    config = _load("configs", args.config)
    for k, v in config.get("env", {}).items():
        os.environ[k] = str(v)
    faults = [n for n in (None,) + CONTROLS + READINGS
              if not args.only or (n or "sound") in args.only.split(",")]
    lens = [int(n) for n in args.prompts.split(",")]
    got = judge(config, _load("traffic", args.traffic), args.seed, lens,
                args.new, faults, log)
    verdict = (got.get("sound", {"ok": True})["ok"]
               and not any(got[n]["ok"] for n in CONTROLS if n in got))
    print(json.dumps(dict(got, prompts=lens, new=args.new,
                          verdict=bool(verdict))))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
