"""What the benchmark's ``correct`` can see of Phi-4-mini-flash: the cell's
own server and engine (``benchmarks/builders/phi4flash.py``, the server
group of the cell's traffic file, the Pallas read, the 32-stream decode
bucket with its padding rows) answer a few requests, and
``benchmarks/lib/serve_loop.py::check_outputs`` - the comparison that
decides ``correct`` in the cell, with its own limit - judges them. Once
as the program is, which has to come out ``ok``, and once with each of
these planted, which has to come out NOT ok:

* ``tail``: a Mamba layer's convolution tail is not carried from one
  dispatch to the next (read as zeros).
* ``window``: a sliding window one token short.
* ``lam``: the second softmax of every differential pair dropped
  (``lam`` forced to 0).
* ``lower_precision``: the nearest precision below the configuration's:
  the residual stream rounded to an 8-bit float (e4m3) after every layer
  where the configuration says bfloat16, the scan's state kept in
  bfloat16 where it says float32.
* ``scan_bf16``: the scan's state alone kept in bfloat16 between
  dispatches. A READING, not a control: half a percent of a scan's
  output at the published step sizes, below what bfloat16 activations
  already cost, so no limit on greedy tokens can tell it apart.

    chiprun -- python3 tools/phi4flash_correct_controls.py
    JAX_PLATFORMS=cpu python3 tools/phi4flash_correct_controls.py \
        --config tiny_phi4flash --traffic tiny_reason_ctx_closed \
        --prompts 40,52 --new 12

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

CONTROLS = ("tail", "window", "lam", "lower_precision")
READINGS = ("scan_bf16",)


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

    from mxnet_tpu.gluon.model_zoo.nlp import phi4flash as model
    from mxnet_tpu.ops import diff_attention as diff_ops

    engine = model.Phi4FlashDecodeEngine
    sound = (model._mamba_layer, model._mlp,
             diff_ops.diff_attention_combine, engine._make_arenas)
    mamba_layer, mlp, combine, make_arenas = sound
    cfg, window = net._decode_cfg, net._decode_cfg["window"]
    low = jnp.dtype(jnp.bfloat16)

    def no_tail(x, p, tails, *rest):
        return mamba_layer(x, p, jnp.zeros_like(tails), *rest)

    def low_state(x, p, tails, states, *rest):
        out, y, tails, new = mamba_layer(
            x, p, tails, states.astype(jnp.float32), *rest)
        return out, y, tails, new.astype(states.dtype)

    def low_state_arenas(self, pool):
        arenas = make_arenas(self, pool)
        self.slot_arrays["states"] = [
            a.astype(low) for a in self.slot_arrays["states"]]
        return arenas

    def first_softmax_only(paired, *a, **kw):
        return combine(paired.at[..., 1, :].set(0.0), *a, **kw)

    def low_residual(x, p, eps):
        # e4m3's 4 exponent and 3 mantissa bits; a convert there and
        # back is one the compiler may drop (excess precision)
        return jax.lax.reduce_precision(mlp(x, p, eps), 4, 3)

    if fault is not None:
        cfg["planted"] = fault
    if fault == "tail":
        model._mamba_layer = no_tail
    elif fault == "window":
        cfg["window"] = window - 1
    elif fault == "lam":
        diff_ops.diff_attention_combine = first_softmax_only
    elif fault in ("scan_bf16", "lower_precision"):
        model._mamba_layer = low_state
        engine._make_arenas = low_state_arenas
        if fault == "lower_precision":
            model._mlp = low_residual
    try:
        yield
    finally:
        (model._mamba_layer, model._mlp, diff_ops.diff_attention_combine,
         engine._make_arenas) = sound
        cfg.pop("planted", None)
        cfg["window"] = window


def judge(config: dict, traffic: dict, seed: int, prompt_lens, n_new: int,
          faults, log=lambda msg: None) -> dict:
    """``check_outputs`` on the answers of the cell's own server, once per
    entry of ``faults`` (None: the program as it is)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.builders import phi4flash as builder
    from benchmarks.lib import arrivals, serve_loop
    from benchmarks.references import phi4flash as reference

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
    ap.add_argument("--config", default="phi4_mini_flash")
    ap.add_argument("--traffic", default="reason_ctx_closed_c32")
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--prompts", default="4096,6100",
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

    faults = [n for n in (None,) + CONTROLS + READINGS
              if not args.only or (n or "sound") in args.only.split(",")]
    lens = [int(n) for n in args.prompts.split(",")]
    got = judge(_load("configs", args.config),
                _load("traffic", args.traffic), args.seed, lens, args.new,
                faults, log)
    verdict = (got.get("sound", {"ok": True})["ok"]
               and not any(got[n]["ok"] for n in CONTROLS if n in got))
    print(json.dumps(dict(got, prompts=lens, new=args.new,
                          verdict=bool(verdict))))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
