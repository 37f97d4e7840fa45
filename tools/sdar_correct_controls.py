"""What the benchmark's ``correct`` can see of SDAR's block decoding: the
cell's own server and engine (``benchmarks/builders/sdar_moe.py``, the
server group of the cell's traffic file, the paged kernel with the block
folded into the head group, the grouped matmuls, the 128-stream bucket
with its padding rows) answer a few requests, and
``benchmarks/drivers/closed_loop_blocks.py::check_blocks`` - the
comparison that decides ``correct`` in the cell, with its own limit -
judges them. Once as the program is, which has to come out ``ok``, and
once with each of these planted:

* ``lower_precision``: the nearest precision below the configuration's:
  the residual stream rounded to an 8-bit float (e4m3) after every layer,
  where the configuration's products take bfloat16.
* ``causal_in_block``: a block step's queries see the keys at or before
  their own position only (the causal mask inside the block).
* ``stale_keys``: a block step attends to the cache as it was BEFORE the
  step wrote the block's keys and values: the block's own keys are those
  of the step before (of whatever the pages held, in a block's first).
* ``skipped_commit``: the commit forward is not run, so the cache keeps a
  finished block as its last denoising step saw it (its last position
  still the mask token).

Each has to come out NOT ok (by the tokens, limit (a), or by the
positions, limit (b)), or be named in ``PINNED`` with the tier-1 test that
holds it where this comparison cannot see it.

    chiprun -- python3 tools/sdar_correct_controls.py
    JAX_PLATFORMS=cpu python3 tools/sdar_correct_controls.py \\
        --config tiny_sdar_moe --traffic tiny_blockgen_closed \\
        --prompts 13,22 --new 12

Exit code 0 only if the sound program is ok and every control is not ok
or pinned. Prints one JSON line with every reading (1.0 is the limit).
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

CONTROLS = ("lower_precision", "causal_in_block", "stale_keys",
            "skipped_commit")
# a control the comparison may not see at some size, and what holds it
PINNED = {
    "causal_in_block": "tests/test_sdar_moe.py::"
                       "test_block_one_is_the_causal_mask_exactly",
    "stale_keys": "tests/test_sdar_moe.py::"
                  "test_prefill_then_block_steps_match_the_references_loop",
    "skipped_commit": "tests/test_sdar_moe.py::"
                      "test_a_skipped_commit_changes_the_next_blocks_logits",
}


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
    import numpy as np

    from mxnet_tpu.gluon.model_zoo.nlp import sdar_moe as model
    from mxnet_tpu.ops import attention

    engine = model.SdarMoeDecodeEngine
    sound = (model._layer_forward, model._scatter_rows,
             attention.paged_attention, engine.decode_block)
    layer_forward, scatter_rows, paged_attention, decode_block = sound
    cfg = net._decode_cfg
    bk = cfg["block_length"]

    def rounded(*args, **kw):
        # a convert there and back is one the compiler may drop (excess
        # precision); reduce_precision is not
        x, *rest = layer_forward(*args, **kw)
        return (jax.lax.reduce_precision(x, 4, 3), *rest)

    def causal(query, *args, block=1, **kw):
        return paged_attention(query, *args, block=1, **kw)

    before = []

    def remembering(arena, rows, page, offset):
        before.append(arena)
        return scatter_rows(arena, rows, page, offset)

    def stale(query, k_arena, v_arena, *args, **kw):
        if query.shape[2] == bk:        # a block step: the arenas before
            kv = k_arena.shape[-2:]
            v_old, k_old = before.pop(), before.pop()
            k_arena = k_old[..., :kv[0] * kv[1]].reshape(-1, *kv)
            v_arena = v_old[..., :kv[0] * kv[1]].reshape(-1, *kv)
        else:
            del before[:]
        return paged_attention(query, k_arena, v_arena, *args, **kw)

    def no_commit(self, tokens, lengths, page_table, quota):
        # a row with nothing to unmask (a commit; a padding row is one
        # already) becomes a padding row: it writes nothing
        lengths = np.where(np.asarray(quota) > 0, lengths, 0)
        out = decode_block(self, tokens, lengths, page_table, quota)
        return np.where(np.asarray(quota)[:, None] > 0, out, tokens)

    if fault is not None:
        cfg["planted"] = fault
    if fault == "lower_precision":
        model._layer_forward = rounded
    elif fault == "causal_in_block":
        attention.paged_attention = causal
    elif fault == "stale_keys":
        model._scatter_rows = remembering
        attention.paged_attention = stale
    elif fault == "skipped_commit":
        engine.decode_block = no_commit
    try:
        yield
    finally:
        (model._layer_forward, model._scatter_rows,
         attention.paged_attention, engine.decode_block) = sound
        cfg.pop("planted", None)


def judge(config: dict, traffic: dict, seed: int, prompt_lens, n_new: int,
          faults, log=lambda msg: None) -> dict:
    """``check_blocks`` on the answers of the cell's own server, once per
    entry of ``faults`` (None: the program as it is)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.builders import sdar_moe as builder
    from benchmarks.drivers import closed_loop_blocks
    from benchmarks.lib import arrivals, serve_loop
    from benchmarks.references import sdar_moe as reference

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
        check = closed_loop_blocks.check_blocks(run, weights, gen.records,
                                                len(prompts))
        errors = [repr(r.error) for r in gen.records if r.error]
        if errors:
            check = dict(check, ok=False, errors=errors)
        # how many different tokens an answer holds: one would mean the
        # picked token no longer depends on the layers
        check["distinct_tokens"] = [
            int(np.unique(r.handle.result(timeout=1.0)).size)
            for r in gen.records if r.error is None]
        log(f"{fault or 'sound'}: {check}")
        got[fault or "sound"] = check
    return got


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sdar_30b_a3b_l6")
    ap.add_argument("--traffic", default="blockgen_closed_c128")
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--prompts", default="131,384,66",
                    help="the requests' prompt lengths")
    ap.add_argument("--new", type=int, default=256,
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
    faults = [n for n in (None,) + CONTROLS
              if not args.only or (n or "sound") in args.only.split(",")]
    lens = [int(n) for n in args.prompts.split(",")]
    got = judge(config, _load("traffic", args.traffic), args.seed, lens,
                args.new, faults, log)
    unseen = [n for n in CONTROLS if n in got and got[n]["ok"]]
    verdict = (got.get("sound", {"ok": True})["ok"]
               and all(n in PINNED for n in unseen))
    print(json.dumps(dict(got, prompts=lens, new=args.new, unseen=unseen,
                          pinned={n: PINNED[n] for n in unseen
                                  if n in PINNED},
                          verdict=bool(verdict))))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
