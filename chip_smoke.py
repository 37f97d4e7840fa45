"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls
(``import mxnet_tpu as mx``, the model zoo, ``parallel.TrainStep``,
``serving.Server``), on ONE process and ONE TPU chip:

* phase ``train`` — BERT-base at its published size (12 layers, 768 units,
  12 heads, vocab 30522, bf16, batch 32 x sequence 512) with the fused CE
  head, 5 Adam steps on seeded data;
* phase ``serve`` — ``serving.Server`` over a ``LlamaModel`` at the
  Llama-3-8B widths (units 4096, hidden 14336, 32 heads, 8 KV heads, head
  dim 128, vocab 128256, rope theta 500000), bf16, depth cut so weights and
  the KV arena fit 16 GB; 8 concurrent generate requests, each compared
  against one plain forward of the same net.

``--chips 4`` runs ONLY the data-parallel phase and what it is compared
with: the ``train`` model under ``TrainStep(mesh dp=4)`` against ``dp=1``
in the same process, then a Gluon ``Trainer(kvstore="tpu_sync")`` over
four contexts against one.

Each phase prints one JSON object; the LAST line of stdout is
``{"ok": true, "device": {...}}`` and is printed only when every check
held on a TPU. Anything else — no TPU, a phase raising, a check failing —
ends the run at once with a non-zero exit code. ``--tiny`` shrinks every
size for a CPU rehearsal of the control flow and never prints the last
line: a CPU run is not a chip run. Timings here are smoke timings (host
clock around ``asnumpy()``), not a benchmark.
"""
from __future__ import annotations

import argparse
import faulthandler
import gc
import json
import os
import sys
import threading
import time

import numpy as np


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


_T0 = time.perf_counter()


def note(what: str) -> None:
    """Progress on stderr, so a run that dies says how far it got."""
    print(f"[chip_smoke +{time.perf_counter() - _T0:6.1f}s] {what}",
          file=sys.stderr, flush=True)


def metric(name: str, **labels) -> float:
    """Sum of a telemetry counter's samples matching ``labels``."""
    from mxnet_tpu import telemetry

    fam = telemetry.snapshot()["metrics"].get(name)
    return sum(s["value"] for s in (fam["samples"] if fam else ())
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def pallas_counts() -> dict:
    from mxnet_tpu import telemetry

    fam = telemetry.snapshot()["metrics"].get("mxnet_pallas_dispatch_total")
    return {s["labels"]["kernel"]: int(s["value"])
            for s in (fam["samples"] if fam else ())}


def platforms_of(arr) -> set:
    return {d.platform for d in arr.devices()}


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def bert_config(tiny: bool) -> dict:
    if tiny:
        return dict(net=dict(vocab_size=1000, num_layers=2, units=128,
                             hidden_size=512, num_heads=2, chunk=500),
                    batch=4, seq=128)
    # published BERT-base; the CE head's vocabulary in 6 chunks of 5120
    return dict(net=dict(vocab_size=30522, num_layers=12, units=768,
                         hidden_size=3072, num_heads=12, chunk=5120),
                batch=32, seq=512)


def llama_config(tiny: bool) -> dict:
    if tiny:
        return dict(net=dict(vocab_size=512, num_layers=2, units=256,
                             hidden_size=512, num_heads=2, num_kv_heads=1,
                             rope_theta=500000.0),
                    prompt_lens=(5, 9, 14, 16, 20, 27, 31, 32),
                    len_buckets=(16, 32), new_tokens=8, cut={})
    # Llama-3-8B widths (its published config.json); depth is
    # the one thing cut: 32 layers of bf16 weights are 16 GB on their own
    return dict(net=dict(vocab_size=128256, num_layers=8, units=4096,
                         hidden_size=14336, num_heads=32, num_kv_heads=8,
                         rope_theta=500000.0),
                prompt_lens=(64, 100, 128, 200, 256, 320, 448, 512),
                len_buckets=(64, 128, 256, 512), new_tokens=32,
                cut={"num_layers": "32 -> 8"})


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def build_bert_step(cfg, seed, ctx, mesh):
    """BERT-base + fused CE head under TrainStep."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo.nlp import bert

    mx.random.seed(seed)
    net = bert.BERTForPretrainFused(dropout=0.1, **cfg["net"])
    net.initialize(ctx=ctx)
    net.cast("bfloat16")
    step = par.TrainStep(
        net, lambda outs, *a: outs, "adam", mesh=mesh, loss_only=True,
        optimizer_params={"learning_rate": 1e-4, "multi_precision": True})
    return net, step


def bert_batch(cfg, seed, ctx):
    import mxnet_tpu as mx

    rs = np.random.RandomState(seed)
    vocab = cfg["net"]["vocab_size"]
    shape = (cfg["batch"], cfg["seq"])
    tokens = mx.nd.array(rs.randint(0, vocab, shape).astype(np.int32),
                         ctx=ctx)
    labels = mx.nd.array(rs.randint(0, vocab, shape).astype(np.int32),
                         ctx=ctx)
    return tokens, labels


def run_steps(step, batch, n):
    """``n`` steps on one batch: per-step loss, host-clock seconds around
    ``asnumpy()``, and the last loss array."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss, _ = step(batch, ())
        losses.append(float(loss.asnumpy()))
        secs.append(time.perf_counter() - t0)
    return losses, secs, loss


def phase_train(args, on_chip: bool) -> None:
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par

    cfg = bert_config(args.tiny)
    ctx = mx.tpu(0)
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    net, step = build_bert_step(cfg, args.seed, ctx, mesh)
    batch = bert_batch(cfg, args.seed, ctx)
    note("train: net built, batch staged")

    def train_step_misses():
        return metric("mxnet_jit_cache_total", cache="train_step",
                      result="miss")

    # deferred parameters draw their values at the first forward
    mx.random.seed(args.seed)
    losses, secs, _ = run_steps(step, batch, 2)
    note("train: two steps done (compile included)")
    misses_after_2 = train_step_misses()
    more, more_secs, loss = run_steps(step, batch, 3)
    losses += more
    secs += more_secs

    check(all(np.isfinite(losses)), f"train: non-finite loss in {losses}")
    check(losses[4] < losses[0],
          f"train: loss did not fall over 5 steps: {losses}")
    check(train_step_misses() == misses_after_2,
          "train: the compilation service recorded a train_step miss "
          f"after step 2 ({misses_after_2} -> {train_step_misses()})")
    counts = pallas_counts()
    if on_chip:
        plats = set()
        for p in net.collect_params().values():
            plats |= platforms_of(p.data().data)
        check(plats == {"tpu"}, f"train: parameters live on {plats}")
        check(platforms_of(loss.data) == {"tpu"},
              f"train: the loss lives on {platforms_of(loss.data)}")
        for kernel in ("flash_attention", "fused_layer_norm",
                       "fused_bias_gelu"):
            for name in (kernel, kernel + "_bwd"):
                check(counts.get(name, 0) > 0,
                      f"train: Pallas kernel {name} was never routed to "
                      f"(mxnet_pallas_dispatch_total = {counts})")
        check(counts.get("fused_opt_sweep", 0) == 0,
              "train: the packed optimizer sweep ran inside the jitted "
              "step, where it re-packs every parameter each step and "
              f"collapses no dispatch ({counts})")
        check("tpu_custom_call" in step.compiled(batch, ()).as_text(),
              "train: no tpu_custom_call in the compiled step")
    emit({"phase": "train", "model": "bert-base", **cfg["net"],
          "batch": cfg["batch"], "seq": cfg["seq"], "dtype": "bfloat16",
          "losses": [round(v, 4) for v in losses],
          "pallas_dispatch": counts,
          "train_step_misses": int(misses_after_2),
          "smoke_first_step_with_compile_s": round(secs[0], 2),
          "smoke_steady_step_s": round(float(np.median(secs[2:])), 4)})


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def phase_serve(args, on_chip: bool) -> None:
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo.nlp import LlamaModel

    cfg = llama_config(args.tiny)
    ctx = mx.tpu(0)
    new = cfg["new_tokens"]
    lens = cfg["prompt_lens"]
    page = 16
    # every stream's whole budget, plus the reserved scratch page
    decode_pages = len(lens) * -(-(max(lens) + new) // page) + 1
    threads_before = set(threading.enumerate())

    t0 = time.perf_counter()
    mx.random.seed(args.seed)
    net = LlamaModel(**cfg["net"])
    # a server holds no gradients: without this every weight gets a
    # gradient buffer of its own size on the device
    net.collect_params().setattr("grad_req", "null")
    net.cast("bfloat16")
    # Xavier keeps activations O(1) through the stack, the regime a
    # trained model runs in; the default U(-0.07, 0.07) at width 4096
    # saturates every softmax
    net.initialize(mx.init.Xavier(), ctx=ctx)
    note("serve: known-shape parameters initialised")
    rs = np.random.RandomState(args.seed)
    prompts = [rs.randint(0, cfg["net"]["vocab_size"], (n,)).astype(np.int32)
               for n in lens]

    srv = serving.Server(
        net, batch_buckets=(len(prompts),), dtype="int32", ctx=ctx,
        slo_ms=60000.0, decode_pages=decode_pages,
        page_size=page, len_buckets=cfg["len_buckets"],
        max_generate_tokens=max(lens) + new, name="chip_smoke")
    srv.start()
    build_s = time.perf_counter() - t0
    note("serve: server started (deferred parameters settled, arena built)")
    try:
        t0 = time.perf_counter()
        handles = [srv.submit_generate(p, new) for p in prompts]
        outs = [np.asarray(h.result(timeout=900)) for h in handles]
        gen_s = time.perf_counter() - t0
        stats = srv.stats()
    finally:
        srv.stop()
    note("serve: all requests answered, server stopped")
    left = [t.name for t in set(threading.enumerate()) - threads_before
            if t.is_alive()]
    check(not left, f"serve: threads left after stop(): {left}")
    check(all(o.shape == (new,) for o in outs),
          f"serve: token counts {[o.shape for o in outs]}, wanted {new}")

    # the reference: ONE plain forward of the same net over prompt +
    # generated tokens, right-padded to one length (causal attention
    # makes suffix padding transparent). Logit i-1 scores token i.
    total = max(lens) + new
    full = np.zeros((len(prompts), total), np.int32)
    for r, (p, o) in enumerate(zip(prompts, outs)):
        full[r, :p.size] = p
        full[r, p.size:p.size + new] = o
    logits = net(mx.nd.array(full, ctx=ctx, dtype="int32"))
    note("serve: reference forward dispatched")
    check(logits.shape == (len(prompts), total, cfg["net"]["vocab_size"]),
          f"serve: reference logits shape {logits.shape}")
    worst = 0.0
    for r, (p, o) in enumerate(zip(prompts, outs)):
        ref = logits[r, p.size - 1:p.size - 1 + new].asnumpy() \
            .astype(np.float32)                            # (new, vocab)
        check(np.isfinite(ref).all(), f"serve: reference row {r} not finite")
        top = ref.max(axis=1)
        # bf16 carries 8 bits: 2**-5 of the largest logit is 8 of its ulps
        tol = np.abs(ref).max(axis=1) * 2.0 ** -5
        gap = (top - ref[np.arange(new), o]) / tol
        worst = max(worst, float(gap.max()))
        check((gap <= 1.0).all(),
              f"serve: request {r} (prompt {p.size}): generated token's "
              f"reference logit is {float(gap.max()):.2f} tolerances below "
              f"the top logit at step {int(gap.argmax())}")

    counts = pallas_counts()
    n_layers = cfg["net"]["num_layers"]
    kv_heads = cfg["net"]["num_kv_heads"]
    head_dim = cfg["net"]["units"] // cfg["net"]["num_heads"]
    arena_shape = (decode_pages, page, kv_heads * head_dim)
    if on_chip:
        arenas = [a for a in jax.live_arrays() if a.shape == arena_shape]
        check(len(arenas) >= 2 * n_layers,
              f"serve: {len(arenas)} KV arenas of shape {arena_shape} among "
              f"the live device arrays, a key and a value one for each of "
              f"{n_layers} layers expected")
        for a in arenas:
            check(platforms_of(a) == {"tpu"},
                  f"serve: a KV arena lives on {platforms_of(a)}")
        check(platforms_of(logits.data) == {"tpu"},
              f"serve: reference logits live on {platforms_of(logits.data)}")
        strays = [(a.shape, str(a.dtype)) for a in jax.live_arrays()
                  if a.nbytes >= (1 << 20) and platforms_of(a) != {"tpu"}]
        check(not strays, f"serve: arrays of 1 MiB+ off the TPU: {strays}")
        check(counts.get("paged_attention", 0) > 0,
              "serve: the paged decode kernel was never routed to "
              f"(mxnet_pallas_dispatch_total = {counts})")
    emit({"phase": "serve", "model": "llama-3-8b widths", **cfg["net"],
          "dtype": "bfloat16", "cut": cfg["cut"],
          "requests": len(prompts), "prompt_lens": list(lens),
          "new_tokens": new, "decode_pages": decode_pages,
          "tokens_served": int(stats["tokens"]),
          "worst_gap_in_tolerances": round(worst, 3),
          "pallas_dispatch": counts,
          "smoke_build_and_start_s": round(build_s, 2),
          "smoke_generate_all_s": round(gen_s, 2)})


# ---------------------------------------------------------------------------
# option: --chips 4
# ---------------------------------------------------------------------------

def phase_data_parallel(args, on_chip: bool) -> None:
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par

    cfg = bert_config(args.tiny)
    devices = jax.devices()[:4]
    n_steps = 3
    runs = {}
    for dp in (4, 1):
        mesh = par.make_mesh({"dp": dp}, devices=devices[:dp])
        net, step = build_bert_step(cfg, args.seed, mx.tpu(0), mesh)
        batch = bert_batch(cfg, args.seed, mx.tpu(0))
        mx.random.seed(args.seed)
        losses, secs, _ = run_steps(step, batch, n_steps)
        note(f"dp={dp}: {n_steps} steps done, losses {losses}")
        check(all(np.isfinite(losses)), f"dp={dp}: losses {losses}")
        text = step.compiled(batch, ()).as_text()
        if dp == 4:
            step.stage_batch(batch, ())
            shard_devs = {s.device for s in batch[0].data.addressable_shards}
            shard_shapes = {s.data.shape
                            for s in batch[0].data.addressable_shards}
            check(len(shard_devs) == 4,
                  f"dp=4: batch shards sit on {shard_devs}")
            check(shard_shapes == {(cfg["batch"] // 4, cfg["seq"])},
                  f"dp=4: batch shard shapes {shard_shapes}")
            for p in net.collect_params().values():
                arr = p.data().data
                devs = {s.device for s in arr.addressable_shards}
                check(len(devs) == 4 and all(
                    s.data.shape == arr.shape
                    for s in arr.addressable_shards),
                    f"dp=4: {p.name} is not replicated on 4 devices "
                    f"({arr.sharding})")
            if on_chip:
                check({d.platform for d in shard_devs} == {"tpu"},
                      f"dp=4: shard devices {shard_devs}")
            check("all-reduce" in text,
                  "dp=4: no all-reduce in the compiled step")
        runs[dp] = {"losses": losses, "all_reduce": text.count("all-reduce"),
                    "tpu_custom_call": text.count("tpu_custom_call"),
                    "first_step_with_compile_s": round(secs[0], 2),
                    "steady_step_s": round(secs[-1], 4)}
        del net, step, batch
        gc.collect()
    # bf16 carries 8 bits: 2**-7 of the loss is 2 of its ulps
    for a, b in zip(runs[4]["losses"], runs[1]["losses"]):
        check(abs(a - b) <= abs(b) * 2.0 ** -7,
              f"dp=4 and dp=1 losses differ: {runs[4]['losses']} vs "
              f"{runs[1]['losses']}")
    emit({"phase": "train_dp4", "model": "bert-base", **cfg["net"],
          "global_batch": cfg["batch"], "seq": cfg["seq"],
          "steps": n_steps,
          "dp4": runs[4], "dp1": runs[1]})

    kv = trainer_losses([mx.tpu(i) for i in range(4)], args.seed)
    one = trainer_losses([mx.tpu(0)], args.seed)
    check(np.allclose(kv["losses"], one["losses"], rtol=1e-5, atol=1e-6),
          f"tpu_sync over 4 contexts {kv['losses']} vs 1 {one['losses']}")
    for name in kv["weights"]:
        check(np.allclose(kv["weights"][name], one["weights"][name],
                          rtol=1e-5, atol=1e-6),
              f"tpu_sync: weight {name} differs from the one-context run")
    check(len(kv["devices"]) == 4,
          f"tpu_sync: parameter copies sit on {kv['devices']}")
    emit({"phase": "trainer_tpu_sync", "contexts": 4,
          "losses_4ctx": kv["losses"], "losses_1ctx": one["losses"],
          "devices": sorted(kv["devices"]),
          "collective_dispatches": kv["collectives"]})


def trainer_losses(ctxs, seed, steps=3, batch=32):
    """``steps`` of Gluon ``Trainer(kvstore="tpu_sync")`` on a small Dense
    net split over ``ctxs``; every shape explicit, weights set by name."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(64, in_units=32, activation="relu"))
    net.add(nn.Dense(8, in_units=64))
    net.initialize(ctx=ctxs)
    rs = np.random.RandomState(seed)
    for _, p in sorted(net._collect_params_with_prefix().items()):
        p.set_data(mx.nd.array(
            rs.uniform(-0.3, 0.3, p.shape).astype(np.float32)))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="tpu_sync")
    loss_fn = gluon.loss.L2Loss()
    before = metric("mxnet_kvstore_collective_dispatch_total")
    per = batch // len(ctxs)
    losses = []
    for s in range(steps):
        x = rs.uniform(-1, 1, (batch, 32)).astype(np.float32)
        y = rs.uniform(-1, 1, (batch, 8)).astype(np.float32)
        xs = [mx.nd.array(x[i * per:(i + 1) * per], ctx=c)
              for i, c in enumerate(ctxs)]
        ys = [mx.nd.array(y[i * per:(i + 1) * per], ctx=c)
              for i, c in enumerate(ctxs)]
        with autograd.record():
            ls = [loss_fn(net(a), b) for a, b in zip(xs, ys)]
            for l in ls:
                l.backward()
        trainer.step(batch)
        losses.append(sum(float(l.sum().asnumpy()) for l in ls))
    devices = set()
    for p in net.collect_params().values():
        for arr in p.list_data():
            devices |= {str(d) for d in arr.data.devices()}
    return {"losses": losses, "devices": devices,
            "collectives": int(
                metric("mxnet_kvstore_collective_dispatch_total") - before),
            "weights": {k: p.data(ctxs[0]).asnumpy() for k, p in
                        net._collect_params_with_prefix().items()}}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal of the control flow at toy sizes; "
                         "never prints the final ok line")
    args = ap.parse_args(argv)
    # a compiler or runtime abort names the python frame it came from
    faulthandler.enable()

    # the fused-layer routing on, as the benchmark's BERT builder sets it
    os.environ.setdefault("MXNET_PALLAS_FUSED", "1")
    import jax

    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    if not on_chip and not args.tiny:
        print(f"chip_smoke.py needs a TPU; jax.devices() = {devices}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py --chips {args.chips} needs {args.chips} "
              f"devices; jax.devices() = {devices}", file=sys.stderr)
        return 1

    import mxnet_tpu as mx  # noqa: F401  (sets up the compile cache)
    from mxnet_tpu import telemetry
    from mxnet_tpu.compiler import persistent

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    emit({"phase": "start", "tiny": args.tiny, "chips": args.chips,
          "seed": args.seed, "devices": [str(d) for d in devices],
          "compile_cache": persistent.stats()})
    telemetry.enable()
    try:
        if args.chips == 4:
            phase_data_parallel(args, on_chip)
        else:
            phase_train(args, on_chip)
            gc.collect()
            phase_serve(args, on_chip)
    finally:
        telemetry.disable()
    emit({"phase": "end", "compile_cache": persistent.stats(),
          "persistent_cache_events": cache_events})
    if not on_chip:
        print("chip_smoke.py --tiny: the rehearsal ran to its end on "
              f"{devices[0].platform}; a CPU run is not a chip run, so no "
              "ok line", file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
