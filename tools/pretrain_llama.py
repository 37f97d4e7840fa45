"""Llama pretraining driver — the stretch config (BASELINE.json config[4]).

End-to-end causal-LM pretraining on a TPU mesh with the framework's fused
TrainStep: forward + CE loss + backward + AdamW-family update + the
GSPMD-inserted collectives in ONE compiled executable per step.

    # single chip, 1B-ish proxy, synthetic tokens
    python tools/pretrain_llama.py --config proxy1b --steps 20

    # 8-device virtual mesh (tp x dp), tiny config, real shardings
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/pretrain_llama.py --config tiny --mesh dp=2,tp=2,sp=2

    # full Llama-3-8B dims, AOT compile only (no weights materialized):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/pretrain_llama.py --config 8b --mesh dp=2,tp=4 \
        --compile-only

Data: ``--data synthetic`` (default) draws random token ids host-side once
and reuses the staged device batch (benchmark methodology, PERF_HISTORY.md);
``--data <path.rec>`` streams token records through io.RecordIter.
Checkpointing: ``--save-dir`` writes net .params + trainer state every
``--save-every`` steps via the framework's V3 checkpoint format.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIGS = {
    # test-sized
    "tiny": dict(vocab_size=256, num_layers=2, units=64, hidden_size=128,
                 num_heads=4, num_kv_heads=2, rope_theta=10000.0),
    # ~0.7B single-chip proxy of the 8B recipe (same code path, same
    # ratios: GQA 2:1 over d=128 heads, SwiGLU ~3.5x, untied head)
    "proxy1b": dict(vocab_size=32768, num_layers=10, units=2048,
                    hidden_size=7168, num_heads=16, num_kv_heads=8,
                    rope_theta=500000.0),
    # Llama-3-8B
    "8b": dict(vocab_size=128256, num_layers=32, units=4096,
               hidden_size=14336, num_heads=32, num_kv_heads=8,
               rope_theta=500000.0),
}


def param_count(cfg):
    u, h, v = cfg["units"], cfg["hidden_size"], cfg["vocab_size"]
    d = u // cfg["num_heads"]
    kv = cfg["num_kv_heads"] * d
    per_layer = u * u + u * 2 * kv + u * u + 2 * u * h + h * u + 2 * u
    return cfg["num_layers"] * per_layer + 2 * v * u + u


def parse_mesh(spec):
    axes = {}
    if spec:
        for part in spec.split(","):
            k, v = part.split("=")
            axes[k.strip()] = int(v)
    return axes or {"dp": 1}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="tiny", choices=sorted(CONFIGS))
    ap.add_argument("--mesh", default="", help="e.g. dp=2,tp=2,sp=2")
    ap.add_argument("--batch", type=int, default=None, help="global batch")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--weight-decay", type=float, default=0.1)
    ap.add_argument("--remat", nargs="?", const=True, default=None,
                    help="enable remat; optional value picks the policy "
                         "('full' save-nothing, 'dots' keep matmul outputs)")
    ap.add_argument("--no-remat", dest="remat", action="store_false")
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--save-dir", default=None)
    ap.add_argument("--save-every", type=int, default=1000)
    ap.add_argument("--no-fused-ce", dest="fused_ce",
                    action="store_false", default=True,
                    help="materialize logits + separate CE instead of "
                         "the fused projection+CE head")
    ap.add_argument("--compile-only", action="store_true",
                    help="AOT lower+compile the sharded train step without "
                         "materializing weights (validates the 8B recipe "
                         "on hosts that cannot hold 8B params)")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.callback import device_peak_flops
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.nlp.llama import (
        LlamaModel, llama_sharding_rules)

    cfg = dict(CONFIGS[args.config])
    n_params = param_count(cfg)
    axes = parse_mesh(args.mesh)
    seq = args.seq or (2048 if args.config != "tiny" else 128)
    batch = args.batch or max(2 * axes.get("dp", 1),
                              4 if args.config == "proxy1b" else 2)
    remat = args.remat if args.remat is not None else args.config != "tiny"

    mesh = par.make_mesh(axes)
    rules = llama_sharding_rules(tp_axis="tp") if "tp" in axes else None
    ring_axis = "sp" if "sp" in axes else None

    net = LlamaModel(**cfg, remat=remat, ring_axis=ring_axis,
                     fused_ce=args.fused_ce)
    loss_fn = (_FusedLossPassthrough() if args.fused_ce
               else _CausalLMLoss(gloss))

    if args.compile_only:
        return _compile_only(jax, mx, par, net, loss_fn, mesh, rules,
                             batch, seq, cfg, args, n_params)

    net.initialize()
    net.cast(args.dtype)

    step = par.TrainStep(
        net, loss_fn, "adamw", mesh=mesh, rules=rules,
        batch_axis=("dp",), seq_axis=("sp" if "sp" in axes else None),
        loss_only=True,
        optimizer_params={"learning_rate": args.lr,
                          "wd": args.weight_decay,
                          "beta1": 0.9, "beta2": 0.95,
                          "multi_precision": True})

    data_iter = _make_data(mx, args.data, batch, seq, cfg["vocab_size"],
                           int_labels=args.fused_ce)
    tokens, labels = next(data_iter)

    def run_step(tokens, labels):
        if args.fused_ce:
            return step((tokens, labels), ())
        return step(tokens, labels)

    t0 = time.time()
    loss, _ = run_step(tokens, labels)
    loss_val = float(loss.asnumpy())
    print(f"step 1: loss {loss_val:.4f} "
          f"(compile+run {time.time() - t0:.0f}s; {n_params / 1e6:.0f}M "
          f"params, mesh {dict(zip(mesh.axis_names, mesh.devices.shape))})",
          flush=True)
    if args.data == "synthetic":
        if args.fused_ce:
            step.stage_batch((tokens, labels), ())
        else:
            step.stage_batch(tokens, labels)

    # Throughput methodology: async dispatch means per-step host timers
    # measure DISPATCH, not device time, and the final fetch's wait
    # carries EVERY queued step's device time — a trailing window that
    # doesn't start from a synced point mis-attributes earlier steps'
    # device work into its own denominator (round 4 found the round-3
    # proxy number undercounted ~2x this way; the jax.profiler trace
    # shows back-to-back 575 ms device steps). So: sync (fetch) at the
    # steady-window boundary, wall-time the remaining steps as one span
    # ending in a fetch — the same synced-span method bench.py uses.
    times = []
    sync_at = min(max(2, args.steps // 2), max(args.steps - 1, 1))
    t_span = None
    span_dt = None
    span_steps = 0
    save_s = 0.0  # checkpoint-write time inside the span, excluded below
    for i in range(2, args.steps + 1):
        if args.data != "synthetic":
            tokens, labels = next(data_iter)
        t0 = time.time()
        loss, _ = run_step(tokens, labels)
        if i == sync_at:
            loss_val = float(loss.asnumpy())  # drain the dispatch queue
            t_span = time.time()
        elif i == args.steps or i % 20 == 0:
            loss_val = float(loss.asnumpy())
        if i > sync_at:
            span_steps += 1
        if i == args.steps and t_span is not None:
            # span ends HERE, at the final fetch — checkpoint saves must
            # not leak into the throughput denominator
            span_dt = time.time() - t_span - save_s
        times.append(time.time() - t0)
        if args.save_dir and i % args.save_every == 0:
            t_save = time.time()
            _save(net, step, args.save_dir, i)
            if t_span is not None and i < args.steps:
                save_s += time.time() - t_save
        if i == args.steps or i % 20 == 0:
            tok_s = batch * seq / (sum(times[-10:]) / len(times[-10:]))
            print(f"step {i}: loss {loss_val:.4f} tokens/s {tok_s:.0f} "
                  f"(rolling dispatch-window; final number is synced-span)",
                  flush=True)
    if args.save_dir and args.steps % args.save_every != 0:
        _save(net, step, args.save_dir, args.steps)

    peak = device_peak_flops()
    if span_dt is not None and span_steps > 0:
        tok_s = batch * seq * span_steps / span_dt
    else:  # --steps 1: only the compile step ran; t0 is its dispatch
        tok_s = batch * seq / max(time.time() - t0, 1e-9)
    mfu = 6.0 * n_params * tok_s / peak if peak else None
    print(json.dumps({
        "config": args.config, "params": n_params, "tokens_per_sec":
        round(tok_s, 1), "mfu": round(mfu, 4) if mfu else None,
        "final_loss": loss_val}))
    return 0


class _FusedLossPassthrough:
    """fused_ce=True: the model already returns per-token loss."""

    def __call__(self, outs, *a):
        return outs[0] if isinstance(outs, (list, tuple)) else outs


class _CausalLMLoss:
    """Next-token CE over (B, L, vocab) logits (shift-by-one)."""

    def __init__(self, gloss):
        self._l = gloss.SoftmaxCrossEntropyLoss()

    def __call__(self, outs, labels):
        logits = outs[0] if isinstance(outs, (list, tuple)) else outs
        b, l, v = logits.shape
        return self._l(logits.reshape(-1, v), labels.reshape(-1))


def _make_data(mx, source, batch, seq, vocab, int_labels=False):
    lab_dtype = np.int32 if int_labels else np.float32
    if source == "synthetic":
        rs = np.random.RandomState(0)
        toks = rs.randint(0, vocab, (batch, seq + 1))

        def gen():
            while True:
                yield (mx.nd.array(toks[:, :-1].astype(np.int32)),
                       mx.nd.array(toks[:, 1:].astype(lab_dtype)))
        return gen()

    from mxnet_tpu import recordio

    def gen_rec():
        while True:
            reader = recordio.MXRecordIO(source, "r")
            buf_t, buf_l = [], []
            while True:
                rec = reader.read()
                if rec is None:
                    break
                arr = np.frombuffer(rec, dtype=np.int32)
                if arr.shape[0] < seq + 1:
                    continue
                buf_t.append(arr[:seq])
                buf_l.append(arr[1:seq + 1])
                if len(buf_t) == batch:
                    yield (mx.nd.array(np.stack(buf_t)),
                           mx.nd.array(np.stack(buf_l).astype(lab_dtype)))
                    buf_t, buf_l = [], []
            reader.close()
    return gen_rec()


def _save(net, step, save_dir, i):
    os.makedirs(save_dir, exist_ok=True)
    net.save_parameters(os.path.join(save_dir, f"llama-{i:07d}.params"))
    # optimizer states via the kvstore-free trainer-state format
    import pickle

    states = [s.asnumpy() for s in step._state_leaf_nds]
    with open(os.path.join(save_dir, f"llama-{i:07d}.states"), "wb") as f:
        pickle.dump({"num_update": step.optimizer.num_update,
                     "leaves": states}, f)
    print(f"saved checkpoint @ step {i} -> {save_dir}", flush=True)


def _compile_only(jax, mx, par, net, loss_fn, mesh, rules, batch, seq, cfg,
                  args, n_params):
    """AOT-compile the full sharded train step on abstract weights.

    Validates that the 8B recipe (shardings x remat x fused TrainStep)
    lowers and compiles for the target mesh without needing a host that
    can hold the weights: the net "initializes" under
    ``gluon.parameter.abstract_init()`` and ``TrainStep.aot_compile``
    runs the normal settle/state/build/lower path on ShapeDtypeStructs.
    """
    import jax.numpy as jnp

    from mxnet_tpu.gluon.parameter import abstract_init

    t0 = time.time()
    with abstract_init():
        net.initialize()
        for p in net.collect_params().values():
            p._dtype = args.dtype
        step = par.TrainStep(
            net, loss_fn, "adamw", mesh=mesh, rules=rules,
            batch_axis=("dp",), seq_axis=("sp" if "sp" in
                                          mesh.axis_names else None),
            loss_only=True,
            optimizer_params={"learning_rate": args.lr,
                              "wd": args.weight_decay,
                              "beta1": 0.9, "beta2": 0.95,
                              "multi_precision": True})
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        if args.fused_ce:
            # fused head: labels are the model's second DATA input
            lbl = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
            compiled = step.aot_compile((tok, lbl), ())
        else:
            lbl = jax.ShapeDtypeStruct((batch, seq), jnp.float32)
            compiled = step.aot_compile(tok, lbl)
    try:
        mem = compiled.memory_analysis()
        arg_b = getattr(mem, "argument_size_in_bytes", None)
        tmp_b = getattr(mem, "temp_size_in_bytes", None)
    except Exception:
        arg_b = tmp_b = None
    print(json.dumps({
        "config": args.config, "compile_only": True, "params": n_params,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "batch": batch, "seq": seq, "remat": bool(net._remat),
        "compile_s": round(time.time() - t0, 1),
        "argument_bytes_per_device": arg_b,
        "temp_bytes_per_device": tmp_b,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
