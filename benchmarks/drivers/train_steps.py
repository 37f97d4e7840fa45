"""Training steps back to back over a pool of seeded batches staged on
the device. Emits ``train_tokens_s``.

Traffic parameters: ``batch`` (global), ``seq``, ``pool``, ``warm_steps``,
``check_rows``, ``trace_after_steps``, ``trace_steps``.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.lib import harness

# bf16 carries 8 bits. The eval-mode loss is a mean over rows x seq
# positions of a log-sum-exp over the vocabulary, after 12 layers of bf16
# matmuls with f32 accumulation: the errors of single positions (a few
# 2**-8 of the logit range) average out, and 2**-6 of the loss is the
# band they stay in. An 8-bit-float forward (3 mantissa bits) would be
# 2**-3 off and fails.
LOSS_RTOL = 2.0 ** -6


def run(run: harness.Run) -> harness.Result:
    import jax
    from mxnet_tpu import telemetry, tracing

    cfg, tr, b = run.config, run.traffic, run.builder
    built = b.build(cfg, tr, run.seed, run.devices)
    step = built["step"]
    pool = b.make_pool(built, cfg, tr, run.seed)
    run.log(f"net built, pool of {len(pool)} batches on the device")

    # -- correct, part 1 (set-up): eval-mode loss against the reference ----
    rows = tr["check_rows"]
    tokens = pool[0][0].asnumpy()[:rows]
    labels = pool[0][1].asnumpy()[:rows]
    sys_loss = b.eval_loss(built, tokens, labels)
    ref_loss = run.reference.loss(b.export_weights(built), cfg, tokens, labels)
    loss_ok = (math.isfinite(sys_loss)
               and abs(sys_loss - ref_loss) <= LOSS_RTOL * abs(ref_loss))
    run.log(f"eval loss {sys_loss:.5f} vs reference {ref_loss:.5f} "
            f"(tolerance {LOSS_RTOL * abs(ref_loss):.5f}): "
            f"{'ok' if loss_ok else 'MISMATCH'}")

    # -- warm-up: the one shape this cell uses ------------------------------
    if run.trace:
        telemetry.enable()
        tracing.enable()
    for batch in pool:
        step.stage_batch(batch, ())
    warm = []
    for i in range(tr["warm_steps"]):
        loss, _ = step(pool[i % len(pool)], ())
        warm.append(float(loss.asnumpy()))
    run.log(f"{len(warm)} warm-up steps done, losses {warm}")

    setup_compiles = run.watch.snapshot()
    counters_0 = harness.program_counters()
    profile = harness.ProfileSlice(run.out_dir) if run.trace else None
    k_after, k_steps = tr["trace_after_steps"], tr["trace_steps"]
    annotate = jax.profiler.TraceAnnotation

    # -- the window ---------------------------------------------------------
    losses, dispatch_s = [], []
    setup_s = time.perf_counter() - run.t0
    t_start = time.perf_counter()
    deadline = t_start + run.seconds
    prev = None
    i = 0
    paused_s = 0.0      # traced run: draining for and starting/stopping
    while time.perf_counter() < deadline:       # the profiler is no step time
        if profile is not None and i == k_after:
            t_p = time.perf_counter()
            jax.block_until_ready(prev)
            profile.start()
            paused_s += time.perf_counter() - t_p
        t_a = time.perf_counter()
        with annotate("bench:dispatch"):
            loss, _ = step(pool[i % len(pool)], ())
        dispatch_s.append(time.perf_counter() - t_a)
        losses.append(loss.data)
        # one step queued behind the one that runs: the device never
        # waits for the host, the host never runs far ahead
        if prev is not None:
            with annotate("bench:block"):
                jax.block_until_ready(prev)
        prev = loss.data
        i += 1
        if profile is not None and i == k_after + k_steps:
            with annotate("bench:block"):
                jax.block_until_ready(prev)
            t_p = time.perf_counter()
            profile.stop()
            paused_s += time.perf_counter() - t_p
    jax.block_until_ready(prev)
    window_s = time.perf_counter() - t_start - paused_s
    if profile is not None:
        profile.stop()
    compiles = harness.CompileWatch.delta(run.watch.snapshot(), setup_compiles)
    counters_1 = harness.program_counters()
    peak = harness.peak_memory_bytes(run.devices)
    if run.trace:
        telemetry.disable()
        tracing.disable()

    # -- correct, part 2: the losses of the window --------------------------
    losses = [float(np.asarray(v)) for v in losses]
    n = len(losses)
    half = max(1, min(len(pool), n // 2))
    first, last = np.mean(losses[:half]), np.mean(losses[-half:])
    finite = all(math.isfinite(v) for v in losses)
    falling = n >= 2 and last < first
    run.log(f"{n} steps in {window_s:.3f} s; loss {first:.4f} -> {last:.4f}")

    tokens_per_step = tr["batch"] * tr["seq"]
    return harness.Result(
        correct=bool(loss_ok and finite and falling),
        attempted=n, failed=sum(not math.isfinite(v) for v in losses),
        end_to_end={"setup_s": setup_s,
                    "train_tokens_s": n * tokens_per_step / window_s},
        layer={"steps": n, "window_s": window_s,
               "tokens_per_step": tokens_per_step,
               "dispatch_s": dispatch_s,
               "flops_per_token": b.flops_per_token(cfg, tr),
               "counters_before": counters_0, "counters_after": counters_1,
               "compiles": compiles, "peak_bytes": peak,
               "trace": profile.load() if profile is not None else None,
               "trace_steps": k_steps},
        notes={"eval_loss": sys_loss, "reference_loss": ref_loss,
               "loss_ok": loss_ok, "losses_finite": finite,
               "loss_first": float(first), "loss_last": float(last),
               "loss_falling": falling, "steps": n, "window_s": window_s,
               "warm_losses": warm, "compiles_in_setup": setup_compiles,
               "compiles_in_window": compiles,
               "memory_stats": run.devices[0].memory_stats()})
