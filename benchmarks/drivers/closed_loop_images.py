"""Closed loop whose requests carry IMAGES: ``clients`` callers that each
send their next request (one or two page images and an instruction) when
the last was answered. Emits ``served_tokens_s`` (prompt tokens, an
image's rows among them, and answer tokens) and ``tpot_p50_ms``.

``lib/serve_loop.py``'s ``Generator.send``, ``warm_up`` and
``check_outputs`` pass token ids alone, so this driver repeats
``run_serving``'s flow with a request that has images (ROADMAP.md, C
debts: a ``benchmark`` PR gives those three a request-payload seam and
folds this file in). What it shares it imports: the records, the drain,
the tolerance, the length quantiles, the profiler slice, the result.

Traffic parameters: ``clients``, ``max_rps_per_client``, ``block`` (the
stratification block of a caller's list), ``images_per_request``
(``{"1": share, "2": share}``), ``image_tokens`` (log-uniform ``min`` ..
``max`` language tokens an image; patches are 4 x that),
``aspect_ratios`` (width : height pairs), ``text_len``, ``text_before``,
``output_len`` (length specs of ``lib/arrivals.py``), ``warmup`` (requests
``[patches, text tokens, answer tokens]`` sent together before the
window: between them every patch bucket, every prefill signature and
every decode bucket), ``server``, ``check_requests``,
``trace_start_frac``, ``trace_len_s``.
"""
from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from benchmarks.lib import arrivals, harness, stats
from benchmarks.lib.serve_loop import (DRAIN_TIMEOUT_S, LOGIT_TOL, Generator,
                                       Rec)

PATCH_DIM = 588
# rows of the seeded noise every image of a run is a window of
POOL_ROWS = 1 << 16


@dataclass
class ImageRequest(arrivals.Request):
    images: list = field(default_factory=list)  # [(patches, (rows, cols))]


class ImageGenerator(Generator):
    def send(self, rec: Rec, due: float, after=None) -> None:
        """``Generator.send`` with the request's images."""
        from mxnet_tpu import tracing

        rec.due = due
        budget = rec.req.max_new
        times = rec.times

        def on_token(i, _token):
            times.append(time.perf_counter())
            if after is not None and i + 1 == budget:
                try:
                    after(rec)
                except BaseException as e:  # noqa: BLE001 - kept for correct
                    self.callback_errors.append(repr(e))

        with self.lock:
            self.records.append(rec)
        rec.sent = time.perf_counter()
        try:
            if self.traced:
                rec.trace = tracing.new_trace("bench.request")
                with tracing.active(rec.trace):
                    rec.handle = self.srv.submit_generate(
                        rec.req.prompt, budget, on_token=on_token,
                        images=rec.req.images)
            else:
                rec.handle = self.srv.submit_generate(
                    rec.req.prompt, budget, on_token=on_token,
                    images=rec.req.images)
        except Exception as e:  # noqa: BLE001 - a refusal is a failed request
            rec.error = e


# -- the seeded requests ------------------------------------------------------

def _quantiles(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "loguniform":
        return arrivals.quantile_lengths(spec, n)
    u = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(spec["min"]) + u * (
        math.log(spec["max"]) - math.log(spec["min"])))).astype(np.int64)


def _dealt(rs, values_of, n: int, block: int) -> np.ndarray:
    """``n`` values: every ``block`` consecutive ones are ``values_of(size)``
    (the block's stratified quantiles) in a seeded order, as
    ``arrivals.draw_lengths`` deals lengths."""
    out = [rs.permutation(values_of(min(block, n - start)))
           for start in range(0, n, block)]
    return np.concatenate(out) if out else np.zeros((0,), np.int64)


def grid_of(tokens: int, ratio, lo: int, hi: int) -> tuple:
    """The patch grid (rows, cols) of an image of about ``tokens`` merge
    groups at ``ratio`` = (width, height): both sides a whole number of
    28-pixel merge groups, the product kept inside ``lo`` .. ``hi``."""
    w, h = ratio
    gh = max(1, int(round(math.sqrt(tokens * h / w))))
    gw = max(1, int(round(math.sqrt(tokens * w / h))))
    while gh * gw > hi:
        gh, gw = (gh - 1, gw) if gh >= gw else (gh, gw - 1)
    while gh * gw < lo:
        gh, gw = (gh + 1, gw) if gh <= gw else (gh, gw + 1)
    return 2 * gh, 2 * gw


def noise_pool(seed: int, dtype) -> np.ndarray:
    """The run's pixels: seeded standard-normal noise, of which every
    image is a window (so that making an image copies nothing)."""
    import jax.numpy as jnp

    rs = np.random.RandomState((seed + 11) % (2 ** 32))
    return rs.standard_normal((POOL_ROWS, PATCH_DIM)).astype(
        np.float32).astype(jnp.dtype(dtype))


def make_request(rs, pool, index, client, grids, text, before, max_new,
                 holder: int) -> ImageRequest:
    """Text ids drawn below the placeholder id (0 is left out: the server
    pads with it), ``before`` of them in front of the images' runs of
    placeholder ids."""
    images = []
    for rows, cols in grids:
        start = int(rs.randint(0, pool.shape[0] - rows * cols))
        images.append((pool[start:start + rows * cols], (rows, cols)))
    ids = rs.randint(1, holder, (int(text),)).astype(np.int32)
    runs = [np.full((r * c // 4,), holder, np.int32) for r, c in grids]
    prompt = np.concatenate([ids[:before], *runs, ids[before:]])
    return ImageRequest(index, 0.0, prompt, int(max_new), client, images)


def schedule(seed: int, traffic: dict, holder: int, per_client: int,
             pool) -> list:
    """``clients`` lists of ``per_client`` requests. Image counts, sizes,
    aspect ratios and lengths are dealt to each caller as stratified
    quantiles in blocks: two seeds differ in which request has which,
    not in what a block of requests holds."""
    rs = np.random.RandomState(seed % (2 ** 32))
    block = int(traffic["block"])
    size, ratios = traffic["image_tokens"], traffic["aspect_ratios"]
    shares = sorted((int(k), float(v))
                    for k, v in traffic["images_per_request"].items())

    def counts(n):
        # the share of each count among n requests, the rounding's
        # remainder going to the first
        per = [int(round(share * n)) for _, share in shares]
        per[0] += n - sum(per)
        return np.repeat([k for k, _ in shares], per)

    out, index = [], 0
    for c in range(int(traffic["clients"])):
        n_img = _dealt(rs, counts, per_client, block)
        total = int(n_img.sum())
        tokens = _dealt(rs, lambda n: _quantiles(size, n), total, block)
        ratio = _dealt(rs, lambda n: np.arange(n) % len(ratios), total,
                       len(ratios))
        text = _dealt(rs, lambda n: _quantiles(traffic["text_len"], n),
                      per_client, block)
        before = _dealt(rs, lambda n: _quantiles(traffic["text_before"], n),
                        per_client, block)
        olen = _dealt(rs, lambda n: _quantiles(traffic["output_len"], n),
                      per_client, block)
        reqs, k = [], 0
        for i in range(per_client):
            grids = [grid_of(int(tokens[k + j]), ratios[int(ratio[k + j])],
                             size["min"], size["max"])
                     for j in range(int(n_img[i]))]
            k += int(n_img[i])
            reqs.append(make_request(rs, pool, index + i, c, grids, text[i],
                                     min(before[i], text[i]), olen[i],
                                     holder))
        out.append(reqs)
        index += per_client
    return out


# -- warm-up ------------------------------------------------------------------

def warm_up(run: harness.Run, srv, groups, holder: int, pool) -> int:
    """Send the traffic file's ``warmup`` requests together and wait for
    them all: the scheduler encodes one image a tick and prefills each
    request alone, so the streams join the decode round one by one and
    the round passes through every width up to their number."""
    rs = np.random.RandomState((run.seed + 1) % (2 ** 32))
    handles = []
    for i, (patches, text, max_new) in enumerate(groups):
        side = 2 ** (int(math.log2(patches)) // 2)       # even x even
        req = make_request(rs, pool, i, 0, [(side, patches // side)], text,
                           min(8, text), max_new, holder)
        handles.append(srv.submit_generate(req.prompt, req.max_new,
                                           images=req.images))
    for h in handles:
        h.result(timeout=1100.0)
    return len(handles)


# -- correct ------------------------------------------------------------------

def check_outputs(run: harness.Run, weights, records, n_sample: int) -> dict:
    """A seeded sample of completed requests against the plain reference:
    the tower over each image and ONE full float32 forward over prompt
    (its image rows in place) + generated tokens. As the harness's own
    ``check_outputs``: with seeded weights the top logits lie close
    together, so the generated token's REFERENCE logit has to lie within
    ``LOGIT_TOL`` (2**-5: 8 bf16 ulps of the logit range; an 8-bit float
    is 2**-2 off) of the largest reference logit magnitude below the
    reference's top logit."""
    done = [r for r in records if r.error is None and r.handle is not None
            and len(r.times) == r.req.max_new]
    rs = np.random.RandomState((run.seed + 2) % (2 ** 32))
    picks = [done[i] for i in rs.choice(len(done), min(n_sample, len(done)),
                                        replace=False)] if done else []
    worst, checked = 0.0, 0
    for rec in picks:
        out = np.asarray(rec.handle.result(timeout=1.0), np.int32)
        p, new = rec.req.prompt.size, out.size
        seq = np.concatenate([rec.req.prompt, out])
        ref = np.asarray(run.reference.logits_at(
            weights, run.config, seq, np.arange(p - 1, p - 1 + new),
            images=rec.req.images), np.float32)
        if not np.isfinite(ref).all():
            return {"ok": False, "why": "reference logits not finite",
                    "checked": checked, "worst_gap_in_tolerances": worst}
        tol = np.abs(ref).max(axis=1) * LOGIT_TOL
        gap = (ref.max(axis=1) - ref[np.arange(new), out]) / tol
        worst = max(worst, float(gap.max()))
        checked += 1
    return {"ok": bool(picks) and worst <= 1.0, "checked": checked,
            "worst_gap_in_tolerances": worst}


# -- the run ------------------------------------------------------------------

def run(run: harness.Run) -> harness.Result:
    import gc

    from mxnet_tpu import telemetry, tracing

    cfg, tr = run.config, run.traffic
    holder = cfg["image_token_id"]
    threads_before = set(threading.enumerate())
    if run.trace:
        telemetry.enable()
        tracing.enable()
    built = run.builder.build(cfg, tr, run.seed, run.devices)
    srv = built["server"]
    run.log("server started")
    pool = noise_pool(run.seed, cfg["dtype"])
    n_warm = warm_up(run, srv, tr["warmup"], holder, pool)
    run.log(f"warm-up: {n_warm} requests")
    per_client = int(run.seconds * tr["max_rps_per_client"]) + 4
    clients = schedule(run.seed, tr, holder, per_client, pool)
    setup_compiles = run.watch.snapshot()
    counters_0 = harness.program_counters()
    gen = ImageGenerator(run, srv, traced=run.trace)
    profile = harness.ProfileSlice(run.out_dir) if run.trace else None

    # -- the window ---------------------------------------------------------
    setup_s = time.perf_counter() - run.t0
    t0, epoch_t0 = time.perf_counter(), time.time()
    t_end = t0 + run.seconds
    slice_at = (t0 + run.seconds * tr["trace_start_frac"],
                min(t0 + run.seconds * tr["trace_start_frac"]
                    + tr["trace_len_s"], t_end))
    if profile is not None:
        profile.at(*slice_at)
    exhausted = 0
    cursor = [1] * len(clients)

    def next_for(rec):
        c = rec.req.client
        now = time.perf_counter()
        if now >= t_end:
            return
        if cursor[c] >= len(clients[c]):
            nonlocal exhausted
            exhausted += 1
            return
        nxt = Rec(clients[c][cursor[c]])
        cursor[c] += 1
        gen.send(nxt, now, after=next_for)

    for reqs in clients:
        gen.send(Rec(reqs[0]), time.perf_counter(), after=next_for)
    time.sleep(max(0.0, t_end - time.perf_counter()))
    pending_at_end = srv.stats().get("generates_pending", 0)
    gen.drain(DRAIN_TIMEOUT_S)
    if profile is not None:
        profile.join()
    compiles = harness.CompileWatch.delta(run.watch.snapshot(), setup_compiles)
    counters_1 = harness.program_counters()
    peak = harness.peak_memory_bytes(run.devices)
    stats_end = srv.stats()
    srv.stop(timeout=60.0)
    if run.trace:
        telemetry.disable()
        tracing.disable()
    left = [t.name for t in set(threading.enumerate()) - threads_before
            if t.is_alive()]
    run.log(f"window done: {len(gen.records)} requests sent, server stopped")

    # -- reduce -------------------------------------------------------------
    recs = gen.records
    ttft, tpot, gaps, late = [], [], [], []
    served_tokens = image_tokens = 0
    failed = 0
    for r in recs:
        late.append((r.sent - r.due) * 1e3)
        ok = (r.error is None and r.handle is not None
              and len(r.times) == r.req.max_new)
        if not ok:
            failed += 1
        if r.times:
            ttft.append((r.times[0] - r.due) * 1e3 if ok else math.inf)
            if r.times[0] <= t_end:
                served_tokens += r.req.prompt.size
                image_tokens += sum(g[0] * g[1] // 4
                                    for _, g in r.req.images)
            served_tokens += sum(1 for t in r.times if t <= t_end)
            gaps.extend((b - a) * 1e3 for a, b in zip(r.times, r.times[1:]))
        else:
            ttft.append(math.inf)
        if ok and len(r.times) > 1:
            tpot.append((r.times[-1] - r.times[0]) * 1e3
                        / (len(r.times) - 1))
        else:
            tpot.append(math.inf)
    spans = []
    if run.trace:
        for r in recs:
            if r.trace is not None:
                spans.extend(r.trace.export_spans())

    # -- correct: a sample against the reference (the arena is freed first)
    weights = run.builder.export_weights(built)
    gen.srv = None
    del srv, built["server"]
    gc.collect()
    check = check_outputs(run, weights, recs, tr["check_requests"])
    run.log(f"reference check: {check}")
    correct = bool(check["ok"] and failed == 0 and not left
                   and not gen.callback_errors and exhausted == 0)

    # the program's own host spans explain the device's idle gaps: an
    # idle gap under an encode is labelled as one. Only the spans that
    # touch the slice (a second to either side) are handed over: the
    # labelling compares every gap with every span it is given
    trace = None
    if profile is not None:
        lo = (epoch_t0 + slice_at[0] - t0 - 1.0) * 1e6
        hi = (epoch_t0 + slice_at[1] - t0 + 1.0) * 1e6
        trace = profile.load([
            s for s in spans
            if s["name"] in ("prefill", "decode.step", "vision.encode")
            and s["ts"] <= hi and s["ts"] + s["dur"] >= lo])
    return harness.Result(
        correct=correct, attempted=len(recs), failed=failed,
        end_to_end={"setup_s": setup_s,
                    "served_tokens_s": served_tokens / run.seconds,
                    "tpot_p50_ms": stats.percentile(tpot, 50.0)},
        layer={"window_s": run.seconds, "spans": spans,
               "late_ms": late, "gap_ms": gaps,
               "ttft_ms": ttft, "tpot_ms": tpot,
               "counters_before": counters_0, "counters_after": counters_1,
               "compiles": compiles, "peak_bytes": peak,
               "trace_prompt_len": {r.trace.trace_id: int(r.req.prompt.size)
                                    for r in recs if r.trace is not None},
               "trace": trace,
               "trace_clock_offset_ns": profile.clock_offset_ns
               if profile is not None else None},
        notes={"samples": {"ttft": len(ttft), "tpot": len(tpot),
                           "token_gaps": len(gaps)},
               "ttft_p50_ms": stats.percentile(ttft, 50.0),
               "ttft_p95_ms": stats.percentile(ttft, 95.0),
               "tpot_p95_ms": stats.percentile(tpot, 95.0),
               "image_tokens_s": image_tokens / run.seconds,
               "images_sent": sum(len(r.req.images) for r in recs),
               "pending_at_end": pending_at_end,
               "reference_check": check, "threads_left": left,
               "callback_errors": gen.callback_errors,
               "clients_exhausted": exhausted,
               "server_stats": {k: stats_end.get(k) for k in
                                ("requests", "batches", "errors", "tokens",
                                 "preemptions", "defrags", "kvcache")},
               "compiles_in_setup": setup_compiles,
               "compiles_in_window": compiles,
               "memory_stats": run.devices[0].memory_stats()})
