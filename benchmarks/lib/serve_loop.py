"""The one general load generator for ``serving.Server.submit_generate``:
open loop (requests due on a seeded schedule, sent whether or not earlier
ones were answered) and closed loop (each client sends its next request
when the last was answered). Everything a traffic file can vary is a
parameter read here; a new mix is a new data file.

One process: the generator is the calling thread, ``on_token`` runs on the
server's scheduler thread and only appends a clock reading (plus, in a
closed loop, sends the client's next request).
"""
from __future__ import annotations

import math
import threading
import time
from typing import List, Optional

import numpy as np

from benchmarks.lib import arrivals, harness, stats

# The served model computes in bf16 (8 bits of mantissa) through
# num_hidden_layers blocks; the reference is float32 end to end. With
# seeded random weights the top logits lie close together, so tokens are
# not compared: the generated token's REFERENCE logit has to lie within
# 2**-5 of the largest reference logit magnitude below the reference's
# top logit (8 bf16 ulps of the logit range; an 8-bit float would be 2**-2
# off and fails).
LOGIT_TOL = 2.0 ** -5
DRAIN_TIMEOUT_S = 120.0


class Rec:
    """One request as the generator saw it."""
    __slots__ = ("req", "due", "sent", "times", "handle", "error", "trace")

    def __init__(self, req: arrivals.Request):
        self.req = req
        self.due = math.nan
        self.sent = math.nan
        self.times: List[float] = []
        self.handle = None
        self.error: Optional[BaseException] = None
        self.trace = None


class Generator:
    def __init__(self, run: harness.Run, srv, traced: bool):
        self.run, self.srv, self.traced = run, srv, traced
        self.records: List[Rec] = []
        self.callback_errors: List[str] = []
        self.lock = threading.Lock()

    def send(self, rec: Rec, due: float, after=None) -> None:
        """Submit ``rec``; ``after(rec)`` runs on the scheduler thread when
        its last token has arrived."""
        from mxnet_tpu import tracing

        rec.due = due
        budget = rec.req.max_new
        times = rec.times

        def on_token(i, _token):
            times.append(time.perf_counter())
            if after is not None and i + 1 == budget:
                try:
                    after(rec)
                except BaseException as e:  # noqa: BLE001 - the server
                    # swallows callback errors; keep this one for `correct`
                    self.callback_errors.append(repr(e))

        with self.lock:
            self.records.append(rec)
        rec.sent = time.perf_counter()
        try:
            if self.traced:
                rec.trace = tracing.new_trace("bench.request")
                with tracing.active(rec.trace):
                    rec.handle = self.srv.submit_generate(
                        rec.req.prompt, budget, on_token=on_token)
            else:
                rec.handle = self.srv.submit_generate(
                    rec.req.prompt, budget, on_token=on_token)
        except Exception as e:  # noqa: BLE001 - a refusal is a failed request
            rec.error = e

    def drain(self, timeout: float) -> None:
        deadline = time.perf_counter() + timeout
        for rec in list(self.records):
            if rec.handle is None:
                continue
            try:
                rec.handle.result(max(0.0, deadline - time.perf_counter()))
            except BaseException as e:  # noqa: BLE001 - typed failure/timeout
                rec.error = e


def send_open_loop(gen: Generator, schedule, t0: float, at_half=None,
                   half_s: float = math.inf) -> None:
    """Send each request when it is due (``t0`` + its offset), whether or
    not earlier ones were answered; ``at_half()`` runs once, before the
    first request due at or after ``half_s``."""
    for req in schedule:
        due = t0 + req.due_s
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if at_half is not None and req.due_s >= half_s:
            at_half()
            at_half = None
        gen.send(Rec(req), due)


def warm_up(run: harness.Run, srv, groups, vocab: int) -> int:
    """Drive every (batch bucket, len bucket) prefill signature and every
    decode batch bucket the window can hit: each ``[n, prompt_len]`` group
    is submitted from the scheduler thread (in the ``on_token`` of the
    previous group's last token), so its ``n`` requests are admitted in
    ONE tick and prefill as one batch; two tokens each, so one decode
    round of ``n`` streams follows."""
    rs = np.random.RandomState(run.seed + 1)
    groups = [[1, groups[0][1]]] + [list(g) for g in groups]
    done = threading.Event()
    state = {"next": 0, "left": 0}
    handles, errors = [], []

    def submit_group():
        n, plen = groups[state["next"]]
        state["next"] += 1
        state["left"] = 2 * n
        for _ in range(n):
            prompt = rs.randint(1, vocab, (plen,)).astype(np.int32)
            handles.append(srv.submit_generate(prompt, 2, on_token=on_token))

    def on_token(_i, _token):
        state["left"] -= 1
        if state["left"] > 0:
            return
        try:
            if state["next"] < len(groups):
                submit_group()
            else:
                done.set()
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)
            done.set()

    batches_before = srv.stats()["batches"]
    submit_group()
    if not done.wait(timeout=1100.0):
        raise RuntimeError(f"warm-up stalled at group {state['next']} "
                           f"of {len(groups)}")
    if errors:
        raise errors[0]
    for h in handles:
        h.result(timeout=60.0)
    batches = srv.stats()["batches"] - batches_before
    if batches != len(groups):
        raise RuntimeError(
            f"warm-up: {len(groups)} groups made {batches} prefill batches; "
            "a group was split, so a signature may be cold")
    return len(handles)


def check_outputs(run: harness.Run, weights, records, n_sample: int) -> dict:
    """A seeded sample of completed requests against the plain reference:
    one full float32 forward over prompt + generated tokens each."""
    done = [r for r in records if r.error is None and r.handle is not None
            and len(r.times) == r.req.max_new]
    rs = np.random.RandomState(run.seed + 2)
    picks = [done[i] for i in rs.choice(len(done), min(n_sample, len(done)),
                                        replace=False)] if done else []
    worst, checked = 0.0, 0
    for rec in picks:
        out = np.asarray(rec.handle.result(timeout=1.0), np.int32)
        p, new = rec.req.prompt.size, out.size
        total = p + new
        padded = -(-total // 256) * 256     # few distinct compiled lengths
        seq = np.zeros((padded,), np.int32)
        seq[:p], seq[p:total] = rec.req.prompt, out
        ref = np.asarray(run.reference.logits_at(
            weights, run.config, seq, np.arange(p - 1, p - 1 + new)),
            np.float32)
        if not np.isfinite(ref).all():
            return {"ok": False, "why": "reference logits not finite",
                    "checked": checked, "worst_gap_in_tolerances": worst}
        tol = np.abs(ref).max(axis=1) * LOGIT_TOL
        gap = (ref.max(axis=1) - ref[np.arange(new), out]) / tol
        worst = max(worst, float(gap.max()))
        checked += 1
    return {"ok": bool(picks) and worst <= 1.0, "checked": checked,
            "worst_gap_in_tolerances": worst}


def run_serving(run: harness.Run, mode: str) -> harness.Result:
    import gc

    from mxnet_tpu import telemetry, tracing

    cfg, tr = run.config, run.traffic
    vocab = cfg["vocab_size"]
    threads_before = set(threading.enumerate())
    if run.trace:
        # on before any program is traced: the routing counters count
        # at trace time
        telemetry.enable()
        tracing.enable()
    built = run.builder.build(cfg, tr, run.seed, run.devices)
    srv = built["server"]
    run.log("server started")
    n_warm = warm_up(run, srv, tr["server"]["warmup"], vocab)
    run.log(f"warm-up: {n_warm} requests in "
            f"{len(tr['server']['warmup']) + 1} groups")

    if mode == "open":
        schedule = arrivals.open_loop_schedule(run.seed, tr, vocab,
                                               run.seconds)
    else:
        per_client = int(run.seconds * tr["max_rps_per_client"]) + 4
        clients = arrivals.closed_loop_schedule(run.seed, tr, vocab,
                                                per_client)
    setup_compiles = run.watch.snapshot()
    counters_0 = harness.program_counters()
    gen = Generator(run, srv, traced=run.trace)
    profile = harness.ProfileSlice(run.out_dir) if run.trace else None

    # -- the window ---------------------------------------------------------
    setup_s = time.perf_counter() - run.t0
    t0 = time.perf_counter()
    t_end = t0 + run.seconds
    if profile is not None:
        a = t0 + run.seconds * tr["trace_start_frac"]
        profile.at(a, min(a + tr["trace_len_s"], t_end))
    exhausted = 0
    if mode == "open":
        send_open_loop(gen, schedule, t0)
    else:
        cursor = [0] * len(clients)

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

        for c, reqs in enumerate(clients):
            cursor[c] = 1
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
    served_tokens = 0
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
            served_tokens += sum(1 for t in r.times if t <= t_end)
            gaps.extend((b - a) * 1e3 for a, b in zip(r.times, r.times[1:]))
        else:
            ttft.append(math.inf)
        if ok and len(r.times) > 1:
            tpot.append((r.times[-1] - r.times[0]) * 1e3
                        / (len(r.times) - 1))
        else:
            tpot.append(math.inf)
    # where the traffic file states the knee's limits: the share of the
    # requests sent that met both (a cell below the knee keeps it high)
    limits = tr.get("knee", {}).get("limits")
    attainment = None
    if limits and recs:
        attainment = sum(a <= limits["ttft_ms"] and b <= limits["tpot_ms"]
                         for a, b in zip(ttft, tpot)) / len(recs)
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

    # the program's own host spans explain the device's idle gaps
    trace = profile.load([s for s in spans
                          if s["name"] in ("prefill", "decode.step")]) \
        if profile is not None else None
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
               # per request (tokens, TPOT ms): what the tail is made of
               "tpot_by_request": [[len(r.times), round(t, 3)]
                                   for r, t in zip(recs, tpot)
                                   if math.isfinite(t)],
               "gen_late_p99_ms": stats.percentile(late, 99.0),
               "pending_at_end": pending_at_end,
               "attainment_of_knee_limits": attainment,
               "reference_check": check, "threads_left": left,
               "callback_errors": gen.callback_errors,
               "clients_exhausted": exhausted,
               "server_stats": {k: stats_end.get(k) for k in
                                ("requests", "batches", "errors", "tokens",
                                 "preemptions", "defrags", "kvcache")},
               "compiles_in_setup": setup_compiles,
               "compiles_in_window": compiles,
               "memory_stats": run.devices[0].memory_stats()})
