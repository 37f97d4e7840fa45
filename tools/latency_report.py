"""Per-stage latency decomposition from request-trace JSONL dumps.

Usage:
  python tools/latency_report.py /tmp/traces.*.jsonl
  python tools/latency_report.py --json /tmp/traces.12345.jsonl

Reads flight-recorder dumps (``MXNET_TRACING_OUT`` / ``/traces`` /
``mx.tracing.dump``) — one JSON object per line, completed traces and
structured events interleaved — and answers the question the serving
histograms cannot: **which stage** makes a p99 slow. Every request
trace is split into its named spans (``ingress.decode``,
``router.queue``, ``router.attempt``, ``batch.wait``, ``dispatch``,
``wire.return``, ``ingress.reply``) and each stage's p50/p99 is
reported alongside its share of end-to-end time.

The three-bucket rollup at the end maps stages onto a framing / socket /
scheduling decomposition of the ingress path's overhead:

* framing     — ``ingress.decode`` + ``ingress.reply`` (codec seams);
* socket      — ``wire.return`` (the measured socket leg home; the
  outbound leg hides inside router.attempt's wire wait);
* scheduling  — ``router.queue`` + ``batch.wait`` (time spent waiting
  for a thread or a batch slot, not moving bytes).

The split is derived from traces alone, no benchmark run needed.

Stage spans may overlap (``router.attempt`` contains the replica-side
spans), so shares are reported against the root request span, not
summed to 100%.

Multi-tenant dumps additionally get a **per-tenant rollup** — spans
are tagged ``model`` + ``slo_class`` at every seam, so the report
groups traces by tenant and prints one table per model (request
p50/p99, TTFT and per-token percentiles for generate traces, shed
counts by reason) plus a preemption rollup pairing beneficiary with
victim ("who preempted whom", with the victim's clean-prefix length).
That answers the multi-tenant question the aggregate table cannot:
WHOSE p99 is slow, and at whose expense. Traces with no ``model`` tag
are the default tenant — absent field = default, same as the wire.

Dumps of a decoding server also get a **round rollup**: a decode
round's record (one ``decode.round`` span and its six ``round.*``
children — wait, sched, build, launch, fetch, emit — which tile the
scheduler thread's time) lives in the trace of the round's FIRST
stream only, so it is left out of the per-request stage sums (a request
holds the rounds it happened to lead, not the ones it paid for) and
reported per round: p50 / p99 of each phase, and how much of ``emit``
ran inside the callers' own callbacks (``callback_us``).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

# stage -> overhead bucket
_BUCKETS = {
    "ingress.decode": "framing",
    "ingress.reply": "framing",
    "wire.return": "socket",
    "router.queue": "scheduling",
    "batch.wait": "scheduling",
}

# presentation order; anything else observed is appended alphabetically
_STAGE_ORDER = ["ingress.decode", "router.queue", "router.attempt",
                "gen.queue", "prefill", "decode.step",
                "batch.wait", "dispatch", "wire.return", "ingress.reply",
                "request"]


# a decode round's record: per ROUND, not per request (round_rollup)
_ROUND, _ROUND_PHASE = "decode.round", "round."
_ROUND_PHASES = ("wait", "sched", "build", "launch", "fetch", "emit")


def _is_round_span(name: str) -> bool:
    return name == _ROUND or name.startswith(_ROUND_PHASE)


def _pctl(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(int(q * len(xs)), len(xs) - 1)
    return xs[i]


def load_traces(paths) -> tuple:
    """Parse dump files -> (traces, events). Unparseable lines are
    counted, not fatal — dumps happen at crash time."""
    traces, events, bad = [], [], 0
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    bad += 1
                    continue
                if "trace_id" in obj and "spans" in obj:
                    traces.append(obj)
                elif "event" in obj:
                    events.append(obj)
    if bad:
        print(f"warning: {bad} unparseable line(s) skipped",
              file=sys.stderr)
    return traces, events


def stage_latencies(traces) -> Dict[str, List[float]]:
    """stage name -> list of per-request durations (ms). A stage that
    appears more than once in a trace (failover retries both
    router.queue and router.attempt) contributes its SUM — the request
    paid all of it. A decode round's record is no stage of a request:
    :func:`round_rollup` reads it."""
    out: Dict[str, List[float]] = {}
    for t in traces:
        per: Dict[str, float] = {}
        for s in t.get("spans", []):
            name = s.get("name")
            dur = s.get("dur")
            if not isinstance(name, str) or _is_round_span(name) or \
                    not isinstance(dur, (int, float)):
                continue
            per[name] = per.get(name, 0.0) + dur / 1e3
        for name, ms in per.items():
            out.setdefault(name, []).append(ms)
    return out


def decode_rollup(traces) -> Dict:
    """TTFT vs per-token latency for generate traces (those carrying
    ``prefill`` / ``decode.step`` spans). TTFT is trace start to the
    end of ``prefill`` — the first token is emitted there — so it
    includes queueing and admission, which is what a caller feels.
    Per-token latency is the gap between consecutive ``decode.step``
    span ends inside one trace: the steady-state streaming interval,
    which stays flat only while every step re-hits the one warm
    ``(batch, 1)`` executable."""
    ttfts: List[float] = []
    gaps: List[float] = []
    ntoks: List[int] = []
    for t in traces:
        spans = [s for s in t.get("spans", [])
                 if isinstance(s.get("ts"), (int, float))]
        pre = [s for s in spans if s.get("name") == "prefill"]
        steps = [s for s in spans if s.get("name") == "decode.step"]
        if not pre and not steps:
            continue
        t0 = min(s["ts"] for s in spans)
        if pre:
            first = min(p["ts"] + (p.get("dur") or 0) for p in pre)
            ttfts.append((first - t0) / 1e3)
        ends = sorted(s["ts"] + (s.get("dur") or 0) for s in steps)
        gaps.extend((b - a) / 1e3 for a, b in zip(ends, ends[1:]))
        ntoks.append(len(steps) + (1 if pre else 0))
    if not ntoks:
        return {}
    return {
        "generate_traces": len(ntoks),
        "tokens_p50": _pctl([float(n) for n in ntoks], 0.50),
        "ttft_p50_ms": round(_pctl(ttfts, 0.50), 3),
        "ttft_p99_ms": round(_pctl(ttfts, 0.99), 3),
        "per_token_p50_ms": round(_pctl(gaps, 0.50), 3),
        "per_token_p99_ms": round(_pctl(gaps, 0.99), 3),
    }


def round_rollup(traces) -> Dict:
    """Where the scheduler thread's time goes, per decode ROUND: p50 and
    p99 (ms) of the whole round and of each of its six phases over every
    ``decode.round`` record in the dump (once each: by ``span_id``), the
    live streams a round, how many rounds failed, and the share of the
    ``emit`` phases' time that ran inside the callers' callbacks."""
    rounds: Dict[str, dict] = {}
    phases: Dict[str, dict] = {}
    for t in traces:
        for s in t.get("spans", []):
            name, sid = s.get("name"), s.get("span_id")
            if not isinstance(name, str) or not _is_round_span(name) \
                    or not isinstance(s.get("dur"), (int, float)):
                continue
            (rounds if name == _ROUND else phases)[sid] = s
    if not rounds:
        return {}
    by_phase: Dict[str, List[float]] = {p: [] for p in _ROUND_PHASES}
    callback_us = emit_us = 0.0
    for s in phases.values():
        phase = s["name"][len(_ROUND_PHASE):]
        if s.get("parent_id") not in rounds or phase not in by_phase:
            continue
        by_phase[phase].append(s["dur"] / 1e3)
        if phase == "emit":
            emit_us += s["dur"]
            callback_us += (s.get("tags") or {}).get("callback_us", 0)
    tags = [r.get("tags") or {} for r in rounds.values()]
    durs = [r["dur"] / 1e3 for r in rounds.values()]
    return {
        "rounds": len(rounds),
        "errors": sum(t.get("outcome") == "error" for t in tags),
        "streams_p50": _pctl([float(t.get("streams", 0)) for t in tags],
                             0.50),
        "round_p50_ms": round(_pctl(durs, 0.50), 3),
        "round_p99_ms": round(_pctl(durs, 0.99), 3),
        "phases": {p: {"p50_ms": round(_pctl(xs, 0.50), 3),
                       "p99_ms": round(_pctl(xs, 0.99), 3)}
                   for p, xs in by_phase.items()},
        "emit_callback_share": (round(callback_us / emit_us, 3)
                                if emit_us else None),
    }


def _trace_tenant(t) -> tuple:
    """(model, slo_class) for one trace. Tenant tags ride several
    spans (server root, ``batch.wait``, ``router.generate``); the
    first occurrence wins. No tag anywhere = the default tenant,
    mirroring the wire contract (absent field = default)."""
    model = slo = None
    for s in t.get("spans", []):
        tags = s.get("tags")
        if not isinstance(tags, dict):
            continue
        if model is None and isinstance(tags.get("model"), str):
            model = tags["model"]
        if slo is None and isinstance(tags.get("slo_class"), str):
            slo = tags["slo_class"]
        if model is not None and slo is not None:
            break
    return model or "default", slo or "standard"


def tenant_rollup(traces, events) -> List[Dict]:
    """One row per tenant: request p50/p99 off the root span, decode
    percentiles for generate traces, shed counts by reason from the
    recorder's ``shed`` events."""
    groups: Dict[str, Dict] = {}
    for t in traces:
        model, slo = _trace_tenant(t)
        g = groups.setdefault(model, {"slo_class": slo, "traces": []})
        g["traces"].append(t)
    sheds: Dict[str, Dict[str, int]] = {}
    for e in events:
        if e.get("event") != "shed":
            continue
        m = str(e.get("model", "default"))
        reason = str(e.get("reason", "?"))
        sheds.setdefault(m, {})[reason] = \
            sheds.get(m, {}).get(reason, 0) + 1
    rows = []
    for model in sorted(set(groups) | set(sheds)):
        g = groups.get(model, {"slo_class": "standard", "traces": []})
        ts = g["traces"]
        stages = stage_latencies(ts)
        roots = stages.get("request", []) + stages.get("generate", [])
        statuses: Dict[str, int] = {}
        for t in ts:
            st = t.get("status", "open")
            statuses[st] = statuses.get(st, 0) + 1
        row = {
            "model": model, "slo_class": g["slo_class"],
            "traces": len(ts), "statuses": statuses,
            "request_p50_ms": round(_pctl(roots, 0.50), 3),
            "request_p99_ms": round(_pctl(roots, 0.99), 3),
            "sheds": sheds.get(model, {}),
        }
        dec = decode_rollup(ts)
        if dec:
            row["decode"] = dec
        rows.append(row)
    return rows


def preemption_rollup(events) -> Dict:
    """Pair beneficiary with victim across the recorder's
    ``preempted`` events: who preempted whom, how often, and how long
    the victims' sealed clean prefixes were when the pages were
    taken."""
    pre = [e for e in events if e.get("event") == "preempted"]
    if not pre:
        return {}
    pairs: Dict[str, Dict] = {}
    for e in pre:
        key = (f"{e.get('beneficiary_model', '?')} preempted "
               f"{e.get('victim_model', '?')}")
        p = pairs.setdefault(key, {"count": 0, "victim_tokens": []})
        p["count"] += 1
        vt = e.get("victim_tokens")
        if isinstance(vt, (int, float)):
            p["victim_tokens"].append(float(vt))
    out = {"events": len(pre), "pairs": {}}
    for key, p in sorted(pairs.items()):
        out["pairs"][key] = {
            "count": p["count"],
            "victim_clean_prefix_p50_tokens":
                round(_pctl(p["victim_tokens"], 0.50), 1),
        }
    return out


def report(traces, events) -> Dict:
    stages = stage_latencies(traces)
    roots = stages.get("request", [])
    root_p50 = _pctl(roots, 0.50)

    order = [s for s in _STAGE_ORDER if s in stages]
    order += sorted(s for s in stages if s not in _STAGE_ORDER)

    table = []
    for name in order:
        xs = stages[name]
        p50 = _pctl(xs, 0.50)
        table.append({
            "stage": name, "n": len(xs),
            "p50_ms": round(p50, 3),
            "p99_ms": round(_pctl(xs, 0.99), 3),
            "max_ms": round(max(xs), 3),
            "share_of_request_p50": (round(p50 / root_p50, 3)
                                     if root_p50 else None),
        })

    rollup = {"framing": 0.0, "socket": 0.0, "scheduling": 0.0}
    for name, bucket in _BUCKETS.items():
        rollup[bucket] += _pctl(stages.get(name, []), 0.50)

    statuses: Dict[str, int] = {}
    for t in traces:
        st = t.get("status", "open")
        statuses[st] = statuses.get(st, 0) + 1
    ev_kinds: Dict[str, int] = {}
    for e in events:
        k = e.get("event", "?")
        ev_kinds[k] = ev_kinds.get(k, 0) + 1

    rep = {
        "traces": len(traces),
        "statuses": statuses,
        "events": ev_kinds,
        "stages": table,
        # the overhead rollup (measured, per-request p50)
        "serving_ingress_overhead_framing_ms": round(rollup["framing"], 3),
        "serving_ingress_overhead_socket_ms": round(rollup["socket"], 3),
        "serving_ingress_overhead_scheduling_ms":
            round(rollup["scheduling"], 3),
    }
    dec = decode_rollup(traces)
    if dec:
        rep["decode"] = dec
    rounds = round_rollup(traces)
    if rounds:
        rep["round_rollup"] = rounds
    tenants = tenant_rollup(traces, events)
    # the per-tenant table earns its ink only when there IS more than
    # one tenant (or sheds/preemptions name one): a single-tenant dump
    # reads the same as the aggregate table above
    if (len(tenants) > 1 or any(t["sheds"] for t in tenants)
            or any(t["model"] != "default" for t in tenants)):
        rep["tenants"] = tenants
    pre = preemption_rollup(events)
    if pre:
        rep["preemptions"] = pre
    return rep


def _print_table(rep: Dict) -> None:
    print(f"{rep['traces']} trace(s); statuses: {rep['statuses']}")
    if rep["events"]:
        print(f"recorder events: {rep['events']}")
    print()
    hdr = f"{'stage':<16}{'n':>6}{'p50 ms':>10}{'p99 ms':>10}" \
          f"{'max ms':>10}{'share':>8}"
    print(hdr)
    print("-" * len(hdr))
    for row in rep["stages"]:
        share = ("" if row["share_of_request_p50"] is None
                 else f"{row['share_of_request_p50']:.0%}")
        print(f"{row['stage']:<16}{row['n']:>6}{row['p50_ms']:>10.3f}"
              f"{row['p99_ms']:>10.3f}{row['max_ms']:>10.3f}{share:>8}")
    print()
    print("overhead rollup (p50):")
    for k in ("framing", "socket", "scheduling"):
        print(f"  {k:<11} "
              f"{rep[f'serving_ingress_overhead_{k}_ms']:.3f} ms")
    dec = rep.get("decode")
    if dec:
        print()
        print(f"decode rollup ({dec['generate_traces']} generate "
              f"trace(s), {dec['tokens_p50']:.0f} tokens p50):")
        print(f"  TTFT        p50 {dec['ttft_p50_ms']:.3f} ms   "
              f"p99 {dec['ttft_p99_ms']:.3f} ms")
        print(f"  per-token   p50 {dec['per_token_p50_ms']:.3f} ms   "
              f"p99 {dec['per_token_p99_ms']:.3f} ms")
    rounds = rep.get("round_rollup")
    if rounds:
        print()
        print(f"round rollup ({rounds['rounds']} decode round(s), "
              f"{rounds['streams_p50']:.0f} streams p50, "
              f"{rounds['errors']} failed): scheduler-thread ms a round")
        print(f"  {'round':<8}p50 {rounds['round_p50_ms']:.3f}   "
              f"p99 {rounds['round_p99_ms']:.3f}")
        for phase, row in rounds["phases"].items():
            print(f"  {phase:<8}p50 {row['p50_ms']:.3f}   "
                  f"p99 {row['p99_ms']:.3f}")
        if rounds["emit_callback_share"] is not None:
            print(f"  {rounds['emit_callback_share']:.0%} of emit ran in "
                  "the callers' callbacks")
    tenants = rep.get("tenants")
    if tenants:
        print()
        print("per-tenant rollup (whose p99):")
        hdr = (f"  {'model':<12}{'slo class':<10}{'n':>6}"
               f"{'p50 ms':>10}{'p99 ms':>10}  sheds")
        print(hdr)
        print("  " + "-" * (len(hdr) - 2))
        for row in tenants:
            shed = ", ".join(f"{k}={v}"
                             for k, v in sorted(row["sheds"].items()))
            print(f"  {row['model']:<12}{row['slo_class']:<10}"
                  f"{row['traces']:>6}{row['request_p50_ms']:>10.3f}"
                  f"{row['request_p99_ms']:>10.3f}  {shed or '-'}")
            dec = row.get("decode")
            if dec:
                print(f"  {'':<12}TTFT p50 {dec['ttft_p50_ms']:.3f} / "
                      f"p99 {dec['ttft_p99_ms']:.3f} ms; per-token "
                      f"p50 {dec['per_token_p50_ms']:.3f} / "
                      f"p99 {dec['per_token_p99_ms']:.3f} ms")
    pre = rep.get("preemptions")
    if pre:
        print()
        print(f"preemptions ({pre['events']} event(s), "
              "who preempted whom):")
        for key, p in pre["pairs"].items():
            print(f"  {key}: {p['count']}x, victim clean prefix p50 "
                  f"{p['victim_clean_prefix_p50_tokens']:g} tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tools/latency_report.py",
        description="per-stage p50/p99 decomposition from trace JSONL")
    ap.add_argument("paths", nargs="+", help="trace dump file(s)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of a table")
    args = ap.parse_args(argv)

    traces, events = load_traces(args.paths)
    if not traces:
        print("no completed traces found", file=sys.stderr)
        return 1
    rep = report(traces, events)
    if args.json:
        print(json.dumps(rep, indent=2, sort_keys=True))
    else:
        _print_table(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
