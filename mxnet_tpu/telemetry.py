"""``mx.telemetry`` — always-available runtime metrics.

The reference pairs its dependency engine with a first-class profiler
(``src/profiler/profiler.cc``); profiling answers "where did this one run
spend its time", but a serving-scale system also needs cheap *structured
counters* that are always on in production: op mix, comms volume, compile
-cache behaviour, step throughput. This module is that spine: a thread-safe
registry of counters, gauges and fixed-bucket histograms (no unbounded
state) with three exporters:

* ``dumps()``       — structured JSON snapshot;
* ``prom_text()``   — Prometheus text exposition format (no dependency);
* ``chrome_counter_events()`` — chrome-trace ``ph:"C"`` counter events,
  merged into ``profiler.dumps(format="chrome_trace")``'s timeline.

Recording is **default-off**: every instrumented hot path guards on one
module-level flag (``_state.enabled`` — a single attribute load + branch)
so the disabled fast path costs one branch and allocates nothing. Enable
with ``MXNET_TELEMETRY=1`` in the environment or ``telemetry.enable()``.

Instrumented layers (each records through the ``record_*`` helpers below,
which also no-op when disabled, so call sites may skip the outer guard off
the hot path):

* op dispatch    — ``ops/registry.py::eager_call`` +
  ``ndarray.imperative_invoke`` (per-op counts, host dispatch latency);
* engine         — live-array gauge, ``wait_for_all`` block time,
  live-ref eviction counter (``engine.track`` overflow);
* kvstore        — push/pull/allreduce call counts, bytes moved, latency;
* jit caches     — hit/miss per cache (eager per-op executables, CachedOp,
  TrainStep, symbol Executor);
* training loop  — ``TrainingTelemetry`` step hook: step time,
  examples/sec, MFU (FLOP accounting from :func:`xla_cost_analysis`).
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "enable", "disable", "enabled", "reset",
    "counter", "gauge", "histogram",
    "dumps", "prom_text", "chrome_counter_events", "snapshot",
    "start_exporter", "MetricsExporter",
    "parse_prom_text", "emit_prom_text", "scrape", "prom_value",
    "record_op_dispatch", "record_cache", "record_cache_eviction",
    "record_cold_start", "record_warm_start", "record_elastic_warm",
    "record_kv",
    "record_kv_collective", "record_kv_bucket", "record_kv_compression",
    "record_optimizer_dispatch", "record_optimizer_bucket",
    "record_engine_wait", "set_live_arrays", "record_live_evictions",
    "record_training_step", "record_xla_dispatch", "record_bulk_flush",
    "record_fault_injected", "record_retry", "record_checkpoint_write",
    "record_step_skipped",
    "record_data_wait", "set_data_queue_depth", "record_images_decoded",
    "record_serving_request", "record_serving_batch",
    "record_serving_queue_time", "set_serving_queue_depth",
    "record_serving_reload",
    "record_serving_shed", "record_serving_failover",
    "record_decode_step", "record_block_round", "record_round_phases",
    "record_host_fetch", "record_prefill_dispatch",
    "record_moe_picks",
    "record_prefill_chunk", "record_dsa_keys",
    "record_token", "set_kvcache_pages",
    "record_serving_route_retry", "record_router_queue_wait",
    "set_router_queue_depth", "set_replica_health",
    "record_breaker_transition", "record_router_request",
    "record_worker_restart", "record_ingress_rejected",
    "record_ingress_request", "set_ingress_connections",
    "set_router_inflight", "set_predicted_wait",
    "TrainingTelemetry", "xla_cost_analysis",
    "pop_telemetry_out_flag", "write_snapshot",
    "LATENCY_BUCKETS", "STEP_BUCKETS", "SEGMENT_BUCKETS",
    "BYTES_BUCKETS", "SERVING_BUCKETS", "OCCUPANCY_BUCKETS",
]


class _State:
    __slots__ = ("enabled",)

    def __init__(self, enabled: bool):
        self.enabled = enabled


# THE fast-path guard: instrumented modules read `_state.enabled` directly
# (one attribute load + branch; never swap the _State instance, callers
# cache a reference to it).
_state = _State(os.environ.get("MXNET_TELEMETRY", "0") == "1")


def enabled() -> bool:
    return _state.enabled


def enable() -> None:
    _state.enabled = True


def disable() -> None:
    _state.enabled = False


# ---------------------------------------------------------------------------
# Metric registry
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_registry: Dict[str, "_Family"] = {}

# Per-family label-child cap: label values come from bounded sets (op names,
# cache names) but a bug upstream must degrade to a catch-all child, never
# to unbounded registry growth.
_MAX_CHILDREN = 4096
_OVERFLOW_LABEL = "_overflow"

# host-side dispatch/comms latencies: 10 µs .. 30 s, ~x3 geometric
LATENCY_BUCKETS: Tuple[float, ...] = (
    10e-6, 30e-6, 100e-6, 300e-6, 1e-3, 3e-3, 10e-3, 30e-3,
    100e-3, 300e-3, 1.0, 3.0, 10.0, 30.0)
# training steps: 1 ms .. 100 s
STEP_BUCKETS: Tuple[float, ...] = (
    1e-3, 3e-3, 10e-3, 30e-3, 100e-3, 300e-3, 1.0, 3.0, 10.0, 30.0, 100.0)
# bulk-segment lengths (op counts): powers of two up to the practical cap
SEGMENT_BUCKETS: Tuple[float, ...] = (
    1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
# payload sizes (gradient buckets): 4 KB .. 1 GB, x4 geometric
BYTES_BUCKETS: Tuple[float, ...] = (
    4096, 16384, 65536, 262144, 1 << 20, 4 << 20, 16 << 20, 64 << 20,
    256 << 20, 1 << 30)
# inference request latencies: LATENCY_BUCKETS bottoms out too coarse for
# serving p50s (a batched CPU dense dispatch answers in tens of µs) —
# 20 µs .. 10 s, ~x2–2.5 geometric, dense through the sub-millisecond range
SERVING_BUCKETS: Tuple[float, ...] = (
    20e-6, 50e-6, 100e-6, 200e-6, 500e-6, 1e-3, 2e-3, 5e-3, 10e-3,
    20e-3, 50e-3, 100e-3, 200e-3, 500e-3, 1.0, 2.0, 5.0, 10.0)
# batch occupancy (real rows / padded bucket capacity): eighths of a batch
OCCUPANCY_BUCKETS: Tuple[float, ...] = (
    0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)


class _Counter:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up; use a gauge")
        with _lock:
            self.value += amount


class _Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, value: float) -> None:
        with _lock:
            self.value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with _lock:
            self.value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)


class _Histogram:
    __slots__ = ("edges", "counts", "sum", "count", "exemplars")

    def __init__(self, edges: Tuple[float, ...]):
        self.edges = edges
        self.counts = [0] * (len(edges) + 1)   # last slot = +Inf
        self.sum = 0.0
        self.count = 0
        # OpenMetrics exemplars: bucket index -> (labels, value, ts).
        # None until the first exemplar so plain observes stay
        # allocation-free; kept as last-write-wins per bucket.
        self.exemplars = None

    def observe(self, value: float,
                exemplar: Optional[Dict[str, str]] = None) -> None:
        i = 0
        edges = self.edges
        n = len(edges)
        # linear scan: bucket lists are ~a dozen entries, and bisect on a
        # tuple of floats is not faster at this size
        while i < n and value > edges[i]:
            i += 1
        with _lock:
            self.counts[i] += 1
            self.sum += value
            self.count += 1
            if exemplar is not None:
                if self.exemplars is None:
                    self.exemplars = {}
                self.exemplars[i] = (dict(exemplar), value, time.time())


_KINDS = {"counter": _Counter, "gauge": _Gauge, "histogram": _Histogram}


class _Family:
    """One named metric with a fixed label schema and per-labelset children."""

    __slots__ = ("name", "kind", "help", "labelnames", "buckets", "children")

    def __init__(self, name, kind, help="", labelnames=(), buckets=None):
        self.name = name
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(buckets) if buckets is not None else None
        self.children: Dict[Tuple[str, ...], object] = {}

    def labels(self, *values) -> object:
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {values!r}")
        key = tuple(str(v) for v in values)
        child = self.children.get(key)
        if child is None:
            with _lock:
                child = self.children.get(key)
                if child is None:
                    if len(self.children) >= _MAX_CHILDREN:
                        key = (_OVERFLOW_LABEL,) * len(self.labelnames)
                        child = self.children.get(key)
                        if child is not None:
                            return child
                    child = (_Histogram(self.buckets)
                             if self.kind == "histogram"
                             else _KINDS[self.kind]())
                    self.children[key] = child
        return child

    # label-less convenience: family with no labelnames acts as its child
    def _solo(self):
        return self.labels()

    def inc(self, amount: float = 1.0):
        self._solo().inc(amount)

    def set(self, value: float):
        self._solo().set(value)

    def dec(self, amount: float = 1.0):
        self._solo().dec(amount)

    def observe(self, value: float,
                exemplar: Optional[Dict[str, str]] = None):
        self._solo().observe(value, exemplar=exemplar)


def _get_or_create(name, kind, help, labelnames, buckets=None) -> _Family:
    fam = _registry.get(name)
    if fam is not None:
        if (fam.kind != kind or fam.labelnames != tuple(labelnames)
                or (buckets is not None and fam.buckets != tuple(buckets))):
            raise ValueError(
                f"metric {name!r} already registered as {fam.kind} with "
                f"labels {fam.labelnames} and buckets {fam.buckets}")
        return fam
    with _lock:
        fam = _registry.get(name)
        if fam is None:
            fam = _Family(name, kind, help, labelnames, buckets)
            _registry[name] = fam
    return fam


def counter(name: str, help: str = "",
            labelnames: Sequence[str] = ()) -> _Family:
    """Get or create a monotonically-increasing counter family."""
    return _get_or_create(name, "counter", help, labelnames)


def gauge(name: str, help: str = "",
          labelnames: Sequence[str] = ()) -> _Family:
    """Get or create a gauge (set/inc/dec) family."""
    return _get_or_create(name, "gauge", help, labelnames)


def histogram(name: str, help: str = "", labelnames: Sequence[str] = (),
              buckets: Sequence[float] = LATENCY_BUCKETS) -> _Family:
    """Get or create a fixed-bucket histogram family."""
    edges = tuple(sorted(float(b) for b in buckets))
    if not edges:
        raise ValueError("histogram needs at least one bucket edge")
    return _get_or_create(name, "histogram", help, labelnames, edges)


def reset() -> None:
    """Drop all registered metrics (values AND families).

    Instrumentation re-creates families lazily through the ``record_*``
    helpers, so a full clear is safe; tests use this for isolation.
    """
    with _lock:
        _registry.clear()


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def snapshot() -> Dict:
    """Point-in-time dict of every metric (the JSON exporter's payload)."""
    out: Dict = {"enabled": _state.enabled, "metrics": {}}
    with _lock:
        families = list(_registry.values())
    for fam in families:
        samples: List[Dict] = []
        with _lock:
            children = list(fam.children.items())
        for key, child in children:
            labels = dict(zip(fam.labelnames, key))
            if fam.kind == "histogram":
                with _lock:
                    counts = list(child.counts)
                    hsum, hcount = child.sum, child.count
                    exemplars = (dict(child.exemplars)
                                 if child.exemplars else None)
                cum = 0
                buckets = {}
                edges = list(fam.buckets) + [math.inf]
                ex_out = {}
                for i, (edge, c) in enumerate(zip(edges, counts)):
                    cum += c
                    le = _fmt_float(edge)
                    buckets[le] = cum
                    if exemplars is not None and i in exemplars:
                        xlabels, xval, xts = exemplars[i]
                        ex_out[le] = {"labels": xlabels, "value": xval,
                                      "ts": xts}
                buckets["+Inf"] = hcount
                sample = {"labels": labels, "sum": hsum,
                          "count": hcount, "buckets": buckets}
                if ex_out:
                    sample["exemplars"] = ex_out
                samples.append(sample)
            else:
                samples.append({"labels": labels, "value": child.value})
        out["metrics"][fam.name] = {
            "type": fam.kind, "help": fam.help, "samples": samples}
    return out


def dumps(indent: Optional[int] = None) -> str:
    """Structured JSON snapshot of all metrics."""
    return json.dumps(snapshot(), indent=indent, sort_keys=True)


def _fmt_float(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    s = repr(float(v))
    return s[:-2] if s.endswith(".0") else s


def _esc_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _prom_labels(labels: Dict[str, str], extra: Tuple[str, str] = None) -> str:
    items = list(labels.items())
    if extra is not None:
        items.append(extra)
    if not items:
        return ""
    body = ",".join(f'{k}="{_esc_label(str(v))}"' for k, v in items)
    return "{" + body + "}"


def prom_text() -> str:
    """Prometheus text exposition format (version 0.0.4) of all metrics."""
    snap = snapshot()
    lines: List[str] = []
    for name in sorted(snap["metrics"]):
        fam = snap["metrics"][name]
        if fam["help"]:
            lines.append(f"# HELP {name} {fam['help']}")
        lines.append(f"# TYPE {name} {fam['type']}")
        for s in fam["samples"]:
            if fam["type"] == "histogram":
                exemplars = s.get("exemplars") or {}
                for le, cum in s["buckets"].items():
                    line = (f"{name}_bucket"
                            f"{_prom_labels(s['labels'], ('le', le))} {cum}")
                    ex = exemplars.get(le)
                    if ex is not None:
                        # OpenMetrics exemplar suffix:
                        #   ... 5 # {trace_id="deadbeef"} 0.053 1690000000.0
                        line += (f" # {_prom_labels(ex['labels'])} "
                                 f"{_fmt_float(ex['value'])}"
                                 + (f" {_fmt_float(ex['ts'])}"
                                    if ex.get("ts") is not None else ""))
                    lines.append(line)
                lines.append(
                    f"{name}_sum{_prom_labels(s['labels'])} "
                    f"{_fmt_float(s['sum'])}")
                lines.append(
                    f"{name}_count{_prom_labels(s['labels'])} {s['count']}")
            else:
                lines.append(
                    f"{name}{_prom_labels(s['labels'])} "
                    f"{_fmt_float(s['value'])}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# HTTP exporter + scrape parser: the cross-process half of telemetry.
# A process (serving worker, router host) exposes /metrics + /healthz via
# stdlib http.server; a scraper (FleetController's ScrapeFleetSignals,
# Prometheus itself) pulls the text format back and parses it — the only
# signal channel that works when the observed fleet is not in the
# observer's address space.
# ---------------------------------------------------------------------------

class MetricsExporter:
    """Serve ``/metrics`` (Prometheus text 0.0.4 via :func:`prom_text`)
    and ``/healthz`` (JSON; ``healthz_fn`` supplies the body) from a
    daemon thread. ``port=0`` binds an ephemeral port — read
    :attr:`port` after construction."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 healthz_fn=None):
        import http.server

        exporter = self

        class _Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):          # noqa: N802 - stdlib contract
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    body = prom_text().encode("utf-8")
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif path == "/varz":
                    # the /metrics payload without the prometheus
                    # lossiness: full JSON snapshot, exemplars included
                    body = dumps(indent=2).encode("utf-8")
                    ctype = "application/json"
                elif path == "/traces":
                    # flight-recorder ring as JSONL (one event or
                    # completed trace per line); empty when tracing off
                    from . import tracing
                    body = tracing.dump_jsonl().encode("utf-8")
                    ctype = "application/jsonl"
                elif path == "/healthz":
                    try:
                        payload = (exporter.healthz_fn()
                                   if exporter.healthz_fn else
                                   {"ok": True, "pid": os.getpid()})
                    except Exception as e:  # noqa: BLE001 - report it
                        payload = {"ok": False, "error": str(e)}
                    body = json.dumps(payload).encode("utf-8")
                    ctype = "application/json"
                else:
                    self.send_error(404)
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # scrapes are high-rate; silence
                pass

        self.healthz_fn = healthz_fn
        self._server = http.server.ThreadingHTTPServer(
            (host, port), _Handler)
        self._server.daemon_threads = True
        self.host = host
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"telemetry-exporter-{self.port}", daemon=True)
        self._thread.start()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}/metrics"

    def stop(self, timeout: Optional[float] = 5.0) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout)


def start_exporter(port: int = 0, host: str = "127.0.0.1",
                   healthz_fn=None) -> MetricsExporter:
    """Start a :class:`MetricsExporter`; returns it (``.port``/``.url``/
    ``.stop()``)."""
    return MetricsExporter(port=port, host=host, healthz_fn=healthz_fn)


def _unquote_label(s: str, i: int) -> Tuple[str, int]:
    """Parse one double-quoted prometheus label value starting at the
    opening quote ``s[i]``; returns (value, index past closing quote).
    Inverse of :func:`_esc_label`: ``\\\\``, ``\\"`` and ``\\n``."""
    if s[i] != '"':
        raise ValueError(f"expected '\"' at col {i} of {s!r}")
    i += 1
    buf: List[str] = []
    while True:
        if i >= len(s):
            raise ValueError(f"unterminated label value in {s!r}")
        c = s[i]
        if c == "\\":
            nxt = s[i + 1] if i + 1 < len(s) else ""
            buf.append({"\\": "\\", '"': '"', "n": "\n"}.get(nxt, nxt))
            i += 2
        elif c == '"':
            return "".join(buf), i + 1
        else:
            buf.append(c)
            i += 1


def _parse_label_set(s: str, i: int) -> Tuple[Dict[str, str], int]:
    """Parse ``{k="v",...}`` starting at the opening brace ``s[i]``;
    returns (labels, index past the closing brace)."""
    labels: Dict[str, str] = {}
    i += 1
    while i < len(s) and s[i] != "}":
        eq = s.index("=", i)
        key = s[i:eq].strip().lstrip(",").strip()
        value, i = _unquote_label(s, eq + 1)
        labels[key] = value
        if i < len(s) and s[i] == ",":
            i += 1
    if i >= len(s) or s[i] != "}":
        raise ValueError(f"unterminated label set in {s!r}")
    return labels, i + 1


def _parse_exemplar(text: str) -> Dict:
    """OpenMetrics exemplar tail ``{labels} value [ts]`` -> dict."""
    text = text.strip()
    labels: Dict[str, str] = {}
    i = 0
    if text.startswith("{"):
        labels, i = _parse_label_set(text, 0)
    rest = text[i:].split()
    if not rest:
        raise ValueError(f"exemplar with no value in {text!r}")
    ex: Dict = {"labels": labels, "value": float(rest[0])}
    if len(rest) > 1:
        ex["ts"] = float(rest[1])
    return ex


def _parse_sample_line(line: str
                       ) -> Tuple[str, Dict[str, str], float,
                                  Optional[Dict]]:
    """One exposition sample line -> (sample_name, labels, value,
    exemplar-or-None). The `` # {...} v [ts]`` OpenMetrics exemplar
    suffix is preserved structurally, never folded into the value."""
    brace = line.find("{")
    if brace == -1:
        main, _, ex_text = line.partition(" # ")
        name, _, val = main.partition(" ")
        return (name, {}, float(val),
                _parse_exemplar(ex_text) if ex_text else None)
    name = line[:brace]
    # the main label set may contain a quoted '#': parse it first, then
    # look for the exemplar separator in the remainder only
    labels, i = _parse_label_set(line, brace)
    main, _, ex_text = line[i:].partition(" # ")
    return (name, labels, float(main.strip()),
            _parse_exemplar(ex_text) if ex_text else None)


def parse_prom_text(text: str) -> Dict[str, Dict]:
    """Parse Prometheus text exposition (the :func:`prom_text` format)
    into ``{family: {"type", "help", "samples": [{"name", "labels",
    "value"}]}}``. Histogram ``_bucket``/``_sum``/``_count`` samples are
    attributed to their family; label-value escaping is fully reversed
    (``\\\\`` / ``\\"`` / ``\\n``). Malformed lines raise ``ValueError``
    — a scrape that half-parses is worse than one that fails."""
    out: Dict[str, Dict] = {}

    def family(name: str) -> Dict:
        fam = out.get(name)
        if fam is None:
            fam = out[name] = {"type": None, "help": "", "samples": []}
        return fam

    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            name, _, help_text = line[len("# HELP "):].partition(" ")
            family(name)["help"] = help_text
        elif line.startswith("# TYPE "):
            name, _, kind = line[len("# TYPE "):].partition(" ")
            family(name)["type"] = kind.strip()
        elif line.startswith("#"):
            continue
        else:
            sname, labels, value, exemplar = _parse_sample_line(line)
            fam_name = sname
            if fam_name not in out:
                for suffix in ("_bucket", "_sum", "_count"):
                    if sname.endswith(suffix) and \
                            sname[: -len(suffix)] in out:
                        fam_name = sname[: -len(suffix)]
                        break
            sample = {"name": sname, "labels": labels, "value": value}
            if exemplar is not None:
                sample["exemplar"] = exemplar
            family(fam_name)["samples"].append(sample)
    return out


def emit_prom_text(parsed: Dict[str, Dict]) -> str:
    """Re-emit a :func:`parse_prom_text` structure as exposition text
    (label values re-escaped) — ``parse -> emit -> parse`` is the
    identity, which is what makes the scrape channel trustworthy."""
    lines: List[str] = []
    for name in sorted(parsed):
        fam = parsed[name]
        if fam.get("help"):
            lines.append(f"# HELP {name} {fam['help']}")
        if fam.get("type"):
            lines.append(f"# TYPE {name} {fam['type']}")
        for s in fam["samples"]:
            line = (f"{s['name']}{_prom_labels(s['labels'])} "
                    f"{_fmt_float(s['value'])}")
            ex = s.get("exemplar")
            if ex is not None:
                line += (f" # {_prom_labels(ex['labels'])} "
                         f"{_fmt_float(ex['value'])}"
                         + (f" {_fmt_float(ex['ts'])}"
                            if ex.get("ts") is not None else ""))
            lines.append(line)
    return "\n".join(lines) + "\n"


def scrape(url: str, timeout_s: float = 2.0) -> Dict[str, Dict]:
    """HTTP GET ``url`` (a ``/metrics`` endpoint) and parse it. Stdlib
    urllib; raises on HTTP/socket errors (the caller decides whether a
    failed scrape is fatal — the autoscaler skips the tick)."""
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return parse_prom_text(resp.read().decode("utf-8"))


def prom_value(parsed: Dict[str, Dict], name: str,
               labels: Optional[Dict[str, str]] = None,
               default: float = 0.0) -> float:
    """Sum of the samples named exactly ``name`` whose labels are a
    superset of ``labels`` (counters with label dimensions scrape back
    as one series per labelset; the controller wants the total)."""
    fam = parsed.get(name)
    if fam is None:
        return default
    want = labels or {}
    total, hit = 0.0, False
    for s in fam["samples"]:
        if s["name"] != name:
            continue
        if all(s["labels"].get(k) == v for k, v in want.items()):
            total += s["value"]
            hit = True
    return total if hit else default


def chrome_counter_events(ts_us: Optional[float] = None) -> List[Dict]:
    """Current counter/gauge values as chrome-trace ``ph:"C"`` events.

    ``profiler.dumps(format="chrome_trace")`` merges these onto its
    timeline so about:tracing shows telemetry counters next to the spans.
    Histograms contribute their ``_count`` and ``_sum`` series.
    """
    if ts_us is None:
        ts_us = time.perf_counter() * 1e6
    snap = snapshot()
    events: List[Dict] = []
    for name, fam in sorted(snap["metrics"].items()):
        for s in fam["samples"]:
            series = "/".join(v for v in s["labels"].values()) or "value"
            if fam["type"] == "histogram":
                args = {series + "_count": s["count"],
                        series + "_sum": s["sum"]}
            else:
                args = {series: s["value"]}
            events.append({"name": name, "ph": "C", "pid": 0, "tid": 0,
                           "ts": ts_us, "args": args})
    return events


# ---------------------------------------------------------------------------
# Tool plumbing: the shared `--telemetry-out PATH` contract lives here so
# the flag cannot drift between tools.
# ---------------------------------------------------------------------------

def pop_telemetry_out_flag(argv: Sequence[str]
                           ) -> Tuple[List[str], Optional[str]]:
    """Strip ``--telemetry-out PATH`` / ``--telemetry-out=PATH`` from argv.

    Returns ``(argv_without_flag, path_or_None)`` — positionals keep their
    slots. A flag with no PATH is a hard error (SystemExit) rather than a
    silent no-snapshot run discovered only after an expensive trace.
    """
    out: List[str] = []
    path: Optional[str] = None
    it = iter(argv)
    for a in it:
        if a == "--telemetry-out":
            path = next(it, None)
        elif a.startswith("--telemetry-out="):
            path = a.split("=", 1)[1]
        else:
            out.append(a)
            continue
        if not path or path.startswith("-"):
            # a following option is NOT a path — erroring beats silently
            # consuming the flag and snapshotting into "--some-flag"
            raise SystemExit("--telemetry-out requires a PATH argument")
    return out, path


def write_snapshot(path: str) -> None:
    """Write an indented JSON snapshot to ``path`` (tool exit hook).

    Atomic (temp + fsync + rename via :func:`checkpoint.atomic_write`):
    a scraper or post-mortem reader never sees a half-written snapshot,
    and a crash mid-dump leaves the previous one intact."""
    from . import checkpoint   # lazy: avoid import cycle at module load

    checkpoint.atomic_write(path, dumps(indent=2).encode("utf-8"))


# MXNET_TELEMETRY_OUT=PATH: enable recording and write a snapshot at
# interpreter exit — how driver-spawned subprocesses report telemetry
# without any CLI plumbing of their own.
_env_out = os.environ.get("MXNET_TELEMETRY_OUT")
if _env_out:
    import atexit

    _state.enabled = True
    atexit.register(write_snapshot, _env_out)


# ---------------------------------------------------------------------------
# Recording helpers — the one place metric names/schemas are defined.
# All no-op when telemetry is disabled.
# ---------------------------------------------------------------------------

def record_op_dispatch(op: str, seconds: float) -> None:
    """One imperative op dispatch: per-op count + host latency."""
    if not _state.enabled:
        return
    counter("mxnet_op_dispatch_total",
            "Imperative op dispatches by op name.",
            ("op",)).labels(op).inc()
    histogram("mxnet_op_dispatch_seconds",
              "Host-side dispatch latency per op (async: excludes device "
              "execution).", ("op",)).labels(op).observe(seconds)


def record_cache(cache: str, hit: bool) -> None:
    """One lookup in a jit/CachedOp compile cache."""
    if not _state.enabled:
        return
    counter("mxnet_jit_cache_total",
            "Compile-cache lookups by cache and result.",
            ("cache", "result")).labels(
                cache, "hit" if hit else "miss").inc()


def record_cache_eviction(cache: str, n: int = 1) -> None:
    """LRU eviction(s) from a compile cache (or the persistent XLA disk
    tier). Previously silent — a thrashing cache recompiled forever with
    nothing on the dashboard; now the rate is a first-class signal."""
    if not _state.enabled:
        return
    counter("mxnet_jit_cache_evictions_total",
            "Compile-cache LRU evictions by cache.",
            ("cache",)).labels(cache).inc(n)


def record_cold_start(event: str, seconds: float) -> None:
    """A cold-start milestone (``compiler.mark_event``): seconds from
    package import to the first ``warm_start_done`` / ``first_train_step``
    / ``first_response``. Set once per event per process."""
    if not _state.enabled:
        return
    gauge("mxnet_coldstart_seconds",
          "Seconds from package import to each first-time lifecycle "
          "event.", ("event",)).labels(event).set(seconds)


def record_elastic_warm(seconds: float) -> None:
    """Duration of one elastic warm_start hook (fires per membership
    epoch — a DURATION histogram, distinct from the since-import
    ``mxnet_coldstart_seconds`` milestones)."""
    if not _state.enabled:
        return
    histogram("mxnet_elastic_warm_seconds",
              "Elastic warm_start hook duration per (re-)bootstrap.",
              buckets=STEP_BUCKETS).observe(seconds)


def record_warm_start(outcome: str, n: int = 1) -> None:
    """Manifest warm-start replay outcomes (``replayed``: compiled AOT,
    ``deduped``: already in the in-process executable table, ``skipped``:
    no provider for the entry, ``failed``)."""
    if not _state.enabled:
        return
    counter("mxnet_compile_warm_total",
            "Signature-manifest warm-start entries by outcome.",
            ("outcome",)).labels(outcome).inc(n)


def record_kv(op: str, nbytes: float, seconds: float) -> None:
    """One kvstore operation (push/pull/allreduce/row_sparse_pull)."""
    if not _state.enabled:
        return
    counter("mxnet_kvstore_calls_total",
            "KVStore operations by kind.", ("op",)).labels(op).inc()
    counter("mxnet_kvstore_bytes_total",
            "Payload bytes moved through the kvstore by kind.",
            ("op",)).labels(op).inc(float(nbytes))
    histogram("mxnet_kvstore_seconds",
              "Host-side kvstore call latency by kind.",
              ("op",)).labels(op).observe(seconds)


def record_kv_collective(path: str, n: int = 1) -> None:
    """One gradient-reduction dispatch on the comms path. ``path``:
    ``per_key`` (one reduce/psum per parameter — the reference shape),
    ``bucketed`` (one collective per fused gradient bucket),
    ``hierarchical`` (one topology-aware bucket collective — intra-host
    ICI + inter-host DCN factored through the 2-D device mesh; the count
    IS the inter-host dispatch count, exactly one per bucket), or
    ``zero`` (one fused reduce-scatter + shard-update + allgather
    program per ZeRO bucket). The per-step dispatch-reduction ratio in
    BENCH/PERF rounds is computed from this."""
    if not _state.enabled:
        return
    counter("mxnet_kvstore_collective_dispatch_total",
            "Gradient-reduction collective dispatches by path "
            "(per_key/bucketed/hierarchical/zero).", ("path",)).labels(path).inc(n)


def record_kv_bucket(nbytes: float, nkeys: int) -> None:
    """One fused gradient bucket exchanged by batched pushpull."""
    if not _state.enabled:
        return
    histogram("mxnet_kvstore_bucket_bytes",
              "Payload bytes per fused gradient bucket.",
              buckets=BYTES_BUCKETS).observe(float(nbytes))
    counter("mxnet_kvstore_bucketed_keys_total",
            "Parameter keys coalesced through bucketed pushpull."
            ).inc(nkeys)


def record_kv_bucket_fallback(reason: str, nkeys: int = 1) -> None:
    """Keys that fell OFF the fused bucketed-pushpull path back to the
    per-key exchange. ``reason``: ``row_sparse`` (non-default storage —
    PR 5's documented gap), ``zero_family`` (optimizer family the ZeRO
    shard sweep cannot reproduce bit-exactly, e.g. LAMB's cross-member
    trust-ratio norms), ``zero_multi_precision``, ``zero_sparse``.
    Observability for coverage gaps that used to be silent."""
    if not _state.enabled:
        return
    counter("mxnet_kvstore_bucket_fallback_total",
            "Keys excluded from fused bucketed pushpull by reason.",
            ("reason",)).labels(reason).inc(nkeys)


def record_optimizer_state_bytes(mode: str, nbytes: float) -> None:
    """Persistent optimizer-state bytes held by THIS rank, by layout
    ``mode``: ``replicated`` (every rank holds the full state — the
    reference KVStore shape), ``zero1`` / ``zero2`` (this rank's shard
    under ZeRO partitioning). The ZeRO engine publishes BOTH its actual
    per-rank bytes and the replicated-equivalent total, so the ~1/world
    memory drop is read directly off the gauge pair."""
    if not _state.enabled:
        return
    gauge("mxnet_optimizer_state_bytes",
          "Per-rank persistent optimizer-state bytes by layout mode.",
          ("mode",)).labels(mode).set(float(nbytes))


def record_kv_compression(ratio: float, elements: int) -> None:
    """One compressed bucket. ``ratio``: logical wire compression
    (uncompressed payload bits / 2-bit payload, e.g. 16x for fp32)."""
    if not _state.enabled:
        return
    gauge("mxnet_kvstore_compression_ratio",
          "Logical wire compression of the most recent compressed "
          "bucket (uncompressed bits / 2-bit quantized bits).").set(ratio)
    counter("mxnet_kvstore_compressed_elements_total",
            "Gradient elements through the 2-bit quantizer.").inc(elements)


def record_pallas_dispatch(kernel: str, n: int = 1) -> None:
    """A Pallas kernel routed into a trace. ``kernel``: flash_attention /
    fused_layer_norm / fused_rms_norm / fused_bias_gelu / ..., and
    ``<kernel>_bwd`` where a custom-vjp backward kernel was traced. Counts
    ROUTING decisions (the Python dispatch site runs once per trace, not
    per executed step), so this is the kernel ADOPTION observable: zero
    while MXNET_PALLAS_FUSED / shape gates keep a model on the eager
    path, one per kernel site per compiled executable otherwise."""
    if not _state.enabled:
        return
    counter("mxnet_pallas_dispatch_total",
            "Pallas-kernel routings into compiled traces by kernel "
            "(adoption counter: one per kernel site per trace).",
            ("kernel",)).labels(kernel).inc(n)


def record_optimizer_dispatch(path: str, n: int = 1) -> None:
    """One optimizer-phase update dispatch on the eager Trainer path.
    ``path``: ``per_param`` (one updater call per parameter — the
    reference shape) or ``fused_sweep`` (one packed multi-tensor sweep
    per dtype bucket). The O(params) -> O(buckets) collapse the fused
    engine exists for is read directly off this counter."""
    if not _state.enabled:
        return
    counter("mxnet_optimizer_dispatch_total",
            "Optimizer-phase update dispatches by path "
            "(per_param/fused_sweep).", ("path",)).labels(path).inc(n)


def record_optimizer_bucket(nbytes: float, nparams: int) -> None:
    """One fused optimizer bucket swept (packed multi-tensor update)."""
    if not _state.enabled:
        return
    histogram("mxnet_optimizer_bucket_bytes",
              "Parameter bytes per fused optimizer sweep bucket.",
              buckets=BYTES_BUCKETS).observe(float(nbytes))
    counter("mxnet_optimizer_bucketed_params_total",
            "Parameters updated through fused multi-tensor sweeps."
            ).inc(nparams)


def record_kv_overlap(when: str, n: int = 1) -> None:
    """One gradient-bucket pushpull dispatched by the overlapped-comms
    trainer. ``when``: ``backward`` (issued from the grad-ready hook
    while autograd's reverse sweep was still running — the overlap win)
    or ``step`` (flushed by Trainer.step for buckets whose members never
    became ready in the backward)."""
    if not _state.enabled:
        return
    counter("mxnet_kvstore_overlap_dispatch_total",
            "Overlapped-comms bucket dispatches by phase "
            "(backward/step).", ("when",)).labels(when).inc(n)


def record_engine_wait(seconds: float) -> None:
    if not _state.enabled:
        return
    histogram("mxnet_engine_wait_all_seconds",
              "Time blocked in engine.wait_for_all.").observe(seconds)


def set_live_arrays(n: int) -> None:
    if not _state.enabled:
        return
    gauge("mxnet_engine_live_arrays",
          "Arrays tracked by the engine whose async work may be in "
          "flight.").set(n)


def record_live_evictions(n: int) -> None:
    """Still-live refs evicted by engine.track overflow compaction —
    a nonzero rate means wait_for_all coverage is leaking."""
    if not _state.enabled or n <= 0:
        return
    counter("mxnet_engine_live_evictions_total",
            "Still-live refs evicted from the engine registry by "
            "overflow compaction.").inc(n)


def record_xla_dispatch(kind: str) -> None:
    """One host→XLA dispatch (a compiled-callable invocation). ``kind``:
    ``eager_op`` (cached per-op executable), ``eager_uncached`` (tracer/
    fallback path), ``fused_segment`` (one bulked segment). The eager-vs-
    bulk dispatch-reduction ratio in BENCH rounds is computed from this."""
    if not _state.enabled:
        return
    counter("mxnet_xla_dispatch_total",
            "Host-side XLA dispatches by kind (a fused bulk segment "
            "counts once however many ops it contains).",
            ("kind",)).labels(kind).inc()


def record_bulk_flush(reason: str, n_ops: int, seconds: float) -> None:
    """One bulk-segment flush: why it flushed, how many ops it fused,
    and host-side flush latency (cache lookup + dispatch)."""
    if not _state.enabled:
        return
    counter("mxnet_bulk_flush_total",
            "Bulk segment flushes by trigger (sync/size/unrecordable/"
            "scope_exit/nested_scope).", ("reason",)).labels(reason).inc()
    counter("mxnet_bulk_ops_total",
            "Imperative ops executed via fused bulk segments.").inc(n_ops)
    histogram("mxnet_bulk_segment_ops",
              "Ops fused per flushed bulk segment.",
              buckets=SEGMENT_BUCKETS).observe(n_ops)
    histogram("mxnet_bulk_flush_seconds",
              "Host-side bulk flush latency (fused-cache lookup + "
              "dispatch).").observe(seconds)


def record_fault_injected(site: str) -> None:
    """One fault fired by the injector (mxnet_tpu/fault.py)."""
    if not _state.enabled:
        return
    counter("mxnet_fault_injected_total",
            "Faults fired by the fault injector by site.",
            ("site",)).labels(site).inc()


def record_retry(site: str, outcome: str) -> None:
    """One retry event at a comms/IO site. ``outcome``: ``retry`` (one
    failed attempt), ``recovered`` (call succeeded after >=1 retry),
    ``exhausted`` (attempts used up, error surfaced)."""
    if not _state.enabled:
        return
    counter("mxnet_retry_total",
            "Retry events by site and outcome (retry/recovered/"
            "exhausted).", ("site", "outcome")).labels(site, outcome).inc()


def record_checkpoint_write(seconds: float) -> None:
    """One committed checkpoint bundle write (manifest valid on disk)."""
    if not _state.enabled:
        return
    histogram("mxnet_checkpoint_write_seconds",
              "Wall time to write + commit one checkpoint bundle.",
              buckets=STEP_BUCKETS).observe(seconds)


def record_step_skipped(reason: str) -> None:
    """One training step skipped by an anomaly guard. ``reason``:
    ``nonfinite_grad`` (Trainer guard) or ``amp_overflow`` (loss-scaler
    backoff)."""
    if not _state.enabled:
        return
    counter("mxnet_steps_skipped_total",
            "Training steps skipped by anomaly guards, by reason.",
            ("reason",)).labels(reason).inc()


def set_elastic_epoch(epoch: int) -> None:
    """Current elastic membership epoch (parallel/elastic.py) — bumps
    on every worker join/leave re-bootstrap."""
    if not _state.enabled:
        return
    gauge("mxnet_elastic_membership_epoch",
          "Elastic membership epoch (monotonic; one bump per worker "
          "join/leave re-bootstrap).").set(int(epoch))


def record_elastic_restart(n: int = 1) -> None:
    """Worker restarts observed by the elastic runtime: a rank's own
    rejoin-restore from a bundle, plus each sibling rejoin it
    witnesses."""
    if not _state.enabled or n <= 0:
        return
    counter("mxnet_elastic_worker_restarts_total",
            "Worker restarts observed by the elastic runtime "
            "(self rejoin-restores + witnessed sibling rejoins).").inc(n)


def record_elastic_heartbeat_miss(rank) -> None:
    """One rank declared dead by heartbeat expiry
    (MXNET_ELASTIC_HEARTBEAT_TIMEOUT exceeded)."""
    if not _state.enabled:
        return
    counter("mxnet_elastic_heartbeat_miss_total",
            "Heartbeat expiries (rank declared dead) by missed rank.",
            ("rank",)).labels(str(rank)).inc()


def record_elastic_preemption() -> None:
    """One graceful preemption leave: the runner checkpointed at the
    step boundary and exited for the supervisor to respawn (spot /
    preemptible capacity reclaim — the control plane's common case,
    not a failure)."""
    if not _state.enabled:
        return
    counter("mxnet_elastic_preemptions_total",
            "Graceful preemption leaves (checkpoint-then-exit on the "
            "preemption signal).").inc()


def set_fleet_size(n: int, router: str = "") -> None:
    """Current serving replica count behind the Router (non-draining) —
    the autoscaler's actuator state. Labeled by ``router``: a process
    may host several Routers, and a scrape-fed
    controller must be able to tell whose fleet it is reading."""
    if not _state.enabled:
        return
    gauge("mxnet_controller_fleet_size",
          "Serving replicas currently in the Router fleet "
          "(draining replicas excluded).",
          ("router",)).labels(router).set(int(n))


def record_fleet_scale(direction: str, outcome: str = "ok") -> None:
    """One autoscaler action: ``direction`` up/down, ``outcome`` ok /
    failed (replica factory or start raised — the controller contains
    it and retries on a later tick)."""
    if not _state.enabled:
        return
    counter("mxnet_controller_scale_total",
            "Autoscaler scale actions by direction and outcome.",
            ("direction", "outcome")).labels(direction, outcome).inc()


def record_fleet_scale_seconds(direction: str, seconds: float) -> None:
    """Wall seconds for one completed scale action — scale-up includes
    the replica's full grid warmup (the number that must stay small for
    autoscaling to matter; warm-started spawn via the compilation
    service is what keeps it small)."""
    if not _state.enabled:
        return
    histogram("mxnet_controller_scale_seconds",
              "Scale-action duration (up includes replica warmup).",
              ("direction",), buckets=STEP_BUCKETS
              ).labels(direction).observe(seconds)


def record_upgrade_replica(outcome: str) -> None:
    """Rolling-upgrade per-replica outcomes: ``ok`` (swapped and baked
    healthy), ``rolled_back`` (this replica's old model was restored),
    ``aborted`` (rollout stopped before touching this replica)."""
    if not _state.enabled:
        return
    counter("mxnet_serving_upgrade_total",
            "Rolling-upgrade replica outcomes.",
            ("outcome",)).labels(outcome).inc()


def record_data_wait(seconds: float, stage: str = "device_feed") -> None:
    """Time the consumer blocked waiting on an input-pipeline stage.

    The host-vs-device starvation discriminator: a real-data step whose
    ``mxnet_data_wait_seconds`` sum approaches wall time is host-starved
    (feed the device more); one near zero is device-bound (the pipeline
    keeps up)."""
    if not _state.enabled:
        return
    histogram("mxnet_data_wait_seconds",
              "Time the training loop blocked waiting for the input "
              "pipeline, by stage.", ("stage",)).labels(stage).observe(seconds)


def set_data_queue_depth(stage: str, depth: int) -> None:
    """Prefetched batches currently ready in a pipeline stage's queue."""
    if not _state.enabled:
        return
    gauge("mxnet_data_queue_depth",
          "Prefetched batches ready per input-pipeline stage.",
          ("stage",)).labels(stage).set(depth)


def record_images_decoded(n: int) -> None:
    """Images decoded+augmented by the host input pipeline."""
    if not _state.enabled or n <= 0:
        return
    counter("mxnet_data_decoded_images_total",
            "Images decoded and augmented by the input pipeline.").inc(n)


def record_serving_request(seconds: float, outcome: str = "ok",
                           trace_id: Optional[str] = None,
                           model: Optional[str] = None) -> None:
    """One served request, end-to-end (submit -> future resolved).
    ``outcome``: ``ok``, ``error`` (dispatch failed after retries) or
    ``rejected`` (queue full / server stopped — no latency recorded).
    p50/p99 come from the histogram quantiles. ``trace_id`` (when the
    request was traced) becomes an OpenMetrics exemplar on the latency
    bucket it lands in — the jump from "p99 is slow" to THE trace that
    explains it. ``model`` (multi-tenant serving) additionally counts
    the request into the per-tenant family
    ``mxnet_serving_tenant_requests_total{model,outcome}`` — the
    unlabeled family stays the fleet total, so existing dashboards and
    label sets are untouched."""
    if not _state.enabled:
        return
    counter("mxnet_serving_requests_total",
            "Serving requests by outcome (ok/error/rejected).",
            ("outcome",)).labels(outcome).inc()
    if model is not None:
        counter("mxnet_serving_tenant_requests_total",
                "Serving requests per tenant model, by outcome.",
                ("model", "outcome")).labels(model, outcome).inc()
    if outcome != "rejected":
        histogram("mxnet_serving_request_seconds",
                  "End-to-end request latency (submit to future "
                  "resolution).", buckets=SERVING_BUCKETS).observe(
            seconds,
            exemplar=({"trace_id": trace_id}
                      if trace_id is not None else None))


def record_serving_batch(n_real: int, capacity: int, reason: str) -> None:
    """One dispatched inference batch. ``reason``: what closed it —
    ``full`` (bucket capacity reached), ``deadline`` (oldest request
    neared its SLO), ``drain`` (server stopping)."""
    if not _state.enabled:
        return
    counter("mxnet_serving_batches_total",
            "Inference batches dispatched, by close reason "
            "(full/deadline/drain).", ("reason",)).labels(reason).inc()
    if capacity > 0:
        histogram("mxnet_serving_batch_occupancy",
                  "Real requests / padded bucket capacity per dispatched "
                  "batch.", buckets=OCCUPANCY_BUCKETS).observe(
                      n_real / capacity)
    pad = capacity - n_real
    if pad > 0:
        counter("mxnet_serving_padded_slots_total",
                "Padding rows dispatched to round batches up to their "
                "bucket.").inc(pad)


def record_serving_queue_time(seconds: float) -> None:
    """Time one request spent queued before its batch dispatched."""
    if not _state.enabled:
        return
    histogram("mxnet_serving_time_in_queue_seconds",
              "Time a request waited in the submission queue before "
              "batch dispatch.", buckets=SERVING_BUCKETS).observe(seconds)


def set_serving_queue_depth(depth: int) -> None:
    """Requests currently waiting in the server's submission queue."""
    if not _state.enabled:
        return
    gauge("mxnet_serving_queue_depth",
          "Requests waiting in the serving submission queue.").set(depth)


def record_serving_reload(seconds: float, outcome: str = "ok") -> None:
    """One hot-reload attempt (build + restore + warmup + swap)."""
    if not _state.enabled:
        return
    counter("mxnet_serving_reloads_total",
            "Model hot-reload attempts by outcome (ok/error).",
            ("outcome",)).labels(outcome).inc()
    if outcome == "ok":
        histogram("mxnet_serving_reload_seconds",
                  "Wall time to build, warm and swap in a reloaded "
                  "model.", buckets=STEP_BUCKETS).observe(seconds)


def record_router_request(seconds: float, outcome: str = "ok",
                          trace_id: Optional[str] = None) -> None:
    """One Router-level request resolution. A SEPARATE family from
    ``mxnet_serving_requests_total``: every routed request is also
    counted by the replica Server that served it, and after a failover
    the layers legitimately disagree (replica error, router ok) — one
    shared counter would double-count RPS and mix the two stories.
    ``trace_id`` rides along as an exemplar (see
    :func:`record_serving_request`)."""
    if not _state.enabled:
        return
    counter("mxnet_serving_router_requests_total",
            "Router requests by final outcome (ok/error/rejected).",
            ("outcome",)).labels(outcome).inc()
    if outcome != "rejected":
        histogram("mxnet_serving_router_request_seconds",
                  "End-to-end router request latency (submit to future "
                  "resolution).", buckets=SERVING_BUCKETS).observe(
            seconds,
            exemplar=({"trace_id": trace_id}
                      if trace_id is not None else None))


def record_serving_shed(reason: str, model: Optional[str] = None) -> None:
    """One request shed by admission control. ``reason``:
    ``queue_full`` (bounded queue at capacity), ``predicted_wait``
    (predicted queue wait exceeds the request's deadline), ``expired``
    (deadline blew while queued — the in-queue safety net),
    ``kvcache_full`` (a generate request that cannot fit the paged
    KV-cache budget) or ``throttled`` (a tenant's admission token
    bucket is empty). ``model`` additionally counts into
    ``mxnet_serving_tenant_shed_total{model,reason}`` — the isolation
    witness: under one tenant's overload, shed increments stay
    confined to that tenant's label."""
    if not _state.enabled:
        return
    counter("mxnet_serving_shed_total",
            "Requests shed by router admission control, by reason "
            "(queue_full/predicted_wait/expired/kvcache_full/"
            "throttled).",
            ("reason",)).labels(reason).inc()
    if model is not None:
        counter("mxnet_serving_tenant_shed_total",
                "Requests shed per tenant model, by reason.",
                ("model", "reason")).labels(model, reason).inc()


def record_decode_step(n_requests: int,
                       model: Optional[str] = None) -> None:
    """One continuous-batching decode step: a single (batch, 1)
    executable advancing ``n_requests`` co-batched completions by one
    token each. ``model`` counts the step into the per-tenant family
    ``mxnet_serving_tenant_decode_steps_total{model}``."""
    if not _state.enabled:
        return
    counter("mxnet_serving_decode_steps_total",
            "Autoregressive decode steps dispatched (one fused "
            "(batch, 1) executable per step).").inc()
    histogram("mxnet_serving_decode_batch_width",
              "Active completions co-batched per decode step.",
              buckets=(1, 2, 4, 8, 16, 32, 64)).observe(n_requests)
    if model is not None:
        counter("mxnet_serving_tenant_decode_steps_total",
                "Decode steps dispatched per tenant model.",
                ("model",)).labels(model).inc()


def record_block_round(denoise: int, commit: int, unmasked: int) -> None:
    """One decode round of a model that generates by diffusion over
    blocks: ``denoise`` streams ran a denoising step of their block,
    ``commit`` streams the forward that writes a finished block's keys
    and values into the cache, and the round unmasked ``unmasked``
    tokens in all. Forwards over tokens is what a token costs: 1 +
    1 / block_length where every step unmasks one token."""
    if not _state.enabled:
        return
    forwards = counter(
        "mxnet_diffusion_block_forwards_total",
        "Stream-forwards of block-diffusion decode rounds by kind "
        "(denoise: a denoising step of a block; commit: the forward "
        "that writes a finished block into the cache).", ("kind",))
    forwards.labels("denoise").inc(denoise)
    forwards.labels("commit").inc(commit)
    counter("mxnet_diffusion_tokens_unmasked_total",
            "Tokens unmasked by block-diffusion denoising steps.").inc(
                unmasked)


def record_round_phases(phases) -> None:
    """One decode round's scheduler-thread time by phase: ``phases`` is
    ``[(phase, seconds), ...]`` over ``wait`` / ``sched`` / ``build`` /
    ``launch`` / ``fetch`` / ``emit`` (the ``round.*`` spans of
    ``Server._decode_batch``, from the same clock readings).
    ``rate(phase seconds) / rate(mxnet_serving_decode_steps_total)`` is
    the host's time a round in that phase."""
    if not _state.enabled:
        return
    fam = counter("mxnet_serving_round_phase_seconds_total",
                  "Scheduler-thread seconds of decode rounds by phase "
                  "(wait/sched/build/launch/fetch/emit); the phases tile "
                  "the thread's time between rounds.", ("phase",))
    for phase, seconds in phases:
        # the epoch clock (the spans' and the device trace's) may step back
        fam.labels(phase).inc(max(seconds, 0.0))


def record_prefill_chunk(model: Optional[str] = None) -> None:
    """One chunk of a prompt longer than the server's largest length
    bucket was prefilled at its offset (a chunk a tick; the server's
    ``prefill`` span carries ``chunk``, ``chunks`` and ``offset``)."""
    if not _state.enabled:
        return
    counter("mxnet_prefill_chunks_total",
            "Chunks of long prompts prefilled against the cache, by "
            "tenant model.", ("model",)).labels(model or "default").inc()


def record_vision_encode(patches: int, padded: int) -> None:
    """One image went through a serving engine's vision tower: its live
    ``patches`` and the ``padded`` ones its patch-count bucket added."""
    if not _state.enabled:
        return
    counter("mxnet_vision_images_total",
            "Images encoded by a serving engine's vision tower.").inc()
    counter("mxnet_vision_patches_total",
            "Live patches of the images encoded.").inc(patches)
    counter("mxnet_vision_patches_padded_total",
            "Padding patches the patch-count buckets added to the images "
            "encoded.").inc(padded)


def record_dsa_keys(scored: int, selected: int, phase: str) -> None:
    """One dispatch of a model with learned sparse attention: over its
    real queries and attention layers, ``scored`` cached keys got an
    index score and ``selected`` of them were attended to. ``phase``:
    ``prefill`` or ``decode``."""
    if not _state.enabled:
        return
    counter("mxnet_dsa_keys_scored_total",
            "Cached keys the sparse-attention indexer scored (queries x "
            "keys visible to each, summed over layers), by phase.",
            ("phase",)).labels(phase).inc(scored)
    counter("mxnet_dsa_keys_selected_total",
            "Cached keys the sparse attention attended to after the "
            "top-k selection, by phase.", ("phase",)).labels(phase).inc(
                selected)


def set_state_slots(in_use: int, alloc: bool = False) -> None:
    """Per-stream state slots (``serving.kvcache.StateSlots``: what a
    stream of a recurrent or windowed model holds beside its pages) in
    use, the scratch slot not counted; ``alloc``: one was just taken."""
    if not _state.enabled:
        return
    gauge("mxnet_state_slots_in_use",
          "Per-stream state slots held by live streams.").set(in_use)
    if alloc:
        counter("mxnet_state_slot_allocs_total",
                "State slots handed to admitted streams.").inc()


def set_state_bytes_live(nbytes: float) -> None:
    """Bytes of recurrent state (an engine's slot arrays: scan or
    delta-rule states, convolution tails) the live streams hold now, and
    the most they have held (a gauge each: the first is back at a
    stream or two when a run ends)."""
    if not _state.enabled:
        return
    gauge("mxnet_state_bytes_live",
          "Bytes of per-stream recurrent state held by live streams."
          ).set(nbytes)
    peak = gauge("mxnet_state_bytes_live_peak",
                 "The most bytes of per-stream recurrent state live "
                 "streams have held at once.")
    if nbytes > peak._solo().value:
        peak.set(nbytes)


def record_shared_kv_read(tokens: int, phase: str) -> None:
    """One dispatch of a model whose cross-attention layers read ONE
    layer's cached keys and values: ``tokens`` = the live cached tokens
    of its reading rows x the layers that read them."""
    if not _state.enabled:
        return
    counter("mxnet_shared_kv_tokens_read_total",
            "Cached tokens of the one shared K/V cache read (live tokens "
            "of a dispatch's reading rows x reading layers), by phase.",
            ("phase",)).labels(phase).inc(tokens)


def record_prefill_rows(self_rows: int, cross_rows: int) -> None:
    """One prefill dispatch of a model that runs its cross-decoder on a
    prompt's last token only: real rows through the self-decoder
    (``part="self"``) and through the cross-decoder (``part="cross"``)."""
    if not _state.enabled:
        return
    c = counter("mxnet_prefill_rows_total",
                "Real token rows a prefill dispatch ran through the "
                "self-decoder and through the cross-decoder.", ("part",))
    c.labels("self").inc(self_rows)
    c.labels("cross").inc(cross_rows)


def record_host_fetch(n_bytes: int, phase: str) -> None:
    """One generate dispatch (``phase`` ``prefill`` or ``decode``)
    brought ``n_bytes`` of its result from the device to the host: 4 a
    row when the greedy pick is made on the device, 4 x vocab a row
    if logits crossed."""
    if not _state.enabled:
        return
    counter("mxnet_serving_host_fetch_bytes_total",
            "Bytes of generate dispatches' results fetched from the "
            "device to the host, by phase (prefill/decode).",
            ("phase",)).labels(phase).inc(n_bytes)


def record_prefill_dispatch(path: str) -> None:
    """One forward of more than one position by an engine that reads
    its attention off the positions (``LlamaDecodeEngine``,
    ``DotsVlmDecodeEngine``). ``path``:
    ``fresh`` (every row starts at position 0: the layers attend over
    the dispatch's own keys and values, causal) or ``gather`` (a forward
    at an offset: the layers gather every slot the page table reaches)."""
    if not _state.enabled:
        return
    counter("mxnet_serving_prefill_dispatch_total",
            "Prefill dispatches by attention path (fresh: over the "
            "dispatch's own keys and values; gather: through the page "
            "table).", ("path",)).labels(path).inc()


def record_moe_picks(held: int, zero: int, absent: int, touched: int,
                     n_layers: int, phase: str = "decode") -> None:
    """One dispatch of a model with routed experts of which only some
    live on this chip. ``held`` / ``zero`` / ``absent``: (token, expert)
    picks, summed over the ``n_layers`` expert layers, that went to
    experts held here, to zero-compute (identity) experts, and to
    experts on other chips; ``touched``: held experts that got at least
    one token, summed over layers. ``phase``: ``prefill`` or ``decode``.
    ``mxnet_moe_held_experts_touched`` is observed per decode dispatch
    with the mean over layers."""
    if not _state.enabled:
        return
    picks = counter("mxnet_moe_picks_total",
                    "Expert picks by destination (held here / zero-compute "
                    "/ absent: on another chip) and phase.",
                    ("to", "phase"))
    picks.labels("held", phase).inc(held)
    picks.labels("zero", phase).inc(zero)
    picks.labels("absent", phase).inc(absent)
    counter("mxnet_moe_layer_calls_total",
            "Expert-layer executions (layers x dispatches) by phase.",
            ("phase",)).labels(phase).inc(n_layers)
    if phase == "decode" and n_layers > 0:
        histogram("mxnet_moe_held_experts_touched",
                  "Held experts that got a token, mean over the expert "
                  "layers of one decode dispatch.",
                  buckets=(1, 2, 4, 8, 12, 14, 16, 32, 64)).observe(
                      touched / n_layers)
    counter("mxnet_moe_held_experts_touched_total",
            "Held experts that got a token, summed over expert layers "
            "and dispatches, by phase.", ("phase",)).labels(phase).inc(touched)


def record_token(seconds: float, model: Optional[str] = None) -> None:
    """One emitted token's inter-token latency (prefill first token:
    submit -> first token, i.e. TTFT). ``model`` counts the token into
    ``mxnet_serving_tenant_tokens_total{model}`` — per-tenant token
    share is the weighted-fairness witness."""
    if not _state.enabled:
        return
    counter("mxnet_serving_tokens_total",
            "Tokens emitted by autoregressive decode (prefill first "
            "tokens included).").inc()
    histogram("mxnet_serving_token_seconds",
              "Per-token latency: time since the previous token of the "
              "same completion (first token: since submit — TTFT).",
              buckets=SERVING_BUCKETS).observe(seconds)
    if model is not None:
        counter("mxnet_serving_tenant_tokens_total",
                "Tokens emitted per tenant model.",
                ("model",)).labels(model).inc()


def set_tenant_queue_depth(depth: int, model: str,
                           router: str = "") -> None:
    """Requests currently queued for ONE tenant model (replica level
    when ``router`` is empty, router level otherwise). Scraped into
    :class:`~.serving.controller.ScrapeFleetSignals` so the autoscaler
    sees per-tenant backlog, not just the fleet total."""
    if not _state.enabled:
        return
    gauge("mxnet_serving_tenant_queue_depth",
          "Requests waiting per tenant model (replica queues when "
          "router label is empty, router queue otherwise).",
          ("model", "router")).labels(model, router).set(depth)


def record_preemption(victim: str, beneficiary: str) -> None:
    """One priority preemption: ``victim``'s stream had its KV-cache
    pages reclaimed (between decode steps) for a higher-priority
    ``beneficiary`` arrival. Both are tenant model names — the counter
    answers "who preempted whom"."""
    if not _state.enabled:
        return
    counter("mxnet_serving_preempted_total",
            "Generate streams preempted, by victim and beneficiary "
            "tenant model.",
            ("victim", "beneficiary")).labels(victim, beneficiary).inc()


def record_kvcache_defrag(n_moves: int) -> None:
    """One automatic KV-cache defrag pass (pages packed between decode
    steps when fragmentation crossed the server's threshold)."""
    if not _state.enabled:
        return
    counter("mxnet_serving_kvcache_defrag_total",
            "Automatic KV-cache defrag passes.").inc()
    if n_moves > 0:
        counter("mxnet_serving_kvcache_defrag_moves_total",
                "Pages moved by automatic KV-cache defrag passes."
                ).inc(n_moves)


def set_kvcache_pages(free: int, used: int, reserved: int = 0) -> None:
    """Paged KV-cache arena occupancy, by page state."""
    if not _state.enabled:
        return
    g = gauge("mxnet_serving_kvcache_pages",
              "KV-cache arena pages by state (free/used/reserved).",
              ("state",))
    g.labels("free").set(free)
    g.labels("used").set(used)
    g.labels("reserved").set(reserved)


def record_serving_failover(replica: str) -> None:
    """One request re-submitted away from a failed/hung replica."""
    if not _state.enabled:
        return
    counter("mxnet_serving_failover_total",
            "Requests failed over from a replica to a healthy sibling.",
            ("replica",)).labels(replica).inc()


def record_serving_route_retry(reason: str) -> None:
    """One routing retry event at the Router. ``reason``:
    ``route_fault`` (injected/transient routing failure),
    ``replica_error`` (dispatch failed at the replica),
    ``replica_down`` (replica stopped between health check and submit),
    ``hung`` (dispatch exceeded the dispatch timeout), ``refused``
    (replica queue refused the submit — retried, no budget burned)."""
    if not _state.enabled:
        return
    counter("mxnet_serving_route_retry_total",
            "Router routing retries, by reason (route_fault/"
            "replica_error/replica_down/hung/refused).",
            ("reason",)).labels(reason).inc()


def record_router_queue_wait(seconds: float) -> None:
    """Time one request spent in the ROUTER queue before being
    forwarded to a replica (replica queue time is
    ``mxnet_serving_time_in_queue_seconds``)."""
    if not _state.enabled:
        return
    histogram("mxnet_serving_router_queue_wait_seconds",
              "Time a request waited in the router queue before being "
              "forwarded to a replica.",
              buckets=SERVING_BUCKETS).observe(seconds)


def set_router_queue_depth(depth: int, router: str = "") -> None:
    """Requests currently waiting in the Router's global queue
    (labeled per router — see :func:`set_fleet_size`)."""
    if not _state.enabled:
        return
    gauge("mxnet_serving_router_queue_depth",
          "Requests waiting in the serving router's global queue.",
          ("router",)).labels(router).set(depth)


def set_replica_health(replica: str, value: float) -> None:
    """Per-replica health gauge: 1 = closed (healthy), 0.5 = half-open
    (probing), 0 = open (quarantined)."""
    if not _state.enabled:
        return
    gauge("mxnet_serving_replica_healthy",
          "Replica circuit-breaker health (1 closed / 0.5 half-open / "
          "0 open).", ("replica",)).labels(replica).set(value)


def record_breaker_transition(replica: str, to_state: str) -> None:
    """One circuit-breaker state transition observed by the router."""
    if not _state.enabled:
        return
    counter("mxnet_serving_breaker_transitions_total",
            "Replica circuit-breaker state transitions, by target "
            "state.", ("replica", "to")).labels(replica, to_state).inc()


def record_worker_restart(replica: str, outcome: str = "ok") -> None:
    """One worker-process respawn by the :class:`RemoteReplica`
    supervisor. ``outcome="ok"`` counts a successful restart
    (``mxnet_worker_restarts_total{replica}``); ``"failed"`` counts a
    spawn attempt that raised and re-entered backoff (a separate
    family — a flapping spawn path must not read as recoveries)."""
    if not _state.enabled:
        return
    if outcome == "ok":
        counter("mxnet_worker_restarts_total",
                "Successful worker-process respawns by replica.",
                ("replica",)).labels(replica).inc()
    else:
        counter("mxnet_worker_respawn_failures_total",
                "Failed worker respawn attempts by replica (retried "
                "with exponential backoff).", ("replica",)
                ).labels(replica).inc()


def set_ingress_connections(state: str, n: int) -> None:
    """Current ingress connection gauge. ``state``: ``open`` (accepted,
    connected) or ``busy`` (with >= 1 request in flight)."""
    if not _state.enabled:
        return
    gauge("mxnet_ingress_connections",
          "Ingress connections by state (open/busy).",
          ("state",)).labels(state).set(n)


def record_ingress_rejected(reason: str) -> None:
    """One request rejected at the ingress with a typed error frame.
    ``reason``: ``window_full`` (per-connection backpressure),
    ``overloaded`` (router admission shed), ``failover_exhausted``,
    ``connection_limit``, ``bad_frame`` (corrupt/torn stream),
    ``fault`` (injected ``serving.ingress`` fault), ``error``."""
    if not _state.enabled:
        return
    counter("mxnet_ingress_rejected_total",
            "Requests rejected at the ingress by reason.",
            ("reason",)).labels(reason).inc()


def record_ingress_request(seconds: float, outcome: str = "ok",
                           trace_id: Optional[str] = None) -> None:
    """One ingress request resolved end-to-end (frame in -> result
    frame out). ``outcome``: ``ok``, ``error`` (typed error frame), or
    ``undeliverable`` (resolved after the client disconnected).
    ``trace_id`` rides along as an exemplar (see
    :func:`record_serving_request`)."""
    if not _state.enabled:
        return
    counter("mxnet_ingress_requests_total",
            "Ingress requests by outcome (ok/error/undeliverable).",
            ("outcome",)).labels(outcome).inc()
    histogram("mxnet_ingress_request_seconds",
              "Ingress request latency (submit frame received to "
              "result frame written).",
              buckets=SERVING_BUCKETS).observe(
        seconds,
        exemplar=({"trace_id": trace_id}
                  if trace_id is not None else None))


def set_router_inflight(n: int, router: str = "") -> None:
    """Requests the Router has forwarded to replicas and not yet
    resolved — the scrape-fed utilization numerator (labeled per
    router — see :func:`set_fleet_size`)."""
    if not _state.enabled:
        return
    gauge("mxnet_serving_router_inflight",
          "Router requests forwarded to replicas, unresolved.",
          ("router",)).labels(router).set(n)


def set_predicted_wait(seconds: float, router: str = "") -> None:
    """The Router admission controller's current predicted queue wait
    (0 when unarmed) — the scrape-fed autoscaler's scale-up signal
    (labeled per router — see :func:`set_fleet_size`)."""
    if not _state.enabled:
        return
    gauge("mxnet_serving_predicted_wait_seconds",
          "Admission controller's predicted completion wait for a "
          "request submitted now (0 = no estimate/unarmed).",
          ("router",)).labels(router).set(seconds)


def record_training_step(seconds: float, examples: float,
                         mfu_pct: Optional[float] = None) -> None:
    if not _state.enabled:
        return
    counter("mxnet_training_steps_total", "Completed training steps.").inc()
    counter("mxnet_training_examples_total",
            "Examples consumed by training steps.").inc(examples)
    histogram("mxnet_training_step_seconds", "Training step wall time.",
              buckets=STEP_BUCKETS).observe(seconds)
    if seconds > 0:
        gauge("mxnet_training_examples_per_sec",
              "Throughput of the most recent training step.").set(
                  examples / seconds)
    if mfu_pct is not None:
        gauge("mxnet_training_mfu_pct",
              "Model-FLOP utilization of the most recent step (percent)."
              ).set(mfu_pct)


# ---------------------------------------------------------------------------
# Training-step observability
# ---------------------------------------------------------------------------

def xla_cost_analysis(step, batch) -> Dict[str, float]:
    """Static cost analysis of a TrainStep's compiled executable.

    The compiler's FLOP accounting of one step: mirror
    ``TrainStep.__call__``'s argument assembly, lower the cached
    executable, and return XLA's ``compiled.cost_analysis()`` dict —
    ``'flops'`` is the compiler's own static per-step FLOP count.

    .. warning:: This EXECUTES one real training step on ``batch`` to
       populate the step's executable cache: parameters, optimizer state,
       ``optimizer.num_update`` and the RNG stream all advance by one
       update. Call it before training starts (a warmup batch), not
       mid-run.
    """
    import numpy as np

    import jax
    from . import random_state
    from .parallel.step import _as_tuple

    loss, _ = step(*batch)
    loss.asnumpy()
    data_tuple = _as_tuple(batch[0])
    label_tuple = _as_tuple(batch[1]) if len(batch) > 1 else ()
    entry = next(iter(step._cache.values()))
    jitted = entry["jitted"]
    optimizer = step.optimizer
    t = np.int32(optimizer.num_update)
    lr = np.float32(optimizer.learning_rate)
    rng = random_state.get_state_key()
    param_vals = tuple(p.data().data for p in step._params)
    state_vals = tuple(s.data for s in step._state_leaf_nds)
    batch_vals = [jax.device_put(v.data, sh)
                  for v, sh in zip(tuple(data_tuple) + tuple(label_tuple),
                                   entry["batch_sh"])]
    with step.tracing():
        lowered = jitted.lower(param_vals, state_vals, t, lr, rng,
                               *batch_vals)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return ca


class TrainingTelemetry:
    """Per-step observability hook for Gluon/Module training loops.

    Records step wall time, examples/sec and an MFU estimate into the
    telemetry registry (when enabled) and keeps the latest values as
    attributes (always), so it is usable standalone::

        tt = telemetry.TrainingTelemetry(batch_size=256,
                                         flops_per_step=fl, peak_flops=pk)
        for x, y in loader:
            with tt.step():
                loss, _ = train_step(x, y)
        print(tt.last_examples_per_sec, tt.last_mfu_pct)

    ``Module.fit``-style loops attach it as a batch-end callback
    (``batch_end_callback=tt.batch_end`` — step time is measured between
    consecutive calls, reference ``BatchEndParam`` contract).

    FLOP accounting: pass ``flops_per_step`` (e.g. from
    :func:`xla_cost_analysis`'s ``'flops'``) or ``flops_per_sample``
    (6ND-style);
    :meth:`for_step` derives it from a TrainStep via the compiler. The MFU
    denominator is ``peak_flops`` or ``callback.device_peak_flops() x
    num_devices`` (None on hosts with no known peak — MFU is skipped then).
    """

    def __init__(self, batch_size: int, flops_per_step: Optional[float] = None,
                 flops_per_sample: Optional[float] = None,
                 num_devices: Optional[int] = None,
                 peak_flops: Optional[float] = None):
        self.batch_size = batch_size
        self.flops_per_step = flops_per_step
        if flops_per_step is None and flops_per_sample is not None:
            self.flops_per_step = flops_per_sample * batch_size
        self._num_devices = num_devices
        self._peak = peak_flops
        self._peak_resolved = peak_flops is not None
        self._t0: Optional[float] = None
        self._last_batch_end: Optional[float] = None
        self.steps = 0
        self.last_step_seconds: Optional[float] = None
        self.last_examples_per_sec: Optional[float] = None
        self.last_mfu_pct: Optional[float] = None

    @classmethod
    def for_step(cls, step, batch, batch_size: int, **kwargs
                 ) -> "TrainingTelemetry":
        """Build with ``flops_per_step`` read from XLA's cost analysis of
        ``step``'s compiled executable. Note this runs one REAL optimizer
        update on ``batch`` (see :func:`xla_cost_analysis`) — use it
        during setup, counting ``batch`` as a consumed warmup step."""
        ca = xla_cost_analysis(step, batch)
        flops = float(ca.get("flops", 0.0)) or None
        return cls(batch_size, flops_per_step=flops, **kwargs)

    # -- explicit step timing -----------------------------------------
    def step_begin(self) -> None:
        self._t0 = time.perf_counter()

    def step_end(self) -> None:
        if self._t0 is None:
            return
        self._observe(time.perf_counter() - self._t0)
        self._t0 = None

    class _StepScope:
        __slots__ = ("tt",)

        def __init__(self, tt):
            self.tt = tt

        def __enter__(self):
            self.tt.step_begin()
            return self.tt

        def __exit__(self, *exc):
            self.tt.step_end()
            return False

    def step(self) -> "_StepScope":
        """Context manager timing one training step."""
        return self._StepScope(self)

    # -- Module.fit / BatchEndParam adapter ---------------------------
    def batch_end(self, param=None) -> None:
        """Batch-end callback: step time = time since the previous call
        (the first call only arms the clock)."""
        now = time.perf_counter()
        if getattr(param, "nbatch", None) == 0:
            # first batch of an epoch (reference BatchEndParam: nbatch
            # resets per epoch): the gap since the previous call spans
            # validation/checkpointing, not a training step — re-arm
            self._last_batch_end = now
            return
        if self._last_batch_end is not None:
            self._observe(now - self._last_batch_end)
        self._last_batch_end = now

    __call__ = batch_end

    # -- internals ----------------------------------------------------
    def _resolve_peak(self) -> Optional[float]:
        if not self._peak_resolved:
            from .callback import device_peak_flops

            per_chip = device_peak_flops()
            if per_chip:
                if self._num_devices is None:
                    import jax

                    self._num_devices = jax.device_count()
                self._peak = per_chip * self._num_devices
            self._peak_resolved = True
        return self._peak

    def _observe(self, dt: float) -> None:
        self.steps += 1
        self.last_step_seconds = dt
        self.last_examples_per_sec = self.batch_size / dt if dt > 0 else None
        mfu = None
        if self.flops_per_step and dt > 0:
            peak = self._resolve_peak()
            if peak:
                mfu = 100.0 * self.flops_per_step / (dt * peak)
        self.last_mfu_pct = mfu
        record_training_step(dt, self.batch_size, mfu)
