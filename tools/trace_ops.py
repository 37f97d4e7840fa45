"""Per-op device-time breakdown of a compiled step from a jax.profiler trace.

Usage:
  python tools/trace_ops.py bert   # trace bench_bert's TrainStep
  python tools/trace_ops.py resnet # trace bench.py's TrainStep
  python tools/trace_ops.py bert 40 --telemetry-out /tmp/telemetry.json
                                   # also dump an mx.telemetry snapshot
                                   # (op mix, jit-cache hit/miss)

Captures a few steps under jax.profiler.trace, parses the perfetto
trace.json.gz, and prints device ops aggregated by fusion-name prefix,
sorted by total time. The methodology behind PERF_HISTORY.md's trace tables.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_bert_step():
    # trace the published bench configuration: fused layer kernels ON
    # (bench_bert.py sets the same default)
    os.environ.setdefault("MXNET_PALLAS_FUSED", "1")
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.nlp import bert

    # defaults track bench_bert.py so the trace profiles the published
    # configuration
    batch, seq = int(os.environ.get("BENCH_BERT_BATCH", 32)), 512
    rs = np.random.RandomState(0)
    tokens = mx.nd.array(rs.randint(0, 30000, (batch, seq)).astype(np.int32))
    labels = mx.nd.array(rs.randint(0, 30000, (batch, seq)).astype(np.float32))

    class MLMLoss(gloss.SoftmaxCrossEntropyLoss):
        def hybrid_forward(self, F, pred, label):
            return super().hybrid_forward(
                F, pred.reshape(-1, pred.shape[-1]), label.reshape(-1))

    class LossAdapter:
        def __init__(self):
            self._l = MLMLoss()

        def __call__(self, outs, label):
            mlm = outs[1] if isinstance(outs, (list, tuple)) else outs
            return self._l(mlm, label)

    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    if os.environ.get("BENCH_BERT_FUSED", "1") != "0":
        net = bert.BERTForPretrainFused(
            dropout=0.1,
            chunk=int(os.environ.get("BENCH_BERT_CHUNK", 5120)))
        net.initialize()
        net.cast("bfloat16")
        labels_i = mx.nd.array(labels.asnumpy().astype(np.int32))
        step = par.TrainStep(net, lambda outs, *a: outs, "adam", mesh=mesh,
                             loss_only=True,
                             optimizer_params={"learning_rate": 1e-4,
                                               "multi_precision": True})
        return step, ((tokens, labels_i), ())
    net = bert.bert_12_768_12(use_decoder=True, use_pooler=False,
                              use_classifier=False)
    net.initialize()
    net.cast("bfloat16")
    step = par.TrainStep(net, LossAdapter(), "adam", mesh=mesh,
                         optimizer_params={"learning_rate": 1e-4,
                                           "multi_precision": True})
    return step, (tokens, labels)


def build_llama_step():
    """The 0.7B proxy exactly as bench_llama.py runs it (no-remat,
    fused CE, AdamW, bf16) — VERDICT r4: trace the Llama path the way
    BERT was traced."""
    os.environ.setdefault("MXNET_PALLAS_FUSED", "1")
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo.nlp.llama import LlamaModel

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from pretrain_llama import CONFIGS

    batch, seq = int(os.environ.get("BENCH_LLAMA_BATCH", 8)), 2048
    cfg = CONFIGS["proxy1b"]
    raw = os.environ.get("LLAMA_REMAT", "").lower()
    remat = (True if raw in ("1", "true", "yes") else
             False if raw in ("", "0", "false", "no") else raw)
    net = LlamaModel(**cfg, remat=remat, fused_ce=True)
    net.initialize()
    net.cast("bfloat16")
    rs = np.random.RandomState(0)
    toks = mx.nd.array(rs.randint(0, cfg["vocab_size"],
                                  (batch, seq)).astype(np.int32))
    labs = mx.nd.array(rs.randint(0, cfg["vocab_size"],
                                  (batch, seq)).astype(np.int32))
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = par.TrainStep(net, lambda outs, *a: outs, "adamw", mesh=mesh,
                         loss_only=True,
                         optimizer_params={"learning_rate": 3e-4,
                                           "wd": 0.1, "beta1": 0.9,
                                           "beta2": 0.95,
                                           "multi_precision": True})
    return step, ((toks, labs), ())


def build_resnet_step():
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    batch = 256
    net = resnet50_v1(classes=1000,
                      layout=os.environ.get("RESNET_LAYOUT", "NHWC"))
    net.initialize()
    net.cast("bfloat16")
    rs = np.random.RandomState(0)
    images = mx.nd.array(rs.uniform(-1, 1, (batch, 3, 224, 224)).astype(
        np.float32)).astype("bfloat16")
    labels = mx.nd.array(rs.randint(0, 1000, (batch,)).astype(np.float32))
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         mesh=mesh,
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9,
                                           "multi_precision": True})
    return step, (images, labels)


GROUPS = [
    # first so ops dispatched via an engine.bulk fused segment (the jitted
    # module is named "fused_segment", see ops/registry.py::_build_fused)
    # are attributed to bulking rather than the generic fusion bucket
    ("bulk_fused", r"fused_segment"),
    # fused layer kernels (pallas_kernels/fused_layers.py) before the
    # flash groups: their kernel names also contain _fwd/_bwd
    ("pallas_layer", r"_norm_fwd_kernel|_norm_bwd_kernel|_bias_gelu"),
    ("flash_fwd", r"flash|_fwd_kernel"),
    ("flash_bwd", r"dkdv|_bwd_"),
    ("fusion", r"^fusion"),
    ("copy", r"^copy|^bitcast"),
    ("dot", r"^dot|convolution"),
    ("custom-call", r"custom-call"),
    ("transpose", r"transpose"),
    ("rng", r"rng"),
]

# device ops executed by ANY Pallas kernel of ours — tagged "[pallas] "
# in the per-op table (like "[bulk] " for fused segments) so kernel
# adoption is visible straight in the trace, next to the
# mxnet_pallas_dispatch_total{kernel} telemetry counter
PALLAS_PAT = re.compile(
    r"_norm_fwd_kernel|_norm_bwd_kernel|_bias_gelu|_fwd_kernel"
    r"|_bwd_dkdv|_bwd_dq|_bwd_fused|flash")


def classify(name, ctx=""):
    # only the bulk group consults the HLO metadata ctx: the module name
    # lives there, whereas matching every group's pattern against ctx
    # would misbin ops whose OPERAND names mention e.g. "transpose"
    if ctx and re.search(r"fused_segment", ctx):
        return "bulk_fused"
    for g, pat in GROUPS:
        if re.search(pat, name):
            return g
    return "other"


def _event_ctx(e):
    """Trace-event metadata that carries the owning jit module / HLO
    provenance (XLA puts the module name in args, not the event name)."""
    args = e.get("args") or {}
    return " ".join(str(args[k]) for k in ("long_name", "tf_op", "source",
                                           "group_by", "hlo_module")
                    if k in args)


def main():
    from mxnet_tpu.telemetry import pop_telemetry_out_flag

    argv, telemetry_out = pop_telemetry_out_flag(sys.argv[1:])
    which = argv[0] if argv else "bert"
    topn = int(argv[1]) if len(argv) > 1 else 40
    import jax

    if telemetry_out:
        from mxnet_tpu import telemetry

        telemetry.enable()

    step, batch = {"bert": build_bert_step, "resnet": build_resnet_step,
                   "llama": build_llama_step}[which]()
    loss, _ = step(*batch)
    loss.asnumpy()
    step.stage_batch(*batch)
    loss, _ = step(*batch)
    loss.asnumpy()

    tdir = os.environ.get("TRACE_DIR") or tempfile.mkdtemp(prefix="mxtrace_")
    nsteps = 3
    with jax.profiler.trace(tdir):
        for _ in range(nsteps):
            loss, _ = step(*batch)
        loss.asnumpy()

    traces = glob.glob(os.path.join(tdir, "**", "*.trace.json.gz"),
                       recursive=True)
    if not traces:
        print("no trace.json.gz found under", tdir)
        return 1
    with gzip.open(sorted(traces)[-1], "rt") as f:
        data = json.load(f)

    # device-side complete events: pick the pid whose thread names look like
    # TPU/device lanes ("/device:" or "XLA Op" tracks carry the op names)
    events = [e for e in data.get("traceEvents", []) if e.get("ph") == "X"]
    pid_names = {}
    for e in data.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
    dev_pids = {p for p, n in pid_names.items()
                if "TPU" in n or "/device" in n.lower() or "gpu" in n.lower()}
    dev_events = [e for e in events if e["pid"] in dev_pids]
    if not dev_events:
        # fall back: everything that is not a python/host thread
        dev_events = events

    per_op = collections.Counter()
    per_group = collections.Counter()
    total = 0.0
    for e in dev_events:
        name = e.get("name", "?")
        dur = e.get("dur", 0) / 1e3  # us -> ms
        # skip obvious host-side module-level events
        if name.startswith(("jit_", "Thread", "pjit")):
            continue
        ctx = _event_ctx(e)
        if "fused_segment" in name or "fused_segment" in ctx:
            # executed via an engine.bulk fused segment — mark it so the
            # per-op table shows which device time came from bulked
            # imperative chains vs ordinary per-op dispatch
            name = "[bulk] " + name
        elif PALLAS_PAT.search(name) or PALLAS_PAT.search(ctx):
            # executed by one of our Pallas kernels (flash attention or
            # the fused layer kernels) — adoption visible per-op
            name = "[pallas] " + name
        per_op[name] += dur
        per_group[classify(name, ctx)] += dur
        total += dur

    print(f"== {which}: {nsteps} steps, device op time total "
          f"{total:.1f} ms ({total / nsteps:.1f} ms/step) ==")
    print("-- by group (ms/step) --")
    for g, t in per_group.most_common():
        print(f"  {g:12s} {t / nsteps:8.2f}")
    print(f"-- top {topn} ops (ms/step) --")
    for name, t in per_op.most_common(topn):
        print(f"  {t / nsteps:8.3f}  {name[:110]}")
    print("trace dir:", tdir)
    if telemetry_out:
        from mxnet_tpu import telemetry

        telemetry.write_snapshot(telemetry_out)
        print("telemetry snapshot:", telemetry_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
