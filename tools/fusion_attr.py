"""Attribute opaque trace fusion names to HLO contents (conv/dot/reduce).

The per-op names in a TPU perfetto trace are XLA fusion instruction names
(``fusion.48``) that mean nothing on their own. This tool AOT-compiles the
same step the trace profiled, maps each fusion instruction to the ops its
called computation contains, and joins that against the trace's per-op
device times — the methodology behind PERF_HISTORY.md's round-4 conv-attribution
table (which found the "conv-bwd" cost was mostly fused BatchNorm-backward
arithmetic).

Usage:
  python tools/fusion_attr.py resnet /tmp/mxtrace_dir   # build+compile+join
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def parse_hlo(txt):
    """fusion-instruction name -> {kinds, conv signatures, big shapes}."""
    calls = {}
    for m in re.finditer(
            r'%([\w\.\-]+) = [^\n]*? fusion\([^\n]*?calls=%([\w\.\-]+)', txt):
        calls[m.group(1)] = m.group(2)

    comp_info = collections.defaultdict(
        lambda: {"convs": [], "dots": 0, "reduces": 0, "kinds": set()})
    cur = None
    for line in txt.splitlines():
        s = line.strip()
        m = re.match(r'%([\w\.\-]+) \([^)]*\) -> ', s)
        if m and s.endswith("{"):
            cur = m.group(1)
        if cur is None:
            continue
        if " convolution(" in s:
            out = re.match(r'%[\w\.\-]+ = (\S+?)\{', s)
            win = re.search(r'window=\{([^}]*)\}', s)
            dl = re.search(r'dim_labels=(\S+?)(,|$)', s)
            comp_info[cur]["convs"].append({
                "out": out.group(1) if out else "?",
                "window": win.group(1) if win else "",
                "dl": dl.group(1) if dl else "",
            })
            comp_info[cur]["kinds"].add("conv")
        elif re.search(r'= \S+ dot\(', s):
            comp_info[cur]["dots"] += 1
            comp_info[cur]["kinds"].add("dot")
        elif re.search(r'= \S+ reduce\(', s):
            comp_info[cur]["reduces"] += 1
            comp_info[cur]["kinds"].add("reduce")
    return calls, comp_info


def classify_conv(c):
    dl, w = c["dl"], c["window"]
    lhs = dl.split("->")[0].split("_")[0]
    if re.search(r'f01b|01bf', lhs) or "->fb01" in dl or "->bf01" in dl:
        return "dW"
    if "_io01" in dl or "rhs_reversal" in w or "lhs_dilate" in w:
        return "dX"
    return "fwd"


def trace_times(tdir):
    tr = sorted(glob.glob(os.path.join(tdir, "**", "*.trace.json.gz"),
                          recursive=True))[-1]
    with gzip.open(tr, "rt") as f:
        data = json.load(f)
    per_op = collections.Counter()
    for e in data.get("traceEvents", []):
        if e.get("ph") == "X":
            n = e.get("name", "")
            # host-side python/runtime frames leak into the event stream;
            # XLA device ops never contain source locations or $-frames
            if (n.startswith(("jit_", "Thread", "pjit", "$", "np.", "Pjit"))
                    or ".py:" in n or " " in n):
                continue
            per_op[n] += e.get("dur", 0) / 1e3
    return per_op


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    tdir = sys.argv[2]
    nsteps = int(os.environ.get("TRACE_NSTEPS", "3"))
    import trace_ops

    step, batch = {"bert": trace_ops.build_bert_step,
                   "resnet": trace_ops.build_resnet_step,
                   "llama": trace_ops.build_llama_step}[which]()
    if which in ("bert", "llama"):
        compiled = step.aot_compile(*batch)
    else:
        data, label = batch
        compiled = step.aot_compile((data,), (label,))
    txt = compiled.as_text()
    calls, comp_info = parse_hlo(txt)
    per_op = trace_times(tdir)

    by_class = collections.Counter()
    by_sig = collections.Counter()
    rows = []
    for name, t in per_op.items():
        comp = calls.get(name)
        info = comp_info.get(comp) if comp else None
        if info and info["convs"]:
            k = classify_conv(info["convs"][0])
            out = info["convs"][0]["out"].split("{")[0]
            w = info["convs"][0]["window"][:28]
            key = f"conv:{k}"
        elif info and "dot" in info["kinds"]:
            k, out, w = "dot", "", ""
            key = "dot"
        elif info and info["reduces"]:
            k, out, w = f'reduce x{info["reduces"]}', "", ""
            key = "reduce"
        elif info is not None:
            k, out, w = "elementwise", "", ""
            key = "elementwise"
        else:
            k, out, w = "?", "", ""
            key = "unfused/" + re.sub(r'[\d\.]+$', "", name)
        by_class[key] += t / nsteps
        by_sig[(k, out, w)] += t / nsteps
        rows.append((t / nsteps, name, k, out, w))

    rows.sort(reverse=True)
    print(f"-- by class (ms/step over {nsteps} steps) --")
    for k, v in by_class.most_common(15):
        print(f"  {k:28s} {v:8.2f}")
    print("\n-- by (kind, conv out, window) --")
    for (k, out, w), v in by_sig.most_common(30):
        print(f"{v:7.2f}  {k:10s} {out:26s} {w}")
    print("\n-- top fusions --")
    for t, name, k, out, w in rows[:25]:
        print(f"{t:7.3f}  {name:28s} {k:8s} {out} {w}")


if __name__ == "__main__":
    main()
