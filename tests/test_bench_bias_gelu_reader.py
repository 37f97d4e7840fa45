"""``bias_gelu_ms_per_step``: the reader on a synthetic trace, nothing
where there is nothing to read, and its place in ``BENCHMARK.json``."""
import json
import os

import pytest

from benchmarks.layer_metrics.bias_gelu_ms_per_step import read as _read
from benchmarks.lib import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1e6
CALL = ('%{name}.{n} = bf16[16384,3072]{{1,0}} custom-call(bf16[16384,3072]'
        '{{1,0}} %a, bf16[1,3072]{{1,0}} %b), '
        'custom_call_target="tpu_custom_call"')


# the custom calls' HLO names: under the op's own jit (the parent commit)
# and under the jitted kernel wrapper (this tree), forward then backward
OP_JIT = ("jvp_jit__contrib_fused_bias_gelu__",
          "transpose_jvp_jit__contrib_fused_bias_gelu__")
WRAPPER_JIT = ("_fused_bias_gelu_pallas", "_fused_bias_gelu_pallas")


def _inputs(steps=3, names=WRAPPER_JIT):
    """Per step one forward site (0.4 ms), one backward site (0.6 ms), a
    flash attention call and an XLA fusion that names the op it fused."""
    events, at = [], 0.0
    for n in range(steps):
        for name, ms, text in (
                (names[0], 0.4, CALL), (names[1], 0.6, CALL),
                ("jvp_jit__contrib_sdp_attention__", 2.0, CALL),
                ("fusion", 5.0, "%fusion.{n} = bf16[8] fusion(...), "
                 "calls=%fused_bias_gelu_computation")):
            events.append(trace_reduce.Event(
                f"{name}.{n}", at, ms * MS, text.format(name=name, n=n)))
            at += ms * MS
    return {"trace": trace_reduce.Trace(devices={0: events}),
            "trace_steps": steps}


@pytest.mark.parametrize("names", [OP_JIT, WRAPPER_JIT],
                         ids=["parent_names", "this_tree_names"])
def test_sums_forward_and_backward_sites_per_step(names):
    assert _read(_inputs(steps=3, names=names)) == pytest.approx(0.4 + 0.6)


@pytest.mark.parametrize("case", ["no-trace", "no-device-events",
                                  "no-steps"])
def test_reports_nothing_where_there_is_nothing_to_read(case):
    inputs = _inputs()
    if case == "no-trace":
        inputs["trace"] = None
    elif case == "no-device-events":
        inputs["trace"] = trace_reduce.Trace()
    else:
        inputs["trace_steps"] = 0
    assert _read(inputs) is None


def test_a_program_without_the_kernel_reads_zero():
    """Under dp4 XLA's own fusion runs: no custom call, no time."""
    inputs = _inputs()
    inputs["trace"] = trace_reduce.Trace(devices={0: [
        e for e in inputs["trace"].devices[0] if "fusion" in e.name]})
    assert _read(inputs) == 0.0


def test_manifest_entry_is_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("bias_gelu_ms_per_step") == 55
    assert manifest["per_layer"][55] == {
        "name": "bias_gelu_ms_per_step", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_s",
        "workloads": ["bert_base_s512", "bert_base_s128"]}
