"""``mla_attn_roofline``: the kernel's FLOPs and bytes by hand, the reader
on a synthetic trace, and nothing where the program ran no such kernel
(the parent commit, a CPU trace)."""
import importlib
import json
import os

import pytest

from benchmarks.kernels import mla_paged_attention as k
from benchmarks.lib import trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
CALL = ('%{name}.{n} = bf16[256,64,512]{{2,1,0}} custom-call(s32[256]{{0}} '
        '%a, s32[18432]{{0}} %b), custom_call_target="tpu_custom_call", '
        'metadata={{op_name="jit(longcat_decode)/mla.decode/{name}"}}')


def _config():
    with open(os.path.join(BENCH, "configs",
                           "longcat_flash_chat_ep32.json")) as f:
        return json.load(f)


def _read(inputs):
    reader = importlib.import_module(
        "benchmarks.layer_metrics.mla_attn_roofline")
    return reader.read(inputs)


def test_counts_by_hand():
    s = k.shapes(_config(), {}, 1)
    assert s == {"heads": 64, "latent": 512, "row": 640, "sites": 8}
    # 256 streams of 400 live rows, one round, 8 sublayers
    rows = 256 * 400
    assert k.flops(s, rows) == 8 * rows * 2 * 64 * (640 + 512)
    assert k.bytes_moved(s, rows, 256) == \
        8 * (rows * 1280 + 256 * 64 * (640 + 512) * 2)
    # the bytes bind at this length: 0.21 ms a sublayer against 0.077
    assert k.bytes_moved(s, rows, 256) / 8 / PEAKS["hbm_bytes_s"] == \
        pytest.approx(0.206e-3, rel=0.01)
    assert k.flops(s, rows) / 8 / PEAKS["bf16_flops"] == \
        pytest.approx(0.0767e-3, rel=0.01)


def _inputs(kernel_name="mla_paged_decode", kernel_ms=0.5):
    """Two streams in one decode round of the slice (prompts 100 and 300,
    tokens 20 and 50 made), one stream's span before the slice; eight
    kernel runs of ``kernel_ms`` and an XLA fusion."""
    ms = 1e6
    events = [trace_reduce.Event("fusion.1", 0.0, 1 * ms,
                                 "%fusion.1 = bf16[8] fusion(...)")]
    for n in range(8):
        text = CALL.format(name=kernel_name, n=n)
        events.append(trace_reduce.Event(
            f"{kernel_name}.{n}", (2 + n) * ms, kernel_ms * ms, text))
    trace = trace_reduce.Trace(devices={0: events})
    offset = 5 * ms                      # host clock + offset = device's
    def span(trace_id, token, at_ns):
        return {"name": "decode.step", "trace_id": trace_id,
                "ts": (at_ns - offset) / 1e3, "dur": 900.0,
                "tags": {"token": token}}
    spans = [span("a", 20, 1.5 * ms), span("b", 50, 1.5 * ms),
             span("c", 7, -3 * ms),
             {"name": "prefill", "trace_id": "a", "ts": 0.0, "dur": 1.0,
              "tags": {}}]
    return {"trace": trace, "trace_clock_offset_ns": offset, "spans": spans,
            "trace_prompt_len": {"a": 100, "b": 300, "c": 50},
            "config": _config(), "traffic": {}, "peaks": PEAKS}


def test_reader_on_a_synthetic_trace():
    inputs = _inputs(kernel_ms=0.5)
    rows = (100 + 20 + 1) + (300 + 50 + 1)
    s = k.shapes(inputs["config"], {}, 1)
    floor_s = max(k.flops(s, rows) / PEAKS["bf16_flops"],
                  k.bytes_moved(s, rows, 2) / PEAKS["hbm_bytes_s"])
    assert _read(inputs) == pytest.approx(100.0 * floor_s / (8 * 0.5e-3))
    # twice the kernel time, half the share
    assert _read(_inputs(kernel_ms=1.0)) == pytest.approx(
        50.0 * floor_s / (8 * 0.5e-3))


@pytest.mark.parametrize("case", ["other-kernel", "no-trace", "no-offset",
                                  "no-spans-in-slice", "no-device-events"])
def test_reader_reports_nothing_where_there_is_nothing_to_read(case):
    """The parent commit's program has the gather, not the kernel; a CPU
    trace has no device plane: no value, no error."""
    inputs = _inputs()
    if case == "other-kernel":           # megablox, the GQA paged kernel
        inputs = _inputs(kernel_name="_unknown_")
    elif case == "no-trace":
        inputs["trace"] = None
    elif case == "no-offset":
        inputs["trace_clock_offset_ns"] = None
    elif case == "no-spans-in-slice":
        inputs["spans"] = inputs["spans"][2:]
    elif case == "no-device-events":
        inputs["trace"] = trace_reduce.Trace()
    assert _read(inputs) is None


def test_manifest_entry():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": "mla_attn_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": ["longcat_flash_decode_c256"]}
