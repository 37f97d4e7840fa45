"""The reduction from a profiler trace to numbers, on a hand-built trace
with known busy / idle / exposed values and on a small trace recorded on
the v5e in PR 24's chip runs (benchmarks/testdata/)."""
import glob
import os

import pytest

from benchmarks.lib import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def _xspace(lines_by_plane):
    """Text-proto XSpace: {plane: {line: [(name, start_ns, dur_ns)]}}."""
    out = []
    for plane, lines in lines_by_plane.items():
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = []
        for line, evs in lines.items():
            events = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1000)} "
                f"duration_ps: {int(d * 1000)} }}\n" for n, s, d in evs)
            body.append(f'lines {{ name: "{line}" timestamp_ns: 0\n'
                        f"{events}}}\n")
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        out.append(f'planes {{ name: "{plane}"\n{"".join(body)}{meta}}}\n')
    return "".join(out)


@pytest.fixture(scope="module")
def synthetic():
    import jax

    # chip 0: fusion 0-40, all-reduce 30-60 (10 of it under the fusion's
    # tail on another line's clock: 30-40 overlapped), idle 60-80,
    # fusion 80-100. chip 1: one op 0-50.
    text = _xspace({
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 0, 40), ("all-reduce.7", 30, 30),
                        ("fusion.2", 80, 20)],
            "Steps": [("0", 0, 100)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 50)]},
        "/host:CPU": {
            "python3": [("bench:dispatch", 55, 10), ("bench:block", 62, 30),
                        ("PjitFunction(f)", 0, 5)]},
    })
    return tr.from_profile_data(jax.profiler.ProfileData.from_text_proto(text))


def test_planes_and_lines(synthetic):
    assert sorted(synthetic.devices) == [0, 1]
    assert [e.name for e in synthetic.devices[0]] == [
        "fusion.1", "all-reduce.7", "fusion.2"]
    # only the benchmark's own annotations are kept from the host
    assert [e.name for e in synthetic.host] == ["bench:dispatch",
                                               "bench:block"]
    assert synthetic.lines["/device:TPU:0"] == ["XLA Ops", "Steps"]


def test_busy_idle(synthetic):
    ev = synthetic.devices[0]
    assert tr.span_of(ev) == (0, 100)
    assert tr.busy_ns(ev) == 80            # 0-60 and 80-100
    assert tr.idle_gaps(ev) == [(60, 80)]
    assert tr.busy_ns(ev, window=(50, 90)) == 20
    assert tr.idle_gaps(ev, window=(50, 110)) == [(60, 80), (100, 110)]


def test_collective_overlap(synthetic):
    total, exposed = tr.collective_ns(synthetic.devices[0])
    assert total == 30                     # 30-60
    assert exposed == 20                   # 40-60: no other op ran


def test_by_name_and_matching(synthetic):
    ev = synthetic.devices[0]
    assert tr.by_name(ev) == {"fusion.1": 40, "all-reduce.7": 30,
                              "fusion.2": 20}
    assert [e.name for e in tr.matching(ev, r"all-reduce")] == [
        "all-reduce.7"]


def test_gap_labels(synthetic):
    # gap 60-80: dispatch covers 60-65 (5), block covers 62-80 (18)
    assert tr.label_gaps([(60, 80)], synthetic.host) == [("block", 20)]
    assert tr.label_gaps([(200, 210)], synthetic.host) == [
        ("unattributed", 10)]


def test_summary(synthetic):
    s = tr.summary(synthetic)
    assert s["busy_s"] == pytest.approx((80 + 50) / 2 / 1e9)
    assert s["window_s"] == pytest.approx((100 + 50) / 2 / 1e9)
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(40 / 1e9)]
    assert s["idle_gaps"] == [["block", pytest.approx(20 / 1e9)]]


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 10), (5, 20)], [], [(0, 20)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_recorded_trace():
    """A trace taken on the v5e: device plane found, busy within span,
    and the numbers written down when it was recorded still come out."""
    import json

    paths = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))
    assert paths, "benchmarks/testdata holds no recorded trace"
    trace = tr.load(paths[0])
    with open(os.path.join(TESTDATA, "recorded_expect.json")) as f:
        want = json.load(f)
    ev = trace.devices[0]
    assert len(ev) == want["events"]
    assert tr.busy_ns(ev) == pytest.approx(want["busy_ns"])
    assert tr.total([tr.span_of(ev)]) == pytest.approx(want["span_ns"])
    assert 0 < tr.busy_ns(ev) <= tr.total([tr.span_of(ev)])
    top = max(tr.by_name(ev).items(), key=lambda kv: kv[1])
    assert top[0] == want["top_op"]
    assert [e.name for e in trace.host][:1] == want["first_host_event"]


PAGED = ('%_unknown_.50 = bf16[32,32,128]{2,1,0:T(8,128)(2,1)} custom-call('
         's32[32,160]{1,0:T(8,128)} %page_table.1, s32[32]{0:T(128)} '
         '%lengths.1, bf16[32,32,128]{2,1,0:T(8,128)(2,1)} %reshape.815, '
         'bf16[46096,1024]{1,0:T(8,128)(2,1)} %copy_bitcast_fusion.3), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         '{s32[32,160]{1,0}, s32[32]{0}}')
RMS = ('%_unknown_.3 = bf16[4,4096]{1,0:T(4,128)(2,1)} custom-call('
       'bf16[4,4096]{1,0:T(4,128)(2,1)} %x, bf16[1,4096]{1,0} %w), '
       'custom_call_target="tpu_custom_call"')
FLASH = ('%transpose_jvp_jit__contrib_sdp_attention___.7 = (bf16[32,12,512,'
         '64]{3,2,1,0}) custom-call(bf16[32,12,512,64]{3,2,1,0} %q), '
         'custom_call_target="tpu_custom_call"')
FUSION = ('%fusion.20 = f32[109514298]{0:T(1024)} fusion(f32[154]{0:T(256)} '
          '%custom-call.34), kind=kCustom, calls=%fused_computation.7')


def test_kernel_patterns_on_hlo_text_as_the_profiler_prints_it():
    """The texts are copied from PR 24's traces on the v5e."""
    from benchmarks.kernels import flash_attention, paged_attention
    from benchmarks.lib import readers

    events = [tr.Event(t.split(" = ")[0].lstrip("%"), 10.0 * i, 5.0, t)
              for i, t in enumerate((PAGED, RMS, FLASH, FUSION))]
    inputs = {"trace": tr.Trace(devices={0: events})}
    names = lambda evs: [e.name for e in evs]          # noqa: E731
    # a fusion that READS a custom call's result is not a kernel
    assert names(readers.pallas_events(inputs)) == [
        "_unknown_.50", "_unknown_.3",
        "transpose_jvp_jit__contrib_sdp_attention___.7"]
    assert names(readers.pallas_events(inputs, paged_attention.PATTERN)) \
        == ["_unknown_.50"]
    assert names(readers.pallas_events(inputs, flash_attention.PATTERN)) \
        == ["transpose_jvp_jit__contrib_sdp_attention___.7"]
    assert tr.short_label(events[0], 60) == (
        "%_unknown_.50 = bf16[32,32,128] custom-call(s32[32,160] %pag")
