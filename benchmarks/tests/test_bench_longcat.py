"""The LongCat cell's control flow on the CPU at tiny sizes
(``configs/tiny_longcat.json`` + ``traffic/tiny_reason_closed.json``): a
``harness.Run`` built by hand, the closed-loop driver run to its end with
``correct`` true, and the new per-layer readers on what it hands back.
``rehearsal.json`` lists no such cell: this test stands in."""
import importlib
import json
import math
import os
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_READERS = ("moe_ms_per_round", "mla_attn_ms_per_round",
                 "moe_experts_roofline", "moe_tokens_per_held_expert",
                 "decode_streams_per_round")
NEW_READERS = TRACE_READERS + ("moe_zero_pick_pct", "ttft_p95_ms_c256",
                               "peak_hbm_gb_c256")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One traced closed-loop run of the tiny cell: telemetry and tracing
    on, as run.py --trace 1 has them."""
    import jax

    from benchmarks.builders import longcat_flash as builder
    from benchmarks.drivers import closed_loop
    from benchmarks.lib import harness
    from benchmarks.references import longcat_flash as reference

    config = _load("configs", "tiny_longcat")
    traffic = _load("traffic", "tiny_reason_closed")
    run = harness.Run(
        cell={"name": "tiny_longcat_closed", "config": "tiny_longcat",
              "traffic": "tiny_reason_closed", "chips": 1},
        config=config, traffic=traffic, seed=2147483700, seconds=2.0,
        trace=True, devices=jax.devices()[:1], peaks=None, builder=builder,
        reference=reference,
        out_dir=str(tmp_path_factory.mktemp("bench_out")),
        t0=time.perf_counter(), watch=harness.CompileWatch())
    return run, closed_loop.run(run)


def test_closed_loop_runs_to_its_end_correct(traced_run):
    run, result = traced_run
    assert result.correct, result.notes
    assert result.failed == 0 and result.attempted >= 3
    assert result.notes["reference_check"]["checked"] == 3
    assert result.end_to_end["served_tokens_s"] > 0
    assert math.isfinite(result.end_to_end["tpot_p50_ms"])
    # every warm-up group was one prefill batch inside the bound
    stats = result.notes["server_stats"]
    assert stats["errors"] == 0 and stats["batches"] >= 6
    assert result.notes["compiles_in_window"]["compiles"] == 0


def _inputs(traced_run, **extra):
    run, result = traced_run
    return dict(result.layer, config=run.config, traffic=run.traffic,
                cell=run.cell, peaks=PEAKS, **extra)


def test_counter_readers_on_the_run(traced_run):
    inputs = _inputs(traced_run)
    zero_pct = _reader("moe_zero_pick_pct").read(inputs)
    # 2 of 8 routed experts held, 4 zero experts, top-3 of 12 outputs
    assert 0.0 < zero_pct < 100.0
    assert _reader("decode_batch_mean").read(inputs) >= 1.0
    assert _reader("compiles_in_window_serve").read(inputs) == 0.0
    assert _reader("ttft_p95_ms_c256").read(inputs) == \
        _reader("ttft_p95_ms").read(inputs) > 0.0
    # the CPU reports no memory statistics: nothing to read, no error
    assert _reader("peak_hbm_gb_c256").read(inputs) is None


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_report_nothing_without_device_names(traced_run,
                                                           name):
    """A CPU trace has no TPU plane (and a program without the scopes and
    marks has no such names): the reader returns None and does not
    raise."""
    assert _reader(name).read(_inputs(traced_run)) is None
    assert _reader(name).read(_inputs(traced_run, trace=None)) is None
    assert _reader(name).read(_inputs(
        traced_run, scoped={"ops": [], "modules": [], "marks": []})) is None


def test_the_engine_marks_its_picks_in_a_running_trace(traced_run):
    """The traced slice of the tiny run holds the engine's ``moe.picks:``
    annotations, and each accounts for ``moe_topk`` picks a token."""
    from benchmarks.lib import trace_reduce, xplane_scopes

    run, _ = traced_run
    path = trace_reduce.find_xplane(os.path.join(run.out_dir, "profile"))
    marks = xplane_scopes.pick_marks(path)
    decode = [m for m in marks if m["phase"] == "decode"]
    assert decode and {m["phase"] for m in marks} <= {"decode", "prefill"}
    k, layers = run.config["moe_topk"], run.config["num_layers"]
    for m in decode:
        tokens, rest = divmod(m["held"] + m["zero"] + m["absent"],
                              k * layers)
        assert rest == 0 and 1 <= tokens <= 4 and m["layers"] == layers
        assert m["touched"] <= layers * run.config["n_routed_experts"]


def _synthetic_chip(rounds=3, layers=2):
    """``rounds`` decode rounds of ``layers`` runs of the layer program
    each, with a prefill between the rounds."""
    from benchmarks.lib.xplane_scopes import ScopedOp

    ops, modules, t = [], [], 0.0
    for _ in range(rounds):
        for _ in range(layers):
            modules.append(ScopedOp("jit_longcat_decode(123)", t, 9e6))
            for scope, dur in (("mla.decode/dot_general:", 1e6),
                               ("moe.router/top_k:", 0.25e6),
                               ("moe.experts/while:", 2e6),
                               # inside the loop: not counted twice
                               ("moe.experts/while/body/pallas_call:",
                                1.5e6),
                               ("moe.zero/mul:", 0.25e6),
                               ("ffn.dense/dot_general:", 4e6)):
                nested = "body" in scope
                start = t - 2e6 + 0.1e6 if nested else t
                ops.append(ScopedOp(
                    f"jit(longcat_decode)/jit(main)/{scope}", start, dur))
                if not nested:
                    t += dur
            t += 1e6
        # a prefill in between: another program, not a decode round
        modules.append(ScopedOp("jit_longcat_prefill(77)", t, 9e6))
        ops.append(ScopedOp("jit(longcat_prefill)/jit(main)/moe.experts/x:",
                            t, 9e6))
        t += 40e6
    # a full decode round (4 streams x top-3 x 2 layers = 24 picks) and
    # a prefill's mark, which no decode reader may count
    marks = rounds * [
        {"phase": "decode", "held": 6, "zero": 8, "absent": 10,
         "touched": 3, "layers": layers},
        {"phase": "prefill", "held": 90, "zero": 90, "absent": 90,
         "touched": 4, "layers": layers}]
    return {"ops": ops, "modules": modules, "marks": marks}


def test_trace_readers_on_a_synthetic_trace(traced_run):
    inputs = _inputs(traced_run, scoped=_synthetic_chip())
    assert inputs["config"]["num_layers"] == 2
    assert _reader("moe_ms_per_round").read(inputs) == pytest.approx(5.0)
    assert _reader("mla_attn_ms_per_round").read(inputs) == \
        pytest.approx(2.0)
    assert _reader("moe_tokens_per_held_expert").read(inputs) == \
        pytest.approx(6 / 2 / 2)
    assert _reader("decode_streams_per_round").read(inputs) == \
        pytest.approx(4.0)
    # per layer call: 3 pairs on 1.5 touched experts, 2 ms under the scope
    from benchmarks.kernels import moe_experts as k

    s = k.shapes(inputs["config"], inputs["traffic"], 1)
    floor_s = max(k.flops(s, 3.0) / PEAKS["bf16_flops"],
                  k.bytes_moved(s, 3.0, 1.5) / PEAKS["hbm_bytes_s"])
    assert _reader("moe_experts_roofline").read(inputs) == \
        pytest.approx(100.0 * floor_s / 2e-3)


def test_roofline_counts_by_hand():
    from benchmarks.kernels import moe_experts as k

    s = k.shapes(_load("configs", "longcat_flash_chat_ep32"), {}, 1)
    assert s == {"hidden": 6144, "expert_hidden": 2048, "held": 16}
    assert k.flops(s, 64) == 6 * 6144 * 2048 * 64
    # 16 experts of 37.75 M parameters in bf16, plus 64 pairs' rows
    assert k.bytes_moved(s, 64, 16) == \
        2 * (16 * 3 * 6144 * 2048 + 64 * (2 * 6144 + 2 * 2048))


def test_scoped_names_of_a_recorded_trace():
    """The wire-format reader on the recorded chip trace: the same events
    as ``jax.profiler.ProfileData`` shows, with the name it does not."""
    from benchmarks.lib import trace_reduce, xplane_scopes

    path = os.path.join(BENCH, "testdata", "small.xplane.pb")
    chip = xplane_scopes.read_xplane(path)[0]
    events = trace_reduce.load(path).devices[0]
    assert len(chip["ops"]) == len(events) == 12
    for op, e in zip(chip["ops"], events):
        assert abs(op.start_ns - e.start_ns) < 2 and \
            abs(op.dur_ns - e.dur_ns) < 2
    named = {o.op_name for o in chip["ops"] if o.op_name}
    assert named == {"jit(<lambda>)/dot_general:"}
    assert xplane_scopes.runs_of(chip["modules"], "_lambda") == 4
    assert xplane_scopes.scope_ns(chip["ops"], "<lambda>",
                                  "dot_general") == pytest.approx(
        sum(e.dur_ns for e in events if e.name == "fusion"), rel=1e-3)


def test_cell_files_meet_what_the_harness_reads():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "longcat_flash_decode_c256")
    config = _load("configs", cell["config"])
    traffic = _load("traffic", cell["traffic"])
    assert cell["chips"] == 1 and traffic["driver"] == "closed_loop"
    s = traffic["server"]
    bound = s["max_prefill_tokens"]
    from mxnet_tpu.serving.buckets import BucketGrid

    grid = BucketGrid(tuple(s["batch_buckets"]), None,
                      len_buckets=tuple(s["len_buckets"]))
    warmed = set()
    for n, plen in s["warmup"]:
        sig = (grid.batch_bucket(n), grid.prefill_bucket(plen))
        assert sig[0] * sig[1] <= bound, (n, plen)      # one batch each
        warmed.add(sig)
    # every prefill signature the bound allows, every decode bucket
    allowed = {(b, l) for b in s["batch_buckets"] for l in s["len_buckets"]
               if b * l <= bound}
    assert warmed == allowed
    assert {grid.batch_bucket(n) for n, _ in s["warmup"]} == \
        set(s["batch_buckets"])
    assert (s["decode_pages"] - 1) * s["page_size"] == \
        traffic["clients"] * s["max_generate_tokens"]
    assert s["max_generate_tokens"] == \
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    for name in NEW_READERS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [cell["name"]]
    assert config["router_outputs"] == 768 and config["moe_topk"] == 12
    weights_gb = 2 * sum(np.prod(s) for s in _all_shapes(config)) / 1e9
    assert 10.3 < weights_gb < 10.4


def _all_shapes(config):
    from benchmarks.builders import longcat_flash as b

    u, v = config["hidden_size"], config["vocab_size"]
    layer = (2 * list(b._sub_shapes(config).values())
             + list(b._moe_shapes(config).values()))
    return config["num_layers"] * layer + [(v, u), (v, u), (u,)]
