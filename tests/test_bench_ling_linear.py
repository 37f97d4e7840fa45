"""The Ling cell's control flow on the CPU at tiny sizes
(``configs/tiny_ling_linear.json`` + ``traffic/tiny_reason_long_closed.json``):
a ``harness.Run`` built by hand, the first-wave closed-loop driver run to
its end with ``correct`` true (prompts of several chunks beside decoding
streams, one of the checked requests a multi-chunk prompt), the cell's
per-layer readers on what it hands back, the manifest entries, and what
the harness's ``correct`` sees (``tools/ling_chip_check.py``).
``rehearsal.json`` lists no such cell: this test stands in, as
``test_bench_falcon_h1.py`` does."""
import importlib
import json
import math
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "ling3_flash_reason_closed_c256"
CONFIG = "ling3_flash_ep8_l7"
SCOPE_READERS = ("kda_update_ms_per_round", "kda_proj_ms_per_round",
                 "kda_chunk_ms_per_prefill_chunk",
                 "ling_mla_attn_ms_per_round", "ling_moe_ms_per_round",
                 "ling_head_ms_per_round")
TRACE_READERS = SCOPE_READERS + ("kda_update_roofline",
                                 "ling_moe_tokens_per_held_expert",
                                 "ling_decode_streams_per_round")
NEW_READERS = TRACE_READERS + ("kda_state_gb_live", "ttft_p95_ms_ling",
                               "peak_hbm_gb_ling")
HOST_TURN = tuple(f"host_turn_{p}ms_per_round" for p in
                  ("", "emit_", "sched_", "build_", "launch_", "fetch_"))
APPENDED = ("tok_gap_p99_ms", "tpot_p95_ms", "compiles_in_window_serve",
            "prefill_ms_p50", "decode_step_ms_p50", "pallas_sites_serve",
            "device_idle_pct_serve", "prefill_chunk_ms_p50",
            "prefill_chunks_per_request", "state_slots_in_use") + HOST_TURN


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One traced closed-loop run of the tiny cell: telemetry and tracing
    on, as run.py --trace 1 has them."""
    import jax

    from benchmarks.builders import ling_linear as builder
    from benchmarks.lib import harness
    from benchmarks.references import ling_linear as reference
    import mxnet_tpu as mx

    assert mx.tpu(0).jax_device().platform == "cpu"
    config = _load("configs", "tiny_ling_linear")
    traffic = _load("traffic", "tiny_reason_long_closed")
    driver = importlib.import_module(
        f"benchmarks.drivers.{traffic['driver']}")
    run = harness.Run(
        cell={"name": "tiny_ling_closed", "config": "tiny_ling_linear",
              "traffic": "tiny_reason_long_closed", "chips": 1},
        config=config, traffic=traffic, seed=2147483700, seconds=2.0,
        trace=True, devices=jax.devices()[:1], peaks=None, builder=builder,
        reference=reference,
        out_dir=str(tmp_path_factory.mktemp("bench_out")),
        t0=time.perf_counter(), watch=harness.CompileWatch())
    return run, driver.run(run)


def test_closed_loop_runs_to_its_end_correct(traced_run):
    run, result = traced_run
    assert result.correct, result.notes
    assert result.failed == 0 and result.attempted > 6
    assert result.notes["reference_check"]["checked"] == 3
    assert math.isfinite(result.end_to_end["tpot_p50_ms"])
    assert result.notes["server_stats"]["errors"] == 0
    assert result.notes["compiles_in_window"]["compiles"] == 0


def test_one_checked_request_spans_more_than_a_chunk(traced_run,
                                                     monkeypatch):
    """The driver's one addition: where a run has prompts longer than the
    largest length bucket and checks at least two requests, ONE of the
    checked is such a prompt; the comparison is ``check_outputs``'s
    own."""
    from benchmarks.drivers import closed_loop_first_wave_chunk_check as d
    from benchmarks.drivers import closed_loop_first_wave
    from benchmarks.lib import serve_loop

    run, _ = traced_run
    calls = []

    def fake_check(run_, weights, records, n):
        calls.append(([r.req.prompt.size for r in records], n))
        return {"ok": True, "checked": n, "worst_gap_in_tolerances": 0.25 * n}

    class Rec:
        def __init__(self, n):
            self.req = type("R", (), {"prompt": type("P", (), {"size": n})})

    def fake_run(run_):
        return serve_loop.check_outputs(
            run_, None, [Rec(n) for n in (5, 40, 9, 17, 12)], 3)

    monkeypatch.setattr(serve_loop, "check_outputs", fake_check)
    monkeypatch.setattr(closed_loop_first_wave, "run", fake_run)
    out = d.run(run)
    # the largest length bucket is 16: 40 and 17 span more than a chunk
    assert calls == [([40, 17], 1), ([5, 9, 12], 2)]
    assert out == {"ok": True, "checked": 3,
                   "worst_gap_in_tolerances": 0.5}
    assert serve_loop.check_outputs is fake_check     # put back


def _inputs(traced_run, **extra):
    run, result = traced_run
    return dict(result.layer, config=run.config, traffic=run.traffic,
                cell=run.cell, peaks=None, **extra)


def test_counter_and_span_readers_on_the_run(traced_run):
    run, result = traced_run
    inputs = _inputs(traced_run)
    assert _reader("state_slots_in_use").read(inputs) == 3.0
    # three streams x 3 KDA layers x (4 heads x 16 x 16 + 3 x 192) floats
    assert _reader("kda_state_gb_live").read(inputs) == pytest.approx(
        3 * 4 * 3 * (4 * 256 + 3 * 192) / 1e9)
    assert _reader("compiles_in_window_serve").read(inputs) == 0.0
    assert _reader("ttft_p95_ms_ling").read(inputs) == \
        _reader("ttft_p95_ms").read(inputs) > 0.0
    assert _reader("peak_hbm_gb_ling").read(inputs) is None   # the CPU
    assert _reader("decode_step_ms_p50").read(inputs) > 0.0
    assert _reader("prefill_chunks_per_request").read(inputs) > 1.0
    steps = [s for s in inputs["spans"] if s["name"] == "decode.step"]
    assert steps and all("round" in s["tags"] for s in steps)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_report_nothing_without_device_names(traced_run,
                                                           name):
    """A CPU trace has no TPU plane, and the parent's program has no such
    scopes: the reader returns None, no raise."""
    assert _reader(name).read(_inputs(traced_run)) is None
    assert _reader(name).read(_inputs(traced_run, trace=None)) is None
    empty = {"ops": [], "modules": [], "marks": []}
    assert _reader(name).read(_inputs(traced_run, scoped=empty)) is None
    falcon = dict(_inputs(traced_run, scoped=empty),
                  config=_load("configs", "tiny_falcon_h1"))
    assert _reader(name).read(falcon) is None


def test_counter_reader_reports_nothing_on_a_program_without_it():
    """What the parent commit's run of another cell hands back."""
    inputs = {"counters_before": {}, "counters_after": {}, "window_s": 2.0,
              "spans": [], "config": _load("configs", "tiny_longcat")}
    assert _reader("kda_state_gb_live").read(inputs) is None


def _synthetic_chip(rounds=3):
    """``rounds`` decode rounds of the tiny configuration (a dense KDA
    layer, two KDA expert layers, one MLA expert layer), a prefill chunk
    between rounds whose last token runs the head program too; the picks
    of each forward as a mark."""
    from benchmarks.lib.xplane_scopes import ScopedOp

    ops, modules, marks, t = [], [], [], 0.0

    def run(prog, scopes):
        nonlocal t
        modules.append(ScopedOp(f"jit_{prog}(3)", t, 9e6))
        for scope, dur in scopes:
            ops.append(ScopedOp(f"jit({prog})/jit(main)/{scope}", t, dur))
            t += dur
        t += 1e6

    kda = [("kda.proj/dot_general:", 1e6), ("kda.proj/mul:", 0.25e6),
           ("kda.update/jit(kda_state_update)/pallas_call:", 1.5e6),
           ("kda.out/dot_general:", 0.5e6)]
    moe = [("moe.router/dot_general:", 0.25e6), ("moe.experts/while:", 1e6),
           ("moe.shared/dot_general:", 0.5e6)]
    mla = [("mla.proj/dot_general:", 0.5e6),
           ("mla.attend/jit(mla_paged_decode)/pallas_call:", 0.75e6)]
    chunk = [("kda.proj/dot_general:", 4e6), ("kda.chunk/while:", 6e6),
             ("kda.out/dot_general:", 1e6)]
    for _ in range(rounds):
        run("ling_decode_layer_kda_dense",
            kda + [("ffn.dense/dot_general:", 2e6)])
        run("ling_decode_layer_kda", kda + moe)
        run("ling_decode_layer_mla", mla + moe)
        run("ling_decode_layer_kda", kda + moe)
        run("ling_head", [("h1.head/dot_general:", 3e6)])
        marks.append({"phase": "decode", "held": 12, "zero": 0,
                      "absent": 36, "touched": 9, "layers": 3})
        run("ling_prefill_layer_kda_dense", chunk)
        run("ling_prefill_layer_kda", chunk + moe)
        run("ling_prefill_layer_mla", [("mla.attend/while:", 9e6)] + moe)
        run("ling_prefill_layer_kda", chunk + moe)
        run("ling_head", [("h1.head/dot_general:", 3e6)])
        marks.append({"phase": "prefill", "held": 99, "zero": 0,
                      "absent": 9, "touched": 9, "layers": 3})
    return {"ops": ops, "modules": modules, "marks": marks}


def test_scope_readers_on_a_synthetic_trace(traced_run):
    inputs = _inputs(traced_run, scoped=_synthetic_chip())
    read = {n: _reader(n).read(inputs) for n in SCOPE_READERS}
    assert read["kda_update_ms_per_round"] == pytest.approx(3 * 1.5)
    assert read["kda_proj_ms_per_round"] == pytest.approx(3 * 1.75)
    assert read["kda_chunk_ms_per_prefill_chunk"] == pytest.approx(3 * 6.0)
    assert read["ling_mla_attn_ms_per_round"] == pytest.approx(0.75)
    assert read["ling_moe_ms_per_round"] == pytest.approx(3 * 1.75)
    # a RUN of the head program, whoever ran it
    assert read["ling_head_ms_per_round"] == pytest.approx(3.0)
    # 12 held picks a round over 3 expert layers and 4 held experts
    assert _reader("ling_moe_tokens_per_held_expert").read(inputs) == \
        pytest.approx(12 / 3 / 4)


def _slice_inputs(kernel):
    """A slice of two rounds (256 and 255 streams) on the published
    sizes: twelve runs of the state-update kernel (six KDA layers a
    round) of 1.6 ms each, or none."""
    from benchmarks.lib import trace_reduce

    name = ("%kda_state_update.3 = (f32[257,32,128,128], f32[256,32,128]) "
            "custom-call(s32[256] %p, f32[257,32,128,128] %s)") if kernel \
        else "%fusion.9 = f32[257,32,128,128] fusion(...)"
    events = [trace_reduce.Event(name, 1e6 + i * 2e6, 1.6e6)
              for i in range(12)]
    for e in events:
        e.long_name = name + (' custom_call_target="tpu_custom_call"'
                              if kernel else "")
    trace = type("T", (), {"devices": {0: events}})()
    spans = [{"name": "decode.step", "ts": 1e3 + r * 12e3, "dur": 11e3,
              "trace_id": f"s{i}", "tags": {"token": r, "round": 40 + r}}
             for r in range(2) for i in range(256 - r)]
    return {"trace": trace, "trace_clock_offset_ns": 0, "spans": spans,
            "trace_prompt_len": {f"s{i}": 100 for i in range(256)},
            "config": _load("configs", CONFIG),
            "traffic": _load("traffic", "reason_long_closed_c256"),
            "cell": {"name": CELL, "chips": 1},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "scoped": {"ops": [], "marks": [], "modules": [
                type("M", (), {"op_name": "jit_ling_decode_layer_mla(1)"})()
                for _ in range(2)]}}


def test_update_roofline_counts_each_state_once_in_and_once_out():
    from benchmarks.kernels import kda_state_update as k

    config = _load("configs", CONFIG)
    s = k.shapes(config, {}, 1)
    assert s["sites"] == 6
    assert k.state_values(s) == 32 * 128 * 128 == 524288
    # one stream, one layer: 2.1 MB in, 2.1 MB out, 98 KB of rows
    assert k.bytes_moved(s, 1) == 2 * 2097152 + 4 * 6 * 4096
    assert k.flops(s, 1) == 7 * 524288
    inputs = _slice_inputs(True)
    updates = 12 * 255.5
    floor = k.bytes_moved(s, updates) / 819e9
    got = _reader("kda_update_roofline").read(inputs)
    assert got == pytest.approx(100.0 * floor / (12 * 1.6e-3))
    assert 0 < got < 100
    assert _reader("kda_update_roofline").read(_slice_inputs(False)) is None
    # neither state kernel's pattern takes the other's runs for its own
    from benchmarks.kernels import ssd_state_update
    from benchmarks.lib import readers

    assert readers.pallas_events(inputs, ssd_state_update.PATTERN) == []
    assert _reader("ssd_update_roofline").read(inputs) is None
    assert _reader("ling_decode_streams_per_round").read(inputs) > 200


# -- the manifest and the cell's files ---------------------------------------------------------

def test_manifest_entries_and_their_places():
    manifest = _manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    # twelve cells when this one was accepted; later PRs append theirs
    assert cells[11:12] == [CELL] and len(cells) >= 12
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert [c["name"] for c in manifest["configs"]][8:9] == [CONFIG]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    # new entries went to the END of the list, every accepted one is
    # where it was accepted
    assert names[98:98 + len(NEW_READERS)] == list(NEW_READERS)
    assert names.index("mla_attn_roofline") == 33
    assert names.index("ssm_ms_per_round") == 45
    assert names.index("host_turn_fetch_ms_per_round") == 61
    assert names.index("ssd_update_roofline") == 67
    for name in NEW_READERS:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tpot_p50_ms"
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert per_layer["kda_update_roofline"]["unit"] == "%"
    assert per_layer["kda_update_roofline"]["layer"] == \
        per_layer["ssd_update_roofline"]["layer"] == "kernels"
    for name in APPENDED:
        # later cells append theirs behind it
        assert CELL in per_layer[name]["workloads"], name
    assert per_layer["peak_hbm_gb_ling"]["better"] == "lower"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["tpot_p50_ms"]["workloads"]
    assert CELL not in e2e["served_tokens_s"]["workloads"]
    for w in manifest["workloads"][11:12]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
        assert (w["config"], w["traffic"]) == (CONFIG,
                                               "reason_long_closed_c256")
    entry = manifest["configs"][8]
    assert len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_config_keeps_the_catalog_row():
    """Every key of the catalog row's ``config`` is in the file under the
    same name, unchanged but the four cuts; no width is among them."""
    config = _load("configs", CONFIG)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    entry = next(c for c in _manifest()["configs"] if c["name"] == CONFIG)
    assert config["source"] == entry["source"] == row["source_url"]
    cut = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]
    assert config["reduced"] == entry["reduced"] == cut
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
    assert config["published"] == {k: row["config"][k] for k in cut}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_experts"], config["vocab_size"]) == (7, 1, 64, 19648)
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["num_experts"] * 8 == row["config"]["num_experts"] \
        == config["router_outputs"]
    # the published widths, by name
    for key, value in (
            ("hidden_size", 2560), ("num_attention_heads", 32),
            ("head_dim", 128), ("kv_lora_rank", 512),
            ("qk_rope_head_dim", 64), ("moe_intermediate_size", 768),
            ("intermediate_size", 6144), ("num_experts_per_tok", 8),
            ("n_group", 8), ("topk_group", 4),
            ("short_conv_kernel_size", 4), ("kda_lower_bound", -5),
            ("q_lora_rank", None)):
        assert config[key] == value, key
    # the layer kinds are stated, and are the published pattern of the
    # published layers held
    held = config["published_layers_held"]
    assert held == list(range(1, 8))
    assert config["layer_kinds"] == [
        "mla" if (i + 1) % config["layer_group_size"] == 0 else "kda"
        for i in held]
    assert config["layer_kinds"][config["first_k_dense_replace"]:].count(
        "kda") == 5
    for key in ("deployment", "reduced_why", "assumed", "not_served"):
        assert config[key], key
    assert "8 chips share each layer" in config["deployment"]
    assert set(config["not_served"]) == {"vision_tower", "mtp"}
    for key in ("values_from_memory", "kda_gates_rank", "dt_bias",
                "write_strength", "convolution", "qk_norm", "output_norm",
                "rotary", "mla_gate", "router", "swiglu_limits", "weights",
                "what_correct_sees", "what_correct_cannot_see"):
        assert config["assumed"][key], key
    assert config["dtype"] == "bfloat16"


def test_cell_files_meet_what_the_harness_reads():
    traffic = _load("traffic", "reason_long_closed_c256")
    config = _load("configs", CONFIG)
    assert traffic["driver"] == "closed_loop_first_wave_chunk_check"
    assert traffic["clients"] == 256 and traffic["check_requests"] == 2
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.8, "min": 256, "max": 4096}
    assert traffic["output_len"]["min"] >= 2048
    s = traffic["server"]
    assert s["batch_buckets"] == [1, 32, 256]
    assert s["len_buckets"] == [64, 2048]      # the 512 bucket went: why
    assert s["page_size"] == 16 and s["max_prefill_tokens"] == 2048
    # 256 streams x 7,168 tokens of ONE latent layer + the scratch page
    assert s["decode_pages"] == 256 * 7168 // 16 + 1 == 114689
    assert s["max_generate_tokens"] == 7168 >= \
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    from mxnet_tpu.serving.buckets import BucketGrid

    grid = BucketGrid(tuple(s["batch_buckets"]), None,
                      len_buckets=tuple(s["len_buckets"]))
    bound = s["max_prefill_tokens"]
    warmed = set()
    groups = [[1, s["warmup"][0][1]]] + s["warmup"]   # serve_loop.warm_up
    for n, plen in groups:
        sig = (grid.batch_bucket(n), grid.prefill_bucket(plen))
        assert sig[0] * sig[1] <= bound, (n, plen)      # one batch each
        warmed.add(sig)
    # every prefill signature the bound lets a tick make, and every
    # decode bucket but the widest, which the builder warms itself
    allowed = {(b, l) for b in s["batch_buckets"]
               for l in s["len_buckets"] if b * l <= bound}
    assert warmed == allowed
    assert {grid.batch_bucket(n) for n, _ in groups} == \
        set(s["batch_buckets"][:-1])
    for key in ("why", "trace_why"):
        assert traffic[key] and "TO BE FILLED" not in traffic[key], key
    assert "TO BE FILLED" not in s["why"]
    # the reference pads every request of the cell to ONE length
    from benchmarks.references import ling_linear as reference

    assert reference.LONG == s["max_generate_tokens"]
    assert config["num_hidden_layers"] == len(config["layer_kinds"])


def test_builder_warms_the_widest_decode_bucket_itself(monkeypatch):
    """(32, 64) is the widest prefill batch the cell's bound allows, so
    the harness's warm-up (one prefill batch a group) cannot reach the
    256-stream decode program: the builder runs it before the harness's
    groups (Falcon-H1's ``warm_widest_decode``). The cell's shape in
    small: buckets (1, 2, 8), a bound of two prompts."""
    import mxnet_tpu as mx
    from benchmarks.builders import ling_linear as builder
    from mxnet_tpu.gluon.model_zoo.nlp import ling_linear as model

    s = _load("traffic", "reason_long_closed_c256")["server"]
    assert s["batch_buckets"][-1] * s["len_buckets"][0] \
        > s["max_prefill_tokens"] == s["batch_buckets"][1] \
        * s["len_buckets"][0]
    config = _load("configs", "tiny_ling_linear")
    traffic = _load("traffic", "tiny_reason_long_closed")
    net, ctx = builder.build_net(config, 5, ctx=mx.cpu(0))
    seen = []
    run = model.LingLinearDecodeEngine._run

    def watched(self, b, l, *rest, **seam):
        seen.append((b, l))
        return run(self, b, l, *rest, **seam)

    monkeypatch.setattr(model.LingLinearDecodeEngine, "_run", watched)
    tight = dict(traffic, server=dict(
        traffic["server"], batch_buckets=[1, 2, 8], max_prefill_tokens=16,
        decode_pages=49))
    srv = builder.start_server(net, ctx, tight)
    try:
        assert builder.warm_widest_decode(srv, tight, 128, 5) == 5
        assert srv.stats()["generates_active"] == 0
    finally:
        srv.stop(timeout=30.0)
    assert set(seen) == {(1, 8), (2, 8), (1, 1), (2, 1), (8, 1)}
    assert max(b * l for b, l in seen) <= 16


def test_weights_cache_and_slots_fill_the_chip():
    """The sizes the configuration file states, recomputed: 5.73 GB of
    weights, 3.46 GB of slots, 2.35 GB of pages of ONE latent layer."""
    from benchmarks.builders import ling_linear as b

    config = _load("configs", CONFIG)
    s = _load("traffic", "reason_long_closed_c256")["server"]
    params = 2 * config["vocab_size"] * config["hidden_size"]
    for i, kind in enumerate(config["layer_kinds"]):
        moe = i >= config["first_k_dense_replace"]
        for shape in b._layer_shapes(config, kind, moe).values():
            params += math.prod(shape)
    assert 5.70e9 < 2 * params < 5.76e9
    h, d = config["num_attention_heads"], config["head_dim"]
    slot = 4 * 6 * (h * d * d + 3 * 3 * h * d)
    assert slot == 13467648
    slots = (s["batch_buckets"][-1] + 1) * slot
    pages = s["decode_pages"] * s["page_size"] * 640 * 2
    assert 3.45e9 < slots < 3.47e9 and 2.34e9 < pages < 2.36e9
    # over a quarter of a 16 GB chip before a single temporary, under 95%
    assert 0.25 * 16e9 < 2 * params + slots + pages < 0.8 * 16e9


def test_flops_per_token_counts_the_share():
    from benchmarks.builders import ling_linear as builder

    config = _load("configs", CONFIG)
    flops = builder.flops_per_token(config, {})
    # ~0.57 B parameters read a token outside the routed experts, one
    # held pick a token (8 x 64 / 512), the delta rule's 8 x 0.52 M x 6
    assert 1.1e9 < flops < 1.3e9
    shapes = builder._layer_shapes(config, "kda", True)
    assert shapes["gate_up"] == (64, 2560, 1536)
    assert shapes["qkv"] == (3 * 4096, 2560) and shapes["f"] == (4096, 2560)
    assert builder._layer_shapes(config, "mla", True)["q"] == (6144, 2560)


@pytest.mark.slow
def test_chip_check_control_flow_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "ling_chip_check.py"),
         "--config", "tiny_ling_linear", "--prompts", "37,12", "--new", "6",
         "--chunk", "16", "--page-size", "8", "--streams", "4", "--slots",
         "7", "--rows", "48"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["kernel"]["ok"] and line["mixer"]["ok"] \
        and line["experts"]["ok"]
    got = line["controls"]
    assert got["sound"]["ok"]
    for fault in ("no_decay", "no_delta", "unsafe_gate", "chunk_from_zero"):
        assert not got[fault]["ok"], fault


def test_chip_check_plants_and_lifts_its_faults():
    """Every fault changes what is traced and is lifted afterwards."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import ling_chip_check as tool
    from mxnet_tpu.gluon.model_zoo.nlp import ling_linear as model
    from mxnet_tpu.gluon.model_zoo.nlp import ling_linear_tiny
    from mxnet_tpu.ops import linear_attention as la

    net = ling_linear_tiny()
    sound = (la.kda_chunk_scan, la.kda_slot_update, la.kda_gates,
             model._kda_layer, model._mla_layer, dict(net._decode_cfg))
    for fault in tool.CONTROLS + tool.READINGS:
        with tool.planted(fault, net):
            now = (la.kda_chunk_scan, la.kda_slot_update, la.kda_gates,
                   model._kda_layer, model._mla_layer,
                   dict(net._decode_cfg))
            assert now[5]["planted"] == fault
            assert any(a is not b for a, b in zip(now[:5], sound[:5])) \
                or {k: v for k, v in now[5].items() if k != "planted"} \
                != sound[5], fault
        assert (la.kda_chunk_scan, la.kda_slot_update, la.kda_gates,
                model._kda_layer, model._mla_layer,
                dict(net._decode_cfg)) == sound
