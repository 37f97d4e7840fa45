"""The Falcon-H1 cell's control flow on the CPU at tiny sizes
(``configs/tiny_falcon_h1.json`` + ``traffic/tiny_chat_closed.json``): a
``harness.Run`` built by hand, the closed-loop driver run to its end with
``correct`` true (slots turning over inside the window, prompts of several
chunks beside decoding streams), the cell's per-layer readers on what it
hands back, the manifest entries, and what the harness's ``correct`` sees
(``tools/falcon_h1_correct_controls.py``). ``rehearsal.json`` lists no
such cell: this test stands in, as ``test_bench_phi4flash.py`` does."""
import importlib
import json
import math
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "falcon_h1_chat_closed_c128"
LONGCTX = "mistral7b_longctx_decode"
SCOPE_READERS = ("ssd_ms_per_round", "h1_attn_ms_per_round",
                 "h1_proj_ms_per_round", "h1_mlp_ms_per_round",
                 "h1_head_ms_per_round")
TRACE_READERS = SCOPE_READERS + ("ssd_update_roofline",
                                 "h1_decode_streams_per_round")
NEW_READERS = TRACE_READERS + ("state_slot_allocs_per_s",
                               "ttft_p95_ms_c128", "peak_hbm_gb_c128")
HOST_TURN = tuple(f"host_turn_{p}ms_per_round" for p in
                  ("", "emit_", "sched_", "build_", "launch_", "fetch_"))
APPENDED = ("tok_gap_p99_ms", "tpot_p95_ms", "compiles_in_window_serve",
            "prefill_ms_p50", "decode_step_ms_p50", "pallas_sites_serve",
            "device_idle_pct_serve", "paged_attn_roofline") + HOST_TURN


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

    from benchmarks.builders import falcon_h1 as builder
    from benchmarks.drivers import closed_loop
    from benchmarks.lib import harness
    from benchmarks.references import falcon_h1 as reference
    import mxnet_tpu as mx

    assert mx.tpu(0).jax_device().platform == "cpu"
    config = _load("configs", "tiny_falcon_h1")
    traffic = _load("traffic", "tiny_chat_closed")
    run = harness.Run(
        cell={"name": "tiny_falcon_h1_closed", "config": "tiny_falcon_h1",
              "traffic": "tiny_chat_closed", "chips": 1},
        config=config, traffic=traffic, seed=2147483700, seconds=2.0,
        trace=True, devices=jax.devices()[:1], peaks=None, builder=builder,
        reference=reference,
        out_dir=str(tmp_path_factory.mktemp("bench_out")),
        t0=time.perf_counter(), watch=harness.CompileWatch())
    return run, closed_loop.run(run)


def test_closed_loop_runs_to_its_end_correct(traced_run):
    run, result = traced_run
    assert result.correct, result.notes
    # three callers whose answers end inside the window: slots turn over
    assert result.failed == 0 and result.attempted > 6
    assert result.notes["reference_check"]["checked"] == 3
    assert math.isfinite(result.end_to_end["tpot_p50_ms"])
    stats = result.notes["server_stats"]
    assert stats["errors"] == 0
    assert result.notes["compiles_in_window"]["compiles"] == 0


def _inputs(traced_run, **extra):
    run, result = traced_run
    return dict(result.layer, config=run.config, traffic=run.traffic,
                cell=run.cell, peaks=None, **extra)


def test_counter_and_span_readers_on_the_run(traced_run):
    run, result = traced_run
    inputs = _inputs(traced_run)
    # three callers: at most three streams ever held a slot, all did
    assert _reader("state_slots_in_use").read(inputs) == 3.0
    per_s = _reader("state_slot_allocs_per_s").read(inputs)
    assert per_s == pytest.approx(result.attempted / run.seconds)
    assert _reader("compiles_in_window_serve").read(inputs) == 0.0
    assert _reader("ttft_p95_ms_c128").read(inputs) == \
        _reader("ttft_p95_ms").read(inputs) > 0.0
    assert _reader("peak_hbm_gb_c128").read(inputs) is None   # the CPU
    assert _reader("decode_step_ms_p50").read(inputs) > 0.0
    from benchmarks.lib import harness

    after = result.layer["counters_after"]
    assert harness.counter_sum(after, "mxnet_state_slots_in_use") == 0.0
    # every stream's decode.step span names its round
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
    phi = dict(_inputs(traced_run, scoped=empty),
               config=_load("configs", "tiny_phi4flash"))
    assert _reader(name).read(phi) is None


def test_counter_reader_reports_nothing_on_a_program_without_it():
    """What the parent commit's run of another cell hands back."""
    inputs = {"counters_before": {}, "counters_after": {}, "window_s": 2.0,
              "spans": [], "config": _load("configs", "tiny_longcat")}
    assert _reader("state_slot_allocs_per_s").read(inputs) is None


def _synthetic_chip(rounds=3, layers=4):
    """``rounds`` decode rounds of the tiny configuration's four layers,
    a prefill between rounds (its layer program has another name; its
    last token runs the head program too)."""
    from benchmarks.lib.xplane_scopes import ScopedOp

    ops, modules, t = [], [], 0.0

    def run(prog, scopes):
        nonlocal t
        modules.append(ScopedOp(f"jit_{prog}(3)", t, 9e6))
        for scope, dur in scopes:
            ops.append(ScopedOp(f"jit({prog})/jit(main)/{scope}", t, dur))
            t += dur
        t += 1e6

    layer = [("ssd.proj/dot_general:", 1e6), ("ssd.scan/mul:", 0.25e6),
             ("ssd.scan/jit(ssd_state_update)/pallas_call:", 1.5e6),
             ("ssd.proj/dot_general:", 0.5e6), ("h1.proj/dot_general:", 0.5e6),
             ("h1.attn/jit(paged)/pallas_call:", 0.75e6),
             ("h1.proj/dot_general:", 0.25e6), ("h1.mlp/dot_general:", 2e6)]
    for _ in range(rounds):
        for _ in range(layers):
            run("falcon_h1_decode_layer", layer)
        run("falcon_h1_head", [("h1.head/dot_general:", 3e6)])
        run("falcon_h1_prefill_layer", [("ssd.scan/while:", 30e6)])
        run("falcon_h1_head", [("h1.head/dot_general:", 3e6)])
    return {"ops": ops, "modules": modules, "marks": []}


def test_scope_readers_on_a_synthetic_trace(traced_run):
    inputs = _inputs(traced_run, scoped=_synthetic_chip())
    read = {n: _reader(n).read(inputs) for n in SCOPE_READERS}
    assert read["ssd_ms_per_round"] == pytest.approx(4 * 1.75)
    assert read["h1_attn_ms_per_round"] == pytest.approx(4 * 0.75)
    assert read["h1_proj_ms_per_round"] == pytest.approx(4 * 2.25)
    assert read["h1_mlp_ms_per_round"] == pytest.approx(4 * 2.0)
    # a RUN of the head program, whoever ran it
    assert read["h1_head_ms_per_round"] == pytest.approx(3.0)


def _slice_inputs(kernel):
    """A slice of two rounds (128 and 127 streams) on the published
    sizes: twelve runs of the state-update kernel (six layers a round) of
    1.5 ms each, or none."""
    from benchmarks.lib import trace_reduce

    name = ("%ssd_state_update.3 = (f32[129,32,256,128], f32[128,32,128]) "
            "custom-call(s32[128] %p, f32[129,32,256,128] %s)") if kernel \
        else "%fusion.9 = f32[129,32,256,128] fusion(...)"
    events = [trace_reduce.Event(name, 1e6 + i * 2e6, 1.5e6)
              for i in range(12)]
    for e in events:
        e.long_name = name + (' custom_call_target="tpu_custom_call"'
                              if kernel else "")
    trace = type("T", (), {"devices": {0: events}})()
    spans = [{"name": "decode.step", "ts": 1e3 + r * 12e3, "dur": 11e3,
              "trace_id": f"s{i}", "tags": {"token": r, "round": 40 + r}}
             for r in range(2) for i in range(128 - r)]
    return {"trace": trace, "trace_clock_offset_ns": 0, "spans": spans,
            "trace_prompt_len": {f"s{i}": 100 for i in range(128)},
            "config": _load("configs", "falcon_h1_34b_l6"),
            "traffic": _load("traffic", "chat_closed_c128"),
            "cell": {"name": CELL, "chips": 1},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "scoped": {"ops": [], "marks": [], "modules": [
                type("M", (), {"op_name": "jit_falcon_h1_decode_layer(1)"})()
                for _ in range(12)]}}


def test_update_roofline_counts_each_state_once_in_and_once_out():
    from benchmarks.kernels import ssd_state_update as k

    config = _load("configs", "falcon_h1_34b_l6")
    s = k.shapes(config, {}, 1)
    assert k.state_values(s) == 32 * 256 * 128 == 1048576
    # one stream, one layer: 4.19 MB in, 4.19 MB out, 57 KB of rows
    assert k.bytes_moved(s, 1) == 2 * 4194304 + 4 * (3 * 4096 + 2 * 512)
    assert k.flops(s, 1) == 5 * 1048576
    inputs = _slice_inputs(True)
    if not inputs["trace"].devices[0][0].long_name:
        pytest.skip("no long_name on trace events")
    updates = 12 * 127.5
    floor = k.bytes_moved(s, updates) / 819e9
    assert _reader("ssd_update_roofline").read(inputs) == \
        pytest.approx(100.0 * floor / (12 * 1.5e-3))
    assert 0 < _reader("ssd_update_roofline").read(inputs) < 100
    assert _reader("ssd_update_roofline").read(_slice_inputs(False)) is None
    # the paged kernel's pattern does not take the update for its own
    from benchmarks.kernels import paged_attention
    from benchmarks.lib import readers

    assert readers.pallas_events(inputs, paged_attention.PATTERN) == []
    assert _reader("h1_decode_streams_per_round").read(inputs) > 100


# -- the manifest and the cell's files ---------------------------------------------------------

def test_manifest_entries_and_their_places():
    manifest = _manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    # nine cells when this one was accepted; later PRs append theirs
    assert cells[8:9] == [CELL] and len(cells) >= 9
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert [c["name"] for c in manifest["configs"]][5:6] == \
        ["falcon_h1_34b_l6"]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    # new entries went to the END of the list, every accepted one is
    # where it was accepted
    assert names[62:62 + len(NEW_READERS)] == list(NEW_READERS)
    assert names.index("mla_attn_roofline") == 33
    assert names.index("ssm_ms_per_round") == 45
    assert names.index("host_turn_fetch_ms_per_round") == 61
    for name in NEW_READERS:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tpot_p50_ms"
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert per_layer["ssd_update_roofline"]["unit"] == "%"
    for name in APPENDED:
        assert CELL in per_layer[name]["workloads"], name
    # later cells with state slots append theirs (PR 50)
    assert per_layer["state_slots_in_use"]["workloads"][:2] == \
        ["phi4flash_reason_c32", CELL]
    assert CELL in per_layer["pallas_ms_per_round_serve"]["workloads"]
    assert per_layer["peak_hbm_gb_c128"]["better"] == \
        per_layer["peak_hbm_gb_c32"]["better"] == "lower"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["tpot_p50_ms"]["workloads"]
    for w in manifest["workloads"][8:9]:
        assert len(w["why"]) <= 200 and w["chips"] == 1


def test_config_keeps_the_catalog_row():
    """Every key of the catalog row's ``config`` is in the file under the
    same name, unchanged but the depth."""
    config = _load("configs", "falcon_h1_34b_l6")
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    entry = next(c for c in _manifest()["configs"]
                 if c["name"] == "falcon_h1_34b_l6")
    assert config["source"] == entry["source"] == row["source_url"]
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert config["published"] == row["config"]
    assert row["config"]["num_hidden_layers"] == 72
    assert config["num_hidden_layers"] in (5, 6)
    assert config["not_served"] == {}
    for key in ("deployment", "reduced_why", "assumed"):
        assert config[key], key
    for key in ("values_from_memory", "convolution", "gated_norm", "rotary",
                "dt", "precision", "weights", "what_correct_sees",
                "what_correct_cannot_see"):
        assert config["assumed"][key], key


def test_cell_files_meet_what_the_harness_reads():
    traffic = _load("traffic", "chat_closed_c128")
    config = _load("configs", "falcon_h1_34b_l6")
    assert traffic["driver"] == "closed_loop"
    s = traffic["server"]
    from mxnet_tpu.serving.buckets import BucketGrid

    grid = BucketGrid(tuple(s["batch_buckets"]), None,
                      len_buckets=tuple(s["len_buckets"]))
    bound = s["max_prefill_tokens"]
    assert bound == 4096
    warmed = set()
    groups = [[1, s["warmup"][0][1]]] + s["warmup"]   # serve_loop.warm_up
    for n, plen in groups:
        sig = (grid.batch_bucket(n), grid.prefill_bucket(plen))
        assert sig[0] * sig[1] <= bound, (n, plen)      # one batch each
        warmed.add(sig)
    # every prefill signature the bound lets a tick make of the traffic's
    # prompts, and every decode bucket but the widest: 128 streams decode
    # only after 17 prompts prefilled as one batch, which the bound allows
    # at no length bucket; the builder warms that bucket itself
    # (test_builder_warms_the_widest_decode_bucket_itself)
    allowed = {(b, l) for b in s["batch_buckets"]
               for l in s["len_buckets"] if b * l <= bound}
    assert warmed == allowed
    assert {grid.batch_bucket(n) for n, _ in groups} == \
        set(s["batch_buckets"][:-1])
    assert s["batch_buckets"][-1] * s["len_buckets"][0] > bound
    # ISSUE 40's traffic and server group, letter for letter
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.8,
        "min": 32, "max": 384}
    assert traffic["output_len"] == {"dist": "uniform", "min": 256,
                                     "max": 640}
    assert (traffic["clients"], traffic["max_rps_per_client"]) == (128, 0.5)
    assert s["batch_buckets"] == [1, 16, 128]
    assert s["len_buckets"] == [64, 384] and s["page_size"] == 16
    assert s["max_generate_tokens"] == 1024 == \
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert (s["decode_pages"] - 1) * s["page_size"] == \
        traffic["clients"] * s["max_generate_tokens"]
    # a slot a stream of the widest decode round
    assert max(s["batch_buckets"]) == traffic["clients"]
    assert config["env"] == {"MXNET_PALLAS_FUSED": "1"}


def test_builder_warms_the_widest_decode_bucket_itself(monkeypatch):
    """Where ``max_prefill_tokens`` keeps every prefill batch below the
    widest batch bucket, the harness's warm-up (one prefill batch a
    group) cannot reach that bucket's decode program: the builder runs it
    before the harness's groups, with prompts of the shortest length
    bucket a bounded batch at a time; where the harness's groups reach
    it, the builder sends nothing."""
    import mxnet_tpu as mx
    from benchmarks.builders import falcon_h1 as builder
    from mxnet_tpu.gluon.model_zoo.nlp import falcon_h1 as model

    config = _load("configs", "tiny_falcon_h1")
    traffic = _load("traffic", "tiny_chat_closed")
    net, ctx = builder.build_net(config, 5, ctx=mx.cpu(0))
    seen = []
    run = model.FalconH1DecodeEngine._run

    def watched(self, b, l, *rest, **seam):
        seen.append((b, l))
        return run(self, b, l, *rest, **seam)

    monkeypatch.setattr(model.FalconH1DecodeEngine, "_run", watched)
    srv = builder.start_server(net, ctx, traffic)
    try:
        assert builder.warm_widest_decode(srv, traffic, 128, 5) == 0
        assert not seen
    finally:
        srv.stop(timeout=30.0)
    # the cell's shape in small: buckets (1, 2, 8), a bound of two prompts
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
    from benchmarks.builders import falcon_h1 as b

    config = _load("configs", "falcon_h1_34b_l6")
    s = _load("traffic", "chat_closed_c128")["server"]
    layers = config["num_hidden_layers"]
    per_layer = sum(int(np.prod(x))
                    for x in b._layer_shapes(config).values())
    assert 430.0e6 < per_layer < 430.3e6
    params = layers * per_layer + 2 * 261120 * 5120 + 5120
    token = layers * 2 * 4 * 128 * 2                # K and V, bf16
    cache_gb = s["decode_pages"] * s["page_size"] * token / 1e9
    slot = layers * 4 * (32 * 256 * 128 + 3 * 5120)
    slots_gb = (max(s["batch_buckets"]) + 1) * slot / 1e9
    if layers == 6:
        assert 5.25e9 < params < 5.26e9             # 10.51 GB of bf16
        assert 1.60 < cache_gb < 1.62 and 25.5e6 < slot < 25.6e6
        assert 3.29 < slots_gb < 3.31
    # far over a quarter of a 16.9 GB chip before a prefill's temporaries
    assert (2 * params / 1e9 + cache_gb + slots_gb) / 16.9 > 0.75
    assert b.flops_per_token(config, {}) > 2 * layers * 430e6


def test_the_builder_fails_at_once_without_the_model():
    """On a checkout without the model (the parent commit with this PR's
    benchmark files laid over it) importing the builder raises
    ImportError before anything is built: run.py exits at once."""
    with open(os.path.join(BENCH, "builders", "falcon_h1.py")) as f:
        text = f.read()
    first = next(line for line in text.splitlines()
                 if line.startswith(("import ", "from "))
                 and "__future__" not in line and line != "import math")
    assert first.startswith(
        "import mxnet_tpu.gluon.model_zoo.nlp.falcon_h1")


def test_longctx_cell_was_left_out_with_its_reason():
    """ISSUE 40's second cell, ``mistral7b_longctx_decode``, cannot run
    from data files alone: the GQA builder does not hand the traffic
    file's ``max_prefill_tokens`` to the server, so 24 prompts of 2k
    tokens arriving together are ONE (32, 2048) prefill whose score matrix
    is 16 GB (my chip run, PR 40). It stays first in ``PERF.md`` section
    7's list with the one line a ``benchmark`` PR has to add."""
    cells = [w["name"] for w in _manifest()["workloads"]]
    assert LONGCTX not in cells
    assert not os.path.exists(os.path.join(BENCH, "traffic",
                                           "longctx_closed_c24.json"))
    with open(os.path.join(BENCH, "builders",
                           "llama_family_decoder.py")) as f:
        assert "max_prefill_tokens" not in f.read()
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    assert "Planned in ISSUE 24 and not built" in text
    planned = text[text.index("Planned in ISSUE 24 and not built"):]
    assert planned.index("mistral7b_longctx_decode") < \
        planned.index("mistral7b_chat_burst")
    assert "resnet50_real_data" not in planned


# -- what `correct` can see: tools/falcon_h1_correct_controls.py ------------------------------

@pytest.fixture(scope="module")
def judged():
    """``serve_loop.check_outputs`` on the answers of the tiny cell's own
    server, sound and with each fault planted."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "falcon_h1_correct_controls",
        os.path.join(ROOT, "tools", "falcon_h1_correct_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = tool.judge(_load("configs", "tiny_falcon_h1"),
                     _load("traffic", "tiny_chat_closed"), 2147483693,
                     [21, 33], 12, (None,) + tool.CONTROLS + tool.READINGS)
    return tool, got


def test_correct_holds_for_the_sound_program(judged):
    _, got = judged
    sound = got["sound"]
    assert sound["ok"] and sound["checked"] == 2
    assert sound["worst_gap_in_tolerances"] == 0.0      # float32, tiny
    # the layers, not the last token alone, pick the next one
    assert min(sound["distinct_tokens"]) >= 6


@pytest.mark.parametrize("fault", ("tail", "key_multiplier",
                                   "ssm_multipliers", "group_norm"))
def test_correct_fails_with_a_fault_planted(judged, fault):
    """The tiny preset holds the four faults in the mathematics;
    ``lower_precision`` needs the published depth and ~1,000 positions to
    pass the limit (0.8 of it over 24 positions here) and is held on the
    chip (``PERF.md`` section 6)."""
    tool, got = judged
    assert fault in tool.CONTROLS
    assert not got[fault]["ok"], got[fault]
    assert got[fault]["worst_gap_in_tolerances"] > 2.0


def test_planted_faults_are_taken_out_again(judged):
    from mxnet_tpu.gluon.model_zoo.nlp import falcon_h1 as model
    from mxnet_tpu.ops import ssm

    tool, got = judged
    assert set(got) == {"sound"} | set(tool.CONTROLS) | set(tool.READINGS)
    assert got["lower_precision"]["worst_gap_in_tolerances"] > \
        10 * got["residual_bf16"]["worst_gap_in_tolerances"]
    assert model._mixer.__name__ == "_mixer"
    assert model._layer_forward.__name__ == "_layer_forward"
    assert ssm.gated_group_norm.__name__ == "gated_group_norm"
    assert model.FalconH1DecodeEngine._make_arenas.__name__ == \
        "_make_arenas"
