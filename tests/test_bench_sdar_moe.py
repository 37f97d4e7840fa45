"""The SDAR cell's control flow on the CPU at tiny sizes
(``configs/tiny_sdar_moe.json`` + ``traffic/tiny_blockgen_closed.json``):
a ``harness.Run`` built by hand, the block driver run to its end with
``correct`` true (streams at every step of their blocks, slots turning
over inside the window), the cell's per-layer readers on what it hands
back, the manifest entries, and what the driver's ``correct`` sees
(``tools/sdar_correct_controls.py``). ``rehearsal.json`` lists no such
cell: this test stands in, as ``test_bench_falcon_h1.py`` does."""
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
CELL = "sdar_blockdiff_closed_c128"
CONFIG = "sdar_30b_a3b_l6"
SCOPE_READERS = ("sdar_attn_ms_per_round", "sdar_moe_ms_per_round",
                 "sdar_head_pick_ms_per_round")
TRACE_READERS = SCOPE_READERS + ("sdar_moe_tokens_per_expert",
                                 "sdar_moe_experts_roofline",
                                 "sdar_decode_streams_per_round")
NEW_READERS = ("sdar_forwards_per_token", "sdar_unmasked_per_denoise_step"
               ) + TRACE_READERS[:4] + (
    "sdar_moe_experts_roofline", "sdar_decode_streams_per_round",
    "ttft_p95_ms_sdar", "peak_hbm_gb_sdar")
HOST_TURN = tuple(f"host_turn_{p}ms_per_round" for p in
                  ("", "emit_", "sched_", "build_", "launch_", "fetch_"))
APPENDED = ("tok_gap_p99_ms", "tpot_p95_ms", "compiles_in_window_serve",
            "prefill_ms_p50", "decode_step_ms_p50", "pallas_sites_serve",
            "device_idle_pct_serve", "pallas_ms_per_round_serve",
            "paged_attn_roofline") + HOST_TURN


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

    from benchmarks.builders import sdar_moe as builder
    from benchmarks.drivers import closed_loop_blocks
    from benchmarks.lib import harness, serve_loop
    from benchmarks.references import sdar_moe as reference
    import mxnet_tpu as mx

    assert mx.tpu(0).jax_device().platform == "cpu"
    config = _load("configs", "tiny_sdar_moe")
    traffic = _load("traffic", "tiny_blockgen_closed")
    run = harness.Run(
        cell={"name": "tiny_sdar_closed", "config": "tiny_sdar_moe",
              "traffic": "tiny_blockgen_closed", "chips": 1},
        config=config, traffic=traffic, seed=2147483700, seconds=2.0,
        trace=True, devices=jax.devices()[:1], peaks=None, builder=builder,
        reference=reference,
        out_dir=str(tmp_path_factory.mktemp("bench_out")),
        t0=time.perf_counter(), watch=harness.CompileWatch())
    plain = serve_loop.check_outputs
    result = closed_loop_blocks.run(run)
    assert serve_loop.check_outputs is plain    # the driver put it back
    return run, result


def test_closed_loop_runs_to_its_end_correct(traced_run):
    run, result = traced_run
    assert result.correct, result.notes
    assert result.failed == 0 and result.attempted > 6
    check = result.notes["reference_check"]
    assert check["checked"] == 3 and check["steps"] >= 6
    # float32 on the CPU: the replay is the served program's own numbers
    assert check["worst_gap_in_tolerances"] < 0.01
    assert check["worst_position_gap_in_tolerances"] < 0.01
    assert math.isfinite(result.end_to_end["tpot_p50_ms"])
    assert result.notes["server_stats"]["errors"] == 0
    assert result.notes["compiles_in_window"]["compiles"] == 0


def _inputs(traced_run, **extra):
    run, result = traced_run
    return dict(result.layer, config=run.config, traffic=run.traffic,
                cell=run.cell, **{"peaks": None, **extra})


def test_counter_and_span_readers_on_the_run(traced_run):
    inputs = _inputs(traced_run)
    per_token = _reader("sdar_forwards_per_token").read(inputs)
    # the static schedule; short answers, so the uncommitted last blocks
    # and the blocks opened by a prompt's tail move it more than in the
    # cell (answers of 5-11 tokens here, 256-768 there)
    assert 1.0 < per_token < 1.5
    assert _reader("sdar_unmasked_per_denoise_step").read(inputs) == 1.0
    assert _reader("compiles_in_window_serve").read(inputs) == 0.0
    assert _reader("ttft_p95_ms_sdar").read(inputs) == \
        _reader("ttft_p95_ms").read(inputs) > 0.0
    assert _reader("peak_hbm_gb_sdar").read(inputs) is None   # the CPU
    assert _reader("decode_step_ms_p50").read(inputs) > 0.0
    # the round's phase spans are there for the host_turn_* readers
    # (which need a device trace to read idle time against)
    assert {"decode.round", "round.build", "round.fetch", "round.emit"} <= \
        {s["name"] for s in inputs["spans"]}
    # a stream's decode.step span says where its block is
    steps = [s for s in inputs["spans"] if s["name"] == "decode.step"]
    assert steps and all(
        {"round", "step", "commit", "unmasked", "token"} <= set(s["tags"])
        for s in steps)
    assert {s["tags"]["step"] for s in steps} == {0, 1, 2, 3, 4}
    assert all(s["tags"]["unmasked"] == (0 if s["tags"]["commit"] else 1)
               for s in steps)
    assert all(s["tags"]["commit"] == (s["tags"]["step"] == 4)
               for s in steps if s["tags"]["step"] in (0, 4))


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_report_nothing_without_device_names(traced_run,
                                                           name):
    """A CPU trace has no TPU plane, and the parent's program has no such
    programs: the reader returns None, no raise."""
    assert _reader(name).read(_inputs(traced_run)) is None
    assert _reader(name).read(_inputs(traced_run, trace=None)) is None
    empty = {"ops": [], "modules": [], "marks": []}
    assert _reader(name).read(_inputs(traced_run, scoped=empty)) is None
    other = dict(_inputs(traced_run, scoped=empty),
                 config=_load("configs", "tiny_falcon_h1"))
    assert _reader(name).read(other) is None


@pytest.mark.parametrize("name", ["sdar_forwards_per_token",
                                  "sdar_unmasked_per_denoise_step"])
def test_counter_readers_report_nothing_on_a_program_without_them(name):
    """What the parent commit's run of another cell hands back."""
    inputs = {"counters_before": {}, "counters_after": {}, "window_s": 2.0,
              "spans": [], "config": _load("configs", "tiny_longcat")}
    assert _reader(name).read(inputs) is None


def _synthetic_chip(rounds=3, layers=3, streams=5):
    """``rounds`` block rounds of the tiny configuration's three layers, a
    prefill between rounds (its layer program has another name and no
    head), and each forward's pick marks."""
    from benchmarks.lib.xplane_scopes import ScopedOp

    ops, modules, marks, t = [], [], [], 0.0

    def run(prog, scopes):
        nonlocal t
        modules.append(ScopedOp(f"jit_{prog}(3)", t, 9e6))
        for scope, dur in scopes:
            ops.append(ScopedOp(f"jit({prog})/jit(main)/{scope}", t, dur))
            t += dur
        t += 1e6

    layer = [("sdar.attn/dot_general:", 0.5e6),
             ("sdar.attn/jit(paged)/pallas_call:", 0.75e6),
             ("moe.router/dot_general:", 0.25e6),
             ("moe.experts/while/body/gmm:", 2e6),
             ("add:", 0.125e6)]
    for _ in range(rounds):
        for _ in range(layers):
            run("sdar_block_layer", layer)
        run("sdar_head", [("sdar.head/dot_general:", 1e6),
                          ("diffusion.pick/reduce:", 0.5e6)])
        pairs = streams * 4 * 3             # 3 picks a position
        marks.append({"phase": "decode", "held": pairs * layers, "zero": 0,
                      "absent": 0, "touched": 8 * layers, "layers": layers})
        for _ in range(layers):
            run("sdar_prefill_layer", [("moe.experts/while:", 30e6)])
        marks.append({"phase": "prefill", "held": 999, "zero": 0,
                      "absent": 0, "touched": 8 * layers, "layers": layers})
    return {"ops": ops, "modules": modules, "marks": marks}


def test_scope_readers_on_a_synthetic_trace(traced_run):
    inputs = _inputs(traced_run, scoped=_synthetic_chip(),
                     peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9})
    read = {n: _reader(n).read(inputs) for n in TRACE_READERS}
    assert read["sdar_attn_ms_per_round"] == pytest.approx(3 * 1.25)
    assert read["sdar_moe_ms_per_round"] == pytest.approx(3 * 2.25)
    assert read["sdar_head_pick_ms_per_round"] == pytest.approx(1.5)
    # 5 streams x 4 positions x 3 picks over the tiny preset's 8 experts
    assert read["sdar_moe_tokens_per_expert"] == pytest.approx(60 / 8)
    assert read["sdar_decode_streams_per_round"] == pytest.approx(5.0)
    from benchmarks.kernels import moe_experts as k

    s = {"hidden": 64, "expert_hidden": 32, "held": 8}
    floor = max(k.flops(s, 60) / 197e12, k.bytes_moved(s, 60, 8) / 819e9)
    assert read["sdar_moe_experts_roofline"] == pytest.approx(
        100.0 * floor * 9 / (9 * 2e-3))


def test_experts_roofline_at_the_cells_own_widths():
    """The published widths through ``benchmarks/kernels/moe_experts.py``:
    6 x 2048 x 768 FLOP a pair; a round's 4,096 pairs over 128 touched
    experts move 1.21 GB a layer (the weights: 1.208), which bounds it."""
    from benchmarks.kernels import moe_experts as k

    config = _load("configs", CONFIG)
    s = {"hidden": config["hidden_size"],
         "expert_hidden": config["moe_intermediate_size"],
         "held": config["num_experts"]}
    assert k.flops(s, 1) == 6 * 2048 * 768
    moved = k.bytes_moved(s, 4096, 128)
    assert 1.20e9 < 128 * 3 * 2048 * 768 * 2 < moved < 1.26e9
    assert moved / 819e9 > 5 * k.flops(s, 4096) / 197e12


# -- the manifest and the cell's files ---------------------------------------------------------

def test_manifest_entries_and_their_places():
    manifest = _manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    # eleven cells when this one was accepted; later PRs append theirs
    assert cells[10:11] == [CELL] and len(cells) >= 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert [c["name"] for c in manifest["configs"]][7:8] == [CONFIG]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    # new entries went to the END of the list, every accepted one is
    # where it was accepted
    assert names[88:88 + len(NEW_READERS)] == list(NEW_READERS)
    assert names.index("mla_attn_roofline") == 33
    assert names.index("host_turn_fetch_ms_per_round") == 61
    assert names.index("peak_hbm_gb_c128") == 71
    for name in NEW_READERS:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tpot_p50_ms"
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert per_layer["sdar_moe_experts_roofline"]["unit"] == "%"
    for name in APPENDED:
        # later cells append theirs behind it
        assert CELL in per_layer[name]["workloads"], name
    # LongCat's and GLM's own readers stay theirs
    assert per_layer["moe_experts_roofline"]["workloads"] == \
        ["longcat_flash_decode_c256"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["tpot_p50_ms"]["workloads"]
    assert CELL not in e2e["served_tokens_s"]["workloads"]
    layers = {m["layer"] for m in manifest["per_layer"][:88]}
    assert {per_layer[n]["layer"] for n in NEW_READERS} <= layers
    for w in manifest["workloads"][10:11]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
        assert (w["config"], w["traffic"]) == (CONFIG,
                                               "blockgen_closed_c128")
    entry = manifest["configs"][7]
    assert len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_config_keeps_the_catalog_row():
    """Every key of the catalog row's ``config`` is in the file under the
    same name, unchanged but the depth."""
    config = _load("configs", CONFIG)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    entry = next(c for c in _manifest()["configs"] if c["name"] == CONFIG)
    assert config["source"] == entry["source"] == row["source_url"]
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert config["published"] == row["config"]
    assert row["config"]["num_hidden_layers"] == 48
    assert config["num_hidden_layers"] == 6
    assert (config["num_experts"], config["num_experts_per_tok"],
            config["vocab_size"]) == (128, 8, 151936)
    assert 0 <= config["mask_token_id"] < config["vocab_size"]
    assert config["not_served"] == {}
    for key in ("deployment", "reduced_why", "assumed"):
        assert config[key], key
    for key in ("values_from_memory", "q_k_norm", "rotary", "router",
                "block_length", "denoising_steps", "remasking_strategy",
                "mask_token_id", "greedy", "commit", "precision", "weights",
                "what_correct_sees", "what_correct_cannot_see"):
        assert config["assumed"][key], key


def test_cell_files_meet_what_the_harness_reads():
    traffic = _load("traffic", "blockgen_closed_c128")
    assert traffic["driver"] == "closed_loop_blocks"
    s = traffic["server"]
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
    # every prefill signature the bound lets a tick make, and every block
    # bucket but the widest, which the builder warms itself
    allowed = {(b, l) for b in s["batch_buckets"]
               for l in s["len_buckets"] if b * l <= bound}
    assert warmed == allowed
    assert {grid.batch_bucket(n) for n, _ in groups} == \
        set(s["batch_buckets"][:-1])
    assert s["batch_buckets"][-1] * s["len_buckets"][0] > bound
    # ISSUE 47's traffic, letter for letter
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.7,
        "min": 64, "max": 1024}
    assert traffic["output_len"] == {"dist": "uniform", "min": 256,
                                     "max": 768}
    assert traffic["clients"] in (128, 96)
    assert s["page_size"] == 16
    assert s["len_buckets"][-1] == traffic["prompt_len"]["max"]
    assert s["max_generate_tokens"] == 1792 == \
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert (s["decode_pages"] - 1) * s["page_size"] == \
        traffic["clients"] * s["max_generate_tokens"]
    assert max(s["batch_buckets"]) >= traffic["clients"]


def test_builder_warms_the_widest_block_bucket_itself(monkeypatch):
    """Where ``max_prefill_tokens`` keeps every prefill batch below the
    widest batch bucket, the harness's warm-up cannot reach that bucket's
    block program: the builder runs it first (Falcon-H1's
    ``warm_widest_decode``), with prompts of the shortest length bucket a
    bounded batch at a time."""
    import mxnet_tpu as mx
    from benchmarks.builders import sdar_moe as builder
    from mxnet_tpu.gluon.model_zoo.nlp import sdar_moe as model

    config = _load("configs", "tiny_sdar_moe")
    traffic = _load("traffic", "tiny_blockgen_closed")
    net, ctx = builder.build_net(config, 5, ctx=mx.cpu(0))
    seen = []
    run = model.SdarMoeDecodeEngine._run

    def watched(self, b, l, *rest, **seam):
        seen.append((b, l))
        return run(self, b, l, *rest, **seam)

    monkeypatch.setattr(model.SdarMoeDecodeEngine, "_run", watched)
    # the cell's shape in small: buckets (1, 2, 8), a bound of two prompts
    tight = dict(traffic, server=dict(
        traffic["server"], batch_buckets=[1, 2, 8], max_prefill_tokens=16,
        decode_pages=97))
    srv = builder.start_server(net, ctx, tight)
    try:
        assert builder.warm_widest_decode(srv, tight, 255, 5) == 5
        assert srv.stats()["generates_active"] == 0
    finally:
        srv.stop(timeout=30.0)
    assert set(seen) == {(1, 8), (2, 8), (1, 4), (2, 4), (8, 4)}


def test_weights_and_cache_fill_the_chip():
    from benchmarks.builders import sdar_moe as b

    config = _load("configs", CONFIG)
    s = _load("traffic", "blockgen_closed_c128")["server"]
    layers = config["num_hidden_layers"]
    per_layer = sum(int(np.prod(x))
                    for x in b._layer_shapes(config).values())
    assert 623.0e6 < per_layer < 623.2e6
    params = layers * per_layer + 2 * 151936 * 2048 + 2048
    assert 4.360e9 < params < 4.362e9               # 8.72 GB of bf16
    token = layers * 2 * 4 * 128 * 2                # K and V, bf16
    assert token == 12288
    cache_gb = s["decode_pages"] * s["page_size"] * token / 1e9
    assert cache_gb < 2.83
    # far over a quarter of a 16.9 GB chip before a prefill's temporaries
    assert (2 * params / 1e9 + cache_gb) / 16.9 > 0.6
    # a position's forward: 8 of 128 experts, the attention, the head
    flops = b.flops_per_token(config, {})
    assert 2 * (layers * (18.87e6 + 8 * 4.718e6) + 311.1e6) < flops < \
        2 * (layers * (19.2e6 + 8 * 4.72e6) + 311.3e6)


def test_the_builder_fails_at_once_without_the_model():
    """On a checkout without the model (the parent commit with this PR's
    benchmark files laid over it) importing the builder raises
    ImportError before anything is built: run.py exits at once."""
    with open(os.path.join(BENCH, "builders", "sdar_moe.py")) as f:
        text = f.read()
    first = next(line for line in text.splitlines()
                 if line.startswith(("import ", "from "))
                 and "__future__" not in line and line != "import math")
    assert first.startswith("import mxnet_tpu.gluon.model_zoo.nlp.sdar_moe")


# -- what `correct` can see: tools/sdar_correct_controls.py ----------------------------------

@pytest.fixture(scope="module")
def judged():
    """The driver's ``check_blocks`` on the answers of the tiny cell's
    own server, sound and with each fault planted."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "sdar_correct_controls",
        os.path.join(ROOT, "tools", "sdar_correct_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = tool.judge(_load("configs", "tiny_sdar_moe"),
                     _load("traffic", "tiny_blockgen_closed"), 2147483693,
                     [13, 22], 12, (None,) + tool.CONTROLS)
    return tool, got


def test_correct_holds_for_the_sound_program(judged):
    _, got = judged
    sound = got["sound"]
    assert sound["ok"] and sound["checked"] == 2 and sound["steps"] >= 20
    assert sound["worst_gap_in_tolerances"] < 0.01      # float32, tiny
    assert sound["worst_position_gap_in_tolerances"] < 0.01
    # the layers, not a position's own embedding, pick the tokens
    assert min(sound["distinct_tokens"]) >= 5


@pytest.mark.parametrize("fault", ("causal_in_block", "stale_keys",
                                   "skipped_commit"))
def test_correct_fails_with_a_fault_planted(judged, fault):
    """The tiny preset holds the three faults in the mathematics, by the
    tokens (a) AND by the positions (b); ``lower_precision`` passes (b)
    alone here (three layers, 24 positions) and is held on the chip
    (``PERF.md`` section 6)."""
    tool, got = judged
    assert fault in tool.CONTROLS and fault in tool.PINNED
    assert not got[fault]["ok"], got[fault]
    assert got[fault]["worst_gap_in_tolerances"] > 2.0
    assert got[fault]["worst_position_gap_in_tolerances"] > 2.0


def test_planted_faults_are_taken_out_again(judged):
    from mxnet_tpu.gluon.model_zoo.nlp import sdar_moe as model
    from mxnet_tpu.ops import attention

    tool, got = judged
    assert set(got) == {"sound"} | set(tool.CONTROLS)
    assert got["lower_precision"]["worst_gap_in_tolerances"] > \
        50 * max(got["sound"]["worst_gap_in_tolerances"], 1e-3)
    assert model._layer_forward.__name__ == "_layer_forward"
    assert model._scatter_rows.__name__ == "_scatter_rows"
    assert attention.paged_attention.__name__ == "paged_attention"
    assert model.SdarMoeDecodeEngine.decode_block.__name__ == \
        "decode_block"
    for test in tool.PINNED.values():
        path, name = test.split("::")
        with open(os.path.join(ROOT, path)) as f:
            assert f"def {name}(" in f.read(), test
