"""The Phi-4-mini-flash cell's control flow on the CPU at tiny sizes
(``configs/tiny_phi4flash.json`` + ``traffic/tiny_reason_ctx_closed.json``):
a ``harness.Run`` built by hand, the closed-loop driver run to its end
with ``correct`` true (prompts of several chunks beside decoding streams,
every stream in a state slot), and the cell's per-layer readers on what it
hands back. ``rehearsal.json`` lists no such cell: this test stands in,
as ``test_bench_glm_dsa.py`` does for GLM-5's."""
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
CELL = "phi4flash_reason_c32"
SCOPE_READERS = ("ssm_ms_per_round", "swa_attn_ms_per_round",
                 "yoco_attn_ms_per_round", "gmu_ms_per_round")
TRACE_READERS = SCOPE_READERS + ("phi_decode_streams_per_round",
                                 "diff_paged_attn_roofline")
NEW_READERS = TRACE_READERS + ("prefill_cross_rows_pct",
                               "state_slots_in_use", "ttft_p95_ms_c32",
                               "peak_hbm_gb_c32")
APPENDED = ("tok_gap_p99_ms", "tpot_p95_ms", "compiles_in_window_serve",
            "prefill_ms_p50", "decode_step_ms_p50", "pallas_sites_serve",
            "device_idle_pct_serve", "prefill_chunk_ms_p50",
            "prefill_chunks_per_request")


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

    from benchmarks.builders import phi4flash as builder
    from benchmarks.drivers import closed_loop_first_wave as closed_loop
    from benchmarks.lib import harness
    from benchmarks.references import phi4flash as reference
    import mxnet_tpu as mx

    assert mx.tpu(0).jax_device().platform == "cpu"
    config = _load("configs", "tiny_phi4flash")
    traffic = _load("traffic", "tiny_reason_ctx_closed")
    run = harness.Run(
        cell={"name": "tiny_phi4flash_closed", "config": "tiny_phi4flash",
              "traffic": "tiny_reason_ctx_closed", "chips": 1},
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
    assert math.isfinite(result.end_to_end["tpot_p50_ms"])
    stats = result.notes["server_stats"]
    assert stats["errors"] == 0
    # prompts of 12-60 tokens against a largest length bucket of 16: more
    # prefill dispatches than requests
    assert stats["batches"] >= 4 + result.attempted
    assert result.notes["compiles_in_window"]["compiles"] == 0


def _inputs(traced_run, **extra):
    run, result = traced_run
    return dict(result.layer, config=run.config, traffic=run.traffic,
                cell=run.cell, peaks=None, **extra)


def test_counter_and_span_readers_on_the_run(traced_run):
    inputs = _inputs(traced_run)
    # the cross-decoder ran on one row a request, the self-decoder on
    # every prompt token (12-60 of them)
    pct = _reader("prefill_cross_rows_pct").read(inputs)
    assert 100.0 / 60 <= pct <= 100.0 / 12
    # three callers: at most three streams ever held a slot, all did
    assert _reader("state_slots_in_use").read(inputs) == 3.0
    assert _reader("prefill_chunk_ms_p50").read(inputs) > 0.0
    assert 1.0 <= _reader("prefill_chunks_per_request").read(inputs) <= 4.0
    assert _reader("compiles_in_window_serve").read(inputs) == 0.0
    assert _reader("ttft_p95_ms_c32").read(inputs) == \
        _reader("ttft_p95_ms").read(inputs) > 0.0
    assert _reader("peak_hbm_gb_c32").read(inputs) is None   # the CPU
    run, result = traced_run
    after = result.layer["counters_after"]
    from benchmarks.lib import harness

    assert harness.counter_sum(after, "mxnet_state_slots_in_use") == 0.0
    assert harness.counter_sum(after, "mxnet_state_slot_allocs_total") \
        >= result.attempted
    for phase in ("prefill", "decode"):
        assert harness.counter_sum(
            after, "mxnet_shared_kv_tokens_read_total", phase=phase) > 0


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_report_nothing_without_device_names(traced_run,
                                                           name):
    """A CPU trace has no TPU plane, and the parent's program has no such
    scopes: the reader returns None, no raise."""
    assert _reader(name).read(_inputs(traced_run)) is None
    assert _reader(name).read(_inputs(traced_run, trace=None)) is None
    empty = {"ops": [], "modules": [], "marks": []}
    assert _reader(name).read(_inputs(traced_run, scoped=empty)) is None
    glm = dict(_inputs(traced_run, scoped=empty),
               config=_load("configs", "tiny_glm_dsa"))
    assert _reader(name).read(glm) is None


@pytest.mark.parametrize("name", ("prefill_cross_rows_pct",
                                  "state_slots_in_use"))
def test_counter_readers_report_nothing_on_a_program_without_them(name):
    """What the parent commit's run hands back: no such counters."""
    inputs = {"counters_before": {}, "counters_after": {},
              "spans": [{"name": "decode.step", "dur": 5.0, "ts": 9.0}],
              "config": _load("configs", "tiny_longcat")}
    assert _reader(name).read(inputs) is None


def _synthetic_chip(rounds=3, pairs=2):
    """``rounds`` decode rounds of a model with ``pairs`` self-decoder
    and ``pairs - 1`` cross-decoder pairs (the tiny configuration's 8
    layers), a prefill chunk between rounds."""
    from benchmarks.lib.xplane_scopes import ScopedOp

    ops, modules, t = [], [], 0.0

    def run(prog, scopes):
        nonlocal t
        modules.append(ScopedOp(f"jit_{prog}(3)", t, 9e6))
        for scope, dur in scopes:
            nested = "body" in scope
            ops.append(ScopedOp(f"jit({prog})/jit(main)/{scope}",
                                t - 0.2e6 if nested else t, dur))
            if not nested:
                t += dur
        t += 1e6

    mamba = [("ssm.proj/dot_general:", 1e6), ("ssm.scan/mul:", 0.5e6),
             ("ssm.scan/body/add:", 0.1e6), ("mlp/dot_general:", 2e6)]
    for _ in range(rounds):
        for _ in range(pairs):
            run("phi4flash_decode_self", mamba + [
                ("swa.attend/jit(diff_paged_decode_kernel)/pallas_call:",
                 0.75e6), ("mlp/dot_general:", 2e6)])
        run("phi4flash_decode_mid", mamba + [("yoco.kv/scatter:", 0.25e6)])
        run("phi4flash_decode_full", [("yoco.attend/pallas_call:", 1e6),
                                      ("mlp/dot_general:", 2e6)])
        for _ in range(pairs - 1):
            run("phi4flash_decode_cross", [
                ("gmu/dot_general:", 0.5e6), ("mlp/dot_general:", 2e6),
                ("yoco.attend/pallas_call:", 1e6),
                ("mlp/dot_general:", 2e6)])
        run("phi4flash_head", [("lm_head/dot_general:", 1e6)])
        run("phi4flash_prefill_self", [("ssm.scan/while:", 30e6)])
    return {"ops": ops, "modules": modules, "marks": []}


def test_scope_readers_on_a_synthetic_trace(traced_run):
    inputs = _inputs(traced_run, scoped=_synthetic_chip())
    read = {n: _reader(n).read(inputs) for n in SCOPE_READERS}
    # per round: 3 Mamba layers, 2 window layers, 1 + 1 shared reads, 1 GMU
    assert read["ssm_ms_per_round"] == pytest.approx(3 * 1.5)
    assert read["swa_attn_ms_per_round"] == pytest.approx(2 * 0.75)
    assert read["yoco_attn_ms_per_round"] == pytest.approx(2 * 1.0)
    assert read["gmu_ms_per_round"] == pytest.approx(1 * 0.5)


def test_first_wave_is_the_same_work_under_every_seed():
    """The callers' first prompts are the quantiles of the distribution
    across callers: two seeds differ in who has which, not in the
    lengths; later requests are ``closed_loop``'s."""
    from benchmarks.drivers import closed_loop_first_wave as driver
    from benchmarks.lib import arrivals

    traffic = _load("traffic", "reason_ctx_closed_c32")
    assert traffic["driver"] == "closed_loop_first_wave"
    waves, plain_sums = [], []
    for seed in (3, 2147483999, 2 ** 31 + 11):
        plain = arrivals.closed_loop_schedule(seed, traffic, 1000, 6)
        plain_sums.append(sum(c[0].prompt.size for c in plain))
        later = [[r.prompt.size for r in c[1:]] for c in plain]
        dealt = driver.deal_first_wave(plain, seed, traffic, 1000)
        assert [[r.prompt.size for r in c[1:]] for c in dealt] == later
        waves.append([(c[0].prompt.size, c[0].max_new) for c in dealt])
        assert all(c[0].client == i and c[0].prompt.min() >= 1
                   for i, c in enumerate(dealt))
    want = arrivals.quantile_lengths(traffic["prompt_len"], 32)
    for wave in waves:
        assert sorted(p for p, _ in wave) == want.tolist()
        assert sum(o for _, o in wave) == sum(o for _, o in waves[0])
    assert waves[0] != waves[1] != waves[2]
    assert int(want.sum()) == 163319 and want.min() == 1024 \
        and want.max() == 16384
    # what it replaces: 32 independent draws, whose sum follows the seed
    assert len(set(plain_sums)) == 3


def _slice_inputs(paged):
    """A slice of two rounds of three streams (contexts 40, 100, 700; the
    second round's spans lie half outside it) on the phi configuration's
    published sizes, with or without the paged kernel's custom calls in
    it."""
    from benchmarks.lib import trace_reduce

    name = "%diff_paged_decode.3 = f32[8,48,1280] custom-call(...)" \
        if paged else "%fusion.9 = bf16[8,18944,1280] fusion(...)"
    events = [trace_reduce.Event(name, 1e6 + i * 1e5, 5e4)
              for i in range(32)]          # the slice: 1.00 ms .. 4.15 ms
    if paged:
        for e in events:
            e.long_name = name + ' custom_call_target="tpu_custom_call"'
    trace = type("T", (), {"devices": {0: events}})()
    spans = [{"name": "decode.step", "ts": 1.15e3 + r * 2.0e3,
              "dur": 2.0e3, "trace_id": tid, "tags": {"token": r}}
             for r in range(2) for tid in ("a", "b", "c")]
    return {"trace": trace, "trace_clock_offset_ns": 0, "spans": spans,
            "trace_prompt_len": {"a": 39, "b": 99, "c": 699},
            "config": _load("configs", "phi4_mini_flash"),
            "traffic": _load("traffic", "reason_ctx_closed_c32"),
            "cell": {"name": CELL, "chips": 1},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            "scoped": {"ops": [], "marks": [], "modules": [
                type("M", (), {"op_name": f"jit_phi4flash_decode_{part}(1)"})()
                for part in 16 * ["self"] + 2 * ["mid", "full"]
                + 14 * ["cross"]]}}


def test_slice_readers_on_synthetic_spans():
    paged = _slice_inputs(True)
    if not paged["trace"].devices[0][0].long_name:
        pytest.skip("no long_name on trace events")
    from benchmarks.lib import phi4flash_scopes

    assert phi4flash_scopes.decode_rounds(
        paged["scoped"], paged["config"]) == 2.0
    # a slice of 3.15 ms holds 2.0 + 1.0 ms of each stream's two spans
    assert _reader("phi_decode_streams_per_round").read(paged) == \
        pytest.approx(3 * 3.0 / 3.15)
    assert _reader("diff_paged_attn_roofline").read(_slice_inputs(False)) \
        is None
    # 32 kernel calls of 50 us; 8 shared sites whole, 8 ring sites capped
    # at the window of 512
    contexts = [(40, 1), (100, 1), (700, 1), (41, .5), (101, .5), (701, .5)]
    rows = sum(w * (8 * c + 8 * min(c, 512)) for c, w in contexts)
    floor = (2 * rows * 1280 + 4.5 * 16 * 40 * 192) * 2 / 819e9
    assert _reader("diff_paged_attn_roofline").read(paged) == \
        pytest.approx(100.0 * floor / (32 * 5e4 / 1e9))


def test_cell_files_meet_what_the_harness_reads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    config = _load("configs", cell["config"])
    traffic = _load("traffic", cell["traffic"])
    assert cell["chips"] == 1 \
        and traffic["driver"] == "closed_loop_first_wave"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"]
    s = traffic["server"]
    from mxnet_tpu.serving.buckets import BucketGrid

    grid = BucketGrid(tuple(s["batch_buckets"]), None,
                      len_buckets=tuple(s["len_buckets"]))
    bound, chunk = s["max_prefill_tokens"], s["len_buckets"][-1]
    assert bound == chunk == 2048
    warmed = set()
    for n, plen in s["warmup"]:
        sig = (grid.batch_bucket(n), grid.prefill_bucket(plen))
        assert sig[0] * sig[1] <= bound, (n, plen)      # one batch each
        warmed.add(sig)
    assert {(1, l) for l in s["len_buckets"]} <= warmed
    assert {grid.batch_bucket(n) for n, _ in s["warmup"]} == \
        set(s["batch_buckets"])
    # ISSUE 35's traffic, letter for letter
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.7,
        "min": 1024, "max": 16384}
    assert traffic["output_len"] == {"dist": "uniform", "min": 2048,
                                     "max": 2560}
    assert (traffic["clients"], traffic["max_rps_per_client"]) == (32, 0.04)
    assert s["max_generate_tokens"] == 18944 == \
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert (s["decode_pages"] - 1) * s["page_size"] == \
        traffic["clients"] * s["max_generate_tokens"]
    # ISSUE 35's two allowed departures, each with its reading (PERF.md
    # section 6): no 8 bucket, and a slice of 0.16 s where it set 0.5
    assert s["batch_buckets"] == [1, 32]
    assert (traffic["trace_start_frac"], traffic["trace_len_s"]) == (0.5,
                                                                     0.16)
    # a slot a stream of the widest decode round
    assert max(s["batch_buckets"]) == traffic["clients"]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        # a later cell whose streams hold state slots may be appended to
        # the slot reader
        assert per_layer[name]["workloads"][0] == CELL
        assert (len(per_layer[name]["workloads"]) == 1
                or name == "state_slots_in_use")
        assert per_layer[name]["moves"] == "tpot_p50_ms"
    for name in APPENDED:
        assert CELL in per_layer[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["tpot_p50_ms"]["workloads"]
    # new entries went to the END of the list
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("mla_attn_roofline") == 33
    assert min(names.index(n) for n in NEW_READERS) == 45


def test_config_keeps_the_catalog_row():
    """Every key of the catalog row's ``config`` is in the file under the
    same name, unchanged: nothing is reduced."""
    config = _load("configs", "phi4_mini_flash")
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert config["published"] == row["config"]
    assert config["reduced"] == [] and config["not_served"] == {}
    assert config["assumed_sizes"] == {"d_state": 16, "d_conv": 4,
                                       "expand": 2, "dt_rank": 160,
                                       "head_dim": 64}
    for key in ("deployment", "reduced_why", "assumed"):
        assert config[key], key
    for key in ("weights", "what_correct_sees", "what_correct_cannot_see",
                "differential_attention", "memory", "window", "precision",
                "positional_encoding", "biases", "mamba_sizes"):
        assert config["assumed"][key], key


def test_weights_cache_and_slots_fill_the_chip():
    from benchmarks.builders import phi4flash as b
    from mxnet_tpu.gluon.model_zoo.nlp.phi4flash import layer_kinds

    config = _load("configs", "phi4_mini_flash")
    s = _load("traffic", "reason_ctx_closed_c32")["server"]
    u, v = config["hidden_size"], config["vocab_size"]
    shapes = [(v, u), (u,), (u,)]
    kinds = layer_kinds(config["num_hidden_layers"])
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    for kind in kinds:
        shapes += list(b._layer_shapes(config, kind).values())
    params = sum(int(np.prod(x)) for x in shapes)
    assert 3.85e9 < params < 3.86e9                     # 7.71 GB of bf16
    cache_gb = s["decode_pages"] * s["page_size"] * 5120 / 1e9
    assert 3.09 < cache_gb < 3.11
    # rings bf16; scan states and convolution tails float32
    slot = 8 * 512 * 5120 + 9 * (5120 * 16 * 4 + 3 * 5120 * 4)
    assert 24.3e6 < slot < 24.6e6
    slots_gb = (max(s["batch_buckets"]) + 1) * slot / 1e9
    # over a quarter of a 16 GB chip before a chunk's temporaries
    assert (2 * params / 1e9 + cache_gb + slots_gb) / 16.0 > 0.6
    assert b.flops_per_token(config, {}) > 2 * 3.8e9


# -- what `correct` can see: tools/phi4flash_correct_controls.py ------------

@pytest.fixture(scope="module")
def judged():
    """``serve_loop.check_outputs`` on the answers of the tiny cell's own
    server, sound and with each fault planted."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "phi4flash_correct_controls",
        os.path.join(ROOT, "tools", "phi4flash_correct_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = tool.judge(_load("configs", "tiny_phi4flash"),
                     _load("traffic", "tiny_reason_ctx_closed"), 2147483693,
                     [40, 52], 12, (None,) + tool.CONTROLS + tool.READINGS)
    return tool, got


def test_correct_holds_for_the_sound_program(judged):
    _, got = judged
    sound = got["sound"]
    assert sound["ok"] and sound["checked"] == 2
    assert sound["worst_gap_in_tolerances"] == 0.0      # float32, tiny
    # the layers, not the last token's own embedding, pick the next one
    assert min(sound["distinct_tokens"]) >= 6


@pytest.mark.parametrize("fault", ("tail", "window", "lam",
                                   "lower_precision"))
def test_correct_fails_with_a_fault_planted(judged, fault):
    tool, got = judged
    assert fault in tool.CONTROLS
    assert not got[fault]["ok"], got[fault]
    assert got[fault]["worst_gap_in_tolerances"] > 2.0


def test_planted_faults_are_taken_out_again(judged):
    from mxnet_tpu.gluon.model_zoo.nlp import phi4flash as model
    from mxnet_tpu.ops import diff_attention as diff_ops

    tool, got = judged
    assert set(got) == {"sound"} | set(tool.CONTROLS) | set(tool.READINGS)
    assert model._mamba_layer.__name__ == "_mamba_layer"
    assert model._mlp.__name__ == "_mlp"
    assert diff_ops.diff_attention_combine.__name__ == \
        "diff_attention_combine"
    assert model.Phi4FlashDecodeEngine._make_arenas.__name__ == \
        "_make_arenas"
