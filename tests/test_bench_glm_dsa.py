"""The GLM-5 cell's control flow on the CPU at tiny sizes
(``configs/tiny_glm_dsa.json`` + ``traffic/tiny_longdoc_closed.json``): a
``harness.Run`` built by hand, the closed-loop driver run to its end with
``correct`` true (prompts of several chunks beside decoding streams), and
the cell's per-layer readers on what it hands back. ``rehearsal.json``
lists no such cell: this test stands in, as ``test_bench_longcat.py``
does for LongCat's."""
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
CELL = "glm5_dsa_longctx_c8"
TRACE_READERS = ("dsa_indexer_ms_per_round", "dsa_select_ms_per_round",
                 "dsa_attend_ms_per_round", "glm_moe_ms_per_round",
                 "glm_decode_streams_per_round",
                 "glm_moe_tokens_per_held_expert")
NEW_READERS = TRACE_READERS + ("dsa_selected_pct", "prefill_chunk_ms_p50",
                               "prefill_chunks_per_request",
                               "ttft_p95_ms_c8", "peak_hbm_gb_c8")
APPENDED = ("tok_gap_p99_ms", "tpot_p95_ms", "compiles_in_window_serve",
            "prefill_ms_p50", "decode_step_ms_p50", "pallas_sites_serve",
            "device_idle_pct_serve")


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

    from benchmarks.builders import glm_moe_dsa as builder
    from benchmarks.drivers import closed_loop
    from benchmarks.lib import harness
    from benchmarks.references import glm_moe_dsa as reference
    import mxnet_tpu as mx

    # the builder places the net on tpu(0), which stands in on the CPU
    # under JAX_PLATFORMS=cpu
    assert mx.tpu(0).jax_device().platform == "cpu"
    config = _load("configs", "tiny_glm_dsa")
    traffic = _load("traffic", "tiny_longdoc_closed")
    run = harness.Run(
        cell={"name": "tiny_glm_dsa_closed", "config": "tiny_glm_dsa",
              "traffic": "tiny_longdoc_closed", "chips": 1},
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
    # every request's prompt (20-72 tokens) is longer than the largest
    # length bucket (16): more prefill dispatches than requests
    assert stats["batches"] >= 4 + 2 * result.attempted
    assert result.notes["compiles_in_window"]["compiles"] == 0


def _inputs(traced_run, **extra):
    run, result = traced_run
    return dict(result.layer, config=run.config, traffic=run.traffic,
                cell=run.cell, peaks=None, **extra)


def test_counter_and_span_readers_on_the_run(traced_run):
    inputs = _inputs(traced_run)
    # index_topk 12 of caches of 20-78 tokens
    assert 12 / 78 * 100 < _reader("dsa_selected_pct").read(inputs) < 100.0
    assert _reader("prefill_chunk_ms_p50").read(inputs) > 0.0
    chunks = _reader("prefill_chunks_per_request").read(inputs)
    assert 2.0 <= chunks <= 5.0              # ceil(20 / 16) .. ceil(72 / 16)
    assert _reader("compiles_in_window_serve").read(inputs) == 0.0
    assert _reader("ttft_p95_ms_c8").read(inputs) == \
        _reader("ttft_p95_ms").read(inputs) > 0.0
    assert _reader("peak_hbm_gb_c8").read(inputs) is None   # the CPU
    run, result = traced_run
    spans = [s for s in result.layer["spans"] if s["name"] == "prefill"]
    first = [s for s in spans if s["tags"].get("chunk") == 0]
    assert len(first) == result.attempted
    for s in spans:
        t = s["tags"]
        assert t["offset"] == 16 * t["chunk"] and t["chunk"] < t["chunks"]


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_report_nothing_without_device_names(traced_run,
                                                           name):
    """A CPU trace has no TPU plane, and the parent's program has no such
    scopes, marks or config keys: the reader returns None, no raise."""
    assert _reader(name).read(_inputs(traced_run)) is None
    assert _reader(name).read(_inputs(traced_run, trace=None)) is None
    empty = {"ops": [], "modules": [], "marks": []}
    assert _reader(name).read(_inputs(traced_run, scoped=empty)) is None
    longcat = dict(_inputs(traced_run, scoped=empty),
                   config=_load("configs", "tiny_longcat"))
    assert _reader(name).read(longcat) is None


@pytest.mark.parametrize("name", ("dsa_selected_pct", "prefill_chunk_ms_p50",
                                  "prefill_chunks_per_request"))
def test_counter_readers_report_nothing_on_a_program_without_them(name):
    """What the parent commit's run hands back: no such counters, no
    ``chunks`` tag on any span."""
    inputs = {"counters_before": {}, "counters_after": {},
              "spans": [{"name": "prefill", "dur": 5.0, "ts": 0.0,
                         "tags": {"len_bucket": 64}},
                        {"name": "decode.step", "dur": 5.0, "ts": 9.0}],
              "config": _load("configs", "tiny_longcat")}
    assert _reader(name).read(inputs) is None


def _synthetic_chip(rounds=3, moe_layers=2):
    """``rounds`` decode rounds: one run of the dense layer program and
    ``moe_layers`` of the expert one, a prefill chunk between rounds."""
    from benchmarks.lib.xplane_scopes import ScopedOp

    ops, modules, t = [], [], 0.0
    for _ in range(rounds):
        for kind in ["dense"] + moe_layers * ["moe"]:
            prog = f"glm_dsa_decode_{kind}"
            modules.append(ScopedOp(f"jit_{prog}(12)", t, 9e6))
            scopes = [("mla.proj/dot_general:", 1e6),
                      ("dsa.indexer/dot_general:", 0.5e6),
                      ("dsa.select/while:", 0.75e6),
                      # inside the loop: not counted twice
                      ("dsa.select/while/body/reduce_sum:", 0.5e6),
                      ("dsa.attend/gather:", 0.25e6)]
            scopes += [("moe.router/top_k:", 0.25e6),
                       ("moe.experts/while:", 1e6),
                       ("moe.shared/dot_general:", 0.75e6)] \
                if kind == "moe" else [("ffn.dense/dot_general:", 2e6)]
            for scope, dur in scopes:
                nested = "body" in scope
                ops.append(ScopedOp(f"jit({prog})/jit(main)/{scope}",
                                    t - 0.6e6 if nested else t, dur))
                if not nested:
                    t += dur
            t += 1e6
        modules.append(ScopedOp("jit_glm_dsa_prefill_moe(7)", t, 9e6))
        ops.append(ScopedOp("jit(glm_dsa_prefill_moe)/jit(main)/dsa.attend/x:",
                            t, 9e6))
        t += 40e6
    # 3 streams x top-2 x 2 expert layers = 12 picks a round
    marks = rounds * [
        {"phase": "decode", "held": 3, "zero": 0, "absent": 9,
         "touched": 2, "layers": moe_layers},
        {"phase": "prefill", "held": 40, "zero": 0, "absent": 88,
         "touched": 4, "layers": moe_layers}]
    return {"ops": ops, "modules": modules, "marks": marks}


def test_trace_readers_on_a_synthetic_trace(traced_run):
    inputs = _inputs(traced_run, scoped=_synthetic_chip())
    cfg = inputs["config"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 2
    read = {n: _reader(n).read(inputs) for n in TRACE_READERS}
    # per round: 3 layers of indexer, select and attend; 2 of experts
    assert read["dsa_indexer_ms_per_round"] == pytest.approx(1.5)
    assert read["dsa_select_ms_per_round"] == pytest.approx(2.25)
    assert read["dsa_attend_ms_per_round"] == pytest.approx(0.75)
    assert read["glm_moe_ms_per_round"] == pytest.approx(4.0)
    assert read["glm_decode_streams_per_round"] == pytest.approx(3.0)
    assert read["glm_moe_tokens_per_held_expert"] == \
        pytest.approx(3 / 2 / cfg["n_routed_experts"])


def test_cell_files_meet_what_the_harness_reads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    config = _load("configs", cell["config"])
    traffic = _load("traffic", cell["traffic"])
    assert cell["chips"] == 1 and traffic["driver"] == "closed_loop"
    # eight cells when this one was accepted; later PRs append theirs
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 5
    assert len(manifest["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
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
    # a long prompt's chunks are dispatched alone: batch bucket 1 at both
    # length buckets; and every decode bucket
    assert {(1, l) for l in s["len_buckets"]} <= warmed
    assert {grid.batch_bucket(n) for n, _ in s["warmup"]} == \
        set(s["batch_buckets"])
    assert traffic["prompt_len"]["min"] > chunk          # always chunked
    assert (s["decode_pages"] - 1) * s["page_size"] == \
        traffic["clients"] * s["max_generate_tokens"]
    # ISSUE 33's traffic, with the one departure it allowed (the prompts'
    # median 16384 -> 12288); the longest stream fills its budget
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 12288, "sigma": 0.5,
        "min": 8192, "max": 32768}
    assert traffic["output_len"] == {"dist": "uniform", "min": 2048,
                                     "max": 2560}
    assert (traffic["clients"], traffic["max_rps_per_client"]) == (8, 0.04)
    assert s["max_generate_tokens"] == 35328 == \
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        # a later chunked-prefill cell may be appended to the chunk readers
        assert per_layer[name]["workloads"][0] == CELL
        assert (len(per_layer[name]["workloads"]) == 1
                or name.startswith("prefill_chunk"))
        assert per_layer[name]["moves"] == "tpot_p50_ms"
    for name in APPENDED:
        assert CELL in per_layer[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["tpot_p50_ms"]["workloads"]


def test_config_keeps_the_catalog_row():
    """Every key of the catalog row's ``config`` is in the file under the
    same name, unchanged unless ``reduced`` lists it."""
    config = _load("configs", "glm5_ep16")
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["router_outputs"], config["num_experts_per_tok"],
            config["index_topk"], config["index_n_heads"],
            config["index_head_dim"]) == (256, 8, 2048, 32, 128)
    for key in ("published", "deployment", "reduced_why", "assumed",
                "not_served"):
        assert config[key], key
    assert set(config["reduced_why"]) == set(config["reduced"])


def test_weights_and_cache_fill_the_chip():
    from benchmarks.builders import glm_moe_dsa as b

    config = _load("configs", "glm5_ep16")
    s = _load("traffic", "longdoc_reason_closed_c8")["server"]
    u, v = config["hidden_size"], config["vocab_size"]
    shapes = [(v, u), (v, u), (u,)]
    for i in range(config["num_hidden_layers"]):
        shapes += list(b._layer_shapes(
            config, i >= config["first_k_dense_replace"]).values())
    weights_gb = 2 * sum(int(np.prod(x)) for x in shapes) / 1e9
    assert 7.8 < weights_gb < 7.85
    cache_gb = (s["decode_pages"] * s["page_size"] * (640 + 128) * 2
                * config["num_hidden_layers"]) / 1e9
    assert 2.15 < cache_gb < 2.2
    # over a quarter of a 16 GB chip before a chunk's temporaries
    assert (weights_gb + cache_gb) / 16.0 > 0.25
    assert b.flops_per_token(config, {}) > 2 * 1.2e9
