"""The harness is driven by data: a cell, a configuration, a traffic mix
and a per-layer metric are each added with new files and new entries, and
no file that is there is edited. Also the contract's refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _run(root, *args, env=None, timeout=600):
    e = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=e, capture_output=True, text=True, timeout=timeout)


def _copy(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    return root


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in d:
            continue
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hash(fh.read())
    return out


def test_additions_need_only_new_files_and_entries(tmp_path):
    root = _copy(tmp_path)
    before = _digest(root)
    b = os.path.join(root, "benchmarks")

    def load(*p):
        with open(os.path.join(*p)) as f:
            return json.load(f)

    # a configuration: the tiny decoder at another width, in a file of its own
    cfg = load(b, "configs", "tiny_decoder.json")
    cfg.update(hidden_size=64, intermediate_size=128, head_dim=16)
    with open(os.path.join(b, "configs", "dummy_decoder.json"), "w") as f:
        json.dump(cfg, f)
    # a traffic mix: data only, read by the general generator
    mix = load(b, "traffic", "tiny_closed.json")
    mix.update(clients=3, output_len={"dist": "fixed", "value": 3})
    with open(os.path.join(b, "traffic", "dummy_mix.json"), "w") as f:
        json.dump(mix, f)
    # a per-layer metric: a reader of its own
    with open(os.path.join(b, "layer_metrics", "dummy_requests.py"), "w") as f:
        f.write("def read(inputs):\n"
                "    return float(len(inputs['late_ms']))\n")
    # entries: the manifest and the rehearsal list are appended to
    manifest = load(root, "BENCHMARK.json")
    manifest["configs"].append({
        "name": "dummy_decoder", "source": "none", "reduced": [],
        "file": "benchmarks/configs/dummy_decoder.json", "why": "test"})
    manifest["workloads"].append({
        "name": "dummy_cell", "config": "dummy_decoder",
        "traffic": "dummy_mix", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("served_tokens_s", "tpot_p50_ms"):
            m["workloads"].append("dummy_cell")
    manifest["per_layer"].append({
        "name": "dummy_requests", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "entry / load generator",
        "moves": "served_tokens_s", "workloads": ["dummy_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rehearsal = load(b, "rehearsal.json")
    rehearsal["workloads"].append({
        "name": "dummy_cell", "config": "dummy_decoder",
        "traffic": "dummy_mix", "chips": 1})
    with open(os.path.join(b, "rehearsal.json"), "w") as f:
        json.dump(rehearsal, f)

    edited = {p for p, h in _digest(root).items()
              if p in before and before[p] != h}
    assert edited == {os.path.join(b, "rehearsal.json")}   # a list of entries

    env = {"PYTHONPATH": REPO}
    r = _run(root, "--workload", "dummy_cell", "--rehearse", "--seconds", "2",
             "--trace", "1", env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "would report" in r.stderr and "dummy_requests" in r.stderr
    assert '"correct"' not in r.stdout.strip().splitlines()[-1]
    r = _run(root, "--workload", "dummy_cell", "--rehearse", "--seconds", "2",
             "--trace", "0", env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "served_tokens_s" in r.stderr and "train_tokens_s" not in \
        r.stderr.split("would report")[1]


@pytest.mark.parametrize("cell", ["tiny_bert_train_dp4"])
def test_rehearsal_on_four_virtual_devices(cell):
    r = _run(REPO, "--workload", cell, "--rehearse", "--seconds", "2")
    assert r.returncode == 0, r.stderr[-3000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"correct"' not in last         # never the result line
    assert "correct=True" in r.stderr


def test_refuses_without_a_tpu():
    r = _run(REPO, "--workload", "bert_base_s512", "--seed", "0",
             "--seconds", "1", "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode not in (0, None)
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files: non-zero exit, no result."""
    root = _copy(tmp_path)
    r = _run(root, "--workload", "bert_base_s512", "--seed", "0",
             "--seconds", "1", "--trace", "0",
             env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert r.returncode not in (0, None)
    assert r.stdout.strip() == ""


def test_manifest_meets_the_contract():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and 1 <= m["run_seconds"] <= 51
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    assert len(cells) == len(m["workloads"]) >= 2
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == \
        len(cells)
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(w["config"] == c["name"] for w in cells.values())
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|_size)$", key), key
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for x in e2e.values():
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    layers = set()
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(x["name"]) and x["moves"] in e2e
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", x["unit"])
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", x["name"] + ".py"))
        layers.add(x["layer"])
        # reported only where the metric it moves is
        moved = e2e[x["moves"]].get("workloads", list(cells))
        assert set(x.get("workloads", list(cells))) <= set(moved), x["name"]
    for name, w in cells.items():
        mine = [x for x in m["end_to_end"]
                if name in x.get("workloads", [name])]
        assert len(mine) >= 2 and any(x["name"] == "setup_s" for x in mine)
        assert any(name in x.get("workloads", [name])
                   for x in m["per_layer"])
    assert len(json.dumps(m)) < 64 * 1024
