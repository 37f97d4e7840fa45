"""Tier-1 twin of ``benchmarks/tests/test_bench_mla_attn_roofline.py``
(the ``mla_attn_roofline`` reader and its kernel's counts), collected
here so that the driver's test command runs it."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests.test_bench_mla_attn_roofline import *  # noqa: E402,F401,F403


# where PR 30 appended the entry; a list of BENCHMARK.json only grows at
# its end, so the place is the entry's for good
MLA_ATTN_ROOFLINE_AT = 33


def test_manifest_entry():  # noqa: F811 - replaces the imported test
    """The benchmark's own test pins ``mla_attn_roofline`` as the LAST
    ``per_layer`` entry, which held until a later PR appended entries
    (new entries go at the end of a list, never before an accepted one:
    the benchmark's file is a ``benchmark`` PR's to change). Here the
    pin is the PLACE the entry was accepted at."""
    import json
    import os

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("mla_attn_roofline") == MLA_ATTN_ROOFLINE_AT
    assert names.count("mla_attn_roofline") == 1
    assert manifest["per_layer"][MLA_ATTN_ROOFLINE_AT] == {
        "name": "mla_attn_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": ["longcat_flash_decode_c256"]}
