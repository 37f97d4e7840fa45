"""The paged latent-attention (MLA) decode kernel against the gather path
of ``mla_paged_decode``, in interpret mode on the CPU, and the op's
routing between the two.

The kernel walks a stream's live pages a block of ``_BLOCK_TOKENS``
tokens at a time; the cases sit on that walk's edges: one token, a whole
block, one token past it, a full page table (whose last block is ragged),
rows of length 0 in front of, between and behind live rows, page ids
scattered over the arena and the scratch page 0 repeated behind a row's
live pages. What the chip's compiler makes of the kernel is
``tests/test_tpu_compile.py``'s to say.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (x64 on, as every real trace has it)
from mxnet_tpu import telemetry
from mxnet_tpu.base import execution_platform
from mxnet_tpu.ops.attention import mla_paged_decode
from mxnet_tpu.pallas_kernels import paged_attention as mk

pytestmark = pytest.mark.pallas

BLOCK = mk._BLOCK_TOKENS
# heads, nope, rope, v, rank, page size, table width: a bf16 shape whose
# output is narrower than its rows (rank a lane multiple), and the tiny
# float32 configuration of ``longcat_flash_tiny`` (rows of 12 values
# padded to 128 lanes, so the kernel emits whole rows and the op cuts)
BF16 = dict(dtype=jnp.bfloat16, heads=16, nope=32, rope=64, v=32, rank=128,
            page=16, table_w=72, tol=2e-2)
TINY = dict(dtype=jnp.float32, heads=4, nope=8, rope=4, v=8, rank=8,
            page=8, table_w=144, tol=1e-5)
FULL = 72 * 16
assert FULL == TINY["table_w"] * TINY["page"] and FULL % BLOCK

CASES = {
    "b1-one-token": (BF16, [1]),
    "b1-block-edge": (BF16, [BLOCK]),
    "b1-past-block-edge": (BF16, [BLOCK + 1]),
    "b1-full-table": (BF16, [FULL]),
    "b8-mixed": (BF16, [1, BLOCK, BLOCK + 1, FULL, 100, 640, 16, 17]),
    "b8-padded-behind": (BF16, [300, BLOCK + 1, 1, 0, 0, 0, 0, 0]),
    "b8-padded-between": (BF16, [0, 0, 77, 0, FULL, 0, BLOCK, 0]),
    "b8-all-padding": (BF16, [0] * 8),
    "tiny-f32-b1": (TINY, [BLOCK + 1]),
    "tiny-f32-b8-mixed": (TINY, [1, BLOCK, FULL, 0, 9, 0, BLOCK + 1, 640]),
}


def _inputs(shape, lengths, seed=0):
    """Seeded query, arena, tables and fold weights: every live page of
    every row is another page of the arena, in no order; a table's dead
    part repeats the scratch page 0, which holds numbers like any other
    (padding rows write there)."""
    rng = np.random.default_rng(seed)
    b, dt = len(lengths), shape["dtype"]
    page, table_w = shape["page"], shape["table_w"]
    width = -(-(shape["rank"] + shape["rope"]) // 128) * 128
    n_pages = 1 + b * table_w
    arena = jnp.asarray(rng.standard_normal((n_pages, page, width)), dt)
    free = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((b, table_w), np.int32)
    used = 0
    for i, n in enumerate(lengths):
        live = -(-n // page)
        table[i, :live] = free[used:used + live]
        used += live
    query = jnp.asarray(rng.standard_normal(
        (b, shape["heads"], shape["nope"] + shape["rope"])), dt)
    kvb = jnp.asarray(rng.standard_normal(
        (shape["heads"] * (shape["nope"] + shape["v"]), shape["rank"]))
        / np.sqrt(shape["rank"]), dt)
    return (query, arena, jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32), kvb)


def _decode(shape, *args):
    return mla_paged_decode(
        *args, nope_dim=shape["nope"], v_dim=shape["v"],
        scale=(shape["nope"] + shape["rope"]) ** -0.5)


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """Route as on the chip, run the kernel in interpret mode; yields the
    list of the kernel's calls (one per traced site)."""
    calls = []
    kernel = mk.mla_paged_decode_kernel

    def interpreted(*args, **kw):
        calls.append(args[0].shape)
        return kernel(*args, interpret=True, **kw)

    monkeypatch.setattr(mk, "mla_paged_decode_kernel", interpreted)
    with execution_platform("tpu"):
        yield calls


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_the_gather_path(case, interpreted_kernel,
                                        monkeypatch):
    shape, lengths = CASES[case]
    # the tiny configuration has 4 heads, half a sublane tile: nothing to
    # the interpreter, and its rows are the ones the CPU suites decode
    monkeypatch.setattr(mk, "mla_paged_shape_supported", lambda q, a: True)
    args = _inputs(shape, lengths)
    got = np.asarray(_decode(shape, *args), np.float32)
    assert len(interpreted_kernel) == 1
    with execution_platform("cpu"):
        want = np.asarray(_decode(shape, *args), np.float32)
    assert len(interpreted_kernel) == 1            # the gather path ran
    live = np.asarray(lengths) > 0
    assert got.shape == want.shape
    if live.any():
        np.testing.assert_allclose(got[live], want[live], rtol=0,
                                   atol=shape["tol"] * np.abs(want).max())
    # a padding row attends to nothing: zeros, where the gather path
    # averages whatever its table reaches
    assert not got[~live].any()


def test_scratch_page_content_does_not_reach_a_live_row(interpreted_kernel):
    """What lies behind a row's live tokens - the rest of its last page,
    the scratch page its table repeats - changes nothing."""
    shape, lengths = BF16, [BLOCK + 3, 5]
    query, arena, table, lens, kvb = _inputs(shape, lengths)
    base = np.asarray(_decode(shape, query, arena, table, lens, kvb),
                      np.float32)
    last = int(table[1, 0])
    other = arena.at[0].set(1e4).at[last, 5:].set(-1e4)
    got = np.asarray(_decode(shape, query, other, table, lens, kvb),
                     np.float32)
    np.testing.assert_array_equal(got, base)


INELIGIBLE = {
    "platform-cpu": ("cpu", BF16),
    "heads-not-a-sublane-tile": ("tpu", dict(BF16, heads=4)),
    "bf16-page-of-8": ("tpu", dict(BF16, page=8)),
}


@pytest.mark.parametrize("case", list(INELIGIBLE))
def test_ineligible_calls_take_the_gather_path(case, interpreted_kernel):
    platform, shape = INELIGIBLE[case]
    args = _inputs(shape, [40, 0])
    telemetry.reset()
    telemetry.enable()
    try:
        with execution_platform(platform):
            _decode(shape, *args)
        assert not interpreted_kernel
        assert _dispatches() == 0
    finally:
        telemetry.disable()
        telemetry.reset()


def _dispatches():
    fam = telemetry.snapshot()["metrics"].get("mxnet_pallas_dispatch_total")
    return sum(s["value"] for s in (fam["samples"] if fam else ())
               if s["labels"]["kernel"] == "mla_paged_decode")


@pytest.mark.parametrize("sites", [1, 2])
def test_eligible_call_takes_the_kernel_and_counts_each_traced_site(
        sites, interpreted_kernel):
    """The counter counts ROUTINGS: one per call site per trace of a
    program (a LongCat double layer has two), none per execution."""
    shape = TINY
    shape_ok = dict(shape, heads=8)         # float32: 8 sublanes
    args = _inputs(shape_ok, [9, 0, BLOCK + 1])
    decode = functools.partial(_decode, shape_ok)

    @jax.jit
    def program(*args):
        return sum(decode(*args) for _ in range(sites))

    telemetry.reset()
    telemetry.enable()
    try:
        first = program(*args)
        again = program(*args)
        assert len(interpreted_kernel) == sites
        assert _dispatches() == sites
        np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("dtype, page, heads, width, ok", [
    (jnp.bfloat16, 16, 64, 640, True),      # the LongCat cell
    (jnp.float32, 8, 8, 128, True),
    (jnp.bfloat16, 8, 64, 640, False),      # half a bf16 sublane tile
    (jnp.bfloat16, 16, 4, 640, False),
    (jnp.bfloat16, 16, 64, 576, False),     # rows not lane-padded
    (jnp.int8, 32, 64, 640, False),
])
def test_shape_gate(dtype, page, heads, width, ok):
    q = jax.ShapeDtypeStruct((2, heads, width), dtype)
    arena = jax.ShapeDtypeStruct((9, page, width), dtype)
    assert mk.mla_paged_shape_supported(q, arena) is ok
    assert not mk.mla_paged_shape_supported(
        jax.ShapeDtypeStruct((2, heads, width), jnp.float16), arena)
