"""The ONE walk behind the three paged decode kernels
(``pallas_kernels/paged_attention.py``): each kernel in interpret mode
against its gather oracle on the walk's edges that the kernels' own
suites (``test_serving_decode.py::TestPagedKernel``,
``test_mla_paged_attention.py``, ``test_phi4flash.py``) do not take for
every kernel, and the pages-per-block rule at the serving cells' shapes.
What the chip's compiler makes of the kernels is
``tests/test_tpu_compile.py``'s to say.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (x64 on, as every real trace has it)
from mxnet_tpu.ops.attention import _mla_paged_reference, _paged_reference
from mxnet_tpu.ops.diff_attention import _diff_paged_reference
from mxnet_tpu.pallas_kernels import paged_attention as pk

pytestmark = pytest.mark.pallas

PAGE = 8                                    # float32: one sublane tile
BLOCK = pk._BLOCK_TOKENS
SCALE = 0.17


def _gqa(rs, pages, table, lengths):
    """20 heads over 4 kv groups of 128 lanes: the head rows are padded
    to a sublane tile and the group sum has five rows a group."""
    b, h, kv, d = len(lengths), 20, 4, 128
    ka, va = (jnp.asarray(rs.randn(pages * PAGE, kv, d), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rs.randn(b, h, 1, d), jnp.float32)
    got = pk.paged_attention_kernel(q, ka, va, table, lengths,
                                    page_size=PAGE, scale=SCALE,
                                    interpret=True)
    want = _paged_reference(q, ka, va, table, lengths,
                            (lengths - 1)[:, None], PAGE, SCALE)
    return got[:, :, 0], want[:, :, 0]


def _mla(rs, pages, table, lengths):
    """Rows of 256 lanes that are key and, in their leading 128, value."""
    b, h, width, out_w = len(lengths), 8, 256, 128
    arena = jnp.asarray(rs.randn(pages, PAGE, width), jnp.float32)
    q = jnp.asarray(rs.randn(b, h, width), jnp.float32)
    got = pk.mla_paged_decode_kernel(q, arena, table, lengths, scale=SCALE,
                                     out_width=out_w, interpret=True)
    want = _mla_paged_reference(q, arena, table, lengths, SCALE)
    return got, want[..., :out_w]


def _diff(rs, pages, table, lengths):
    """A key and a value arena of 256-lane rows, every head over the
    whole row."""
    b, h, width = len(lengths), 8, 256
    ka, va = (jnp.asarray(rs.randn(pages, PAGE, width), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rs.randn(b, h, width), jnp.float32)
    got = pk.diff_paged_decode_kernel(q, ka, va, table, lengths,
                                      scale=SCALE, interpret=True)
    return got, _diff_paged_reference(q, ka, va, table, lengths, SCALE)


KERNELS = {"gqa": _gqa, "mla": _mla, "diff": _diff}
# lengths a row, table width in pages: a block of the walk is 512 tokens
# = 64 pages of 8, so 70 pages hold a block and a ragged second one
CASES = {
    "first_live_row_is_not_row_0": ([0, 0, 37, 5], 70),
    "ends_on_a_block_edge_and_one_past": ([BLOCK, BLOCK + 1], 70),
    "every_row_empty": ([0, 0, 0], 70),
    "table_narrower_than_a_block": ([40, 3, 0, 17], 5),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_walk_matches_the_gather(kernel, case):
    """Page ids out of order, the scratch page 0 behind every row's live
    pages; a live row is its oracle's, a row of length 0 is zeros."""
    lengths, table_w = CASES[case]
    lengths = np.asarray(lengths, np.int32)
    rs = np.random.RandomState(sum(map(ord, kernel + case)))
    live_pages = -(-lengths // PAGE)
    pages = 1 + int(live_pages.sum())
    free = rs.permutation(np.arange(1, pages))
    table = np.zeros((len(lengths), table_w), np.int32)
    used = 0
    for row, n in enumerate(live_pages):
        table[row, :n] = free[used:used + n]
        used += n
    assert (table_w * PAGE < BLOCK) == (case == "table_narrower_than_a_block")
    got, want = (np.asarray(x, np.float32) for x in KERNELS[kernel](
        rs, pages, jnp.asarray(table), jnp.asarray(lengths)))
    assert got.shape == want.shape
    live = lengths > 0
    if live.any():
        np.testing.assert_allclose(got[live], want[live], rtol=0,
                                   atol=1e-5 * np.abs(want[live]).max())
    assert not got[~live].any()


def test_every_serving_cell_walks_blocks_of_32_pages():
    """Pages of 16 bf16 rows at the six cells' row widths: the block is
    512 tokens wherever the table holds one (Phi's 1,280-lane rows make
    1.3 MB buffers of it; its window's ring is exactly one block). A row
    of 32 kv heads x 128 lanes, which no cell has, is cut to the byte
    cap: 16 pages, two 2 MB buffers an arena."""
    rows = {"mistral": (8 * 128, 160), "falcon_h1": (4 * 128, 64),
            "longcat": (640, 72), "dots": (640, 416),
            "phi_shared": (20 * 64, 1184), "phi_ring": (20 * 64, 32)}
    for cell, (width, table_w) in rows.items():
        assert pk.pages_per_block(16, width, 2, table_w) == 32, cell
    assert pk.pages_per_block(16, 32 * 128, 2, 34) == 16       # mha
    assert pk.pages_per_block(16, 640, 2, 9) == 9              # narrow table
    assert pk.pages_per_block(1024, 128, 2, 4) == 1            # never 0
