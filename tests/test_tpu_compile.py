"""Main-path Pallas kernels, compiled for a described TPU v5e.

Interpret mode cannot show what the chip's compiler refuses: a block that
is not a legal (8, 128) tile, a primitive Mosaic has no lowering for, an
index map that carries a 64-bit constant. The TPU compiler is installed
with jax, and compiles for a chip that is DESCRIBED and not attached
(``jax.experimental.topologies``), so each kernel ``chip_smoke.py``'s two
phases reach is compiled here, forward and backward, at that phase's
real widths: BERT-base batch 32 x sequence 512 for ``train``, the
Llama-3-8B widths for ``serve``. Nothing runs; a pass is a compile, not a
chip run.
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (x64 on, as every real trace has it)
from mxnet_tpu.pallas_kernels import fused_layers as fl
from mxnet_tpu.pallas_kernels import (flash_attention,
                                      paged_attention_kernel)

pytestmark = pytest.mark.pallas

BF16 = jnp.bfloat16
BERT = dict(batch=32, seq=512, units=768, heads=12, hidden=3072)
LLAMA = dict(units=4096, heads=32, kv_heads=8, head_dim=128)


@pytest.fixture(scope="module")
def v5e_topology():
    """A described ``v5e:2x2``; the whole file is skipped where the
    topology cannot be described."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it refuses
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_topology):
    """Sharding on one chip of the described ``v5e:2x2``."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_topology.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _grad_of(fn, n):
    """d(sum fn)/d(first n arguments) — forces the backward kernels."""
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(n)))


def _bert_rows(d):
    return ((BERT["batch"], BERT["seq"], d), BF16)


_ATT = ((BERT["batch"], BERT["heads"], BERT["seq"],
         BERT["units"] // BERT["heads"]), BF16)
_VEC = ((BERT["units"],), BF16)
_SEED = ((), jnp.uint32)


def _ln_res_drop(x, res, g, b, seed):
    return fl.fused_layer_norm(x, g, b, res, dropout=0.1, seed=seed)


# (id, function of its array arguments, argument shapes, how many of the
# leading arguments the backward case differentiates)
TRAIN_KERNELS = [
    ("flash", lambda q, k, v: flash_attention(q, k, v),
     [_ATT, _ATT, _ATT], 3),
    ("layer_norm", lambda x, g, b: fl.fused_layer_norm(x, g, b),
     [_bert_rows(768), _VEC, _VEC], 3),
    ("layer_norm_residual",
     lambda x, r, g, b: fl.fused_layer_norm(x, g, b, r),
     [_bert_rows(768), _bert_rows(768), _VEC, _VEC], 4),
    ("layer_norm_residual_dropout", _ln_res_drop,
     [_bert_rows(768), _bert_rows(768), _VEC, _VEC, _SEED], 4),
    ("bias_gelu", lambda x, b: fl.fused_bias_gelu(x, b),
     [_bert_rows(BERT["hidden"]), ((BERT["hidden"],), BF16)], 2),
]


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("name,fn,shapes,n_diff", TRAIN_KERNELS,
                         ids=[k[0] for k in TRAIN_KERNELS])
def test_train_kernel_compiles(v5e, name, fn, shapes, n_diff, direction):
    if direction == "bwd":
        fn = _grad_of(fn, n_diff)
    assert "tpu_custom_call" in _compile(fn, v5e, *shapes)


def _kernel_calls(text):
    """The compiled program's Pallas custom calls and the bytes of scoped
    VMEM each of them took."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    scoped = [int(n) for line in calls for n in re.findall(
        r'used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)]
    return calls, scoped


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("d", [BERT["hidden"], BERT["units"]],
                         ids=["ffn", "mlm_head"])
def test_bias_gelu_keeps_its_chain_in_registers(v5e, d, direction):
    """The custom call's scoped VMEM is its pipelined blocks and little
    else. As whole-block expressions the two bodies made ~35 block-sized
    float32 temporaries, which Mosaic kept in 7.75 MB of scratch beside
    3.1 MB of blocks, and the stores of those bound the kernel
    (PERF.md section 6, PR 37)."""
    fn = lambda x, b: fl.fused_bias_gelu(x, b)  # noqa: E731
    n_blocks = 2                                # x in, y out
    if direction == "bwd":
        fn, n_blocks = _grad_of(fn, 2), 3       # x and dy in, dx out
    text = _compile(fn, v5e, _bert_rows(d), ((d,), BF16))
    calls, scoped = _kernel_calls(text)
    br = fl._bias_gelu_block_rows(BERT["batch"] * BERT["seq"], d, 2,
                                  n_blocks)
    blocks = 2 * n_blocks * br * d * 2          # double-buffered bf16
    assert len(scoped) == len(calls) == 1
    assert 0 < max(scoped) <= blocks + (256 << 10), (scoped, blocks)


def test_dp4_step_runs_the_kernels_over_its_batch_shards(v5e_topology,
                                                          monkeypatch):
    """A two-layer BERT step (forward, backward, update) for all four
    chips of the described ``v5e:2x2`` under ``TrainStep(mesh dp=4)``:
    the gates put each kernel inside a ``shard_map`` over ``dp``, so the
    compiled step holds the Mosaic custom calls (flash attention,
    LayerNorm with and without the residual, bias-GELU, each forward and
    backward) and never the ``(8, 12, 512, 512)`` scores of a shard's
    batch that XLA's reference attention keeps for the backward
    (PERF.md section 6, PR 39). Per-shard batch 8 of a global 32."""
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo.nlp import bert

    monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
    net = bert.BERTForPretrainFused(
        vocab_size=1024, token_type_vocab_size=2, max_length=BERT["seq"],
        num_layers=2, units=BERT["units"], hidden_size=BERT["hidden"],
        num_heads=BERT["heads"], dropout=0.1, attn_dropout=0.0, chunk=512)
    net.initialize()
    net.cast("bfloat16")
    mesh = par.make_mesh({"dp": 4}, devices=list(v5e_topology.devices))
    step = par.TrainStep(net, lambda outs, *rest: outs, "adam", mesh=mesh,
                         loss_only=True,
                         optimizer_params={"multi_precision": True})
    tokens = jax.ShapeDtypeStruct((32, BERT["seq"]), jnp.int32)
    text = step.aot_compile((tokens, tokens), ()).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = " ".join(re.findall(r'op_name="([^"]*)"', " ".join(calls)))
    for op in ("_contrib_sdp_attention", "_contrib_fused_layer_norm",
               "LayerNorm", "_contrib_fused_bias_gelu"):
        assert f"jvp(jit({op}))/shard_map" in names, op
        assert f"transpose(jvp(jit({op})))/shard_map" in names, op
    assert any(line.lstrip().startswith("%_fused_bias_gelu_pallas")
               for line in calls)
    # 2 layers x (flash + 2 LayerNorm + bias-GELU) + the embedding's and
    # the head's LayerNorm + the head's bias-GELU, forward and backward
    assert len(calls) == 2 * (2 * 4 + 3)
    assert "[8,12,512,512]" not in text and "[32,12,512,512]" not in text


def test_optimizer_sweep_compiles(v5e, monkeypatch):
    """The fused multi-tensor Adam sweep of the eager ``Trainer``
    (multi-precision: f32 master, bf16 gradient). The sweep is
    elementwise over a packed, padded bucket, so it has no width: a
    weight-and-bias bucket of BERT's hidden size stands for all of them
    (XLA's own compile of the packing grows with the bucket)."""
    import mxnet_tpu as mx
    from mxnet_tpu.optimizer import multi_tensor as mt

    monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
    opt = mx.optimizer.create("adam", learning_rate=1e-4,
                              multi_precision=True)
    static = mt.family_static(opt, "adam")
    shapes = [(BERT["units"], 128), (BERT["units"],)]

    def sweep(w0, w1, g0, g1, m0, m1, v0, v1, lr):
        # lr traced, as the jitted eager sweep has it: a python float
        # would hand XLA a bucket-sized constant to fold
        out = mt.packed_apply(
            "adam", static, shapes,
            {"w": [w0, w1], "g": [g0, g1], "mean": [m0, m1],
             "var": [v0, v1]},
            {"lr": [lr, lr], "wd": [lr * 0, lr * 0]}, 1.0,
            low_dtype=BF16, platform="tpu")
        return out["w_low"]

    f32 = [(s, jnp.float32) for s in shapes]
    text = _compile(sweep, v5e, *f32, *[(s, BF16) for s in shapes],
                    *f32, *f32, ((), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [(8, 512), (8, 1)],
                         ids=["prefill", "decode"])
def test_serve_rms_norm_compiles(v5e, rows):
    u = LLAMA["units"]
    text = _compile(lambda x, w: fl.fused_rms_norm(x, w, eps=1e-5), v5e,
                    (rows + (u,), BF16), ((u,), BF16))
    assert "tpu_custom_call" in text


def test_serve_rms_norm_backward_compiles(v5e):
    u = LLAMA["units"]
    fn = _grad_of(lambda x, w: fl.fused_rms_norm(x, w, eps=1e-5), 2)
    assert "tpu_custom_call" in _compile(fn, v5e, ((8, 512, u), BF16),
                                         ((u,), BF16))


def _paged_walks(compiled):
    """The compiled module's operations that the benchmark's trace readers
    (``paged_attn_roofline``, ``pallas_ms_per_round_serve``,
    ``readers.decode_rounds_in_trace``) take for the paged GQA kernel: HLO
    as the profiler prints it (every operand's shape before its name),
    layouts stripped, searched for the readers' pattern."""
    from jax._src.lib import _jax

    from benchmarks.kernels.paged_attention import PATTERN
    from benchmarks.lib.trace_reduce import strip_layouts

    options = _jax.HloPrintOptions.short_parsable()
    options.print_operand_shape = True
    options.print_percent = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(options)
    found = []
    for line in map(strip_layouts, text.splitlines()):
        match = re.search(PATTERN, line)
        if match:
            assert "tpu_custom_call" in line, line[:200]
            found.append(match.group(0))
    return found


# heads, kv heads, table width, pages of a layer's arena: the engine's
# shapes in chip_smoke's serve phase and in the benchmark's three cells
# that decode through this kernel (page 16, bf16)
PAGED = {"llama": (LLAMA["heads"], LLAMA["kv_heads"], 34, 273),
         # 32 kv heads: a 8 KB row, so a block is cut to its bytes
         "mha": (32, 32, 34, 273),
         "mistral": (32, 8, 160, 2880),
         "falcon": (20, 4, 64, 8193)}


@pytest.mark.parametrize("model,batch", [
    ("llama", 8), ("llama", 1), ("mha", 8),
    ("mistral", 1), ("mistral", 2), ("mistral", 4), ("mistral", 8),
    ("mistral", 32), ("falcon", 1), ("falcon", 16), ("falcon", 128)])
def test_serve_paged_attention_compiles(v5e, model, batch):
    """The decode kernel with the shapes the engines pass: one layer's
    arena viewed (slots, KV, D), page 16, a page table one request's
    budget wide, every decode bucket of the cells. The custom call's first
    operands are the page table and the lengths: the benchmark's trace
    readers find the kernel by them."""
    h, kv, table_w, pages = PAGED[model]
    d, page = LLAMA["head_dim"], 16

    def decode(q, k, v, table, lengths):
        return paged_attention_kernel(q, k, v, table, lengths,
                                      page_size=page, scale=d ** -0.5)

    arena = ((pages * page, kv, d), BF16)
    args = [jax.ShapeDtypeStruct(s, t, sharding=v5e) for s, t in (
        ((batch, h, 1, d), BF16), arena, arena,
        ((batch, table_w), jnp.int32), ((batch,), jnp.int32))]
    compiled = jax.jit(decode).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    walk, = _paged_walks(compiled)
    assert walk.startswith(f"custom-call(s32[{batch},{table_w}] ")


LONGCAT = dict(units=6144, expert_hidden=2048, held=16, outputs=768,
               top_k=12, heads=64, nope=128, rope=64, v=128, rank=512)


@pytest.mark.parametrize("tokens", [256, 2048])
def test_serve_routed_experts_compile(v5e, tokens):
    """LongCat-Flash's expert layer at its published widths and this
    repo's share (16 of 512 routed experts, 768 router outputs, top-12):
    the grouped-matmul kernel under the package's global x64 (its tile
    count must not become an s64 grid bound), at the 256-stream decode
    width and at a prefill width."""
    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops.contrib import moe_routed_experts

    c = LONGCAT

    def layer(x, router, bias, gate_up, down, valid):
        return moe_routed_experts(
            x, router, bias, gate_up, down, valid, first_held=0,
            n_routed=c["outputs"] - 256, n_zero=256, top_k=c["top_k"],
            scale=6.0)

    u, e = c["units"], c["expert_hidden"]
    with execution_platform("tpu"):
        text = _compile(
            layer, v5e, ((tokens, u), BF16), ((c["outputs"], u), BF16),
            ((c["outputs"],), BF16), ((c["held"], u, 2 * e), BF16),
            ((c["held"], e, u), BF16), ((tokens,), jnp.bool_))
    assert text.count("tpu_custom_call") >= 2        # gate/up and down


def _latent_decode_text(v5e, batch):
    """HLO of ``mla_paged_decode`` over one sublayer's arena at the
    LongCat cell's sizes, and the shapes an arena copy would have."""
    from mxnet_tpu.ops.attention import mla_paged_decode

    c = LONGCAT
    page, table_w, pages = 16, 72, 18433

    def decode(q, arena, table, lengths, kvb):
        return mla_paged_decode(q, arena, table, lengths, kvb,
                                nope_dim=c["nope"], v_dim=c["v"],
                                scale=192 ** -0.5)

    width = -(-(c["rank"] + c["rope"]) // 128) * 128
    text = _compile(
        decode, v5e, ((batch, c["heads"], c["nope"] + c["rope"]), BF16),
        ((pages, page, width), BF16), ((batch, table_w), jnp.int32),
        ((batch,), jnp.int32),
        ((c["heads"] * (c["nope"] + c["v"]), c["rank"]), BF16))
    big = (f"bf16[{pages},{page},{width}]",
           f"bf16[{batch},{table_w * page},{width}]",
           f"bf16[{batch},{table_w},{page},{width}]")
    copies = [ln for ln in text.splitlines() if " copy(" in ln
              and ln.split(" = ", 1)[-1].startswith(big)]
    return text, big, copies


def test_serve_latent_decode_reads_the_arena_in_place(v5e):
    """The absorbed latent attention by gather (the path off the TPU
    gate) over one sublayer's arena at the LongCat cell's sizes: pages
    lead and rows are lane-padded, so the arena keeps the default
    row-major layout and the compiled step holds no copy of it (a
    (slots, 576) arena is laid out column-major and copied twice a
    step), and the gathered block is neither sliced nor relaid."""
    _, _, copies = _latent_decode_text(v5e, 256)
    assert not copies, copies[:2]


@pytest.mark.parametrize("batch", [1, 8, 256])
def test_serve_latent_decode_kernel_reads_live_pages(v5e, batch):
    """The same op routed as on the chip, at each decode bucket of the
    LongCat cell: ONE Mosaic kernel that takes the arena as it lies,
    and neither the gathered block nor the score matrix of the gather
    path is left in the program."""
    from mxnet_tpu.base import execution_platform

    with execution_platform("tpu"):
        text, big, copies = _latent_decode_text(v5e, batch)
    assert text.count("tpu_custom_call") == 1
    assert not copies, copies[:2]
    heads = LONGCAT["heads"]
    for gone in big[1:] + (f"f32[{batch},{heads},{72 * 16}]",):
        assert gone not in text, gone


def _entry_results(text):
    """The result shapes of the compiled program's entry computation."""
    head = re.sub(r"\{[^{}]*\}|/\*.*?\*/", "",               # layouts and
                  text.split("\n", 1)[0])                     # index marks
    (results,) = re.findall(r"entry_computation_layout=\{.*->(\(.*?\))\}",
                            head)
    return results


def test_serve_longcat_head_returns_the_picked_ids(v5e):
    """The last program of a LongCat forward at the cell's decode shape
    (256 streams, the 16,384-row vocabulary slice): ONE program whose
    results are the greedy ids, which the host fetches, and the float32
    logits, which stay on the device; the pick has no 64-bit index."""
    from mxnet_tpu.gluon.model_zoo.nlp.longcat_flash import _head

    b, v, u = 256, 16384, LONGCAT["units"]
    text = _compile(lambda *a: _head(*a, eps=1e-5), v5e,
                    ((b, 1, u), BF16), ((u,), BF16), ((v, u), BF16),
                    ((b, 1), jnp.int32), ((b,), jnp.int32))
    assert text.count("HloModule") == 1
    assert _entry_results(text) == f"(s32[{b}], f32[{b},{v}])"
    assert "s64[" not in text


# the Mistral cells' cache: pages of 16 on a table of 160; two layers,
# since the depth changes nothing after the stack
MISTRAL = dict(vocab=32768, ffn=14336, layers=2, pages=2881, page=16,
               table_w=160)


def _llama_serve(v5e, batch, length, fresh=False):
    """The Llama engine's one program at the Mistral cells' sizes
    (published widths), compiled for the described chip as the engine
    jits it, every arena array donated. ``fresh``: the form a prefill
    from position 0 takes."""
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp.llama import _paged_forward

    v, layers, ffn, page, table_w, pages = (MISTRAL[k] for k in (
        "vocab", "layers", "ffn", "page", "table_w", "pages"))
    u, h, kv, d = (LLAMA[k] for k in ("units", "heads", "kv_heads",
                                      "head_dim"))
    cfg = {"num_heads": h, "num_kv_heads": kv, "head_dim": d,
           "rope_theta": 1e6, "eps": 1e-5}

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    layer = tuple(of(s) for s in (
        (u,), (h * d, u), (2 * kv * d, u), (u, h * d), (u,), (2 * ffn, u),
        (u, ffn)))
    params = (of((v, u)), (layer,) * layers, of((u,)), of((v, u)))
    ints = [of(s, jnp.int32) for s in (
        (batch, length), (batch, length), (batch, table_w), (batch,))]
    arenas = [of((pages, page, kv * d))] * (2 * layers)
    with execution_platform("tpu"):
        return jax.jit(
            functools.partial(_paged_forward, cfg=cfg, page_size=page,
                              fresh=fresh),
            donate_argnums=tuple(range(5, 5 + len(arenas)))).lower(
                params, *ints, *arenas).compile()


def test_serve_llama_decode_returns_the_picked_ids(v5e, monkeypatch):
    """The Llama decode program at the Mistral cells' decode shape (32
    streams): still ONE program, with the greedy ids beside the logits
    in the head's own dtype and a key and a value array a layer."""
    monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")       # as the cells run
    b, v = 32, MISTRAL["vocab"]
    text = _llama_serve(v5e, b, 1).as_text()
    assert text.count("HloModule") == 1
    arenas = ", ".join([f"bf16[{MISTRAL['pages']},16,1024]"]
                       * (2 * MISTRAL["layers"]))
    assert _entry_results(text) == f"(s32[{b}], bf16[{b},{v}], {arenas})"
    assert "s64[" not in text


def test_serve_llama_decode_walks_live_pages_without_the_switch(
        v5e, monkeypatch):
    """The paged kernel is routed by platform and shapes alone: with
    ``MXNET_PALLAS_FUSED`` unset the decode program still holds one
    live-page walk a layer (and none of the norms' row kernels, which
    the switch decides)."""
    monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
    compiled = _llama_serve(v5e, 32, 1)
    assert len(_paged_walks(compiled)) == MISTRAL["layers"]
    assert compiled.as_text().count("tpu_custom_call") == MISTRAL["layers"]


@pytest.mark.parametrize("batch,length", [(32, 1), (4, 2048)],
                         ids=["decode", "prefill"])
def test_serve_llama_reads_the_arenas_in_place(v5e, monkeypatch, batch,
                                               length):
    """Both phases of the Llama program at the Mistral cells' sizes,
    ``[prefill]`` in the form that attends THROUGH the page table (what
    a forward of several positions at an offset runs; a prefill from
    position 0 takes the fresh form, below): a layer scatters its rows
    into its own two arrays and the attention reads them as they lie
    (the kernel when decoding, the gather otherwise), so no operation
    slices, reshapes, copies or transposes
    an arena-sized array (the stacked ``(layers, slots, 8, 128)`` block
    cost a 94 MB slice and a 94 MB relayout a layer and side), every
    arena is aliased to its result, and a decode round's temporaries
    are the activations alone."""
    monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")       # as the cells run
    compiled = _llama_serve(v5e, batch, length)
    pages, layers = MISTRAL["pages"], MISTRAL["layers"]
    big = tuple(f"bf16[{shape}]" for shape in (
        f"1,{pages * 16},8,128", f"{pages * 16},8,128",
        f"{pages},16,1024", f"{pages * 16},1024"))
    moved = [ln for ln in compiled.as_text().splitlines()
             if re.search(r" (slice|reshape|copy|transpose)\(", ln)
             and ln.split(" = ", 1)[-1].startswith(big)]
    assert not moved, moved[:2]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * layers * pages * 16 * 1024 * 2
    if length == 1:
        assert memory.temp_size_in_bytes < 0.02e9


@pytest.mark.parametrize("batch,length", [(4, 2048), (1, 1024), (32, 2048)])
def test_serve_llama_fresh_prefill_attends_over_its_own_rows(v5e, monkeypatch,
                                                             batch, length):
    """The program of a prefill from position 0 at the Mistral cells'
    sizes: ONE flash custom call a layer over the dispatch's own q / k /
    v and nothing else from Pallas, no score matrix over the table's
    2,560 slots, none of the operations the benchmark's readers take
    for the paged decode kernel (the flash call's operands are q, k, v,
    seed), every arena still aliased, and temporaries under 1 GB where
    the gather form's are 4.24 at (4, 2048). (32, 2048), which the
    gather form cannot fit on the chip, compiles."""
    monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
    compiled = _llama_serve(v5e, batch, length, fresh=True)
    text = compiled.as_text()
    pages, layers = MISTRAL["pages"], MISTRAL["layers"]
    assert text.count("tpu_custom_call") == layers
    assert not _paged_walks(compiled)
    slots = MISTRAL["table_w"] * MISTRAL["page"]
    assert f"{length},{slots}]" not in text      # f32[B,32,L,2560] least of all
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * layers * pages * 16 * 1024 * 2
    print(f"fresh prefill ({batch}, {length}): temp_size "
          f"{memory.temp_size_in_bytes / 1e9:.4f} GB")
    if batch * length <= 8192:
        assert memory.temp_size_in_bytes < 1.0e9


GLM5 = dict(units=6144, heads=64, q_rank=2048, kv_rank=512, nope=192,
            rope=64, v=256, index_heads=32, index_dim=128, topk=2048,
            ffn=12288, expert=2048, held=16, outputs=256,
            pages=17665, page=16, table_w=2208)


def _glm5_layer(v5e, kind, batch, length):
    """The GLM-5 engine's layer program of ``kind`` at the cell's sizes
    (8 streams x 35,328 tokens of cache on one page table), compiled for
    the described chip as the engine jits it."""
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import glm_moe_dsa as g

    c = GLM5
    u, h = c["units"], c["heads"]
    cfg = dict(num_heads=h, q_lora_rank=c["q_rank"], kv_lora_rank=c["kv_rank"],
               nope=c["nope"], rope=c["rope"], v_dim=c["v"],
               index_heads=c["index_heads"], index_dim=c["index_dim"],
               index_topk=c["topk"], rope_theta=1e6, eps=1e-5,
               scale=(c["nope"] + c["rope"]) ** -0.5, n_routed=c["outputs"],
               top_k=8, moe_scale=2.5, first_held=0, held=c["held"])

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    qk = c["nope"] + c["rope"]
    lp = {"in_norm": of((u,)), "qa": of((c["q_rank"], u)),
          "qnorm": of((c["q_rank"],)), "qb": of((h * qk, c["q_rank"])),
          "kva": of((c["kv_rank"] + c["rope"], u)),
          "kvnorm": of((c["kv_rank"],)),
          "kvb": of((h * (c["nope"] + c["v"]), c["kv_rank"])),
          "out": of((u, h * c["v"])),
          "iq": of((c["index_heads"] * c["index_dim"], c["q_rank"])),
          "ik": of((c["index_dim"], u)), "ik_gain": of((c["index_dim"],)),
          "ik_bias": of((c["index_dim"],)), "iw": of((c["index_heads"], u)),
          "post_norm": of((u,))}
    if kind == "moe":
        e = c["expert"]
        lp.update(moe={"router": of((c["outputs"], u)),
                       "router_bias": of((c["outputs"],)),
                       "gate_up": of((c["held"], u, 2 * e)),
                       "down": of((c["held"], e, u))},
                  shared_gate_up=of((2 * e, u)), shared_down=of((u, e)))
    else:
        lp.update(ffn_gate_up=of((2 * c["ffn"], u)),
                  ffn_down=of((u, c["ffn"])))
    fn = functools.partial(g._layer_forward, cfg=cfg, moe=kind == "moe")
    with execution_platform("tpu"):
        return jax.jit(fn, donate_argnums=(2, 3)).lower(
            of((batch, length, u)), lp, of((c["pages"], c["page"], 640)),
            of((c["pages"], c["page"], 128)),
            of((batch, length), jnp.int32),
            of((batch, c["table_w"]), jnp.int32),
            of((batch,), jnp.int32)).compile()


@pytest.mark.parametrize("kind,batch,length", [("moe", 8, 1),
                                               ("dense", 8, 1),
                                               ("moe", 1, 2048),
                                               ("dense", 8, 256)])
def test_serve_glm5_layer_program_compiles(v5e, kind, batch, length):
    """A decode round of 8 streams through both layer programs, a prefill
    chunk of 2048 tokens and the warm-up's two-stream prefill, each over
    35,328 cached slots a stream: the program compiles for the chip,
    updates both arenas in place (the outputs alias them) and its
    temporaries leave room beside 7.8 GB of weights and 2.2 GB of cache.
    A chunk holds ONE key block's expanded keys and values and one (H,
    query block, key block) score tile, not (L, H, T) of them, whatever
    the batch bucket."""
    compiled = _glm5_layer(v5e, kind, batch, length)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    arenas = GLM5["pages"] * GLM5["page"] * (640 + 128) * 2
    assert mem.alias_size_in_bytes >= arenas
    assert mem.temp_size_in_bytes < (0.2e9 if length == 1 else 1.5e9)
    # the held experts run through the megablox kernel, twice a layer
    assert text.count("tpu_custom_call") == (2 if kind == "moe" else 0)
    assert "s64[" not in text
    # the exact top-2048 is a threshold, not a top_k: no sort under the
    # sparse attention's scopes
    assert not [ln for ln in text.splitlines()
                if " sort(" in ln and "/dsa." in ln]
    if length == 1:
        # a decode step lists its selected slots by counting them and
        # reads their page ids through a one-hot product: neither a
        # scatter of one update a slot (1 ms a layer on the chip) nor a
        # scalar gather under the attention's scope, whose only gather is
        # the one of the selected rows
        attend = [ln for ln in text.splitlines() if "/dsa.attend" in ln]
        assert not [ln for ln in attend if " scatter(" in ln]
        assert len([ln for ln in attend if " gather(" in ln]) == 1


PHI4 = dict(units=2560, heads=40, kv_heads=20, head_dim=64, window=512,
            d_inner=5120, d_state=16, d_conv=4, dt_rank=160, ffn=10240,
            vocab=200064, pages=37889, page=16, table_w=1184, slots=33)


def _phi4_program(v5e, part, batch, length):
    """One of the Phi-4-mini-flash engine's programs at the cell's sizes
    (33 state slots, 37,889 pages, tables of 1,184 pages), compiled for
    the described chip as the engine jits it."""
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import phi4flash as m

    c = PHI4
    u, d_in, width = c["units"], c["d_inner"], c["kv_heads"] * c["head_dim"]
    cfg = dict(num_layers=32, units=u, num_heads=c["heads"],
               num_kv_heads=c["kv_heads"], head_dim=c["head_dim"],
               window=c["window"], d_inner=d_in, d_state=c["d_state"],
               d_conv=c["d_conv"], dt_rank=c["dt_rank"], eps=1e-5,
               page_size=c["page"])

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    common = {"ln1_g": of((u,)), "ln1_b": of((u,)), "ln2_g": of((u,)),
              "ln2_b": of((u,)), "gate_up": of((2 * c["ffn"], u)),
              "down": of((u, c["ffn"]))}
    heads = {k: of((c["head_dim"],)) for k in ("lq1", "lk1", "lq2", "lk2")}
    heads.update(subln=of((2 * c["head_dim"],)), o=of((u, u)), o_b=of((u,)),
                 lam0=of((), jnp.float32))
    mamba = dict(common, **{
        "in": of((2 * d_in, u)), "conv_w": of((d_in, c["d_conv"])),
        "conv_b": of((d_in,)), "x": of((c["dt_rank"] + 2 * c["d_state"],
                                        d_in)),
        "dt_w": of((d_in, c["dt_rank"])), "dt_b": of((d_in,)),
        "a_log": of((d_in, c["d_state"])), "d": of((d_in,)),
        "out": of((u, d_in))})
    attn = dict(common, **heads, qkv=of((u + 2 * width, u)),
                qkv_b=of((u + 2 * width,)))
    gmu = dict(common, gmu_in=of((d_in, u)), gmu_out=of((u, d_in)))
    cross = dict(common, **heads, q=of((u, u)), q_b=of((u,)))
    s = c["slots"]
    tails = of((s, c["d_conv"] - 1, d_in))
    states = of((s, c["d_state"], d_in), jnp.float32)
    ring = of((s, c["window"], width))
    arena = of((c["pages"], c["page"], width))
    ints = lambda *shape: of(shape, jnp.int32)  # noqa: E731
    x = of((batch, length, u))
    pos, lens, slots = ints(batch, length), ints(batch), ints(batch)
    table = ints(batch, c["table_w"])
    if part == "self":
        fn, donate = m._self_pair, (3, 4, 5, 6)
        args = (x, mamba, attn, tails, states, ring, ring, pos, lens, slots)
    elif part == "mid":
        fn, donate = m._middle, (3, 4, 5, 6)
        args = (x, mamba, attn, tails, states, arena, arena, pos, table,
                lens, slots)
    elif part == "full":
        fn, donate = m._full_last, ()
        args = (x, attn, arena, arena, table, lens)
    else:
        fn, donate = m._cross_pair, ()
        args = (x, of((batch, 1, d_in)), gmu, cross, arena, arena, table,
                lens)
    with execution_platform("tpu"):
        return jax.jit(functools.partial(fn, cfg=cfg),
                       donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("part,batch,length", [
    ("self", 32, 1), ("mid", 32, 1), ("full", 32, 1), ("cross", 32, 1),
    ("self", 1, 2048), ("mid", 1, 2048), ("self", 32, 64)])
def test_serve_phi4flash_program_compiles(v5e, part, batch, length):
    """A decode round of 32 streams through the four programs, a prefill
    chunk of 2,048 tokens and the warm-up's 32-stream prefill: each
    compiles for the chip, updates the slot arrays and the page arenas
    in place (the outputs alias them), reads rings and shared pages
    through the paged kernel where it takes one row a stream, and its
    temporaries leave room beside 7.7 GB of weights, 3.1 GB of pages and
    0.8 GB of slots. A chunk's scan holds a block of steps, never (L,
    d_state, d_inner)."""
    compiled = _phi4_program(v5e, part, batch, length)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    c = PHI4
    width = c["kv_heads"] * c["head_dim"]
    slot_bytes = c["slots"] * (
        (c["d_conv"] - 1) * c["d_inner"] * 2
        + c["d_state"] * c["d_inner"] * 4)
    if part == "self":
        assert mem.alias_size_in_bytes >= slot_bytes \
            + 2 * c["slots"] * c["window"] * width * 2
    elif part == "mid":
        assert mem.alias_size_in_bytes >= slot_bytes \
            + 2 * c["pages"] * c["page"] * width * 2
    assert mem.temp_size_in_bytes < (0.4e9 if length == 1 else 2.0e9)
    kernels = {"self": 1 if length == 1 else 0, "mid": 0, "full": 1,
               "cross": 1}[part]
    assert text.count("tpu_custom_call") == kernels
    assert "s64[" not in text


# -- Falcon-H1 (falcon_h1_chat_closed_c128): one layer program a signature --------------------

FALCON = dict(units=5120, ffn=21504, heads=20, kv_heads=4, head_dim=128,
              d_ssm=4096, ssm_heads=32, d_state=256, groups=2, d_conv=4,
              vocab=261120, pages=8193, page=16, table_w=64, slots=129)


def _falcon_cfg():
    """The engine's ``cfg`` at the published sizes (the model's defaults;
    one layer: no parameter is allocated)."""
    from mxnet_tpu.gluon.model_zoo.nlp.falcon_h1 import FalconH1Model

    cfg = FalconH1Model(num_layers=1)._decode_cfg
    assert (cfg["units"], cfg["d_ssm"], cfg["d_state"]) == (
        FALCON["units"], FALCON["d_ssm"], FALCON["d_state"])
    return dict(cfg, page_size=FALCON["page"])


def _falcon_program(v5e, part, batch, length, monkeypatch, switch="1"):
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import falcon_h1 as m

    if switch is None:
        monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
    else:
        monkeypatch.setenv("MXNET_PALLAS_FUSED", switch)    # as the cell runs
    c, cfg = FALCON, _falcon_cfg()
    u, d = c["units"], c["d_ssm"]
    width = d + 2 * c["groups"] * c["d_state"]

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ints = lambda *shape: of(shape, jnp.int32)  # noqa: E731
    x = of((batch, length, u), jnp.float32)
    pos, lens, slots = ints(batch, length), ints(batch), ints(batch)
    if part == "head":
        fn = functools.partial(m._head, eps=1e-5, multiplier=0.0078125)
        args, donate = (x, of((u,)), of((c["vocab"], u)), pos, lens), ()
    else:
        layer = {
            "ln1": of((u,)), "ln2": of((u,)),
            "in": of((2 * d + 2 * c["groups"] * c["d_state"]
                      + c["ssm_heads"], u)),
            "mup": of((width + d + c["ssm_heads"],), jnp.float32),
            "conv_w": of((width, c["d_conv"])), "conv_b": of((width,)),
            "dt_b": of((c["ssm_heads"],)), "a_log": of((c["ssm_heads"],)),
            "d": of((c["ssm_heads"],)), "norm": of((d,)), "out": of((u, d)),
            "q": of((c["heads"] * c["head_dim"], u)),
            "k": of((c["kv_heads"] * c["head_dim"], u)),
            "v": of((c["kv_heads"] * c["head_dim"], u)),
            "o": of((u, c["heads"] * c["head_dim"])),
            "gate_up": of((2 * c["ffn"], u)), "down": of((u, c["ffn"]))}
        arena = of((c["pages"], c["page"], c["kv_heads"] * c["head_dim"]))
        fn = functools.partial(m._layer_forward, cfg=cfg)
        args = (x, layer, arena, arena,
                of((c["slots"], c["d_conv"] - 1, width), jnp.float32),
                of((c["slots"], c["ssm_heads"], c["d_state"],
                    d // c["ssm_heads"]), jnp.float32),
                pos, ints(batch, c["table_w"]), lens, slots)
        donate = (2, 3, 4, 5)
    with execution_platform("tpu"):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("part,batch,length", [
    ("layer", 128, 1), ("head", 128, 1), ("layer", 1, 384),
    ("layer", 16, 64)])
def test_serve_falcon_h1_program_compiles(v5e, monkeypatch, part, batch,
                                          length):
    """A decode round of 128 streams through the layer program and the
    head, the largest prefill of one prompt and the widest prefill
    batch the cell's bound allows: each compiles for the chip, updates
    the layer's two page arenas and two slot arrays in place (the outputs
    alias them: 0.27 GB of pages and 0.55 GB of slots a layer), runs the
    paged GQA kernel and the SSD state-update kernel where it takes one
    token a stream, and its temporaries leave room beside 10.5 GB of
    weights, 1.6 GB of pages and 3.46 GB of slots."""
    compiled = _falcon_program(v5e, part, batch, length, monkeypatch)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    c = FALCON
    if part == "layer":
        width = c["d_ssm"] + 2 * c["groups"] * c["d_state"]
        aliased = (2 * c["pages"] * c["page"] * c["kv_heads"]
                   * c["head_dim"] * 2
                   + c["slots"] * 4 * ((c["d_conv"] - 1) * width
                                       + c["d_state"] * c["d_ssm"]))
        assert mem.alias_size_in_bytes >= aliased
        # the two norms' row kernels; one token a stream: the paged read
        # and the state update beside them
        assert text.count("tpu_custom_call") == (4 if length == 1 else 2)
        assert ("ssd_state_update" in text) == (length == 1)
    print(part, batch, length, text.count("tpu_custom_call"),
          mem.temp_size_in_bytes / 1e9, mem.alias_size_in_bytes / 1e9)
    # no copy of an arena: the kernel reads the layer's pages as they lie
    assert mem.temp_size_in_bytes < (0.1e9 if length == 1 else 0.75e9)
    assert "s64[" not in text


def test_falcon_h1_decode_layer_holds_its_kernels_without_the_switch(
        v5e, monkeypatch):
    """The paged read and the in-place state update are routed by
    platform and shapes alone: with ``MXNET_PALLAS_FUSED`` unset the
    decode layer program holds both (and neither norm's row kernel)."""
    compiled = _falcon_program(v5e, "layer", 128, 1, monkeypatch,
                               switch=None)
    text = compiled.as_text()
    assert len(_paged_walks(compiled)) == 1
    assert "ssd_state_update" in text
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("batch,parent_temp", [
    (128, 4460032), (16, 0), (1, 1451520)])
def test_falcon_h1_decode_layer_temporaries_do_not_grow(
        v5e, monkeypatch, batch, parent_temp):
    """The cell holds 97.6% of the chip's memory, and 0.27 GB more of
    temporaries serialised its round's dispatches (ROADMAP A0 g): the
    decode layer program's temporaries in each decode bucket are no
    larger than with the grid-per-page kernel (``parent_temp``: the same
    compile at commit a8ffb64), and the live-page walk is there under the
    signature the benchmark's readers look for."""
    compiled = _falcon_program(v5e, "layer", batch, 1, monkeypatch)
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp
    assert len(_paged_walks(compiled)) == 1


DOTS = dict(units=7168, heads=128, q_rank=1536, kv_rank=512, nope=128,
            rope=64, v=128, ffn=18432, expert=2048, held=16, outputs=256,
            pages=3329, page=16, table_w=416,
            vit=dict(embed_dim=1536, num_layers=42, num_heads=12,
                     intermediate_size=4224, out_dim=7168, patch_dim=588,
                     eps=1e-5, rope_theta=10000.0))


def _dots_layer(v5e, kind, batch, length):
    """The dots.vlm1 engine's layer program of ``kind`` at the cell's
    sizes (8 streams x 6,656 tokens of latent cache), compiled for the
    described chip as the engine jits it."""
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import dots_vlm as g
    from mxnet_tpu.ops.attention import yarn_mscale

    c = DOTS
    u, h = c["units"], c["heads"]
    qk = c["nope"] + c["rope"]
    cfg = dict(num_heads=h, q_lora_rank=c["q_rank"], kv_lora_rank=c["kv_rank"],
               nope=c["nope"], rope=c["rope"], v_dim=c["v"], rope_theta=1e4,
               yarn=(40.0, 32.0, 1.0, 4096.0), eps=1e-6,
               scale=yarn_mscale(40.0) ** 2 * qk ** -0.5,
               n_routed=c["outputs"], top_k=8, n_group=8, topk_group=4,
               moe_scale=2.5, first_held=0, held=c["held"])

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    lp = {"in_norm": of((u,)), "qa": of((c["q_rank"], u)),
          "qnorm": of((c["q_rank"],)), "qb": of((h * qk, c["q_rank"])),
          "kva": of((c["kv_rank"] + c["rope"], u)),
          "kvnorm": of((c["kv_rank"],)),
          "kvb": of((h * (c["nope"] + c["v"]), c["kv_rank"])),
          "out": of((u, h * c["v"])), "post_norm": of((u,))}
    if kind == "moe":
        e = c["expert"]
        lp.update(moe={"router": of((c["outputs"], u)),
                       "router_bias": of((c["outputs"],)),
                       "gate_up": of((c["held"], u, 2 * e)),
                       "down": of((c["held"], e, u))},
                  shared_gate_up=of((2 * e, u)), shared_down=of((u, e)))
    else:
        lp.update(ffn_gate_up=of((2 * c["ffn"], u)),
                  ffn_down=of((u, c["ffn"])))
    fn = functools.partial(g._layer_forward, cfg=cfg, moe=kind == "moe")
    with execution_platform("tpu"):
        return jax.jit(fn, donate_argnums=(2,)).lower(
            of((batch, length, u)), lp, of((c["pages"], c["page"], 640)),
            of((batch, length), jnp.int32),
            of((batch, c["table_w"]), jnp.int32),
            of((batch,), jnp.int32)).compile()


def _computations(text):
    """An HLO module's computations by name: ``{name: body text}``."""
    out, name, body = {}, None, []
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head and name is None:
            name, body = head.group(1), []
        elif name is not None and ln == "}":
            out[name], name = "\n".join(body), None
        elif name is not None:
            body.append(ln)
    return out


def _reached_from(comps, name):
    """The text of computation ``name`` and of every computation it
    calls, however deep (fusions, loop bodies and conditions, branches)."""
    seen, todo = [], [name]
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen.append(n)
        todo += re.findall(r"%([\w.\-]+)", " ".join(re.findall(
            r"(?:calls|to_apply|body|condition)=%[\w.\-]+|"
            r"branch_computations=\{[^}]*\}",
            comps[n])))
    return "\n".join(comps[n] for n in seen)


# temporaries of a prefill layer program, bytes: what this file's compile
# read (PR 49; the parent's walk alone: moe 2048 1.0639e9, dense 2048
# 1.0748e9, moe 1024 0.6905e9) with ~4% of room
_DOTS_PREFILL_TEMP = {("moe", 2048): 1.20e9, ("dense", 2048): 1.12e9,
                      ("moe", 1024): 0.78e9}


@pytest.mark.parametrize("kind,batch,length", [("moe", 8, 1),
                                               ("dense", 8, 1),
                                               ("moe", 1, 2048),
                                               ("dense", 1, 2048),
                                               ("moe", 1, 1024)])
def test_serve_dots_vlm_layer_program_compiles(v5e, kind, batch, length):
    """A decode round of 8 streams through both language layer programs
    (128 query heads through the paged latent kernel) and a prefill chunk
    of 2,048 or 1,024 tokens over 6,656 cached slots: the program compiles
    for the chip, updates the arena in place and its temporaries leave
    room beside 11.7 GB of weights. A chunk's program holds BOTH forms of
    its attention behind one ``conditional`` on the positions: the fresh
    branch is the causal flash forward at head width 192 (values
    zero-padded from 128) and makes nothing as wide as the page table's
    6,656 slots; the other branch is the walk over the cache."""
    compiled = _dots_layer(v5e, kind, batch, length)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= DOTS["pages"] * DOTS["page"] * 640 * 2
    assert mem.temp_size_in_bytes < (
        0.2e9 if length == 1 else _DOTS_PREFILL_TEMP[kind, length])
    # megablox twice in an expert layer; the paged latent kernel a step,
    # the flash forward a chunk
    calls = text.count("tpu_custom_call")
    assert calls == (2 if kind == "moe" else 0) + 1
    assert "s64[" not in text
    conds = [ln for ln in text.splitlines() if " conditional(" in ln]
    if length == 1:
        assert not conds
        return
    assert len(conds) == 1
    comps = _computations(text)
    branches = [_reached_from(comps, n) for n in re.findall(
        r"%([\w.\-]+)", re.search(r"branch_computations=\{[^}]*\}",
                                  conds[0]).group(0))]
    assert len(branches) == 2
    fresh, = [t for t in branches if "tpu_custom_call" in t]
    walk, = [t for t in branches if "tpu_custom_call" not in t]
    slots = str(DOTS["table_w"] * DOTS["page"])
    assert slots in walk and " while(" in walk
    assert slots not in fresh and " while(" not in fresh
    assert f"bf16[{DOTS['heads']},{length},192]" in fresh


@pytest.mark.parametrize("bucket", [2048, 12288])
def test_serve_dots_vit_encode_compiles(v5e, bucket):
    """The tower's encode program at the smallest and the largest
    patch-count bucket: 42 layers under one scan, attention through the
    key-length-bounded flash forward (one custom call, in the scan's
    body), the rows written into the request's buffer in place."""
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.vision import navit

    v = DOTS["vit"]
    e, f, n = v["embed_dim"], v["intermediate_size"], v["num_layers"]

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    w = {"patch_w": of((e, 588)), "patch_b": of((e,)),
         "patch_norm": of((e,)), "post_norm": of((e,)), "ln_g": of((e,)),
         "ln_b": of((e,)), "merger_a": of((4 * e, 4 * e)),
         "merger_a_b": of((4 * e,)), "merger_b": of((v["out_dim"], 4 * e)),
         "merger_b_b": of((v["out_dim"],)),
         "blocks": {"norm1": of((n, e)), "qkv": of((n, 3 * e, e)),
                    "proj": of((n, e, e)), "norm2": of((n, e)),
                    "fc13": of((n, 2 * f, e)), "fc2": of((n, e, f))}}
    rows = 6144 + 3072
    fn = functools.partial(navit._encode_into, cfg=v)
    with execution_platform("tpu"):
        compiled = jax.jit(fn, donate_argnums=(4,)).lower(
            w, of((bucket, 588)), of((bucket, 2), jnp.int32),
            of((), jnp.int32), of((1, rows, v["out_dim"])),
            of((), jnp.int32)).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1
    assert mem.alias_size_in_bytes >= rows * v["out_dim"] * 2
    assert mem.temp_size_in_bytes < 1.0e9
    assert "s64[" not in text


def test_flash_forward_with_a_key_length_compiles(v5e):
    """The key-length-bounded flash forward at the tower's head shape (12
    heads of 128) over the largest bucket: a scalar-prefetch custom call
    whose first operand is the int32 lengths."""
    fn = lambda q, k, v, n: flash_attention(q, k, v, kv_len=n)  # noqa: E731
    args = [jax.ShapeDtypeStruct((1, 12, 12288, 128), BF16, sharding=v5e)
            for _ in range(3)]
    n = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=v5e)
    text = jax.jit(fn).lower(*args, n).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
            and "custom-call(" in ln]
    assert len(call) == 1 and "s32[1]" in call[0]


@pytest.mark.parametrize("rows", [2048, 12288])
def test_bounded_flash_keeps_its_chain_in_registers(v5e, rows):
    """The custom call's scoped VMEM is its pipelined blocks, its declared
    scratch and little else: a tile's scores live in registers and the
    compiler's spill slots between the two products. As one whole
    ``(1024, 512)`` tile a grid step the scores were a 2 MB float32
    temporary beside 3.1 MB of blocks and scratch (5,214,208 B scoped:
    PERF.md section 6, PR 45)."""
    from mxnet_tpu.pallas_kernels.flash_attention import _bounded_blocks

    d = 128
    fn = lambda q, k, v, n: flash_attention(q, k, v, kv_len=n)  # noqa: E731
    text = _compile(fn, v5e, *[((1, 12, rows, d), BF16)] * 3,
                    ((1,), jnp.int32))
    calls, scoped = _kernel_calls(text)
    assert len(scoped) == len(calls) == 1 and "flash_fwd_bounded" in calls[0]
    bq, bkv, _, _ = _bounded_blocks(rows, rows, d, 2)
    blocks = 2 * 2 * (bq + bkv) * d * 2     # q o, k v; double-buffered bf16
    scratch = bq * (d + 128 + 128) * 4      # acc, m, l: float32
    assert 0 < scoped[0] <= blocks + scratch + (256 << 10), (
        scoped, blocks, scratch)


# sha256 of BERT's forward flash custom call (layouts stripped) as the
# commit BEFORE the key-length bound compiled it: the bound is a separate
# kernel behind an argument, the call without it is the call it was
BERT_FLASH_CALL_SHA256 = "1bf732d0df62e7cdb666bf22be5610f39a66ebd820cb7bf48238b0c237c085c9"


def test_bert_flash_call_is_the_parents(v5e):
    import hashlib

    from benchmarks.lib import trace_reduce

    d = BERT["units"] // BERT["heads"]
    shape = ((BERT["batch"], BERT["heads"], BERT["seq"], d), BF16)
    text = _compile(lambda q, k, v: flash_attention(q, k, v), v5e,
                    shape, shape, shape)
    calls = [trace_reduce.strip_layouts(ln.strip())
             for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1
    digest = hashlib.sha256(calls[0].encode()).hexdigest()
    assert digest == BERT_FLASH_CALL_SHA256


# -- SDAR-MoE (sdar_blockdiff_closed_c128): a block step and a prefill ---------------------------

SDAR = dict(units=2048, heads=32, kv_heads=4, head_dim=128, expert=768,
            experts=128, top_k=8, vocab=151936, pages=14337, page=16,
            table_w=112, block=4)


def _sdar_cfg():
    """The engine's ``cfg`` at the published sizes (the model's defaults;
    one layer: no parameter is allocated)."""
    from mxnet_tpu.gluon.model_zoo.nlp.sdar_moe import SdarMoeModel

    cfg = SdarMoeModel(num_layers=1)._decode_cfg
    assert (cfg["units"], cfg["n_experts"], cfg["expert_hidden_size"],
            cfg["vocab_size"], cfg["block_length"]) == (
        SDAR["units"], SDAR["experts"], SDAR["expert"], SDAR["vocab"],
        SDAR["block"])
    return dict(cfg, page_size=SDAR["page"])


def _sdar_program(v5e, part, batch, length):
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import sdar_moe as m

    c, cfg = SDAR, _sdar_cfg()
    u = c["units"]

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ints = lambda *shape: of(shape, jnp.int32)  # noqa: E731
    x = of((batch, length, u), jnp.float32)
    if part == "head":
        fn = functools.partial(m._head_pick, eps=cfg["eps"],
                               mask_id=cfg["mask_token_id"],
                               threshold=cfg["confidence_threshold"])
        args, donate = (x, of((u,)), of((c["vocab"], u)),
                        ints(batch, length), ints(batch)), ()
    else:
        layer = {
            "ln1": of((u,)), "ln2": of((u,)),
            "q_norm": of((c["head_dim"],)), "k_norm": of((c["head_dim"],)),
            "q": of((c["heads"] * c["head_dim"], u)),
            "k": of((c["kv_heads"] * c["head_dim"], u)),
            "v": of((c["kv_heads"] * c["head_dim"], u)),
            "o": of((u, c["heads"] * c["head_dim"])),
            "router": of((c["experts"], u)),
            "router_bias": of((c["experts"],)),
            "gate_up": of((c["experts"], u, 2 * c["expert"])),
            "down": of((c["experts"], c["expert"], u))}
        arena = of((c["pages"], c["page"], c["kv_heads"] * c["head_dim"]))
        fn = functools.partial(m._layer_forward, cfg=cfg)
        args = (x, layer, arena, arena, ints(batch, length),
                ints(batch, c["table_w"]), ints(batch))
        donate = (2, 3)
    with execution_platform("tpu"):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("part,batch,length", [
    ("layer", 128, 4), ("layer", 16, 4), ("layer", 1, 4), ("head", 128, 4),
    ("layer", 1, 1024), ("layer", 16, 128)])
def test_serve_sdar_program_compiles(v5e, part, batch, length):
    """A block round of 128, 16 and 1 streams through the layer program
    and the head with the pick, the largest prefill of one prompt and the
    widest prefill batch the cell's bound allows: each compiles for the
    chip. The layer program updates its two page arenas in place (the
    outputs alias them: 0.47 GB a layer); a block step reads them through
    the paged GQA kernel under the signature the benchmark's readers look
    for (the block's 4 positions folded into the head group) and runs the
    two grouped matmuls; a prefill takes the gather under the block mask.
    The head holds its (batch, 4, vocab) float32 logits (0.31 GB at 128
    streams) and no second array of that size: the pick's reductions read
    them as they lie. Temporaries leave room beside 8.7 GB of weights and
    2.8 GB of pages."""
    compiled = _sdar_program(v5e, part, batch, length)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    c = SDAR
    print(part, batch, length, text.count("tpu_custom_call"),
          mem.temp_size_in_bytes / 1e9, mem.alias_size_in_bytes / 1e9,
          mem.output_size_in_bytes / 1e9)
    assert "s64[" not in text
    if part == "head":
        logits = batch * length * c["vocab"] * 4
        assert mem.output_size_in_bytes >= logits
        assert mem.temp_size_in_bytes < 0.25 * logits
        return
    aliased = 2 * c["pages"] * c["page"] * c["kv_heads"] * c["head_dim"] * 2
    assert mem.alias_size_in_bytes >= aliased
    walks = _paged_walks(compiled)
    if length == c["block"]:
        assert len(walks) == 1
        assert text.count("tpu_custom_call") == 3       # + gate/up, down
        # no copy of an arena: the kernel reads the layer's pages in place
        assert mem.temp_size_in_bytes < 0.15e9
    else:
        assert not walks and text.count("tpu_custom_call") == 2
        assert mem.temp_size_in_bytes < 2.0e9


# -- Ling-3.0-flash (ling3_flash_reason_closed_c256): a program a kind of layer ------------------

LING = dict(units=2560, heads=32, head_dim=128, conv=4, ffn=6144, expert=768,
            held=64, outputs=512, kv_rank=512, nope=128, rope=64, v=128,
            vocab=19648, pages=114689, page=16, table_w=448, slots=257)


def _ling_program(v5e, kind, moe, batch, length):
    """The Ling engine's layer program of ``kind`` at the cell's sizes
    (257 state slots; 256 streams x 7,168 tokens of ONE latent layer),
    compiled for the described chip."""
    import functools

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import ling_linear as m

    c = LING
    cfg = m.LingLinearModel(layer_kinds=())._decode_cfg
    assert (cfg["units"], cfg["num_heads"], cfg["head_dim"],
            cfg["kv_lora_rank"], cfg["n_routed"], cfg["held"]) == (
        c["units"], c["heads"], c["head_dim"], c["kv_rank"], c["outputs"],
        c["held"])
    u, hd = c["units"], c["heads"] * c["head_dim"]

    def of(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ints = lambda *shape: of(shape, jnp.int32)  # noqa: E731
    layer = {"in_norm": of((u,)), "post_norm": of((u,))}
    if moe:
        layer.update(
            moe={"router": of((c["outputs"], u)),
                 "router_bias": of((c["outputs"],)),
                 "gate_up": of((c["held"], u, 2 * c["expert"])),
                 "down": of((c["held"], c["expert"], u))},
            shared_gate_up=of((2 * c["expert"], u)),
            shared_down=of((u, c["expert"])))
    else:
        layer.update(ffn_gate_up=of((2 * c["ffn"], u)),
                     ffn_down=of((u, c["ffn"])))
    x = of((batch, length, u), jnp.float32)
    pos, lens = ints(batch, length), ints(batch)
    if kind == "kda":
        layer.update(qkv=of((3 * hd, u)), conv=of((3 * hd, c["conv"])),
                     f=of((hd, u)), dt_b=of((hd,)), a_log=of((c["heads"],)),
                     b=of((c["heads"], u)), g=of((hd, u)),
                     o_norm=of((c["head_dim"],)), o=of((u, hd)))
        fn = functools.partial(m._kda_layer, cfg=cfg, moe=moe)
        args = (x, layer,
                of((c["slots"], c["conv"] - 1, 3 * hd), jnp.float32),
                of((c["slots"], c["heads"], c["head_dim"], c["head_dim"]),
                   jnp.float32), pos, lens, ints(batch))
        donate = (2, 3)
    else:
        layer.update(q=of((c["heads"] * (c["nope"] + c["rope"]), u)),
                     kva=of((c["kv_rank"] + c["rope"], u)),
                     kvnorm=of((c["kv_rank"],)),
                     kvb=of((c["heads"] * (c["nope"] + c["v"]),
                             c["kv_rank"])),
                     gate=of((c["heads"], u)),
                     out=of((u, c["heads"] * c["v"])))
        fn = functools.partial(m._mla_layer, cfg=cfg, moe=moe)
        args = (x, layer, of((c["pages"], c["page"], 640)), pos,
                ints(batch, c["table_w"]), lens)
        donate = (2,)
    with execution_platform("tpu"):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("kind,moe,batch,length", [
    ("kda", True, 256, 1), ("kda", False, 256, 1), ("mla", True, 256, 1),
    ("kda", True, 1, 2048), ("mla", True, 1, 2048), ("kda", True, 32, 64)])
def test_serve_ling_program_compiles(v5e, monkeypatch, kind, moe, batch,
                                     length):
    """A decode round of 256 streams through the three kinds of layer
    program, a 2,048-token prefill chunk through the chunk form of the
    delta rule and through MLA over the cache, and the widest prefill
    batch the warm-up makes: each compiles for the chip inside the cell's
    memory (5.7 GB of weights, 3.46 GB of slots, 2.35 GB of pages held),
    updates its slot arrays (0.54 GB of states a layer) or its arena in
    place, and where it takes one token a stream runs the in-place
    state-update kernel or the latent paged kernel."""
    monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
    compiled = _ling_program(v5e, kind, moe, batch, length)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    c = LING
    if kind == "kda":
        aliased = c["slots"] * 4 * c["heads"] * c["head_dim"] * (
            c["head_dim"] + 3 * (c["conv"] - 1))
        assert ("kda_state_update" in text) == (length == 1)
    else:
        aliased = c["pages"] * c["page"] * 640 * 2
        assert ("mla_paged_decode" in text) == (length == 1)
    print(kind, moe, batch, length, text.count("tpu_custom_call"),
          mem.temp_size_in_bytes / 1e9, mem.alias_size_in_bytes / 1e9)
    assert mem.alias_size_in_bytes >= aliased
    # no copy of a slot array or of the arena, and a chunk's temporaries
    # leave room beside 11.4 GB held
    assert mem.temp_size_in_bytes < (0.25e9 if length == 1 else 2.0e9)
    assert "s64[" not in text
