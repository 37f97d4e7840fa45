"""SDAR-MoE on the chip at the benchmark's widths against its float32
reference (``benchmarks/references/sdar_moe.py``), through the cell's own
page size: numbers, not tokens.

1. ``logits``: prompts (one with a tail that opens the first block, one
   shorter than a block) prefilled, then block decoding through the cache
   in a batch bucket WITH padding rows (every denoising step and every
   commit, the streams at different steps of their blocks): the engine's
   logits of every forward against the reference's replay of the same
   states in ONE forward, as the largest difference over the reference
   logits' spread (``LIMIT``); and the same with the causal mask planted
   inside the block, which has to read worse by far.
2. ``kernel``: the paged GQA kernel with a block's 4 queries folded into
   the head group (32 x 4 = 128 query rows over 4 key-value heads) against
   ``ops/attention.py::_paged_reference`` under the block mask, on a round's
   shapes: 128 streams of ragged lengths, padding rows among them.
3. ``experts``: the routed expert layer alone
   (``ops/contrib.py::moe_routed_experts``: megablox grouped matmuls, all
   experts held, one pass) on a round's 512 rows against the reference's
   dense sum over the picked experts.

    chiprun -- python3 tools/sdar_chip_check.py
    JAX_PLATFORMS=cpu python3 tools/sdar_chip_check.py \\
        --config tiny_sdar_moe --prompts 13,3 --new 9 --page-size 8 \\
        --width 4 --streams 5 --rows 24

Exit code 0 only if every comparison is inside its limit and the control
outside. Prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the largest logit difference over the reference logits' spread
# (standard deviation): bfloat16 operands through six layers whose routed
# experts are ALL held, so that a token's 8th and 9th pick may swap
# visibly (the dense cells read 0.02-0.03; the causal mask planted inside
# the block read 5.7)
LIMIT = 0.2
# the kernel's and the expert layer's output: largest difference over the
# output's spread, bfloat16 operands and probabilities (the folded-query
# kernel read 0.048, Falcon-H1's attention alone 0.042; the experts 0.015)
PART_LIMIT = 0.08


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


def serve_by_hand(engine, pool, prompts, max_new, width=None, commit=True,
                  seen=None):
    """The published loop over the engine's own calls for ``prompts``
    together, one row each of a ``width``-row bucket (the rest padding):
    per stream the answer, the step each token was unmasked at and the
    forwards run. ``seen``: a list a stream that gets (state before,
    logits) of every forward. ``commit`` False: no commit forward runs (a
    planted fault)."""
    import numpy as np

    bk, mask = engine.block_length, engine.mask_id
    n = len(prompts)
    width = width or n
    wholes = [bk * (p.size // bk) for p in prompts]
    totals = [w + bk * -(-(p.size - w + max_new) // bk)
              for p, w in zip(prompts, wholes)]
    owners = [object() for _ in prompts]
    table = np.zeros((width, pool.pages_for(max(totals))), np.int32)
    for i, (o, t) in enumerate(zip(owners, totals)):
        pages = pool.alloc(o, t)
        table[i, :len(pages)] = pages
    for i, (p, w) in enumerate(zip(prompts, wholes)):
        if w:       # a prompt shorter than a block is never prefilled
            engine.prefill(p[None, :w], np.array([w], np.int32),
                           table[i:i + 1])
    base = list(wholes)
    block = np.full((n, bk), mask, np.int32)
    known = [p.size - w for p, w in zip(prompts, wholes)]
    for i, (p, w) in enumerate(zip(prompts, wholes)):
        block[i, :known[i]] = p[w:]
    when = np.full((n, bk), -1)
    step = [0] * n
    outs, steps, forwards = ([[] for _ in prompts] for _ in range(3))
    live = set(range(n))
    while live:
        tokens = np.zeros((width, bk), np.int32)
        lengths = np.zeros((width,), np.int32)
        quota = np.zeros((width,), np.int32)
        for i in live:
            tokens[i], lengths[i] = block[i], base[i] + bk
            if (block[i] == mask).any():
                quota[i] = engine.transfer[step[i]]
        new = engine.decode_block(tokens, lengths, table, quota)
        logits = engine.last_logits() if seen is not None else None
        for i in sorted(live):
            forwards[i].append(int(quota[i]))
            if seen is not None:
                seen[i].append((block[i].copy(), logits[i]))
            masked = block[i] == mask
            if masked.any():
                when[i][masked & (new[i] != mask)] = step[i]
                block[i], step[i] = new[i], step[i] + 1
                if (block[i] == mask).any() or commit:
                    continue
            # the block is final (and committed, or never will be)
            outs[i] += block[i, known[i]:].tolist()
            steps[i] += when[i, known[i]:].tolist()
            base[i] += bk
            block[i], when[i], step[i], known[i] = mask, -1, 0, 0
            if base[i] >= totals[i]:
                live.discard(i)
    for o in owners:
        pool.free(o)
    return ([np.asarray(o[:max_new], np.int32) for o in outs],
            [np.asarray(s[:max_new], np.int32) for s in steps],
            [len(f) for f in forwards])


def run_logits(net, weights, config, reference, args, prompts, np):
    """Prefill + block decoding through one engine; the worst relative
    logit difference over every forward of every stream."""
    from mxnet_tpu.serving.kvcache import PagePool

    bk = config["block_length"]
    most = max(p.size for p in prompts) + args.new + bk
    pool = PagePool(len(prompts) * -(-most // args.page_size) + 2,
                    args.page_size)
    engine = net.decode_engine(pool)
    seen = [[] for _ in prompts]
    outs, steps, forwards = serve_by_hand(engine, pool, prompts, args.new,
                                          width=args.width, seen=seen)
    del engine
    worst, spread = 0.0, 0.0
    for prompt, out, rows in zip(prompts, outs, seen):
        # the final tokens, then a copy of each block in every state the
        # engine ran it in (denoising steps and commits), at the block's
        # own positions: one forward
        whole = bk * (prompt.size // bk)
        full = np.concatenate([prompt, out])
        n_full = bk * (full.size // bk)
        seq, pos, group = [full[:n_full]], [np.arange(n_full)], \
            [np.zeros(n_full, np.int64)]
        at, base, kept = n_full, whole, []
        for g, (state, logits) in enumerate(rows, 1):
            if base + bk > n_full:
                break                   # a last block cut by the budget
            seq.append(state)
            pos.append(np.arange(base, base + bk))
            group.append(np.full(bk, g))
            kept.append((at, logits))
            at += bk
            if not (state == config["mask_token_id"]).any():
                base += bk              # that was the block's commit
        seq, pos, group = (np.concatenate(a) for a in (seq, pos, group))
        pad = -(-seq.size // args.pad) * args.pad - seq.size
        ref = np.asarray(reference.forward(
            weights, config, np.pad(seq, (0, pad)),
            np.concatenate([np.arange(a, a + bk) for a, _ in kept]),
            np.pad(pos, (0, pad)),
            np.pad(group, (0, pad), constant_values=-1)), np.float32)
        got = np.concatenate([l for _, l in kept]).astype(np.float32)
        worst = max(worst, float(np.abs(got - ref).max() / ref.std()))
        spread = float(ref.std())
    return {"rel": worst, "spread": spread, "forwards": forwards,
            "distinct_tokens": [int(np.unique(o).size) for o in outs]}


def run_kernel(config, args, np):
    """The folded-query kernel call against the gather under the block
    mask, on a round's shapes."""
    import jax.numpy as jnp

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops import attention

    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, bk, ps = config["head_dim"], config["block_length"], args.page_size
    b = args.streams
    rs = np.random.RandomState(7)
    dtype = jnp.dtype(config["dtype"])
    lengths = bk * rs.randint(1, 6 * ps // bk, (b,)).astype(np.int32)
    lengths[::5] = 0                     # padding rows
    w = -(-int(lengths.max()) // ps)
    pages = b * w + 1
    table = np.zeros((b, w), np.int32)
    order = rs.permutation(pages - 1) + 1
    for i in range(b):
        n = -(-int(lengths[i]) // ps)
        table[i, :n] = order[i * w:i * w + n]
    k = jnp.asarray(rs.randn(pages * ps, kv, d), dtype)
    v = jnp.asarray(rs.randn(pages * ps, kv, d), dtype)
    q = jnp.asarray(rs.randn(b, h, bk, d), dtype)
    pos = lengths[:, None] - bk + np.arange(bk)[None, :]
    platform = next(iter(q.devices())).platform
    with execution_platform(platform):
        out = attention.paged_attention(
            q, k, v, jnp.asarray(table), jnp.asarray(lengths),
            jnp.asarray(pos), page_size=ps, block=bk)
    ref = attention._paged_reference(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), jnp.asarray(table), jnp.asarray(lengths),
        jnp.asarray(pos), ps, 1.0 / d ** 0.5, bk)
    real = lengths > 0
    diff = np.abs(np.asarray(out, np.float32) - np.asarray(ref))[real]
    return {"rel": float(diff.max() / np.asarray(ref)[real].std()),
            "streams": int(real.sum()), "platform": platform}


def run_experts(weights, config, reference, args, np):
    """The routed expert layer of layer 0 alone on a round's rows."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops.contrib import moe_routed_experts

    lw = weights["layers"][0]
    rs = np.random.RandomState(9)
    rows, top_k = args.rows, config["num_experts_per_tok"]
    u = jnp.asarray(rs.randn(rows, config["hidden_size"]), lw["q"].dtype)
    platform = next(iter(u.devices())).platform
    with execution_platform(platform):
        out, counts = jax.jit(lambda u: moe_routed_experts(
            u, lw["router"], lw["router_bias"], lw["gate_up"], lw["down"],
            first_held=0, n_routed=config["num_experts"], top_k=top_k,
            score="softmax", renormalize=True,
            rows_per_pass=-(-rows * top_k // 128) * 128))(u)
    with jax.default_matmul_precision("highest"):
        ref = reference.expert_sum(u.astype(jnp.float32), lw, top_k)
    ref = np.asarray(ref)
    # a row whose 8th and 9th router scores swap between bfloat16 and
    # float32 inputs differs by a whole expert: count such rows apart
    row_err = np.abs(np.asarray(out, np.float32) - ref).max(axis=1) \
        / ref.std()
    return {"rel_median_row": float(np.median(row_err)),
            "rel_p99_row": float(np.percentile(row_err, 99)),
            "rows_over_limit": int((row_err > PART_LIMIT).sum()),
            "rows": rows, "counts": np.asarray(counts).tolist()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sdar_30b_a3b_l6")
    ap.add_argument("--seed", type=int, default=2147483697)
    ap.add_argument("--prompts", default="301,128,3",
                    help="the streams' prompt lengths")
    ap.add_argument("--new", type=int, default=22)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--width", type=int, default=16,
                    help="rows of the block step's batch bucket")
    ap.add_argument("--streams", type=int, default=128)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--pad", type=int, default=256)
    args = ap.parse_args()
    t0 = time.perf_counter()

    def log(msg):
        print(f"[check +{time.perf_counter() - t0:6.1f}s] {msg}", flush=True)

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.builders import sdar_moe as builder
    from benchmarks.references import sdar_moe as reference
    from mxnet_tpu.ops import attention

    config = _load("configs", args.config)
    for k, v in config.get("env", {}).items():
        os.environ[k] = str(v)
    on_cpu = jax.devices()[0].platform == "cpu"
    net, _ = builder.build_net(config, args.seed,
                               ctx=mx.cpu() if on_cpu else None)
    weights = builder.export_weights({"net": net})
    log("weights made")
    rs = np.random.RandomState(args.seed % (2 ** 31))
    prompts = [rs.randint(1, config["vocab_size"] - 1, (int(n),)).astype(
        np.int32) for n in args.prompts.split(",")]
    got = {"logits": run_logits(net, weights, config, reference, args,
                                prompts, np)}
    log(f"logits: {got['logits']}")
    # the control: the causal mask inside the block
    sound = attention.paged_attention
    attention.paged_attention = lambda *a, block=1, **kw: sound(*a, **kw)
    net._decode_cfg["planted"] = "causal_in_block"
    try:
        got["causal_in_block"] = run_logits(net, weights, config, reference,
                                            args, prompts, np)
    finally:
        attention.paged_attention = sound
        del net._decode_cfg["planted"]
    log(f"causal_in_block: {got['causal_in_block']}")
    got["kernel"] = run_kernel(config, args, np)
    log(f"kernel: {got['kernel']}")
    got["experts"] = run_experts(weights, config, reference, args, np)
    log(f"experts: {got['experts']}")
    verdict = (got["logits"]["rel"] < LIMIT
               and got["causal_in_block"]["rel"] > 4 * LIMIT
               and got["kernel"]["rel"] < PART_LIMIT
               and got["experts"]["rel_median_row"] < PART_LIMIT
               and got["experts"]["rows_over_limit"] <= args.rows // 50)
    print(json.dumps(dict(got, limit=LIMIT, part_limit=PART_LIMIT,
                          device=jax.devices()[0].device_kind,
                          verdict=bool(verdict))))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
