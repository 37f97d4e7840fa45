"""Phi-4-mini-flash-reasoning on the chip against its plain reference, at
the published sizes and under the cell's shapes (a 6k+ prompt prefilled in
chunks of 2048 at the cell's page-table width, 33 state slots), before any
timing is believed.

    chiprun -- python3 tools/phi4flash_chip_check.py            # the chip
    JAX_PLATFORMS=cpu python3 tools/phi4flash_chip_check.py \
        --config tiny_phi4flash --prompt 45 --new 4 --chunk 16 --tail 8 \
        --page-size 8 --width 9 --slots 5

Five comparisons, on LOGITS or activations, never on tokens, each with a
control that has to FAIL. The reference makes ONE float32 pass over the
finished sequence, a layer at a time; 2 to 4 are made on the REFERENCE's
own layer input (cast to the served dtype), so differences do not pile up
across layers, on what the MIXER makes (the scan's output, the
attention's contribution, the gated memory), as a share of the
reference's largest value of it: the residual stream itself is bf16, and
its rounding (2^-9 of values ten times a layer's change) would drown
every control.

1. **End to end**: the prompt prefilled through the decode engine in
   chunks (scan state, convolution tail and ring carried from chunk to
   chunk, the cross-decoder on the last chunk's last token only) and
   ``--new`` tokens decoded through the caches; every step's logits
   against the reference's. Limit ``E2E_TOL`` of the largest reference
   logit, set between the program's reading and the lower precision's
   (PERF.md, PR 35). Controls: one more decode step after the
   stream's slot was zeroed (the recurrent state and the rings lost);
   and the same tokens through an engine in the nearest precision below
   the configuration's (``tools/phi4flash_correct_controls.py``'s
   ``lower_precision``: the residual stream an 8-bit float after every
   layer, the scan's state bfloat16).
2. **A Mamba layer's scan output** (layer 0), chunks then steps, against
   the reference's token-by-token recurrence. Control: the convolution
   tail dropped at every chunk boundary.
3. **A window layer's attention** (layer 1) at positions past the window
   across chunk boundaries, then steps through the ring. Control: a
   window one token short.
4. **The cross-decoder** given the reference's memory and the
   full-attention layer's keys and values in the pages: the first gated
   memory unit (control: fed the scan output of the Mamba layer BELOW
   the publishing one) and the first cross-attention through the page
   table (control: ``lam`` forced to 0, the second softmax dropped).
5. **Padding**: a prefill and a decode step with padding rows and a
   stream shorter than its bucket leave every other slot bit-equal.
   Control: the same padding rows pointed at a live slot.

Every limit is set from the readings on the chip, between pass and
control (PERF.md, PR 35). Exit code 0 only if 1 to 5 pass and every
control fails. Also prints what a prefill chunk at each offset and a
one-stream decode step took (host clock around a blocking call), the
Pallas kernels the op routing took, and the device's peak memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

E2E_TOL = 2.0 ** -4       # of max |reference logit|: between the program
#                           (0.02) and one precision lower (0.2)
MAMBA_TOL = 2.0 ** -5     # of max |reference scan output|
WINDOW_TOL = 2.0 ** -5    # of max |reference attention contribution|
CROSS_TOL = 2.0 ** -5     # of max |reference gated memory / attention|


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="phi4_mini_flash")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--prompt", type=int, default=6200)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--tail", type=int, default=64,
                    help="the length bucket of a prompt's short tail")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--width", type=int, default=1184,
                    help="page-table width (the cell's: 18944 tokens)")
    ap.add_argument("--slots", type=int, default=33)
    args = ap.parse_args()

    with open(args.config if args.config.endswith(".json") else os.path.join(
            ROOT, "benchmarks", "configs", args.config + ".json")) as f:
        config = json.load(f)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import phi4flash as model
    from mxnet_tpu.ops import diff_attention as diff_ops
    from mxnet_tpu.serving.kvcache import PagePool

    from benchmarks.builders import phi4flash as builder
    from benchmarks.references import phi4flash as reference

    t0 = time.perf_counter()

    def log(msg):
        print(f"[check +{time.perf_counter() - t0:6.1f}s] {msg}", flush=True)

    log(f"devices {jax.devices()}")
    telemetry.enable()      # which Pallas kernels the op routing took
    on_cpu = jax.devices()[0].platform == "cpu"
    net, ctx = builder.build_net(config, args.seed,
                                 ctx=mx.cpu() if on_cpu else None)
    weights = builder.export_weights({"net": net})
    log("weights made")
    ps, width, chunk = args.page_size, args.width, args.chunk
    p, n_new = args.prompt, args.new
    short = 2 * -(-(args.tail + 4) // ps)       # two short streams' pages
    pool = PagePool(width + 1 + short, ps, n_state_slots=args.slots)
    engine = net.decode_engine(pool)
    cfg = engine.cfg
    window = cfg["window"]
    rs = np.random.RandomState(args.seed % (2 ** 31))
    prompt = rs.randint(1, config["vocab_size"], (p,)).astype(np.int32)
    owner = object()
    pages = pool.alloc(owner, p + n_new + 1)
    slot = pool.state_slots.alloc(owner)
    table = np.zeros((1, width), np.int32)
    table[0, :len(pages)] = pages
    slots = np.array([slot], np.int32)

    def chunks(total):
        """(offset, real tokens, length bucket) of a prompt's chunks."""
        for off in range(0, total, chunk):
            n = min(chunk, total - off)
            yield off, n, chunk if n > args.tail else args.tail

    # -- 1. the program: prefill in chunks, then decode ---------------------
    def through(engine, forced=None):
        """The prompt in chunks and ``n_new`` decode steps through
        ``engine`` (its own greedy tokens, or the ``forced`` ones): the
        logits of every step, the tokens fed, the chunks' and steps'
        seconds and the last step's token."""
        times, step_s = [], []
        for off, n, bucket in chunks(p):
            part = np.zeros((1, bucket), np.int32)
            part[0, :n] = prompt[off:off + n]
            t = time.perf_counter()
            nxt = engine.prefill(part, np.array([off + n], np.int32), table,
                                 np.array([off], np.int32) if off else None,
                                 slots, np.array([off + n == p]))
            jax.block_until_ready(engine.arenas)
            times.append((off, bucket, time.perf_counter() - t))
        got = [engine.last_logits()[0]]
        seq = list(prompt)
        for i in range(n_new):
            seq.append(int(nxt[0]) if forced is None else int(forced[i]))
            t = time.perf_counter()
            nxt = engine.decode_step(seq[-1:], np.array([len(seq)], np.int32),
                                     table, slots)
            step_s.append(time.perf_counter() - t)
            got.append(engine.last_logits()[0])
        return got, seq, times, step_s, nxt

    got, seq, times, step_s, nxt = through(engine)
    # the control: the stream's slot zeroed, one more step
    kept = {k: list(v) for k, v in engine.slot_arrays.items()}
    for arrays in engine.slot_arrays.values():
        for i, a in enumerate(arrays):
            arrays[i] = a.at[slot].set(0)
    seq.append(int(nxt[0]))
    engine.decode_step(nxt, np.array([len(seq)], np.int32), table, slots)
    got.append(engine.last_logits()[0])
    engine.slot_arrays.update(kept)
    # the control in precision: the same tokens through an engine one
    # precision lower, on the same pages and slot
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from phi4flash_correct_controls import planted

    with planted("lower_precision", net):
        low = np.asarray(through(net.decode_engine(pool), seq[p:-1])[0],
                         np.float32)
    seq = np.asarray(seq, np.int32)
    got = np.asarray(got, np.float32)
    log("prefill chunks (offset, bucket, ms; the first of a bucket "
        "compiles): "
        + ", ".join(f"{o}:{b}:{s * 1e3:.0f}" for o, b, s in times))
    log("decode steps of one stream, ms: "
        + ", ".join(f"{s * 1e3:.1f}" for s in step_s))

    # -- the reference, a layer at a time -----------------------------------
    n_layers = config["num_hidden_layers"]
    mid = n_layers // 2
    consts = dict(reference.constants(config))
    total = len(seq)
    keep = {0, 1, mid - 2, mid + 2, mid + 3}
    with jax.default_matmul_precision("highest"):
        io = []
        x, memory, k_ref, v_ref = reference._run(weights, config, seq,
                                                 collect=io, keep=keep)
        ref = np.asarray(reference._head(float(config["layer_norm_eps"]))(
            x, jnp.arange(p - 1, p - 1 + len(got), dtype=jnp.int32),
            weights["norm_g"], weights["norm_b"], weights["embed"]),
            np.float32)
        # the scan output of the Mamba layer below the publishing one
        lw = weights["layers"][mid - 2]
        wrong_memory = reference.mamba(
            reference._layer_norm(io[mid - 2]["x"], lw["ln1_g"], lw["ln1_b"],
                                  consts["eps"]), lw, consts)[1]
    del x
    each = np.abs(got - ref).max(axis=1) / np.abs(ref).max()
    e2e, lost = float(each[:-1].max()), float(each[-1])
    lower = float(np.abs(low - ref[:-1]).max() / np.abs(ref).max())
    ok = {"e2e": e2e <= E2E_TOL < min(lost, lower)}
    log("1. per position (the prefill's, then each decode step's): "
        + ", ".join(f"{e:.4f}" for e in each[:-1]))
    log(f"1. end to end ({len(times)} chunks, {n_new} decode steps): worst "
        f"|logit - ref| = {e2e:.5f} of max |ref| {np.abs(ref).max():.3f} "
        f"(limit {E2E_TOL:.5f}); with the slot zeroed {lost:.5f}; one "
        f"precision lower (8-bit residual stream, bfloat16 scan state) "
        f"{lower:.5f} -> {'pass' if ok['e2e'] else 'FAIL'}")

    # -- 2 and 3: one mixer alone, chunks then steps, on private slots ------
    dtype = jnp.dtype(config["dtype"])
    _, layers, _, _ = engine._params
    dev = ctx.jax_device()
    eps = consts["eps"]
    d_in, n_state, width_kv = (cfg["d_inner"], cfg["d_state"],
                               cfg["num_kv_heads"] * cfg["head_dim"])

    def mixer_alone(run, x_ref, make_state, before_chunk=None):
        """``run`` over the sequence's chunks then its last ``n_new``
        tokens a step at a time, on the reference's layer input; what
        the mixer makes at every real position, float32."""
        state, out = make_state(), None
        one = jnp.array([1], jnp.int32)
        for off, n, bucket in list(chunks(p)) + [
                (p + i, 1, 1) for i in range(total - p)]:
            rows = jnp.zeros((1, bucket, x_ref.shape[1]), dtype).at[
                0, :n].set(x_ref[off:off + n].astype(dtype))
            pos = off + jnp.arange(bucket, dtype=jnp.int32)[None]
            if before_chunk is not None and bucket > 1:
                state = before_chunk(state)
            y, *state = run(rows, *state, pos,
                            jnp.array([off + n], jnp.int32), one)
            if out is None:
                out = np.zeros((total, y.shape[-1]), np.float32)
            out[off:off + n] = np.asarray(y[0, :n], np.float32)
        return out

    def share(out, want, rows=slice(None)):
        """Worst |out - ref| over ``rows`` as a share of the reference's
        largest value there."""
        want = np.asarray(want, np.float32)[rows]
        return float(np.abs(out[rows] - want).max() / np.abs(want).max())

    def mamba_state():
        return [jnp.zeros((2, cfg["d_conv"] - 1, d_in), dtype, device=dev),
                jnp.zeros((2, n_state, d_in), jnp.float32, device=dev)]

    @jax.jit
    def mamba_run(x, tails, states, pos, lengths, slots_):
        """The scan's output ``y`` of layer 0 (before the gate)."""
        _, y, tails, states = model._mamba_layer(
            x, layers[0], tails, states, pos, lengths, slots_, cfg)
        return y, tails, states

    lw = weights["layers"][0]
    with jax.default_matmul_precision("highest"):
        y_ref = reference.mamba(reference._layer_norm(
            io[0]["x"], lw["ln1_g"], lw["ln1_b"], eps), lw, consts)[1][:total]
    with execution_platform(dev.platform):
        sound = share(mixer_alone(mamba_run, io[0]["x"], mamba_state), y_ref)
        dropped = share(mixer_alone(
            mamba_run, io[0]["x"], mamba_state,
            before_chunk=lambda s: [jnp.zeros_like(s[0]), s[1]]), y_ref)
    ok["mamba"] = sound <= MAMBA_TOL < dropped
    log(f"2. a Mamba layer's scan output, chunks then steps: {sound:.5f} "
        f"of its largest value (limit {MAMBA_TOL:.5f}); the tail dropped "
        f"at chunk boundaries {dropped:.5f} -> "
        f"{'pass' if ok['mamba'] else 'FAIL'}")

    lw = weights["layers"][1]
    with jax.default_matmul_precision("highest"):
        att_ref = reference.diff_attention(
            *reference._qkv(reference._layer_norm(
                io[1]["x"], lw["ln1_g"], lw["ln1_b"], eps), lw, consts),
            lw, jnp.float32(1), consts, window)[:total]
    past = slice(min(window, total - 1), None)

    def window_layer(w):
        c = dict(cfg, window=w)

        def ring_state():
            return [jnp.zeros((2, w, width_kv), dtype, device=dev)
                    for _ in range(2)]

        @jax.jit
        def run(x, ring_k, ring_v, pos, lengths, slots_):
            return model._window_attend(x, layers[1], ring_k, ring_v, pos,
                                        lengths, slots_, c)

        with execution_platform(dev.platform):
            return share(mixer_alone(run, io[1]["x"], ring_state), att_ref,
                         past)

    sound, shorter = window_layer(window), window_layer(window - 1)
    ok["window"] = sound <= WINDOW_TOL < shorter
    log(f"3. a window layer's attention past position {window}, across "
        f"chunk boundaries and through the ring: {sound:.5f} of its "
        f"largest value (limit {WINDOW_TOL:.5f}); a window of "
        f"{window - 1}: {shorter:.5f} -> "
        f"{'pass' if ok['window'] else 'FAIL'}")
    del y_ref, att_ref

    # -- 4. the cross-decoder on the reference's memory and K/V -------------
    n_q = 8
    at = np.linspace(window, total - 1, n_q).astype(np.int32)
    pg, pc = layers[mid + 2], layers[mid + 3]
    lg, lc = weights["layers"][mid + 2], weights["layers"][mid + 3]
    page = jnp.asarray(table[0][np.arange(total) // ps])
    offs = jnp.arange(total) % ps
    k_arena = model._scatter_rows(engine.arenas[0],
                                  k_ref[:total].astype(dtype), page, offs)
    v_arena = model._scatter_rows(engine.arenas[1],
                                  v_ref[:total].astype(dtype), page, offs)
    tables = jnp.asarray(np.repeat(table, n_q, axis=0))
    with jax.default_matmul_precision("highest"):
        h = reference._layer_norm(io[mid + 2]["x"][at], lg["ln1_g"],
                                  lg["ln1_b"], eps)
        gmu_ref = reference._mm(
            memory[at] * jax.nn.silu(reference._mm(h, lg["gmu_in"])),
            lg["gmu_out"])
        h = reference._layer_norm(io[mid + 3]["x"], lc["ln1_g"], lc["ln1_b"],
                                  eps)
        cross_ref = reference.diff_attention(
            reference._mm(h, lc["q"], lc["q_b"]), k_ref, v_ref, lc,
            jnp.float32(mid + 3), consts)[at]

    def gmu(memory_rows):
        from mxnet_tpu.ops.ssm import gated_memory_unit

        x = io[mid + 2]["x"][at][:, None].astype(dtype)
        with execution_platform(dev.platform):
            y = jax.jit(lambda x, m: gated_memory_unit(
                model._ln(x, pg, "ln1", eps), m, pg["gmu_in"],
                pg["gmu_out"]))(x, memory_rows[at][:, None].astype(dtype))
        return share(np.asarray(y[:, 0], np.float32), gmu_ref)

    def cross(drop_second=False):
        sound_combine = diff_ops.diff_attention_combine

        def first_only(paired, *a, **kw):
            return sound_combine(paired.at[..., 1, :].set(0.0), *a, **kw)

        x = io[mid + 3]["x"][at][:, None].astype(dtype)
        if drop_second:
            diff_ops.diff_attention_combine = first_only
        try:
            with execution_platform(dev.platform):
                y = jax.jit(lambda x, ka, va: model._shared_attend(
                    model._ln(x, pc, "ln1", eps) @ pc["q"].T + pc["q_b"],
                    pc, ka, va, tables, jnp.asarray(at + 1), cfg))(
                        x, k_arena, v_arena)
        finally:
            diff_ops.diff_attention_combine = sound_combine
        return share(np.asarray(y[:, 0], np.float32), cross_ref)

    sound_g, wrong_m = gmu(memory), gmu(wrong_memory)
    sound_c, no_lam = cross(), cross(drop_second=True)
    ok["cross"] = (sound_g <= CROSS_TOL < wrong_m
                   and sound_c <= CROSS_TOL < no_lam)
    log(f"4. the cross-decoder at {n_q} positions on the reference's "
        f"memory and K/V: the first gated memory unit {sound_g:.5f} of its "
        f"largest value (limit {CROSS_TOL:.5f}), on the memory of layer "
        f"{mid - 2}: {wrong_m:.5f}; the first cross-attention "
        f"{sound_c:.5f}, with lam forced to 0: {no_lam:.5f} -> "
        f"{'pass' if ok['cross'] else 'FAIL'}")
    del k_arena, v_arena, io, memory, wrong_memory, k_ref, v_ref

    # -- 5. padding rows and a short stream leave other slots alone ---------
    def snapshot():
        return {k: [np.asarray(a) for a in v]
                for k, v in engine.slot_arrays.items()}

    def changed(before, after, spare):
        """Slots other than ``spare`` whose bytes differ."""
        hit = set()
        for key in before:
            for a, b in zip(before[key], after[key]):
                diff = (a != b).reshape(a.shape[0], -1).any(axis=1)
                hit |= set(np.nonzero(diff)[0].tolist())
        return sorted(hit - set(spare))

    def padded_dispatches(pad_slot):
        """A (bucket 4+, tail) prefill of two short streams (one shorter
        than the bucket) and padding rows, then a decode step of the
        two."""
        owners = [object(), object()]
        lens = np.array([args.tail, args.tail - 3], np.int32)
        b = 8 if args.slots > 8 else 4
        tbl = np.zeros((b, width), np.int32)
        sl = np.full((b,), pad_slot, np.int32)
        toks = np.zeros((b, args.tail), np.int32)
        for i, o in enumerate(owners):
            got_pages = pool.alloc(o, int(lens[i]) + 2)
            tbl[i, :len(got_pages)] = got_pages
            sl[i] = pool.state_slots.alloc(o)
            toks[i, :lens[i]] = rs.randint(1, config["vocab_size"],
                                           (lens[i],))
        full_lens = np.zeros((b,), np.int32)
        full_lens[:2] = lens
        nxt_ = engine.prefill(toks, full_lens, tbl, None, sl,
                              np.arange(b) < 2)
        full_lens[:2] += 1
        step = np.zeros((b,), np.int32)
        step[:2] = nxt_[:2]
        engine.decode_step(step, full_lens, tbl, sl)
        for o in owners:
            pool.free(o)
            pool.state_slots.free(o)
        return sl[:2].tolist()

    before = snapshot()
    mine = padded_dispatches(0)
    touched = changed(before, snapshot(), [0] + mine)
    before = snapshot()
    mine = padded_dispatches(slot)      # the control: padding on a live slot
    touched_control = changed(before, snapshot(), [0] + mine)
    ok["padding"] = not touched and touched_control == [slot]
    log(f"5. padded prefill + decode step: other slots changed {touched} "
        f"(want none); with the padding rows on live slot {slot}: "
        f"{touched_control} -> {'pass' if ok['padding'] else 'FAIL'}")

    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
        f"peak_bytes_reserved {stats.get('peak_bytes_reserved')}, "
        f"bytes_limit {stats.get('bytes_limit')}")
    from benchmarks.lib import harness

    routed = {labels["kernel"]: n for labels, n in
              harness.program_counters().get("mxnet_pallas_dispatch_total",
                                             ())}
    log(f"Pallas kernels routed (sites per traced program): {routed}")
    verdict = all(ok.values())
    print(json.dumps(dict(
        ok, end_to_end=e2e, slot_zeroed=lost, lower_precision=lower,
        pallas_sites=routed,
        chunk_ms=[[o, b, round(s * 1e3, 1)] for o, b, s in times],
        decode_step_ms=[round(s * 1e3, 2) for s in step_s],
        verdict=bool(verdict))))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
