"""Plain reference: SDAR-MoE's forward and its generation loop in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no
kernel, no cache, no batching, one sequence at a time.

One layer, ``x`` (L, hidden), positions ``t``, block length ``Bk``; every
projection without bias; ``RMS`` = RMSNorm with gain, eps ``rms_norm_eps``:

    h  = RMS(x)
    q  = RMS_128(reshape(h Wq, (L, heads, 128)))      per head, gain g_q
    k  = RMS_128(reshape(h Wk, (L, kv_heads, 128)))   gain g_k
    v  = reshape(h Wv, (L, kv_heads, 128))
    q, k = rope(q, t), rope(k, t)       rotate-half over the whole head
    s[h, i, j] = q[i, h] . k[j, h // rep] / sqrt(128), allowed where
                 j // Bk <= i // Bk (block-causal: both ways inside a block)
    x  = x + concat_h(softmax_j(s) v) Wo
    u  = RMS(x)
    p  = softmax(u Wr) over the experts, float32
    E  = the num_experts_per_tok largest p;  w_e = p_e / sum_{e in E} p_e
    x  = x + sum_{e in E} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e

then a final RMSNorm and the untied head. A logit at position ``i`` is
for the token AT position ``i`` (a masked position's input is the mask
token's embedding), not for the next one.

**Generation** (:func:`generate`, the published ``block_diffusion_generate``
with ``remasking_strategy`` ``low_confidence_dynamic``, greedy): the
prompt's whole blocks are the context; what is left of it opens the first
generated block already unmasked. A block starts as its known tokens
followed by mask tokens. A denoising step runs the model on everything so
far with the block in its CURRENT state and gives each position ``x0 =
argmax logits`` and ``c = softmax(logits)[x0]``; of the positions still
masked, those with ``c > confidence_threshold`` are unmasked if they are
at least the step's quota (``block_length // denoising_steps``, one more
in the first ``block_length % denoising_steps`` steps), otherwise the
quota of largest ``c`` (a tie to the lower position). A block with
nothing masked is final; later blocks see its final tokens (the served
path's commit forward writes exactly those keys and values).

**The replay** (:func:`replay_stats`) recomputes, for one finished
request, the logits of EVERY denoising step of every block in ONE
forward: the sequence is the final tokens followed by one copy of each
block in each state it went through, at the block's own positions; a
copy sees the final tokens of the blocks before its own and itself,
nothing else (:func:`forward` takes that as a ``group`` id a row: a row
sees group 0 rows of earlier blocks, and the rows of its own group in its
own block). That is the same mathematics as one forward a step, which
``tests/test_sdar_moe.py`` holds it to.

Departures from the published model and loop, listed under ``assumed`` in
the config file: the per-head q / k norms and rotate-half rotary are
Qwen3's (the ``config.json`` does not state them); ``block_length`` 4,
``denoising_steps`` 4, ``confidence_threshold`` 0.9 and ``mask_token_id``
151669 are the family's convention; decoding is greedy (the card samples
at temperature 1.0); the mask token is never a candidate of the argmax
(the published loop would leave such a position masked for good); a step
unmasks masked positions only (the published ``topk`` over ``-inf`` could
name an unmasked one when fewer are masked than the quota).

The weights arrive in the dtype they are served in and are cast up one
matrix (one expert) at a time.
"""
from __future__ import annotations

import functools
import math

import numpy as np

# queries of one slice of the attention, experts' rows of one slice of
# the head: what bounds the float32 temporaries beside the served weights
_Q_CHUNK = 512
_HEAD_CHUNK = 256


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * g.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: (L, H, D) at ``positions`` (L,); rotate-half convention."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(x, lw, positions, group, *, heads, kv_heads, head_dim, theta,
               eps, block):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    l = x.shape[0]
    h = _rms(x, lw["ln1"], eps)
    q = _rms((h @ lw["q"].astype(f32).T).reshape(l, heads, head_dim),
             lw["q_norm"], eps)
    k = _rms((h @ lw["k"].astype(f32).T).reshape(l, kv_heads, head_dim),
             lw["k_norm"], eps)
    v = (h @ lw["v"].astype(f32).T).reshape(l, kv_heads, head_dim)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blk = positions // block

    def rows(args):
        qc, blk_q, group_q = args
        s = jnp.einsum("qhd,khd->hqk", qc, k) / math.sqrt(head_dim)
        seen = ((group[None, :] == 0) & (blk[None, :] < blk_q[:, None])) | (
            (group[None, :] == group_q[:, None])
            & (blk[None, :] == blk_q[:, None]))
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    n = -(-l // _Q_CHUNK)
    pad = n * _Q_CHUNK - l

    def cut(a, fill):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=fill).reshape(
                           (n, _Q_CHUNK) + a.shape[1:])

    # a padded query row sees nothing it shares a group with but itself
    att = jax.lax.map(rows, (cut(q, 0.0), cut(blk, -2), cut(group, -2)))
    att = att.reshape(n * _Q_CHUNK, heads * head_dim)[:l]
    return x + att @ lw["o"].astype(f32).T


def expert_sum(u, lw, top_k):
    """The whole routed expert layer on its (normed) input ``u``, dense:
    every expert over every row, weighted by the renormalised pick (0
    where not picked)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    p = jax.nn.softmax(u @ lw["router"].astype(f32).T, axis=-1)
    top, idx = jax.lax.top_k(p, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    n_experts = lw["router"].shape[0]
    hidden = lw["down"].shape[1]

    def one(e, acc):
        w = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        gu = u @ lw["gate_up"][e].astype(f32)
        y = (jax.nn.silu(gu[:, :hidden]) * gu[:, hidden:]) \
            @ lw["down"][e].astype(f32)
        return acc + w[:, None] * y

    return jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(u))


def _experts(x, lw, *, top_k, eps):
    return x + expert_sum(_rms(x, lw["ln2"], eps), lw, top_k)


def _layer(x, lw, positions, group, *, top_k, eps, **attn):
    return _experts(_attention(x, lw, positions, group, eps=eps, **attn),
                    lw, top_k=top_k, eps=eps)


def _head_stats(x, norm_w, head_w, rows, chosen, *, eps, mask_id):
    """For the rows ``rows`` of ``x``: the largest logit (the mask id
    left out), its id, ``logsumexp``, the largest magnitude, and the
    logit of ``chosen[i]``; each (len(rows),)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    h = _rms(x[rows], norm_w, eps)
    n = -(-h.shape[0] // _HEAD_CHUNK)
    pad = n * _HEAD_CHUNK - h.shape[0]
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, _HEAD_CHUNK, -1)
    chosen = jnp.pad(chosen, (0, pad)).reshape(n, _HEAD_CHUNK)
    w = head_w.astype(f32)

    def chunk(args):
        hc, cc = args
        logits = hc @ w.T
        logits = jnp.where(jnp.arange(logits.shape[1]) == mask_id,
                           -jnp.inf, logits)
        return (jnp.max(logits, axis=-1),
                jnp.argmax(logits, axis=-1).astype(jnp.int32),
                jax.nn.logsumexp(logits, axis=-1),
                jnp.max(jnp.where(jnp.isfinite(logits), jnp.abs(logits),
                                  0.0), axis=-1),
                jnp.take_along_axis(logits, cc[:, None], axis=1)[:, 0])

    return tuple(a.reshape(-1)[:rows.shape[0]]
                 for a in jax.lax.map(chunk, (h, chosen)))


@functools.lru_cache(maxsize=None)
def _jitted(heads, kv_heads, head_dim, theta, eps, block, top_k, mask_id):
    import jax

    layer = jax.jit(functools.partial(
        _layer, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        theta=theta, eps=eps, block=block, top_k=top_k))
    stats = jax.jit(functools.partial(_head_stats, eps=eps, mask_id=mask_id))

    def logits(x, norm_w, head_w, rows):
        import jax.numpy as jnp

        return _rms(x[rows], norm_w, eps) @ head_w.astype(jnp.float32).T

    return layer, stats, jax.jit(logits)


def _programs(config: dict):
    h = config["num_attention_heads"]
    return _jitted(
        h, config["num_key_value_heads"],
        config.get("head_dim") or config["hidden_size"] // h,
        float(config["rope_theta"]), float(config["rms_norm_eps"]),
        int(config["block_length"]), int(config["num_experts_per_tok"]),
        int(config["mask_token_id"]))


def _stream(weights, config, tokens, positions, group):
    """The residual stream (L, hidden) after the last layer."""
    import jax.numpy as jnp

    layer = _programs(config)[0]
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32) \
        if positions is None else jnp.asarray(positions, jnp.int32)
    group = jnp.zeros_like(positions) if group is None \
        else jnp.asarray(group, jnp.int32)
    x = weights["embed"][tokens].astype(jnp.float32)
    for lw in weights["layers"]:
        x = layer(x, lw, positions, group)
    return x


def forward(weights: dict, config: dict, tokens, rows, positions=None,
            group=None):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` at
    the rows ``rows``: row i scores the token AT i. ``positions`` (L,):
    the rows' positions (default 0..L-1); ``group`` (L,): 0 for the
    sequence proper (block-causal among itself), g > 0 for a copy of a
    block in an earlier state, which sees the group-0 rows of earlier
    blocks and its own group's rows (default: all 0). A row of a group
    below 0 is padding: nothing real sees it."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _stream(weights, config, tokens, positions, group)
        return _programs(config)[2](x, weights["norm"], weights["head"],
                                    jnp.asarray(rows, jnp.int32))


def quota_of(config: dict, step: int) -> int:
    """The published ``get_num_transfer_tokens``."""
    base, rem = divmod(int(config["block_length"]),
                       int(config["denoising_steps"]))
    return base + (step < rem)


def pick(config: dict, state, x0, logc, step: int):
    """Which masked positions of ``state`` a denoising step unmasks,
    given every position's candidate ``x0`` and log-confidence
    ``logc``: the new state."""
    mask_id = config["mask_token_id"]
    state = np.array(state, np.int64)
    masked = state == mask_id
    conf = np.where(masked, np.asarray(logc, np.float64), -np.inf)
    high = conf > math.log(config["confidence_threshold"])
    quota = quota_of(config, step)
    if high.sum() >= quota:
        take = high
    else:
        # the quota of largest confidence, a tie to the lower position
        order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
        take = np.zeros_like(masked)
        take[order[:quota]] = True
    state[take] = np.asarray(x0)[take]
    return state


def generate(weights: dict, config: dict, prompt, max_new: int,
             pad_to: int = 0, commit: bool = True):
    """The published loop, one full forward a denoising step: returns
    (tokens (max_new,), steps (max_new,)): the answer and the denoising
    step of its block at which each token was unmasked. ``pad_to``: the
    one length every forward is padded to (fewer compiled shapes).
    ``commit`` False is a planted fault for the tests: later blocks see a
    finished block as it was BEFORE its last denoising step (what a
    cache holds when the commit forward is skipped)."""
    import jax

    bk, mask_id = config["block_length"], config["mask_token_id"]
    prompt = np.asarray(prompt, np.int64)
    whole = bk * (prompt.size // bk)
    total = whole + bk * -(-(prompt.size - whole + max_new) // bk)
    width = max(pad_to, total)
    seq = np.full((width,), mask_id, np.int64)
    seq[:prompt.size] = prompt
    group = np.where(np.arange(width) < total, 0, -1)
    seen = seq.copy()                   # what later blocks see of a block
    steps = np.zeros((width,), np.int64)
    stats = _programs(config)[1]
    for base in range(whole, total, bk):
        rows = np.arange(base, base + bk)
        for step in range(config["denoising_steps"] + 1):
            state = seq[rows]
            if not (state == mask_id).any():
                break
            seen[rows] = state          # the state this forward ran on
            ctx = np.where(np.arange(width) < base, seen, seq)
            with jax.default_matmul_precision("highest"):
                x = _stream(weights, config, ctx, None, group)
                top, x0, lse, _, _ = (np.asarray(a) for a in stats(
                    x, weights["norm"], weights["head"], rows,
                    np.zeros((bk,), np.int32)))
            new = pick(config, state, x0, top - lse, step)
            steps[rows[(state == mask_id) & (new != mask_id)]] = step
            seq[rows] = new
        if commit:
            seen[rows] = seq[rows]
    out = slice(prompt.size, prompt.size + max_new)
    return seq[out].astype(np.int32), steps[out].astype(np.int32)


def replay_layout(config: dict, prompt, tokens, steps):
    """The ONE sequence that replays a finished request: ``(seq,
    positions, group, states)``; ``states`` lists, per block and
    denoising step the served path ran, ``(first row of the copy, step,
    the block's state at that step, the positions it unmasked)``. A last
    block that the budget cut short is left out: the positions past the
    budget were computed and not returned, so its states are not known."""
    bk, mask_id = config["block_length"], config["mask_token_id"]
    prompt = np.asarray(prompt, np.int64)
    tokens, steps = np.asarray(tokens, np.int64), np.asarray(steps, np.int64)
    whole = bk * (prompt.size // bk)
    full = np.concatenate([prompt, tokens])
    n_full = bk * (full.size // bk)     # whole blocks known to the end
    known = np.concatenate([np.full(prompt.size, -1), steps])
    seq, pos, group, states = [full[:n_full]], [np.arange(n_full)], \
        [np.zeros(n_full, np.int64)], []
    at, g = n_full, 0
    for base in range(whole, n_full, bk):
        final, when = full[base:base + bk], known[base:base + bk]
        for step in range(int(when.max()) + 1):
            g += 1
            state = np.where(when < step, final, mask_id)
            states.append((at, step, state, np.flatnonzero(when == step)))
            seq.append(state)
            pos.append(np.arange(base, base + bk))
            group.append(np.full(bk, g))
            at += bk
    return (np.concatenate(seq), np.concatenate(pos), np.concatenate(group),
            states)


def replay_stats(weights: dict, config: dict, prompt, tokens, steps,
                 pad_to: int = 0):
    """Every denoising step of a finished request in one forward: per
    state of :func:`replay_layout` a dict of (block_length,) arrays
    ``top`` / ``x0`` / ``lse`` / ``absmax`` / ``chosen`` (the logit of
    the token the served path has at that position in the block's final
    state) with the state's ``step``, ``state`` and ``unmasked``."""
    import jax
    import jax.numpy as jnp

    bk = config["block_length"]
    seq, pos, group, states = replay_layout(config, prompt, tokens, steps)
    if not states:
        return []
    n_full = int(np.sum(group == 0))
    width = max(pad_to, seq.size)
    pad = width - seq.size
    seq = np.pad(seq, (0, pad))
    pos = np.pad(pos, (0, pad))
    group = np.pad(group, (0, pad), constant_values=-1)
    rows = np.concatenate([np.arange(at, at + bk) for at, *_ in states])
    # the token each copy's position ends as
    chosen = seq[:n_full][pos[rows]]
    with jax.default_matmul_precision("highest"):
        x = _stream(weights, config, seq, pos, group)
        top, x0, lse, absmax, got = (np.asarray(a) for a in _programs(
            config)[1](x, weights["norm"], weights["head"],
                       jnp.asarray(rows, jnp.int32),
                       jnp.asarray(chosen, jnp.int32)))
    out = []
    for i, (_, step, state, unmasked) in enumerate(states):
        s = slice(i * bk, (i + 1) * bk)
        out.append({"step": step, "state": state, "unmasked": unmasked,
                    "top": top[s], "x0": x0[s], "lse": lse[s],
                    "absmax": absmax[s], "chosen": got[s]})
    return out


def check_request(weights: dict, config: dict, prompt, tokens, steps,
                  tol: float, pad_to: int = 0) -> dict:
    """A served answer against the replay. For every denoising step of
    every whole block: (a) each token it unmasked has a reference logit
    within ``tol`` x the position's largest logit magnitude of the
    reference's top logit there; (b) each position it unmasked has a
    reference log-confidence within the same band of what the rule asks:
    the threshold's, or the quota-th best of the positions still masked.
    Returns the worst of each, in tolerances (1.0 is the limit), and how
    many steps were compared."""
    worst_a = worst_b = 0.0
    n = 0
    thr = math.log(config["confidence_threshold"])
    mask_id = config["mask_token_id"]
    for st in replay_stats(weights, config, prompt, tokens, steps, pad_to):
        if not np.isfinite(st["top"]).all():
            return {"finite": False, "steps": n, "token_gap": math.inf,
                    "position_gap": math.inf}
        band = st["absmax"] * tol
        logc = st["top"] - st["lse"]
        masked = np.flatnonzero(st["state"] == mask_id)
        quota = min(quota_of(config, st["step"]), masked.size)
        need = min(thr, np.sort(logc[masked])[::-1][quota - 1])
        for i in st["unmasked"]:
            worst_a = max(worst_a, float(
                (st["top"][i] - st["chosen"][i]) / band[i]))
            worst_b = max(worst_b, float((need - logc[i]) / band[i]))
        n += 1
    return {"finite": True, "steps": n, "token_gap": worst_a,
            "position_gap": worst_b}
