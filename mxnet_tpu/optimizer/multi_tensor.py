"""Horizontally-fused multi-tensor optimizer sweeps.

Reference: MXNet's ``multi_sgd_update`` / ``multi_mp_sgd_mom_update`` /
``mp_lamb_update_*`` family (``src/operator/optimizer_op.cc``) — one
kernel launch updating a whole parameter list instead of one per
parameter. The round-5 roofline (PERF_HISTORY.md) put the Adam elementwise sweep
in the top-5 HBM buckets precisely because it ran as O(params) separate
dispatches; this module is the TPU-native answer:

* **bucket planning** — all (param, grad, optimizer-state) leaves of like
  dtype/precision are grouped into buckets (:func:`plan_buckets`), each
  bucket packed into coalesced flat buffers;
* **packed sweep** (:func:`packed_apply`) — the whole bucket's update is
  ONE elementwise pass over the flat buffers: a Pallas VMEM sweep on TPU
  (``pallas_kernels/fused_optimizer.py``, behind the same
  ``MXNET_PALLAS_FUSED`` + platform gates as the layer kernels) with a
  pure-``lax`` fallback that is the CPU oracle. LAMB's two-phase
  trust-ratio runs its per-tensor norms as a single fused
  ``multi_sum_sq``-style pass over the packed buffer
  (:func:`segment_sumsq`);
* **bit-identity with the per-param path** — every formula transcribes
  the single-tensor op math (``ops/optimizer_op.py``) exactly: the same
  f32 casts, the same scalar-broadcast multiply order, per-param norms
  reduced over the ORIGINAL param shape. A fused step is bit-identical
  to the per-param reference, which is the test gate
  (``tests/test_optimizer.py::TestFusedSweep*``).

Two consumers, both where a sweep replaces O(params) DISPATCHES (the
jitted ``parallel.TrainStep`` keeps the per-parameter loop: inside one
executable there is nothing to collapse, and packing the parameter set
costs more than the whole update):

* ``gluon/trainer.py`` — :func:`eager_fused_update` collapses the eager
  ``step()`` optimizer phase from O(params) dispatches to one jitted
  sweep per dtype bucket, cached through the compilation service
  (``SiteCache("optimizer_sweep")``), journaled to the signature
  manifest and replayed by ``compiler.warm_start`` with no provider
  (:func:`warm_sweep_spec` rebuilds the sweep from the spec alone);
* ``ops/optimizer_op.py`` — the ``multi_sgd_*`` / ``multi_lamb_*`` ops
  are re-expressed on the same packed layout.

Opt out with ``MXNET_FUSED_OPTIMIZER=0`` (read by the eager callers at
each step; no traced body depends on it).
"""
from __future__ import annotations

import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as _np

__all__ = [
    "fused_sweep_enabled", "family_of", "family_static", "state_roles",
    "collect_scalars", "plan_buckets", "packed_apply", "segment_sumsq",
    "plan_eager", "apply_eager_plan", "eager_fused_update",
    "warm_sweep_spec", "sweep_cache", "Bucket",
]

# the families the packed sweep reproduces bit-exactly; keyed by EXACT
# class (a subclass overriding update() must keep the per-param path)
_FAMILIES = ("sgd", "adam", "adamw", "lamb")


def fused_sweep_enabled() -> bool:
    """The routing knob: ``MXNET_FUSED_OPTIMIZER=0`` opts out of the
    fused sweep everywhere (Trainer, warm replay). Default on.
    Read per call, outside any trace, so tests can toggle it."""
    return os.environ.get("MXNET_FUSED_OPTIMIZER", "1") != "0"


def family_of(optimizer) -> Optional[str]:
    """The packed-sweep family for this optimizer, or None when it must
    stay on the per-param path (unknown class, or a SUBCLASS of a known
    one — an overridden update() would silently not run)."""
    from .optimizer import SGD, Adam, AdamW, LAMB

    t = type(optimizer)
    if t is SGD:
        return "sgd"
    if t is Adam:
        return "adam"
    if t is AdamW:
        return "adamw"
    if t is LAMB:
        return "lamb"
    return None


def family_static(optimizer, family: str) -> tuple:
    """The optimizer hyperparameters baked into the traced sweep body,
    as a sorted item tuple (part of the cache signature)."""
    clip = optimizer.clip_gradient
    if family == "sgd":
        items = {"momentum": float(optimizer.momentum)}
    elif family in ("adam", "adamw"):
        items = {"beta1": float(optimizer.beta1),
                 "beta2": float(optimizer.beta2),
                 "epsilon": float(optimizer.epsilon)}
    elif family == "lamb":
        items = {"beta1": float(optimizer.beta1),
                 "beta2": float(optimizer.beta2),
                 "epsilon": float(optimizer.epsilon),
                 "bias_correction": bool(optimizer.bias_correction),
                 "lower_bound": optimizer.lower_bound,
                 "upper_bound": optimizer.upper_bound,
                 # eager mode matches the reference's constant-folded
                 # reciprocal-multiply; dynamic mode its true division
                 # (see collect_scalars)
                 "bc_recip": optimizer._dyn is None}
    else:
        raise ValueError(f"unknown sweep family {family!r}")
    items["clip_gradient"] = clip
    return tuple(sorted(items.items()))


def state_roles(family: str, static: dict) -> Tuple[str, ...]:
    """Names of the family's optimizer-state leaves, in the flatten order
    ``create_state`` produces (the fp32 master of a multi-precision param
    is handled separately as the ``w32`` role)."""
    if family == "sgd":
        return ("mom",) if static["momentum"] != 0.0 else ()
    return ("mean", "var")


def collect_scalars(optimizer, family: str, ks: Sequence[int]) -> Dict[str, list]:
    """Per-param runtime scalars for the sweep, computed with EXACTLY the
    per-family ``Optimizer.update`` scalar prep (same expressions, same
    evaluation order) so the packed multiply reproduces the per-param
    result bit-for-bit. Values are python floats on the eager path and
    traced 0-d scalars under ``optimizer.dynamic`` — both feed
    :func:`packed_apply` unchanged.
    """
    lrs, wds, bc1s, bc2s = [], [], [], []
    for k in ks:
        lr = optimizer._get_lr(k)
        wd = optimizer._get_wd(k)
        if family == "adam":
            t = optimizer._t(k)
            # reference: Adam.update folds bias correction into lr
            lr = lr * ((1.0 - optimizer.beta2 ** t) ** 0.5
                       / (1.0 - optimizer.beta1 ** t))
        elif family == "adamw":
            if optimizer.correct_bias:
                t = optimizer._t(k)
                lr = lr * ((1.0 - optimizer.beta2 ** t) ** 0.5
                           / (1.0 - optimizer.beta1 ** t))
        elif family == "lamb" and optimizer.bias_correction:
            t = optimizer._t(k)
            if optimizer._dyn is None:
                # eager reference: t is BAKED into the phase1 op, and
                # XLA constant-folds `m / (1 - beta**t)` into a
                # reciprocal MULTIPLY (f32 reciprocal of the f32
                # constant). Ship that exact f32 inverse so the packed
                # multiply reproduces the reference bit-for-bit — and
                # the sweep compiles ONCE while the reference op
                # retraces per t
                bc1s.append(float(_np.float32(1.0)
                                  / _np.float32(1.0 - optimizer.beta1 ** t)))
                bc2s.append(float(_np.float32(1.0)
                                  / _np.float32(1.0 - optimizer.beta2 ** t)))
            else:
                # traced reference: bc is a runtime scalar -> true
                # division on both paths
                bc1s.append(1.0 - optimizer.beta1 ** t)
                bc2s.append(1.0 - optimizer.beta2 ** t)
        lrs.append(lr)
        wds.append(wd)
    out = {"lr": lrs, "wd": wds}
    if family == "lamb" and optimizer.bias_correction:
        out["bc1"] = bc1s
        out["bc2"] = bc2s
    return out


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------


class Bucket(NamedTuple):
    """One dtype/precision bucket of the parameter set.

    ``members``: positions into the caller's entry list; ``shapes``:
    per-member param shapes; ``wdtype``/``gdtype``: weight/grad dtypes;
    ``mp``: True when the update runs on an fp32 master copy (the
    ``w32`` role) with the low-precision weight downcast at the end.
    """

    members: Tuple[int, ...]
    shapes: Tuple[tuple, ...]
    wdtype: str
    gdtype: str
    mp: bool


def _bucket_cap_bytes() -> int:
    mb = float(os.environ.get("MXNET_OPT_BUCKET_MB", "0"))
    return int(mb * (1 << 20)) if mb > 0 else 0


def plan_buckets(entries, multi_precision: bool) -> List[Bucket]:
    """Group entries into dtype buckets.

    ``entries``: sequence of ``(shape, wdtype, gdtype)``. One bucket per
    (wdtype, gdtype) pair by default — the "one kernel per dtype bucket"
    contract — optionally size-capped via ``MXNET_OPT_BUCKET_MB`` so
    giant models split into fixed total-size classes that the compile
    cache can reuse across param-set growth.
    """
    cap = _bucket_cap_bytes()
    groups: Dict[tuple, list] = {}
    for pos, (shape, wdtype, gdtype) in enumerate(entries):
        groups.setdefault((str(wdtype), str(gdtype)), []).append(
            (pos, tuple(int(s) for s in shape)))
    buckets = []
    for (wdtype, gdtype), mem in groups.items():
        mp = multi_precision and wdtype in ("float16", "bfloat16")
        itemsize = _np.dtype(wdtype).itemsize
        cur, cur_bytes = [], 0
        for pos, shape in mem:
            n_bytes = int(_np.prod(shape or (1,))) * itemsize
            if cap and cur and cur_bytes + n_bytes > cap:
                buckets.append(Bucket(tuple(p for p, _ in cur),
                                      tuple(s for _, s in cur),
                                      wdtype, gdtype, mp))
                cur, cur_bytes = [], 0
            cur.append((pos, shape))
            cur_bytes += n_bytes
        if cur:
            buckets.append(Bucket(tuple(p for p, _ in cur),
                                  tuple(s for _, s in cur),
                                  wdtype, gdtype, mp))
    return buckets


# ---------------------------------------------------------------------------
# the packed sweep
# ---------------------------------------------------------------------------


def _sizes_offsets(shapes):
    sizes = [int(_np.prod(s)) if s else 1 for s in shapes]
    offsets = _np.concatenate([[0], _np.cumsum(sizes)]).tolist()
    return sizes, offsets


def segment_sumsq(flat, shapes, offsets, dtype=None):
    """Per-member sum of squares over the packed buffer — the fused
    ``multi_sum_sq`` norm pass (the LAMB/LARS trust-ratio building
    block). Each segment is reshaped back to its ORIGINAL param shape
    before the reduction so the result is bit-identical to the
    per-param ``jnp.sum(jnp.square(w))``; the optimization barrier
    stops XLA folding the reshape into the reduce (a folded reduce
    accumulates in flat order, which differs from the native-shape
    order at the ULP level)."""
    import jax
    import jax.numpy as jnp

    outs = []
    for shape, off, off2 in zip(shapes, offsets[:-1], offsets[1:]):
        seg = jax.lax.optimization_barrier(
            flat[off:off2].reshape(shape if shape else ()))
        outs.append(jnp.sum(jnp.square(seg)))
    return jnp.stack(outs) if dtype is None \
        else jnp.stack(outs).astype(dtype)


def _pack(arrs):
    """Members -> one flat buffer, in member order. The SINGLE packing
    convention — offsets from :func:`_sizes_offsets` index into exactly
    this concatenation, and every packer (packed_apply, _LambSweep)
    must share it or the per-member slices silently misalign."""
    import jax.numpy as jnp

    if len(arrs) == 1:
        return jnp.reshape(arrs[0], (-1,))
    return jnp.concatenate([jnp.reshape(a, (-1,)) for a in arrs])


def _as_vec(values):
    """(n,) f32 per-member vector from python floats or traced scalars."""
    import jax.numpy as jnp

    if all(isinstance(v, (int, float)) for v in values):
        return _np.asarray(values, _np.float32)
    return jnp.stack([jnp.asarray(v, jnp.float32) for v in values])


def _expand(vec, sizes):
    """Per-member scalars -> per-element vector over the packed layout.

    The sizes are static, so this is a concatenation of per-member
    broadcasts (streaming writes). ``jnp.repeat`` with an array of
    repeats lowers to a cumsum + a ``total``-element gather, which
    cost 0.9 s per vector at 109.5 M elements on a v5e (PERF.md)."""
    import jax.numpy as jnp

    vec = jnp.asarray(vec)
    return jnp.concatenate([jnp.broadcast_to(vec[i], (n,))
                            for i, n in enumerate(sizes)])


# -- elementwise stage formulas ---------------------------------------------
# Each operates on FLAT arrays (any shape — the Pallas kernel calls them
# on (block, 128) tiles, the lax fallback on the 1-D buffer) and
# transcribes the single-tensor op math exactly. ``env`` carries the
# packed tensors + per-element scalar vectors + 0-d scalars.


def _rescale_clip(env, static):
    import jax.numpy as jnp

    g = env["g"].astype(jnp.float32) * env["rescale"]
    clip = static["clip_gradient"]
    if clip is not None and clip >= 0:
        g = jnp.clip(g, -clip, clip)
    return g


def _sgd_elem(env, static):
    import jax.numpy as jnp

    g = _rescale_clip(env, static)
    g = g + env["wd"] * env["w"].astype(jnp.float32)
    if "mom" not in env:
        new_w = env["w"].astype(jnp.float32) - env["lr"] * g
        return {"w": new_w}
    # momentum may be 0.0 here: sgd_mom_update with momentum=0 still
    # rewrites the momentum buffer to -lr*g (the op contract)
    momentum = static["momentum"]
    new_mom = momentum * env["mom"].astype(jnp.float32) - env["lr"] * g
    new_w = env["w"].astype(jnp.float32) + new_mom
    return {"w": new_w, "mom": new_mom}


def _adam_elem(env, static):
    import jax.numpy as jnp

    b1, b2, eps = static["beta1"], static["beta2"], static["epsilon"]
    g = _rescale_clip(env, static)
    g = g + env["wd"] * env["w"].astype(jnp.float32)
    new_mean = b1 * env["mean"].astype(jnp.float32) + (1 - b1) * g
    new_var = b2 * env["var"].astype(jnp.float32) \
        + (1 - b2) * jnp.square(g)
    new_w = env["w"].astype(jnp.float32) \
        - env["lr"] * new_mean / (jnp.sqrt(new_var) + eps)
    return {"w": new_w, "mean": new_mean, "var": new_var}


def _adamw_elem(env, static):
    import jax.numpy as jnp

    b1, b2, eps = static["beta1"], static["beta2"], static["epsilon"]
    g = _rescale_clip(env, static)
    new_mean = b1 * env["mean"] + (1 - b1) * g
    new_var = b2 * env["var"] + (1 - b2) * jnp.square(g)
    w32 = env["w"].astype(jnp.float32)
    new_w = w32 - 1.0 * (env["lr"] * new_mean / (jnp.sqrt(new_var) + eps)
                         + env["wd"] * env["lr"] * w32)
    # per-param AMP overflow guard (reference adamw.cc): `ok` arrives as
    # a per-element 0/1 vector reduced per member OUTSIDE the kernel
    ok = env["ok"] > 0
    new_w = jnp.where(ok, new_w, w32)
    new_mean = jnp.where(ok, new_mean, env["mean"])
    new_var = jnp.where(ok, new_var, env["var"])
    return {"w": new_w, "mean": new_mean, "var": new_var}


def _lamb_phase1_elem(env, static):
    import jax.numpy as jnp

    b1, b2, eps = static["beta1"], static["beta2"], static["epsilon"]
    g = _rescale_clip(env, static)
    new_mean = b1 * env["mean"] + (1 - b1) * g
    new_var = b2 * env["var"] + (1 - b2) * jnp.square(g)
    m, v = new_mean, new_var
    if static["bias_correction"]:
        if static.get("bc_recip"):
            # bc1/bc2 carry f32 INVERSES (see collect_scalars)
            m = m * env["bc1"]
            v = v * env["bc2"]
        else:
            m = m / env["bc1"]
            v = v / env["bc2"]
    upd = m / (jnp.sqrt(v) + eps) + env["wd"] * env["w"].astype(jnp.float32)
    return {"upd": upd, "mean": new_mean, "var": new_var}


def _lamb_phase2_elem(env, static):
    import jax.numpy as jnp

    new_w = env["w"].astype(jnp.float32) - env["lr_ratio"] * env["upd"]
    return {"w": new_w}


def _kernel_routed(platform) -> bool:
    from ..pallas_kernels import fused_optimizer as fopt

    return fopt.fused_opt_supported(platform)


def _run_elementwise(fn, static, flats, vec_el, scalars, out_specs,
                     platform, interpret):
    """One elementwise stage: the Pallas sweep kernel when routed (TPU +
    ``MXNET_PALLAS_FUSED``, or ``interpret`` for the CPU oracle tests),
    else the identical jnp math on the flat buffers."""
    from ..pallas_kernels import fused_optimizer as fopt

    if interpret or fopt.fused_opt_supported(platform):
        from .. import telemetry

        telemetry.record_pallas_dispatch("fused_opt_sweep")
        return fopt.sweep_pallas(fn, static, flats, vec_el, scalars,
                                 out_specs, interpret=interpret)
    env = dict(flats)
    env.update(vec_el)
    env.update(scalars)
    outs = fn(env, static)
    import jax.numpy as jnp

    return {name: outs[name].astype(dtype)
            for name, dtype in out_specs}


def packed_apply(family, static, shapes, ins, vecs, rescale,
                 low_dtype=None, platform=None, interpret=False):
    """Apply one fused sweep over one bucket.

    ``ins``: role -> list of per-member arrays. Roles: ``w`` (the update
    target — the fp32 master in a multi-precision bucket, the weight
    itself otherwise), ``g``, and the family's state roles. ``vecs``:
    name -> per-member scalars (floats or traced 0-d). ``rescale``:
    the grad rescale scalar (float, np, or traced). ``low_dtype``: the
    low-precision weight dtype of a multi-precision bucket — adds a
    ``w_low`` output holding the downcast weights.

    Returns role -> list of updated per-member arrays (original shapes).
    """
    import jax.numpy as jnp

    static = dict(static)
    sizes, offsets = _sizes_offsets(shapes)
    if platform is None:
        from ..base import current_execution_platform

        platform = current_execution_platform(
            ins["w"][0] if ins["w"] else None)

    flats = {role: _pack(arrs) for role, arrs in ins.items()}
    vec_el = {name: _expand(_as_vec(v), sizes)
              for name, v in vecs.items()}
    scalars = {"rescale": rescale if isinstance(rescale, (int, float))
               else jnp.asarray(rescale, jnp.float32)}

    wdt = flats["w"].dtype
    if family == "sgd":
        out_specs = [("w", wdt)]
        if "mom" in flats:
            out_specs.append(("mom", flats["mom"].dtype))
        new = _run_elementwise(_sgd_elem, static, flats, vec_el, scalars,
                              out_specs, platform, interpret)
    elif family == "adam":
        out_specs = [("w", wdt), ("mean", flats["mean"].dtype),
                     ("var", flats["var"].dtype)]
        new = _run_elementwise(_adam_elem, static, flats, vec_el, scalars,
                              out_specs, platform, interpret)
    elif family == "adamw":
        # the per-param overflow scan (isfinite over the rescaled+clipped
        # grad) is a per-member reduction — computed on the packed buffer
        # segment-wise, then broadcast back as a 0/1 vector
        g32 = flats["g"].astype(jnp.float32) * scalars["rescale"]
        clip = static["clip_gradient"]
        if clip is not None and clip >= 0:
            g32 = jnp.clip(g32, -clip, clip)
        oks = [jnp.isfinite(
                   g32[off:off2].reshape(shape if shape else ())).all()
               for shape, off, off2 in zip(shapes, offsets[:-1],
                                           offsets[1:])]
        vec_el["ok"] = _expand(
            jnp.stack(oks).astype(jnp.float32), sizes)
        out_specs = [("w", wdt), ("mean", flats["mean"].dtype),
                     ("var", flats["var"].dtype)]
        new = _run_elementwise(_adamw_elem, static, flats, vec_el,
                              scalars, out_specs, platform, interpret)
    elif family == "lamb":
        import jax

        # phase1 never reads lr (it enters later as the per-member
        # lr*ratio) — don't stream an unused (L,) operand through the
        # kernel on the HBM-bound pass
        p1_vec = {k: v for k, v in vec_el.items() if k != "lr"}
        p1 = _run_elementwise(
            _lamb_phase1_elem, static, flats, p1_vec, scalars,
            [("upd", jnp.float32), ("mean", flats["mean"].dtype),
             ("var", flats["var"].dtype)], platform, interpret)
        # materialization boundary mirroring the reference's op edge
        # (phase1 is ONE op there): without it XLA fuses phase1 into the
        # norm/phase2 consumers and contracts the chain differently than
        # the op-at-a-time reference (ULP drift breaks bit-identity).
        # ONE joint barrier — separate barriers would let XLA duplicate
        # the phase1 chain per consumer, re-opening the drift
        keys_ = sorted(p1)
        vals = jax.lax.optimization_barrier(tuple(p1[k] for k in keys_))
        p1 = dict(zip(keys_, vals))
        # trust-ratio norm pass: one fused multi_sum_sq-style sweep per
        # buffer, per-member reductions over the ORIGINAL shapes. The
        # norms are op outputs in the reference (weight.norm()), so they
        # get the same materialization boundary
        r1 = jax.lax.optimization_barrier(
            jnp.sqrt(segment_sumsq(flats["w"], shapes, offsets)))
        r2 = jax.lax.optimization_barrier(
            jnp.sqrt(segment_sumsq(p1["upd"], shapes, offsets)))
        lo, hi = static["lower_bound"], static["upper_bound"]
        if lo is not None and lo >= 0:
            r1 = jnp.maximum(r1, lo)
        if hi is not None and hi >= 0:
            r1 = jnp.minimum(r1, hi)
        ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
        # materialize the per-member multiplier before it broadcasts into
        # the phase2 loop (same boundary class as the norms above)
        lr_ratio = jax.lax.optimization_barrier(
            _as_vec(vecs["lr"]) * ratio)
        p2_vec = {"lr_ratio": _expand(lr_ratio, sizes)}
        new = _run_elementwise(
            _lamb_phase2_elem, static,
            {"w": flats["w"], "upd": p1["upd"]}, p2_vec, {},
            [("w", wdt)], platform, interpret)
        new["mean"], new["var"] = p1["mean"], p1["var"]
    else:
        raise ValueError(f"unknown sweep family {family!r}")

    out: Dict[str, list] = {}
    for role, flat in new.items():
        out[role] = [flat[off:off2].reshape(shape if shape else ())
                     for shape, off, off2 in zip(shapes, offsets[:-1],
                                                 offsets[1:])]
    if low_dtype is not None:
        out["w_low"] = [w.astype(low_dtype) for w in out["w"]]
    return out


# ---------------------------------------------------------------------------
# eager consumer: Trainer.step's optimizer phase
# ---------------------------------------------------------------------------

_SWEEP_SITE = "optimizer_sweep"


def sweep_cache():
    """The process-global compile cache for eager fused sweeps (shared by
    every Trainer and by warm-start replay)."""
    from ..compiler import service as _csvc

    return _csvc.shared_cache(_SWEEP_SITE)


def _sweep_key(family, static, bucket, state_dtypes, vec_names, n,
               platform):
    from ..compiler import signature

    return signature(
        _SWEEP_SITE, (family, bucket.wdtype, bucket.gdtype, bucket.mp),
        avals=tuple(bucket.shapes) + (tuple(state_dtypes), n),
        attrs=tuple(static), platform=platform,
        extra=(tuple(vec_names),))


class _LambSweep:
    """Eager LAMB bucket sweep as THREE jitted dispatches — the
    reference's own kernel granularity (``lamb_update_phase1`` /
    ``multi_sum_sq`` norms / ``lamb_update_phase2``).

    One fused program would be one dispatch, but XLA may recompute a
    value shared by two in-program consumers with different FMA
    contraction (measured on XLA:CPU: the trust-ratio reduce fused into
    the phase2 loop re-accumulates per member), so bit-identity with
    the op-at-a-time reference REQUIRES real program boundaries at the
    reference's op edges. Elementwise-only families stay at one
    dispatch; LAMB's reduce forces the same three launches MXNet's
    fused LAMB makes.
    """

    n_dispatches = 3

    def __init__(self, static_items, shapes, wdtype, mp, vec_names):
        import jax
        import jax.numpy as jnp

        static = dict(static_items)
        self._vec_names = tuple(vec_names)
        self._mp = mp
        self._n = n = len(shapes)
        sizes, offsets = _sizes_offsets(shapes)
        has_bc = static["bias_correction"]

        def phase1(ws, gs, ms, vs, vecs, rescale):
            # outputs stay FLAT: slicing the state outputs per member
            # HERE would let XLA recompute the shared moment chain per
            # output buffer with different contraction (measured —
            # `upd` drifts 1 ULP); the per-member views are taken in
            # the norms program, where these are materialized inputs
            env = {"w": _pack(ws), "g": _pack(gs), "mean": _pack(ms),
                   "var": _pack(vs), "rescale": rescale}
            for name in ("wd",) + (("bc1", "bc2") if has_bc else ()):
                env[name] = _expand(vecs[name], sizes)
            p1 = _lamb_phase1_elem(env, static)
            return p1["upd"], p1["mean"], p1["var"]

        def norms(ws, upd, fmean, fvar):
            # the fused multi_sum_sq pass: per-member reductions over
            # the ORIGINAL shapes (bit-identical to weight.norm());
            # state slicing rides along — pure views of inputs
            fw = _pack(ws)
            means = [fmean[o:o2].reshape(s) for s, o, o2
                     in zip(shapes, offsets[:-1], offsets[1:])]
            vars_ = [fvar[o:o2].reshape(s) for s, o, o2
                     in zip(shapes, offsets[:-1], offsets[1:])]
            return (jnp.sqrt(segment_sumsq(fw, shapes, offsets)),
                    jnp.sqrt(segment_sumsq(upd, shapes, offsets)),
                    means, vars_)

        lo, hi = static["lower_bound"], static["upper_bound"]

        def phase2(ws, upd, r1, r2, lr):
            if lo is not None and lo >= 0:
                r1 = jnp.maximum(r1, lo)
            if hi is not None and hi >= 0:
                r1 = jnp.minimum(r1, hi)
            ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
            new_w, new_low = [], []
            for j, (s, o, o2) in enumerate(zip(shapes, offsets[:-1],
                                               offsets[1:])):
                w32 = (ws[j].astype(jnp.float32)
                       - lr[j] * ratio[j] * upd[o:o2].reshape(s))
                if mp:
                    new_w.append(w32)
                    new_low.append(w32.astype(wdtype))
                else:
                    new_w.append(w32.astype(ws[j].dtype))
            return new_w, new_low

        self._phase1 = jax.jit(phase1)
        self._norms = jax.jit(norms)
        self._phase2 = jax.jit(phase2)

    def __call__(self, *args):
        n, mp = self._n, self._mp
        pos = 0
        ws = args[pos:pos + n]
        pos += n
        gs = args[pos:pos + n]
        pos += n
        if mp:
            w32 = args[pos:pos + n]
            pos += n
        ms = args[pos:pos + n]
        pos += n
        vs = args[pos:pos + n]
        pos += n
        vecs = {}
        for name in self._vec_names:
            vecs[name] = args[pos]
            pos += 1
        rescale = args[pos]
        tgt = w32 if mp else ws
        # no host-side grad cast: _rescale_clip's astype(f32) inside
        # phase1 reproduces the reference's g32 pre-cast exactly
        upd, fmean, fvar = self._phase1(list(tgt), list(gs), list(ms),
                                        list(vs), vecs, rescale)
        r1, r2, means, vars_ = self._norms(list(tgt), upd, fmean, fvar)
        new_w, new_low = self._phase2(list(tgt), upd, r1, r2,
                                      vecs["lr"])
        if mp:
            return tuple(new_low) + tuple(new_w) + tuple(means) \
                + tuple(vars_)
        return tuple(new_w) + tuple(means) + tuple(vars_)

    def warm_lower(self, sds):
        """AOT-compile all three stage programs at the recorded avals
        (warm_start's replay hook; mirrors ``jit.lower().compile()``)."""
        import jax
        import numpy as _np_

        n, mp = self._n, self._mp
        pos = 0
        ws = list(sds[pos:pos + n])
        pos += n
        gs = list(sds[pos:pos + n])
        pos += n
        if mp:
            w32 = list(sds[pos:pos + n])
            pos += n
        ms = list(sds[pos:pos + n])
        pos += n
        vs = list(sds[pos:pos + n])
        pos += n
        vecs = {}
        for name in self._vec_names:
            vecs[name] = sds[pos]
            pos += 1
        rescale = sds[pos]
        tgt = w32 if mp else ws
        fsum = sum(int(_np.prod(s.shape or (1,))) for s in tgt)
        upd = jax.ShapeDtypeStruct((fsum,), _np_.float32)
        flat_m = jax.ShapeDtypeStruct((fsum,), ms[0].dtype)
        flat_v = jax.ShapeDtypeStruct((fsum,), vs[0].dtype)
        rsd = jax.ShapeDtypeStruct((n,), _np_.float32)
        self._phase1.lower(tgt, gs, ms, vs, vecs, rescale).compile()
        self._norms.lower(tgt, upd, flat_m, flat_v).compile()
        self._phase2.lower(tgt, upd, rsd, rsd, vecs["lr"]).compile()


def _build_sweep_fn(family, static_items, shapes, wdtype, gdtype, mp,
                    state_dtypes, vec_names, platform):
    """The jit-able eager sweep: positional args are
    ``w..., g..., [w32...,] state_role0..., ..., vec..., rescale`` and
    outputs mirror the inputs (updated weights first).

    LAMB routes to the three-dispatch :class:`_LambSweep` when the
    Pallas kernel is not engaged — the trust-ratio reduce needs real
    program boundaries for bit-identity (see _LambSweep). The kernel
    path keeps the single packed program (kernel boundaries give the
    same materialization; identity there is the documented
    FMA-tolerance class of every Pallas kernel)."""
    import jax

    static = dict(static_items)
    roles = state_roles(family, static)
    n = len(shapes)
    if family == "lamb" and not _kernel_routed(platform):
        return _LambSweep(static_items, shapes, wdtype, mp, vec_names)

    def sweep(*args):
        pos = 0
        ws = args[pos:pos + n]
        pos += n
        gs = args[pos:pos + n]
        pos += n
        if mp:
            w32 = args[pos:pos + n]
            pos += n
        state = {}
        for role in roles:
            state[role] = args[pos:pos + n]
            pos += n
        vec = {}
        for name in vec_names:
            vec[name] = args[pos]
            pos += 1
        rescale = args[pos]
        ins = dict(state)
        if mp:
            ins["w"] = list(w32)
            ins["g"] = [g.astype("float32") for g in gs]
        else:
            ins["w"] = list(ws)
            ins["g"] = list(gs)
        new = packed_apply(family, static, shapes, ins, vec, rescale,
                           low_dtype=wdtype if mp else None,
                           platform=platform)
        outs = list(new["w_low"] if mp else new["w"])
        if mp:
            outs += list(new["w"])
        for role in roles:
            outs += list(new[role])
        return tuple(outs)

    return jax.jit(sweep)


def _sweep_jitted(family, static_items, bucket, state_dtypes, vec_names,
                  platform, record=True):
    """Cache-spine lookup for one bucket signature: hit returns the live
    jitted sweep; miss builds it and journals the signature so
    ``warm_start`` can replay it in a fresh process with no provider."""
    cache = sweep_cache()
    key = _sweep_key(family, static_items, bucket, state_dtypes,
                     vec_names, len(bucket.members), platform)
    fn = cache.lookup(key, record=record)
    if fn is not cache.MISS:
        return fn
    fn = _build_sweep_fn(family, static_items, bucket.shapes,
                         bucket.wdtype, bucket.gdtype, bucket.mp,
                         state_dtypes, vec_names, platform)
    cache.insert(key, fn)
    from .. import compiler

    compiler.record_signature(_SWEEP_SITE, {
        "family": family, "static": tuple(static_items),
        "shapes": tuple(bucket.shapes), "wdtype": bucket.wdtype,
        "gdtype": bucket.gdtype, "mp": bucket.mp,
        "state_dtypes": tuple(state_dtypes),
        "vec_names": tuple(vec_names), "platform": platform,
        "routing": compiler.routing_knobs()})
    return fn


class _EagerPlan(NamedTuple):
    """A validated per-updater sweep plan: the family, its static
    hyperparam items, and per-bucket ``(Bucket, state_nds)`` pairs
    (``state_nds``: per member, ``[w32?] + live role leaf NDArrays``)."""

    family: str
    static_items: tuple
    buckets: tuple


def plan_eager(optimizer, updater, items):
    """Build the validated sweep plan for one context's updater, or
    None when the per-param loop must run (unknown family, knob off,
    foreign state layout).

    Creates missing updater states (the lazy ``Updater.__call__``
    contract — save/load_states payloads unchanged) but mutates NOTHING
    else: no counts advance, no weights move. The Trainer pre-flights
    EVERY context through this before :func:`apply_eager_plan` touches
    any of them — a mid-loop fallback after context 0 already swept
    would double-apply context 0's update in the per-param retry, so
    validation and application share THIS one plan structure.
    """
    family = family_of(optimizer)
    if family is None or not fused_sweep_enabled() or not items:
        return None
    import jax

    from ..ndarray import NDArray

    for i, w, _ in items:
        if i not in updater.states:
            updater.states[i] = \
                optimizer.create_state_multi_precision(i, w)
    static_items = family_static(optimizer, family)
    roles = state_roles(family, dict(static_items))
    entries = [(tuple(w.shape), str(w.dtype), str(g.dtype))
               for _, w, g in items]
    is_leaf = lambda x: x is None or isinstance(x, NDArray)
    plans = []
    for b in plan_buckets(entries, optimizer.multi_precision):
        state_nds = []   # per member: [w32?] + live role leaves
        for pos in b.members:
            leaves = jax.tree_util.tree_flatten(
                updater.states[items[pos][0]], is_leaf=is_leaf)[0]
            state_nds.append([lv for lv in leaves if lv is not None])
        expect = (1 if b.mp else 0) + len(roles)
        if any(len(lv) != expect for lv in state_nds):
            return None     # foreign state layout — per-param path
        plans.append((b, state_nds))
    return _EagerPlan(family, static_items, tuple(plans))


def eager_fused_update(optimizer, updater, items) -> bool:
    """Fused optimizer phase for the eager Trainer path: plan + apply.

    ``items``: list of ``(index, weight_nd, grad_nd)`` — one context's
    view of every dense trainable param. Returns False (caller falls
    back to the per-param loop) when :func:`plan_eager` rejects.
    Multi-context callers should plan every context first and then
    apply (see Trainer._fused_update).
    """
    plan = plan_eager(optimizer, updater, items)
    if plan is None:
        return False
    apply_eager_plan(optimizer, plan, items)
    return True


def apply_eager_plan(optimizer, plan, items) -> None:
    """Apply a validated :func:`plan_eager` plan: advance the update
    counts, then ONE jitted packed sweep per dtype bucket."""
    from .. import telemetry

    family = plan.family
    static_items = plan.static_items
    roles = state_roles(family, dict(static_items))

    # count advance for ALL indices before scalar prep; with the
    # standard every-param-every-step loop this is order-identical to
    # the per-param path (each index's t is its own count either way)
    for i, _, _ in items:
        optimizer._update_count(i)

    state_bytes = 0
    for b, state_nds in plan.buckets:
        ks = [items[pos][0] for pos in b.members]
        ws = [items[pos][1] for pos in b.members]
        gs = [items[pos][2] for pos in b.members]
        vecs = collect_scalars(optimizer, family, ks)
        vec_names = sorted(vecs)
        state_dtypes = tuple(str(lv.dtype)
                             for lv in (state_nds[0] if state_nds else ()))
        from ..base import current_execution_platform

        platform = current_execution_platform(ws[0].data)
        fn = _sweep_jitted(family, static_items, b, state_dtypes,
                           vec_names, platform)
        args = [w.data for w in ws] + [g.data for g in gs]
        if b.mp:
            args += [lv[0].data for lv in state_nds]
            base = [lv[1:] for lv in state_nds]
        else:
            base = state_nds
        for ri in range(len(roles)):
            args += [lv[ri].data for lv in base]
        args += [_as_vec(vecs[name]) for name in vec_names]
        args.append(_np.float32(optimizer.rescale_grad))
        outs = fn(*args)
        n = len(b.members)
        pos = 0
        for j, w in enumerate(ws):
            w._set_data(outs[pos + j])
        pos += n
        if b.mp:
            for j, lv in enumerate(state_nds):
                lv[0]._set_data(outs[pos + j])
            pos += n
        for ri in range(len(roles)):
            for j, lv in enumerate(base):
                lv[ri]._set_data(outs[pos + j])
            pos += n
        nbytes = sum(int(_np.prod(s or (1,))) for s in b.shapes) \
            * _np.dtype(b.wdtype).itemsize
        state_bytes += len(roles) * nbytes
        telemetry.record_optimizer_dispatch(
            "fused_sweep", getattr(fn, "n_dispatches", 1))
        telemetry.record_optimizer_bucket(nbytes, len(b.members))
    # per-rank optimizer-state footprint of the replicated sweep — the
    # baseline the ZeRO gauge (mode="zero1"/"zero2") is compared against
    telemetry.record_optimizer_state_bytes("replicated", state_bytes)


# ---------------------------------------------------------------------------
# warm-start replay (compiler.warm_start's optimizer_sweep hook)
# ---------------------------------------------------------------------------


def warm_sweep_spec(spec: dict) -> str:
    """Rebuild + AOT-compile one recorded sweep signature so the first
    real ``Trainer.step`` in this process is a pure cache hit. Needs no
    provider — the spec fully determines the traced body."""
    import jax

    family = spec.get("family")
    if family not in _FAMILIES:
        return "skipped"
    if not fused_sweep_enabled():
        # knob off in THIS process: the consumers will never look these
        # executables up — don't pay their compiles at cold start
        return "skipped"
    shapes = tuple(tuple(s) for s in spec["shapes"])
    static_items = tuple(tuple(kv) for kv in spec["static"])
    vec_names = tuple(spec.get("vec_names", ()))
    state_dtypes = tuple(spec.get("state_dtypes", ()))
    platform = spec.get("platform")
    b = Bucket(tuple(range(len(shapes))), shapes, spec["wdtype"],
               spec["gdtype"], bool(spec["mp"]))
    cache = sweep_cache()
    key = _sweep_key(family, static_items, b, state_dtypes, vec_names,
                     len(shapes), platform)
    hit = cache.lookup(key, record=False)
    if hit is not cache.MISS:
        return "deduped"
    fn = _build_sweep_fn(family, static_items, shapes, spec["wdtype"],
                         spec["gdtype"], bool(spec["mp"]), state_dtypes,
                         vec_names, platform)
    # drive the compile at the recorded avals (zero-filled structs)
    n = len(shapes)
    roles = state_roles(family, dict(static_items))
    sds = []
    for dt in (spec["wdtype"], spec["gdtype"]):
        sds += [jax.ShapeDtypeStruct(s, _np.dtype(dt)) for s in shapes]
    if spec["mp"]:
        sds += [jax.ShapeDtypeStruct(s, _np.float32) for s in shapes]
        sd_states = state_dtypes[1:]
    else:
        sd_states = state_dtypes
    for ri, _ in enumerate(roles):
        dt = sd_states[ri] if ri < len(sd_states) else "float32"
        sds += [jax.ShapeDtypeStruct(s, _np.dtype(dt)) for s in shapes]
    for _ in vec_names:
        sds.append(jax.ShapeDtypeStruct((n,), _np.float32))
    sds.append(jax.ShapeDtypeStruct((), _np.float32))
    try:
        from ..base import execution_platform

        with execution_platform(platform):
            if hasattr(fn, "warm_lower"):
                fn.warm_lower(sds)
            else:
                fn.lower(*sds).compile()
    except Exception:
        return "failed"
    cache.insert(key, fn)
    return "replayed"
