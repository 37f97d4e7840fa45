"""The ``Custom`` operator — user Python code inside graphs.

Reference: ``src/operator/custom/custom.cc`` (Forward/Backward push a
callback onto the engine with CPU-copied NDArrays). TPU-native shape: the
user's ``CustomOp.forward`` runs under ``jax.pure_callback`` so the op is
usable eagerly AND inside jit/pjit-traced graphs (Symbol executor,
hybridized blocks); output shapes/dtypes come statically from the
registered ``CustomOpProp.infer_shape``/``infer_type``; a
``jax.custom_vjp`` routes cotangents through the user's ``backward``
(XLA cannot differentiate an opaque host call).
"""
from __future__ import annotations

import numpy as onp

from .registry import register


@register("Custom", variadic=True, pass_training_flag=True)
def custom(*inputs, op_type, _training=False, **kwargs):
    """Apply a registered user-defined operator (reference:
    ``mx.nd.Custom`` / ``custom.cc``).

    ``inputs`` = arguments then auxiliary states, per the prop's
    ``list_arguments()`` / ``list_auxiliary_states()``. Extra keyword
    attributes are forwarded to the ``CustomOpProp`` constructor.
    """
    import jax
    import jax.numpy as jnp

    from ..base import MXNetError
    from .. import operator as _op_mod

    prop = _op_mod.get_prop_cls(op_type)(**kwargs)
    n_args = len(prop.list_arguments())
    n_aux = len(prop.list_auxiliary_states())
    n_out = len(prop.list_outputs())
    if len(inputs) != n_args + n_aux:
        raise MXNetError(
            f"Custom[{op_type}]: got {len(inputs)} inputs, expected "
            f"{n_args} arguments + {n_aux} auxiliary states")

    in_shapes = [tuple(x.shape) for x in inputs[:n_args]]
    in_dtypes = [onp.dtype(x.dtype) for x in inputs[:n_args]]
    _, out_shapes, _ = prop.infer_shape([list(s) for s in in_shapes])
    _, out_dtypes, _ = prop.infer_type(list(in_dtypes))
    out_specs = tuple(
        jax.ShapeDtypeStruct(tuple(s), onp.dtype(d))
        for s, d in zip(out_shapes, out_dtypes))
    grad_specs = tuple(
        jax.ShapeDtypeStruct(tuple(x.shape), onp.dtype(x.dtype))
        for x in inputs[:n_args])
    is_train = bool(_training)

    def _to_nd(vals):
        # CPU NDArrays for the user's host code — custom.cc's CPU-copy
        # contract: callbacks compute on the host, never on the chip
        from ..context import cpu
        from ..ndarray import array

        return [array(onp.asarray(v), ctx=cpu(0)) for v in vals]

    def _host_forward(*vals):
        nd_in = _to_nd(vals[:n_args])
        nd_aux = _to_nd(vals[n_args:])
        nd_out = _to_nd([onp.zeros(sp.shape, sp.dtype) for sp in out_specs])
        op = prop.create_operator(None, in_shapes, in_dtypes)
        op.forward(is_train=is_train, req=["write"] * n_out,
                   in_data=nd_in, out_data=nd_out, aux=nd_aux)
        return tuple(
            onp.asarray(o.asnumpy(), sp.dtype).reshape(sp.shape)
            for o, sp in zip(nd_out, out_specs))

    def _host_backward(*vals):
        og = _to_nd(vals[:n_out])
        nd_in = _to_nd(vals[n_out:n_out + n_args])
        nd_aux = _to_nd(vals[n_out + n_args:n_out + n_args + n_aux])
        nd_out = _to_nd(vals[n_out + n_args + n_aux:])
        nd_grad = _to_nd([onp.zeros(sp.shape, sp.dtype)
                          for sp in grad_specs])
        op = prop.create_operator(None, in_shapes, in_dtypes)
        op.backward(req=["write"] * n_args, out_grad=og, in_data=nd_in,
                    out_data=nd_out, in_grad=nd_grad, aux=nd_aux)
        return tuple(
            onp.asarray(g.asnumpy(), sp.dtype).reshape(sp.shape)
            for g, sp in zip(nd_grad, grad_specs))

    @jax.custom_vjp
    def f(*xs):
        return tuple(jax.pure_callback(_host_forward, out_specs, *xs))

    def f_fwd(*xs):
        outs = tuple(jax.pure_callback(_host_forward, out_specs, *xs))
        return outs, (xs, outs)

    def f_bwd(res, gouts):
        xs, outs = res
        gargs = jax.pure_callback(_host_backward, grad_specs,
                                  *gouts, *xs, *outs)
        # aux states are read-only: zero cotangents
        gaux = tuple(jnp.zeros(x.shape, x.dtype) for x in xs[n_args:])
        return tuple(gargs) + gaux

    f.defvjp(f_fwd, f_bwd)
    outs = f(*inputs)
    return outs if n_out > 1 else outs[0]
