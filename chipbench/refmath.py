"""Contractions for the plain references, at a stated precision.

``"highest"`` is float32 as the configurations state it: every contraction
at ``Precision.HIGHEST``. ``"high"`` is the control, the nearest precision
below, three bfloat16 passes: on a TPU every contraction, forward and
backward, at ``Precision.HIGH`` as the chip runs it. Elsewhere
``Precision.HIGH`` computes in full float32, so there the three passes are
written out: each float32 operand split into a bfloat16 high part and a
bfloat16 low part, and hi*hi + hi*lo + lo*hi summed in float32, the
backward contractions (cotangent times operand) likewise. The written-out
form is no control on a TPU: XLA folds the split there, and the sum comes
out as one bfloat16 pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three(f, x, y):
    """``f(x, y)`` for a bilinear ``f`` by three bfloat16 passes."""
    xh, xl = _split(x)
    yh, yl = _split(y)
    return f(xh, yh) + f(xh, yl) + f(xl, yh)


def bilinear(op, precision: str):
    """``op(a, b, precision=...)`` evaluated at ``precision``."""

    def exact(a, b):
        return op(a, b, precision=HI)

    if precision == "highest":
        return exact
    if precision != "high":
        raise ValueError(f"unknown reference precision {precision!r}")
    if jax.default_backend() == "tpu":
        return lambda a, b: op(a, b, precision=jax.lax.Precision.HIGH)
    return _three_pass(op, exact)


def _three_pass(op, exact):
    @jax.custom_vjp
    def three_pass(a, b):
        return _three(exact, a, b)

    def fwd(a, b):
        return _three(exact, a, b), (a, b)

    def bwd(res, g):
        # op is linear in each operand, so its transposes do not depend on
        # the point they are taken at
        a, b = res

        def da(g_, b_):
            return jax.vjp(lambda x: exact(x, b_), a)[1](g_)[0]

        def db(a_, g_):
            return jax.vjp(lambda y: exact(a_, y), b)[1](g_)[0]

        return _three(da, g, b), _three(db, a, g)

    three_pass.defvjp(fwd, bwd)
    return three_pass


def xent(logits, labels):
    """Softmax cross-entropy per row, in float32: a loss is their mean,
    and a reported loss is taken in float64 from these rows, so its
    rounding is that of one row's value over the number of rows."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - picked


def sgd(params, grads, lr: float, momentum: float, state):
    """SGD, with momentum ``v = mu * v + g; w = w - lr * v`` when mu > 0."""
    if not momentum:
        return {k: w - lr * grads[k] for k, w in params.items()}, state
    v = {k: momentum * state[k] + grads[k] for k in params}
    return {k: w - lr * v[k] for k, w in params.items()}, v
