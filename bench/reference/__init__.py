"""Plain references, one module per formulation (``primal.py``,
``dual.py``): the classical s = 1 method written in ``jax.numpy``, importing
nothing of the program.  Each module's ``solve(X, y, lam, idx, matmul)``
returns ``(w, alpha)`` after ``idx.shape[0]`` iterations from zero.

``matmul(mode)`` is the product every reference runs its contractions
through.  ``"highest"`` is the reference itself (f32 at full precision).
The lower modes are the controls, the precision cut a faster kernel would
be tempted to make, emulated the same way on every backend:
``"bf16_3x"`` keeps three bf16 partial products (XLA's ``HIGH``) and
``"bf16"`` one (the chip's default single pass).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("highest", "bf16_3x", "bf16")


def _split(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _bf16_dot(a, b):
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def matmul(mode: str):
    if mode == "highest":
        return lambda a, b: jnp.matmul(a, b,
                                       precision=jax.lax.Precision.HIGHEST)
    if mode == "bf16_3x":
        def mm(a, b):
            a_hi, a_lo = _split(a)
            b_hi, b_lo = _split(b)
            return (_bf16_dot(a_hi, b_hi) + _bf16_dot(a_hi, b_lo)
                    + _bf16_dot(a_lo, b_hi))
        return mm
    if mode == "bf16":
        return lambda a, b: _bf16_dot(a.astype(jnp.bfloat16),
                                      b.astype(jnp.bfloat16))
    raise ValueError(f"unknown matmul mode {mode!r}; expected one of {MODES}")


def take(X, idx, axis: int):
    """``X`` sliced at each index of ``idx`` along ``axis``, one dynamic
    slice per index: a gather on the whole operand makes the TPU compiler
    copy the operand in pieces on every iteration."""
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(X, i, 1, axis)
                            for i in idx], axis=axis)


def spd_solve(A, r):
    """The b x b block solve, at full precision in every mode: the
    contractions are what a faster kernel would cut, not this."""
    with jax.default_matmul_precision("highest"):
        return jnp.linalg.solve(A, r)


def module(formulation: str):
    import importlib
    return importlib.import_module(f"bench.reference.{formulation}")
