"""Contractions at a stated precision, the same on every backend.

``mode="highest"`` is float32 (XLA's ``Precision.HIGHEST``). The lower
modes emulate the TPU's bfloat16 passes exactly, so the control that
runs the reference one step below the configuration's precision reads
the same on the chip as on the CPU (whose XLA ignores precision flags):

* ``"high"``: three passes — each float32 operand split into a
  bfloat16 high part and a bfloat16 remainder, and
  ``hi·hi + hi·lo + lo·hi`` accumulated in float32 (``bf16_3x``);
* ``"bf16"``: one pass, ``hi·hi``.

Their gradients are contracted in the same mode (``custom_vjp``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "bf16")
_HI = jax.lax.Precision.HIGHEST


def _bf16(a):
    # An explicit rounding op: XLA may drop a float32 → bfloat16 →
    # float32 round trip as "excess precision", and did so on the TPU
    # inside jit (the three-pass emulation then read as one pass).
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _emulated(spec, mode, a, b):
    ah, al = _split(a)
    bh, bl = _split(b)
    out = _exact(spec, ah, bh)
    if mode == "high":
        out = out + _exact(spec, ah, bl) + _exact(spec, al, bh)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _einsum_lowp(spec, mode, a, b):
    return _emulated(spec, mode, a, b)


def _fwd(spec, mode, a, b):
    return _emulated(spec, mode, a, b), (a, b)


def _bwd(spec, mode, res, g):
    a, b = res
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    da = _emulated(f"{out},{sb}->{sa}", mode, g, b)
    db = _emulated(f"{sa},{out}->{sb}", mode, a, g)
    return da, db


_einsum_lowp.defvjp(_fwd, _bwd)


def einsum(spec: str, a, b, mode: str = "highest"):
    """``jnp.einsum(spec, a, b)`` of two float32 operands at ``mode``.
    Every index of each operand appears in the other operand or in the
    output (true of every contraction the reference makes)."""
    if mode == "highest":
        return _exact(spec, a, b)
    if mode not in MODES:
        raise ValueError(f"precision mode {mode!r} not in {MODES}")
    return _einsum_lowp(spec, mode, a, b)
