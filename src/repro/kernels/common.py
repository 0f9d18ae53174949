"""Shared kernel-side helpers: MXU alignment, quantize prologue, epilogue.

Single home for the ``round_up``/``pad_to`` alignment arithmetic that was
copy-pasted across kernels/ops.py, engine/plan.py and engine/executor.py,
and for the two numeric expressions every quantized-domain kernel shares:

* ``quantize_tile`` — the symmetric-quantizer expression (divide by the
  DAC scale, round, clip, int8).  The fused in-kernel prologues
  (vdpe_gemm_q8, vdpe_conv_q8) and every XLA-side quantize in the engine
  (executor._quantize_per_image, the depthwise and float-oracle paths)
  must round onto the *same integer lattice* for the int8 path to be
  bitwise equal to the quantize-then-float oracle, so they all spell the
  expression through this one helper (built on core/vdp.inv_qmax, the
  single home of the reciprocal-multiply DAC constant).
  core/vdp.quantize_symmetric is the seed paper-reference twin: it spells
  the identical expression but stays standalone (core cannot import the
  kernel package back) — keep the two in sync if the lattice ever
  changes.

* ``dequant_epilogue`` — the fused epilogue ``act(acc * scale + bias)``
  consuming the int32 (or exact-f32) accumulator directly.  The GEMM
  kernels (vdpe_gemm.py) and the implicit-GEMM conv kernels
  (vdpe_conv.py) apply the identical expression, which is what keeps the
  paths bitwise-comparable.

``stable_scale`` pins a DAC scale against XLA algebraic reassociation
(the PR-3 reciprocal/optimization_barrier lesson): the scale is
``absmax * (1/qmax)`` with 1/qmax a compile-time constant, and under a
whole-model jit XLA's simplifier reassociates its later multiply by the
weight scale — ``(m * c) * w -> m * (c * w)`` — shifting the epilogue
scale by 1 ulp, which the quantizer's round() amplifies into integer
flips.  The barrier freezes the association in eager, per-kernel-jit and
whole-model-jit regimes alike.  It is an XLA op: Mosaic has no lowering
for it, so every DAC scale is computed in XLA and enters the kernels as
data; kernel bodies only divide, round and clip.

``default_interpret`` is the one place that decides whether a Pallas
kernel runs compiled (Mosaic, on a TPU backend) or in the interpreter
(every other backend).  Every kernel wrapper's ``interpret=None``
resolves through it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# THE reciprocal-multiply DAC constant, re-exported from its single home
# (core does not import kernels, so this direction is cycle-free); the
# lattice expression below (quantize_tile) builds on it
from ..core.vdp import inv_qmax  # noqa: F401

#: Fused-epilogue activations supported by every kernel in this package.
ACTIVATIONS = ("none", "relu", "relu6")


def default_interpret() -> bool:
    """Interpret Pallas kernels unless JAX's default backend is a TPU.

    No exception is caught: a backend that fails to initialise is an
    error, never a silent interpret-mode run.
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel wrapper's ``interpret`` argument, ``None`` -> the default."""
    return default_interpret() if interpret is None else interpret


def round_up(v: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= ``v``."""
    return (v + mult - 1) // mult * mult


def pad_to(a: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad a 2-D array up to (rows, cols)."""
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


def apply_act(r: jax.Array, act: str) -> jax.Array:
    """Compile-time activation branch of the fused epilogue."""
    if act == "relu":
        return jnp.maximum(r, 0.0)
    if act == "relu6":
        return jnp.clip(r, 0.0, 6.0)
    assert act == "none", act
    return r


def qmax_for(bits: int) -> int:
    """Largest symmetric quantization level for ``bits`` signed bits."""
    return 2 ** (bits - 1) - 1


def stable_scale(x: jax.Array) -> jax.Array:
    """Pin a DAC scale against XLA algebraic reassociation (module doc)."""
    return jax.lax.optimization_barrier(x)


def quantize_tile(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """THE symmetric-quantizer expression: round x/scale onto the int8
    lattice.  ``scale`` broadcasts (scalar, per-row column, per-channel)."""
    q = qmax_for(bits)
    return jnp.clip(jnp.round(x / scale), -q, q).astype(jnp.int8)


def dequant_epilogue(acc: jax.Array, scale: jax.Array, bias: jax.Array,
                     act: str) -> jax.Array:
    """THE fused epilogue: act(acc * scale + bias), f32 out.

    ``acc`` is the int32 MXU accumulator (or the bit-identical exact-f32
    accumulator of the float oracle path); ``scale`` broadcasts.
    """
    return apply_act(acc.astype(jnp.float32) * scale + bias, act)
