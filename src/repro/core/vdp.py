"""JAX numerics for decomposed VDP execution (paper Section II-B, Fig. 2).

This module is the *functional* counterpart of the scheduling model: it
executes a convolution exactly the way the accelerator does —

    flatten kernels to DKVs, im2col inputs to DIVs        (Fig. 2)
    quantize both sides to 4-bit symmetric integers       (Sec. III-B)
    slice the contraction per the Case-1/2/3 plan         (Sec. V-B)
    per-slice segmented dot products (psums)              (VDPEs)
    integer psum accumulation                             (reduction network)
    dequantize                                            (post-processing)

and the central invariant — *slicing + psum reduction is bit-identical to
the direct quantized GEMM* (integer accumulation is associative) — is what
tests/test_vdp_numerics.py property-checks.  An optional analog-noise model
injects the Eq. 9/10 photodetector noise at the summation elements for
accuracy studies.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .mapping import TPCConfig, slice_plan
from . import photonics as ph
from .photonics import InfeasiblePrecisionError  # noqa: F401  (re-export)


# ---------------------------------------------------------------------------
# 4-bit symmetric quantization
# ---------------------------------------------------------------------------

def inv_qmax(bits: int) -> jnp.float32:
    """1/qmax as an explicit f32 constant multiplier.

    The DAC scale is max|x| / qmax; written as a *division by the literal
    qmax* it is regime-unstable — XLA's simplifier rewrites division by a
    compile-time constant into a reciprocal multiply under jit, so an
    eagerly computed scale and a whole-model-jitted one differ by 1 ulp,
    which the quantizer's round() amplifies into integer flips.  Doing the
    reciprocal multiply explicitly makes eager, per-kernel-jit and
    whole-model-jit (engine/pipeline.py) produce bit-identical scales.
    """
    return jnp.float32(1.0 / (2 ** (bits - 1) - 1))


def quantize_symmetric(x: jax.Array, bits: int = 4) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor quantization to ``bits`` signed levels.

    Returns (q, scale) with q int8-valued in [-(2^(b-1)-1), 2^(b-1)-1].
    """
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) * inv_qmax(bits)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale_a: jax.Array, scale_b: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * (scale_a * scale_b)


# ---------------------------------------------------------------------------
# Tensor decomposition: DIVs and DKVs (Fig. 2)
# ---------------------------------------------------------------------------

def out_hw(h: int, w: int, k: int, stride: int = 1,
           padding: str = "SAME") -> Tuple[int, int]:
    if padding == "SAME":
        return math.ceil(h / stride), math.ceil(w / stride)
    return (h - k) // stride + 1, (w - k) // stride + 1


def im2col(x: jax.Array, k: int, stride: int = 1,
           padding: str = "SAME") -> jax.Array:
    """Extract flattened K x K x D patches (DIVs).

    x: (H, W, D)  ->  (H_out * W_out, K*K*D), matching the row-major
    flattening of `dkv_matrix` so that patch . dkv == conv output point.
    """
    h, w, d = x.shape
    if padding == "SAME":
        h_out = math.ceil(h / stride)
        w_out = math.ceil(w / stride)
        pad_h = max((h_out - 1) * stride + k - h, 0)
        pad_w = max((w_out - 1) * stride + k - w, 0)
        x = jnp.pad(x, ((pad_h // 2, pad_h - pad_h // 2),
                        (pad_w // 2, pad_w - pad_w // 2), (0, 0)))
    else:
        h_out = (h - k) // stride + 1
        w_out = (w - k) // stride + 1
    patches = []
    for di in range(k):
        for dj in range(k):
            patches.append(x[di:di + stride * h_out:stride,
                             dj:dj + stride * w_out:stride, :])
    # (H_out, W_out, K*K, D) -> (P, K*K*D)
    stacked = jnp.stack(patches, axis=2)
    return stacked.reshape(h_out * w_out, k * k * d)


def dkv_matrix(kernels: jax.Array) -> jax.Array:
    """Flatten (F, K, K, D) kernel tensors into the (F, S) DKV matrix."""
    f = kernels.shape[0]
    return kernels.reshape(f, -1)


# ---------------------------------------------------------------------------
# Decomposed VDP execution
# ---------------------------------------------------------------------------

def direct_quantized_gemm(divs_q: jax.Array, dkvs_q: jax.Array) -> jax.Array:
    """Reference: one exact int32 GEMM over the full contraction."""
    return jax.lax.dot_general(
        divs_q.astype(jnp.int32), dkvs_q.astype(jnp.int32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)


def sliced_vdp_gemm(divs_q: jax.Array, dkvs_q: jax.Array,
                    tpc: TPCConfig) -> jax.Array:
    """Execute the GEMM through the accelerator's slice plan.

    Each slice group produces integer psums on its VDPE lanes; the psum
    reduction network accumulates them.  Integer associativity makes this
    bit-identical to `direct_quantized_gemm` — the invariant the whole
    accelerator design rests on.
    """
    s = divs_q.shape[1]
    out = jnp.zeros((divs_q.shape[0], dkvs_q.shape[0]), jnp.int32)
    off = 0
    for mode, width, count in slice_plan(tpc, s):
        for _ in range(count):
            a = divs_q[:, off:off + width].astype(jnp.int32)
            b = dkvs_q[:, off:off + width].astype(jnp.int32)
            out = out + jax.lax.dot_general(
                a, b, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            off += width
    return out


def mode2_packed_vdp(divs_q: jax.Array, small_dkvs_q: jax.Array,
                     x: int, y: int, n: int) -> jax.Array:
    """Case-3 Mode 2: y whole DKVs of size S <= x ride one VDPE pass.

    Emulates the comb-switch re-aggregation: the y DKVs are packed onto one
    N-lane VDPE (lane g occupies wavelengths [g*x, g*x + S)); the per-lane
    summation elements produce y results per pass.  Numerically this is a
    block-diagonal GEMM; returns (P, y) integer VDP results.
    """
    s = small_dkvs_q.shape[1]
    assert s <= x and y * x <= n
    # pack: lanes g hold dkv g at offset g*x; off-lane weights are zero
    packed = jnp.zeros((n,), jnp.int32)
    packs = []
    for g in range(y):
        w = jnp.zeros((n,), jnp.int32)
        w = w.at[g * x:g * x + s].set(small_dkvs_q[g].astype(jnp.int32))
        packs.append(w)
    w_block = jnp.stack(packs, axis=1)              # (N, y) block-diagonal
    # the DIV pattern replicates the patch on every lane's wavelengths
    div_rep = jnp.zeros((divs_q.shape[0], n), jnp.int32)
    for g in range(y):
        div_rep = div_rep.at[:, g * x:g * x + s].set(divs_q.astype(jnp.int32))
    return div_rep @ w_block                        # (P, y)


def conv2d_direct(x: jax.Array, kernels: jax.Array, stride: int = 1,
                  padding: str = "SAME") -> jax.Array:
    """Float reference conv via lax.conv_general_dilated (HWC, F-KKD)."""
    lhs = x[None].astype(jnp.float32)               # NHWC
    rhs = jnp.transpose(kernels, (1, 2, 3, 0)).astype(jnp.float32)  # HWIO
    out = jax.lax.conv_general_dilated(
        lhs, rhs, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return out[0]


def conv2d_vdp(x: jax.Array, kernels: jax.Array, tpc: TPCConfig,
               stride: int = 1, padding: str = "SAME", bits: int = 4,
               ) -> Tuple[jax.Array, jax.Array]:
    """Quantized conv through the full decomposed-VDP path.

    Returns (vdp_result, direct_quantized_result); both are dequantized
    floats and must agree exactly (same integer accumulations).
    """
    k = kernels.shape[1]
    f = kernels.shape[0]
    divs = im2col(x, k, stride, padding)
    dkvs = dkv_matrix(kernels)
    divs_q, s_a = quantize_symmetric(divs, bits)
    dkvs_q, s_b = quantize_symmetric(dkvs, bits)
    acc_sliced = sliced_vdp_gemm(divs_q, dkvs_q, tpc)
    acc_direct = direct_quantized_gemm(divs_q, dkvs_q)
    ho, wo = out_hw(x.shape[0], x.shape[1], k, stride, padding)
    out_s = dequantize(acc_sliced, s_a, s_b).reshape(ho, wo, f)
    out_d = dequantize(acc_direct, s_a, s_b).reshape(ho, wo, f)
    return out_s, out_d


def depthwise_conv2d_vdp(x: jax.Array, kernels: jax.Array, tpc: TPCConfig,
                         stride: int = 1, padding: str = "SAME",
                         bits: int = 4) -> Tuple[jax.Array, jax.Array]:
    """Depthwise conv through per-channel VDPs (Fig. 2b).

    kernels: (D, K, K).  Returns (vdp, reference) dequantized outputs.
    """
    d = x.shape[-1]
    k = kernels.shape[-1]
    ho, wo = out_hw(x.shape[0], x.shape[1], k, stride, padding)
    outs_v, outs_r = [], []
    for c in range(d):
        divs = im2col(x[..., c:c + 1], k, stride, padding)
        dkv = kernels[c].reshape(1, -1)
        divs_q, s_a = quantize_symmetric(divs, bits)
        dkv_q, s_b = quantize_symmetric(dkv, bits)
        outs_v.append(dequantize(sliced_vdp_gemm(divs_q, dkv_q, tpc), s_a, s_b))
        outs_r.append(dequantize(direct_quantized_gemm(divs_q, dkv_q), s_a, s_b))
    return (jnp.concatenate(outs_v, -1).reshape(ho, wo, d),
            jnp.concatenate(outs_r, -1).reshape(ho, wo, d))


@partial(jax.jit, static_argnames=("k", "stride", "padding", "bits",
                                   "depthwise"))
def _reference_acc(x: jax.Array, w_q: jax.Array, k: int, stride: int,
                   padding: str, bits: int, depthwise: bool,
                   ) -> Tuple[jax.Array, jax.Array]:
    """One layer's exact int32 accumulators and per-image DIV scales.

    x (B, H, W, D); w_q (F, K*K*D), or (D, K*K) when ``depthwise`` (each
    channel its own VDP, so its DIVs are quantized per channel too).
    """
    ho, wo = out_hw(x.shape[1], x.shape[2], k, stride, padding)

    def one(img):
        divs = im2col(img, k, stride, padding)                 # (P, KK*D)
        if not depthwise:
            q, s = quantize_symmetric(divs, bits)
            return direct_quantized_gemm(q, w_q).reshape(ho, wo, -1), s
        d = img.shape[-1]
        q, s = jax.vmap(lambda c: quantize_symmetric(c, bits),
                        in_axes=2, out_axes=(2, 0))(
                            divs.reshape(-1, k * k, d))        # (P, KK, D)
        acc = jnp.einsum("pkd,dk->pd", q.astype(jnp.int32),
                         w_q.astype(jnp.int32),
                         preferred_element_type=jnp.int32)
        return acc.reshape(ho, wo, d), s

    return jax.vmap(one)(x)


@jax.jit
def _dequant_bias(acc: jax.Array, scale: jax.Array,
                  bias: jax.Array) -> jax.Array:
    """acc * scale + bias as one fused XLA expression.

    A fused epilogue is what every execution path evaluates, and a
    backend may contract it into a fused multiply-add (XLA:CPU always
    does), which rounds once instead of twice.  Evaluating the reference's
    epilogue the same way keeps it comparable bit for bit.
    """
    return acc.astype(jnp.float32) * scale + bias


def reference_forward(layer_defs, x: jax.Array, bits: int = 4) -> jax.Array:
    """Plain jnp reference of an executable layer chain, per image.

    ``layer_defs`` are ``engine.LayerDef``s; ``x`` is an NHWC batch.  Each
    layer runs the paper's quantized VDP semantics with this module's
    functions alone — ``im2col``, per-image ``quantize_symmetric`` of the
    DIVs, per-tensor (per-channel for depthwise) ``quantize_symmetric`` of
    the DKVs, an exact int32 contraction, the dequant scale
    ``scale_a * scale_b``, bias and the activation — and shares no code
    with the engine or its kernels.  The dequant scale is multiplied
    outside any jit, so XLA cannot reassociate it with the constant 1/qmax
    (the reason the engine pins its scales with a barrier).
    """
    from ..cnn.layers import ConvKind        # core.mapping's dependency

    for ld in layer_defs:
        if ld.kind is ConvKind.DC:
            d, k, _ = ld.weights.shape
            w_q, w_s = jax.vmap(lambda v: quantize_symmetric(v, bits))(
                ld.weights.reshape(d, k * k))
            acc, s = _reference_acc(x, w_q, k, ld.stride, ld.padding, bits,
                                    True)
            scale = (s * w_s)[:, None, None, :]
        else:
            k = 1 if ld.kind is ConvKind.FC else ld.weights.shape[1]
            if ld.kind is ConvKind.FC:       # rows as 1x1 maps, K = 1
                x = x.reshape(x.shape[0], 1, 1, -1)
            w_q, w_s = quantize_symmetric(dkv_matrix(ld.weights), bits)
            acc, s = _reference_acc(x, w_q, k, ld.stride, ld.padding, bits,
                                    False)
            scale = (s * w_s)[:, None, None, None]
        bias = jnp.float32(0.0) if ld.bias is None else ld.bias
        x = _dequant_bias(acc, scale, bias)
        if ld.act == "relu":
            x = jnp.maximum(x, 0.0)
        elif ld.act == "relu6":
            x = jnp.clip(x, 0.0, 6.0)
        if ld.kind is ConvKind.FC:
            x = x.reshape(x.shape[0], -1)
    return x


# ---------------------------------------------------------------------------
# Analog noise model (Eq. 9/10) for accuracy studies
# ---------------------------------------------------------------------------

def noisy_vdp_gemm(key: jax.Array, divs_q: jax.Array, dkvs_q: jax.Array,
                   tpc: TPCConfig, br_hz: float = 1e9, bits: int = 4,
                   params: ph.PhotonicParams | None = None) -> jax.Array:
    """Integer GEMM + per-psum Gaussian noise at the summation elements.

    The PD noise current (Eq. 10) at the operating received power maps to an
    equivalent integer-domain sigma via the LSB size at the photodetector:
    one LSB corresponds to the minimum resolvable power step for ``bits``
    (ph.integer_noise_sigma_lsb).

    Raises :class:`repro.core.photonics.InfeasiblePrecisionError` when the
    (bits, BR) point violates the Eq. 9 RIN ceiling — such a point used to
    silently return the *noise-free* result (sigma 0.0), the exact opposite
    of what infeasibility means.
    """
    p = params or ph.PhotonicParams()
    sigma_lsb = ph.integer_noise_sigma_lsb(p, bits, br_hz)
    acc = sliced_vdp_gemm(divs_q, dkvs_q, tpc).astype(jnp.float32)
    n_slices = sum(c for _, _, c in slice_plan(tpc, divs_q.shape[1]))
    noise = (jax.random.normal(key, acc.shape)
             * sigma_lsb * jnp.sqrt(float(n_slices)))
    return jnp.round(acc + noise).astype(jnp.int32)
