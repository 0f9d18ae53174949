"""Implicit-GEMM convolution Pallas kernels: no im2col matrix in HBM.

The im2col -> GEMM path materializes the full (B, P, K*K*D) DIV matrix in
HBM — a K^2x blow-up of the activation footprint — before the GEMM reads
it back.  The photonic accelerator never pays that: DIV streams are formed
on the fly from the activation map as they enter the VDPE lanes.  These
kernels are the software analogue: the activation rides to VMEM at its
natural NHWC size, one band of output rows at a time, and each kernel
instance loads its K*K patch taps as unit-stride windows of the band,
contracting each tap's (rows*wo, D) window D-deep against the matching
D-row band of the resident packed DKV operand.  The K*K-tap loop is
unrolled at trace time (K is static), so the full S = K*K*D contraction
accumulates in VMEM and the DIV matrix never exists anywhere.

Quantized-domain entry points (the serving hot path):

* ``vdpe_conv_q8`` — Mode 1: the *raw f32* activation map enters the
  kernel.  The per-image DAC scale (covered-window absmax times 1/qmax,
  ``dac_scale``) is one XLA reduction in the wrapper — the same code the
  oracle paths use — and rides SMEM into the kernel; the prologue only
  divides, rounds and clips each tap window onto the int8 lattice.  The
  separate XLA round/clip pass and the int8 round-trip of the activation
  through HBM stay fused away.

* ``vdpe_pack_conv_zs_q8`` — Mode 2, zero-skipping, same prologue.

Pre-quantized entry points (oracles + the im2col baseline):

* ``vdpe_conv`` — Mode 1 over an already-quantized activation: rhs is
  the plan's (S_pad, F_pad) MXU-tiled operand; only the first K*K*D rows
  are read, as D-row bands.  Accepts int8 or lattice-f32 operands (f32
  accumulation of int8 products is exact — the quantize-then-float
  oracle's conv).

* ``vdpe_pack_conv_zs`` — Mode 2, zero-skipping: rhs is the (x, F_pad)
  dense segment-sum pack (ops.pack_mode2_segments), never the (y*x, F)
  block-diagonal — asserted structurally, like vdpe_pack_gemm_zs.  The
  contraction is S-deep (S <= x), so the kernel keeps both wins at once:
  no im2col blow-up AND no (y-1)/y zero-FLOPs.

All carry the fused dequant/bias/ReLU(6) epilogue from the GEMM kernels
(kernels/common.dequant_epilogue).  The dequant scale is per image — every
position of image b shares b's input-DAC swing — and rides SMEM as a (B,)
vector indexed by the image grid axis (a scalar scale is broadcast to it).
``bias`` is blocked over the output-channel axis.

Layout and tiling (what Mosaic accepts, found by compiling for a v5e):

* Strides become phase planes.  Mosaic lowers no strided value slice
  ("Only 2D gather is supported"), so the wrapper splits the padded input
  into stride^2 phase planes (B, s*s, Hq, Wq, D) with plane (p, q) holding
  ``x[:, p::s, q::s]``.  Tap (di, dj) then reads plane (di % s, dj % s) at
  the unit-stride offset (di // s, dj // s).  At stride 1 the split is a
  reshape.
* Output rows are grid-tiled into bands.  The grid is (B, bands,
  F_pad / block_o); each instance holds one band of ``band_rows`` output
  rows plus its (K-1)//s halo rows of every phase plane.  Bands overlap
  by the halo, so the input BlockSpec indexes elements, not blocks.  A
  whole-image block does not fit: at 112x112x32, batch 8, the Mode-2 pw1
  layer exceeded the scoped VMEM limit, because D < 128 is padded to 128
  lanes and Pallas double-buffers both the input block and the output
  block.  ``band_rows`` picks the band height from that padded footprint
  (``VMEM_BAND_BYTES``).  The band count need not divide H: the input
  planes are zero-padded to whole bands and the extra output rows are
  sliced off.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (dequant_epilogue, inv_qmax, quantize_tile,
                     resolve_interpret, round_up, stable_scale)
from .vdpe_gemm import BLOCK_O, _acc_dtype

#: Budget of one pipeline buffer (input band + output band, lane- and
#: sublane-padded) per grid instance.  Pallas double-buffers both, and the
#: tap windows and the accumulator need about as much again, which keeps
#: a layer well inside v5e's 16 MiB default scoped VMEM.
VMEM_BAND_BYTES = 2 * 2 ** 20


def conv_window_bounds(k: int, stride: int, ho: int, wo: int) -> tuple:
    """(min Hp, min Wp) the padded activation must satisfy for the taps.

    Tap (di, dj) reads rows di, di+stride, ..., di+stride*(ho-1); with
    di <= k-1 the last read is at stride*(ho-1) + k - 1.  Shared with the
    executor's spatial padding and the tests' structural checks.
    """
    return stride * (ho - 1) + k, stride * (wo - 1) + k


def tap_window(x: jax.Array, di: int, dj: int, stride: int,
               ho: int, wo: int) -> jax.Array:
    """Tap (di, dj)'s strided window: (..., Hp, Wp, D) -> (..., ho, wo, D).

    THE tap-geometry definition of the XLA-side paths: the covered-set
    quantization max (``window_absmax``) and the depthwise taps enumerate
    exactly the pixels the kernels' phase-plane windows read, which is
    what keeps the bitwise contract with the im2col oracle.
    """
    return x[..., di:di + stride * (ho - 1) + 1:stride,
             dj:dj + stride * (wo - 1) + 1:stride, :]


def window_absmax(x4p: jax.Array, k: int, stride: int, ho: int, wo: int,
                  per_channel: bool) -> jax.Array:
    """max |x| over the patch-covered pixel set, per image (and channel).

    Identical to the im2col-matrix max: the taps enumerate exactly the
    pixels the DIV matrix replicates (a strided layer can leave border
    pixels uncovered, so the whole-image max would be *wrong* — the
    covered-set max is what keeps every path bitwise-equal to the oracle).
    """
    axes = (1, 2) if per_channel else (1, 2, 3)
    m = None
    for kk in range(k * k):
        di, dj = divmod(kk, k)
        wm = jnp.max(jnp.abs(tap_window(x4p, di, dj, stride, ho, wo)),
                     axis=axes)
        m = wm if m is None else jnp.maximum(m, wm)
    return m                      # (B,) or (B, D)


def dac_scale(x4p: jax.Array, k: int, stride: int, ho: int, wo: int,
              bits: int, per_channel: bool = False) -> jax.Array:
    """THE per-image input-DAC scale of a conv layer: (B,) or (B, D).

    ``max(covered absmax, 1e-12) * (1/qmax)``, pinned by ``stable_scale``.
    Every path — the q8 kernels' wrappers, the float and im2col oracles,
    the depthwise and guarded paths — computes its scale here.
    """
    with jax.named_scope("dac_scale"):
        m = window_absmax(x4p, k, stride, ho, wo, per_channel)
        return stable_scale(jnp.maximum(m, 1e-12) * inv_qmax(bits))


def band_rows(ho: int, wo: int, d: int, stride: int, k: int,
              block_o: int = BLOCK_O) -> int:
    """Output rows per grid instance, from the padded VMEM footprint.

    One band row costs s*s phase-plane rows of (Wq padded to 8 sublanes,
    D padded to 128 lanes) f32 plus wo output positions of block_o f32
    lanes; the halo rows are paid once per band.  Bands are balanced (a
    112-row layer gets 7 bands of 16, not 6 of 18 and a stub) and, when
    there is more than one, cover a multiple of 8 positions so the output
    block stays sublane-aligned.
    """
    halo = (k - 1) // stride
    wq = wo + halo
    in_row = stride * stride * round_up(wq, 8) * round_up(d, 128) * 4
    out_row = wo * block_o * 4
    fit = max(1, (VMEM_BAND_BYTES - halo * in_row) // (in_row + out_row))
    if fit >= ho:
        return ho
    rows = -(-ho // -(-ho // fit))
    return min(round_up(rows, 8 // math.gcd(wo, 8)), ho)


def _phase_planes(x: jax.Array, stride: int, rows: int,
                  cols: int) -> jax.Array:
    """(B, Hp, Wp, D) -> (B, s*s, rows, cols, D) stride-phase planes.

    Plane p*s + q holds ``x[:, p::s, q::s]``, cropped or zero-padded to
    (rows, cols).  The crop drops only pixels no tap reads; the padding
    rows are read by the last band alone, whose extra outputs are sliced
    off.  At stride 1 this is a reshape (plus the band padding, if any).
    """
    b, hp, wp, d = x.shape
    s = stride
    with jax.named_scope("phase_planes"):
        x = x[:, :rows * s, :cols * s]
        x = jnp.pad(x, ((0, 0), (0, rows * s - x.shape[1]),
                        (0, cols * s - x.shape[2]), (0, 0)))
        x = x.reshape(b, rows, s, cols, s, d).transpose(0, 2, 4, 1, 3, 5)
        return x.reshape(b, s * s, rows, cols, d)


def _conv_kernel(*refs, k, stride, bh, wo, d, bits, act, epilogue):
    """One (image, row band, output-channel block) instance.

    ``bits`` (not None) selects the quantize prologue.  Refs, in order:
    [a_scale (B,) SMEM if bits] [scale (B,) SMEM if epilogue] x (1, s*s, bh + halo, Wq, D) band, rhs (S_rows, block_o),
    [bias (1, block_o) if epilogue], out (1, bh*wo, block_o).

    Each tap is a unit-stride window of one phase plane.  Integer
    accumulation is associative (and exact in f32 for the lattice oracle
    operands), so the tap-major sum is bit-identical to the single S-deep
    im2col contraction.
    """
    quantize = bits is not None
    refs = list(refs)
    a_ref = refs.pop(0) if quantize else None
    scale_ref = refs.pop(0) if epilogue else None
    x_ref, rhs_ref = refs.pop(0), refs.pop(0)
    bias_ref = refs.pop(0) if epilogue else None
    (out_ref,) = refs
    b = pl.program_id(0)
    acc_dtype = jnp.int32 if quantize else _acc_dtype(x_ref.dtype)
    acc = None
    for kk in range(k * k):
        di, dj = divmod(kk, k)
        win = x_ref[0, (di % stride) * stride + dj % stride,
                    pl.ds(di // stride, bh), pl.ds(dj // stride, wo), :]
        if quantize:
            win = quantize_tile(win, a_ref[b], bits)
        part = jax.lax.dot_general(
            win.reshape(bh * wo, d), rhs_ref[pl.ds(kk * d, d), :],
            (((1,), (0,)), ((), ())), preferred_element_type=acc_dtype)
        acc = part if acc is None else acc + part
    if epilogue:
        acc = dequant_epilogue(acc, scale_ref[b], bias_ref[...], act)
    out_ref[0] = acc


def _per_image(scale, b: int) -> jax.Array:
    """A scalar or (B,) / (B, 1) dequant scale as the (B,) SMEM vector."""
    scale = jnp.asarray(scale, jnp.float32).reshape(-1)
    if scale.size not in (1, b):
        raise ValueError(
            f"per-image scale must have one entry per image ({b}), "
            f"got shape {scale.shape}")
    return jnp.broadcast_to(scale, (b,))


def _conv_call(x: jax.Array, rhs: jax.Array, k: int, stride: int,
               ho: int, wo: int, block_o: int, interpret: bool | None,
               scale, bias, act: str, bits: int | None = None,
               a_scale=None, rows: int | None = None) -> jax.Array:
    """Shared pallas_call of all four entry points.

    ``bits``/``a_scale`` select the fused quantize prologue; ``scale``
    the fused epilogue (without it the raw accumulator is returned).
    """
    b, hp, wp, d = x.shape
    s_rows, f_pad = rhs.shape
    min_h, min_w = conv_window_bounds(k, stride, ho, wo)
    assert hp >= min_h and wp >= min_w, (
        f"activation ({hp}, {wp}) too small for {k}x{k}/s{stride} taps over "
        f"({ho}, {wo}) outputs; pad to at least ({min_h}, {min_w})")
    assert k * k * d <= s_rows, (k, d, s_rows)
    assert f_pad % block_o == 0, (f_pad, block_o)
    quantize = bits is not None
    epilogue = scale is not None
    if quantize:
        assert epilogue and rhs.dtype == jnp.int8, rhs.dtype
    if not epilogue:
        assert bias is None and act == "none", "epilogue requires a scale"
    bh = rows if rows is not None else band_rows(ho, wo, d, stride, k,
                                                 block_o)
    bands = -(-ho // bh)
    assert bands == 1 or (bh * wo) % 8 == 0, (
        f"a band of {bh} rows x {wo} positions is not sublane-aligned")
    halo = (k - 1) // stride
    nph, wq = stride * stride, wo + halo
    planes = _phase_planes(x, stride, bands * bh + halo, wq)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs, args = [], []
    if quantize:
        in_specs.append(smem)
        args.append(_per_image(a_scale, b))
    if epilogue:
        in_specs.append(smem)
        args.append(_per_image(scale, b))
    in_specs += [
        pl.BlockSpec((pl.Element(1), pl.Element(nph), pl.Element(bh + halo),
                      pl.Element(wq), pl.Element(d)),
                     lambda i, r, j: (i, 0, r * bh, 0, 0)),
        pl.BlockSpec((s_rows, block_o), lambda i, r, j: (0, j)),
    ]
    args += [planes, rhs]
    if epilogue:
        if bias is None:
            bias = jnp.zeros((1, f_pad), jnp.float32)
        in_specs.append(pl.BlockSpec((1, block_o), lambda i, r, j: (0, j)))
        args.append(bias)
    out_dtype = jnp.float32 if epilogue else _acc_dtype(x.dtype)
    out = pl.pallas_call(
        functools.partial(_conv_kernel, k=k, stride=stride, bh=bh, wo=wo,
                          d=d, bits=bits, act=act, epilogue=epilogue),
        grid=(b, bands, f_pad // block_o),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh * wo, block_o),
                               lambda i, r, j: (i, r, j)),
        out_shape=jax.ShapeDtypeStruct((b, bands * bh * wo, f_pad),
                                       out_dtype),
        interpret=resolve_interpret(interpret),
    )(*args)
    return out if bands * bh == ho else out[:, :ho * wo]


@functools.partial(jax.jit, static_argnames=("k", "stride", "ho", "wo",
                                             "block_o", "interpret", "act"))
def vdpe_conv(x_q: jax.Array, rhs: jax.Array, k: int, stride: int,
              ho: int, wo: int, block_o: int = BLOCK_O,
              interpret: bool | None = None,
              scale: jax.Array | None = None,
              bias: jax.Array | None = None,
              act: str = "none") -> jax.Array:
    """Mode-1 implicit-GEMM conv over a *pre-quantized* activation.

    ``x_q`` is the quantized activation (int8, or the same lattice held
    in f32 for the quantize-then-float oracle), already spatially padded
    for the layer's SAME/VALID policy (conv_window_bounds gives the
    minimum).  ``rhs`` is the plan's Mode-1 (S_pad, F_pad) operand; rows
    beyond K*K*D padding are never read.  Without ``scale`` the result is
    the raw accumulator; with it the f32 epilogue ``act(acc * scale +
    bias)`` is fused.  ``scale`` may be a scalar or a per-image (B,) /
    (B, 1) vector.  The caller slices F_pad -> F and reshapes
    P -> (ho, wo).
    """
    return _conv_call(x_q, rhs, k, stride, ho, wo, block_o, interpret,
                      scale, bias, act)


def _conv_q8(x: jax.Array, rhs: jax.Array, w_scale: jax.Array, k: int,
             stride: int, ho: int, wo: int, bits: int, block_o: int,
             interpret: bool | None, bias, act: str,
             rows: int | None = None) -> jax.Array:
    """The q8 entry points' body: XLA DAC scale, in-kernel quantize.

    The epilogue scale ``a_scale * w_scale`` is the oracle paths' own
    expression.  ``rows`` overrides ``band_rows`` (tests cover uneven
    bands at small sizes with it).
    """
    a_scale = dac_scale(x, k, stride, ho, wo, bits)
    return _conv_call(x, rhs, k, stride, ho, wo, block_o, interpret,
                      a_scale * w_scale, bias, act, bits=bits,
                      a_scale=a_scale, rows=rows)


@functools.partial(jax.jit, static_argnames=("k", "stride", "ho", "wo",
                                             "bits", "block_o", "interpret",
                                             "act"))
def vdpe_conv_q8(x: jax.Array, rhs: jax.Array, w_scale: jax.Array, k: int,
                 stride: int, ho: int, wo: int, bits: int = 4,
                 block_o: int = BLOCK_O, interpret: bool | None = None,
                 bias: jax.Array | None = None,
                 act: str = "none") -> jax.Array:
    """Quantized-domain Mode-1 conv: raw f32 activation in, int8 in-kernel.

    ``x`` is the *unquantized* f32 activation (spatially padded as for
    ``vdpe_conv``).  The per-image DAC scale is ``dac_scale`` (XLA); the
    kernel prologue quantizes each tap window with it, and the fused
    epilogue dequantizes with ``a_scale * w_scale`` — bitwise-identical to
    quantizing in XLA and calling ``vdpe_conv``.
    """
    return _conv_q8(x, rhs, w_scale, k, stride, ho, wo, bits, block_o,
                    interpret, bias, act)


@functools.partial(jax.jit, static_argnames=("k", "stride", "ho", "wo", "x",
                                             "block_o", "interpret", "act"))
def vdpe_pack_conv_zs(x_q: jax.Array, rhs_seg: jax.Array, k: int,
                      stride: int, ho: int, wo: int, x: int,
                      block_o: int = BLOCK_O,
                      interpret: bool | None = None,
                      scale: jax.Array | None = None,
                      bias: jax.Array | None = None,
                      act: str = "none") -> jax.Array:
    """Zero-skipping Mode-2 implicit-GEMM conv (small S = K*K*D <= x).

    ``rhs_seg`` must be the dense (x, F_pad) segment-sum pack
    (ops.pack_mode2_segments) — the (y*x, F) block-diagonal operand is
    structurally rejected, same as vdpe_pack_gemm_zs: the contraction this
    kernel issues is S-deep, never y*x-deep.
    """
    d = x_q.shape[3]
    assert rhs_seg.shape[0] == x, (
        f"rhs must be the (x={x}, F) segment-sum pack, got "
        f"{rhs_seg.shape} (block-diagonal operands are rejected)")
    assert k * k * d <= x, (k, d, x)
    return _conv_call(x_q, rhs_seg, k, stride, ho, wo, block_o, interpret,
                      scale, bias, act)


@functools.partial(jax.jit, static_argnames=("k", "stride", "ho", "wo", "x",
                                             "bits", "block_o", "interpret",
                                             "act"))
def vdpe_pack_conv_zs_q8(xa: jax.Array, rhs_seg: jax.Array,
                         w_scale: jax.Array, k: int, stride: int, ho: int,
                         wo: int, x: int, bits: int = 4,
                         block_o: int = BLOCK_O,
                         interpret: bool | None = None,
                         bias: jax.Array | None = None,
                         act: str = "none") -> jax.Array:
    """Quantized-domain zero-skipping Mode-2 conv (in-kernel quantize).

    ``xa`` is the raw f32 activation; the segment-sum pack contract is
    the same as ``vdpe_pack_conv_zs``, the DAC scale as ``vdpe_conv_q8``.
    """
    d = xa.shape[3]
    assert rhs_seg.shape[0] == x, (
        f"rhs must be the (x={x}, F) segment-sum pack, got "
        f"{rhs_seg.shape} (block-diagonal operands are rejected)")
    assert k * k * d <= x, (k, d, x)
    return _conv_q8(xa, rhs_seg, w_scale, k, stride, ho, wo, bits, block_o,
                    interpret, bias, act)
