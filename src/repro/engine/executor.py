"""Forward execution of pre-packed weight-stationary plans.

The per-call work is exactly what the hardware pays per frame: quantize the
activations (the input DACs) and stream their DIV patches against the
resident DKV state.  Weight-side padding/packing happened once at plan
compile time; the whole quantize prologue AND the dequant-scale + bias +
activation epilogue are fused into the Pallas kernels, so neither the int8
activation stream nor the int32 accumulators ever round-trip HBM.

Three execution paths, one numerics contract:

* **Quantized-domain implicit-GEMM (default, the serving hot path).**
  ``forward`` / ``forward_layer`` route SC/PC conv layers to the
  fused-quantize implicit-GEMM kernels (kernels/vdpe_conv.py): the *raw
  f32* NHWC activation goes to the kernel in bands of output rows, its
  per-image DAC scale (covered-window absmax, one XLA reduction) rides
  SMEM, and the int8 quantize runs in the kernel prologue off the VMEM
  tile, so the separate XLA round/clip pass and the int8 round-trip of
  the activation through HBM disappear.  The K*K patch taps are loaded
  *inside* the kernel — the (B, P, K*K*D) im2col DIV matrix never exists
  in HBM.  Depthwise layers run the same windowed
  gather as a per-channel integer VPU contraction in plain jnp; FC layers
  quantize in the GEMM kernels' prologues (their row absmax is a cheap
  XLA reduction, the quantize itself is fused) and stream K through the
  explicitly double-buffered q8 GEMMs.  ``layer_route`` reports the
  routing per layer.

* **Quantize-then-float (the float oracle).**  ``forward_f32`` /
  ``forward_layer_f32`` keep the pre-fusion structure: activations are
  quantized by separate XLA passes, and the *quantized lattice values are
  streamed as f32* through the same implicit-GEMM kernels with f32
  accumulation.  Because int8-lattice products summed to any paper-CNN
  depth stay far below 2^24, f32 accumulation is exact and the path is
  bit-identical to the int8 path while moving 4x the operand bytes —
  it is both the bitwise oracle for the quantized-domain path and the
  float side of benchmarks/kernel_bench.py's int8-vs-float sweep.

* **im2col -> GEMM (the historical oracle).**  ``forward_im2col`` /
  ``forward_layer_im2col`` keep the materialized-DIV path next to
  kernels/ref.py's oracles; tests/test_implicit_conv.py and
  tests/test_quantized.py assert all paths are bit-identical across all
  layer kinds, strides, paddings and batch shapes.

Bitwise identity holds because every step matches elementwise: the
per-image quantization scale is the max |activation| over exactly the
patch-covered window set (every path computes it through
kconv.dac_scale; SAME-padding zeros never raise a max), the quantizer
rounds onto the same integer lattice through
kernels/common.quantize_tile, integer tap-sum accumulation is
associative (and exact in f32), and every fused epilogue applies the identical
``act(acc * scale + bias)`` expression (kernels/common.dequant_epilogue).

Batching (the serving runtime's path): all paths accept a single image
(H, W, D) or an NHWC batch (B, H, W, D).  Quantization stays *per image*
(each frame gets its own input-DAC swing); the conv kernels read the
per-image scales from SMEM by image grid index, the GEMM paths carry
per-row scale columns (kernels/vdpe_gemm.py).  For the whole-model jitted
pipeline that chases the per-layer Python dispatch out of this loop, see
engine/pipeline.py.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..cnn.layers import ConvKind
from ..core import vdp
from ..kernels import ref
from ..kernels import vdpe_conv as kconv
from ..kernels import vdpe_gemm as kern
from ..kernels.common import (qmax_for, quantize_tile,
                              round_up as _round_up, stable_scale)
from .plan import (LayerPlan, MODE_DENSE, MODE_DEPTHWISE, MODE_PACKED,
                   ModelPlan)

#: layer_route values, in routing-priority order.
ROUTE_FC_GEMM = "fc_gemm"
ROUTE_DEPTHWISE = "depthwise_vpu"
ROUTE_CONV_ZS = "conv_implicit_mode2_zs"
ROUTE_CONV_M1 = "conv_implicit_mode1"


def layer_route(lp: LayerPlan) -> str:
    """Which execution path ``forward_layer`` takes for this layer."""
    if lp.kind is ConvKind.FC:
        return ROUTE_FC_GEMM
    if lp.mode == MODE_DEPTHWISE:
        return ROUTE_DEPTHWISE
    return ROUTE_CONV_ZS if lp.mode == MODE_PACKED else ROUTE_CONV_M1


# ---------------------------------------------------------------------------
# Shared activation-side helpers
# ---------------------------------------------------------------------------

def _pad_spatial(x4: jax.Array, k: int, stride: int,
                 padding: str) -> jax.Array:
    """SAME/VALID spatial zero-padding, split exactly as vdp.im2col does."""
    if padding != "SAME":
        return x4
    _, h, w, _ = x4.shape
    ho, wo = vdp.out_hw(h, w, k, stride, padding)
    pad_h = max((ho - 1) * stride + k - h, 0)
    pad_w = max((wo - 1) * stride + k - w, 0)
    with jax.named_scope("pad"):
        return jnp.pad(x4, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                            (pad_w // 2, pad_w - pad_w // 2), (0, 0)))


def _im2col_batch(x4: jax.Array, k: int, stride: int,
                  padding: str) -> jax.Array:
    """(B, H, W, D) -> (B, P, K*K*D): per-image DIV streams, stacked."""
    return jax.vmap(lambda im: vdp.im2col(im, k, stride, padding))(x4)


def _quantize_per_image(divs: jax.Array, bits: int,
                        ) -> Tuple[jax.Array, jax.Array]:
    """Per-image symmetric quantization of (B, P, S) DIV streams.

    Each image keeps its own input-DAC swing — identical to running
    vdp.quantize_symmetric on every image separately (max is exact, the
    divide/round/clip are elementwise), which is what makes the folded
    batch bit-identical to the per-image loop.  The oracle paths' XLA-side
    twin of the q8 kernels' fused prologue.
    """
    scale = stable_scale(jnp.maximum(jnp.max(jnp.abs(divs), axis=(1, 2)),
                                     1e-12) * vdp.inv_qmax(bits))
    return quantize_tile(divs, scale[:, None, None], bits), scale


def _row_dac_scales(flat: jax.Array, bits: int) -> jax.Array:
    """Per-row DAC scales of a (B, S) stream (the q8 GEMM prologue input)."""
    with jax.named_scope("dac_scale"):
        return stable_scale(jnp.maximum(jnp.max(jnp.abs(flat), axis=1),
                                        1e-12) * vdp.inv_qmax(bits))


# ---------------------------------------------------------------------------
# Quantized-domain implicit-GEMM conv path (the serving hot path)
# ---------------------------------------------------------------------------

def _forward_conv_implicit(lp: LayerPlan, x4: jax.Array, point,
                           interpret: bool | None) -> jax.Array:
    """SC/PC layer through the fused-quantize implicit-GEMM kernels.

    The raw f32 activation goes straight to the kernel wrapper; its
    per-image DAC scale is one XLA reduction (kconv.dac_scale) and the
    int8 quantize happens in the kernel prologue (no int8 round-trip of
    the activation through HBM).
    """
    b, h, w, din = x4.shape
    k = lp.k
    d = lp.s // (k * k)
    if d != din:
        raise ValueError(f"layer {lp.name!r} expects contraction {lp.s}, "
                         f"got input stream of width {k * k * din}")
    ho, wo = vdp.out_hw(h, w, k, lp.stride, lp.padding)
    x4p = _pad_spatial(x4, k, lp.stride, lp.padding)
    if lp.mode == MODE_PACKED:
        out = kconv.vdpe_pack_conv_zs_q8(
            x4p, lp.rhs, lp.w_scale, k, lp.stride, ho, wo, x=point.x,
            bits=point.bits, block_o=point.block_o, interpret=interpret,
            bias=lp.bias, act=lp.act)
    else:
        assert lp.mode == MODE_DENSE
        out = kconv.vdpe_conv_q8(
            x4p, lp.rhs, lp.w_scale, k, lp.stride, ho, wo,
            bits=point.bits, block_o=point.block_o, interpret=interpret,
            bias=lp.bias, act=lp.act)
    with jax.named_scope("out"):
        return out[:, :, :lp.f].reshape(b, ho, wo, lp.f)


def _forward_depthwise(lp: LayerPlan, x4: jax.Array, point) -> jax.Array:
    """Per-channel VPU path, windowed — no materialized (B, P, K*K, D).

    Depthwise kernels pair channel c's patches with channel c's single DKV
    row, so the contraction degenerates to K*K tap-wise multiply-adds over
    the strided windows.  Quantization is per image AND per channel (each
    channel of each frame is an independent VDP), matching
    core/vdp.depthwise_conv2d_vdp bit-for-bit: same covered-set max, and
    the integer tap sum equals the einsum's contraction exactly.
    """
    b, h, w, d = x4.shape
    k = lp.k
    ho, wo = vdp.out_hw(h, w, k, lp.stride, lp.padding)
    x4p = _pad_spatial(x4, k, lp.stride, lp.padding)
    a_scale = kconv.dac_scale(x4p, k, lp.stride, ho, wo, point.bits,
                              per_channel=True)                  # (B, D)
    with jax.named_scope("depthwise"):
        x_q = quantize_tile(x4p, a_scale[:, None, None, :],
                            point.bits).astype(jnp.int32)
        acc = jnp.zeros((b, ho, wo, d), jnp.int32)
        for kk in range(k * k):
            di, dj = divmod(kk, k)
            win = kconv.tap_window(x_q, di, dj, lp.stride, ho, wo)
            acc = acc + win * lp.rhs[:, kk].astype(jnp.int32)[None, None,
                                                              None]
        return ref.epilogue_ref(
            acc, (a_scale * lp.w_scale[None, :])[:, None, None, :],
            None if lp.bias is None else lp.bias[None, None, None, :],
            lp.act)


def forward_layer(plan: ModelPlan, lp: LayerPlan, x: jax.Array,
                  interpret: bool | None = None) -> jax.Array:
    """One layer through its pre-packed kernel with the fused quantize
    prologue and dequant epilogue.

    x: (H, W, D) or batched (B, H, W, D) for conv layers; a flat feature
    vector, (H, W, D) map, batched rows (B, S) or batched maps for FC.
    Conv layers run the quantized-domain implicit-GEMM path (module
    docstring); FC falls through to the q8 GEMM path.  Batched outputs
    are bit-identical to the per-image loop AND to forward_layer_f32 /
    forward_layer_im2col.

    Each layer executes at its *own* operating point (``lp.point``):
    planner-compiled plans carry heterogeneous per-layer packing geometry
    while fixed-point plans repeat the model point.
    """
    point = lp.point
    if lp.kind is not ConvKind.FC:
        batched = x.ndim == 4
        x4 = x if batched else x[None]
        if lp.mode == MODE_DEPTHWISE:
            out = _forward_depthwise(lp, x4, point)
        else:
            out = _forward_conv_implicit(lp, x4, point, interpret)
        return out if batched else out[0]
    return _forward_fc(plan, lp, x, interpret)


def _fc_flatten(lp: LayerPlan, x: jax.Array) -> jax.Array:
    """FC input: flatten maps/vectors to (B, S) rows."""
    if x.ndim == 4:                       # batched feature maps
        flat = x.reshape(x.shape[0], -1)
    elif x.ndim == 2:                     # rows are already the batch
        flat = x
    else:                                 # single map / vector -> (1, S)
        flat = x.reshape(1, -1)
    if flat.shape[1] != lp.s:
        raise ValueError(f"layer {lp.name!r} expects contraction {lp.s}, "
                         f"got input stream of width {flat.shape[1]}")
    return flat


def _forward_fc(plan: ModelPlan, lp: LayerPlan, x: jax.Array,
                interpret: bool | None) -> jax.Array:
    """FC layer: (B, S) rows through the fused-quantize q8 GEMMs.

    The per-row DAC scales (a cheap XLA row reduction — a K-blocked GEMM
    tile cannot see its whole row) go in as data; the divide/round/clip
    quantize itself runs in the kernel prologue and the K axis streams
    through explicitly double-buffered VMEM slots.  Pad rows carry scale
    1 so the prologue quantizes their zeros to zero.
    """
    point = lp.point
    flat = _fc_flatten(lp, x)
    b = flat.shape[0]
    a_scale = _row_dac_scales(flat, point.bits)
    bp = _round_up(b, point.block_b)
    a_rows = jnp.pad(a_scale, (0, bp - b), constant_values=1.0)
    if lp.mode == MODE_PACKED:
        lhs = jnp.pad(flat, ((0, bp - b), (0, point.x - lp.s)))
        out = kern.vdpe_pack_gemm_zs_q8(
            lhs, lp.rhs, a_rows, lp.w_scale, bits=point.bits,
            block_b=point.block_b, block_o=point.block_o,
            interpret=interpret, bias=lp.bias, act=lp.act)
    else:
        assert lp.mode == MODE_DENSE
        ss = lp.rhs.shape[0]
        lhs = jnp.pad(flat, ((0, bp - b), (0, ss - lp.s)))
        out = kern.vdpe_gemm_q8(
            lhs, lp.rhs, a_rows, lp.w_scale, bits=point.bits,
            block_b=point.block_b, block_o=point.block_o,
            block_k=point.block_k, interpret=interpret,
            bias=lp.bias, act=lp.act)
    with jax.named_scope("out"):
        return out[:b, :lp.f]             # FC single image stays (1, F)


def forward(plan: ModelPlan, x: jax.Array,
            interpret: bool | None = None) -> jax.Array:
    """Run activations through every layer of a compiled plan (eager loop).

    Accepts one image (H, W, D) or an NHWC batch (B, H, W, D); batched
    outputs are bit-identical to looping `forward` over the images.  This
    is one Python dispatch per layer — the serving hot path uses the
    whole-model jitted pipeline instead (engine.forward_jit).
    """
    for lp in plan.layers:
        x = forward_layer(plan, lp, x, interpret=interpret)
    return x


# ---------------------------------------------------------------------------
# Quantize-then-float path: the float oracle (and the bench's float side)
# ---------------------------------------------------------------------------

def _forward_conv_implicit_f32(lp: LayerPlan, x4: jax.Array, point,
                               interpret: bool | None) -> jax.Array:
    """SC/PC float oracle: XLA quantize passes + f32 operand streams.

    The pre-fusion structure kept verbatim: covered-window absmax and
    round/clip run as separate XLA passes, then the *lattice values* are
    streamed as f32 (4x the bytes of the int8 stream) through the same
    implicit-GEMM kernels with exact f32 accumulation.
    """
    b, h, w, din = x4.shape
    k = lp.k
    d = lp.s // (k * k)
    if d != din:
        raise ValueError(f"layer {lp.name!r} expects contraction {lp.s}, "
                         f"got input stream of width {k * k * din}")
    ho, wo = vdp.out_hw(h, w, k, lp.stride, lp.padding)
    x4p = _pad_spatial(x4, k, lp.stride, lp.padding)
    a_scale = kconv.dac_scale(x4p, k, lp.stride, ho, wo, point.bits)  # (B,)
    x_q = quantize_tile(x4p, a_scale[:, None, None, None],
                        point.bits).astype(jnp.float32)
    rhs_f = lp.rhs.astype(jnp.float32)
    scale = a_scale * lp.w_scale
    # one image rides the scalar-SMEM epilogue; a batch carries per-image
    # scales through the grid-indexed SMEM variant
    scale_arg = scale[0] if b == 1 else scale
    if lp.mode == MODE_PACKED:
        out = kconv.vdpe_pack_conv_zs(
            x_q, rhs_f, k, lp.stride, ho, wo, x=point.x,
            block_o=point.block_o, interpret=interpret,
            scale=scale_arg, bias=lp.bias, act=lp.act)
    else:
        assert lp.mode == MODE_DENSE
        out = kconv.vdpe_conv(
            x_q, rhs_f, k, lp.stride, ho, wo, block_o=point.block_o,
            interpret=interpret, scale=scale_arg, bias=lp.bias, act=lp.act)
    return out[:, :, :lp.f].reshape(b, ho, wo, lp.f)


def _forward_depthwise_f32(lp: LayerPlan, x4: jax.Array, point) -> jax.Array:
    """Depthwise float oracle: lattice values accumulated exactly in f32."""
    b, h, w, d = x4.shape
    k = lp.k
    ho, wo = vdp.out_hw(h, w, k, lp.stride, lp.padding)
    x4p = _pad_spatial(x4, k, lp.stride, lp.padding)
    a_scale = kconv.dac_scale(x4p, k, lp.stride, ho, wo, point.bits,
                              per_channel=True)                  # (B, D)
    x_q = quantize_tile(x4p, a_scale[:, None, None, :],
                        point.bits).astype(jnp.float32)
    acc = jnp.zeros((b, ho, wo, d), jnp.float32)
    for kk in range(k * k):
        di, dj = divmod(kk, k)
        win = kconv.tap_window(x_q, di, dj, lp.stride, ho, wo)
        acc = acc + win * lp.rhs[:, kk].astype(jnp.float32)[None, None, None]
    return ref.epilogue_ref(
        acc, (a_scale * lp.w_scale[None, :])[:, None, None, :],
        None if lp.bias is None else lp.bias[None, None, None, :],
        lp.act)


def _forward_fc_prequantized(lp: LayerPlan, x: jax.Array,
                             interpret: bool | None,
                             lattice_f32: bool) -> jax.Array:
    """Shared FC oracle body: XLA quantize, pre-quantized GEMM kernels.

    ``lattice_f32`` picks the operand domain — int8 (the historical
    im2col-era path) or the same lattice streamed as f32 (the float
    oracle); everything else (padding, per-row dequant scales, mode
    routing) is identical, which is the point: the oracles cannot drift
    apart structurally.
    """
    point = lp.point
    flat = _fc_flatten(lp, x)
    divs_q, a_scale = _quantize_per_image(flat[:, None, :], point.bits)
    b = flat.shape[0]
    lhs = divs_q.reshape(b, lp.s)
    rhs = lp.rhs
    if lattice_f32:
        lhs = lhs.astype(jnp.float32)
        rhs = rhs.astype(jnp.float32)
    bp = _round_up(b, point.block_b)
    scale = a_scale * lp.w_scale
    if b == 1:
        scale_rows = scale[0]
    else:
        scale_rows = jnp.pad(scale, (0, bp - b))
    if lp.mode == MODE_PACKED:
        lhs = jnp.pad(lhs, ((0, bp - b), (0, point.x - lp.s)))
        out = kern.vdpe_pack_gemm_zs(
            lhs, rhs, block_b=point.block_b, block_o=point.block_o,
            interpret=interpret, scale=scale_rows, bias=lp.bias, act=lp.act)
    else:
        assert lp.mode == MODE_DENSE
        ss = lp.rhs.shape[0]
        lhs = jnp.pad(lhs, ((0, bp - b), (0, ss - lp.s)))
        out = kern.vdpe_gemm(
            lhs, rhs, block_b=point.block_b, block_o=point.block_o,
            block_k=point.block_k, interpret=interpret,
            scale=scale_rows, bias=lp.bias, act=lp.act)
    return out[:b, :lp.f]


def _forward_fc_f32(plan: ModelPlan, lp: LayerPlan, x: jax.Array,
                    interpret: bool | None) -> jax.Array:
    """FC float oracle: the shared body with f32 lattice streams."""
    return _forward_fc_prequantized(lp, x, interpret, lattice_f32=True)


def forward_layer_f32(plan: ModelPlan, lp: LayerPlan, x: jax.Array,
                      interpret: bool | None = None) -> jax.Array:
    """One layer through the quantize-then-float path (module docstring).

    Bit-identical to ``forward_layer`` while streaming f32 operands —
    the float side of the int8-vs-float kernel bench and the oracle the
    quantized-domain tests hold the int8 path against.
    """
    point = lp.point
    if lp.kind is ConvKind.FC:
        return _forward_fc_f32(plan, lp, x, interpret)
    batched = x.ndim == 4
    x4 = x if batched else x[None]
    if lp.mode == MODE_DEPTHWISE:
        out = _forward_depthwise_f32(lp, x4, point)
    else:
        out = _forward_conv_implicit_f32(lp, x4, point, interpret)
    return out if batched else out[0]


def forward_f32(plan: ModelPlan, x: jax.Array,
                interpret: bool | None = None) -> jax.Array:
    """Whole-model quantize-then-float oracle loop."""
    for lp in plan.layers:
        x = forward_layer_f32(plan, lp, x, interpret=interpret)
    return x


# ---------------------------------------------------------------------------
# im2col -> GEMM path: the historical bitwise oracle
# ---------------------------------------------------------------------------

def _forward_depthwise_im2col(lp: LayerPlan, x4: jax.Array,
                              point) -> jax.Array:
    """Depthwise oracle: materialized (B, P, K*K, D) + einsum contraction."""
    b, h, w, d = x4.shape
    k = lp.k
    divs = _im2col_batch(x4, k, lp.stride, lp.padding)    # (B, P, K*K*D)
    p = divs.shape[1]
    divs = divs.reshape(b, p, k * k, d)
    a_scale = stable_scale(jnp.maximum(jnp.max(jnp.abs(divs), axis=(1, 2)),
                                       1e-12)
                           * vdp.inv_qmax(point.bits))       # (B, D)
    divs_q = quantize_tile(divs, a_scale[:, None, None, :], point.bits)
    acc = jnp.einsum("bpkc,ck->bpc", divs_q.astype(jnp.int32),
                     lp.rhs.astype(jnp.int32))
    r = ref.epilogue_ref(acc, (a_scale * lp.w_scale[None, :])[:, None, :],
                         None if lp.bias is None else lp.bias[None, None, :],
                         lp.act)
    ho, wo = vdp.out_hw(h, w, k, lp.stride, lp.padding)
    return r.reshape(b, ho, wo, d)


def forward_layer_im2col(plan: ModelPlan, lp: LayerPlan, x: jax.Array,
                         interpret: bool | None = None) -> jax.Array:
    """One layer through the materialized im2col -> GEMM path.

    The pre-implicit-GEMM execution path, kept verbatim as the bitwise
    oracle (and kernel_bench baseline) for forward_layer: it builds the
    full (B, P, K*K*D) DIV matrix in HBM and folds the batch into one GEMM
    position stream with per-row dequant scales.
    """
    point = lp.point
    if lp.kind is ConvKind.FC:
        return _forward_fc_im2col(plan, lp, x, interpret)
    batched = x.ndim == 4
    x4 = x if batched else x[None]
    if lp.mode == MODE_DEPTHWISE:
        out = _forward_depthwise_im2col(lp, x4, point)
        return out if batched else out[0]
    divs = _im2col_batch(x4, lp.k, lp.stride, lp.padding)  # (B, P, S)
    spatial = vdp.out_hw(x4.shape[1], x4.shape[2], lp.k, lp.stride,
                         lp.padding)
    if divs.shape[2] != lp.s:
        raise ValueError(f"layer {lp.name!r} expects contraction {lp.s}, "
                         f"got input stream of width {divs.shape[2]}")
    b, p, _ = divs.shape
    divs_q, a_scale = _quantize_per_image(divs, point.bits)
    lhs = divs_q.reshape(b * p, lp.s)
    bp = b * p
    pp = _round_up(bp, point.block_b)
    # fold the batch into the position stream; each image's rows carry its
    # own dequant scale into the fused epilogue.  One image has one scale,
    # so it rides the cheaper scalar-SMEM epilogue path.
    scale = a_scale * lp.w_scale
    if b == 1:
        scale_rows = scale[0]
    else:
        scale_rows = jnp.pad(jnp.repeat(scale, p), (0, pp - bp))
    if lp.mode == MODE_PACKED:
        lhs = jnp.pad(lhs, ((0, pp - bp), (0, point.x - lp.s)))
        out = kern.vdpe_pack_gemm_zs(
            lhs, lp.rhs, block_b=point.block_b, block_o=point.block_o,
            interpret=interpret, scale=scale_rows, bias=lp.bias, act=lp.act)
    else:
        assert lp.mode == MODE_DENSE
        ss = lp.rhs.shape[0]
        lhs = jnp.pad(lhs, ((0, pp - bp), (0, ss - lp.s)))
        out = kern.vdpe_gemm(
            lhs, lp.rhs, block_b=point.block_b, block_o=point.block_o,
            block_k=point.block_k, interpret=interpret,
            scale=scale_rows, bias=lp.bias, act=lp.act)
    out = out[:bp, :lp.f].reshape(b, *spatial, lp.f)
    return out if batched else out[0]


def _forward_fc_im2col(plan: ModelPlan, lp: LayerPlan, x: jax.Array,
                       interpret: bool | None) -> jax.Array:
    """FC oracle: the shared body with int8 operand streams."""
    return _forward_fc_prequantized(lp, x, interpret, lattice_f32=False)


def forward_im2col(plan: ModelPlan, x: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """Whole-model oracle loop over forward_layer_im2col."""
    for lp in plan.layers:
        x = forward_layer_im2col(plan, lp, x, interpret=interpret)
    return x


# ---------------------------------------------------------------------------
# Guarded execution path: value-corruption hook + ABFT/guard detection (SDC)
# ---------------------------------------------------------------------------
#
# The serving hot path fuses the int32 accumulators inside the Pallas
# kernels — they never exist as host-visible arrays, so there is nowhere to
# corrupt them or checksum them.  The guarded path is a fourth execution
# path with the SAME numerics contract as the three above: the im2col
# quantize prologue (shared helpers), an *explicit* XLA int32 GEMM whose
# accumulators are materialized, and the identical fused-epilogue
# expression (ref.epilogue_ref).  Integer accumulation is order-invariant
# (int32 addition is associative and commutative, wraparound included), so
# the guarded path is bit-identical to `forward` / `forward_jit` when the
# corruption arguments are null — which is what lets the dispatcher serve
# real traffic through it and lets recovery claim *bitwise* equality with
# the fault-free run.
#
# Between GEMM and epilogue the path (a) applies the fault injector's
# value corruption to the accumulators (deterministic under the dispatch
# seed; exactly zero effect when the corruption args are null) and (b)
# verifies the accumulators with Huang-Abraham-style ABFT checksums, a
# B-bit accumulation range guard, and a weight-imprint checksum, returning
# a per-layer detector bitmask alongside the activations.
#
# Detector algebra (all exact in the ring Z/2^32 — int32 wraparound is
# deterministic two's-complement, and GEMM is linear mod 2^32):
#   column check:  (sum_r lhs[r, :]) @ rhs == sum_r acc[r, :]
#   row check:     lhs @ (sum_f rhs[:, f]) == sum_f acc[:, f]
# A single corrupted element acc[i, j] += d (d != 0 mod 2^32) shifts
# column-sum j and row-sum i by exactly d, so it is ALWAYS detected by
# both checks — no false negatives for single-element corruption, and no
# false positives ever (the checks are identities, not tolerances).  Note
# the checks verify acc *against the rhs as loaded*: a corrupted weight
# imprint yields a GEMM that is self-consistent with the wrong weights,
# which is exactly why the weight-imprint checksum (vs a trace-time golden
# of the pristine rhs) exists as a separate detector.

#: detector bitmask bits (per-layer flags word)
DET_ABFT_COL = 1     # column-checksum mismatch
DET_ABFT_ROW = 2     # row-checksum mismatch
DET_RANGE = 4        # accumulator outside the B-bit accumulation bound
DET_WEIGHT = 8       # resident weight imprint differs from golden

_DETECTOR_NAMES = {DET_ABFT_COL: "abft_col", DET_ABFT_ROW: "abft_row",
                   DET_RANGE: "range_guard", DET_WEIGHT: "weight_checksum"}


def detector_names(mask: int) -> Tuple[str, ...]:
    """Human-readable detector names for a flags bitmask."""
    return tuple(name for bit, name in sorted(_DETECTOR_NAMES.items())
                 if mask & bit)


@dataclasses.dataclass(frozen=True)
class IntegrityPolicy:
    """Which detectors run, and how often (hashable: keys jit caches).

    ``check_every=k`` checksums layers 0, k, 2k, ... (cadence trades
    detection latency against overhead); ``check_every=0`` disables all
    verification (the silent-corruption baseline).  The ABFT identity
    catches any single corrupted accumulator element exactly; the range
    guard bounds |acc| by qmax^2 * depth (a cheap always-on sanity net);
    the weight checksum compares the resident imprint against a trace-time
    golden (the only detector that can see STUCK_MRR weight corruption —
    ABFT verifies the GEMM against the weights *as loaded*).
    """
    abft: bool = True
    range_guard: bool = True
    weight_checksum: bool = True
    check_every: int = 1

    def __post_init__(self) -> None:
        if self.check_every < 0:
            raise ValueError(
                f"check_every must be >= 0, got {self.check_every}")


DEFAULT_POLICY = IntegrityPolicy()
DISABLED_POLICY = IntegrityPolicy(abft=False, range_guard=False,
                                  weight_checksum=False, check_every=0)


class CorruptionArgs(NamedTuple):
    """Traced corruption parameters (jit *arguments*, not constants: one
    guarded executable serves both clean and corrupted dispatches)."""
    key: jax.Array        # PRNG key; folded with the layer index
    sigma_lsb: jax.Array  # ANALOG_NOISE: Gaussian sigma in LSBs
    gain: jax.Array       # THERMAL_DETUNE: multiplicative drift
    bias_lsb: jax.Array   # THERMAL_DETUNE: additive drift in LSBs
    flip_prob: jax.Array  # ADC_BITFLIP: per-element flip probability


def corruption_args(seed: int = 0, sigma_lsb: float = 0.0, gain: float = 1.0,
                    bias_lsb: float = 0.0, flip_prob: float = 0.0,
                    ) -> CorruptionArgs:
    return CorruptionArgs(
        key=jax.random.PRNGKey(seed),
        sigma_lsb=jnp.float32(sigma_lsb), gain=jnp.float32(gain),
        bias_lsb=jnp.float32(bias_lsb), flip_prob=jnp.float32(flip_prob))


def null_corruption_args() -> CorruptionArgs:
    """The identity corruption (a clean dispatch)."""
    return corruption_args()


def corrupt_accumulators(acc: jax.Array, cargs: CorruptionArgs,
                         salt: int) -> jax.Array:
    """Apply the analog fault model to materialized int32 accumulators.

    Three physically-motivated corruptions, each an *exact identity* when
    its parameter is at rest (so a null CorruptionArgs returns ``acc``
    unchanged, bit for bit):

    * ANALOG_NOISE:   acc += round(N(0, sigma_lsb))       per element
    * THERMAL_DETUNE: acc += round(acc*(gain-1) + bias)   (gain/offset)
    * ADC_BITFLIP:    acc ^= (1 << low_bit)               w.p. flip_prob

    All RNG derives from fold_in(cargs.key, salt) — the layer index salts
    the per-dispatch key, so replaying a dispatch corrupts identically.
    The whole block sits under a lax.cond on the traced activity
    predicate: clean dispatches skip the RNG entirely.
    """
    def _apply(a: jax.Array) -> jax.Array:
        key = jax.random.fold_in(cargs.key, salt)
        k_noise, k_flip, k_bit = jax.random.split(key, 3)
        noise = jnp.round(jax.random.normal(k_noise, a.shape)
                          * cargs.sigma_lsb).astype(jnp.int32)
        detune = jnp.round(a.astype(jnp.float32) * (cargs.gain - 1.0)
                           + cargs.bias_lsb).astype(jnp.int32)
        flips = jax.random.uniform(k_flip, a.shape) < cargs.flip_prob
        bit = jax.random.randint(k_bit, a.shape, 0, 12)
        mask = jnp.where(flips, jnp.int32(1) << bit, jnp.int32(0))
        return jax.lax.bitwise_xor(a + noise + detune, mask)

    active = ((cargs.sigma_lsb > 0) | (cargs.gain != 1.0)
              | (cargs.bias_lsb != 0) | (cargs.flip_prob > 0))
    return jax.lax.cond(active, _apply, lambda a: a, acc)


def abft_flags(lhs: jax.Array, rhs: jax.Array, acc: jax.Array) -> jax.Array:
    """ABFT row/column checksum verification of ``acc == lhs @ rhs``.

    Exact identities in Z/2^32 (module comment); cost is two rank-1
    checks, O(BF + BS + SF) vs the GEMM's O(BSF).  Returns an int32
    DET_ABFT_* bitmask (0 when both checks pass).
    """
    li = lhs.astype(jnp.int32)
    ri = rhs.astype(jnp.int32)
    col_ok = jnp.all(jnp.matmul(jnp.sum(li, axis=0), ri)
                     == jnp.sum(acc, axis=0))
    row_ok = jnp.all(jnp.matmul(li, jnp.sum(ri, axis=1))
                     == jnp.sum(acc, axis=1))
    return (jnp.where(col_ok, 0, DET_ABFT_COL)
            | jnp.where(row_ok, 0, DET_ABFT_ROW)).astype(jnp.int32)


def range_guard_flag(acc: jax.Array, bound: int) -> jax.Array:
    """DET_RANGE iff any |acc| exceeds the B-bit accumulation bound.

    A depth-S contraction of qmax-bounded integers satisfies
    |acc| <= qmax^2 * S exactly (equality reachable, so the guard is
    strict).  Two comparisons, not abs(): |INT32_MIN| wraps negative.
    """
    b = jnp.int32(bound)
    exceeds = jnp.any((acc > b) | (acc < -b))
    return jnp.where(exceeds, DET_RANGE, 0).astype(jnp.int32)


def weight_imprint_checksum(rhs: jax.Array) -> jax.Array:
    """Position-weighted int32 checksum of a resident weight imprint.

    The (i mod 97)+1 weights make the sum sensitive to *where* an element
    changed, not just its value (a plain sum misses compensating swaps).
    Compared against a golden computed from the pristine rhs at guarded-
    pipeline build time — the one detector that catches STUCK_MRR faults,
    since ABFT verifies the GEMM against the weights as loaded.
    """
    flat = rhs.astype(jnp.int32).ravel()
    pos = (jnp.arange(flat.shape[0], dtype=jnp.int32) % 97) + 1
    return jnp.sum(flat * pos)


def _integrity_flags(lhs: jax.Array, rhs: jax.Array, acc: jax.Array,
                     bound: int, policy: IntegrityPolicy,
                     golden: Optional[int]) -> jax.Array:
    flags = jnp.int32(0)
    if policy.abft:
        flags = flags | abft_flags(lhs, rhs, acc)
    if policy.range_guard:
        flags = flags | range_guard_flag(acc, bound)
    if policy.weight_checksum and golden is not None:
        ok = weight_imprint_checksum(rhs) == jnp.int32(golden)
        flags = flags | jnp.where(ok, 0, DET_WEIGHT).astype(jnp.int32)
    return flags


def _guarded_conv(lp: LayerPlan, x4: jax.Array, cargs: CorruptionArgs,
                  salt: int, check: bool, policy: IntegrityPolicy,
                  golden: Optional[int]) -> Tuple[jax.Array, jax.Array]:
    """SC/PC conv: the im2col structure with a materialized int32 GEMM.

    Bitwise-identical to the kernel paths: shared quantize helpers, exact
    integer GEMM (order-invariant), identical epilogue expression.  The
    packed Mode-2 rhs (ops.pack_mode2_segments) is a dense (x, F) matrix
    with each column's weights at natural offset, so the same plain GEMM
    covers MODE_PACKED and MODE_DENSE.
    """
    point = lp.point
    divs = _im2col_batch(x4, lp.k, lp.stride, lp.padding)   # (B, P, S)
    spatial = vdp.out_hw(x4.shape[1], x4.shape[2], lp.k, lp.stride,
                         lp.padding)
    if divs.shape[2] != lp.s:
        raise ValueError(f"layer {lp.name!r} expects contraction {lp.s}, "
                         f"got input stream of width {divs.shape[2]}")
    b, p, _ = divs.shape
    divs_q, a_scale = _quantize_per_image(divs, point.bits)
    ss = lp.rhs.shape[0]                       # x (packed) or S_pad (dense)
    lhs = jnp.pad(divs_q.reshape(b * p, lp.s),
                  ((0, 0), (0, ss - lp.s))).astype(jnp.int32)
    rhs = lp.rhs.astype(jnp.int32)
    acc = jnp.matmul(lhs, rhs)                 # (B*P, F_pad) int32
    acc = corrupt_accumulators(acc, cargs, salt)
    qmax = qmax_for(point.bits)
    flags = (_integrity_flags(lhs, rhs, acc, qmax * qmax * lp.s,
                              policy, golden)
             if check else jnp.int32(0))
    acc3 = acc[:, :lp.f].reshape(b, p, lp.f)
    out = ref.epilogue_ref(
        acc3, (a_scale * lp.w_scale)[:, None, None],
        None if lp.bias is None else lp.bias[0][None, None, :lp.f],
        lp.act)
    return out.reshape(b, *spatial, lp.f), flags


def _guarded_depthwise(lp: LayerPlan, x4: jax.Array, cargs: CorruptionArgs,
                       salt: int, check: bool, policy: IntegrityPolicy,
                       golden: Optional[int],
                       ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise: the windowed VPU path with materialized tap windows.

    The ABFT analogue checksums the position axis: summing the tap-sum
    identity over all spatial positions gives
        sum_p acc[b, p, c] == sum_kk (sum_p win_kk[b, p, c]) * rhs[c, kk]
    — linear mod 2^32, so any single corrupted accumulator shifts its
    (b, c) checksum by its nonzero delta and is always detected.
    """
    point = lp.point
    b, h, w, d = x4.shape
    k = lp.k
    ho, wo = vdp.out_hw(h, w, k, lp.stride, lp.padding)
    x4p = _pad_spatial(x4, k, lp.stride, lp.padding)
    a_scale = kconv.dac_scale(x4p, k, lp.stride, ho, wo, point.bits,
                              per_channel=True)                  # (B, D)
    x_q = quantize_tile(x4p, a_scale[:, None, None, :],
                        point.bits).astype(jnp.int32)
    rhs = lp.rhs.astype(jnp.int32)
    wins = []
    acc = jnp.zeros((b, ho, wo, d), jnp.int32)
    for kk in range(k * k):
        di, dj = divmod(kk, k)
        win = kconv.tap_window(x_q, di, dj, lp.stride, ho, wo)
        wins.append(win)
        acc = acc + win * rhs[:, kk][None, None, None]
    acc = corrupt_accumulators(acc, cargs, salt)
    if check:
        flags = jnp.int32(0)
        if policy.abft:
            expect = sum(wins[kk].sum(axis=(1, 2)) * rhs[:, kk][None]
                         for kk in range(k * k))
            ok = jnp.all(expect == acc.sum(axis=(1, 2)))
            flags = flags | jnp.where(ok, 0, DET_ABFT_COL).astype(jnp.int32)
        if policy.range_guard:
            qmax = qmax_for(point.bits)
            flags = flags | range_guard_flag(acc, qmax * qmax * k * k)
        if policy.weight_checksum and golden is not None:
            ok = weight_imprint_checksum(rhs) == jnp.int32(golden)
            flags = flags | jnp.where(ok, 0, DET_WEIGHT).astype(jnp.int32)
    else:
        flags = jnp.int32(0)
    out = ref.epilogue_ref(
        acc, (a_scale * lp.w_scale[None, :])[:, None, None, :],
        None if lp.bias is None else lp.bias[None, None, None, :],
        lp.act)
    return out, flags


def _guarded_fc(lp: LayerPlan, x: jax.Array, cargs: CorruptionArgs,
                salt: int, check: bool, policy: IntegrityPolicy,
                golden: Optional[int]) -> Tuple[jax.Array, jax.Array]:
    """FC: the pre-quantized GEMM structure with materialized accumulators."""
    point = lp.point
    flat = _fc_flatten(lp, x)
    divs_q, a_scale = _quantize_per_image(flat[:, None, :], point.bits)
    b = flat.shape[0]
    ss = lp.rhs.shape[0]                       # x (packed) or S_pad (dense)
    lhs = jnp.pad(divs_q.reshape(b, lp.s),
                  ((0, 0), (0, ss - lp.s))).astype(jnp.int32)
    rhs = lp.rhs.astype(jnp.int32)
    acc = jnp.matmul(lhs, rhs)                 # (B, F_pad) int32
    acc = corrupt_accumulators(acc, cargs, salt)
    qmax = qmax_for(point.bits)
    flags = (_integrity_flags(lhs, rhs, acc, qmax * qmax * lp.s,
                              policy, golden)
             if check else jnp.int32(0))
    out = ref.epilogue_ref(
        acc[:, :lp.f], (a_scale * lp.w_scale)[:, None],
        None if lp.bias is None else lp.bias[:, :lp.f], lp.act)
    return out, flags


def forward_layer_guarded(plan: ModelPlan, lp: LayerPlan, x: jax.Array,
                          cargs: CorruptionArgs, salt: int = 0,
                          check: bool = True,
                          policy: IntegrityPolicy = DEFAULT_POLICY,
                          golden: Optional[int] = None,
                          ) -> Tuple[jax.Array, jax.Array]:
    """One layer through the guarded path: (activations, detector flags).

    Bit-identical to ``forward_layer`` when ``cargs`` is null (the module
    comment's argument); with active corruption the int32 accumulators are
    corrupted *before* the epilogue — exactly where the analog faults land
    in hardware — and the detectors (when ``check``) verify them.  ``salt``
    (normally the layer index) decorrelates per-layer corruption under one
    dispatch key; ``golden`` is the trace-time weight-imprint checksum.
    ``check``/``policy``/``golden``/``salt`` are static: the flags math
    traces away entirely for unchecked layers.
    """
    if lp.kind is ConvKind.FC:
        return _guarded_fc(lp, x, cargs, salt, check, policy, golden)
    batched = x.ndim == 4
    x4 = x if batched else x[None]
    if lp.mode == MODE_DEPTHWISE:
        out, flags = _guarded_depthwise(lp, x4, cargs, salt, check, policy,
                                        golden)
    else:
        out, flags = _guarded_conv(lp, x4, cargs, salt, check, policy,
                                   golden)
    return (out if batched else out[0]), flags
