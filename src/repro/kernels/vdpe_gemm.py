"""Pallas TPU kernels for Mode-1 / Mode-2 VDPE GEMMs.

Hardware adaptation (EXPERIMENTS.md §Perf): the photonic VDPE's fixed N
optical lanes map onto the MXU's fixed 128-wide contraction lanes.  A small
contraction (S << 128) wastes MXU lanes exactly the way S < N strands MRRs
in the paper; Mode-2 re-aggregation maps onto *segment packing*: y small
DKVs occupy disjoint row-segments of one 128-deep K block, and one MXU pass
produces y independent dot products.

Kernels:

* ``vdpe_gemm_q8`` — Mode 1, quantized-domain serving path: the f32 DIV
  stream enters the kernel raw and is quantized onto the int8 lattice *in
  the prologue* (per-row DAC scales — the batched engine's per-image
  swings), contracted int8 x int8 -> int32 against the plan's resident
  int8 operand, and dequantized by the fused epilogue.  The K axis is
  streamed *inside* the kernel with explicit double buffering: lhs/rhs
  live in HBM (``memory_space=ANY``) and the kernel prefetches K-block
  ``kk+1`` into the alternate VMEM slot while the MXU contracts block
  ``kk`` — one grid step per output tile instead of n_k, and the int32
  accumulator lives its whole life in registers/VMEM.

* ``vdpe_pack_gemm_zs_q8`` — Mode 2, quantized-domain + zero-skipping:
  same fused quantize prologue and epilogue; the (block_b, x) DIV blocks
  and the segment-sum rhs ride the Pallas pipeline as BlockSpec operands.

* ``vdpe_gemm`` — Mode 1: K-blocked dense int8 x int8 -> int32 GEMM over
  *pre-quantized* operands (the S >= N slice path).  Also accepts f32
  operands on the quantized lattice (f32 accumulation is exact for int8
  products, so it doubles as the quantize-then-float oracle's GEMM).

* ``vdpe_pack_gemm_zs`` — Mode 2, zero-skipping: because Mode-2 lane
  segments are *column-disjoint* (kernel f lives only in segment f mod y),
  the block-diagonal (y*x, O) operand collapses losslessly to its dense
  segment-sum (x, O).  The kernel therefore issues a single x-deep
  contraction per output tile instead of a (y*x)-deep one against an
  operand that is (y-1)/y zeros — cutting both the y-fold zero-FLOPs and
  the y× RHS VMEM/HBM footprint.  Accepts lattice-f32 operands like
  ``vdpe_gemm``.  The historical block-diagonal kernel lives in
  kernels/ref.py (``vdpe_pack_gemm_blockdiag``) as the oracle.

* ``gemm_bf16`` — bf16 GEMM with f32 accumulation (dense tile path).

All take an optional fused epilogue (dequant scale, bias add, ReLU/ReLU6)
so integer accumulators never round-trip HBM between the GEMM and the
activation: a scalar ``scale`` rides in SMEM, ``bias`` is blocked over O,
and the activation is a compile-time branch.  The pre-quantized GEMMs also
accept a *per-row* scale (shape (B,) or (B, 1)): the batched engine folds
many images' DIV streams into one GEMM, and each image keeps its own
activation-DAC quantization scale, so the dequant scale varies along B.
Per-row scales ride as a (block_b, 1) VMEM column blocked over the B grid
axis and broadcast across the O lanes.

Blocked operands use explicit BlockSpec VMEM tiling with MXU-aligned block
shapes (multiples of (32, 128) for int8 operands, (8, 128) for f32); the
Mode-1 q8 kernel's K stream stays in HBM and rides explicit
``pltpu.make_async_copy`` DMAs into double-buffered VMEM scratch.
Validated against kernels/ref.py in interpret mode (tests/test_kernels.py,
tests/test_engine.py, tests/test_quantized.py); the q8 kernels at
MobileNetV1 widths also compile for a TPU v5e (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (ACTIVATIONS, apply_act as _apply_act,  # noqa: F401
                     dequant_epilogue as _dequant_epilogue, quantize_tile,
                     resolve_interpret)

# MXU-aligned default tile sizes (int8 operands tile as (32, 128) in VMEM).
BLOCK_B = 128
BLOCK_O = 128
BLOCK_K = 128


def _acc_dtype(operand_dtype) -> jnp.dtype:
    """int32 accumulation for int8 operands; exact f32 for the lattice-f32
    oracle operands (int8 products summed in f32 stay < 2^24: exact)."""
    return (jnp.int32 if jnp.issubdtype(operand_dtype, jnp.integer)
            else jnp.float32)


def _dot(lhs, rhs, acc_dtype):
    return jax.lax.dot_general(lhs, rhs, (((1,), (0,)), ((), ())),
                               preferred_element_type=acc_dtype)


# ---------------------------------------------------------------------------
# Mode 1: K-blocked dense GEMM over pre-quantized operands
# ---------------------------------------------------------------------------

def _gemm_kernel(lhs_ref, rhs_ref, out_ref):
    """Mode-1 kernel body: K-accumulating GEMM tile."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _dot(lhs_ref[...], rhs_ref[...], out_ref.dtype)


def _gemm_epilogue_kernel(scale_ref, lhs_ref, rhs_ref, bias_ref, out_ref,
                          acc_ref, *, n_k: int, act: str):
    """Mode-1 fused kernel: accumulator scratch, f32 epilogue at last K.

    The partial sums live only in the ``acc_ref`` scratch; the HBM output
    is the already-dequantized, biased, activated f32 tile.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(lhs_ref[...], rhs_ref[...], acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out_ref[...] = _dequant_epilogue(acc_ref[...], scale_ref[0, 0],
                                         bias_ref[...], act)


def _gemm_epilogue_rows_kernel(lhs_ref, rhs_ref, scale_ref, bias_ref,
                               out_ref, acc_ref, *, n_k: int, act: str):
    """Mode-1 fused kernel with a per-row dequant scale column in VMEM.

    The (block_b, 1) scale block is a narrow f32 block (lane dim < 128),
    the row-wise twin of the (1, block_o) bias block every epilogue here
    already uses; Mosaic pads narrow blocks to the native tile (the q8
    kernels' (block_b, 1) DAC-scale blocks compile for a v5e this way).
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(lhs_ref[...], rhs_ref[...], acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out_ref[...] = _dequant_epilogue(acc_ref[...], scale_ref[...],
                                         bias_ref[...], act)


@functools.partial(jax.jit, static_argnames=("block_b", "block_o", "block_k",
                                             "interpret", "act"))
def vdpe_gemm(lhs: jax.Array, rhs: jax.Array,
              block_b: int = BLOCK_B, block_o: int = BLOCK_O,
              block_k: int = BLOCK_K, interpret: bool | None = None,
              scale: jax.Array | None = None,
              bias: jax.Array | None = None,
              act: str = "none") -> jax.Array:
    """Mode-1 VDPE GEMM: (B, K) x (K, O) pre-quantized -> (B, O).

    B, K, O must be multiples of the block sizes (ops.py / engine pad).
    int8 operands accumulate in int32; lattice-f32 operands (the float
    oracle path) accumulate exactly in f32.  Without ``scale`` the result
    is the raw accumulator; with it the epilogue ``act(acc * scale +
    bias)`` is fused and the result is f32.  ``scale`` may be a scalar
    (one dequant scale for the whole stream) or a (B,) / (B, 1) per-row
    vector (the batched engine's per-image scales).
    """
    b, k = lhs.shape
    k2, o = rhs.shape
    assert k == k2 and b % block_b == 0 and o % block_o == 0 and k % block_k == 0
    n_k = k // block_k
    grid = (b // block_b, o // block_o, n_k)
    acc_dtype = _acc_dtype(lhs.dtype)
    lhs_spec = pl.BlockSpec((block_b, block_k), lambda i, j, kk: (i, kk))
    rhs_spec = pl.BlockSpec((block_k, block_o), lambda i, j, kk: (kk, j))
    out_spec = pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j))
    if scale is None:
        assert bias is None and act == "none", "epilogue requires a scale"
        return pl.pallas_call(
            _gemm_kernel,
            grid=grid,
            in_specs=[lhs_spec, rhs_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((b, o), acc_dtype),
            interpret=resolve_interpret(interpret),
        )(lhs, rhs)
    scale = jnp.asarray(scale, jnp.float32)
    if bias is None:
        bias = jnp.zeros((1, o), jnp.float32)
    if scale.size != 1:
        if scale.size != b:
            raise ValueError(
                f"per-row scale must have one entry per lhs row "
                f"({b}, block-padded), got shape {scale.shape}")
        return pl.pallas_call(
            functools.partial(_gemm_epilogue_rows_kernel, n_k=n_k, act=act),
            grid=grid,
            in_specs=[
                lhs_spec, rhs_spec,
                pl.BlockSpec((block_b, 1), lambda i, j, kk: (i, 0)),
                pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
            ],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_b, block_o), acc_dtype)],
            interpret=resolve_interpret(interpret),
        )(lhs, rhs, scale.reshape(b, 1), bias)
    scale = scale.reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_gemm_epilogue_kernel, n_k=n_k, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0),
                         memory_space=pltpu.SMEM),
            lhs_spec, rhs_spec,
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_o), acc_dtype)],
        interpret=resolve_interpret(interpret),
    )(scale, lhs, rhs, bias)


# ---------------------------------------------------------------------------
# Mode 1, quantized-domain: fused quantize prologue + K-pipelined stream
# ---------------------------------------------------------------------------

def _gemm_q8_kernel(w_scale_ref, lhs_hbm, rhs_hbm, a_scale_ref, bias_ref,
                    out_ref, lhs_buf, rhs_buf, sems, *, n_k: int,
                    block_b: int, block_o: int, block_k: int, bits: int,
                    act: str):
    """Quantized-domain Mode-1 body: in-kernel quantize, K double-buffered.

    lhs/rhs stay in HBM (``ANY``); K-block ``kk+1`` is DMA'd into the
    alternate VMEM slot while block ``kk`` is quantized and contracted.
    The K loop is unrolled at trace time (n_k is static), so the int32
    accumulator never leaves registers/VMEM and the epilogue runs once.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    def copies(slot: int, kk: int):
        return (
            pltpu.make_async_copy(
                lhs_hbm.at[pl.ds(i * block_b, block_b),
                           pl.ds(kk * block_k, block_k)],
                lhs_buf.at[slot], sems.at[slot, 0]),
            pltpu.make_async_copy(
                rhs_hbm.at[pl.ds(kk * block_k, block_k),
                           pl.ds(j * block_o, block_o)],
                rhs_buf.at[slot], sems.at[slot, 1]),
        )

    for c in copies(0, 0):
        c.start()
    a_col = a_scale_ref[...]                       # (block_b, 1) f32
    acc = jnp.zeros((block_b, block_o), jnp.int32)
    for kk in range(n_k):
        slot = kk % 2
        if kk + 1 < n_k:                           # prefetch next K block
            for c in copies((kk + 1) % 2, kk + 1):
                c.start()
        for c in copies(slot, kk):
            c.wait()
        lhs_q = quantize_tile(lhs_buf[slot], a_col, bits)
        acc += _dot(lhs_q, rhs_buf[slot], jnp.int32)
    # per-row dequant scale: image scale x the plan's weight scale, the
    # same association the oracle paths compute outside the kernel
    out_ref[...] = _dequant_epilogue(acc, a_col * w_scale_ref[0, 0],
                                     bias_ref[...], act)


@functools.partial(jax.jit, static_argnames=("bits", "block_b", "block_o",
                                             "block_k", "interpret", "act"))
def vdpe_gemm_q8(lhs: jax.Array, rhs: jax.Array, a_scale: jax.Array,
                 w_scale: jax.Array, bits: int = 4,
                 block_b: int = BLOCK_B, block_o: int = BLOCK_O,
                 block_k: int = BLOCK_K, interpret: bool | None = None,
                 bias: jax.Array | None = None,
                 act: str = "none") -> jax.Array:
    """Quantized-domain Mode-1 GEMM: (B, K) f32 x (K, O) int8 -> (B, O) f32.

    ``lhs`` is the *raw* f32 DIV stream; the kernel prologue quantizes it
    onto the int8 lattice with the per-row DAC scales ``a_scale`` ((B,) or
    (B, 1); pad rows use scale 1).  ``rhs`` is the plan's resident int8
    operand, ``w_scale`` its scalar dequant scale.  The fused epilogue is
    ``act(acc * (a_scale * w_scale) + bias)`` — bitwise-identical to
    quantizing outside and calling ``vdpe_gemm`` with per-row scales,
    while the int8 stream never round-trips HBM and the K axis streams
    through explicitly double-buffered VMEM slots.
    """
    b, k = lhs.shape
    k2, o = rhs.shape
    assert k == k2 and b % block_b == 0 and o % block_o == 0 and k % block_k == 0
    assert rhs.dtype == jnp.int8, rhs.dtype
    n_k = k // block_k
    a_scale = jnp.asarray(a_scale, jnp.float32)
    if a_scale.size != b:
        raise ValueError(
            f"per-row a_scale must have one entry per lhs row "
            f"({b}, block-padded), got shape {a_scale.shape}")
    if bias is None:
        bias = jnp.zeros((1, o), jnp.float32)
    return pl.pallas_call(
        functools.partial(_gemm_q8_kernel, n_k=n_k, block_b=block_b,
                          block_o=block_o, block_k=block_k, bits=bits,
                          act=act),
        grid=(b // block_b, o // block_o),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_o), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, block_b, block_k), jnp.float32),
            pltpu.VMEM((2, block_k, block_o), jnp.int8),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(w_scale, jnp.float32).reshape(1, 1), lhs, rhs,
      a_scale.reshape(b, 1), bias)


# ---------------------------------------------------------------------------
# Mode 2: zero-skipping segment-sum GEMM
# ---------------------------------------------------------------------------

def zs_block_shapes(x: int, block_b: int = BLOCK_B,
                    block_o: int = BLOCK_O) -> tuple:
    """(lhs, rhs, out) block shapes of the zero-skipping Mode-2 kernel.

    Single source of truth for the kernel's BlockSpecs — the engine tests
    assert the rhs block (and therefore the contraction issued per output
    tile) is x deep, not y*x deep.
    """
    return (block_b, x), (x, block_o), (block_b, block_o)


def _pack_gemm_zs_kernel(lhs_ref, rhs_ref, out_ref):
    """Zero-skipping Mode-2 body: one x-deep dot per output tile."""
    out_ref[...] = _dot(lhs_ref[...], rhs_ref[...], out_ref.dtype)


def _pack_gemm_zs_epilogue_kernel(scale_ref, lhs_ref, rhs_ref, bias_ref,
                                  out_ref, *, act: str):
    acc = _dot(lhs_ref[...], rhs_ref[...], _acc_dtype(lhs_ref.dtype))
    out_ref[...] = _dequant_epilogue(acc, scale_ref[0, 0], bias_ref[...],
                                     act)


def _pack_gemm_zs_epilogue_rows_kernel(lhs_ref, rhs_ref, scale_ref, bias_ref,
                                       out_ref, *, act: str):
    """Zero-skipping Mode-2 body with a per-row dequant scale column."""
    acc = _dot(lhs_ref[...], rhs_ref[...], _acc_dtype(lhs_ref.dtype))
    out_ref[...] = _dequant_epilogue(acc, scale_ref[...], bias_ref[...],
                                     act)


@functools.partial(jax.jit, static_argnames=("block_b", "block_o",
                                             "interpret", "act"))
def vdpe_pack_gemm_zs(lhs: jax.Array, rhs_seg: jax.Array,
                      block_b: int = BLOCK_B, block_o: int = BLOCK_O,
                      interpret: bool | None = None,
                      scale: jax.Array | None = None,
                      bias: jax.Array | None = None,
                      act: str = "none") -> jax.Array:
    """Zero-skipping Mode-2 GEMM: (B, x) x (x, O) pre-quantized -> (B, O).

    ``rhs_seg`` is the dense *segment-sum* of the block-diagonal packed
    operand (ops.pack_mode2_segments): column f holds kernel f's weights at
    their natural offset.  Because lane segments are column-disjoint the
    result is bit-identical to the (y*x)-deep block-diagonal oracle
    (ref.vdpe_pack_gemm_blockdiag) while issuing only an x-deep contraction
    and reading/holding 1/y of the RHS bytes.  Lattice-f32 operands (the
    float oracle path) accumulate exactly in f32.

    ``scale`` follows the vdpe_gemm convention: scalar, or per-row (B,) /
    (B, 1) for the batched engine's folded multi-image streams.
    """
    b, x = lhs.shape
    x2, o = rhs_seg.shape
    assert x == x2, (x, x2)  # structurally cannot issue a (y*x)-deep pass
    assert b % block_b == 0 and o % block_o == 0
    grid = (b // block_b, o // block_o)
    acc_dtype = _acc_dtype(lhs.dtype)
    lhs_shape, rhs_shape, out_shape = zs_block_shapes(x, block_b, block_o)
    lhs_spec = pl.BlockSpec(lhs_shape, lambda i, j: (i, 0))
    rhs_spec = pl.BlockSpec(rhs_shape, lambda i, j: (0, j))
    out_spec = pl.BlockSpec(out_shape, lambda i, j: (i, j))
    if scale is None:
        assert bias is None and act == "none", "epilogue requires a scale"
        return pl.pallas_call(
            _pack_gemm_zs_kernel,
            grid=grid,
            in_specs=[lhs_spec, rhs_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((b, o), acc_dtype),
            interpret=resolve_interpret(interpret),
        )(lhs, rhs_seg)
    scale = jnp.asarray(scale, jnp.float32)
    if bias is None:
        bias = jnp.zeros((1, o), jnp.float32)
    if scale.size != 1:
        if scale.size != b:
            raise ValueError(
                f"per-row scale must have one entry per lhs row "
                f"({b}, block-padded), got shape {scale.shape}")
        return pl.pallas_call(
            functools.partial(_pack_gemm_zs_epilogue_rows_kernel, act=act),
            grid=grid,
            in_specs=[
                lhs_spec, rhs_spec,
                pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, block_o), lambda i, j: (0, j)),
            ],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
            interpret=resolve_interpret(interpret),
        )(lhs, rhs_seg, scale.reshape(b, 1), bias)
    scale = scale.reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_pack_gemm_zs_epilogue_kernel, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            lhs_spec, rhs_spec,
            pl.BlockSpec((1, block_o), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(scale, lhs, rhs_seg, bias)


# ---------------------------------------------------------------------------
# Mode 2, quantized-domain: fused quantize prologue
# ---------------------------------------------------------------------------

def _pack_gemm_zs_q8_kernel(w_scale_ref, lhs_ref, rhs_ref, a_scale_ref,
                            bias_ref, out_ref, *, bits: int, act: str):
    """Quantized-domain zero-skipping body: quantize, one x-deep dot,
    epilogue.  The (block_b, x) DIV block rides the Pallas pipeline (x
    equals the full lane dim, so the block is legal at any x <= 128)."""
    a_col = a_scale_ref[...]                       # (block_b, 1) f32
    lhs_q = quantize_tile(lhs_ref[...], a_col, bits)
    acc = _dot(lhs_q, rhs_ref[...], jnp.int32)
    out_ref[...] = _dequant_epilogue(acc, a_col * w_scale_ref[0, 0],
                                     bias_ref[...], act)


@functools.partial(jax.jit, static_argnames=("bits", "block_b", "block_o",
                                             "interpret", "act"))
def vdpe_pack_gemm_zs_q8(lhs: jax.Array, rhs_seg: jax.Array,
                         a_scale: jax.Array, w_scale: jax.Array,
                         bits: int = 4, block_b: int = BLOCK_B,
                         block_o: int = BLOCK_O,
                         interpret: bool | None = None,
                         bias: jax.Array | None = None,
                         act: str = "none") -> jax.Array:
    """Quantized-domain Mode-2 GEMM: (B, x) f32 x (x, O) int8 -> (B, O) f32.

    ``lhs`` is the raw f32 DIV stream (quantized in the kernel prologue
    with per-row DAC scales ``a_scale``; pad rows use scale 1); ``rhs_seg``
    the dense int8 segment-sum pack with scalar dequant scale ``w_scale``.
    Bitwise-identical to quantizing outside and calling
    ``vdpe_pack_gemm_zs`` with per-row scales.  The DIV stream is a
    BlockSpec operand, so Pallas double-buffers it: a hand-written DMA
    into an (x < 128)-lane scratch slot is refused by Mosaic (the slice
    must be 128-lane aligned).
    """
    b, x = lhs.shape
    x2, o = rhs_seg.shape
    assert x == x2, (x, x2)
    assert b % block_b == 0 and o % block_o == 0
    assert rhs_seg.dtype == jnp.int8, rhs_seg.dtype
    a_scale = jnp.asarray(a_scale, jnp.float32)
    if a_scale.size != b:
        raise ValueError(
            f"per-row a_scale must have one entry per lhs row "
            f"({b}, block-padded), got shape {a_scale.shape}")
    if bias is None:
        bias = jnp.zeros((1, o), jnp.float32)
    lhs_shape, rhs_shape, out_shape = zs_block_shapes(x, block_b, block_o)
    return pl.pallas_call(
        functools.partial(_pack_gemm_zs_q8_kernel, bits=bits, act=act),
        grid=(b // block_b, o // block_o),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(lhs_shape, lambda i, j: (i, 0)),
            pl.BlockSpec(rhs_shape, lambda i, j: (0, j)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_o), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec(out_shape, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(w_scale, jnp.float32).reshape(1, 1), lhs, rhs_seg,
      a_scale.reshape(b, 1), bias)


# ---------------------------------------------------------------------------
# Dense bf16 tile path
# ---------------------------------------------------------------------------

def _gemm_bf16_kernel(lhs_ref, rhs_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _dot(lhs_ref[...], rhs_ref[...], jnp.float32)


def _gemm_bf16_epilogue_kernel(lhs_ref, rhs_ref, bias_ref, out_ref, acc_ref,
                               *, n_k: int, act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(lhs_ref[...], rhs_ref[...], jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out_ref[...] = _apply_act(acc_ref[...] + bias_ref[...], act)


@functools.partial(jax.jit, static_argnames=("block_b", "block_o", "block_k",
                                             "interpret", "act"))
def gemm_bf16(lhs: jax.Array, rhs: jax.Array,
              block_b: int = BLOCK_B, block_o: int = BLOCK_O,
              block_k: int = BLOCK_K, interpret: bool | None = None,
              bias: jax.Array | None = None,
              act: str = "none") -> jax.Array:
    """bf16 GEMM with f32 accumulation — the framework's dense tile path.

    With ``bias``/``act`` the epilogue fuses into the last K step (no
    dequant scale: the operands are already real-valued).
    """
    b, k = lhs.shape
    _, o = rhs.shape
    assert b % block_b == 0 and o % block_o == 0 and k % block_k == 0
    n_k = k // block_k
    grid = (b // block_b, o // block_o, n_k)
    lhs_spec = pl.BlockSpec((block_b, block_k), lambda i, j, kk: (i, kk))
    rhs_spec = pl.BlockSpec((block_k, block_o), lambda i, j, kk: (kk, j))
    out_spec = pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j))
    if bias is None and act == "none":
        return pl.pallas_call(
            _gemm_bf16_kernel,
            grid=grid,
            in_specs=[lhs_spec, rhs_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
            interpret=resolve_interpret(interpret),
        )(lhs, rhs)
    if bias is None:
        bias = jnp.zeros((1, o), jnp.float32)
    return pl.pallas_call(
        functools.partial(_gemm_bf16_epilogue_kernel, n_k=n_k, act=act),
        grid=grid,
        in_specs=[
            lhs_spec, rhs_spec,
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_o), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(lhs, rhs, bias)
