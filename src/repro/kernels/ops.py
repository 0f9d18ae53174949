"""jit'd wrappers around the Pallas VDPE kernels: padding, packing, routing.

`mixed_size_gemm` is the public entry point the framework layers use: given
a DIV matrix and a DKV matrix of arbitrary contraction size S, it routes to
the Mode-1 K-blocked kernel (S >= the MXU lane budget) or the zero-skipping
Mode-2 segment-sum kernel (small S), exactly mirroring the paper's
Case-1/2/3 selection with N = 128 lanes and x = the natural small-tensor
width.  All paths take an optional fused epilogue (dequant scale, bias,
ReLU/ReLU6).  ref.py holds the oracles, including the historical
block-diagonal Mode-2 kernel.  For the pack-once weight-stationary path
that skips the per-call padding/packing done here, see repro.engine.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import vdpe_gemm as k
from .common import pad_to as _pad_to, round_up as _round_up


def pack_mode2_weights(dkvs: jax.Array, x: int, y: int) -> jax.Array:
    """Pack (F, s<=x) small DKVs into a (y*x, F) block-diagonal matrix.

    Column f carries kernel f's weights in lane-segment (f mod y); the
    Mode-2 kernel replicates the DIV tile across segments so each column's
    dot product sees exactly its own kernel.
    """
    f, s = dkvs.shape
    assert s <= x, (s, x)
    seg = jnp.arange(f, dtype=jnp.int32) % y            # (F,)
    row = jnp.arange(y * x, dtype=jnp.int32)            # (y*x,)
    # row r belongs to segment r // x at offset r % x
    row_seg = row // x
    row_off = row % x
    dkvs_padded = jnp.pad(dkvs, ((0, 0), (0, x - s)))   # (F, x)
    # out[r, f] = dkvs_padded[f, row_off[r]] if row_seg[r] == seg[f] else 0
    vals = dkvs_padded[:, row_off].T                    # (y*x, F)
    mask = row_seg[:, None] == seg[None, :]
    return jnp.where(mask, vals, jnp.zeros_like(vals))


def pack_mode2_segments(dkvs: jax.Array, x: int) -> jax.Array:
    """Pack (F, s<=x) small DKVs into the dense (x, F) segment-sum.

    The zero-skipping Mode-2 operand: because `pack_mode2_weights` assigns
    column f to lane-segment f mod y and segments are therefore
    column-disjoint, summing the y row-segments of the block-diagonal pack
    loses nothing — column f simply carries kernel f's weights at their
    natural offset.  1/y the footprint, and the kernel contracts x deep
    instead of y*x deep.
    """
    f, s = dkvs.shape
    assert s <= x, (s, x)
    return jnp.pad(dkvs, ((0, 0), (0, x - s))).T


@functools.partial(jax.jit, static_argnames=("interpret", "act"))
def mode1_gemm(divs_q: jax.Array, dkvs_q: jax.Array,
               interpret: bool | None = None,
               scale: jax.Array | None = None,
               bias: jax.Array | None = None,
               act: str = "none") -> jax.Array:
    """Mode-1 path: (P, S) x (F, S) -> (P, F), padded to MXU tiles.

    int32 without ``scale``; f32 with the fused `act(acc*scale+bias)`
    epilogue.
    """
    p, s = divs_q.shape
    f, _ = dkvs_q.shape
    pp, ss, ff = _round_up(p, k.BLOCK_B), _round_up(s, k.BLOCK_K), \
        _round_up(f, k.BLOCK_O)
    lhs = _pad_to(divs_q, pp, ss)
    rhs = _pad_to(dkvs_q.T, ss, ff)
    if bias is not None:
        bias = jnp.pad(bias.reshape(1, -1), ((0, 0), (0, ff - f)))
    out = k.vdpe_gemm(lhs, rhs, interpret=interpret,
                      scale=scale, bias=bias, act=act)
    return out[:p, :f]


@functools.partial(jax.jit, static_argnames=("x", "y", "interpret", "act"))
def mode2_gemm(divs_q: jax.Array, dkvs_q: jax.Array, x: int, y: int,
               interpret: bool | None = None,
               scale: jax.Array | None = None,
               bias: jax.Array | None = None,
               act: str = "none") -> jax.Array:
    """Mode-2 path: (P, s<=x) x (F, s) -> (P, F) via the zero-skipping kernel.

    ``y`` is the hardware lane count (comb-switch pairs); it sizes the
    perf model (ceil(F/y) passes per slice), not the computation — the
    segment-sum operand already collapses the y lane-segments.
    """
    del y  # hardware lane count; see docstring
    p, s = divs_q.shape
    f, _ = dkvs_q.shape
    pp, ff = _round_up(p, k.BLOCK_B), _round_up(f, k.BLOCK_O)
    lhs = _pad_to(divs_q, pp, x)
    rhs = _pad_to(pack_mode2_segments(dkvs_q, x), x, ff)
    if bias is not None:
        bias = jnp.pad(bias.reshape(1, -1), ((0, 0), (0, ff - f)))
    out = k.vdpe_pack_gemm_zs(lhs, rhs, interpret=interpret,
                              scale=scale, bias=bias, act=act)
    return out[:p, :f]


#: TPU "VDPE size": the MXU contraction-lane budget per pass.
N_TPU = 128
#: TPU re-aggregation width: small-tensor lane segment (the paper's x=9
#: generalizes to the most common small contraction; 32 aligns to the int8
#: sublane tile).
X_TPU = 32


def mixed_size_gemm(divs_q: jax.Array, dkvs_q: jax.Array,
                    interpret: bool | None = None,
                    scale: jax.Array | None = None,
                    bias: jax.Array | None = None,
                    act: str = "none") -> jax.Array:
    """Route a (P, S) x (F, S) quantized contraction per the paper's cases.

    S >= N_TPU           -> Mode 1 (K-blocked dense kernel)
    S <= X_TPU           -> Mode 2 (zero-skipping segment-sum kernel)
    X_TPU < S < N_TPU    -> Mode 1 with a single padded K block (the MXU has
                            no sub-128 pass, so Case 2 re-aggregation only
                            pays above the segment width)

    Optional fused epilogue (scale/bias/act) as in mode1_gemm/mode2_gemm.
    """
    s = divs_q.shape[1]
    if s <= X_TPU:
        y = N_TPU // X_TPU
        return mode2_gemm(divs_q, dkvs_q, X_TPU, y, interpret=interpret,
                          scale=scale, bias=bias, act=act)
    return mode1_gemm(divs_q, dkvs_q, interpret=interpret,
                      scale=scale, bias=bias, act=act)


def grouped_matmul(tokens: jax.Array, weights: jax.Array,
                   group_ids: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """MoE ragged GEMM: out[t] = tokens[t] @ weights[group_ids[t]].

    Sorts tokens by expert, pads each group to the 128-token block size
    (the Mode-2 analogue: small expert batches share MXU passes instead of
    padding to the max group), runs the scalar-prefetch grouped kernel,
    and unsorts.
    """
    from . import moe_gemm
    t, d = tokens.shape
    e = weights.shape[0]
    order = jnp.argsort(group_ids)
    sorted_ids = group_ids[order]
    sorted_tokens = tokens[order]
    bt = moe_gemm.BLOCK_T
    # scatter each sorted token into its group's padded region
    counts = jnp.bincount(group_ids, length=e)
    padded = ((counts + bt - 1) // bt) * bt
    starts = jnp.concatenate([jnp.zeros(1, padded.dtype),
                              jnp.cumsum(padded)[:-1]])
    # position within group = running index minus group's first index
    group_first = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                   jnp.cumsum(counts)[:-1]])
    pos_in_group = jnp.arange(t) - group_first[sorted_ids]
    dest = starts[sorted_ids] + pos_in_group
    t_pad = int(e * bt + ((t + bt - 1) // bt) * bt)  # static upper bound
    buf = jnp.zeros((t_pad, d), tokens.dtype)
    buf = buf.at[dest].set(sorted_tokens)
    nb = t_pad // bt
    # block -> expert map (blocks beyond a group's padded range point at
    # expert 0; their rows are zero so the product is zero)
    block_starts = jnp.arange(nb) * bt
    block_expert = jnp.clip(
        jnp.searchsorted(jnp.cumsum(padded), block_starts, side="right"),
        0, e - 1).astype(jnp.int32)
    hp = _round_up(weights.shape[2], moe_gemm.BLOCK_H)
    w = jnp.pad(weights, ((0, 0), (0, 0), (0, hp - weights.shape[2])))
    out = moe_gemm.grouped_matmul_kernel(buf, w, block_expert,
                                         interpret=interpret)
    gathered = out[dest]                     # back to sorted order
    inv = jnp.argsort(order)
    return gathered[inv][:, :weights.shape[2]]


def gemm_bf16(lhs: jax.Array, rhs: jax.Array,
              interpret: bool | None = None,
              bias: jax.Array | None = None,
              act: str = "none") -> jax.Array:
    """Padded bf16 GEMM through the Pallas dense kernel (+fused bias/act)."""
    b, s = lhs.shape
    _, o = rhs.shape
    bb, ss, oo = _round_up(b, k.BLOCK_B), _round_up(s, k.BLOCK_K), \
        _round_up(o, k.BLOCK_O)
    if bias is not None:
        bias = jnp.pad(bias.reshape(1, -1), ((0, 0), (0, oo - o)))
    out = k.gemm_bf16(_pad_to(lhs, bb, ss), _pad_to(rhs, ss, oo),
                      interpret=interpret, bias=bias, act=act)
    return out[:b, :o]
