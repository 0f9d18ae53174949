"""Oracles for every Pallas kernel in this package.

Mostly pure-jnp references; additionally holds the *historical*
block-diagonal Mode-2 Pallas kernel (``vdpe_pack_gemm_blockdiag``), kept
verbatim as the oracle + benchmark baseline for the zero-skipping kernel
that replaced it in vdpe_gemm.py (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import resolve_interpret


def vdpe_gemm_ref(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """Mode-1 oracle: exact int32 GEMM."""
    return jax.lax.dot_general(
        lhs.astype(jnp.int32), rhs.astype(jnp.int32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)


def vdpe_pack_gemm_ref(lhs: jax.Array, rhs_packed: jax.Array,
                       y: int) -> jax.Array:
    """Mode-2 oracle: replicate the DIV tile then dense int32 GEMM."""
    a_rep = jnp.concatenate([lhs] * y, axis=1)
    return vdpe_gemm_ref(a_rep, rhs_packed)


def _pack_gemm_blockdiag_kernel(lhs_ref, rhs_ref, out_ref, *, y: int):
    """Pre-zero-skipping Mode-2 body: replicate the DIV tile y times and
    contract (y*x)-deep against the mostly-zero block-diagonal operand."""
    a = lhs_ref[...]                           # (bb, x)
    a_rep = jnp.concatenate([a] * y, axis=1)   # (bb, y*x) in VMEM/VREGs
    out_ref[...] = jax.lax.dot_general(
        a_rep, rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("y", "block_b", "block_o",
                                             "interpret"))
def vdpe_pack_gemm_blockdiag(lhs: jax.Array, rhs_packed: jax.Array, y: int,
                             block_b: int = 128, block_o: int = 128,
                             interpret: bool | None = None) -> jax.Array:
    """The original Mode-2 Pallas kernel: (B, x) x (y*x, O) packed -> (B, O).

    ``rhs_packed`` is block-diagonal (ops.pack_mode2_weights): column f is
    non-zero only inside lane-segment f mod y, so (y-1)/y of the operand —
    and of the MXU contraction depth — is zeros.  Kept as the oracle and
    benchmark baseline for vdpe_gemm.vdpe_pack_gemm_zs.
    """
    b, x = lhs.shape
    k, o = rhs_packed.shape
    assert k == y * x, (k, y, x)
    assert b % block_b == 0 and o % block_o == 0
    grid = (b // block_b, o // block_o)
    return pl.pallas_call(
        functools.partial(_pack_gemm_blockdiag_kernel, y=y),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, x), lambda i, j: (i, 0)),
            pl.BlockSpec((y * x, block_o), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(lhs, rhs_packed)


def pack_mode2_segments_ref(dkvs: jax.Array, x: int, y: int) -> jax.Array:
    """Oracle for ops.pack_mode2_segments: the dense segment-sum (x, F).

    Derived independently of the implementation: build the block-diagonal
    pack (pack_block_diagonal_ref) and sum its y row-segments — lossless
    because segments are column-disjoint.
    """
    f, _ = dkvs.shape
    bd = pack_block_diagonal_ref(dkvs, x, y).astype(jnp.int32)
    return bd.reshape(y, x, f).sum(axis=0).astype(dkvs.dtype)


def epilogue_ref(acc: jax.Array, scale, bias, act: str) -> jax.Array:
    """Oracle for the fused GEMM epilogue: act(acc * scale + bias)."""
    r = acc.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
    if bias is not None:
        r = r + bias
    if act == "relu":
        r = jnp.maximum(r, 0.0)
    elif act == "relu6":
        r = jnp.clip(r, 0.0, 6.0)
    else:
        assert act == "none", act
    return r


def gemm_bf16_ref(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        lhs, rhs, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def pack_block_diagonal_ref(dkvs: jax.Array, x: int, y: int) -> jax.Array:
    """Oracle for ops.pack_mode2_weights: (F, s<=x) -> (y*x, F) packed.

    Column f carries kernel f's weights in segment (f mod y).
    """
    f, s = dkvs.shape
    assert s <= x
    out = jnp.zeros((y * x, f), dkvs.dtype)
    for i in range(f):
        seg = i % y
        out = out.at[seg * x:seg * x + s, i].set(dkvs[i])
    return out


def grouped_matmul_ref(tokens: jax.Array, weights: jax.Array,
                       group_ids: jax.Array) -> jax.Array:
    """Oracle for the MoE grouped GEMM: per-token expert matmul.

    tokens: (T, D); weights: (E, D, H); group_ids: (T,) in [0, E).
    Returns (T, H) with out[t] = tokens[t] @ weights[group_ids[t]].
    """
    gathered = weights[group_ids]            # (T, D, H)
    return jnp.einsum("td,tdh->th", tokens, gathered)


def flash_attention_ref(q, w_k, v, causal: bool = True):
    """Oracle for the fused attention kernel: naive softmax attention.

    q: (BH, S, hd); w_k/v: (BH, T, hd) -> (BH, S, hd).
    """
    import math
    s = jnp.einsum("bsh,bth->bst", q.astype(jnp.float32),
                   w_k.astype(jnp.float32)) / math.sqrt(q.shape[-1])
    if causal:
        sq, t = q.shape[1], w_k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,bth->bsh", p,
                      v.astype(jnp.float32)).astype(q.dtype)
