"""Pallas grouped (ragged) GEMM for MoE expert compute.

The paper's mixed-size-tensor problem reappears in MoE layers: each expert
serves a different-sized token group, and padding every group to the max
wastes MXU passes exactly like S < N strands MRRs.  This kernel runs one
token-block per grid row with the expert id scalar-prefetched, so a block
reads ONLY its expert's weight tile — groups are padded to the block size
(128) instead of the max group size.

Layout contract (prepared by ops.grouped_matmul):
    tokens   (T_pad, D)   — sorted by expert, each group padded to block_t
    weights  (E, D, H)
    block_to_expert (T_pad / block_t,) int32 — scalar-prefetched map
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import resolve_interpret

BLOCK_T = 128
BLOCK_H = 128


def _kernel(block_to_expert, tokens_ref, w_ref, out_ref):
    del block_to_expert  # consumed by the index maps
    out_ref[...] = jax.lax.dot_general(
        tokens_ref[...], w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_h",
                                             "interpret"))
def grouped_matmul_kernel(tokens: jax.Array, weights: jax.Array,
                          block_to_expert: jax.Array,
                          block_t: int = BLOCK_T, block_h: int = BLOCK_H,
                          interpret: bool | None = None) -> jax.Array:
    t_pad, d = tokens.shape
    e, _, h = weights.shape
    assert t_pad % block_t == 0 and h % block_h == 0
    nb = t_pad // block_t
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, h // block_h),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j, bmap: (i, 0)),
            pl.BlockSpec((1, d, block_h), lambda i, j, bmap: (bmap[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_h), lambda i, j, bmap: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, h), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(block_to_expert, tokens, weights)
