"""Fused (flash) attention forward — Pallas TPU kernel.

The §Perf Cell-B analysis shows the (B, H, S, T) score tensor dominates
train/prefill memory traffic; on TPU the answer is to never materialize it
in HBM.  This kernel computes one (q-block × head) tile with an online-
softmax running (max, sum) state, streaming K/V blocks through VMEM:

    HBM traffic = Q + K + V + O        (vs  Q+K+V+O + 2·S·T scores)

Forward-only (serving/prefill path; training keeps the XLA attention whose
backward is generated automatically).  Causal masking by absolute position;
GQA via q-head -> kv-head grouping handled in ops.flash_attention.
Validated against a pure-jnp oracle in interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import resolve_interpret

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool,
                  sm_scale: float, block_k: int, kv_len: int):
    """One (batch*head, q-block) tile; loops KV blocks with online softmax.

    Block refs: q (1, block_q, hd); k/v (1, kv_len, hd); o (1, block_q, hd).
    """
    _, block_q, hd = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
    k_all = k_ref[0]
    v_all = v_ref[0]

    def body(start, carry):
        acc, m_prev, l_prev = carry
        k = jax.lax.dynamic_slice_in_dim(
            k_all, start * block_k, block_k, 0).astype(jnp.float32)
        v = jax.lax.dynamic_slice_in_dim(
            v_all, start * block_k, block_k, 0).astype(jnp.float32)
        s = q @ k.T                                     # (bq, bk)
        k_pos = start * block_k + jax.lax.iota(jnp.int32, block_k)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l_new

    n_kv = kv_len // block_k
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_kv, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True,
                           block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                           interpret: bool | None = None) -> jax.Array:
    """q: (BH, S, hd); k/v: (BH, T, hd) -> (BH, S, hd).

    S % block_q == 0 and T % block_k == 0 (ops.py pads).
    """
    bh, s, hd = q.shape
    _, t, _ = k.shape
    assert s % block_q == 0 and t % block_k == 0
    sm_scale = 1.0 / math.sqrt(hd)
    grid = (bh, s // block_q)
    return pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, sm_scale=sm_scale,
                          block_k=block_k, kv_len=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
