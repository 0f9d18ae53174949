"""Reference module of the test configuration ``tiny_chain``.

The chain of ``models/tiny_chain.py`` in the paper's quantized VDP
semantics, in ``jax.numpy`` alone: each layer's rows (a conv's K x K
patches after SAME padding, the dense layer's flattened map) are
quantized per image to ``bits`` signed levels with the scale
max|rows| / qmax, the weights per tensor, the contraction is exact in
int32, and the output is act(acc * a_scale * w_scale + bias).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _quantized(x, absmax, bits: int):
    qmax = 2 ** (bits - 1) - 1
    scale = jax.lax.optimization_barrier(
        jnp.maximum(absmax, 1e-12) * np.float32(1.0 / qmax))
    return jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8), scale


def _patch_rows(x, k: int, s: int):
    """(B, H, W, D) -> (B, ho * wo, K*K*D), taps in (row, column, channel)
    order, after SAME padding split as floor/ceil of the total."""
    n, h, w, d = x.shape
    ho, wo = -(-h // s), -(-w // s)
    ph, pw = max((ho - 1) * s + k - h, 0), max((wo - 1) * s + k - w, 0)
    x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2),
                    (0, 0)))
    taps = [x[:, i:i + s * (ho - 1) + 1:s, j:j + s * (wo - 1) + 1:s]
            for i in range(k) for j in range(k)]
    return jnp.stack(taps, axis=3).reshape(n, ho * wo, k * k * d), (ho, wo)


def _vdp(rows, w, b, relu: bool, bits: int):
    """rows (B, P, S) against weights (F, S), per-image activation scale."""
    qa, a = _quantized(rows, jnp.max(jnp.abs(rows), axis=(1, 2),
                                     keepdims=True), bits)
    qw, w_s = _quantized(w, jnp.max(jnp.abs(w)), bits)
    acc = jax.lax.dot_general(qa, qw, (((2,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    r = acc.astype(jnp.float32) * (a * w_s) + b
    return jnp.maximum(r, 0.0) if relu else r


@partial(jax.jit, static_argnums=(2,))
def _forward(weights, x, bits: int):
    n = x.shape[0]
    for (w, b), k, s in zip(weights[:2], (3, 1), (2, 1)):
        rows, (ho, wo) = _patch_rows(x, k, s)
        x = _vdp(rows, w.reshape(w.shape[0], -1), b, True,
                 bits).reshape(n, ho, wo, -1)
    w, b = weights[2]
    return _vdp(x.reshape(n, 1, -1), w, b, False, bits).reshape(n, -1)


def forward(cfg: dict, weights: list, images, bits: int):
    """Logits of ``images`` (N, H, W, C)."""
    x = jnp.asarray(np.asarray(images, np.float32))
    return np.asarray(_forward(weights, x, bits))
