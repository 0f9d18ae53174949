"""Plain reference of a configuration's quantized forward pass.

It follows the configuration file and the paper's quantized VDP
semantics, in ``jax.numpy`` alone, and imports nothing of the program:
each layer gathers its K x K patches (the DIVs), quantizes them per image
(per image and channel for depthwise and the pool) to ``bits`` signed
levels with the scale max|covered patch| / qmax, quantizes the weights
per tensor (per channel for depthwise and the pool), contracts exactly
in int32, and applies act(acc * a_scale * w_scale + bias).  The DAC scale
sits behind an optimization barrier so that XLA cannot re-associate its
product with the weight scale.  Lattice values are at most 2**(bits-1)-1,
so the int8 contraction is exact.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import opcount


def _qmax(bits: int) -> int:
    return 2 ** (bits - 1) - 1


def _scale(absmax: jax.Array, bits: int) -> jax.Array:
    inv = np.float32(1.0 / _qmax(bits))
    return jax.lax.optimization_barrier(jnp.maximum(absmax, 1e-12) * inv)


def _quantize(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    q = _qmax(bits)
    return jnp.clip(jnp.round(x / scale), -q, q).astype(jnp.int8)


def _patches(x: jax.Array, g: opcount.Geom) -> jax.Array:
    """(B, H, W, D) -> (B, ho, wo, K*K, D): every tap's strided window,
    after SAME padding split as floor/ceil of the total."""
    k, s = g.k, g.stride
    if g.padding == "SAME":
        ph = max((g.ho - 1) * s + k - g.h, 0)
        pw = max((g.wo - 1) * s + k - g.w, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    taps = [x[:, di:di + s * (g.ho - 1) + 1:s, dj:dj + s * (g.wo - 1) + 1:s]
            for di in range(k) for dj in range(k)]
    return jnp.stack(taps, axis=3)


def _epilogue(acc, scale, bias, act):
    r = acc.astype(jnp.float32) * scale
    if bias is not None:
        r = r + bias
    if act == "relu6":
        r = jnp.clip(r, 0.0, 6.0)
    elif act == "relu":
        r = jnp.maximum(r, 0.0)
    return r


def _layer(g: opcount.Geom, w, b, x, bits: int):
    n = x.shape[0]
    if g.kind == "dense":
        rows = x.reshape(n, -1)
        a = _scale(jnp.max(jnp.abs(rows), axis=1), bits)           # (B,)
        w_s = _scale(jnp.max(jnp.abs(w)), bits)
        acc = jax.lax.dot_general(
            _quantize(rows, a[:, None], bits), _quantize(w, w_s, bits),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
        return _epilogue(acc, a[:, None] * w_s, b, g.act)
    p = _patches(x, g)                                 # (B, ho, wo, KK, D)
    if g.kind == "conv":
        divs = p.reshape(n, g.ho * g.wo, g.k * g.k * g.d)
        a = _scale(jnp.max(jnp.abs(divs), axis=(1, 2)), bits)      # (B,)
        dkv = w.reshape(g.f, -1)
        w_s = _scale(jnp.max(jnp.abs(dkv)), bits)
        acc = jax.lax.dot_general(
            _quantize(divs, a[:, None, None], bits), _quantize(dkv, w_s, bits),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.int32)
        out = _epilogue(acc, (a * w_s)[:, None, None], b, g.act)
        return out.reshape(n, g.ho, g.wo, g.f)
    # depthwise and the pool: every channel is its own VDP
    a = _scale(jnp.max(jnp.abs(p), axis=(1, 2, 3)), bits)          # (B, D)
    dkv = w.reshape(g.d, -1)                                       # (D, KK)
    w_s = _scale(jnp.max(jnp.abs(dkv), axis=1), bits)              # (D,)
    q = _quantize(p, a[:, None, None, None, :], bits).astype(jnp.int32)
    acc = jnp.einsum("bhwkd,dk->bhwd", q,
                     _quantize(dkv, w_s[:, None], bits).astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    return _epilogue(acc, (a * w_s)[:, None, None, :], b, g.act)


@partial(jax.jit, static_argnums=(0, 3))
def _forward(geoms: tuple, weights: list, x: jax.Array, bits: int):
    for g, (w, b) in zip(geoms, weights):
        x = _layer(g, w, b, x, bits)
    return x.reshape(x.shape[0], -1)


def forward(cfg: dict, weights: list, images, bits: int, block: int = 16):
    """Logits of ``images`` (N, H, W, C), ``block`` images per call."""
    geoms = tuple(opcount.walk(cfg))
    images = np.asarray(images, np.float32)
    outs = []
    for i in range(0, len(images), block):
        xb = images[i:i + block]
        pad = block - len(xb)
        if pad:     # one compiled shape; every image is its own problem
            xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:],
                                              np.float32)])
        out = np.asarray(_forward(geoms, weights, jnp.asarray(xb), bits))
        outs.append(out[:block - pad])
    return np.concatenate(outs)


def logit_gap(served: np.ndarray, want: np.ndarray) -> float:
    """Widest gap of a served logit from the reference's, over the
    requests compared, each as a share of that request's largest
    reference logit magnitude.  A missing or non-finite answer reads inf."""
    served = np.asarray(served, np.float64)
    want = np.asarray(want, np.float64)
    if served.shape != want.shape or not np.all(np.isfinite(served)):
        return float("inf")
    gap = np.max(np.abs(served - want), axis=1)
    norm = np.maximum(np.max(np.abs(want), axis=1), 1e-30)
    return float(np.max(gap / norm))
