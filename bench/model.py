"""Weights of a configuration from a seed, and the program's layer chain.

The benchmark makes the weights itself, on the device, in one jitted
call, so the reference can take the same arrays without taking anything
the program made.  ``layer_defs`` hands them to the program as its
``LayerDef`` chain; the pool is the program's own convention, a VALID
depthwise layer with constant weights 1/(h*w).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import opcount

BN_EPS = 1e-3


def prng_key(seed: int, stream: int) -> jax.Array:
    """A key for ``stream`` from a seed of any size: seeds may pass
    2**32, and a jax key takes 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), stream)


def _folded(key, shape, fan_in):
    """He-normal weights with a random inference BatchNorm folded in:
    w * gamma / sqrt(var + eps), bias beta - mean * that."""
    kw, kg, kb, km, kv = jax.random.split(key, 5)
    c = shape[0]
    w = jax.random.normal(kw, shape, jnp.float32) * np.sqrt(2.0 / fan_in)
    gamma = 1.0 + 0.1 * jax.random.normal(kg, (c,), jnp.float32)
    beta = 0.1 * jax.random.normal(kb, (c,), jnp.float32)
    mean = 0.1 * jax.random.normal(km, (c,), jnp.float32)
    var = jax.random.uniform(kv, (c,), jnp.float32, 0.5, 1.5)
    g = gamma / jnp.sqrt(var + BN_EPS)
    return w * g.reshape((c,) + (1,) * (len(shape) - 1)), beta - mean * g


@partial(jax.jit, static_argnums=(0,))
def _make(geoms: tuple, key: jax.Array) -> list:
    out = []
    for i, g in enumerate(geoms):
        k = jax.random.fold_in(key, i)
        if g.kind == "conv":
            out.append(_folded(k, (g.f, g.k, g.k, g.d), g.k * g.k * g.d))
        elif g.kind == "depthwise":
            out.append(_folded(k, (g.d, g.k, g.k), g.k * g.k))
        elif g.kind == "global_avg_pool":
            out.append((jnp.full((g.d, g.k, g.k), 1.0 / (g.k * g.k),
                                 jnp.float32), None))
        else:
            kw, kb = jax.random.split(k)
            w = jax.random.normal(kw, (g.f, g.d), jnp.float32) / np.sqrt(g.d)
            b = (0.1 * jax.random.normal(kb, (g.f,), jnp.float32)
                 if g.bias else None)
            out.append((w, b))
    return out


def make_weights(cfg: dict, seed: int) -> list:
    """[(weights, bias or None)] per layer, made on the device."""
    return _make(tuple(opcount.walk(cfg)), prng_key(seed, 0))


def layer_defs(cfg: dict, weights: list) -> list:
    """The program's ``LayerDef`` chain over the benchmark's weights."""
    from repro.cnn.layers import ConvKind
    from repro.engine import LayerDef

    defs = []
    for g, (w, b) in zip(opcount.walk(cfg), weights):
        if g.kind == "conv":
            kind = ConvKind.SC if g.k > 1 else ConvKind.PC
        elif g.kind == "dense":
            kind = ConvKind.FC
        else:
            kind = ConvKind.DC
        defs.append(LayerDef(g.name, kind, w, bias=b, act=g.act,
                             stride=g.stride, padding=g.padding))
    return defs
