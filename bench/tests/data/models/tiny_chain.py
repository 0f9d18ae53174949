"""Model module of the test configuration ``tiny_chain``.

Three layers, each with a bias: a 3x3 stride-2 conv and a pointwise conv
with relu, then a dense layer to the logits.  The configuration states
its widths under keys of its own (``stem_filters``, ``pointwise_filters``,
``num_classes``), which only this module and its reference read; nothing
here comes from ``bench/model.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import opcount


def geoms(cfg: dict) -> list:
    h, w, d = cfg["input_shape"]
    f1, f2 = cfg["stem_filters"], cfg["pointwise_filters"]
    ho, wo = -(-h // 2), -(-w // 2)
    return [
        opcount.Geom("stem", "conv", 3, 2, "SAME", h, w, d, ho, wo, f1,
                     "relu", True),
        opcount.Geom("pointwise", "conv", 1, 1, "SAME", ho, wo, f1, ho, wo,
                     f2, "relu", True),
        opcount.Geom("logits", "dense", 1, 1, "SAME", 1, 1, ho * wo * f2,
                     1, 1, cfg["num_classes"], "none", True),
    ]


def ops_per_image(cfg: dict) -> int:
    return 2 * sum(g.macs for g in geoms(cfg))


@partial(jax.jit, static_argnums=(0,))
def _make(shapes: tuple, key: jax.Array) -> list:
    out = []
    for i, shape in enumerate(shapes):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        std = np.sqrt(2.0 / np.prod(shape[1:]))
        out.append((std * jax.random.normal(kw, shape, jnp.float32),
                    0.1 * jax.random.normal(kb, shape[:1], jnp.float32)))
    return out


def make_weights(cfg: dict, seed: int) -> list:
    """[(weights, bias)] per layer: convs (F, K, K, D), dense (F, D)."""
    shapes = tuple((g.f, g.k, g.k, g.d) if g.kind == "conv" else (g.f, g.d)
                   for g in geoms(cfg))
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return _make(shapes, key)


def program(cfg: dict, weights: list):
    from repro.cnn.layers import ConvKind
    from repro.engine import LayerDef

    kinds = (ConvKind.SC, ConvKind.PC, ConvKind.FC)
    defs = [LayerDef(g.name, kind, w, bias=b, act=g.act, stride=g.stride)
            for g, kind, (w, b) in zip(geoms(cfg), kinds, weights)]
    return lambda: defs
