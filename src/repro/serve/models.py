"""Serving model zoo: executable mini variants of the paper CNNs.

The analytic model zoo (cnn/models.py) describes the paper's CNNs as flat
LayerSpec *censuses* — residual branches, SE blocks and concats appear as
standalone rows — which is exactly what the mapping study and simulator
need, but such a table is not a sequentially executable network.  For the
functional serving path each paper CNN therefore gets a small *sequential*
stand-in here that preserves its architectural signature (EfficientNet's
expand/depthwise/SE-ish/project MBConv shape, Xception's separable-conv
chains, ShuffleNetV2's pointwise/depthwise/pointwise units), spans both
paper GEMM modes (Mode-2 small-S contractions AND Mode-1 dense ones) plus
the depthwise VPU path, and is cheap enough to run through the Pallas
kernels in interpret mode on a CPU host.

Weight factories are deterministic in (model, seed): the registry can
evict a plan and re-imprint bit-identical DKVs later.

Hardware-time telemetry does NOT use these minis: the simulator costs the
*paper-scale* layer tables (PAPER_SCALE_SPECS — the full EfficientNetB7 /
Xception / ShuffleNetV2 censuses), modeling the real CNN the mini stands
in for.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..cnn.layers import ConvKind, LayerSpec
from ..cnn.models import MODEL_ZOO
from ..engine import LayerDef, defs_to_specs


def _w(rng: np.random.Generator, shape: Tuple[int, ...]) -> jnp.ndarray:
    return jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)


def _b(rng: np.random.Generator, n: int) -> jnp.ndarray:
    return jnp.asarray(rng.normal(size=(n,)) * 0.1, jnp.float32)


def efficientnet_mini(seed: int = 0) -> List[LayerDef]:
    """MBConv-shaped stand-in: stem SC, expand PC, DC, project PC, head, FC."""
    rng = np.random.default_rng((seed, 0xEFF))
    return [
        LayerDef("stem", ConvKind.SC, _w(rng, (8, 3, 3, 3)),
                 act="relu", stride=2),
        LayerDef("expand", ConvKind.PC, _w(rng, (24, 1, 1, 8)),
                 bias=_b(rng, 24), act="relu6"),
        LayerDef("dwconv", ConvKind.DC, _w(rng, (24, 3, 3)),
                 act="relu6", stride=2),
        LayerDef("project", ConvKind.PC, _w(rng, (16, 1, 1, 24))),
        LayerDef("head", ConvKind.PC, _w(rng, (32, 1, 1, 16)),
                 bias=_b(rng, 32), act="relu"),
        LayerDef("predictions", ConvKind.FC, _w(rng, (10, 4 * 4 * 32))),
    ]


def xception_mini(seed: int = 0) -> List[LayerDef]:
    """Separable-conv-chain stand-in: entry SC then two dw+pw sepconvs."""
    rng = np.random.default_rng((seed, 0xCEB))
    return [
        LayerDef("conv1", ConvKind.SC, _w(rng, (16, 3, 3, 3)),
                 act="relu", stride=2),
        LayerDef("sep1_dw", ConvKind.DC, _w(rng, (16, 3, 3)), act="relu"),
        LayerDef("sep1_pw", ConvKind.PC, _w(rng, (32, 1, 1, 16)),
                 bias=_b(rng, 32), act="relu"),
        LayerDef("sep2_dw", ConvKind.DC, _w(rng, (32, 3, 3)),
                 act="relu", stride=2),
        # S = 32 rides Mode 2; the exit 1x1 below (S = 48) needs Mode 1
        LayerDef("sep2_pw", ConvKind.PC, _w(rng, (48, 1, 1, 32)), act="relu"),
        LayerDef("exit_pw", ConvKind.PC, _w(rng, (64, 1, 1, 48)), act="relu"),
        LayerDef("predictions", ConvKind.FC, _w(rng, (10, 4 * 4 * 64))),
    ]


def shufflenet_mini(seed: int = 0) -> List[LayerDef]:
    """ShuffleNetV2-unit stand-in: stem SC, pw/dw/pw unit, conv5, FC."""
    rng = np.random.default_rng((seed, 0x5F7))
    return [
        LayerDef("conv1", ConvKind.SC, _w(rng, (12, 3, 3, 3)),
                 act="relu", stride=2),
        LayerDef("unit_pw1", ConvKind.PC, _w(rng, (24, 1, 1, 12)),
                 act="relu"),
        LayerDef("unit_dw", ConvKind.DC, _w(rng, (24, 3, 3)), stride=2),
        LayerDef("unit_pw2", ConvKind.PC, _w(rng, (24, 1, 1, 24)),
                 bias=_b(rng, 24), act="relu"),
        LayerDef("conv5", ConvKind.PC, _w(rng, (48, 1, 1, 24)), act="relu"),
        LayerDef("predictions", ConvKind.FC, _w(rng, (10, 4 * 4 * 48))),
    ]


def _folded_conv(rng: np.random.Generator, shape: Tuple[int, ...],
                 fan_in: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """He-initialised conv weights with a random inference BatchNorm
    folded in: w * gamma / sqrt(var + eps), bias beta - mean * that."""
    w = rng.normal(size=shape) * np.sqrt(2.0 / fan_in)
    c = shape[0]
    gamma = 1.0 + 0.1 * rng.normal(size=c)
    beta = 0.1 * rng.normal(size=c)
    mean = 0.1 * rng.normal(size=c)
    g = gamma / np.sqrt(rng.uniform(0.5, 1.5, size=c) + 1e-3)
    w = w * g.reshape((c,) + (1,) * (len(shape) - 1))
    return (jnp.asarray(w, jnp.float32),
            jnp.asarray(beta - mean * g, jnp.float32))


def mobilenet_v1_defs(seed: int = 0, resolution: int = 224,
                      ) -> List[LayerDef]:
    """MobileNetV1 1.0 at full width as an executable chain.

    Derived from the ``cnn/models.py`` census (``mobilenet_v1``): conv1,
    13 depthwise/pointwise pairs and the 1024 -> 1000 FC, every conv with
    BN folded and relu6.  The strides are the census' own spatial walk at
    224; other resolutions keep them.  The census leaves out the global
    average pool (no tensor product); here it is a VALID depthwise layer
    over the whole final map with constant 1/(h*w) weights, which the
    per-channel weight quantizer maps exactly onto qmax.  Not in
    ``SERVING_MODELS``: the CPU tests iterate that table in interpret mode.
    """
    rng = np.random.default_rng((seed, 0x3B1))
    defs: List[LayerDef] = []
    h, h_census = resolution, 224
    for spec in MODEL_ZOO["mobilenet_v1"]():
        if spec.kind is ConvKind.FC:
            defs.append(LayerDef(
                "avg_pool", ConvKind.DC,
                jnp.full((spec.d, h, h), 1.0 / (h * h), jnp.float32),
                padding="VALID"))
            w = rng.normal(size=(spec.f, spec.d)) / np.sqrt(spec.d)
            defs.append(LayerDef(spec.name, ConvKind.FC,
                                 jnp.asarray(w, jnp.float32),
                                 bias=_b(rng, spec.f)))
            continue
        stride = h_census // spec.h_out
        h_census, h = spec.h_out, -(-h // stride)
        k = spec.k
        if spec.kind is ConvKind.DC:
            w, b = _folded_conv(rng, (spec.f, k, k), k * k)
        else:
            w, b = _folded_conv(rng, (spec.f, k, k, spec.d), k * k * spec.d)
        defs.append(LayerDef(spec.name, spec.kind, w, bias=b, act="relu6",
                             stride=stride))
    return defs


#: name -> (weight factory, input shape HWC, paper-scale simulator table)
SERVING_MODELS: Dict[str, Tuple[Callable[[int], List[LayerDef]],
                                Tuple[int, int, int], str]] = {
    "efficientnet_mini": (efficientnet_mini, (16, 16, 3), "efficientnet_b7"),
    "xception_mini": (xception_mini, (16, 16, 3), "xception"),
    "shufflenet_mini": (shufflenet_mini, (16, 16, 3), "shufflenet_v2"),
}


def serving_defs(name: str, seed: int = 0) -> List[LayerDef]:
    return SERVING_MODELS[name][0](seed)


def serving_input_shape(name: str) -> Tuple[int, int, int]:
    return SERVING_MODELS[name][1]


def paper_scale_specs(name: str) -> List[LayerSpec]:
    """The full paper-CNN layer table this serving model stands in for."""
    return MODEL_ZOO[SERVING_MODELS[name][2]]()


def specs_for_defs(defs: Sequence[LayerDef],
                   input_shape: Tuple[int, int, int]) -> List[LayerSpec]:
    """Derive the analytic LayerSpec table of an executable LayerDef chain.

    Delegates to ``engine.defs_to_specs`` (the planner scores the same
    walk), so ``simulate(acc, specs_for_defs(defs, shape), batch)`` models
    precisely the tensor products the engine will run.
    """
    return list(defs_to_specs(defs, input_shape))
