"""The serving path's kernels compile for a TPU v5e, with no chip attached.

Each case lowers a Pallas kernel with ``interpret=False`` at MobileNetV1
1.0/224 widths, batch 8, for one chip of a described ``v5e:2x2`` topology
and compiles it with the installed TPU compiler: what Mosaic refuses here
(an unaligned slice, a primitive without a lowering rule, a block that
overflows VMEM) it would refuse on the chip.  A compile that passes is not
a chip run and says nothing about results or times.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and test workers that collected
different tests would run none.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine
from repro.engine import pipeline
from repro.kernels import vdpe_conv as kconv
from repro.kernels import vdpe_gemm as kern
from repro.serve import models as zoo

B = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # keep the TPU library's logs out of /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written but can never be read."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _conv_q8(k, stride, ho, wo, mode2):
    if mode2:
        return lambda x, r, w: kconv.vdpe_pack_conv_zs_q8(
            x, r, w, k, stride, ho, wo, x=32, interpret=False)
    return lambda x, r, w: kconv.vdpe_conv_q8(x, r, w, k, stride, ho, wo,
                                              interpret=False)


#: name -> (fn, argument (shape, dtype)s); MobileNetV1 1.0/224 layers
KERNELS = {
    "conv1_3x3s2_d3": (_conv_q8(3, 2, 112, 112, True),
                       [((B, 225, 225, 3), jnp.float32),
                        ((32, 128), jnp.int8), ((), jnp.float32)]),
    "pw1_112x112x32_mode2": (_conv_q8(1, 1, 112, 112, True),
                             [((B, 112, 112, 32), jnp.float32),
                              ((32, 128), jnp.int8), ((), jnp.float32)]),
    "pw2_56x56x64_mode1": (_conv_q8(1, 1, 56, 56, False),
                           [((B, 56, 56, 64), jnp.float32),
                            ((128, 128), jnp.int8), ((), jnp.float32)]),
    "pw13_7x7x1024_mode1": (_conv_q8(1, 1, 7, 7, False),
                            [((B, 7, 7, 1024), jnp.float32),
                             ((1024, 1024), jnp.int8), ((), jnp.float32)]),
    "fc_1024x1000": (lambda x, r, a, w: kern.vdpe_gemm_q8(
                         x, r, a, w, interpret=False),
                     [((128, 1024), jnp.float32), ((1024, 1024), jnp.int8),
                      ((128,), jnp.float32), ((), jnp.float32)]),
    "pack_gemm_zs_q8": (lambda x, r, a, w: kern.vdpe_pack_gemm_zs_q8(
                            x, r, a, w, interpret=False),
                        [((128, 32), jnp.float32), ((32, 128), jnp.int8),
                         ((128,), jnp.float32), ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, arg_specs = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in arg_specs]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def test_mobilenet_v1_pipeline_compiles_for_v5e(one_chip):
    """The whole forward_jit program of MobileNetV1 at batch 8: one Mosaic
    kernel per SC/PC/FC layer, and it fits the chip."""
    plan = engine.compile_model("mobilenet_v1_compile",
                                zoo.mobilenet_v1_defs(0))
    fn = pipeline.get_pipeline(plan, interpret=False)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        pipeline._layer_params(plan))
    x = jax.ShapeDtypeStruct((B, 224, 224, 3), jnp.float32,
                             sharding=one_chip)
    compiled = fn.lower(params, x).compile()
    kernel_layers = sum(1 for lp in plan.layers
                        if lp.mode != engine.MODE_DEPTHWISE)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == kernel_layers == 15
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30
