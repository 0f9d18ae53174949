"""Quantized-domain execution: int8 operand streams end to end.

Bitwise contracts under test:

* the fused-quantize kernels (vdpe_gemm_q8 / vdpe_pack_gemm_zs_q8 /
  vdpe_conv_q8 / vdpe_pack_conv_zs_q8) == quantizing in XLA and calling
  the pre-quantized kernels — including the explicit double-buffered
  K-block / DIV-stream DMA loops and multi-block grids;
* pre-quantized kernels fed lattice-f32 operands (the quantize-then-float
  oracle's GEMMs) == their int8 results exactly (f32 accumulation of int8
  products is exact below 2^24);
* engine forward / forward_layer (int8 path) == forward_f32 (the float
  oracle) == forward_im2col across ALL FOUR layer kinds (SC/DC/PC/FC),
  both packing modes, per-image dequant scales, ragged batches, eager and
  whole-model jit;
* plan weight-bytes accounting and the registry's packed-vs-f32 report.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.cnn.layers import ConvKind
from repro.engine import executor as ex
from repro.kernels import common
from repro.kernels import vdpe_conv as kconv
from repro.kernels import vdpe_gemm as kern
from repro.serve import models as zoo
from repro.serve.registry import PlanRegistry

jax.config.update("jax_platform_name", "cpu")


def _eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _quantize_rows(lhs, a_rows, bits=4):
    qmax = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(lhs / a_rows[:, None]),
                    -qmax, qmax).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Kernel level: fused quantize prologue == quantize-then-kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_k", [1, 3])
@pytest.mark.parametrize("act", ["none", "relu6"])
def test_vdpe_gemm_q8_matches_prequantized(n_k, act):
    """The double-buffered K-pipelined q8 GEMM == XLA quantize + vdpe_gemm
    with per-row scales (pad rows carry scale 1)."""
    rng = np.random.default_rng(0)
    b, k, o = 256, 128 * n_k, 256
    lhs = jnp.asarray(rng.normal(size=(b, k)) * 3.0, jnp.float32)
    rhs = jnp.asarray(rng.integers(-7, 8, (k, o)), jnp.int8)
    a_rows = jnp.asarray(np.abs(rng.normal(size=(b,))) + 0.05, jnp.float32)
    a_rows = a_rows.at[-5:].set(1.0)              # "pad" rows
    w_scale = jnp.float32(0.037)
    bias = jnp.asarray(rng.normal(size=(1, o)), jnp.float32)
    got = kern.vdpe_gemm_q8(lhs, rhs, a_rows, w_scale, bits=4,
                            interpret=True, bias=bias, act=act)
    want = kern.vdpe_gemm(_quantize_rows(lhs, a_rows), rhs,
                          interpret=True, scale=a_rows * w_scale,
                          bias=bias, act=act)
    _eq(got, want)


@pytest.mark.parametrize("n_b", [1, 3])
def test_vdpe_pack_gemm_zs_q8_matches_prequantized(n_b):
    """The stream-double-buffered zero-skipping q8 GEMM == XLA quantize +
    vdpe_pack_gemm_zs, across multiple DIV-stream blocks."""
    rng = np.random.default_rng(1)
    b, x, o = 128 * n_b, 32, 128
    lhs = jnp.asarray(rng.normal(size=(b, x)) * 2.0, jnp.float32)
    rhs = jnp.asarray(rng.integers(-7, 8, (x, o)), jnp.int8)
    a_rows = jnp.asarray(np.abs(rng.normal(size=(b,))) + 0.05, jnp.float32)
    w_scale = jnp.float32(0.021)
    got = kern.vdpe_pack_gemm_zs_q8(lhs, rhs, a_rows, w_scale, bits=4,
                                    interpret=True, act="relu")
    want = kern.vdpe_pack_gemm_zs(_quantize_rows(lhs, a_rows), rhs,
                                  interpret=True, scale=a_rows * w_scale,
                                  act="relu")
    _eq(got, want)


@pytest.mark.parametrize("k,stride", [(1, 1), (3, 1), (3, 2)])
def test_conv_q8_matches_prequantized(k, stride):
    """The fused-prologue conv kernels (in-kernel absmax + quantize) ==
    the XLA quantize passes + the pre-quantized conv kernels."""
    rng = np.random.default_rng(2)
    b, h, w, d = 3, 9, 9, 4
    from repro.core import vdp
    ho, wo = vdp.out_hw(h, w, k, stride, "SAME")
    x4 = jnp.asarray(rng.normal(size=(b, h, w, d)) * 4.0, jnp.float32)
    x4p = ex._pad_spatial(x4, k, stride, "SAME")
    s = k * k * d
    s_rows = common.round_up(s, 128)
    rhs = jnp.asarray(rng.integers(-7, 8, (s_rows, 128)), jnp.int8)
    w_scale = jnp.float32(0.013)
    bias = jnp.asarray(rng.normal(size=(1, 128)), jnp.float32)
    got = kconv.vdpe_conv_q8(x4p, rhs, w_scale, k, stride, ho, wo,
                             bits=4, interpret=True, bias=bias, act="relu")
    a_scale = kconv.dac_scale(x4p, k, stride, ho, wo, 4)
    x_q = jnp.clip(jnp.round(x4p / a_scale[:, None, None, None]),
                   -7, 7).astype(jnp.int8)
    want = kconv.vdpe_conv(x_q, rhs, k, stride, ho, wo, interpret=True,
                           scale=a_scale * w_scale, bias=bias, act="relu")
    _eq(got, want)


def test_lattice_f32_gemms_match_int8_exactly():
    """int8-lattice values streamed as f32 accumulate EXACTLY: the float
    oracle's GEMMs are bit-convertible to the int8 GEMMs' results."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.integers(-7, 8, (128, 256)), jnp.int8)
    w = jnp.asarray(rng.integers(-7, 8, (256, 128)), jnp.int8)
    got_i = kern.vdpe_gemm(q, w, interpret=True)
    got_f = kern.vdpe_gemm(q.astype(jnp.float32), w.astype(jnp.float32),
                           interpret=True)
    assert got_f.dtype == jnp.float32
    _eq(got_i.astype(jnp.float32), got_f)
    qs = jnp.asarray(rng.integers(-7, 8, (128, 32)), jnp.int8)
    ws = jnp.asarray(rng.integers(-7, 8, (32, 128)), jnp.int8)
    _eq(kern.vdpe_pack_gemm_zs(qs, ws, interpret=True).astype(jnp.float32),
        kern.vdpe_pack_gemm_zs(qs.astype(jnp.float32),
                               ws.astype(jnp.float32), interpret=True))


# ---------------------------------------------------------------------------
# Engine level: all four kinds, both modes, per-image scales, ragged
# ---------------------------------------------------------------------------

def _all_modes_defs():
    """A chain covering SC/DC/PC/FC in BOTH packing modes.

    stem SC s=27 (Mode 2) -> dw DC -> sc2 SC s=72 (Mode 1) -> pw1 PC s=10
    (Mode 2) -> pw2 PC s=40 (Mode 1) -> fc1 S=192 (Mode 1) -> fc2 S=16
    (Mode 2).
    """
    rng = np.random.default_rng(42)

    def w(shape, s=0.5):
        return jnp.asarray(rng.normal(size=shape) * s, jnp.float32)

    return [
        engine.LayerDef("stem", ConvKind.SC, w((8, 3, 3, 3)),
                        act="relu", stride=2),
        engine.LayerDef("dw", ConvKind.DC, w((8, 3, 3)), act="relu6"),
        engine.LayerDef("sc2", ConvKind.SC, w((10, 3, 3, 8)),
                        bias=w((10,), 0.1), act="relu"),
        engine.LayerDef("pw1", ConvKind.PC, w((40, 1, 1, 10)), act="relu"),
        engine.LayerDef("pw2", ConvKind.PC, w((12, 1, 1, 40)),
                        bias=w((12,), 0.1), act="relu6"),
        engine.LayerDef("fc1", ConvKind.FC, w((16, 4 * 4 * 12)),
                        bias=w((16,), 0.1), act="relu"),
        engine.LayerDef("fc2", ConvKind.FC, w((5, 16))),
    ]


@pytest.fixture(scope="module")
def all_modes_plan():
    plan = engine.compile_model("q8_all_modes", _all_modes_defs())
    modes = [(lp.kind, lp.mode) for lp in plan.layers]
    # the chain must actually span both modes for every GEMM-kind
    assert (ConvKind.SC, engine.MODE_PACKED) in modes
    assert (ConvKind.SC, engine.MODE_DENSE) in modes
    assert (ConvKind.PC, engine.MODE_PACKED) in modes
    assert (ConvKind.PC, engine.MODE_DENSE) in modes
    assert (ConvKind.FC, engine.MODE_PACKED) in modes
    assert (ConvKind.FC, engine.MODE_DENSE) in modes
    assert (ConvKind.DC, engine.MODE_DEPTHWISE) in modes
    return plan


@pytest.mark.parametrize("batch", [1, 3, 5])
def test_q8_layerwise_matches_float_oracle(all_modes_plan, batch):
    """Satellite contract: per-image dequant-scale epilogues on the int8
    path, ragged batches, all four layer kinds, both modes — bitwise vs
    the float oracle, layer by layer."""
    plan = all_modes_plan
    rng = np.random.default_rng(batch)
    # per-image magnitudes spanning 4 orders: per-image DAC scales differ
    # wildly, so any cross-image scale leakage would flip integers
    mags = (10.0 ** np.arange(batch) / 100.0).reshape(batch, 1, 1, 1)
    x = jnp.asarray(rng.normal(size=(batch, 8, 8, 3)) * mags, jnp.float32)
    for lp in plan.layers:
        got = ex.forward_layer(plan, lp, x, interpret=True)
        want = ex.forward_layer_f32(plan, lp, x, interpret=True)
        _eq(got, want)
        x = got


def test_q8_batched_equals_per_image_loop(all_modes_plan):
    """Per-image quantization survives batching on the int8 path."""
    plan = all_modes_plan
    rng = np.random.default_rng(9)
    xs = jnp.asarray(rng.normal(size=(4, 8, 8, 3))
                     * (10.0 ** np.arange(4)).reshape(4, 1, 1, 1) / 10.0,
                     jnp.float32)
    batched = engine.forward(plan, xs, interpret=True)
    for i in range(4):
        # a single image's FC output stays (1, F) by the engine contract
        _eq(batched[i], engine.forward(plan, xs[i], interpret=True)[0])


@pytest.mark.parametrize("model", list(zoo.SERVING_MODELS))
def test_zoo_q8_eager_jit_and_oracles(model):
    """Whole serving zoo: int8 path == float oracle == im2col oracle,
    eager AND whole-model jit, batched and ragged."""
    engine.pipeline_cache_clear()
    plan = engine.compile_model(f"q8_{model}", zoo.serving_defs(model, 0))
    shape = zoo.serving_input_shape(model)
    rng = np.random.default_rng(0)
    for batch in (1, 5):
        x = jnp.asarray(rng.normal(size=(batch, *shape)), jnp.float32)
        got = engine.forward(plan, x, interpret=True)
        _eq(got, engine.forward_f32(plan, x, interpret=True))
        _eq(got, engine.forward_im2col(plan, x, interpret=True))
        _eq(got, engine.forward_jit(plan, x, interpret=True))


def test_planner_plan_q8_bitwise(all_modes_plan):
    """Planner-compiled heterogeneous-point plans ride the q8 path too and
    stay bitwise-equal to the fixed-point plan."""
    defs = _all_modes_defs()
    planned = engine.plan_model("q8_all_modes_planned", defs, (8, 8, 3))
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(3, 8, 8, 3)), jnp.float32)
    _eq(engine.forward(planned, x, interpret=True),
        engine.forward(all_modes_plan, x, interpret=True))


# ---------------------------------------------------------------------------
# Plan weight bytes: the int8 imprint's HBM footprint
# ---------------------------------------------------------------------------

def test_plan_weight_bytes_halve_or_better(all_modes_plan):
    plan = all_modes_plan
    for lp in plan.layers:
        assert lp.rhs.dtype == jnp.int8       # pre-quantized at plan time
    assert plan.weight_bytes < plan.weight_bytes_f32
    # int8 operands + f32 scale/bias metadata: at least 2x under the f32
    # stream (in practice close to 4x — biases are the f32 remainder)
    assert plan.weight_bytes_f32 / plan.weight_bytes >= 2.0


def test_registry_weight_report():
    reg = PlanRegistry(capacity=2)
    reg.register("wr", lambda: _all_modes_defs(), input_shape=(8, 8, 3))
    reg.register("other", lambda: _all_modes_defs(), input_shape=(8, 8, 3))
    # cold-model report is read-only: computed out-of-band, nothing loaded
    rep_cold = reg.weight_report("wr")
    assert rep_cold["packed_bytes"] > 0
    assert rep_cold["ratio"] >= 2.0
    assert reg.loaded == []
    # resident report peeks the loaded plan without LRU promotion
    reg.get("wr")
    reg.get("other")                       # LRU order: [wr, other]
    rep = reg.weight_report("wr")
    assert rep == rep_cold
    assert reg.loaded == ["wr", "other"]   # no move_to_end from the peek
    st = reg.stats()
    assert st["weight_bytes_packed"] == 2 * rep["packed_bytes"]
    assert st["weight_bytes_f32_equiv"] == 2 * rep["f32_equiv_bytes"]
    with pytest.raises(KeyError):
        reg.weight_report("never_registered")
