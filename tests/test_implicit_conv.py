"""Implicit-GEMM conv kernels + whole-model jitted pipeline.

Bitwise contracts under test:

* kernels/vdpe_conv.py == the materialized im2col -> GEMM oracle at the
  raw-int32 and fused-epilogue levels (scalar-SMEM and per-image scales);
* engine forward_layer (implicit) == forward_layer_im2col across
  SC/PC/DC, strides 1/2, SAME/VALID, single images and batches;
* engine.forward_jit (one XLA dispatch, bucketed batches) == the eager
  layer loop for ragged batch sizes, compiling once per (plan, bucket);
* the shared alignment helpers (kernels/common.py).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.cnn.layers import ConvKind
from repro.core import vdp
from repro.engine import executor as ex
from repro.kernels import common, ops, ref
from repro.kernels import vdpe_conv as kconv
from repro.serve import models as zoo

jax.config.update("jax_platform_name", "cpu")


def _rand_int8(rng, shape, lo=-7, hi=8):
    return jnp.asarray(rng.integers(lo, hi, shape), jnp.int8)


# ---------------------------------------------------------------------------
# Shared helpers (kernels/common.py)
# ---------------------------------------------------------------------------

def test_round_up():
    assert common.round_up(0, 128) == 0
    assert common.round_up(1, 128) == 128
    assert common.round_up(128, 128) == 128
    assert common.round_up(129, 128) == 256
    assert common.round_up(27, 32) == 32


def test_pad_to():
    a = jnp.ones((3, 5), jnp.int8)
    p = common.pad_to(a, 8, 128)
    assert p.shape == (8, 128)
    np.testing.assert_array_equal(np.asarray(p[:3, :5]), np.asarray(a))
    assert int(jnp.abs(p).sum()) == 15          # padding is zeros


def test_single_round_up_definition():
    """The alignment helper has ONE home; the old copy-paste sites import
    from it instead of redefining it."""
    from repro.engine import plan as plan_mod
    assert ops._round_up is common.round_up
    assert plan_mod._round_up is common.round_up
    assert ex._round_up is common.round_up


# ---------------------------------------------------------------------------
# Kernel level: implicit gather == materialized im2col contraction
# ---------------------------------------------------------------------------

def _im2col_int(x_q, k, stride, ho, wo):
    """Oracle DIV matrix from the already-padded quantized image batch."""
    b, hp, wp, d = x_q.shape
    cols = []
    for kk in range(k * k):
        di, dj = divmod(kk, k)
        cols.append(x_q[:, di:di + stride * (ho - 1) + 1:stride,
                        dj:dj + stride * (wo - 1) + 1:stride, :])
    return jnp.stack(cols, axis=3).reshape(b, ho * wo, k * k * d)


@pytest.mark.parametrize("k,stride", [(1, 1), (1, 2), (3, 1), (3, 2)])
def test_vdpe_conv_matches_im2col_gemm_raw(k, stride):
    """Raw int32 accumulators: the in-kernel tap gather == the (B, P, S)
    DIV matrix contraction, for every tap geometry."""
    rng = np.random.default_rng(10 * k + stride)
    b, d, f_pad = 2, 5, 128
    ho = wo = 4
    hp = stride * (ho - 1) + k
    x_q = _rand_int8(rng, (b, hp, hp, d))
    s = k * k * d
    rhs = _rand_int8(rng, (s, f_pad))
    got = kconv.vdpe_conv(x_q, rhs, k, stride, ho, wo, interpret=True)
    divs = _im2col_int(x_q, k, stride, ho, wo)
    want = jax.lax.dot_general(
        divs.astype(jnp.int32), rhs.astype(jnp.int32),
        (((2,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("scale_kind", ["scalar", "per_image"])
def test_vdpe_conv_fused_epilogue_variants(scale_kind):
    """Both epilogue variants == epilogue_ref over the raw accumulator."""
    rng = np.random.default_rng(7)
    b, d, f_pad, k, stride = 3, 4, 128, 3, 1
    ho = wo = 5
    hp = stride * (ho - 1) + k
    x_q = _rand_int8(rng, (b, hp, hp, d))
    rhs = _rand_int8(rng, (k * k * d, f_pad))
    bias = jnp.asarray(rng.normal(size=(1, f_pad)), jnp.float32)
    if scale_kind == "scalar":
        scale = jnp.float32(0.037)
        scale_bc = scale
    else:
        scale = jnp.asarray(rng.random(b) * 0.1 + 0.01, jnp.float32)
        scale_bc = scale[:, None, None]
    raw = kconv.vdpe_conv(x_q, rhs, k, stride, ho, wo, interpret=True)
    got = kconv.vdpe_conv(x_q, rhs, k, stride, ho, wo, interpret=True,
                          scale=scale, bias=bias, act="relu6")
    want = ref.epilogue_ref(raw, scale_bc, bias[None], "relu6")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def test_pack_conv_zs_rejects_block_diagonal_operand():
    """Structural zero-skipping: the (y*x, F) block-diagonal pack cannot
    enter — only the (x, F) segment-sum rides the Mode-2 conv kernel."""
    rng = np.random.default_rng(0)
    x_q = _rand_int8(rng, (1, 4, 4, 9))
    y = ops.N_TPU // ops.X_TPU
    rhs_bd = _rand_int8(rng, (y * ops.X_TPU, 128))
    with pytest.raises(AssertionError, match="segment-sum"):
        kconv.vdpe_pack_conv_zs(x_q, rhs_bd, 1, 1, 4, 4, x=ops.X_TPU,
                                interpret=True)


def test_pack_conv_zs_matches_mode1_conv():
    """The zero-skipping conv == the dense Mode-1 conv on the same weights
    (segment rows beyond S are zero, so both contract the same S taps)."""
    rng = np.random.default_rng(3)
    b, d, k, f = 2, 3, 3, 16
    ho = wo = 4
    x_q = _rand_int8(rng, (b, ho + k - 1, wo + k - 1, d))
    s = k * k * d                                 # 27 <= x = 32
    dkvs = _rand_int8(rng, (f, s))
    rhs_seg = common.pad_to(ops.pack_mode2_segments(dkvs, ops.X_TPU),
                            ops.X_TPU, 128)
    rhs_m1 = common.pad_to(jnp.transpose(dkvs), s, 128)
    got = kconv.vdpe_pack_conv_zs(x_q, rhs_seg, k, 1, ho, wo,
                                  x=ops.X_TPU, interpret=True)
    want = kconv.vdpe_conv(x_q, rhs_m1, k, 1, ho, wo, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_conv_window_bounds_guard():
    """An activation smaller than the tap window is rejected, not read OOB."""
    rng = np.random.default_rng(1)
    x_q = _rand_int8(rng, (1, 4, 4, 2))
    rhs = _rand_int8(rng, (3 * 3 * 2, 128))
    with pytest.raises(AssertionError, match="pad"):
        kconv.vdpe_conv(x_q, rhs, 3, 2, 4, 4, interpret=True)


# ---------------------------------------------------------------------------
# Executor level: implicit path == im2col oracle path, bitwise
# ---------------------------------------------------------------------------

def _layer_def(kind, k, stride, padding, bias, act, rng, d=6, f=20):
    if kind is ConvKind.DC:
        w = jnp.asarray(rng.normal(size=(d, k, k)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(d,)), jnp.float32) if bias else None
    else:
        kk = 1 if kind is ConvKind.PC else k
        w = jnp.asarray(rng.normal(size=(f, kk, kk, d)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(f,)), jnp.float32) if bias else None
    return engine.LayerDef("l", kind, w, bias=b, act=act,
                           stride=stride, padding=padding)


@pytest.mark.parametrize("kind", [ConvKind.SC, ConvKind.PC, ConvKind.DC])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_implicit_matches_im2col_oracle(kind, stride, padding):
    """forward_layer (implicit) == forward_layer_im2col, bitwise, for
    single images and batches (both epilogue variants), biased relu6."""
    rng = np.random.default_rng(hash((kind.value, stride, padding)) % 2**32)
    ld = _layer_def(kind, 3, stride, padding, bias=True, act="relu6", rng=rng)
    plan = engine.compile_model(
        f"imp_{kind.value}_{stride}_{padding}", [ld])
    (lp,) = plan.layers
    for b in (1, 3):                  # scalar-SMEM and per-image epilogues
        x = jnp.asarray(rng.normal(size=(b, 9, 9, 6)), jnp.float32)
        xin = x[0] if b == 1 else x   # also cover the single-image API
        got = engine.forward_layer(plan, lp, xin, interpret=True)
        want = engine.forward_layer_im2col(plan, lp, xin, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind,act,bias", [
    (ConvKind.SC, "none", False),
    (ConvKind.PC, "relu", False),
    (ConvKind.DC, "relu", True),
])
def test_implicit_matches_im2col_oracle_epilogue_mix(kind, act, bias):
    """Bias-free and activation-mix coverage of the same bitwise contract."""
    rng = np.random.default_rng(17)
    ld = _layer_def(kind, 3, 1, "SAME", bias=bias, act=act, rng=rng)
    plan = engine.compile_model(f"mix_{kind.value}_{act}_{bias}", [ld])
    (lp,) = plan.layers
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 6)), jnp.float32)
    got = engine.forward_layer(plan, lp, x, interpret=True)
    want = engine.forward_layer_im2col(plan, lp, x, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_implicit_dense_mode1_conv_matches_oracle():
    """A conv with S > X_TPU routes to the dense implicit kernel and still
    matches the oracle bitwise."""
    rng = np.random.default_rng(23)
    ld = _layer_def(ConvKind.PC, 1, 1, "SAME", bias=True, act="relu",
                    rng=rng, d=48, f=12)
    plan = engine.compile_model("imp_dense_pc", [ld])
    (lp,) = plan.layers
    assert lp.mode == engine.MODE_DENSE
    assert engine.layer_route(lp) == ex.ROUTE_CONV_M1
    x = jnp.asarray(rng.normal(size=(2, 5, 5, 48)), jnp.float32)
    got = engine.forward_layer(plan, lp, x, interpret=True)
    want = engine.forward_layer_im2col(plan, lp, x, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_layer_route_census():
    """Every serving-zoo layer routes off the im2col path: conv layers to
    the implicit kernels, DC to the windowed VPU path, FC to the GEMM."""
    for name in zoo.SERVING_MODELS:
        plan = engine.compile_model(
            f"route_{name}", zoo.serving_defs(name, 0))
        routes = [engine.layer_route(lp) for lp in plan.layers]
        assert routes[-1] == ex.ROUTE_FC_GEMM
        assert set(routes[:-1]) <= {ex.ROUTE_CONV_M1, ex.ROUTE_CONV_ZS,
                                    ex.ROUTE_DEPTHWISE}
        assert any(r in (ex.ROUTE_CONV_M1, ex.ROUTE_CONV_ZS)
                   for r in routes)


def test_whole_model_implicit_matches_im2col():
    """Whole serving-zoo models, batched: implicit == im2col, bitwise."""
    rng = np.random.default_rng(5)
    for name in zoo.SERVING_MODELS:
        plan = engine.compile_model(
            f"wm_{name}", zoo.serving_defs(name, 0))
        x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
        got = engine.forward(plan, x, interpret=True)
        want = engine.forward_im2col(plan, x, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Whole-model jitted pipeline
# ---------------------------------------------------------------------------

def test_batch_bucket():
    assert [engine.batch_bucket(b) for b in (1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 16]


def test_forward_jit_bitwise_ragged_batches():
    """Bucket-padded jitted pipeline == the eager layer loop, bitwise, for
    ragged batch sizes (pad images never leak into real outputs)."""
    engine.pipeline_cache_clear()
    rng = np.random.default_rng(9)
    plan = engine.compile_model(
        "jit_ragged", zoo.serving_defs("xception_mini", 0))
    for b in (1, 2, 3, 5):
        x = jnp.asarray(rng.normal(size=(b, 16, 16, 3)), jnp.float32)
        got = engine.forward_jit(plan, x, interpret=True)
        want = engine.forward(plan, x, interpret=True)
        assert got.shape[0] == b
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_forward_jit_compiles_once_per_plan_bucket():
    """The compile-stall contract: one trace per (plan, batch bucket);
    every later batch in the bucket reuses the executable."""
    engine.pipeline_cache_clear()
    rng = np.random.default_rng(2)
    plan = engine.compile_model(
        "jit_cache", zoo.serving_defs("shufflenet_mini", 0))

    def compiles():
        return engine.pipeline_cache_info()["compiles"]

    x3 = jnp.asarray(rng.normal(size=(3, 16, 16, 3)), jnp.float32)
    engine.forward_jit(plan, x3, interpret=True)        # bucket 4: compile
    assert compiles() == 1
    x4 = jnp.asarray(rng.normal(size=(4, 16, 16, 3)), jnp.float32)
    engine.forward_jit(plan, x4, interpret=True)        # same bucket: hit
    assert compiles() == 1
    x2 = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    engine.forward_jit(plan, x2, interpret=True)        # bucket 2: compile
    assert compiles() == 2
    engine.forward_jit(plan, x3, interpret=True)        # bucket 4 again: hit
    assert compiles() == 2
    # a distinct plan compiles its own pipeline
    other = engine.compile_model(
        "jit_cache_other", zoo.serving_defs("shufflenet_mini", 1))
    engine.forward_jit(plan, x4, interpret=True)
    engine.forward_jit(other, x4, interpret=True)
    assert compiles() == 3


def test_forward_jit_rejects_single_image():
    plan = engine.compile_model(
        "jit_shape", zoo.serving_defs("shufflenet_mini", 2))
    with pytest.raises(ValueError, match="batches"):
        engine.forward_jit(plan, jnp.zeros((16, 16, 3), jnp.float32),
                           interpret=True)


def test_pipeline_cache_bounded_lru(monkeypatch):
    """Beyond CACHE_CAPACITY plans, the least-recently-used pipeline (and
    its strong plan reference) is dropped — unregistered callers cannot
    pin every imprint they ever served."""
    from repro.engine import pipeline
    engine.pipeline_cache_clear()
    monkeypatch.setattr(pipeline, "CACHE_CAPACITY", 2)
    plans = [engine.compile_model(f"lru_{i}",
                                  zoo.serving_defs("shufflenet_mini", 10 + i))
             for i in range(3)]
    x = jnp.zeros((1, 16, 16, 3), jnp.float32)
    engine.forward_jit(plans[0], x, interpret=True)
    engine.forward_jit(plans[1], x, interpret=True)
    engine.forward_jit(plans[0], x, interpret=True)   # refresh plan 0
    engine.forward_jit(plans[2], x, interpret=True)   # evicts plan 1 (LRU)
    info = engine.pipeline_cache_info()
    assert info["size"] == 2 and info["evictions"] == 1
    assert id(plans[1]) not in pipeline._PIPELINES
    assert id(plans[0]) in pipeline._PIPELINES
    engine.pipeline_cache_clear()


def test_pipeline_evict_drops_plan_entry():
    engine.pipeline_cache_clear()
    plan = engine.compile_model(
        "jit_evict", zoo.serving_defs("shufflenet_mini", 3))
    x = jnp.zeros((1, 16, 16, 3), jnp.float32)
    engine.forward_jit(plan, x, interpret=True)
    assert engine.pipeline_cache_info()["size"] == 1
    engine.pipeline_evict(plan)
    assert engine.pipeline_cache_info()["size"] == 0


def test_server_counts_pipeline_compile_stalls():
    """A served model pays one pipeline compile per batch bucket; warmed
    buckets pay zero (registry.warm_pipelines)."""
    from repro import serve
    engine.pipeline_cache_clear()
    rng = np.random.default_rng(4)
    reg = serve.paper_cnn_registry(capacity=3)
    srv = serve.CNNServer(reg, max_batch=2, max_wait_s=0.0)
    model = "shufflenet_mini"

    def _submit(n):
        for _ in range(n):
            srv.submit(model, rng.normal(size=(16, 16, 3)))

    _submit(2)
    srv.run_until_drained()
    assert srv.pipeline_compiles == 1          # bucket 2, cold
    _submit(2)
    srv.run_until_drained()
    assert srv.pipeline_compiles == 1          # bucket 2 again, warm
    _submit(1)
    srv.run_until_drained()
    assert srv.pipeline_compiles == 2          # bucket 1, cold

    # pre-warming removes the stalls entirely for a fresh registry
    engine.pipeline_cache_clear()
    reg2 = serve.paper_cnn_registry(capacity=3)
    srv2 = serve.CNNServer(reg2, max_batch=2, max_wait_s=0.0)
    assert reg2.warm_pipelines(model, max_batch=2) == [1, 2]
    for n in (2, 1):
        for _ in range(n):
            srv2.submit(model, rng.normal(size=(16, 16, 3)))
        srv2.run_until_drained()
    assert srv2.pipeline_compiles == 0


def test_forward_jit_fc_row_batches():
    """FC-first plans serve (B, S) row batches through the pipeline too."""
    engine.pipeline_cache_clear()
    rng = np.random.default_rng(8)
    w = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
    plan = engine.compile_model(
        "jit_fc", [engine.LayerDef("fc", ConvKind.FC, w)])
    xb = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    got = engine.forward_jit(plan, xb, interpret=True)
    want = engine.forward(plan, xb, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Banded, phase-split kernels: hoisted DAC scale, strides, uneven bands
# ---------------------------------------------------------------------------

# the kernels' fused epilogue is one XLA expression (which XLA:CPU
# contracts into an FMA); the oracle's epilogue is evaluated the same way
_jit_epilogue = jax.jit(ref.epilogue_ref, static_argnames="act")


@pytest.mark.parametrize("mode2", [False, True])
@pytest.mark.parametrize("k,stride,h,w,rows", [
    (1, 1, 9, 8, None), (1, 1, 9, 8, 2), (3, 1, 9, 8, 4), (3, 1, 9, 8, None),
    (1, 2, 9, 16, 2), (3, 2, 9, 16, 2), (3, 2, 9, 16, None)])
def test_banded_conv_q8_matches_oracles(mode2, k, stride, h, w, rows):
    """The q8 kernels — DAC scale computed in XLA and read from SMEM,
    stride taps from phase planes, output rows tiled into bands that need
    not divide H — == the im2col oracle and the f32-lattice kernel."""
    rng = np.random.default_rng(100 * k + 10 * stride + (rows or 0))
    b, d = 2, 3
    x4 = jnp.asarray(rng.normal(size=(b, h, w, d)) * 3.0, jnp.float32)
    x4p = ex._pad_spatial(x4, k, stride, "SAME")
    ho, wo = vdp.out_hw(h, w, k, stride, "SAME")
    if rows is not None:
        assert ho % rows != 0                     # the last band is partial
    s = k * k * d
    rhs = _rand_int8(rng, (ops.X_TPU if mode2 else common.round_up(s, 128),
                           128))
    w_scale = jnp.float32(0.021)
    bias = jnp.asarray(rng.normal(size=(1, 128)), jnp.float32)
    if rows is None:                              # the public entry points
        if mode2:
            got = kconv.vdpe_pack_conv_zs_q8(
                x4p, rhs, w_scale, k, stride, ho, wo, x=ops.X_TPU,
                bias=bias, act="relu6", interpret=True)
        else:
            got = kconv.vdpe_conv_q8(x4p, rhs, w_scale, k, stride, ho, wo,
                                     bias=bias, act="relu6", interpret=True)
    else:                                         # their body, banded
        got = kconv._conv_q8(x4p, rhs, w_scale, k, stride, ho, wo, 4, 128,
                             True, bias, "relu6", rows=rows)
    a_scale = kconv.dac_scale(x4p, k, stride, ho, wo, 4)
    x_q = common.quantize_tile(x4p, a_scale[:, None, None, None], 4)
    acc = jax.lax.dot_general(
        _im2col_int(x_q, k, stride, ho, wo).astype(jnp.int32),
        rhs[:s].astype(jnp.int32), (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    want = _jit_epilogue(acc, (a_scale * w_scale)[:, None, None], bias[None],
                         act="relu6")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    lattice = kconv.vdpe_conv(x_q.astype(jnp.float32),
                              rhs.astype(jnp.float32), k, stride, ho, wo,
                              scale=a_scale * w_scale, bias=bias,
                              act="relu6", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(lattice))


@pytest.mark.parametrize("stride", [1, 2, 3])
def test_phase_planes_hold_strided_pixels(stride):
    """Plane p*s + q is x[:, p::s, q::s], cropped or zero-padded."""
    x = jnp.arange(2 * 10 * 11 * 3, dtype=jnp.float32).reshape(2, 10, 11, 3)
    rows, cols = 5, 4
    planes = kconv._phase_planes(x, stride, rows, cols)
    assert planes.shape == (2, stride * stride, rows, cols, 3)
    for p in range(stride):
        for q in range(stride):
            sub = x[:, p::stride, q::stride][:, :rows, :cols]
            want = jnp.pad(sub, ((0, 0), (0, rows - sub.shape[1]),
                                 (0, cols - sub.shape[2]), (0, 0)))
            np.testing.assert_array_equal(
                np.asarray(planes[:, p * stride + q]), np.asarray(want))


@pytest.mark.parametrize("ho,wo,d,stride,k", [
    (112, 112, 3, 2, 3), (112, 112, 32, 1, 1), (56, 56, 64, 1, 1),
    (28, 28, 256, 1, 1), (14, 14, 512, 1, 1), (7, 7, 1024, 1, 1)])
def test_band_rows_fit_vmem_budget(ho, wo, d, stride, k):
    """MobileNetV1's SC/PC layers get sublane-aligned bands whose padded
    input + output blocks fit one pipeline buffer's budget."""
    rows = kconv.band_rows(ho, wo, d, stride, k)
    assert 1 <= rows <= ho
    assert rows == ho or (rows * wo) % 8 == 0
    halo = (k - 1) // stride
    in_bytes = (stride * stride * (rows + halo) * common.round_up(wo + halo, 8)
                * common.round_up(d, 128) * 4)
    assert in_bytes + rows * wo * 128 * 4 <= kconv.VMEM_BAND_BYTES


# ---------------------------------------------------------------------------
# MobileNetV1 1.0 as an executable chain
# ---------------------------------------------------------------------------

def test_mobilenet_v1_defs_follow_the_census():
    """Full-width defs at 224 walk the census' shapes, plus the pool."""
    from repro.cnn.models import MODEL_ZOO
    defs = zoo.mobilenet_v1_defs(0)
    specs = engine.defs_to_specs(defs, (224, 224, 3))
    census = MODEL_ZOO["mobilenet_v1"]()
    pool = [sp for sp in specs if sp.name == "avg_pool"]
    assert [sp.canonical() for sp in specs if sp.name != "avg_pool"] == \
        [sp.canonical() for sp in census]
    assert pool[0].k == 7 and pool[0].f == 1024 and pool[0].h_out == 1
    assert all(ld.act == "relu6" for ld in defs
               if ld.name not in ("avg_pool", "predictions"))


def test_mobilenet_v1_chain_matches_plain_reference():
    """At 32x32 the chain hits all four routes, and the whole-model jitted
    pipeline equals core.vdp.reference_forward bit for bit."""
    defs = zoo.mobilenet_v1_defs(0, resolution=32)
    plan = engine.compile_model("mobilenet_v1_32", defs)
    routes = {lp.name: engine.layer_route(lp) for lp in plan.layers}
    assert len(routes) == 29
    assert routes["conv1"] == routes["pw1"] == ex.ROUTE_CONV_ZS
    assert all(routes[f"pw{i}"] == ex.ROUTE_CONV_M1 for i in range(2, 14))
    assert all(routes[f"dw{i}"] == ex.ROUTE_DEPTHWISE for i in range(1, 14))
    assert routes["avg_pool"] == ex.ROUTE_DEPTHWISE
    assert routes["predictions"] == ex.ROUTE_FC_GEMM
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 32, 32, 3)),
                    jnp.float32)
    got = engine.forward_jit(plan, x, interpret=True)
    want = vdp.reference_forward(defs, x)
    assert got.shape == (2, 1000)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
