"""The serving step's phases on the profiler's clock: the sub-spans of
``CNNServer.step`` and their counters, the ``Tracer``'s
``jax.profiler.TraceAnnotation`` mirror (on a CPU profile's host plane),
the free path with tracing off, the batch's stack (on the host and in one
transfer for host images, on the device when a request's image is already
there, the same bits either way), and the pipeline's named scopes per
layer and stage in the compiled HLO."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs, serve
from repro.cnn.layers import ConvKind
from repro.engine import executor, pipeline
from repro.obs import tracer as tracer_mod
from repro.serve import server as server_mod

jax.config.update("jax_platform_name", "cpu")

SHAPE = (8, 8, 3)
#: span -> its parent in one unsharded ``CNNServer.step``
PARENT = {"plan.fetch": "batch", "stack": "batch", "host_stack": "stack",
          "h2d": "stack",
          "exec": "batch", "dispatch": "exec", "device_wait": "exec",
          "epilogue": "batch", "d2h": "epilogue", "telemetry": "epilogue"}


def _registry():
    """SC stem + DC + PC + FC: every route of the engine."""
    def factory():
        rng = np.random.default_rng(7)
        return [
            engine.LayerDef("stem", ConvKind.SC,
                            jnp.asarray(rng.normal(size=(6, 3, 3, 3)),
                                        jnp.float32), act="relu", stride=2),
            engine.LayerDef("dw", ConvKind.DC,
                            jnp.asarray(rng.normal(size=(6, 3, 3)),
                                        jnp.float32), act="relu6"),
            engine.LayerDef("pw", ConvKind.PC,
                            jnp.asarray(rng.normal(size=(8, 1, 1, 6)),
                                        jnp.float32), act="relu"),
            engine.LayerDef("fc", ConvKind.FC,
                            jnp.asarray(rng.normal(size=(4, 4 * 4 * 8)),
                                        jnp.float32)),
        ]
    reg = serve.PlanRegistry(capacity=2)
    reg.register("micro", factory, input_shape=SHAPE)
    return reg


def _images(n, seed=0):
    return np.random.default_rng(seed).normal(
        size=(n,) + SHAPE).astype(np.float32)


def _host_images(kind, n):
    """``n`` host images of one kind: f32 views into one buffer at
    unaligned, overlapping offsets (as the benchmark's generator cuts
    them), or whole f64 or uint8 arrays."""
    size = int(np.prod(SHAPE))
    rng = np.random.default_rng(3)
    if kind == "f32_views":
        buf = rng.uniform(-1.0, 1.0, 7 * n + size).astype(np.float32)
        return [buf[7 * i:7 * i + size].reshape(SHAPE) for i in range(n)]
    if kind == "f64":
        return list(rng.normal(size=(n,) + SHAPE))
    return list(rng.integers(0, 256, size=(n,) + SHAPE, dtype=np.uint8))


HOST_KINDS = ["f32_views", "f64", "u8"]


def _serve_one_batch(srv, n=4, t_submit=(1.0, 1.5, 2.0, 2.5), now=3.0):
    for x, t in zip(_images(n), t_submit):
        srv.submit("micro", x, now=t)
    return srv.step(now=now, force=True)


def _traced_server():
    tr = obs.Tracer()
    srv = serve.CNNServer(_registry(), max_batch=4, max_wait_s=0.0,
                          tracer=tr, interpret=True)
    return srv, tr


def test_traced_step_records_the_phases_under_their_parents():
    srv, tr = _traced_server()
    assert _serve_one_batch(srv) == 4
    spans = [s for s in tr.events() if s.ph == "X"]
    by_name = {s.name: s for s in spans}
    assert set(by_name) == set(PARENT) | {"batch"}
    assert len(spans) == len(by_name)          # one of each per batch
    for child, parent in PARENT.items():
        assert by_name[child].parent_id == by_name[parent].span_id, child
        c, p = by_name[child], by_name[parent]
        assert p.t0 <= c.t0 and c.t0 + c.dur <= p.t0 + p.dur
    assert by_name["batch"].parent_id is None
    assert by_name["dispatch"].args["bucket"] == 4
    assert by_name["d2h"].args["bytes"] == 4 * 4 * 4   # (4, 4) f32 logits
    # the batch's mean queue wait, where the batcher formed it
    assert by_name["batch"].args["queue_wait_s"] == pytest.approx(
        np.mean([3.0 - t for t in (1.0, 1.5, 2.0, 2.5)]))
    # the modelled-clock mirror still lands on the batch span
    assert by_name["batch"].hw_instance == "local"
    assert by_name["batch"].hw_s > 0


@pytest.mark.parametrize("kind", HOST_KINDS)
@pytest.mark.parametrize("n", [1, 3])
def test_h2d_counts_the_transfers_and_their_bytes(n, kind):
    srv, tr = _traced_server()
    xs = _host_images(kind, n)
    for x in xs:
        srv.submit("micro", x, now=0.0)
    assert srv.step(now=0.0, force=True) == n
    by_name = {s.name: s for s in tr.events() if s.ph == "X"}
    h2d, host = by_name["h2d"], by_name["host_stack"]
    # host images: stacked on the host, then one transfer of the batch's
    # f32 bytes, whatever the images' own dtype
    f32_bytes = n * int(np.prod(SHAPE)) * 4
    assert by_name["stack"].args["host_stacked"] == 1
    assert host.parent_id == by_name["stack"].span_id
    assert host.args["bytes"] == f32_bytes
    assert h2d.args["transfers"] == 1
    assert h2d.args["bytes"] == f32_bytes
    assert 0 <= h2d.args["max_ms"] <= 1e3 * h2d.dur


@pytest.mark.parametrize("kind", HOST_KINDS)
@pytest.mark.parametrize("n", [1, 3, 4])
def test_a_host_stacked_batch_serves_the_bits_of_the_forward_pass(n, kind):
    srv = serve.CNNServer(_registry(), max_batch=4, max_wait_s=0.0,
                          interpret=True)
    xs = _host_images(kind, n)
    rids = [srv.submit("micro", x, now=0.0) for x in xs]
    assert srv.step(now=0.0, force=True) == n
    entry = srv.registry.get("micro")
    want = engine.forward(entry.plan, jnp.asarray(np.stack(xs), jnp.float32),
                          interpret=True)
    np.testing.assert_array_equal(
        np.stack([srv.results[r] for r in rids]), np.asarray(want))


@pytest.mark.parametrize("on_device", [(0, 1, 2), (1,)],
                         ids=["every_image", "one_image"])
def test_a_batch_holding_a_device_array_is_stacked_on_the_device(on_device):
    srv, tr = _traced_server()
    xs = _images(3)
    rids = [srv.submit("micro", jnp.asarray(x) if i in on_device else x,
                       now=0.0) for i, x in enumerate(xs)]
    assert srv.step(now=0.0, force=True) == 3
    by_name = {s.name: s for s in tr.events() if s.ph == "X"}
    assert "host_stack" not in by_name
    assert by_name["stack"].args["host_stacked"] == 0
    assert by_name["h2d"].args["transfers"] == 3
    assert by_name["h2d"].args["bytes"] == xs.nbytes
    # the same bits as the host path's
    host_srv = serve.CNNServer(_registry(), max_batch=4, max_wait_s=0.0,
                               interpret=True)
    host_rids = [host_srv.submit("micro", x, now=0.0) for x in xs]
    assert host_srv.step(now=0.0, force=True) == 3
    np.testing.assert_array_equal(
        np.stack([srv.results[r] for r in rids]),
        np.stack([host_srv.results[r] for r in host_rids]))


def _host_events(trace_dir):
    """The profile's ``/host:CPU`` events by name: (start, end, stats)."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


def test_spans_land_on_the_profiles_host_plane_with_their_nesting(tmp_path):
    srv, tr = _traced_server()
    _serve_one_batch(srv)                      # compiles outside the profile
    tr.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve_one_batch(srv, t_submit=(4.0, 4.0, 4.0, 4.0), now=5.0)
    finally:
        jax.profiler.stop_trace()
    host = _host_events(str(tmp_path))
    for name in set(PARENT) | {"batch"}:
        assert len(host.get(name, ())) == 1, name
    for child, parent in PARENT.items():
        (c0, c1, _), = host[child]
        (p0, p1, _), = host[parent]
        assert p0 <= c0 and c1 <= p1, (child, parent)
    (_, _, h2d), = host["h2d"]
    assert h2d["transfers"] == 1 and h2d["bytes"] == 4 * 8 * 8 * 3 * 4
    (_, _, stack), = host["stack"]
    assert stack["host_stacked"] == 1
    (_, _, batch), = host["batch"]
    assert batch["size"] == 4 and batch["model"] == "micro"
    assert batch["queue_wait_s"] == pytest.approx(1.0)


def test_annotations_carry_the_scalar_args_and_skip_sampled_out_spans(
        monkeypatch):
    made = []

    class Recorder:
        def __init__(self, name):
            self.name, self.meta, self.open = name, None, False
            made.append(self)

        def __enter__(self):
            self.open = True

        def set_metadata(self, **kw):
            self.meta = kw

        def __exit__(self, *exc):
            self.open = False

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Recorder)
    tr = obs.Tracer(sample={"shard": 0.5})
    with tr.span("outer", n=3, what="x", shape=(1, 2)) as sp:
        sp.set(late=0.5)
    for _ in range(2):
        with tr.span("s", cat="shard"):
            pass
    assert [a.name for a in made] == ["outer", "s"]    # one s sampled out
    assert made[0].meta == {"n": 3, "what": "x", "late": 0.5}
    assert not any(a.open for a in made)


def test_tracing_off_makes_no_annotation_and_times_no_transfer(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("ran with tracing off")

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", boom)
    monkeypatch.setattr(server_mod, "_timed_transfers", boom)
    srv = serve.CNNServer(_registry(), max_batch=4, max_wait_s=0.0,
                          interpret=True)
    xs = _images(3)
    rids = [srv.submit("micro", x, now=0.0) for x in xs]
    assert srv.step(now=0.0, force=True) == 3
    entry = srv.registry.get("micro")
    want = engine.forward(entry.plan, jnp.asarray(xs), interpret=True)
    np.testing.assert_array_equal(
        np.stack([srv.results[r] for r in rids]), np.asarray(want))


def test_pipeline_hlo_names_each_layer_and_its_stages():
    plan = _registry().get("micro").plan
    fn = pipeline.get_pipeline(plan, interpret=True)
    x = jnp.zeros((2,) + SHAPE, jnp.float32)
    hlo = fn.lower(pipeline._layer_params(plan), x).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]+)"', hlo))
    layers = {m.group(1) for n in op_names
              for m in [re.search(r"/(L\d\d_[^/]+)/", n)] if m}
    want = [pipeline.layer_scope(i, lp) for i, lp in enumerate(plan.layers)]
    assert sorted(layers) == want == [
        "L00_stem_conv_implicit_mode2_zs", "L01_dw_depthwise_vpu",
        "L02_pw_conv_implicit_mode2_zs", "L03_fc_fc_gemm"]
    assert executor.layer_route(plan.layers[1]) == executor.ROUTE_DEPTHWISE

    def stages(layer):
        return {s for n in op_names if f"/{layer}/" in n
                for s in ("pad", "dac_scale", "phase_planes", "depthwise",
                          "out") if f"/{s}/" in n}

    assert stages(want[0]) == {"pad", "dac_scale", "phase_planes", "out"}
    assert stages(want[1]) == {"pad", "dac_scale", "depthwise"}
    # a 1x1 stride-1 layer needs no padding, and its phase planes are a
    # reshape that compiles to no operation
    assert stages(want[2]) == {"dac_scale", "out"}
    assert stages(want[3]) == {"dac_scale", "out"}
