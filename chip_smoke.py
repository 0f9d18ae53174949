"""Serve MobileNetV1 1.0/224 and the three serving minis on one TPU chip.

Drives the main path once, through the entry points a user calls:
``PlanRegistry`` -> ``warm_pipelines`` -> ``CNNServer`` -> the whole-model
jitted pipeline -> the Pallas kernels compiled by Mosaic.  Weights and
images are random, made from ``--seed``.  Every served output is compared
bit for bit with ``core.vdp.reference_forward``, a plain jnp reference that
shares no code with the engine or its kernels.

Run on a machine with one TPU chip:

    python3 chip_smoke.py

It exits non-zero, printing no result, when JAX's default backend is not a
TPU.  The last line of a passing run is one JSON object naming the device.
The img/s it prints is a wall-clock smoke figure, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

import jax

#: Requests per model: MobileNetV1 gets two full batches of 8 and a ragged
#: tail of 5 (bucket 8); each mini gets one ragged batch.
MOBILENET_REQUESTS = 21
MINI_REQUESTS = 5
MAX_BATCH = 8


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}, not a TPU",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import numpy as np

    from repro import compile_cache, engine, serve
    from repro.cnn.layers import ConvKind
    from repro.core import vdp
    from repro.engine import pipeline
    from repro.serve import models as zoo

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {compile_cache.enable()}")

    models = {"mobilenet_v1": (lambda: zoo.mobilenet_v1_defs(args.seed),
                               (224, 224, 3), MOBILENET_REQUESTS)}
    for name in zoo.SERVING_MODELS:
        models[name] = ((lambda n=name: zoo.serving_defs(n, args.seed)),
                        zoo.serving_input_shape(name), MINI_REQUESTS)
    registry = serve.PlanRegistry(capacity=len(models))
    for name, (factory, shape, _) in models.items():
        registry.register(name, factory, shape)

    # the MobileNetV1 pipeline must hold one Mosaic kernel per SC/PC/FC
    # layer: interpret mode would lower to none
    plan = registry.get("mobilenet_v1").plan
    routes = [engine.layer_route(lp) for lp in plan.layers]
    for lp, route in zip(plan.layers, routes):
        print(f"route {plan.name}.{lp.name}: {route}")
    census = dict(Counter(routes))
    print(f"route census mobilenet_v1: {census}")
    kernel_layers = sum(1 for lp in plan.layers
                        if lp.kind is not ConvKind.DC)
    # counted in the compiled HLO: the lowered module calls one private
    # function per distinct kernel, so repeated layers (pw7-pw11) share one
    compiled = pipeline.get_pipeline(plan).lower(
        pipeline._layer_params(plan),
        jax.ShapeDtypeStruct((MAX_BATCH, 224, 224, 3), np.float32)).compile()
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    print(f"tpu_custom_call in mobilenet_v1 pipeline: {calls} "
          f"(SC/PC/FC layers: {kernel_layers})")
    assert calls == kernel_layers, (calls, kernel_layers)
    assert len(census) == 4, census

    server = serve.CNNServer(registry, max_batch=MAX_BATCH, max_wait_s=0.0)
    rng = np.random.default_rng(args.seed)
    ok = True
    for name, (factory, shape, n_req) in models.items():
        t0 = time.perf_counter()
        buckets = registry.warm_pipelines(name, max_batch=MAX_BATCH)
        compile_s = time.perf_counter() - t0
        compiles = engine.pipeline_cache_info()["compiles"]
        print(f"{name}: warmed buckets {buckets} in {compile_s:.3f} s "
              f"(pipeline compiles so far: {compiles})")
        images = rng.normal(size=(n_req, *shape)).astype(np.float32)
        t0 = time.perf_counter()
        rids = [server.submit(name, im) for im in images]
        results = server.run_until_drained()
        wall_s = time.perf_counter() - t0
        served = np.stack([results[r] for r in rids])
        stalls = server.pipeline_compiles
        print(f"{name}: served {n_req} requests in {wall_s:.4f} s, "
              f"{n_req / wall_s:.2f} img/s (wall-clock smoke figure, not a "
              f"benchmark); compile stalls while serving: {stalls}")
        assert stalls == 0, stalls
        defs = factory()
        want = np.concatenate([
            np.asarray(vdp.reference_forward(defs, images[i:i + MAX_BATCH]))
            for i in range(0, n_req, MAX_BATCH)])
        assert served.shape == want.shape, (served.shape, want.shape)
        assert np.all(np.isfinite(served)), name
        diff = float(np.max(np.abs(served - want)))
        bitwise = bool(np.array_equal(served, want))
        print(f"{name}: max |diff| vs plain reference {diff!r}, "
              f"bitwise {'equal' if bitwise else 'NOT equal'}, "
              f"output std {float(np.std(want))!r}")
        ok &= bitwise
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
