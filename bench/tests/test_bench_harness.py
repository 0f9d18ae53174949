"""A run of the harness on the CPU at a tiny size, its output check, and
the check's failures: the control and faults planted under the timed path.

``harness.run`` is what ``run.py`` calls once it has found a TPU; these
tests call it directly on the CPU (Pallas in interpret mode), against a
tiny configuration with MobileNetV1's layer kinds under ``data/``, and
against one that brings its own model and reference modules.
"""
import json
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

import devtrace
import harness

DATA = Path(__file__).parent / "data"
LAYOUT = harness.Layout(benchmark=DATA / "benchmark.json",
                        configs=DATA / "configs", traffic=DATA / "traffic",
                        workloads=DATA / "workloads", peaks=DATA / "peaks.json",
                        modules=DATA)
SEED = 2 ** 40 + 3


def _run(cell, traced=False, seconds=0.5, **kw):
    return harness.run(cell, SEED, seconds, traced, time.perf_counter(),
                       LAYOUT, **kw)


def _well_formed(line, names):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == set(names)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["window"] == {"compile_stalls": 0, "jax_compiles": 0}
    json.dumps(line, allow_nan=False)


@pytest.mark.parametrize("cell, names", [
    ("tiny_bulk", {"img_per_s", "setup_s"}),
    ("tiny_online", {"p50_ms", "setup_s"}),
    # a configuration with its own modules, and no edit to the harness
    ("tiny_chain_bulk", {"img_per_s", "setup_s"}),
])
def test_untraced_run_ends_in_a_well_formed_correct_line(cell, names):
    line = _run(cell)
    _well_formed(line, names)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["logit_gap"]["value"] == 0.0
    assert line["checks"]["compared"]["value"] == 8


@pytest.mark.parametrize("cell, names", [
    ("tiny_bulk", {"host_ms_per_batch.bulk", "xla_ms_per_img",
                   "pallas_ms_per_img", "pallas_roofline", "idle_share",
                   "mfu"}),
    ("tiny_online", {"host_ms_per_batch.online", "queue_wait_ms.online"}),
])
def test_traced_run_reports_the_per_layer_metrics(monkeypatch, cell, names):
    # the CPU has no device trace: the recorded chip excerpt stands in
    ex = json.loads((DATA / "trace_excerpt.json").read_text())
    monkeypatch.setattr(harness, "_reduce_trace",
                        lambda d, window, host: devtrace.reduce(ex, host))
    line = _run(cell, traced=True)
    _well_formed(line, names)
    assert line["correct"] is True
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10


def test_control_at_one_bit_fewer_fails_the_check():
    line = _run("tiny_bulk", point_bits=3)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def _altered(out):
    return out.at[:, 0].add(1.0)


def _answers_swapped(out):
    return out[::-1]


def _half_left_out(out):
    return out.at[out.shape[0] // 2:].set(0.0)


@pytest.mark.parametrize("fault", [_altered, _answers_swapped,
                                   _half_left_out])
def test_a_fault_under_the_timed_path_fails_the_check(monkeypatch, fault):
    from repro import engine
    real = engine.forward_jit
    monkeypatch.setattr(engine, "forward_jit",
                        lambda plan, x, **kw: fault(real(plan, x, **kw)))
    line = _run("tiny_bulk")
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


def test_the_configurations_own_reference_decides_its_check(monkeypatch):
    cfg = json.loads((DATA / "configs" / "tiny_chain.json").read_text())
    ref = harness.config_modules(LAYOUT, cfg).reference
    real = ref.forward
    monkeypatch.setattr(ref, "forward",
                        lambda *a: _altered(jnp.asarray(real(*a))))
    line = _run("tiny_chain_bulk")
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


def test_a_slow_trace_write_leaves_no_request_unanswered(monkeypatch):
    # writing a long window's trace can outlast the drain's limit: the
    # requests still out when the window closes are answered first
    import jax
    ex = json.loads((DATA / "trace_excerpt.json").read_text())
    monkeypatch.setattr(harness, "_reduce_trace",
                        lambda d, window, host: devtrace.reduce(ex, host))
    monkeypatch.setattr(harness, "DRAIN_LIMIT_S", 5.0)
    stop = jax.profiler.stop_trace
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: (stop(), time.sleep(6.0)))
    line = _run("tiny_bulk", traced=True)
    assert line["failed"] == 0 and line["correct"] is True
