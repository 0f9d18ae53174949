"""The readers of the program's sub-spans of ``CNNServer.step``: on a
synthetic run, on a run of a program without the spans, and in a traced
run of the tiny cells on the CPU."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import devtrace
import harness
from repro.obs.tracer import SpanRecord

DATA = Path(__file__).parent / "data"
LAYOUT = harness.Layout(benchmark=DATA / "benchmark_spans.json",
                        configs=DATA / "configs", traffic=DATA / "traffic",
                        workloads=DATA / "workloads",
                        peaks=DATA / "peaks.json")
SEED = 2 ** 41 + 5
SPAN_METRICS = {
    "bulk": ["h2d_ms_per_batch.bulk", "dispatch_ms_per_batch.bulk",
             "telemetry_ms_per_batch.bulk"],
    "online": ["h2d_ms_per_batch.online", "dispatch_ms_per_batch.online",
               "telemetry_ms_per_batch.online", "batcher_wait_ms.online"],
}


def _span(i, name, dur, parent=None, **args):
    return SpanRecord(name=name, cat="batch", ph="X", t0=float(i), dur=dur,
                      tid="main", span_id=i, parent_id=parent, args=args)


def _run(spans):
    empty = np.zeros(0)
    return harness.RunRecord(
        setup_s=1.0, t_start=0.0, t_end=1.0, due=empty, started=empty,
        done=empty, counted=empty.astype(bool), batches=[], geoms=[],
        ops_per_image=1, peaks={}, spans=tuple(spans))


#: two batches: 4 requests waiting 1 ms on average, then 2 waiting 4 ms
SYNTHETIC = [
    _span(1, "batch", 0.030, size=4, queue_wait_s=0.001),
    _span(2, "h2d", 0.010, 1), _span(3, "dispatch", 0.002, 1),
    _span(4, "telemetry", 0.001, 1),
    _span(5, "batch", 0.020, size=2, queue_wait_s=0.004),
    _span(6, "h2d", 0.006, 5), _span(7, "dispatch", 0.001, 5),
    _span(8, "telemetry", 0.003, 5),
    SpanRecord(name="request", cat="request", ph="b", t0=0.0, dur=0.0,
               tid="requests", span_id=9, parent_id=None, args={}, aid=1),
]
WANT = {"h2d_ms_per_batch": 8.0, "dispatch_ms_per_batch": 1.5,
        "telemetry_ms_per_batch": 2.0,
        "batcher_wait_ms": (4 * 1.0 + 2 * 4.0) / 6}


@pytest.mark.parametrize("name", sum(SPAN_METRICS.values(), []))
def test_reader_on_a_synthetic_run(name):
    value = harness.load_reducer(LAYOUT, name)
    assert value(_run(SYNTHETIC)) == pytest.approx(WANT[name.split(".")[0]])


@pytest.mark.parametrize("name", sum(SPAN_METRICS.values(), []))
def test_reader_reads_nothing_where_the_program_has_no_such_span(name):
    # the program before these spans: batch, stack, exec and epilogue only
    old = [_span(1, "batch", 0.03, size=4), _span(2, "stack", 0.01, 1),
           _span(3, "exec", 0.01, 1), _span(4, "epilogue", 0.005, 1)]
    value = harness.load_reducer(LAYOUT, name)
    assert value(_run(old)) is None and value(_run(())) is None


@pytest.mark.parametrize("cell, kind", [("tiny_bulk", "bulk"),
                                        ("tiny_online", "online")])
def test_traced_run_reports_the_sub_span_metrics(monkeypatch, cell, kind):
    ex = json.loads((DATA / "trace_excerpt.json").read_text())
    monkeypatch.setattr(harness, "_reduce_trace",
                        lambda d, window, host: devtrace.reduce(ex, host))
    line = harness.run(cell, SEED, 0.5, True, time.perf_counter(), LAYOUT)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SPAN_METRICS[kind]) <= set(got)
    host = got[f"host_ms_per_batch.{kind}"]
    assert 0 < got[f"h2d_ms_per_batch.{kind}"] + \
        got[f"telemetry_ms_per_batch.{kind}"] <= host
    assert got[f"dispatch_ms_per_batch.{kind}"] > 0
    if kind == "online":
        # the same interval, timed inside the program and from outside
        assert got["batcher_wait_ms.online"] == pytest.approx(
            got["queue_wait_ms.online"], rel=1e-6)
