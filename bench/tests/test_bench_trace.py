"""The device-trace reduction, on a hand-made trace and a recorded one."""
import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).parent / "data"


def test_reduce_hand_made_trace():
    # window 0..100 ns; ops at 10-30 and 20-40 (overlapping) and a kernel
    # at 60-70; the host steps over 0-50, submits over 50-55, and the
    # program's stack span is inside the step from 40 to 50
    ex = {"window": [0, 100],
          "device": [[["a", 10, 20, False], ["b", 20, 20, False],
                      ["k", 60, 10, True], ["late", 95, 10, False]]]}
    t = devtrace.reduce(ex, spans=[("bench.step", 0, 50),
                                   ("bench.submit", 50, 5), ("stack", 40, 10)])
    assert t.window_s == pytest.approx(100e-9)
    # busy: 10-40, 60-70 and 95-100 (clipped to the window)
    assert t.busy_s == pytest.approx(45e-9)
    assert t.pallas_s == pytest.approx(10e-9)
    assert (t.n_ops, t.n_pallas) == (4, 1)
    idle = dict(t.idle_by_host)
    # gaps 0-10 (step), 40-60 (mid 50: submit), 70-95 (nothing open)
    assert idle == pytest.approx({"bench.step": 10e-9,
                                  "bench.submit": 20e-9,
                                  "host.other": 25e-9})
    assert sum(idle.values()) + t.busy_s == pytest.approx(t.window_s)
    assert dict(t.ops)["b"] == pytest.approx(20e-9)


def test_innermost_host_span_names_a_gap():
    ex = {"window": [0, 100], "device": [[["a", 90, 10, False]]]}
    t = devtrace.reduce(ex, spans=[("bench.step", 0, 90), ("batch", 5, 80),
                                   ("stack", 10, 20)])
    # the gap 0-90 has its middle, 45, inside batch but not stack
    assert dict(t.idle_by_host) == pytest.approx({"batch": 90e-9})


def test_recorded_chip_trace_excerpt():
    """Three batches of MobileNetV1 0.25/128 at bucket 32 on one chip."""
    ex = json.loads((DATA / "trace_excerpt.json").read_text())
    t = devtrace.reduce(ex)
    # 15 SC/PC/FC layers run as Mosaic kernels, once per batch
    assert t.n_pallas == 45
    lo, hi = ex["window"]
    # busy by an independent sweep over start/end boundaries
    edges = sorted([(max(s, lo), 1) for _, s, d, _ in ex["device"][0]]
                   + [(min(s + d, hi), -1) for _, s, d, _ in ex["device"][0]])
    busy, depth, last = 0.0, 0, None
    for t_ns, step in edges:
        if depth > 0:
            busy += t_ns - last
        depth += step
        last = t_ns
    assert t.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < t.pallas_s < t.busy_s < t.window_s
    assert sum(v for _, v in t.idle_by_host) == pytest.approx(
        t.window_s - t.busy_s)
    assert [k for k, _ in t.idle_by_host] == ["host.other"]
    names = [k for k, _ in t.ops]
    assert any(k.startswith("vdpe_pack_conv_zs_q8") for k in names)


@pytest.mark.parametrize("hlo, label, pallas", [
    ('%vdpe_conv_q8.3 = f32[32,784,128]{2,1,0:T(8,128)} custom-call(f32[32]'
     '{0} %a), custom_call_target="tpu_custom_call", operand_layout_'
     'constraints={f32[32]{0}}', "vdpe_conv_q8.3 custom-call f32[32,784,128]",
     True),
    ('%custom-call.170 = s8[1024,1024]{1,0:T(8,128)(4,1)S(1)} custom-call('
     's8[256,1024]{1,0} %s), custom_call_target="ConcatBitcast"',
     "custom-call.170 custom-call s8[1024,1024]", False),
    ('%fusion.80 = f32[12544,32,3]{1,2,0:T(4,128)S(1)} fusion(f32[32,225,'
     '225,3]{0,3,2,1:T(4,128)} %pad.2), kind=kCustom',
     "fusion.80 fusion f32[12544,32,3]", False),
])
def test_op_labels_and_kernel_detection(hlo, label, pallas):
    assert devtrace.op_label(hlo) == label
    assert devtrace._is_pallas(hlo) is pallas
