"""The benchmark's files: configurations, cells, mixes and metric readers
are found by name, and agree with BENCHMARK.json."""
import json
from pathlib import Path

import pytest

import generator
import harness
import opcount

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LAYOUT = harness.Layout()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}

#: MobileNetV1's Table 1 (Howard et al. 2017): base filters and strides
#: of the 13 depthwise-separable blocks after conv1 (32 filters, stride 2)
TABLE_1 = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2)] + \
    [(512, 1)] * 5 + [(1024, 2), (1024, 1)]


@pytest.mark.parametrize("name, alpha, res, mmacs, mparams", [
    ("mobilenet_v1_1.0_224", 1.0, 224, 568.8, 4.22),
    ("mobilenet_v1_0.25_128", 0.25, 128, 13.6, 0.47),
])
def test_configuration_follows_the_paper(name, alpha, res, mmacs, mparams):
    cfg = opcount.load_config(name)
    depth = lambda c: max(int(c * alpha), 8)
    want = [("conv", 3, 2, depth(32))]
    for c, s in TABLE_1:
        want += [("depthwise", 3, s, None), ("conv", 1, 1, depth(c))]
    got = [(l["kind"], l["k"], l["stride"], l.get("filters"))
           for l in cfg["layers"][:-2]]
    assert got == want
    assert [l["kind"] for l in cfg["layers"][-2:]] == ["global_avg_pool",
                                                       "dense"]
    assert cfg["input_shape"] == [res, res, 3]
    assert round(opcount.macs_per_image(cfg) / 1e6, 1) == mmacs
    assert round(opcount.param_count(cfg) / 1e6, 2) == mparams
    g = opcount.walk(cfg)
    assert (g[-2].h, g[-2].d, g[-1].f) == (res // 32, depth(1024), 1000)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_name_what_exists(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    c = harness.load_cell(LAYOUT, cell)
    assert (c["config"], c["traffic"]) == (w["config"], w["traffic"])
    cfg = next(x for x in BENCH["configs"] if x["name"] == w["config"])
    cfg_file = json.loads((ROOT / cfg["file"]).read_text())
    assert cfg_file["name"] == cfg["name"]
    harness.config_modules(LAYOUT, cfg_file)     # its modules load whole
    p = c["params"]
    assert p["loop"] in ("closed", "open")
    assert p["max_batch"] in p["warm_batch_sizes"]
    assert c["check"]["requests"] > 0 and c["check"]["logit_gap_limit"] >= 0
    for traced in (False, True):
        ms = harness.cell_metrics(BENCH, cell, traced)
        assert ms, (cell, traced)
        for m in ms:
            assert callable(harness.load_reducer(LAYOUT, m["name"]))
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in harness.cell_metrics(BENCH, cell, True):
        assert m["moves"] in e2e


def test_every_metric_and_config_is_used():
    assert all((LAYOUT.metrics / f"{n}.py").is_file() for n in METRICS)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["per_layer"]:
        assert METRICS[m["moves"]] in BENCH["end_to_end"]


def test_arrivals_fill_the_window_with_a_fixed_count():
    a = generator.arrival_offsets(500.0, 4.0, 2 ** 40 + 9)
    b = generator.arrival_offsets(500.0, 4.0, 7)
    assert len(a) == len(b) == 2000
    assert (a == generator.arrival_offsets(500.0, 4.0, 2 ** 40 + 9)).all()
    for t in (a, b):
        assert (t[1:] >= t[:-1]).all() and 0 <= t[0] and t[-1] < 4.0
    assert not (a == b).all()


def test_images_are_distinct_views_of_one_seeded_buffer():
    im = generator.Images((8, 8, 3), 2 ** 33 + 1)
    assert im[5].base is not None           # a view: no copy per request
    assert not (im[5] == im[6]).all()
    assert (im[5] == generator.Images((8, 8, 3), 2 ** 33 + 1)[5]).all()
    assert im[5].min() >= -1 and im[5].max() <= 1
