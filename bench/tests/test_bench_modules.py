"""A configuration's model and reference modules (``harness.config_modules``):
the default is the layer chain, a module that is missing or short fails
at load, and no reference imports the program."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
import model
import opcount

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).parent / "data"


@pytest.mark.parametrize("name", ["mobilenet_v1_1.0_224",
                                  "mobilenet_v1_0.25_128"])
def test_a_configuration_that_names_no_modules_gets_the_chain(name):
    cfg = opcount.load_config(name)
    assert "model" not in cfg and "reference" not in cfg
    mods = harness.config_modules(harness.Layout(), cfg)
    assert Path(mods.reference.__file__) == BENCH / "reference.py"
    seed = 2 ** 40 + 5
    got, want = mods.model.make_weights(cfg, seed), model.make_weights(cfg,
                                                                       seed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.asarray(a).tobytes() == \
            np.asarray(b).tobytes()
    assert mods.model.geoms(cfg) == opcount.walk(cfg)
    assert mods.model.ops_per_image(cfg) == opcount.ops_per_image(cfg)
    defs = mods.model.program(cfg, want)()
    chain = model.layer_defs(cfg, want)
    assert [(d.name, d.kind, d.act, d.stride, d.padding) for d in defs] == \
        [(d.name, d.kind, d.act, d.stride, d.padding) for d in chain]
    for d, c in zip(defs, chain):
        assert d.weights is c.weights and d.bias is c.bias


@pytest.mark.parametrize("key, text, missing", [
    ("model", None, "does not exist"),
    ("reference", None, "does not exist"),
    ("model", "def make_weights(cfg, seed):\n    return []\n",
     "lacks program, geoms, ops_per_image"),
    ("reference", "forward = None\n", "lacks forward"),
])
def test_a_module_that_is_missing_or_short_fails_at_load(tmp_path, key, text,
                                                         missing):
    if text is not None:
        (tmp_path / "short.py").write_text(text)
    cfg = {"name": "broken", key: "short.py"}
    with pytest.raises((FileNotFoundError, AttributeError)) as err:
        harness.config_modules(harness.Layout(modules=tmp_path), cfg)
    msg = str(err.value)
    assert "'broken'" in msg and f"{key} module" in msg and missing in msg


def _configurations():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    yield from ((harness.Layout(), json.loads((ROOT / c["file"]).read_text()))
                for c in bench["configs"])
    data = harness.Layout(modules=DATA)
    yield from ((data, json.loads(p.read_text()))
                for p in sorted((DATA / "configs").glob("*.json")))


def test_no_reference_module_imports_the_program():
    paths = sorted({str(harness.module_path(layout, cfg, "reference"))
                    for layout, cfg in _configurations()})
    assert str(BENCH / "reference.py") in paths and len(paths) >= 2
    # a fresh interpreter with the bench directory on its path and the
    # program's sources off it: an import of ``repro`` fails or shows
    script = (
        "import importlib.util, json, sys\n"
        f"sys.path.insert(0, {str(BENCH)!r})\n"
        "for i, p in enumerate(json.loads(sys.argv[1])):\n"
        "    spec = importlib.util.spec_from_file_location(f'ref{i}', p)\n"
        "    spec.loader.exec_module(importlib.util.module_from_spec(spec))\n"
        "print(json.dumps(sorted(m for m in sys.modules\n"
        "                        if m.split('.')[0] == 'repro')))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script, json.dumps(paths)],
                         capture_output=True, text=True, env=env,
                         timeout=120, cwd=str(DATA))
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) == []
