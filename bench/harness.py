"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name: its workload file under
``workloads/`` names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``) and may override the mix's
numbers; the configuration may name its own model and reference modules
(``config_modules``); the metrics it reports are the entries of
``BENCHMARK.json`` that apply to it, each computed by
``metrics/<name>.py``.

The timed path is the user's entry: ``CNNServer.submit`` for every
request, then ``CNNServer.step``, which runs the whole-model jitted
pipeline (``engine.forward_jit``: the Pallas kernels and XLA ops).
Nothing in the window calls the engine directly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import importlib.util
import json
import math
import re
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

import generator  # noqa: E402
import opcount  # noqa: E402

#: seconds the server may take past the window's close to answer the
#: requests still in flight; one that has no answer by then has failed
DRAIN_LIMIT_S = 60.0
#: a step longer than this is listed, with its time in the window, on
#: standard error: the stalls that set the tails (PERF.md)
SLOW_STEP_S = 0.05


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where the benchmark's files are (tests point it at their own)."""
    benchmark: Path = ROOT / "BENCHMARK.json"
    configs: Path = BENCH_DIR / "configs"
    traffic: Path = BENCH_DIR / "traffic"
    workloads: Path = BENCH_DIR / "workloads"
    metrics: Path = BENCH_DIR / "metrics"
    peaks: Path = BENCH_DIR / "peaks.json"
    #: where a configuration's ``model`` and ``reference`` paths resolve
    modules: Path = BENCH_DIR


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    return json.loads(path.read_text())


def load_cell(layout: Layout, name: str) -> dict:
    """The cell's workload file, its mix's numbers merged under its own
    ``params``."""
    cell = _read_json(layout.workloads / f"{name}.json")
    mix = _read_json(layout.traffic / f"{cell['traffic']}.json")
    cell["params"] = {**mix, **cell.get("params", {})}
    return cell


def cell_metrics(bench: dict, cell: str, traced: bool) -> List[dict]:
    """``BENCHMARK.json``'s metrics for this cell: its end-to-end metrics
    in an untraced run, its per-layer metrics in a traced one.  A metric
    without ``workloads`` applies wherever its end-to-end metric does."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def _load_module(path: Path) -> ModuleType:
    """The module in the file ``path``, run once per process."""
    name = "bench_" + re.sub(r"\W", "_", str(path.resolve()))
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod


def load_reducer(layout: Layout, name: str) -> Callable:
    """``metrics/<name>.py``'s ``value(run) -> float | None``."""
    path = layout.metrics / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader {path}")
    return _load_module(path).value


#: what a configuration's model module and reference module define
MODEL_API = ("make_weights", "program", "geoms", "ops_per_image")
REFERENCE_API = ("forward",)
#: the modules of a configuration that names none: the layer chain
DEFAULT_MODULES = {"model": BENCH_DIR / "model.py",
                   "reference": BENCH_DIR / "reference.py"}


@dataclasses.dataclass(frozen=True)
class ConfigModules:
    """A configuration's model module and reference module."""
    model: ModuleType
    reference: ModuleType


def module_path(layout: Layout, cfg: dict, key: str) -> Path:
    """The file of ``cfg``'s ``key`` module (``model`` or ``reference``):
    ``cfg[key]`` under ``layout.modules``, else the default."""
    return layout.modules / cfg[key] if key in cfg else DEFAULT_MODULES[key]


def config_modules(layout: Layout, cfg: dict) -> ConfigModules:
    """The model and reference modules of a configuration.

    A configuration file may name them under ``"model"`` and
    ``"reference"``, as paths relative to ``layout.modules`` (the bench
    directory); where it names neither, they are ``model.py`` (the layer
    chain of its ``layers`` table, with ``opcount``'s arithmetic) and
    ``reference.py``.  The model module defines

    - ``make_weights(cfg, seed)``: the weight pytree, made on the device
      from the seed;
    - ``program(cfg, weights)``: the factory that
      ``serve.PlanRegistry.register`` takes;
    - ``geoms(cfg)``: the ``opcount.Geom`` of each layer whose kernel
      ``pallas_roofline`` reads;
    - ``ops_per_image(cfg)``: the operations one image needs, which
      ``mfu`` divides by;

    and the reference module ``forward(cfg, weights, images, bits)``:
    the logits of ``images`` (N, H, W, C), computed at ``bits``, with
    nothing imported from the program.  The check that compares the
    served logits with them is ``reference.logit_gap`` for every
    configuration.  A module that is missing, or lacks one of these
    names, fails here, naming the configuration and what is missing.
    """
    found = {}
    for key, api in (("model", MODEL_API), ("reference", REFERENCE_API)):
        path = module_path(layout, cfg, key)
        if not path.is_file():
            raise FileNotFoundError(
                f"configuration {cfg['name']!r}: its {key} module {path} "
                f"does not exist")
        mod = _load_module(path)
        missing = [n for n in api if not callable(getattr(mod, n, None))]
        if missing:
            raise AttributeError(
                f"configuration {cfg['name']!r}: its {key} module {path} "
                f"lacks {', '.join(missing)}")
        found[key] = mod
    return ConfigModules(**found)


def device_peaks(layout: Layout, device_kind: str) -> dict:
    table = _read_json(layout.peaks)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{layout.peaks}; known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass
class RunRecord:
    """What one run measured; the metric readers take it.  Times are
    seconds on the host's ``perf_counter``."""
    setup_s: float
    t_start: float                   # the window opens
    t_end: float                     # the window closes
    due: np.ndarray                  # per request: when it was due
    started: np.ndarray              # start of the step that served it
    done: np.ndarray                 # its answer on the host (nan: none)
    counted: np.ndarray              # due inside the window
    batches: List[tuple]             # (size, step start, step end)
    geoms: List[opcount.Geom]
    ops_per_image: int
    peaks: dict
    spans: tuple = ()                # the program's Tracer records
    trace: object = None             # devtrace.DeviceTrace

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    def latencies_s(self) -> np.ndarray:
        """Due time to answer, of every request due in the window; one
        with no answer counts as late as the end of the drain."""
        done = np.where(np.isnan(self.done), self.drain_end, self.done)
        return (done - self.due)[self.counted]

    @property
    def drain_end(self) -> float:
        return float(np.nanmax(self.done)) if len(self.done) else self.t_end

    def host_ms_per_batch(self) -> Optional[float]:
        """Mean of the program's ``batch`` spans less their ``exec``
        child: plan fetch, stacking, epilogue and telemetry."""
        batch = {s.span_id: s.dur for s in self.spans
                 if s.ph == "X" and s.name == "batch"}
        exec_ = {s.parent_id: s.dur for s in self.spans if s.ph == "X"
                 and s.name == "exec" and s.parent_id in batch}
        if not exec_:
            return None
        return 1e3 * sum(batch[k] - v for k, v in exec_.items()) / len(exec_)

    def images_in_window(self) -> int:
        d = self.done
        return int(np.sum((d >= self.t_start) & (d <= self.t_end)))


class Requests:
    """Per-request bookkeeping of a window, in arrays by request index."""

    def __init__(self, seed: int, keep: int):
        self.due: List[float] = []
        self.started: List[float] = []
        self.done: List[float] = []
        self.by_rid: Dict[int, int] = {}
        self.seed = seed
        self.keep = keep
        self.sample: list = []          # heap of (-key, index, output)
        self.batches: List[tuple] = []

    def submitted(self, rid: int, due: float) -> int:
        i = len(self.due)
        self.by_rid[rid] = i
        self.due.append(due)
        self.started.append(math.nan)
        self.done.append(math.nan)
        return i

    def answered(self, results: dict, t0: float, t1: float) -> int:
        n = 0
        while results:
            rid, out = results.popitem()
            i = self.by_rid.pop(rid)
            self.started[i], self.done[i] = t0, t1
            if self.keep:
                self._offer(i, out)
            n += 1
        if n:
            self.batches.append((n, t0, t1))
        return n

    def _offer(self, i: int, out) -> None:
        """Keep the answer if its seeded key is among the ``keep``
        smallest seen: a uniform sample, drawn from the seed."""
        key = generator.sample_key(self.seed, i)
        if len(self.sample) < self.keep:
            heapq.heappush(self.sample, (-key, i, np.array(out)))
        elif key < -self.sample[0][0]:
            heapq.heapreplace(self.sample, (-key, i, np.array(out)))


class HostSpans:
    """The harness's own host spans of a traced run, ``(name, start,
    end)`` on ``perf_counter``: what the host was doing in each idle gap
    of the device."""

    def __init__(self):
        self.spans: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))


def annotate(traced: bool):
    """A ``HostSpans`` recorder in a traced run, else a no-op."""
    return HostSpans() if traced else (lambda name: contextlib.nullcontext())


def _closed_loop(server, model: str, images, p: dict, seconds: float,
                 req: Requests, ann) -> tuple:
    """``outstanding`` clients; the window closes with the last step that
    started before ``seconds`` had passed."""
    clock = time.perf_counter
    t_start = clock()
    deadline = t_start + seconds
    with ann("bench.submit"):
        for _ in range(p["outstanding"]):
            i = len(req.due)
            req.submitted(server.submit(model, images[i], now=t_start),
                          t_start)
    t_end = t_start
    while True:
        t0 = clock()
        if t0 >= deadline:
            break
        with ann("bench.step"):
            server.step(now=t0)
        t1 = t_end = clock()
        n = req.answered(server.results, t0, t1)
        with ann("bench.submit"):
            for _ in range(n):
                i = len(req.due)
                req.submitted(server.submit(model, images[i], now=t1), t1)
    return t_start, t_end, len(req.due)


def _drain(server, req: Requests, t_end: float) -> None:
    """Answer the requests a closed loop still has out, after its window."""
    clock = time.perf_counter
    while server.pending() and clock() < t_end + DRAIN_LIMIT_S:
        t0 = clock()
        server.step(now=t0, force=True)
        req.answered(server.results, t0, clock())


def open_loop(server, model: str, images, p: dict, seconds: float,
               seed: int, req: Requests, ann) -> tuple:
    """Arrivals on the seed's schedule; every request due in the window
    is answered (or fails) before the run ends."""
    clock = time.perf_counter
    offsets = generator.arrival_offsets(p["rate_per_s"], seconds, seed)
    n = len(offsets)
    t_start = clock()
    due = t_start + offsets
    max_wait = p["max_wait_s"]
    i = served = 0
    while True:
        now = clock()
        if i < n and due[i] <= now:
            with ann("bench.submit"):
                while i < n and due[i] <= now:
                    req.submitted(server.submit(model, images[i],
                                                now=float(due[i])),
                                  float(due[i]))
                    i += 1
        if served < i:
            with ann("bench.step"):
                server.step(now=now)
            got = req.answered(server.results, now, clock())
            if got:
                served += got
                continue
        if i >= n and served >= i:
            break
        if now > t_start + seconds + DRAIN_LIMIT_S:
            break
        wake = due[i] if i < n else math.inf
        if served < i:
            wake = min(wake, due[served] + max_wait)
        if wake > now:
            with ann("bench.wait"):
                time.sleep(min(wake - now, 0.05))
    return t_start, t_start + seconds, n


class _GcPauses:
    """Python's garbage collections during the window, and their pauses."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def summary(self) -> str:
        if not self.pauses:
            return "none"
        gen, worst = max(self.pauses, key=lambda p: p[1])
        return (f"{len(self.pauses)}, longest {worst * 1e3:.2f} ms "
                f"(generation {gen})")


class _CompileCounter:
    """Counts JAX traces and backend compiles (``jax.monitoring``)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


@dataclasses.dataclass
class Served:
    """A cell's server, warmed and ready for its window."""
    cell: dict
    cfg: dict
    modules: ConfigModules
    weights: object
    registry: object
    server: object
    tracer: object
    images: generator.Images
    peaks: dict
    compiles: "_CompileCounter"

    @property
    def model(self) -> str:
        return self.cfg["name"]


def set_up(cell_name: str, seed: int, traced: bool, layout: Layout,
           point_bits: Optional[int] = None) -> Served:
    """Weights from the seed and the program, both from the configuration's
    model module, the plan, the server, and a warm-up of the
    batch sizes the cell's traffic uses (twice: the first compiles or
    loads from the cache, the second finds everything warm).

    ``point_bits`` builds the plan at another quantization width than the
    configuration states: the control of the output check, which no
    timed run uses.
    """
    import jax

    from repro import compile_cache, engine, serve
    from repro.obs.tracer import Tracer

    compile_cache.enable()
    # cache every program, however quick its compile, so a run's set-up
    # finds all of them after the first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(layout, cell_name)
    p = cell["params"]
    cfg = opcount.load_config(cell["config"], layout.configs)
    mods = config_modules(layout, cfg)
    peaks = device_peaks(layout, jax.devices()[0].device_kind)
    counter = _CompileCounter()
    bits = cfg["quantization_bits"] if point_bits is None else point_bits
    weights = jax.block_until_ready(mods.model.make_weights(cfg, seed))
    shape = tuple(cfg["input_shape"])
    registry = serve.PlanRegistry(
        capacity=1, point=engine.EnginePoint(bits=bits))
    registry.register(cfg["name"], mods.model.program(cfg, weights), shape)
    tracer = Tracer(capacity=1 << 21) if traced else None
    server = serve.CNNServer(registry, max_batch=p["max_batch"],
                             max_wait_s=p["max_wait_s"], tracer=tracer,
                             time_fn=time.perf_counter)
    warm = p["warm_batch_sizes"]
    warm_images = generator.Images(shape, seed + 1)
    for size in warm + warm:
        for j in range(size):
            server.submit(cfg["name"], warm_images[j])
        server.step(force=True)
    server.reset()
    if tracer is not None:
        tracer.clear()
    # what set-up made (JAX's and the program's objects) lives for the whole
    # run: frozen, a full collection in the window does not walk it again
    gc.collect()
    gc.freeze()
    return Served(cell, cfg, mods, weights, registry, server, tracer,
                  generator.Images(shape, seed), peaks, counter)


def run(cell_name: str, seed: int, seconds: float, traced: bool,
        t_process_start: float, layout: Layout = Layout(),
        point_bits: Optional[int] = None, log=sys.stderr) -> dict:
    """One run; returns the result line's object (``set_up`` says what
    ``point_bits`` is for)."""
    import jax

    from repro import engine

    import reference

    say = lambda *a: print(*a, file=log, flush=True)
    bench = _read_json(layout.benchmark)
    metrics = cell_metrics(bench, cell_name, traced)
    if not metrics:
        raise ValueError(f"cell {cell_name!r} has no metrics in "
                         f"{layout.benchmark}")
    readers = {m["name"]: load_reducer(layout, m["name"]) for m in metrics}
    sv = set_up(cell_name, seed, traced, layout, point_bits)
    cell, cfg, p = sv.cell, sv.cfg, sv.cell["params"]
    server, registry, tracer, images = (sv.server, sv.registry, sv.tracer,
                                        sv.images)
    name, counter, dev = sv.model, sv.compiles, jax.devices()[0]
    weights, mods = sv.weights, sv.modules
    setup_s = time.perf_counter() - t_process_start
    say(f"set-up: {setup_s:.3f} s (warmed batch sizes "
        f"{p['warm_batch_sizes']})")

    # -- the measured window ------------------------------------------
    ann = annotate(traced)
    keep = cell["check"]["requests"]
    req = Requests(seed, keep)
    stalls0, compiles0 = server.pipeline_compiles, counter.n
    trace_dir = None
    if traced:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # the device's operations only: the host and Python tracers
        # slow every host call, and the window is found by the wall clock
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc_pauses = _GcPauses()
    gc.callbacks.append(gc_pauses)
    wall0, pc0 = time.time_ns(), time.perf_counter()
    if p["loop"] == "closed":
        t_start, t_end, attempted = _closed_loop(
            server, name, images, p, seconds, req, ann)
    else:
        t_start, t_end, attempted = open_loop(
            server, name, images, p, seconds, seed, req, ann)
    to_wall = lambda pc: wall0 + int((pc - pc0) * 1e9)
    trace_window = (to_wall(t_start), to_wall(time.perf_counter()))
    gc.callbacks.remove(gc_pauses)
    # before the trace is written, which can take longer than the drain's
    # limit; the trace's window has closed already
    _drain(server, req, t_end)
    if traced:
        jax.profiler.stop_trace()
    stalls = server.pipeline_compiles - stalls0
    compiles = counter.n - compiles0
    failures = len(server.failures)
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    spans = tracer.events() if tracer is not None else ()
    say(f"window: {t_end - t_start:.4f} s, requests {len(req.due)}, "
        f"batches {len(req.batches)}, compile stalls {stalls}, "
        f"traces+compiles {compiles}, failures {failures}, "
        f"longest step {1e3 * max(b[2] - b[1] for b in req.batches):.2f} "
        f"ms, garbage collections {gc_pauses.summary()}")
    slow = [(b[1] - t_start, b[2] - b[1]) for b in req.batches
            if b[2] - b[1] > SLOW_STEP_S]
    say(f"steps over {SLOW_STEP_S * 1e3:.0f} ms: {len(slow)}"
        + "".join(f"; at {a:.2f} s, {d * 1e3:.1f} ms" for a, d in slow[:20]))

    done = np.asarray(req.done)
    rec = RunRecord(
        setup_s=setup_s, t_start=t_start,
        t_end=t_end, due=np.asarray(req.due),
        started=np.asarray(req.started), done=done,
        counted=np.arange(len(done)) < attempted, batches=req.batches,
        geoms=mods.model.geoms(cfg),
        ops_per_image=mods.model.ops_per_image(cfg),
        peaks=sv.peaks, spans=spans)
    unanswered = int(np.sum(np.isnan(done[:attempted])))

    # -- free the program's state, then the check against the reference -
    plan = registry.get(name).plan
    engine.pipeline_evict(plan)
    del server, registry, plan, sv
    gc.collect()
    sample = sorted((i, out) for _, i, out in req.sample)
    t0 = time.perf_counter()
    gap = math.inf
    if sample:
        want = mods.reference.forward(cfg, weights,
                                      images.batch([i for i, _ in sample]),
                                      cfg["quantization_bits"])
        gap = reference.logit_gap(np.stack([o for _, o in sample]), want)
    limit = cell["check"]["logit_gap_limit"]
    say(f"reference: {len(sample)} requests in "
        f"{time.perf_counter() - t0:.3f} s")
    correct = bool(gap <= limit and len(sample) == keep)
    checks = {   # JSON has no infinity: a gap with no number reads null
        "logit_gap": {"value": gap if math.isfinite(gap) else None,
                      "limit": limit},
        "compared": {"value": len(sample), "limit": keep},
    }

    if traced:
        host = [(n, to_wall(a), to_wall(b)) for n, a, b in ann.spans]
        host += [(s.name, to_wall(s.t0), to_wall(s.t0 + s.dur))
                 for s in spans if s.ph == "X"]
        rec.trace = _reduce_trace(trace_dir, trace_window, host)

    values = {}
    for m in metrics:
        v = readers[m["name"]](rec)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    line = {"correct": correct, "attempted": int(attempted),
            "failed": int(unanswered + failures), "metrics": values,
            "device": device}
    if traced:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        line["breakdown"] = {
            "device_ops": [[k, v] for k, v in rec.trace.ops[:10]],
            "idle_gaps": [[k, v] for k, v in rec.trace.idle_by_host[:10]]}
    line["window"] = {"compile_stalls": stalls, "jax_compiles": compiles}
    line["checks"] = checks
    return line


def _reduce_trace(trace_dir: str, window_wall_ns: tuple, host_wall_ns):
    """The device trace of the window; ``host_wall_ns`` are host spans
    ``(name, start, end)`` in wall-clock ns, laid on the trace's clock."""
    import shutil

    import devtrace
    try:
        ex = devtrace.extract(devtrace.find_xplane(trace_dir),
                              window_wall_ns)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    shift = ex["window"][0] - window_wall_ns[0]
    return devtrace.reduce(ex, [(n, a + shift, b - a)
                                for n, a, b in host_wall_ns])
