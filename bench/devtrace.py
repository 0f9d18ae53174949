"""Device trace of a traced run: busy time, kernel time and idle gaps.

``extract`` reads the profiler's ``.xplane.pb`` with
``jax.profiler.ProfileData`` and keeps the device's operations inside the
measured window, which the harness gives on the wall clock (the trace's
timestamps count from the profile's start, which the trace records on the
same clock); the result is plain JSON (a small one is committed as a test
excerpt).  ``reduce`` turns it into the numbers the metrics read:

* busy: the union of the device's operation intervals;
* Pallas time: the union of the intervals of its Mosaic kernels
  (``tpu_custom_call``s);
* idle gaps: the stretches of the window with no operation running, each
  named by the innermost host span open at its middle: the harness's own
  (``bench.step``, ``bench.submit``, ``bench.wait``) or the program's
  ``CNNServer`` spans (``stack``, ``exec``, ``epilogue``, ...).

No host annotation is needed, so the profiler's host tracer stays off.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

#: the device line that holds one event per operation executed
OPS_LINE = "XLA Ops"


def _is_pallas(hlo: str) -> bool:
    """A Mosaic kernel: its HLO instruction calls ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in hlo


def op_label(hlo: str) -> str:
    """``%fusion.80 = f32[12544,32,3]{...} fusion(...), kind=kCustom``
    -> ``fusion.80 fusion f32[12544,32,3]``: the instruction, its opcode
    and its result shape (a Pallas kernel's instruction carries the
    kernel's name)."""
    lhs, _, rhs = hlo.partition(" = ")
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])?.*?\}?\)?\s*([a-z][\w-]*)\(", rhs)
    shape, opcode = (m.group(1) or "", m.group(2)) if m else ("", "")
    return " ".join(x for x in (lhs.lstrip("%"), opcode, shape) if x)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(xplane_path: str, window_wall_ns: Tuple[int, int]) -> dict:
    """The device operations inside ``window_wall_ns`` (wall-clock ns),
    as JSON.  Times are nanoseconds from the profile's start; ``device``
    holds one list of ``[name, start, duration, is_pallas]`` per chip that
    ran operations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    t0 = None
    devices: Dict[str, list] = {}
    # a window runs the same few hundred instructions thousands of times:
    # each distinct name is parsed once
    labels: Dict[str, tuple] = {}

    def label(name: str) -> tuple:
        if name not in labels:
            labels[name] = (op_label(name), _is_pallas(name))
        return labels[name]

    for plane in pd.planes:
        if plane.name == "Task Environment":
            t0 = dict(plane.stats)["profile_start_time"]
        elif plane.name.startswith("/device:TPU:"):
            ops = [line for line in plane.lines if line.name == OPS_LINE]
            if ops:
                devices[plane.name] = [
                    [lab, ev.start_ns, ev.duration_ns, pallas]
                    for line in ops for ev in line.events
                    for lab, pallas in (label(ev.name),)]
    if t0 is None:
        raise ValueError(f"no profile start time in {xplane_path}")
    names = sorted(devices, key=lambda n: int(n.rsplit(":", 1)[1]))
    if not names:
        raise ValueError(f"no TPU device ran an operation in {xplane_path}")
    lo, hi = (w - t0 for w in window_wall_ns)
    keep = lambda evs: [e for e in evs if e[1] + e[2] > lo and e[1] < hi]
    return {"window": [lo, hi],
            "device": [keep(devices[n]) for n in names]}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class DeviceTrace:
    """Seconds, averaged over the chips used."""
    window_s: float
    busy_s: float
    pallas_s: float
    #: (operation name, seconds), most time first
    ops: List[Tuple[str, float]]
    #: (what the host was doing, idle seconds), most idle first
    idle_by_host: List[Tuple[str, float]]
    n_ops: int
    n_pallas: int


def reduce(excerpt: dict, spans: Sequence[Tuple[str, float, float]] = (),
           ) -> DeviceTrace:
    """Reduce an ``extract`` result.  ``spans`` are the host's spans
    (name, start ns, duration ns) on the trace's clock."""
    lo, hi = excerpt["window"]
    clip = lambda a, b: (max(a, lo), min(b, hi))
    host = sorted((s, s + d, n) for n, s, d in spans)
    starts = [h[0] for h in host]
    chips = excerpt["device"]
    busy = pallas = 0.0
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    n_ops = n_pallas = 0
    for events in chips:
        iv, kv = [], []
        for name, s, d, is_pallas in events:
            a, b = clip(s, s + d)
            if b <= a:
                continue
            iv.append((a, b))
            ops[name] = ops.get(name, 0.0) + (b - a)
            n_ops += 1
            if is_pallas:
                kv.append((a, b))
                n_pallas += 1
        merged = _union(iv)
        busy += sum(b - a for a, b in merged)
        pallas += sum(b - a for a, b in _union(kv))
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label = _host_label(host, starts, (a + b) / 2)
                idle[label] = idle.get(label, 0.0) + (b - a)
    n = max(len(chips), 1)
    ns = 1e-9
    return DeviceTrace(
        window_s=(hi - lo) * ns, busy_s=busy * ns / n,
        pallas_s=pallas * ns / n,
        ops=sorted(((k, v * ns / n) for k, v in ops.items()),
                   key=lambda kv: -kv[1]),
        idle_by_host=sorted(((k, v * ns / n) for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
        n_ops=n_ops, n_pallas=n_pallas)


def _host_label(host: Sequence[Tuple[float, float, str]],
                starts: Sequence[float], t: float,
                depth: int = 32) -> str:
    """The innermost (shortest) host span open at ``t``.  ``host`` is
    sorted by start; spans nest a few deep and otherwise follow one
    another, so the spans open at ``t`` are among the last few that
    started before it."""
    best, best_len = "host.other", float("inf")
    i = bisect.bisect_right(starts, t)
    for a, b, name in host[max(0, i - depth):i]:
        if t < b and b - a < best_len:
            best, best_len = name, b - a
    return best
