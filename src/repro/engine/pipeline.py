"""Whole-model jitted pipeline: one XLA dispatch per served batch.

``engine.forward`` walks a plan's layers in a Python loop — one kernel
dispatch plus quantize round-trip per layer, ~L host round-trips per
served batch.  The hardware analogue pays none of that: once DKVs are
imprinted, DIV streams flow through the layer sequence with no dead time.
This module closes the gap on the serving hot path:

    forward_jit(plan, xb)  ->  one jitted callable per (plan, batch bucket)

The callable traces the *entire* layer chain — the quantized-domain
implicit-GEMM conv kernels (input-DAC absmax/quantize fused into the
kernel prologues), the depthwise VPU path, the double-buffered q8 FC
GEMMs, fused dequant epilogues — into a single XLA program, so a served
batch is one dispatch instead of ~L.
Inter-layer activations are XLA temporaries (never returned to the host).
The input batch is not donated: no output has its shape, so XLA could
never reuse its buffer.

Batch sizes are bucketed to the next power of two: the dynamic batcher
produces ragged final batches, and compiling per exact size would turn
every straggler into a compile stall.  Padding images are all-zero; since
quantization is per image and GEMM rows/grid instances are per image, the
real images' outputs are bit-identical to the unbucketed call (asserted in
tests/test_implicit_conv.py).

The pipeline cache is memoized on the plan object (like plan.get_plan's
pack cache, but keyed by identity — a plan's arrays are the identity of
its imprint), and ``_STATS["compiles"]`` counts actual retraces: a
(plan, bucket) pair compiles exactly once, every later batch in that
bucket reuses the executable.  The serving registry evicts a plan's
pipelines with its imprint (``evict``).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.common import resolve_interpret
from . import executor
from .plan import ModelPlan

#: Resident pipeline bound: beyond this many plans the least-recently-used
#: entry (its strong plan reference AND its compiled executables) is
#: dropped, so code that compiles plans outside a PlanRegistry — tests,
#: benchmarks, notebooks — cannot pin every imprint it ever served for
#: process lifetime.  Generous next to any registry capacity.
CACHE_CAPACITY = 16

# id(plan) -> (plan, interpret -> jitted fn), LRU-ordered; the strong plan
# reference pins the id for the entry's lifetime (no reuse-after-free key
# aliasing).
_PIPELINES: "OrderedDict[int, Tuple[ModelPlan, Dict[bool, Callable]]]" = (
    OrderedDict())
_STATS = {"hits": 0, "misses": 0, "compiles": 0, "evictions": 0,
          "dispatches": 0}
# (plan name, batch bucket) -> served-dispatch count; the obs layer reads
# this to show which compiled buckets actually carry serving traffic
_DISPATCH_COUNTS: Dict[Tuple[str, int], int] = {}
# the sharded dispatcher serves shards from a thread pool; cache lookups,
# insertions and LRU reordering must not interleave (jit itself is
# thread-safe — only this bookkeeping needs the lock)
_LOCK = threading.RLock()


def batch_bucket(b: int) -> int:
    """Smallest power of two >= b (the compile-shape bucket)."""
    assert b >= 1, b
    bucket = 1
    while bucket < b:
        bucket *= 2
    return bucket


def _layer_params(plan: ModelPlan) -> tuple:
    """The plan's device arrays, passed as jit arguments (not baked into
    the executable as constants — the imprint stays a buffer, the traced
    program stays small).  Per-layer operating points stay *static*: each
    LayerPlan keeps its own ``point``, so a pipeline executable is keyed
    on the plan's whole per-layer point sequence (a planner-compiled plan
    and a fixed-point plan of the same model trace separately)."""
    return tuple((lp.rhs, lp.w_scale, lp.bias) for lp in plan.layers)


def layer_scope(i: int, lp) -> str:
    """The named scope of layer ``i`` in the pipeline: ``L03_pw1_<route>``.
    Inside it the stages are scoped too (``pad``, ``dac_scale``,
    ``phase_planes``, ``depthwise``, ``out``), so each device op of a
    profile carries its layer and stage in its ``op_name``."""
    return f"L{i:02d}_{lp.name}_{executor.layer_route(lp)}"


def _build(plan: ModelPlan, interpret: bool) -> Callable:
    def run(params, xb):
        _STATS["compiles"] += 1   # trace-time side effect: counts retraces
        x = xb
        for i, (lp, (rhs, w_scale, bias)) in enumerate(zip(plan.layers,
                                                           params)):
            lp = dataclasses.replace(lp, rhs=rhs, w_scale=w_scale,
                                     bias=bias)
            # names the layer's device ops in a profile (HLO metadata
            # only: the compiled arithmetic is unchanged)
            with jax.named_scope(layer_scope(i, lp)):
                x = executor.forward_layer(plan, lp, x, interpret=interpret)
        return x

    return jax.jit(run)


def get_pipeline(plan: ModelPlan, interpret: bool | None = None) -> Callable:
    """The plan's jitted whole-model callable (built once per plan).

    jit's own shape cache provides the per-bucket memo: the first batch in
    a bucket traces+compiles (``pipeline_cache_info()["compiles"]`` ticks),
    every later one reuses the executable.
    """
    interpret = resolve_interpret(interpret)
    with _LOCK:
        entry = _PIPELINES.get(id(plan))
        if entry is not None and entry[0] is plan:
            _PIPELINES.move_to_end(id(plan))
            fns = entry[1]
            if interpret in fns:
                _STATS["hits"] += 1
                return fns[interpret]
        else:
            fns = {}
            _PIPELINES[id(plan)] = (plan, fns)
            while len(_PIPELINES) > CACHE_CAPACITY:
                _PIPELINES.popitem(last=False)
                _STATS["evictions"] += 1
        _STATS["misses"] += 1
        fns[interpret] = _build(plan, interpret)
        return fns[interpret]


def forward_jit(plan: ModelPlan, x: jax.Array,
                interpret: bool | None = None) -> jax.Array:
    """Serve a batch through the whole-model jitted pipeline.

    x: NHWC batch (B, H, W, D), or (B, S) rows for FC-first plans.  The
    batch is zero-padded to its power-of-two bucket and the pad rows are
    sliced away after the single dispatch; outputs for the real images are
    bit-identical to ``forward`` (and therefore to the im2col oracle).
    """
    if x.ndim not in (2, 4):
        raise ValueError(
            f"forward_jit serves batches: expected (B, H, W, D) or (B, S), "
            f"got shape {tuple(x.shape)}")
    fn = get_pipeline(plan, interpret)
    b = x.shape[0]
    bucket = batch_bucket(b)
    with _LOCK:
        _STATS["dispatches"] += 1
        key = (plan.name, bucket)
        _DISPATCH_COUNTS[key] = _DISPATCH_COUNTS.get(key, 0) + 1
    if bucket != b:
        pad = [(0, bucket - b)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad)
    out = fn(_layer_params(plan), x)
    return out[:b]


# ---------------------------------------------------------------------------
# Guarded pipeline: the SDC corruption/detection path, whole-model jitted
# ---------------------------------------------------------------------------

def _build_guarded(plan: ModelPlan,
                   policy: executor.IntegrityPolicy) -> Callable:
    """Jit the guarded layer chain (executor.forward_layer_guarded).

    The weight-imprint goldens are computed HERE, from the pristine plan
    arrays, and baked into the traced program as Python int constants —
    the comparison point a corrupted resident imprint is caught against.
    Corruption parameters are jit *arguments* (CorruptionArgs), so one
    executable serves clean and corrupted dispatches alike.
    """
    goldens = tuple(int(executor.weight_imprint_checksum(lp.rhs))
                    for lp in plan.layers)

    def run(params, xb, cargs):
        _STATS["compiles"] += 1
        x = xb
        flags = []
        for i, (lp, (rhs, w_scale, bias)) in enumerate(zip(plan.layers,
                                                           params)):
            lp = dataclasses.replace(lp, rhs=rhs, w_scale=w_scale,
                                     bias=bias)
            check = policy.check_every > 0 and i % policy.check_every == 0
            x, fl = executor.forward_layer_guarded(
                plan, lp, x, cargs, salt=i, check=check, policy=policy,
                golden=goldens[i])
            flags.append(fl)
        return x, jnp.stack(flags)

    return jax.jit(run)


def get_guarded_pipeline(plan: ModelPlan,
                         policy: executor.IntegrityPolicy =
                         executor.DEFAULT_POLICY) -> Callable:
    """The plan's guarded jitted callable, memoized beside the plain one.

    Shares the LRU pipeline store (same eviction lifetime as the plain
    executables); the fns dict keys guarded variants by their (hashable)
    policy, so different cadences coexist.
    """
    with _LOCK:
        entry = _PIPELINES.get(id(plan))
        if entry is not None and entry[0] is plan:
            _PIPELINES.move_to_end(id(plan))
            fns = entry[1]
            key = ("guarded", policy)
            if key in fns:
                _STATS["hits"] += 1
                return fns[key]
        else:
            fns = {}
            _PIPELINES[id(plan)] = (plan, fns)
            while len(_PIPELINES) > CACHE_CAPACITY:
                _PIPELINES.popitem(last=False)
                _STATS["evictions"] += 1
            key = ("guarded", policy)
        _STATS["misses"] += 1
        fns[key] = _build_guarded(plan, policy)
        return fns[key]


def forward_jit_guarded(plan: ModelPlan, x: jax.Array,
                        cargs: Optional[executor.CorruptionArgs] = None,
                        policy: executor.IntegrityPolicy =
                        executor.DEFAULT_POLICY,
                        params: Optional[tuple] = None,
                        ) -> Tuple[jax.Array, jax.Array]:
    """Serve a batch through the guarded pipeline.

    Returns (outputs, flags): outputs as ``forward_jit`` (bit-identical to
    it when ``cargs`` is null and ``params`` are the plan's own — asserted
    in tests/test_sdc.py), flags an (L,) int32 vector of per-layer
    detector bitmasks (executor.DET_*; all zero on a clean dispatch).
    ``params`` overrides the resident weight arrays — the STUCK_MRR
    injection point (engine.corrupted_layer_params builds a corrupted
    imprint) — and defaults to the plan's pristine arrays.
    """
    if x.ndim not in (2, 4):
        raise ValueError(
            f"forward_jit_guarded serves batches: expected (B, H, W, D) or "
            f"(B, S), got shape {tuple(x.shape)}")
    if cargs is None:
        cargs = executor.null_corruption_args()
    fn = get_guarded_pipeline(plan, policy)
    b = x.shape[0]
    bucket = batch_bucket(b)
    with _LOCK:
        _STATS["dispatches"] += 1
        key = (plan.name, bucket)
        _DISPATCH_COUNTS[key] = _DISPATCH_COUNTS.get(key, 0) + 1
    if bucket != b:
        pad = [(0, bucket - b)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad)
    out, flags = fn(params if params is not None else _layer_params(plan),
                    x, cargs)
    return out[:b], flags


def corrupted_layer_params(plan: ModelPlan, seed: int,
                           stuck_rings: int) -> tuple:
    """A copy of the plan's packed weight imprint with stuck MRR elements.

    Models STUCK_MRR: ``stuck_rings`` weight elements (uniformly random
    over layers and positions under ``seed``) are pinned to full
    transmission (+qmax; an already-+qmax element flips to -qmax so the
    corruption is never a no-op on the stored value).  Deterministic:
    (plan, seed, stuck_rings) always corrupts the same elements.  Feed the
    result to ``forward_jit_guarded(..., params=...)`` — ABFT cannot see
    this fault (the GEMM faithfully computes with the wrong weights); the
    weight-imprint checksum is the detector that catches it.
    """
    rng = np.random.default_rng(seed)
    rhss = [np.array(lp.rhs) for lp in plan.layers]
    for _ in range(max(0, int(stuck_rings))):
        li = int(rng.integers(len(rhss)))
        flat = rhss[li].reshape(-1)
        idx = int(rng.integers(flat.size))
        qmax = 2 ** (plan.layers[li].point.bits - 1) - 1
        flat[idx] = -qmax if flat[idx] == qmax else qmax
    return tuple((jnp.asarray(r), lp.w_scale, lp.bias)
                 for r, lp in zip(rhss, plan.layers))


def evict(plan: ModelPlan) -> None:
    """Drop a plan's compiled pipelines (the registry's LRU eviction hook —
    without it the pipeline cache would pin evicted imprints forever)."""
    with _LOCK:
        _PIPELINES.pop(id(plan), None)


def pipeline_cache_info() -> Dict[str, int]:
    return dict(_STATS, size=len(_PIPELINES))


def pipeline_dispatch_counts() -> Dict[Tuple[str, int], int]:
    """Served dispatches per (plan name, batch bucket)."""
    with _LOCK:
        return dict(_DISPATCH_COUNTS)


def pipeline_cache_clear() -> None:
    _PIPELINES.clear()
    _DISPATCH_COUNTS.clear()
    for k in _STATS:
        _STATS[k] = 0
