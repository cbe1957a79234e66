"""The program's own tracing: device scopes and process counters.

``scope(name)`` names a phase of the device program: it is
``jax.named_scope("repro." + name)``, metadata on the HLO that the
profiler and the compiled program's ``op_name`` carry, so it adds no
operation.  Each op belongs to its innermost ``repro.*`` scope:

    apply, apply_adjoint, apply_gram, solve   the plan entry
    layout      pads, crops, partition-order gathers, batch transposes
    spmv        the Block-ELL product (kernel or reference)
    step        one fused Chebyshev / Jacobi update
    sweep       a single-launch sweep kernel (or its reference)
    recurrence  the per-order loop's own arithmetic
    exchange    halo tiles packed, permuted, unpacked and masked
    reorder     a sharded plan's signals moved between vertex and
                partition order, shard to shard

``count`` / ``add`` keep process-wide counters in memory; ``snapshot()``
reads them.  A path change (the sweep falling back to the per-order
kernels, a solve without a runner, ...) is counted where it is decided,
at trace time, so it counts once per trace.

Importing the module registers ``jax.monitoring`` listeners that feed
``compile.trace_s``, ``compile.lower_s`` and ``compile.backend_s`` (the
last includes a load from the persistent cache), ``compile.count`` (one
per backend compile or cache load) and ``compile.cache_hits`` /
``compile.cache_misses``.  These fire when a program is traced and
compiled, never per call.  ``events()`` keeps each with the
``time.perf_counter()`` reading at which it ended, so a reader can tell
the compiles before a moment from those after it.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Tuple

import jax
from jax import monitoring

PREFIX = "repro."

#: jax.monitoring duration events -> counter of seconds
COMPILE_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
}
#: jax.monitoring events -> counter
COMPILE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_lock = threading.Lock()
_counters: Dict[str, float] = {}
_events: List[Tuple[float, str, float]] = []
#: per seconds counter, the [start, seconds] of its outermost spans so far
_outer: Dict[str, List[List[float]]] = {}


def scope(name: str):
    """The device phase `name`: ``jax.named_scope("repro." + name)``."""
    return jax.named_scope(PREFIX + name)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def add(name: str, seconds: float) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0.0) + float(seconds)


def snapshot() -> Dict[str, float]:
    with _lock:
        return dict(_counters)


def events() -> List[Tuple[float, str, float]]:
    """Every compile-time event so far: (perf_counter at its end, counter
    name, seconds); a cache hit or miss takes 0 seconds."""
    with _lock:
        return list(_events)


def reset() -> None:
    with _lock:
        _counters.clear()
        _events.clear()
        _outer.clear()


def _on_duration(event: str, seconds: float, **_) -> None:
    name = COMPILE_SECONDS.get(event)
    if name is None:
        return
    end = time.perf_counter()
    with _lock:
        _events.append((end, name, float(seconds)))
        # an inner jit is traced inside its caller's trace and ends
        # first: count each second once, under the outermost span
        spans = _outer.setdefault(name, [])
        inner = 0.0
        while spans and spans[-1][0] >= end - seconds:
            inner += spans.pop()[1]
        spans.append([end - seconds, float(seconds)])
        _counters[name] = _counters.get(name, 0.0) + seconds - inner
        if name == "compile.backend_s":
            _counters["compile.count"] = _counters.get("compile.count", 0) + 1


def _on_event(event: str, **_) -> None:
    name = COMPILE_COUNTS.get(event)
    if name is None:
        return
    end = time.perf_counter()
    with _lock:
        _events.append((end, name, 0.0))
        _counters[name] = _counters.get(name, 0) + 1


monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)
