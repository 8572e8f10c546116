"""Compiled-cost accounting for jitted hot paths, recorded once at compile.

``launch/dryrun.py`` proved the pattern: XLA's ``cost_analysis`` /
``memory_analysis`` on an AOT-compiled executable give the *device* cost
of a program — FLOPs, HBM bytes accessed, argument/output/temp buffer
sizes — without ever running it. This module generalizes that plumbing
into an always-on accounting layer: wrap any ``jax.jit`` callable in
:class:`CostAccounted` and its compiled cost lands in the owning
registry as ``cost.*`` gauges labeled by hot-path name, exported through
the existing snapshot / Prometheus / Chrome-trace paths and rendered as
a roofline-style table by ``obs_report``.

Zero-sync contract: the analysis runs exactly once, at compile time, on
the host-side executable object — never per tick, and never touching a
device value. After the first call the wrapper is one attribute check
away from the bare compiled executable, identical whether telemetry is
on or off (the obs-on/off bit-parity tests drive both).

No jax import here: the wrapper duck-types ``fn.lower(*args).compile()``
(the AOT API), so the obs package stays importable without jax.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

from repro.obs.registry import Registry, get_registry

__all__ = ["CostAccounted", "compiled_cost", "record_compiled_cost"]

#: ``cost_analysis`` keys -> our metric names (XLA uses spaces in keys)
_COST_KEYS = {"flops": "flops", "bytes accessed": "bytes_accessed"}

#: ``memory_analysis`` attributes -> our metric names
_MEM_ATTRS = {"argument_size_in_bytes": "argument_bytes",
              "output_size_in_bytes": "output_bytes",
              "temp_size_in_bytes": "temp_bytes",
              "alias_size_in_bytes": "alias_bytes",
              "generated_code_size_in_bytes": "generated_code_bytes",
              "peak_memory_in_bytes": "peak_bytes"}


def compiled_cost(compiled: Any) -> Dict[str, float]:
    """Extract a flat ``{metric: value}`` record from a compiled
    executable's cost/memory analyses. Defensive by design: backends
    disagree on the exact surface (CPU's ``cost_analysis`` returns a
    one-element list; ``peak_memory_in_bytes`` is TPU-only), so missing
    pieces are simply absent from the record rather than raising."""
    rec: Dict[str, float] = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        for key, out in _COST_KEYS.items():
            v = cost.get(key)
            if v is not None:
                rec[out] = float(v)
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        for attr, out in _MEM_ATTRS.items():
            v = getattr(mem, attr, None)
            if v is not None:
                rec[out] = float(v)
    except Exception:
        pass
    try:
        # Pallas kernels lower to Mosaic custom calls: their count shows
        # whether a program runs the kernels or an XLA fallback
        rec["kernel_calls"] = float(compiled.as_text().count(
            'custom_call_target="tpu_custom_call"'))
    except Exception:
        pass
    if "peak_bytes" not in rec:
        parts = [rec.get(k) for k in
                 ("argument_bytes", "output_bytes", "temp_bytes")]
        if any(p is not None for p in parts):
            rec["peak_bytes"] = float(sum(p for p in parts if p is not None))
    return rec


def record_compiled_cost(registry: Registry, path: str, compiled: Any, *,
                         lower_s: Optional[float] = None,
                         compile_s: Optional[float] = None,
                         **labels) -> Dict[str, float]:
    """Record one compiled executable's cost as ``cost.*{path=...}``
    gauges plus a ``cost.compiled`` instant event on the timeline."""
    rec = compiled_cost(compiled)
    if lower_s is not None:
        rec["lower_seconds"] = float(lower_s)
    if compile_s is not None:
        rec["compile_seconds"] = float(compile_s)
    if registry.enabled:
        for metric, v in rec.items():
            registry.gauge(f"cost.{metric}", path=path, **labels).set(v)
        registry.counter("cost.compilations", path=path, **labels).inc()
        registry.event("cost.compiled", path=path, **labels, **rec)
    return rec


class CostAccounted:
    """Wrap a ``jax.jit`` callable so its compiled cost is accounted.

    The first call AOT-lowers and compiles (``fn.lower(*args).compile()``)
    — the same single compilation the plain jit would have done — runs
    the cost/memory analyses on the resulting executable, records them
    into ``registry`` (the process default if ``None``, resolved at
    compile time), and then *every* call, including the first, executes
    through the compiled object. Exactly one trace, one compilation, one
    accounting; per-call overhead after that is one ``is None`` check.

    Shape/dtype-polymorphic call sites cannot use this wrapper (the AOT
    executable is specialized to the first call's avals); every hot path
    in this repo is intentionally single-signature — the retrace guards
    in ``tests/test_sim_server.py`` pin that — so this is a feature: a
    second signature now fails loudly instead of silently retracing.
    """

    def __init__(self, fn: Callable, name: str, *,
                 registry: Optional[Registry] = None,
                 labels: Optional[Dict[str, str]] = None):
        self._fn = fn
        self.name = name
        self._labels = dict(labels or {})
        self._registry = registry
        self._compiled: Any = None
        self.num_compilations = 0
        self.cost: Optional[Dict[str, float]] = None

    def _cache_size(self) -> int:
        """Resident compiled programs — mirrors jit's private
        ``_cache_size`` so the zero-extra-compilation guards keep reading
        the same invariant through the wrapper."""
        return self.num_compilations

    def __call__(self, *args):
        if self._compiled is None:
            reg = (self._registry if self._registry is not None
                   else get_registry())
            t0 = time.perf_counter()
            lowered = self._fn.lower(*args)
            t1 = time.perf_counter()
            self._compiled = lowered.compile()
            t2 = time.perf_counter()
            self.num_compilations += 1
            self.cost = record_compiled_cost(
                reg, self.name, self._compiled,
                lower_s=t1 - t0, compile_s=t2 - t1, **self._labels)
        return self._compiled(*args)
