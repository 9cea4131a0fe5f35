"""Back-compat phase timers — a shim over the ``obs`` metrics registry.

The original 67-line ``PhaseTimers`` grew into ``dccrg_tpu.obs``
(structured counters/gauges/histograms + thread-safe, re-entrant phase
spans); this module keeps the old surface alive:

* ``timers`` — the process-wide default, now a view over ``obs.metrics``
  so phases recorded by the instrumented seams (``epoch.build``,
  ``halo.exchange``, ...) appear in ``timers.report()`` unchanged;
* ``PhaseTimers()`` — an isolated registry with the old API
  (``phase``/``report``/``reset``/``total``/``count``/``enabled``).

The old implementation double-counted a ``phase("x")`` nested inside
``phase("x")`` (both spans added their wall time); the obs registry
counts only the outermost span per thread, and is lock-protected.
"""
from __future__ import annotations

from contextlib import contextmanager

from ..obs.registry import MetricsRegistry
from ..obs.registry import metrics as _global_metrics

__all__ = ["PhaseTimers", "timers"]


class PhaseTimers:
    """The pre-obs timer API, delegating to a :class:`MetricsRegistry`."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self._registry = (
            registry if registry is not None else MetricsRegistry()
        )

    @property
    def enabled(self) -> bool:
        return self._registry.enabled

    @enabled.setter
    def enabled(self, value: bool) -> None:
        self._registry.enabled = bool(value)

    def phase(self, name: str):
        return self._registry.phase(name)

    def report(self) -> dict:
        return self._registry.report()["phases"]

    def reset(self):
        self._registry.reset()

    # legacy raw accessors: {name: seconds} / {name: completions}
    @property
    def total(self) -> dict:
        return {n: rec["total_s"] for n, rec in self.report().items()}

    @property
    def count(self) -> dict:
        return {n: rec["count"] for n, rec in self.report().items()}


#: process-wide default registry (a view over ``obs.metrics``)
timers = PhaseTimers(registry=_global_metrics)


@contextmanager
def jax_trace(log_dir: str):
    """Capture a jax.profiler trace around a region (view with
    TensorBoard / xprof) — kept for back-compat; ``obs.profile_trace``
    is the same capture."""
    from ..obs.trace import profile_trace

    with profile_trace(log_dir):
        yield
