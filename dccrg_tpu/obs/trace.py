"""``jax.profiler`` capture around a region.

``profile_trace(log_dir)`` captures a full profiler trace (view with
TensorBoard / xprof, or read with ``jax.profiler.ProfileData``).  Every
``metrics.phase(...)`` span and the per-call spans of the model dispatch
are ``TraceAnnotation``s, so they land on the profiler's host plane on
the same clock as the device ops, whoever started the capture.
"""
from __future__ import annotations

from contextlib import contextmanager

__all__ = ["profile_trace"]


@contextmanager
def profile_trace(log_dir: str):
    """Capture a jax.profiler trace of the enclosed region into
    ``log_dir``."""
    import jax

    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
