"""Post-run reconciliation counters for fused whole-run kernels.

The fused/device-loop run paths (``Advection.run``, the fused GoL board
kernel, the blocked Vlasov step) bypass the host halo seam by design —
their ghost traffic happens inside jit, where per-step recording would
cost dispatch-loop time and trace-time distortion.  This closes the
coverage gap from the HOST side instead: one cheap record per ``run()``
call of

* ``fused.runs{model,path}``   — dispatches of a whole-run kernel,
* ``fused.steps{model,path}``  — device-side steps those dispatches ran,
* ``fused.halo_bytes_equiv{model,path}`` — ``steps x bytes per step``,
  the ghost payload the run ships: the halo schedule's bytes, which the
  host seam WOULD have moved for the same steps, or on the dense slab
  layout its two z-planes per device (0 on a single device, where
  nothing is shipped).

``halo.bytes_moved`` (host seam) + ``fused.halo_bytes_equiv`` together
account for every step's ghost traffic, whichever path ran.
"""
from __future__ import annotations

from .registry import metrics

__all__ = ["record_run"]


def record_run(model: str, path: str, steps, bytes_per_step) -> None:
    """Record one whole-run dispatch.  ``steps`` may be a tracer when a
    caller embeds ``run()`` in its own jit — recording is skipped then
    (same contract as the halo seam's ``_tracing`` guard)."""
    if not metrics.enabled:
        return
    try:
        steps = int(steps)
        bps = int(bytes_per_step)
    except (TypeError, ValueError):  # tracer or abstract value: in-jit
        return
    labels = {"model": model, "path": path}
    metrics.inc_many([
        ("fused.runs", 1, labels),
        ("fused.steps", steps, labels),
        ("fused.halo_bytes_equiv", steps * bps, labels),
    ])
