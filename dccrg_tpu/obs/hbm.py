"""Per-device memory gauges from ``Device.memory_stats()``.

OOM margins are invisible without them: a grid that barely fits HBM
today silently stops fitting after a refinement change.
``sample_hbm`` snapshots each local device's allocator statistics into
``hbm.*{device=d}`` gauges — called at every epoch rebuild
(``parallel/epoch.py``, the moment payload arrays are re-laid-out).

Backends without allocator stats (CPU returns ``None``; some plugins
raise) record nothing — the gauges simply stay absent there.

Ensemble memory accounting (ISSUE 11): allocator stats are per device
and absent on CPU, but the serving tier's headline memory question —
*how many scenarios fit one chip* — is per MEMBER.
:func:`sample_ensemble_hbm` records the
``ensemble.hbm_bytes_per_member{model}`` gauge from the cohort's own
buffer sizes (works on every backend, so CI can gate it): unique table
buffers counted ONCE under broadcast-shared tables, the stacked state
priced at its dispatch-time in-flight cost (2x without effective
donation — input and output coexist — 1x with).  Sampled at cohort
build and every step; ``tools/telemetry_diff.py`` CEILING-gates it so
the donation + shared-table wins cannot silently regress.
"""
from __future__ import annotations

from .registry import metrics

__all__ = ["sample_hbm", "sample_ensemble_hbm"]

#: the allocator stats worth tracking round-over-round (when present)
_STAT_KEYS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "largest_free_block_bytes",
)


def sample_hbm(registry=None, devices=None) -> dict:
    """Record ``hbm.<stat>{device=d}`` gauges for every local device
    that reports memory statistics; returns ``{device_id: {stat: v}}``
    for whatever was sampled (empty on statless backends)."""
    reg = registry if registry is not None else metrics
    if not reg.enabled:
        return {}
    if devices is None:
        try:
            import jax

            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 — no backend, no gauges
            return {}
    out: dict = {}
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — plugin without the API
            stats = None
        if not stats:
            continue
        dev_id = int(getattr(d, "id", 0))
        rec = {}
        for key in _STAT_KEYS:
            v = stats.get(key)
            if isinstance(v, (int, float)):
                reg.gauge(f"hbm.{key}", int(v), device=dev_id)
                rec[key] = int(v)
        if rec:
            out[dev_id] = rec
    return out


def sample_ensemble_hbm(model: str, bytes_per_member: int,
                        registry=None) -> int | None:
    """Record the per-member cohort memory gauge
    ``ensemble.hbm_bytes_per_member{model=...}`` (see module
    docstring); returns the recorded value, or None when telemetry is
    disabled.  The value is computed by the cohort
    (:meth:`dccrg_tpu.serve.ensemble.Cohort.member_hbm_bytes`) — this
    seam only owns the gauge name and registry routing so tools and
    tests have ONE spelling to assert on."""
    reg = registry if registry is not None else metrics
    if not reg.enabled:
        return None
    v = int(bytes_per_member)
    reg.gauge("ensemble.hbm_bytes_per_member", v, model=str(model))
    return v
