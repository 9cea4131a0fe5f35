"""Grid-wide telemetry: metrics registry, phase spans, trace export.

The reference dccrg has no tracing layer at all — timing lives ad hoc in
its example workloads (``examples/game_of_life.cpp:116-146`` via
``chrono``) and its method paper evaluates on end-to-end wall clock only.
This subsystem gives the TPU port structured visibility into every hot
seam instead:

* a process-wide :class:`MetricsRegistry` (``obs.metrics``) holding
  counters, gauges, histograms (all label-aware) and re-entrant,
  thread-safe phase timers;
* instrumentation wired into halo exchange (``parallel/halo.py``),
  epoch construction (``parallel/epoch.py``), load balancing
  (``Grid.balance_load``), AMR commits (``amr/refinement.py``) and
  checkpoint I/O (``io/checkpoint.py``) — all recording from HOST code
  outside jit boundaries, so jitted programs never carry per-call dict
  churn;
* a JSON exporter (:func:`export_json` -> ``telemetry.json``) and a
  ``jax.profiler`` capture context
  (:func:`profile_trace`);
* program spans on the profiler's own clock: every phase is also a
  ``jax.profiler.TraceAnnotation`` of its name (``<layer>.<what>``:
  ``grid.*``, ``epoch.*``, ``amr.*``, ``advection.*``, ``halo.*``), and
  ``Advection.run`` marks its per-call parts the same way, so any
  capture (``profile_trace``, or a harness's own ``start_trace``) holds
  them on its host plane beside the device ops, with no clock alignment
  (``benchmark/`` reduces such captures to its per-layer metrics);
* a streaming exporter (:func:`stream_to`) appending incremental JSONL
  snapshots on a period, so a hung or killed run leaves phase evidence
  behind (``tools/soak.py``);
* a structured event timeline (``obs.timeline``) recording every
  completed phase as a begin/end span, exportable as Chrome trace-event
  JSON (:func:`export_chrome_trace`, view in perfetto), with the
  cross-process fleet merge (:func:`merge_chrome_traces`) and its
  validator (:func:`validate_merged_trace`);
* per-device memory gauges (:func:`sample_hbm` ->
  ``hbm.bytes_in_use{device=d}``), sampled at epoch rebuilds and bench
  checkpoints, and post-run reconciliation counters for the fused
  whole-run kernels that bypass the host halo seam (``obs.fused``);
* the request-level SLO plane (ISSUE 10): ``obs.slo`` — post-hoc
  quantiles (``p50/p95/p99``) and cross-process merges over the
  exported log-bucketed histograms (the serving front-end records
  ``ensemble.queue_wait_s{tenant}`` / ``ensemble.service_s`` /
  ``ensemble.e2e_s`` per request, and every completed phase feeds
  ``phase.duration_s{phase=...}`` via the registry's
  ``observe_duration`` hook; ``DCCRG_PHASE_HIST=0`` opts out) — plus
  the ``obs.flightrec`` black box: an always-on bounded ring of recent
  spans/events/in-flight requests, dumped as a schema-valid postmortem
  on supervisor escalation, oracle mismatch, or demand
  (``DCCRG_FLIGHTREC``, ``DCCRG_FLIGHTREC_DIR``,
  ``DCCRG_FLIGHTREC_CAP``; ``tools/slo_report.py`` is the read side).

* the LIVE side of that plane (ISSUE 16): ``obs.live`` tails the
  per-process ``*.stream.jsonl`` files across a fleet (byte-offset
  resume, torn-tail tolerance, seq-gap counting) and serves sliding-
  window views — windowed rates, windowed p50/p95/p99 via bucket-delta
  subtraction, per-tenant deadline-miss rates — through
  :class:`~dccrg_tpu.obs.live.FleetAggregator` /
  :class:`~dccrg_tpu.obs.live.FleetView`, plus a Prometheus text
  exposition; ``obs.alerts`` evaluates declarative
  :class:`~dccrg_tpu.obs.alerts.AlertRule` predicates (ceiling/floor,
  ``for_s`` duration-to-fire, hysteresis clear) over those views,
  counts firings, lands incidents on the timeline, dumps the flight
  recorder once per incident, and feeds the supervisor's escalation
  ladder (``DCCRG_LIVE_WINDOW_S``, ``DCCRG_ALERTS``,
  ``DCCRG_ALERT_RULES``, ``DCCRG_STREAM_FLUSH_S``;
  ``tools/fleet_top.py`` and ``slo_report.py --live`` are the consoles).

* the PREDICTIVE side (ISSUE 17): ``obs.cost`` turns recorded
  telemetry into forecasts — an online :class:`~dccrg_tpu.obs.cost.
  StepCostModel` of per-step dispatch cost keyed by
  ``(model, sig, k, g, W)`` with a documented cold-start fallback
  chain (exact → same-model → global), a per-tenant chargeback ledger
  (device-seconds, member-steps, halo exchanges, compile time
  attributed from existing series under a conservation invariant) and
  predicted queue-wait gauges (``cost.predicted_queue_wait_s{tenant}``)
  that ``Scheduler.select_k`` and admission advice consume
  (``DCCRG_COST_MODEL``, ``DCCRG_COST_MIN_SAMPLES``,
  ``DCCRG_COST_QUANTILE``; ``tools/cost_report.py`` and
  ``fleet_top.py --cost`` are the consoles).

Telemetry is on by default (the recording sites are per-epoch or
per-host-dispatch, never inside device loops); ``disable()`` — or
``DCCRG_TELEMETRY=0`` in the environment — makes every recording call a
cheap early return that touches no state at all.  The event timeline
can be switched off independently (``DCCRG_TIMELINE=0``).
"""
from .registry import MetricsRegistry, metrics, disable, enable
from .export import export_json
from .trace import profile_trace
from .stream import TelemetryStream, stream_to, maybe_flush
from .events import (
    EventTimeline,
    timeline,
    span,
    export_chrome_trace,
    enable_timeline,
    disable_timeline,
    merge_chrome_traces,
    validate_merged_trace,
)
from .hbm import sample_hbm
from . import fused
from . import slo
from . import live
from . import alerts
from . import cost
from .flightrec import (
    FlightRecorder,
    recorder as flight_recorder,
    validate_flightrec,
)

__all__ = [
    "MetricsRegistry",
    "metrics",
    "enable",
    "disable",
    "export_json",
    "profile_trace",
    "TelemetryStream",
    "stream_to",
    "maybe_flush",
    "EventTimeline",
    "timeline",
    "span",
    "export_chrome_trace",
    "enable_timeline",
    "disable_timeline",
    "sample_hbm",
    "fused",
    "slo",
    "live",
    "alerts",
    "cost",
    "FlightRecorder",
    "flight_recorder",
    "validate_flightrec",
    "merge_chrome_traces",
    "validate_merged_trace",
]
