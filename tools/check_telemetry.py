#!/usr/bin/env python
"""Telemetry gate: run a tiny advection workload and verify the obs
subsystem end to end.

Checks (exit 1 on any failure):

* every instrumented phase fires — ``halo.exchange``, ``epoch.build``,
  ``loadbalance.migrate``, ``amr.refine``, ``checkpoint.write`` — with
  nonzero counts, and the byte counters carry nonzero values where the
  workload exercises them;
* the report exports to ``telemetry.json`` (path via ``--out``) and the
  file round-trips through ``json.load``;
* the streaming exporter leaves a schema-valid JSONL file next to it
  (``<out>.stream.jsonl``: every line a complete snapshot, ``seq``
  strictly increasing, ``ts`` non-decreasing, counters monotonic) and
  the event timeline exports a valid Chrome trace
  (``<out>.trace.json``: matched begin/end pairs, monotonic in-thread
  timestamps) — :func:`validate_stream` / :func:`validate_chrome_trace`
  are also importable and runnable standalone on any such file
  (``--validate-stream`` / ``--validate-trace``);
* a forced injection round (ISSUE 4): a bit-flipped lineage generation
  must be detected by its payload CRC and skipped back to the clean
  one, and an injected ``p2p.recv`` fault must drive the retry plane —
  the probe fails unless ``resilience.injected``,
  ``checkpoint.crc_failures``, ``lineage.generations_skipped`` and
  ``p2p.retries`` all recorded;
* a halo-backend round (ISSUE 7): a forced ``DCCRG_HALO_BACKEND=pallas``
  + ``DCCRG_HALO_VERIFY=1`` grid runs blocking and split exchanges
  through the async-DMA ring bodies (interpreted on CPU) and must leave
  ``halo.verify_checks`` with zero ``halo.verify_mismatches``;
* an elastic round (ISSUE 8): one forced rescale down AND up through a
  checkpoint lineage (payload bit-identical both ways, the
  ``elastic.rescale`` phase + ``elastic.rescales{direction}`` counters
  required) plus a driven watchdog escalation over a synthetic stalled
  heartbeat (warn → rescale-down → restart in order, leaving
  ``supervisor.warnings`` / ``supervisor.escalations`` /
  ``elastic.degraded``);
* an SLO round (ISSUE 10): a deadline-mixed ensemble round must leave
  the request-latency histograms (``ensemble.queue_wait_s`` /
  ``ensemble.service_s`` / ``ensemble.e2e_s``) with sane quantile
  ordering (p50 <= p95 <= p99 recovered from the exported buckets),
  exact ``ensemble.deadline_miss`` counts and request lifecycle spans;
  a forced supervisor escalation with the flight recorder armed must
  produce exactly ONE schema-valid postmortem dump naming the round's
  requests (``obs.validate_flightrec``); the overhead budget below runs
  with the whole request plane on;
* a fleet round (ISSUE 19): two real worker subprocesses on 4-device
  mesh slices behind an in-process gateway; one worker is SIGKILLed
  after it starts stepping and its in-flight scenarios must redispatch
  to the survivor with every accepted scenario retiring EXACTLY once
  (one redispatched member byte-compared against uninterrupted solo
  stepping), one overflow submission must be rejected at the pinned
  queue bound, the loss must leave exactly ONE schema-valid postmortem
  naming the dead worker, and a journal reopen must replay the retired
  state (``gateway.{accepted,rejected,redispatched,journal_replays}``
  all required nonzero);
* side artifacts (``<out>.stream.jsonl`` / ``.trace.json``) land next
  to ``--out`` — or under ``tools/``
  when ``--out`` is the repo root's ``telemetry.json``, keeping its
  byproducts out of the root (``--artifact-dir`` overrides);
* unless ``--skip-overhead``: enabling telemetry must not slow the
  workload's step loop by more than ``--threshold`` (default 1.05 =
  5%) vs the disabled mode — the zero-cost-when-disabled and
  cheap-when-enabled contract.

Runnable standalone (``python tools/check_telemetry.py``) and as a
``not slow`` pytest via ``tests/test_obs.py::test_check_telemetry_tool``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

#: the phase set the acceptance criteria require (ISSUE 1; ISSUE 3 adds
#: the incremental rebuild phase; ISSUE 4 the lineage phases)
REQUIRED_PHASES = (
    "halo.exchange",
    "epoch.build",
    "epoch.delta_build",
    "loadbalance.migrate",
    "amr.refine",
    "checkpoint.write",
    "lineage.commit",
    "lineage.scan",
    # ISSUE 5: kernel (re)traces are timed — a probe run always compiles
    # its kernels at least once in a fresh process
    "compile",
    # ISSUE 8: the forced rescale round must time the full commit ->
    # re-land -> verify pipeline
    "elastic.rescale",
    # ISSUE 9: the ensemble probe's admit -> step -> retire round
    "ensemble.admit",
    "ensemble.step",
    # ISSUE 10: the forced escalation must write its black box
    "flightrec.dump",
    # ISSUE 17: every submission with the cost model armed times its
    # admission estimate
    "cost.estimate",
)

#: counters that must be nonzero after the workload
REQUIRED_NONZERO_COUNTERS = (
    "halo.bytes_moved",
    "halo.cells_moved",
    "amr.cells_refined",
    "checkpoint.bytes_written",
    # the probe's small second commit must take the incremental path,
    # not fall back — a silent fallback is a coverage loss
    "epoch.delta_builds",
    # ISSUE 4: the forced injection round must leave the full
    # detection-path evidence — an injected fault that is not counted,
    # or a corrupt generation whose CRC failure is not counted, means
    # the resilience plane silently lost coverage
    "resilience.injected",
    "checkpoint.crc_failures",
    "lineage.generations_skipped",
    "p2p.retries",
    # ISSUE 5: compiled-schedule accounting — every fresh process traces
    # kernels (recompiles), and the churn probe must HIT the executable
    # cache on its second cycle
    "epoch.recompiles",
    "epoch.cache_hits",
    # ISSUE 7: the forced pallas-backend round must leave its oracle
    # evidence — a verify round that silently checked nothing is a
    # coverage loss, exactly like an uncounted injected fault
    "halo.backend_schedules",
    "halo.verify_checks",
    # ISSUE 8: the forced rescale + driven watchdog ladder must leave
    # the full elastic-fleet evidence — a rescale that is not counted,
    # or an escalation rung that never fires, is lost coverage of the
    # supervised-rescale plane
    "elastic.rescales",
    "elastic.degraded",
    "supervisor.warnings",
    "supervisor.escalations",
    # ISSUE 9: the ensemble probe must leave the full serving-lifecycle
    # evidence — an admission, retirement, or served step that is not
    # counted is lost coverage of the multiplexing plane, and a verify
    # round that checked nothing is a silent oracle loss
    "ensemble.admitted",
    "ensemble.retired",
    "ensemble.steps_served",
    "ensemble.verify_checks",
    # ISSUE 10: the deadline-mixed SLO round must count its misses
    # (silent misses are exactly what the request plane exists to end)
    # and the forced escalation must leave its postmortem evidence
    "ensemble.deadline_miss",
    "flightrec.dumps",
    # ISSUE 17: the cost plane's evidence — admission verdicts counted
    # on every submit, and the conservation companion every dispatch
    # bills wall×mesh device-seconds into
    "ensemble.admission_estimates",
    "ensemble.device_s_total",
    # ISSUE 19: the fleet probe's forced failure round must leave the
    # whole gateway evidence trail — an accepted fleet, an enforced
    # rejection at the pinned queue bound, the kill's redispatch, and a
    # journal reopen that counts its replay.  Any of these at zero
    # means the fault-tolerance plane silently lost coverage.
    "gateway.accepted",
    "gateway.rejected",
    "gateway.redispatched",
    "gateway.journal_replays",
)

#: histograms that must carry samples after the probe (ISSUE 10): the
#: per-request latency distributions the SLO report quantiles, and the
#: phase-duration series the registry's observe_duration hook feeds
REQUIRED_HISTOGRAMS = (
    "ensemble.queue_latency",
    "ensemble.queue_wait_s",
    "ensemble.service_s",
    "ensemble.e2e_s",
    "phase.duration_s",
    # ISSUE 17: the per-key step-cost distributions the online model
    # (and its cross-process merges) are built from
    "cost.step_s",
)


#: keys every streaming snapshot line must carry
STREAM_REQUIRED_KEYS = ("seq", "ts", "phases", "counters", "gauges",
                        "histograms")


def validate_stream(path: str, counts: dict | None = None) -> list:
    """Schema-validate a telemetry JSONL stream (``obs.stream_to``
    output); returns failure strings (empty = valid).  A truncated FINAL
    line is tolerated when the file does not end in a newline — that is
    exactly the killed-mid-write case the stream exists to survive — but
    every complete line must parse and the sequence must be coherent.

    Anomalies that are tolerated are no longer silent (ISSUE 16): pass
    a ``counts`` dict and it comes back with ``lines`` (complete
    snapshot lines), ``seq_gaps`` (missing sequence numbers — lines
    lost to a partial copy or a writer restarted without truncate) and
    ``torn_tail`` (1 when the final line was cut mid-write) — the same
    tallies the live tailer (``obs/live.py``) keeps per file."""
    failures: list = []
    if counts is None:
        counts = {}
    counts.update({"lines": 0, "seq_gaps": 0, "torn_tail": 0,
                   "bad_lines": 0})
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return [f"stream unreadable: {e}"]
    lines = text.split("\n")
    trailing_partial = lines and lines[-1] != ""
    body = [ln for ln in (lines[:-1] if trailing_partial else lines) if ln]
    if trailing_partial:
        try:
            json.loads(lines[-1])
            body.append(lines[-1])  # complete after all, just no newline
        except json.JSONDecodeError:
            # killed mid-write: the complete lines carry the evidence —
            # tolerated, but COUNTED so a consumer can see it happened
            counts["torn_tail"] = 1
    if not body:
        return [f"stream {path} holds no complete snapshot line"]
    prev_seq, prev_ts = None, None
    prev_counters: dict = {}
    for i, ln in enumerate(body):
        try:
            rec = json.loads(ln)
        except json.JSONDecodeError as e:
            counts["bad_lines"] += 1
            failures.append(f"line {i}: not JSON ({e})")
            continue
        if not isinstance(rec, dict):
            counts["bad_lines"] += 1
            failures.append(f"line {i}: not an object")
            continue
        counts["lines"] += 1
        missing = [k for k in STREAM_REQUIRED_KEYS if k not in rec]
        if missing:
            failures.append(f"line {i}: missing keys {missing}")
            continue
        if prev_seq is not None and rec["seq"] <= prev_seq:
            failures.append(
                f"line {i}: seq {rec['seq']} not above {prev_seq}"
            )
        elif prev_seq is not None and rec["seq"] > prev_seq + 1:
            # strictly increasing but not contiguous: lines are MISSING
            # (lost to a partial copy, or a writer reopened an existing
            # file) — coherent enough to consume, counted as gaps
            counts["seq_gaps"] += rec["seq"] - prev_seq - 1
        if prev_ts is not None and rec["ts"] < prev_ts:
            failures.append(
                f"line {i}: ts {rec['ts']} went backwards from {prev_ts}"
            )
        # counters are cumulative monotonic totals — a decrease means a
        # reset mid-stream or a writer bug
        for name, series in rec["counters"].items():
            for label, v in series.items():
                pv = prev_counters.get((name, label))
                if pv is not None and v < pv:
                    failures.append(
                        f"line {i}: counter {name}[{label}] decreased "
                        f"({pv} -> {v})"
                    )
                prev_counters[(name, label)] = v
        prev_seq, prev_ts = rec["seq"], rec["ts"]
    return failures


def validate_chrome_trace(path: str) -> list:
    """Schema-validate a Chrome trace-event export
    (``obs.export_chrome_trace`` output): every ``B`` has a matching
    ``E`` of the same name in stack order per (pid, tid), and in-thread
    timestamps never go backwards.  Returns failure strings."""
    failures: list = []
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"trace unreadable: {e}"]
    events = data.get("traceEvents") if isinstance(data, dict) else data
    if not isinstance(events, list):
        return ["trace has no traceEvents list"]
    stacks: dict = {}
    last_ts: dict = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or "ph" not in ev:
            failures.append(f"event {i}: not a trace event")
            continue
        ph = ev["ph"]
        if ph not in ("B", "E"):
            continue  # X/i/M events are legal, just not produced here
        key = (ev.get("pid"), ev.get("tid"))
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            failures.append(f"event {i}: bad ts {ts!r}")
            continue
        if ts < last_ts.get(key, float("-inf")):
            failures.append(
                f"event {i}: ts {ts} went backwards on tid {key}"
            )
        last_ts[key] = ts
        stack = stacks.setdefault(key, [])
        if ph == "B":
            stack.append((ev.get("name"), ts))
        else:
            if not stack:
                failures.append(
                    f"event {i}: E {ev.get('name')!r} with empty stack "
                    f"on tid {key}"
                )
                continue
            bname, bts = stack.pop()
            if bname != ev.get("name"):
                failures.append(
                    f"event {i}: E {ev.get('name')!r} closes B {bname!r}"
                )
            if ts < bts:
                failures.append(
                    f"event {i}: span {bname!r} ends before it begins"
                )
    for key, stack in stacks.items():
        if stack:
            failures.append(
                f"tid {key}: {len(stack)} unmatched B events "
                f"({[n for n, _ in stack]})"
            )
    return failures


def artifact_path(out_path: str, suffix: str,
                  artifact_dir: str | None = None) -> str:
    """Where a side artifact (``<out basename><suffix>``) lands.

    Default: next to ``out_path`` — EXCEPT when ``out_path`` sits at the
    repo root (the committed ``telemetry.json``), whose byproducts are
    archived under ``tools/`` alongside ``telemetry_prev.json`` and the
    history instead of littering the root (ISSUE 8).  An explicit
    ``artifact_dir`` (``--artifact-dir``) overrides either way."""
    out = pathlib.Path(out_path)
    if artifact_dir is None:
        parent = out.resolve().parent
        artifact_dir = ROOT / "tools" if parent == ROOT else parent
    return str(pathlib.Path(artifact_dir) / (out.name + suffix))


def _ensure_env() -> None:
    """CPU backend with a small virtual mesh (so halo traffic is real)
    when run standalone; inert when a backend is already configured
    (pytest's conftest sets an 8-device mesh)."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()


def build_workload():
    """Tiny refined advection grid: 8^3 level-0 with a refined ball,
    balanced, on the general (host-driven) path."""
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import Advection

    n = 8
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_load_balancing_method("RCB")
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh())
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    r = np.linalg.norm(c - 0.5, axis=1)
    for cid in ids[r < 0.3]:
        g.refine_completely(int(cid))
    g.stop_refining()
    g.balance_load()
    # one small follow-up commit: its closure is a few percent of the
    # grid, so derived state is delta-patched (epoch.delta_build), not
    # rebuilt — the probe covers BOTH rebuild paths
    g.refine_completely(int(g.get_cells()[0]))
    g.stop_refining()
    adv = Advection(g, dtype=np.float32, allow_dense=False)
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    return g, adv, state, dt


def drive(g, adv, state, dt, steps: int):
    """The timed step loop: an explicit host-level ghost refresh (the
    instrumented halo seam) followed by one advection step."""
    import jax

    for _ in range(steps):
        state = {
            **state,
            **g.update_copies_of_remote_neighbors(
                {"density": state["density"]}
            ),
        }
        state = adv.step(state, dt)
    jax.block_until_ready(state["density"])
    return state


def _resilience_probe(g, state) -> list:
    """Forced injection round (ISSUE 4): arm a bit flip, commit two
    lineage generations (one corrupt), and require the full detection
    path to fire — the lineage scan must skip the corrupt generation on
    its payload CRC and resume the clean one — plus one injected
    ``p2p.recv`` fault driven through the real transport receive loop
    so the retry/backoff counter records.  Returns failure strings."""
    import socket

    import numpy as np

    failures: list = []
    from dccrg_tpu.io.checkpoint import CheckpointError
    from dccrg_tpu.resilience import CheckpointLineage, plane
    from dccrg_tpu.utils.collectives import _P2PTransport

    spec = {"density": ((), np.float32)}
    with tempfile.TemporaryDirectory() as td:
        lineage = CheckpointLineage(os.path.join(td, "lineage"), keep=3)
        clean_gen = lineage.commit(g, state, spec, user_header=b"clean")
        plane.arm("checkpoint.bit_flip", prob=1.0, seed=0, count=1)
        try:
            corrupt_gen = lineage.commit(g, state, spec,
                                         user_header=b"corrupt")
        finally:
            plane.disarm("checkpoint.bit_flip")
        try:
            _g2, _s2, hdr, gen = lineage.latest_valid(spec, n_devices=1)
            if gen != clean_gen or hdr != b"clean":
                failures.append(
                    f"lineage scan resumed generation {gen} ({hdr!r}) "
                    f"instead of skipping corrupt generation "
                    f"{corrupt_gen} back to {clean_gen}"
                )
        except CheckpointError as e:
            failures.append(f"lineage scan found no valid generation: {e}")

    # injected recv fault through the real _recvn loop: first attempt
    # raises, backoff fires, the retry drains the socket
    a, b = socket.socketpair()
    try:
        b.sendall(b"probe-ok")
        plane.arm("p2p.recv", prob=1.0, seed=0, count=1)
        try:
            got = _P2PTransport._recvn(a, 8, peer=0)
        finally:
            plane.disarm("p2p.recv")
        if got != b"probe-ok":
            failures.append(f"retried recv returned {got!r}")
    finally:
        a.close()
        b.close()
    return failures


def _churn_probe(g, dt) -> list:
    """Forced churn cycle pair (ISSUE 5): cycle one commits a structural
    change, rebuilds the model and steps — warming the executable cache
    for the (possibly new) shape signature; cycle two repeats with an
    unchanged signature and must compile NOTHING (``epoch.recompiles``
    stays flat — the zero-retrace contract of shape-stable epochs)."""
    import jax
    import numpy as np

    from dccrg_tpu import obs
    from dccrg_tpu.models import Advection

    failures: list = []

    def total_recompiles() -> int:
        rep = obs.metrics.report()
        return int(sum(rep["counters"].get("epoch.recompiles", {})
                       .values()))

    def cycle(i: int):
        cells = g.get_cells()
        lvl = g.mapping.get_refinement_level(cells)
        cand = cells[lvl < g.mapping.max_refinement_level]
        g.refine_completely(int(cand[(i * 13) % len(cand)]))
        g.stop_refining()
        adv = Advection(g, dtype=np.float32, allow_dense=False)
        st = adv.initialize_state()
        st = adv.step(st, dt)
        jax.block_until_ready(st["density"])

    cycle(0)
    sig = g.shape_signature()
    before = total_recompiles()
    cycle(1)
    if g.shape_signature() != sig:
        failures.append(
            "churn probe: one-cell commit changed the shape signature "
            f"({sig} -> {g.shape_signature()}) — bucket hysteresis is "
            "not holding shapes"
        )
    elif total_recompiles() != before:
        failures.append(
            f"churn probe: second same-signature cycle recompiled "
            f"{total_recompiles() - before} kernel(s); the executable "
            "cache must make it zero"
        )
    return failures


def _halo_backend_probe() -> list:
    """Forced pallas-backend round (ISSUE 7): build a small multi-ring
    grid with ``DCCRG_HALO_BACKEND=pallas`` + ``DCCRG_HALO_VERIFY=1``,
    run blocking and split-phase exchanges through the async-DMA ring
    bodies (interpreted on CPU), and require the oracle cross-check to
    have fired with ZERO mismatches — the probe fails exactly when the
    DMA transport stops being bit-identical to the collective path."""
    import numpy as np

    from dccrg_tpu import Grid, make_mesh, obs

    failures: list = []
    saved = {k: os.environ.get(k)
             for k in ("DCCRG_HALO_BACKEND", "DCCRG_HALO_VERIFY")}
    os.environ["DCCRG_HALO_BACKEND"] = "pallas"
    os.environ["DCCRG_HALO_VERIFY"] = "1"
    try:
        g = (
            Grid()
            .set_initial_length((8, 8, 1))
            .set_neighborhood_length(1)
            .set_load_balancing_method("RCB")
            .initialize(mesh=make_mesh())
        )
        if g.halo().backend != "pallas":
            return ["halo backend probe: DCCRG_HALO_BACKEND=pallas did "
                    f"not select the pallas transport "
                    f"(got {g.halo().backend!r})"]
        state = g.new_state({"v": ((), np.float64)})
        cells = g.get_cells()
        state = g.set_cell_data(
            state, "v", cells, np.sin(cells.astype(np.float64))
        )
        state = g.update_copies_of_remote_neighbors(state)
        handle = g.start_remote_neighbor_copy_updates(state)
        g.wait_remote_neighbor_copy_updates(state, handle)
        rep = obs.metrics.report()
        checks = sum(rep["counters"].get("halo.verify_checks", {})
                     .values())
        if checks < 2:
            failures.append(
                f"halo backend probe: verify oracle ran {checks} "
                "checks; the blocking + split round must cross-check "
                "both"
            )
        mismatches = sum(rep["counters"]
                         .get("halo.verify_mismatches", {}).values())
        if mismatches:
            failures.append(
                f"halo backend probe: {mismatches} pallas/collective "
                "mismatches — the DMA ring body is no longer "
                "bit-identical to the oracle"
            )
    except Exception as e:  # noqa: BLE001 — probe reports, not dies
        failures.append(f"halo backend probe failed: {e!r}")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return failures


def _elastic_probe(g, state) -> list:
    """Forced rescale round + driven watchdog ladder (ISSUE 8).

    Rescale the probe grid down to half its devices and back up through
    a checkpoint lineage (``resilience/elastic.py``) — the payload must
    survive both re-landings bit-identically and both directions must be
    counted under the ``elastic.rescale`` phase.  Then drive the
    supervisor's escalation ladder over a synthetic stalled heartbeat:
    warn → rescale-down (``elastic.degraded``) → restart must fire in
    exactly that order.  Returns failure strings."""
    import numpy as np

    from dccrg_tpu import obs
    from dccrg_tpu.resilience import (
        EscalationLadder,
        HeartbeatMonitor,
        Supervisor,
        rescale,
    )

    failures: list = []
    spec = {"density": ((), np.float32)}
    ids = g.get_cells()
    want = np.asarray(g.get_cell_data(state, "density", ids))
    with tempfile.TemporaryDirectory() as td:
        try:
            down = max(1, g.n_devices // 2)
            r = rescale(g, state, spec, down,
                        directory=os.path.join(td, "lineage"),
                        user_header=b"elastic-probe")
            r2 = rescale(r.grid, r.state, spec, g.n_devices,
                         directory=os.path.join(td, "lineage"),
                         user_header=b"elastic-probe")
            for tag, res, nd in (("down", r, down),
                                 ("up", r2, g.n_devices)):
                if res.n_devices_after != nd:
                    failures.append(
                        f"elastic probe: rescale {tag} landed on "
                        f"{res.n_devices_after} devices, wanted {nd}"
                    )
                got = np.asarray(
                    res.grid.get_cell_data(res.state, "density", ids)
                )
                if not np.array_equal(got, want):
                    failures.append(
                        f"elastic probe: rescale {tag} altered the "
                        "payload"
                    )
        except Exception as e:  # noqa: BLE001 — probe reports, not dies
            failures.append(f"elastic rescale probe failed: {e!r}")

    # watchdog ladder over a synthetic stalled heartbeat (injected
    # clock, so the probe never sleeps)
    with tempfile.TemporaryDirectory() as td:
        try:
            hb = os.path.join(td, "hb.jsonl")
            s = obs.TelemetryStream(hb, period=3600.0, truncate=True)
            s.write_snapshot(step=0)
            mon = HeartbeatMonitor(hb, stall_after_s=1.0, now=0.0)
            sup = Supervisor(mon, ladder=EscalationLadder())
            first = sup.poll(now=0.5)
            if first["status"] != "ok":
                failures.append(
                    f"elastic probe: fresh heartbeat read as "
                    f"{first['status']}"
                )
            acts = [sup.poll(now=10.0 + i)["action"] for i in range(3)]
            if acts != ["warn", "rescale_down", "restart"]:
                failures.append(
                    f"elastic probe: escalation ladder ran {acts}, "
                    "wanted ['warn', 'rescale_down', 'restart']"
                )
        except Exception as e:  # noqa: BLE001
            failures.append(f"elastic watchdog probe failed: {e!r}")
    return failures


def _ensemble_probe() -> list:
    """Ensemble serving round (ISSUE 9): one admit → step → retire
    lifecycle through the cohort front-end with the solo-replay oracle
    armed.  Requirements: a second admission wave at the HELD cohort
    width must trace zero new kernels (``epoch.recompiles`` flat — the
    shape-stable serving contract), the oracle must have checked with
    zero mismatches, a sampled member must retire bit-identical to solo
    stepping, and the peak-occupancy gauge must land in (0, 1] (the
    floor the telemetry gate watches).  Returns failure strings."""
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
    from dccrg_tpu.models import GameOfLife
    from dccrg_tpu.serve import Ensemble

    failures: list = []
    try:
        n = 4
        g = (
            Grid()
            .set_initial_length((n, n, n))
            .set_neighborhood_length(0)
            .set_periodic(True, True, True)
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(1.0 / n,) * 3,
            )
            .initialize(mesh=make_mesh())
        )
        g.stop_refining()
        gol = GameOfLife(g, allow_dense=False)
        cells = g.get_cells()
        rng = np.random.default_rng(0)
        mk = lambda: gol.new_state(
            alive_cells=cells[rng.random(len(cells)) < 0.3]
        )

        def recompiles() -> int:
            rep = obs.metrics.report()
            return int(sum(rep["counters"].get("epoch.recompiles", {})
                           .values()))

        ens = Ensemble(verify=True)
        first = [mk() for _ in range(4)]
        tickets = [ens.submit(gol, s, steps=3, tenant=f"tenant{i % 2}")
                   for i, s in enumerate(first)]
        ens.run()                                # warm the cohort body
        before = recompiles()
        for s in (mk() for _ in range(4)):       # churn at held width
            ens.submit(gol, s, steps=2)
        ens.run()
        if recompiles() != before:
            failures.append(
                f"ensemble probe: admission/retirement at a held "
                f"signature recompiled {recompiles() - before} "
                "kernel(s); the cohort executable must make it zero"
            )
        ref = first[0]
        for _ in range(3):
            ref = gol.step(ref)
        import jax

        same = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(jax.tree_util.tree_leaves(ref),
                            jax.tree_util.tree_leaves(tickets[0].result))
        )
        if not same:
            failures.append(
                "ensemble probe: cohort-stepped member diverged from "
                "solo stepping (bit-identity anchor broken)"
            )
        rep = obs.metrics.report()
        checks = sum(rep["counters"].get("ensemble.verify_checks", {})
                     .values())
        if checks < 2:
            failures.append(
                f"ensemble probe: verify oracle ran {checks} checks; "
                "the armed round must replay sampled members"
            )
        mism = sum(rep["counters"].get("ensemble.verify_mismatches", {})
                   .values())
        if mism:
            failures.append(
                f"ensemble probe: {mism} cohort/solo mismatches — the "
                "stacked cohort body is no longer bit-identical to the "
                "member programs"
            )
        occ = rep["gauges"].get("ensemble.cohort_peak_occupancy", {})
        if not occ:
            failures.append(
                "ensemble probe: ensemble.cohort_peak_occupancy gauge "
                "missing after the serving round"
            )
        elif not all(0.0 < v <= 1.0 for v in occ.values()):
            failures.append(
                f"ensemble probe: peak occupancy out of (0, 1]: {occ}"
            )

        # deep dispatch (ISSUE 11): a k=4 cohort round — the fori_loop
        # body must be bit-identical to 4 solo steps (oracle armed), a
        # second wave at the held (signature, width, k) must recompile
        # NOTHING, and the depth + per-member HBM gauges must land
        ens4 = Ensemble(verify=True, steps_per_dispatch=4)
        deep = [mk() for _ in range(4)]
        deep_tickets = [ens4.submit(gol, s, steps=8) for s in deep]
        ens4.run()                               # warms the k=4 body
        before = recompiles()
        for s in (mk() for _ in range(4)):       # churn at held (W, k)
            ens4.submit(gol, s, steps=8)
        ens4.run()
        if recompiles() != before:
            failures.append(
                f"ensemble probe: k=4 churn at a held (signature, "
                f"width, k) recompiled {recompiles() - before} "
                "kernel(s); deep dispatch must re-dispatch the cached "
                "body"
            )
        ref4 = deep[0]
        for _ in range(8):
            ref4 = gol.step(ref4)
        same4 = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(jax.tree_util.tree_leaves(ref4),
                            jax.tree_util.tree_leaves(
                                deep_tickets[0].result))
        )
        if not same4:
            failures.append(
                "ensemble probe: k=4 deep dispatch diverged from 8 "
                "solo steps (k-step bit-identity anchor broken)"
            )
        rep = obs.metrics.report()
        mism = sum(rep["counters"].get("ensemble.verify_mismatches", {})
                   .values())
        if mism:
            failures.append(
                f"ensemble probe: {mism} cohort/solo mismatches after "
                "the deep-dispatch round — the fori_loop cohort body "
                "is not bit-identical to the member program"
            )
        kgauge = rep["gauges"].get("ensemble.steps_per_dispatch", {})
        if not any(v > 0 for v in kgauge.values()):
            failures.append(
                "ensemble probe: ensemble.steps_per_dispatch gauge "
                f"missing or zero after a k=4 round: {kgauge}"
            )
        hbm_g = rep["gauges"].get("ensemble.hbm_bytes_per_member", {})
        if not any(v > 0 for v in hbm_g.values()):
            failures.append(
                "ensemble probe: ensemble.hbm_bytes_per_member gauge "
                f"missing or zero after the serving rounds: {hbm_g}"
            )
    except Exception as e:  # noqa: BLE001 — probe reports, not dies
        failures.append(f"ensemble probe failed: {e!r}")
    return failures


def _wide_halo_probe() -> list:
    """Exchange-amortized deep dispatch round (ISSUE 14): a k=4 wide
    round on a depth-4 ghost zone must pay ONE exchange per dispatch —
    the ``halo.exchanges_per_step`` gauge (the ceiling-gated headline)
    reads exactly 1/4 — with the solo-replay oracle armed and clean,
    and a second wave at the held (signature, width, k, g) must
    recompile NOTHING.  Returns failure strings."""
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
    from dccrg_tpu.models import GameOfLife
    from dccrg_tpu.parallel import halo
    from dccrg_tpu.serve import Ensemble

    failures: list = []
    try:
        n = 6
        g = (
            Grid()
            .set_initial_length((n, n, n))
            .set_neighborhood_length(4)
            .set_periodic(True, True, True)
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(1.0 / n,) * 3,
            )
            .initialize(mesh=make_mesh())
        )
        g.stop_refining()
        moore = [(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1)
                 for k in (-1, 0, 1) if (i, j, k) != (0, 0, 0)]
        g.add_neighborhood(7, moore)
        gol = GameOfLife(g, hood_id=7, allow_dense=False)
        spec = gol.batch_step_spec()
        if spec.wide is None or spec.wide.budget < 4:
            failures.append(
                "wide-halo probe: no engageable wide plan on a depth-4 "
                f"hood (wide={spec.wide!r}); exchange amortization "
                "cannot run"
            )
            return failures
        cells = g.get_cells()
        rng = np.random.default_rng(0)
        mk = lambda: gol.new_state(
            alive_cells=cells[rng.random(len(cells)) < 0.3]
        )

        def recompiles() -> int:
            rep = obs.metrics.report()
            return int(sum(rep["counters"].get("epoch.recompiles", {})
                           .values()))

        halo._amortization.clear()
        ens = Ensemble(verify=True, steps_per_dispatch=4)
        first = [mk() for _ in range(4)]
        tickets = [ens.submit(gol, s, steps=8, tenant="wide")
                   for s in first]
        ens.run()                            # warms the (k=4, g=4) body
        before = recompiles()
        for s in (mk() for _ in range(4)):   # churn at held (W, k, g)
            ens.submit(gol, s, steps=4, tenant="wide")
        ens.run()
        if recompiles() != before:
            failures.append(
                f"wide-halo probe: churn at a held (signature, width, "
                f"k, g) recompiled {recompiles() - before} kernel(s); "
                "the wide cohort body must re-dispatch from cache"
            )
        rep = obs.metrics.report()
        gauge = rep["gauges"].get("halo.exchanges_per_step", {})
        got = gauge.get("model=gol")
        if got != 0.25:
            failures.append(
                f"wide-halo probe: halo.exchanges_per_step = {got!r} "
                "after k=4 wide rounds; one exchange must fund 4 "
                "interior steps (wanted 0.25)"
            )
        checks = sum(rep["counters"].get("ensemble.verify_checks", {})
                     .values())
        if checks < 2:
            failures.append(
                f"wide-halo probe: verify oracle ran {checks} checks; "
                "the armed wide round must replay sampled members"
            )
        mism = sum(rep["counters"].get("ensemble.verify_mismatches", {})
                   .values())
        if mism:
            failures.append(
                f"wide-halo probe: {mism} cohort/solo mismatches — the "
                "amortized body is no longer bit-identical to exchange-"
                "every-step stepping on owned rows"
            )
        # owned-row bit-identity against solo, independent of the oracle
        import jax  # noqa: F401 — tree flatten below

        ref = first[0]
        for _ in range(8):
            ref = gol.step(ref)
        lm = spec.wide.local_mask
        for name in sorted(ref):
            a = np.asarray(ref[name])
            b = np.asarray(tickets[0].result[name])
            if a.shape[:2] == lm.shape:
                a, b = a[lm], b[lm]
            if a.tobytes() != b.tobytes():
                failures.append(
                    f"wide-halo probe: field {name!r} diverged from 8 "
                    "solo steps on owned rows"
                )
    except Exception as e:  # noqa: BLE001 — probe reports, not dies
        failures.append(f"wide-halo probe failed: {e!r}")
    return failures


def _slo_probe() -> list:
    """Request-level SLO round (ISSUE 10).

    Drives a deadline-mixed ensemble round (two tenants; half the
    scenarios submitted with already-passed deadlines, half with far
    ones) and requires the full request plane to materialize: the
    ``ensemble.queue_wait_s`` / ``ensemble.e2e_s`` histograms with sane
    quantile ordering (p50 <= p95 <= p99 from the exported buckets
    alone), exact deadline-miss counts, and request lifecycle spans on
    the timeline.  Then forces a supervisor escalation with the flight
    recorder armed at a scratch directory: the ladder must produce
    EXACTLY ONE schema-valid postmortem dump for the incident, naming
    the round's request activity.  Returns failure strings."""
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
    from dccrg_tpu.models import GameOfLife
    from dccrg_tpu.obs import flight_recorder, slo, validate_flightrec
    from dccrg_tpu.resilience import EscalationLadder
    from dccrg_tpu.serve import Ensemble

    failures: list = []
    prev_dir = flight_recorder.armed_dir
    td = tempfile.mkdtemp(prefix="dccrg_slo_probe_")
    try:
        flight_recorder.arm(td, autodump=False)
        n = 4
        g = (
            Grid()
            .set_initial_length((n, n, n))
            .set_neighborhood_length(0)
            .set_periodic(True, True, True)
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(1.0 / n,) * 3,
            )
            .initialize(mesh=make_mesh())
        )
        g.stop_refining()
        gol = GameOfLife(g, allow_dense=False)
        cells = g.get_cells()
        rng = np.random.default_rng(1)
        mk = lambda: gol.new_state(
            alive_cells=cells[rng.random(len(cells)) < 0.3]
        )
        before_miss = int(sum(
            obs.metrics.report()["counters"]
            .get("ensemble.deadline_miss", {}).values()
        ))
        ens = Ensemble(policy="deadline")
        now = time.perf_counter()
        expect_missed = 0
        for i in range(6):
            # even submissions carry deadlines that already passed —
            # guaranteed misses; odd ones have a generous hour
            past = i % 2 == 0
            ens.submit(gol, mk(), steps=2 + i % 3,
                       tenant=f"tenant{i % 2}",
                       deadline=now - 1.0 if past else now + 3600.0)
            expect_missed += past
        ens.run()

        rep = obs.metrics.report()
        for name in ("ensemble.queue_wait_s", "ensemble.e2e_s",
                     "ensemble.service_s"):
            series = rep["histograms"].get(name)
            if not series:
                failures.append(
                    f"slo probe: histogram {name!r} missing after the "
                    "deadline-mixed round"
                )
                continue
            for label, h in series.items():
                p50, p95, p99 = (slo.quantile(h, q)
                                 for q in (0.5, 0.95, 0.99))
                if p50 is None or not (p50 <= p95 <= p99):
                    failures.append(
                        f"slo probe: {name}{{{label}}} quantiles out of "
                        f"order: p50={p50} p95={p95} p99={p99}"
                    )
        missed = int(sum(
            rep["counters"].get("ensemble.deadline_miss", {}).values()
        )) - before_miss
        if missed != expect_missed:
            failures.append(
                f"slo probe: {missed} deadline misses counted, expected "
                f"exactly {expect_missed} (past-deadline submissions)"
            )
        span_names = {s["name"] for s in obs.timeline.spans()}
        for wanted in ("request.queued", "request.step", "request.e2e"):
            if wanted not in span_names:
                failures.append(
                    f"slo probe: lifecycle span {wanted!r} missing from "
                    "the timeline after the serving round"
                )

        # forced escalation -> exactly one postmortem for the incident
        ladder = EscalationLadder()
        for _ in range(3):
            ladder.escalate("slo-probe-stall")
        dumps = sorted(
            p for p in os.listdir(td)
            if p.startswith("flightrec_") and p.endswith(".json")
        )
        if len(dumps) != 1:
            failures.append(
                f"slo probe: forced escalation left {len(dumps)} "
                f"flight-recorder dumps ({dumps}), wanted exactly one "
                "per incident"
            )
        for p in dumps:
            full = os.path.join(td, p)
            failures += [f"flightrec {p}: {f}"
                         for f in validate_flightrec(full)]
            with open(full) as f:
                rec = json.load(f)
            named = any(
                str(ev.get("kind", "")).startswith("request.")
                for ev in rec.get("events", [])
            ) or any(
                str(sp.get("name", "")).startswith("request.")
                for sp in rec.get("spans", [])
            )
            if not named:
                failures.append(
                    f"slo probe: postmortem {p} names no request "
                    "activity from the serving round"
                )
    except Exception as e:  # noqa: BLE001 — probe reports, not dies
        failures.append(f"slo probe failed: {e!r}")
    finally:
        if prev_dir is not None:
            flight_recorder.arm(prev_dir)
        else:
            flight_recorder.disarm()
        import shutil

        shutil.rmtree(td, ignore_errors=True)
    return failures


def _fleet_probe() -> list:
    """Fleet gateway round (ISSUE 19).

    Launches TWO real worker subprocesses on 4-device mesh slices
    behind an in-process :class:`~dccrg_tpu.serve.Gateway` (in-process
    so the gateway counters land in THIS registry, where the gate's
    required-counter check reads them), submits a small GoL fleet, and
    forces the failure path end to end: one worker is SIGKILLed after
    it reports ``started``, its in-flight scenarios must redispatch to
    the survivor and every accepted scenario must retire EXACTLY once
    — with one redispatched member byte-compared against uninterrupted
    solo stepping.  The queue bound is pinned low enough that one
    overflow submission must be rejected (``gateway.rejected``), the
    worker loss must leave exactly ONE schema-valid flight-recorder
    dump naming the lost worker, and a journal reopen must replay the
    retired set (``gateway.journal_replays``).  Returns failure
    strings."""
    import shutil

    import numpy as np

    from dccrg_tpu import obs
    from dccrg_tpu.obs import flight_recorder, validate_flightrec
    from dccrg_tpu.serve import (
        Ensemble,
        Gateway,
        SubmissionJournal,
        WorkerHandle,
    )
    from dccrg_tpu.serve.worker import build_scenario

    failures: list = []

    def total(name: str) -> int:
        rep = obs.metrics.report()
        return int(sum(rep["counters"].get(name, {}).values()))

    watched = ("gateway.accepted", "gateway.rejected",
               "gateway.redispatched", "gateway.worker_lost",
               "gateway.retired", "gateway.journal_replays")
    before = {n: total(n) for n in watched}
    prev_dir = flight_recorder.armed_dir
    td = tempfile.mkdtemp(prefix="dccrg_fleet_probe_")
    saved_env = {k: os.environ.get(k)
                 for k in ("DCCRG_GATEWAY_QUEUE_MAX",
                           "DCCRG_GATEWAY_STALL_S",
                           "JAX_COMPILATION_CACHE_DIR")}
    gw = None
    try:
        fr_dir = os.path.join(td, "flightrec")
        os.makedirs(fr_dir)
        flight_recorder.arm(fr_dir, autodump=False)
        # worker cold start (jax import + first compile) exceeds the
        # 10 s default stall budget; the kill below is the ONLY loss
        # this probe scripts, so spurious stall escalations must not
        # race it
        os.environ["DCCRG_GATEWAY_STALL_S"] = "120"
        os.environ["DCCRG_GATEWAY_QUEUE_MAX"] = "4"
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(td, "cache")
        workers = [WorkerHandle(w, os.path.join(td, w), n_devices=4)
                   for w in ("w0", "w1")]
        for w in workers:
            w.start()
        gw = Gateway(os.path.join(td, "journal.jsonl"), workers)
        specs = [{"sid": f"fp{i}", "model": "gol", "n": 8, "seed": i,
                  "steps": 24, "tenant": "fleet"} for i in range(4)]
        for s in specs:
            ok, why = gw.submit(dict(s))
            if not ok:
                failures.append(
                    f"fleet probe: {s['sid']} rejected ({why})")
        ok, why = gw.submit({"sid": "fp-overflow", "model": "gol",
                             "steps": 1, "tenant": "fleet"})
        if ok or why != "queue-full":
            failures.append(
                "fleet probe: overflow submission past the pinned "
                f"queue bound was not rejected (got {(ok, why)!r})")
        gw.tick(restart_lost=False)
        victim = "w0" if gw.journal.in_flight("w0") else "w1"
        survivor = "w1" if victim == "w0" else "w0"
        victim_sids = set(gw.journal.in_flight(victim))
        if not victim_sids:
            failures.append(
                "fleet probe: no in-flight work assigned to the victim")
        # wait until the victim reports 'started' (it is genuinely
        # stepping, not just assigned), then SIGKILL it mid-flight
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            gw.tick(restart_lost=False)
            if any(gw.journal.accepted[s].get("sig")
                   for s in victim_sids):
                break
            time.sleep(0.2)
        else:
            failures.append(
                "fleet probe: victim never reported 'started' in 180s")
        victim_sids = set(gw.journal.in_flight(victim))
        gw.workers[victim].kill()
        if not gw.run_until_drained(timeout_s=300.0, restart_lost=False):
            failures.append(
                "fleet probe: fleet failed to drain within 300s after "
                "the forced worker kill")
        # exact retire counts: every accepted scenario exactly once
        accepted = set(gw.journal.accepted)
        if set(gw.journal.retired) != accepted:
            failures.append(
                f"fleet probe: retired {sorted(gw.journal.retired)} != "
                f"accepted {sorted(accepted)}")
        d_retired = total("gateway.retired") - before["gateway.retired"]
        if d_retired != len(specs):
            failures.append(
                f"fleet probe: {d_retired} retirements counted, wanted "
                f"exactly {len(specs)} (at-least-once stepping must "
                "stay exactly-once retirement)")
        if total("gateway.worker_lost") - before["gateway.worker_lost"] \
                != 1:
            failures.append(
                "fleet probe: the one forced kill did not count as "
                "exactly one gateway.worker_lost")
        d_re = (total("gateway.redispatched")
                - before["gateway.redispatched"])
        if d_re != len(victim_sids):
            failures.append(
                f"fleet probe: {d_re} redispatches counted, wanted "
                f"{len(victim_sids)} (the victim's in-flight set)")
        if total("gateway.accepted") - before["gateway.accepted"] \
                != len(specs):
            failures.append(
                "fleet probe: accepted count does not match the "
                "submitted fleet")
        # bit-identity: one redispatched member vs uninterrupted solo
        if victim_sids and not failures:
            sid = sorted(victim_sids)[0]
            res = os.path.join(gw.workers[survivor].workdir,
                               f"result_{sid}.npz")
            spec = next(s for s in specs if s["sid"] == sid)
            bundle = build_scenario(spec, n_devices=4)
            ens = Ensemble()
            t = ens.submit(bundle["model"], bundle["state"],
                           steps=int(spec["steps"]), dt=bundle["dt"])
            ens.run()
            want = np.sort(np.asarray(
                bundle["model"].alive_cells(t.result)))
            try:
                with np.load(res) as z:
                    got = np.asarray(z["alive"])
                if not np.array_equal(want, got):
                    failures.append(
                        f"fleet probe: redispatched member {sid} is not "
                        "bit-identical to uninterrupted solo stepping")
            except OSError as e:
                failures.append(
                    f"fleet probe: result park for {sid} unreadable: {e}")
        # one postmortem per incident, naming the lost worker
        dumps = sorted(p for p in os.listdir(fr_dir)
                       if p.startswith("flightrec_")
                       and p.endswith(".json"))
        if len(dumps) != 1:
            failures.append(
                f"fleet probe: worker loss left {len(dumps)} "
                f"flight-recorder dumps ({dumps}), wanted exactly one")
        for p in dumps:
            full = os.path.join(fr_dir, p)
            failures += [f"fleet flightrec {p}: {f}"
                         for f in validate_flightrec(full)]
            with open(full) as f:
                rec = json.load(f)
            named = any(ev.get("kind") == "worker.lost"
                        and ev.get("worker") == victim
                        for ev in rec.get("events", []))
            if not named:
                failures.append(
                    f"fleet probe: postmortem {p} does not name the "
                    f"lost worker {victim}")
        # crash durability: a journal reopen replays the retired set
        j2 = SubmissionJournal(gw.journal.path)
        if set(j2.retired) != accepted:
            failures.append(
                "fleet probe: journal reopen lost the retired set")
        j2.close()
        if (total("gateway.journal_replays")
                - before["gateway.journal_replays"]) < 1:
            failures.append(
                "fleet probe: journal reopen did not count a replay")
    except Exception as e:  # noqa: BLE001 — probe reports, not dies
        failures.append(f"fleet probe failed: {e!r}")
    finally:
        if gw is not None:
            gw.close()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if prev_dir is not None:
            flight_recorder.arm(prev_dir)
        else:
            flight_recorder.disarm()
        shutil.rmtree(td, ignore_errors=True)
    return failures


def _cost_probe() -> list:
    """Cost & capacity round (ISSUE 17).

    Drives a mixed-tenant ensemble round with the cost model armed and
    requires the predictive plane to materialize: every stepped
    compiled-body key must have samples in BOTH the process model and
    the exported ``cost.step_s`` series (the dual store cross-process
    merges depend on), ``predict`` must answer at the exact level for a
    stepped key and walk the fallback chain to ``global`` for a novel
    model kind, and the chargeback conservation invariant must hold
    (per-tenant ``ensemble.device_s`` sums to the recorded
    ``ensemble.device_s_total`` wall×mesh total).  Then the adversarial
    calibration round: a two-tenant burst into a width-capped cohort so
    requests queue, comparing the ``cost.predicted_queue_wait_s``
    gauges read at submit time against the measured per-tenant
    queue-wait p95 — they must agree within one octave bucket
    (``cost.CALIBRATION_BUCKET``, the predictor's documented
    calibration resolution).  No deadlines are used, so the
    ``ensemble.deadline_miss`` count stays exactly the SLO probe's
    (the telemetry_diff gate pins it).  The ≤5% overhead budget is
    re-passed with the model ON by construction: ``_overhead_probe``
    runs in this same process with the default (armed) cost env, which
    this probe asserts.  Returns failure strings."""
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
    from dccrg_tpu.models import Advection
    from dccrg_tpu.obs import cost, slo
    from dccrg_tpu.serve import Ensemble

    failures: list = []
    try:
        if not cost.enabled():
            return ["cost probe: DCCRG_COST_MODEL is off — the probe "
                    "(and the overhead budget) must run with the model "
                    "armed"]
        # The probe serves the paper's advection model on its own tiny
        # grid (NOT the gol the other ensemble probes drive): the
        # ceiling-gated per-model gauges are latest-wins (hbm) and
        # process-cumulative (exchanges_per_step), so this probe's
        # legacy hood-0 k=4 cohorts would otherwise overwrite/dilute
        # the canonical gol series the wide-halo and slo probes leave
        # behind.  Under its own ``model=advection*`` labels the cost
        # rounds get their own gated baseline instead.
        n = 4
        g = (
            Grid()
            .set_initial_length((n, n, n))
            .set_neighborhood_length(0)
            .set_periodic(True, True, True)
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(1.0 / n,) * 3,
            )
            .initialize(mesh=make_mesh())
        )
        g.stop_refining()
        adv = Advection(g, dtype=np.float32, allow_dense=False)
        dt = np.float32(0.4 * adv.max_time_step(adv.initialize_state()))
        mk = adv.initialize_state

        # (1) mixed-tenant round: every stepped key leaves samples
        ens = Ensemble(steps_per_dispatch=4)
        for i in range(4):
            ens.submit(adv, mk(), steps=8, dt=dt, tenant=f"ct{i % 2}")
        ens.run()
        rep = obs.metrics.report()
        series = rep["histograms"].get(cost.COST_HISTOGRAM) or {}
        if not series:
            failures.append(
                "cost probe: no cost.step_s series after the "
                "mixed-tenant round")
        local = cost.model.series()
        for label, h in series.items():
            mine = local.get(label)
            if mine is None or mine["count"] < h["count"]:
                failures.append(
                    f"cost probe: model/registry divergence at "
                    f"{label!r} — the dual store cross-process merges "
                    "depend on is out of sync")
        for label in series:
            kv = cost.parse_label(label)
            est = cost.model.predict(kv["model"], sig=kv["sig"],
                                     k=kv["k"], g=kv["g"], w=kv["w"])
            if est is None or est.level != "exact" or est.n < 1:
                failures.append(
                    f"cost probe: predict({label!r}) did not answer at "
                    f"the exact level: {est}")
        novel = cost.model.predict("no-such-model-kind")
        if novel is None or novel.level != "global":
            failures.append(
                "cost probe: fallback chain broken — a novel model "
                f"kind must answer at the global level, got {novel}")

        # (2) chargeback conservation over everything recorded so far
        cons = cost.conservation(rep)
        if not cons["ok"]:
            failures.append(
                f"cost probe: chargeback conservation violated — "
                f"attributed {cons['attributed']:.6f}s vs wall×mesh "
                f"total {cons['total']:.6f}s (ratio {cons['ratio']})")
        ledger = cost.chargeback(rep)
        if not any(t.startswith("ct") for t in ledger):
            failures.append(
                f"cost probe: mixed-tenant round missing from the "
                f"chargeback ledger: {sorted(ledger)}")

        # (3) adversarial calibration: two-tenant burst, width-capped
        # cohort (16 pending into width 4, so most requests queue),
        # prediction at submit time vs measured wait p95
        burst = Ensemble(steps_per_dispatch=4, max_width=4)
        for _ in range(4):
            burst.submit(adv, mk(), steps=8, dt=dt, tenant="cwarm")
        burst.run()                  # compiles the (W=4, k=4) body
        cost.tracker.reset()         # drop compile-inflated timings
        for _ in range(4):
            burst.submit(adv, mk(), steps=8, dt=dt, tenant="cwarm")
        burst.run()                  # clean wave trains the rate window
        for i in range(16):
            burst.submit(adv, mk(), steps=8, dt=dt,
                         tenant=f"cburst{i % 2}")
        predicted = {
            cost.parse_label(label).get("tenant"): float(v)
            for label, v in (obs.metrics.report()["gauges"]
                             .get("cost.predicted_queue_wait_s") or {})
            .items()
        }
        burst.run()
        rep = obs.metrics.report()
        waits = rep["histograms"].get("ensemble.queue_wait_s") or {}
        for tenant in ("cburst0", "cburst1"):
            pred = predicted.get(tenant)
            if not pred or pred <= 0:
                failures.append(
                    f"cost probe: no predicted queue-wait gauge for "
                    f"burst tenant {tenant!r} at submit time")
                continue
            h = waits.get(f"tenant={tenant}")
            measured = slo.quantile(h, 0.95) if h else None
            if not measured:
                failures.append(
                    f"cost probe: no measured queue-wait for burst "
                    f"tenant {tenant!r}")
                continue
            ratio = pred / measured
            b = cost.CALIBRATION_BUCKET
            if not (1.0 / b <= ratio <= b):
                failures.append(
                    f"cost probe: predicted queue-wait off by more "
                    f"than one calibration bucket for {tenant!r}: "
                    f"predicted {pred:.4f}s vs measured p95 "
                    f"{measured:.4f}s (ratio {ratio:.2f}, "
                    f"envelope [{1.0 / b:.2f}, {b:.2f}])")
    except Exception as e:  # noqa: BLE001 — probe reports, not dies
        failures.append(f"cost probe failed: {e!r}")
    return failures


#: the live-probe stream writer: file-loads the registry (stdlib-only
#: by contract, so the subprocess never pays a jax import), records a
#: DETERMINISTIC sample schedule into the SLO series at the SLO bucket
#: resolution, and hand-writes the stream lines — writer 1 additionally
#: injects a 2-line seq gap and ends on a torn (newline-less) final
#: line, the anomalies the tailer must count without dropping data
_LIVE_WRITER_SRC = r"""
import importlib.util, json, sys, time
reg_path, out_path, wid = sys.argv[1], sys.argv[2], int(sys.argv[3])
spec = importlib.util.spec_from_file_location("dccrg_live_reg", reg_path)
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
assert "jax" not in sys.modules, "registry file-load imported jax"
reg = mod.MetricsRegistry(enabled=True)
reg.set_histogram_resolution("ensemble.e2e_s", 8)
tenant = "t%d" % wid
seq = 0
f = open(out_path, "w")
def snap():
    global seq
    rec = {"seq": seq, "ts": time.time(), **reg.report()}
    f.write(json.dumps(rec, default=float) + "\n")
    f.flush()
    seq += 1
for j in range(30):
    v = 0.001 * (1 + ((7 * j + 3 * wid) % 40))
    reg.observe("ensemble.e2e_s", v, tenant=tenant)
    reg.inc("ensemble.steps_served", 1, tenant=tenant)
    if j % 5 == 0:
        reg.inc("ensemble.deadline_miss", 1, tenant=tenant)
    if j % 3 == 0:
        snap()
    time.sleep(0.005)
if wid == 1:
    seq += 2  # injected seq gap: two line numbers never written
snap()
if wid == 1:
    f.write('{"seq": %d, "ts"' % seq)  # torn final line: cut mid-write
    f.flush()
f.close()
"""


def _live_probe(g, adv, state, dt, steps: int, reps: int = 11,
                threshold: float = 1.05,
                skip_overhead: bool = False) -> list:
    """Live-telemetry round (ISSUE 16).

    Two subprocess writers stream deterministic registry snapshots into
    a scratch directory (one injects a seq gap and a torn final line)
    while the aggregator tails them; then the probe requires:

    * windowed counts EXACT: the full-window fleet counters equal the
      sum of both writers' final cumulative totals — tailing lost
      nothing to the torn tail or the gap;
    * the live windowed p99 equals the post-hoc pooled
      ``obs/slo.py`` quantile on the same files to within one bucket
      (the acceptance criterion: live == post-hoc on pooled exports);
    * seq gaps and torn tails are COUNTED (tailer and
      ``validate_stream`` agree on the tallies);
    * a forced deadline-miss burst fires its alert rule EXACTLY once
      (no flap across repeated polls) and leaves exactly one
      schema-valid flight-recorder dump naming the rule;
    * the <=5% overhead budget re-passes with a live tailer polling the
      probe's own stream in the background (skipped with
      ``--skip-overhead``)."""
    import subprocess
    import threading

    from dccrg_tpu import obs
    from dccrg_tpu.obs import alerts as alerts_mod
    from dccrg_tpu.obs import flight_recorder, live, slo, validate_flightrec

    failures: list = []
    reg_path = str(ROOT / "dccrg_tpu" / "obs" / "registry.py")
    prev_dir = flight_recorder.armed_dir
    td = tempfile.mkdtemp(prefix="dccrg_live_probe_")
    try:
        paths = [os.path.join(td, f"writer{i}.stream.jsonl")
                 for i in (0, 1)]
        procs = [
            subprocess.Popen([sys.executable, "-c", _LIVE_WRITER_SRC,
                              reg_path, paths[i], str(i)])
            for i in (0, 1)
        ]
        agg = live.FleetAggregator(td, window_s=3600.0)
        while any(p.poll() is None for p in procs):
            agg.poll()
            time.sleep(0.02)
        for i, p in enumerate(procs):
            if p.returncode != 0:
                failures.append(
                    f"live probe: writer {i} exited {p.returncode}")
        agg.poll()  # pick up the final lines (and the torn fragment)
        view = agg.view()

        # ---- exact windowed counts vs the writers' cumulative truth
        served = view.counter("ensemble.steps_served")
        missed = view.counter("ensemble.deadline_miss")
        e2e = view.histogram("ensemble.e2e_s")
        if served != 60:
            failures.append(
                f"live probe: windowed ensemble.steps_served {served} "
                "!= 60 (2 writers x 30) — the tailer dropped lines")
        if missed != 12:
            failures.append(
                f"live probe: windowed ensemble.deadline_miss {missed} "
                "!= 12 (2 writers x 6)")
        if int(e2e.get("count") or 0) != 60:
            failures.append(
                f"live probe: windowed e2e histogram count "
                f"{e2e.get('count')} != 60")

        # ---- live windowed p99 == post-hoc pooled within one bucket
        pooled_reports = [slo.load_report(p) for p in paths]
        pooled = slo.merge_series(pooled_reports, "ensemble.e2e_s")
        pooled_all = slo.merge(*pooled.values()) if pooled else {}
        for q in (0.5, 0.95, 0.99):
            live_q = view.quantile("ensemble.e2e_s", q)
            post_q = slo.quantile(pooled_all, q)
            if live_q is None or post_q is None:
                failures.append(
                    f"live probe: q={q} unavailable "
                    f"(live={live_q}, pooled={post_q})")
                continue
            bucket = 2.0 ** (1.0 / slo.SLO_RESOLUTION)
            if not (post_q / bucket <= live_q <= post_q * bucket + 1e-12):
                failures.append(
                    f"live probe: windowed p{round(q * 100)} {live_q} "
                    f"not within one bucket of pooled {post_q}")

        # ---- anomaly counting: tailer and validate_stream agree
        if view.health["seq_gaps"] != 2:
            failures.append(
                f"live probe: tailer counted {view.health['seq_gaps']} "
                "seq gaps, expected exactly 2 (injected)")
        if view.health["torn_tails"] < 1:
            failures.append(
                "live probe: the torn final line was never counted")
        counts: dict = {}
        vs_failures = validate_stream(paths[1], counts)
        failures += [f"live probe writer1 stream: {f}"
                     for f in vs_failures]
        if counts.get("seq_gaps") != 2 or counts.get("torn_tail") != 1:
            failures.append(
                f"live probe: validate_stream counted {counts}, "
                "expected seq_gaps=2 torn_tail=1")

        # ---- forced deadline-miss burst: one fire, no flap, one dump
        flight_recorder.arm(td, autodump=False)
        rule = alerts_mod.AlertRule(
            "burst-miss-rate", "ensemble.deadline_miss",
            source="miss_rate", kind="ceiling",
            threshold=0.01, clear=0.005, for_s=0.0)
        engine = alerts_mod.AlertEngine(
            [rule], registry=obs.metrics, flight_recorder=flight_recorder)
        for _ in range(4):  # the burst persists: must not flap
            engine.poll(view)
        st = engine.state("burst-miss-rate")
        if st["fires"] != 1 or st["clears"] != 0 \
                or st["status"] != "firing":
            failures.append(
                f"live probe: alert fired {st['fires']}x cleared "
                f"{st['clears']}x status={st['status']} — wanted "
                "exactly one fire, still firing (no flap)")
        dumps = sorted(
            p for p in os.listdir(td)
            if p.startswith("flightrec_") and p.endswith(".json"))
        if len(dumps) != 1:
            failures.append(
                f"live probe: alert firing left {len(dumps)} dumps "
                f"({dumps}), wanted exactly one per incident")
        for p in dumps:
            full = os.path.join(td, p)
            failures += [f"live probe flightrec {p}: {f}"
                         for f in validate_flightrec(full)]
            with open(full) as fh:
                rec = json.load(fh)
            named = "burst-miss-rate" in str(rec.get("reason", "")) or any(
                ev.get("rule") == "burst-miss-rate"
                for ev in rec.get("events", [])
                if isinstance(ev, dict))
            if not named:
                failures.append(
                    f"live probe: postmortem {p} does not name the "
                    "firing rule")

        # ---- overhead budget re-passed with a live tailer running
        if not skip_overhead:
            stream_path = os.path.join(td, "probe.stream.jsonl")
            s = obs.TelemetryStream(stream_path, period=0.05,
                                    truncate=True)
            s.start()
            tail_agg = live.FleetAggregator([stream_path],
                                            window_s=60.0)
            stop_evt = threading.Event()

            def _tail_loop():
                while not stop_evt.is_set():
                    tail_agg.poll()
                    stop_evt.wait(0.05)

            t = threading.Thread(target=_tail_loop, daemon=True)
            t.start()
            try:
                over = _overhead_probe(g, adv, state, dt, steps,
                                       reps=reps, threshold=threshold)
                failures += [f"with live tailer: {f}" for f in over]
            finally:
                stop_evt.set()
                t.join(timeout=5.0)
                s.stop(final=False)
    except Exception as e:  # noqa: BLE001 — probe reports, not dies
        failures.append(f"live probe failed: {e!r}")
    finally:
        if prev_dir is not None:
            flight_recorder.arm(prev_dir)
        else:
            flight_recorder.disarm()
        import shutil

        shutil.rmtree(td, ignore_errors=True)
    return failures


def run_check(out_path: str, steps: int = 20, skip_overhead: bool = False,
              reps: int = 11, threshold: float = 1.05,
              artifact_dir: str | None = None) -> list:
    """Run the workload + checks; returns a list of failure strings
    (empty = pass) and writes ``telemetry.json`` to ``out_path`` (side
    artifacts — stream/trace — via :func:`artifact_path`)."""
    _ensure_env()
    import numpy as np

    from dccrg_tpu import obs

    failures: list = []
    obs.metrics.reset()
    obs.enable()
    obs.timeline.clear()
    obs.enable_timeline()

    g, adv, state, dt = build_workload()
    state = drive(g, adv, state, dt, steps)

    # checkpoint write + read-back round (the checkpoint.* phases)
    spec = {"density": ((), np.float32)}
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "telemetry_probe.dc")
        g.save_grid_data(state, ckpt, spec)
        from dccrg_tpu.grid import Grid

        g2, st2, _hdr = Grid.load_grid_data(ckpt, spec)
        same = np.allclose(
            np.asarray(g.get_cell_data(state, "density", g.get_cells())),
            np.asarray(g2.get_cell_data(st2, "density", g.get_cells())),
        )
        if not same:
            failures.append("checkpoint round-trip altered the payload")

    failures += _resilience_probe(g, state)
    failures += _churn_probe(g, dt)
    failures += _halo_backend_probe()
    failures += _ensemble_probe()
    failures += _wide_halo_probe()
    failures += _slo_probe()

    if not skip_overhead:
        failures += _overhead_probe(g, adv, state, dt, steps,
                                    reps=reps, threshold=threshold)
    failures += _live_probe(g, adv, state, dt, steps,
                            reps=reps, threshold=threshold,
                            skip_overhead=skip_overhead)
    # after the timed overhead reps: the cost probe's burst ensembles
    # allocate enough that their GC debt would land inside the 5%
    # budget's timed halves
    # (the budget is still measured with the cost model armed —
    # DCCRG_COST_MODEL defaults on, asserted inside the probe)
    failures += _cost_probe()
    failures += _elastic_probe(g, state)
    failures += _fleet_probe()

    report = g.report()
    for phase in REQUIRED_PHASES:
        rec = report["phases"].get(phase)
        if not rec or rec["count"] < 1:
            failures.append(f"instrumented phase missing from report: "
                            f"{phase!r}")
    for counter in REQUIRED_NONZERO_COUNTERS:
        series = report["counters"].get(counter, {})
        if not any(v > 0 for v in series.values()):
            failures.append(f"counter {counter!r} recorded no value")
    for hist in REQUIRED_HISTOGRAMS:
        series = report["histograms"].get(hist, {})
        if not any(h.get("count", 0) > 0 for h in series.values()):
            failures.append(f"histogram {hist!r} recorded no samples — "
                            "the SLO plane lost its distribution")

    rep = obs.export_json(out_path, extra={
        "workload": f"advection 8^3 refined-ball, {steps} steps, "
                    f"{g.n_devices} devices",
        "n_cells": int(len(g.get_cells())),
    })
    try:
        with open(out_path) as f:
            loaded = json.load(f)
        if loaded["phases"].keys() != rep["phases"].keys():
            failures.append("telemetry.json phase set differs from report")
    except (OSError, ValueError, KeyError) as e:
        failures.append(f"telemetry.json unreadable: {e}")

    # streaming exporter: a few explicit snapshots (no timer sleeps —
    # the probe must stay fast/deterministic) driven through real work
    # between ticks, then schema-validated like any soak/bench stream
    stream_path = artifact_path(out_path, ".stream.jsonl", artifact_dir)
    s = obs.TelemetryStream(stream_path, period=3600.0, truncate=True,
                            extra={"workload": "check_telemetry probe"})
    s.write_snapshot(checkpoint="pre")
    state = drive(g, adv, state, dt, 2)
    s.write_snapshot(checkpoint="mid")
    s.stop(final=True)
    failures += [f"stream: {f}" for f in validate_stream(stream_path)]

    # event timeline: the probe's spans as a Chrome trace, validated for
    # matched begin/end pairs and monotonic in-thread timestamps
    trace_path = artifact_path(out_path, ".trace.json", artifact_dir)
    if not obs.timeline.enabled or len(obs.timeline) == 0:
        failures.append("event timeline recorded no spans during probe")
    obs.export_chrome_trace(trace_path)
    failures += [f"trace: {f}" for f in validate_chrome_trace(trace_path)]

    return failures


def _overhead_probe(g, adv, state, dt, steps: int, reps: int = 11,
                    threshold: float = 1.05) -> list:
    """Enabled-vs-disabled step-loop cost.  The loop is dominated by
    collective rendezvous on an oversubscribed host, so single
    measurements jitter by several percent — alternate the mode order
    each rep (cancels warm-cache ordering bias), collect garbage first
    (a stray GC pause inside one rep skews its half), and compare
    medians.  The true enabled/disabled ratio sits a couple percent
    under the budget (measured ~1.02-1.04x over 25 reps), so a single
    median can still cross the line on a noisy host — a failed
    measurement is confirmed by ONE re-measure, and only failing both
    fails the gate (a real >5% regression fails every measurement; a
    scheduler stall fails one)."""
    import gc
    import statistics

    from dccrg_tpu import obs

    def measure() -> tuple:
        times: dict = {True: [], False: []}
        gc.collect()
        for i in range(reps):
            order = (True, False) if i % 2 == 0 else (False, True)
            for enabled in order:
                obs.metrics.enabled = enabled
                t0 = time.perf_counter()
                drive(g, adv, state, dt, steps)
                times[enabled].append(time.perf_counter() - t0)
        obs.enable()
        return (statistics.median(times[True]),
                statistics.median(times[False]))

    drive(g, adv, state, dt, 2)  # warm every compile
    on, off = measure()
    if on > off * threshold:
        on, off = measure()   # confirm before failing
    if on > off * threshold:
        return [
            f"telemetry overhead {on / off:.3f}x exceeds "
            f"{threshold:.2f}x (enabled median {on:.4f}s vs "
            f"disabled {off:.4f}s over {reps} reps, confirmed twice)"
        ]
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=str(ROOT / "telemetry.json"),
                    help="where to write telemetry.json")
    ap.add_argument("--artifact-dir", default=None,
                    help="where the stream/trace side "
                         "artifacts land (default: next to --out, or "
                         "tools/ when --out is at the repo root — the "
                         "root stays free of bench byproducts)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reps", type=int, default=11,
                    help="overhead-probe repetitions per mode (one rep "
                         "is a ~20-step loop, so reps are cheap; the "
                         "median over more reps keeps the 5%% gate from "
                         "flaking on scheduler jitter)")
    ap.add_argument("--threshold", type=float, default=1.05,
                    help="max allowed enabled/disabled step-loop ratio")
    ap.add_argument("--skip-overhead", action="store_true",
                    help="only check phase/counter completeness + export")
    ap.add_argument("--validate-stream", default=None, metavar="FILE",
                    help="only schema-validate an existing telemetry "
                         "JSONL stream and exit")
    ap.add_argument("--validate-trace", default=None, metavar="FILE",
                    help="only schema-validate an existing Chrome "
                         "trace-event export and exit")
    ap.add_argument("--validate-merged-trace", default=None, metavar="FILE",
                    help="only schema-validate an existing fleet "
                         "trace (tools/trace_report.py --fleet) and exit")
    args = ap.parse_args(argv)
    if args.validate_stream or args.validate_trace or \
            args.validate_merged_trace:
        failures = []
        if args.validate_stream:
            counts: dict = {}
            failures += [f"stream: {f}"
                         for f in validate_stream(args.validate_stream,
                                                  counts)]
            print(f"stream: {counts['lines']} lines, "
                  f"{counts['seq_gaps']} seq gaps, "
                  f"{counts['torn_tail']} torn tail, "
                  f"{counts['bad_lines']} bad lines", file=sys.stderr)
        if args.validate_trace:
            failures += [f"trace: {f}"
                         for f in validate_chrome_trace(args.validate_trace)]
        if args.validate_merged_trace:
            _ensure_env()
            from dccrg_tpu.obs.events import validate_merged_trace

            failures += [
                f"merged: {f}"
                for f in validate_merged_trace(args.validate_merged_trace)
            ]
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        if not failures:
            print("telemetry stream/trace validation passed")
        return 1 if failures else 0
    failures = run_check(args.out, steps=args.steps,
                         skip_overhead=args.skip_overhead,
                         reps=args.reps, threshold=args.threshold,
                         artifact_dir=args.artifact_dir)
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(f"telemetry check passed; wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
