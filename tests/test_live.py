"""Live fleet telemetry (ISSUE 16): the stream tailer (byte-offset
resume, torn-tail re-join, seq-gap counting), windowed bucket-delta
views over multiple per-process streams, the exact-merge == pooled
property on live data, the Prometheus exposition round-trip, the
alerting plane's for_s/hysteresis no-flap state machine with its
one-dump-per-incident flight-recorder discipline, and the supervisor's
alert signal source."""
import json
import math
import os
import subprocess
import sys
import time

import pytest

from dccrg_tpu.obs import alerts, live, slo
from dccrg_tpu.obs import stream as obs_stream
from dccrg_tpu.obs.flightrec import FlightRecorder, validate_flightrec
from dccrg_tpu.obs.registry import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _write_lines(path, snaps_and_ts, extra=None):
    """Append ``(snapshot, ts)`` stream lines; a snapshot is either a
    ``report()`` dict captured at observe time or a registry (snapshot
    taken NOW — only sound when every line may share the final state)."""
    with open(path, "a") as f:
        for seq, (snap, ts) in enumerate(snaps_and_ts):
            if not isinstance(snap, dict):
                snap = snap.report()
            rec = {"seq": seq, "ts": ts, **(extra or {}), **snap}
            f.write(json.dumps(rec, default=float) + "\n")


def _slo_registry():
    reg = MetricsRegistry(enabled=True)
    reg.set_histogram_resolution("ensemble.e2e_s", slo.SLO_RESOLUTION)
    return reg


# ------------------------------------------------------------- tailer


def test_tailer_byte_offset_resume(tmp_path):
    """Each poll reads only appended bytes; already-read records are
    never re-delivered."""
    p = tmp_path / "a.stream.jsonl"
    reg = _slo_registry()
    _write_lines(p, [(reg, 1.0), (reg, 2.0)])
    t = live.StreamTailer(str(p))
    first = t.poll()
    assert [r["seq"] for r in first] == [0, 1]
    assert t.poll() == []
    with open(p, "a") as f:
        f.write(json.dumps({"seq": 2, "ts": 3.0, **reg.report()},
                           default=float) + "\n")
    assert [r["seq"] for r in t.poll()] == [2]
    assert t.records_read == 3
    assert t.seq_gaps == 0 and t.torn_tails == 0 and t.bad_lines == 0


def test_tailer_torn_tail_resumes_cleanly(tmp_path):
    """Regression (ISSUE 16 satellite): a line cut mid-write is held
    back, COUNTED, and delivered intact once the remainder lands."""
    p = tmp_path / "a.stream.jsonl"
    reg = _slo_registry()
    full = json.dumps({"seq": 0, "ts": 1.0, **reg.report()},
                      default=float) + "\n"
    cut = len(full) // 2
    with open(p, "w") as f:
        f.write(full[:cut])  # torn: the writer died mid-line ... or not
    t = live.StreamTailer(str(p))
    assert t.poll() == []  # fragment withheld, not mis-parsed
    assert t.torn_tails == 1
    with open(p, "a") as f:
        f.write(full[cut:])  # the writer completes the line
    recs = t.poll()
    assert len(recs) == 1 and recs[0]["seq"] == 0
    assert t.bad_lines == 0  # the re-joined line parsed exactly once
    assert t.records_read == 1


def test_tailer_counts_seq_gaps(tmp_path):
    p = tmp_path / "a.stream.jsonl"
    reg = _slo_registry()
    with open(p, "w") as f:
        for seq in (0, 1, 4, 5, 9):  # gaps: 2-3 (2 lines), 6-8 (3)
            f.write(json.dumps({"seq": seq, "ts": float(seq),
                                **reg.report()}, default=float) + "\n")
    t = live.StreamTailer(str(p))
    assert len(t.poll()) == 5
    assert t.seq_gaps == 5


def test_tailer_counts_into_registry(tmp_path):
    p = tmp_path / "a.stream.jsonl"
    reg = _slo_registry()
    with open(p, "w") as f:
        for seq in (0, 3):
            f.write(json.dumps({"seq": seq, "ts": float(seq),
                                **reg.report()}, default=float) + "\n")
        f.write("{not json}\n")
        f.write('{"seq": 4, "ts"')  # torn tail
    counter_reg = MetricsRegistry(enabled=True)
    t = live.StreamTailer(str(p), registry=counter_reg)
    t.poll()
    counters = counter_reg.report()["counters"]
    label = "path=a.stream.jsonl"
    assert counters["stream.seq_gaps"][label] == 2
    assert counters["stream.bad_lines"][label] == 1
    assert counters["stream.torn_tails"][label] == 1


def test_validate_stream_counts_gaps_and_torn_tail(tmp_path):
    """``check_telemetry.validate_stream`` tolerates-but-counts the
    same anomalies the tailer does."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from check_telemetry import validate_stream
    finally:
        sys.path.pop(0)
    p = tmp_path / "a.stream.jsonl"
    reg = _slo_registry()
    with open(p, "w") as f:
        for seq in (0, 1, 5):
            f.write(json.dumps({"seq": seq, "ts": float(seq),
                                **reg.report()}, default=float) + "\n")
        f.write('{"seq": 6, "ts": 6.0, "cut mid-')  # torn final line
    counts: dict = {}
    failures = validate_stream(str(p), counts)
    assert failures == []
    assert counts["lines"] == 3
    assert counts["seq_gaps"] == 3
    assert counts["torn_tail"] == 1


# ---------------------------------------------------- windowed views


def _brute_quantile(samples, q):
    """Sample quantile with the same rank convention slo.quantile uses
    (value at ceil(q*n) in the sorted order)."""
    s = sorted(samples)
    rank = q * len(s)
    idx = max(int(math.ceil(rank)) - 1, 0)
    return s[min(idx, len(s) - 1)]


def test_windowed_quantile_matches_bruteforce(tmp_path):
    """Known-value check: the bucket-delta windowed p50/p95/p99 lands
    within one log-bucket of the brute-force quantile over exactly the
    in-window samples.  Values span one octave so every sub-bucket is
    occupied and the one-bucket bound is tight (sparse buckets would
    legitimately widen the interpolation interval)."""
    p = tmp_path / "a.stream.jsonl"
    reg = _slo_registry()
    rows = []
    samples = []
    t0 = 1000.0
    for j in range(120):
        v = 0.010 * (1.0 + ((j * 37) % 100) / 100.0)  # [0.010, 0.020)
        reg.observe("ensemble.e2e_s", v, tenant="t0")
        samples.append((t0 + j, v))
        rows.append((reg.report(), t0 + j))  # cumulative-at-this-line
    _write_lines(p, rows)

    window = 50.0
    agg = live.FleetAggregator([str(p)], window_s=window)
    now = t0 + 119.5
    agg.poll(now=now)
    view = agg.view(now=now)
    # the window edge snapshot is the newest line with ts <= now-50
    # (ts = t0+69); in-window samples are those observed on later lines
    in_window = [v for ts, v in samples if ts > now - window]
    assert view.histogram("ensemble.e2e_s")["count"] == len(in_window)
    bucket = 2.0 ** (1.0 / slo.SLO_RESOLUTION)
    for q in (0.5, 0.95, 0.99):
        est = view.quantile("ensemble.e2e_s", q)
        true = _brute_quantile(in_window, q)
        assert true / bucket <= est <= true * bucket * (1 + 1e-9), (
            q, est, true)


def test_windowed_counters_and_rates(tmp_path):
    p = tmp_path / "a.stream.jsonl"
    reg = MetricsRegistry(enabled=True)
    rows = []
    for j in range(10):
        reg.inc("ensemble.steps_served", 2, tenant="t0")
        rows.append((reg.report(), 100.0 + j))
    _write_lines(p, rows)
    agg = live.FleetAggregator([str(p)], window_s=4.0)
    agg.poll(now=109.5)
    view = agg.view(now=109.5)
    # edge = line at ts 105 (newest <= 105.5): lines 106..109 in window
    assert view.counter("ensemble.steps_served") == 8
    assert view.rate("ensemble.steps_served") == pytest.approx(2.0)
    # the full cumulative total is still visible
    assert view.counter("ensemble.steps_served", windowed=False) == 20


def test_two_live_streams_merge_equals_pooled(tmp_path):
    """The acceptance criterion: live windowed quantiles over two
    concurrently-written streams match the post-hoc pooled
    ``obs/slo.py`` merge to within one bucket (and counts exactly)."""
    regs = [_slo_registry(), _slo_registry()]
    paths = [tmp_path / f"w{i}.stream.jsonl" for i in (0, 1)]
    pooled_reg = _slo_registry()
    t0 = 500.0
    for i, (reg, p) in enumerate(zip(regs, paths)):
        rows = []
        for j in range(25):
            v = 0.001 * (1.0 + ((j * 7 + i * 3) % 50))
            reg.observe("ensemble.e2e_s", v, tenant=f"t{i}")
            pooled_reg.observe("ensemble.e2e_s", v, tenant=f"t{i}")
            reg.inc("ensemble.steps_served", 1, tenant=f"t{i}")
            if j % 5 == 0:
                reg.inc("ensemble.deadline_miss", 1, tenant=f"t{i}")
            rows.append((reg, t0 + j))
        _write_lines(p, rows)

    agg = live.FleetAggregator([str(q) for q in paths], window_s=3600.0)
    agg.poll(now=t0 + 30)
    view = agg.view(now=t0 + 30)
    assert view.counter("ensemble.steps_served") == 50
    assert view.counter("ensemble.deadline_miss") == 10

    pooled_all = slo.merge(
        *pooled_reg.report()["histograms"]["ensemble.e2e_s"].values())
    live_h = view.histogram("ensemble.e2e_s")
    assert live_h["count"] == pooled_all["count"] == 50
    assert live_h["buckets"] == pooled_all["buckets"]
    for q in (0.5, 0.95, 0.99):
        assert view.quantile("ensemble.e2e_s", q) == pytest.approx(
            slo.quantile(pooled_all, q))
    # per-tenant windowed miss rates carry the slo semantics
    rates = view.miss_rates()
    assert rates["t0"]["completed"] == 25 and rates["t0"]["missed"] == 5
    assert rates["t0"]["rate"] == pytest.approx(0.2)


def test_aggregator_discovers_new_writers(tmp_path):
    reg = _slo_registry()
    a = tmp_path / "a.stream.jsonl"
    _write_lines(a, [(reg, 1.0)])
    agg = live.FleetAggregator(str(tmp_path), window_s=3600.0)
    agg.poll(now=2.0)
    assert agg.view(now=2.0).health["files"] == 1
    b = tmp_path / "b.stream.jsonl"
    _write_lines(b, [(reg, 2.0)])
    agg.poll(now=3.0)
    assert agg.view(now=3.0).health["files"] == 2


# ------------------------------------------------------- exposition


def test_prometheus_exposition_round_trip():
    reg = _slo_registry()
    for v in (0.001, 0.004, 0.032, 0.5):
        reg.observe("ensemble.e2e_s", v, tenant="acme")
    reg.inc("ensemble.steps_served", 7, tenant="acme")
    reg.inc("alerts.fired", 2, rule="queue-depth")
    reg.gauge("ensemble.queue_depth", 3.5)
    rep = reg.report()
    text = live.to_prometheus(rep)
    # exposition shape: TYPE lines, cumulative le buckets, +Inf == count
    assert "# TYPE dccrg_ensemble_e2e_s histogram" in text
    assert 'le="+Inf"' in text
    back = live.parse_prometheus(text)
    assert back["counters"]["ensemble.steps_served"]["tenant=acme"] == 7
    assert back["counters"]["alerts.fired"]["rule=queue-depth"] == 2
    assert back["gauges"]["ensemble.queue_depth"][""] == 3.5
    h = rep["histograms"]["ensemble.e2e_s"]["tenant=acme"]
    b = back["histograms"]["ensemble.e2e_s"]["tenant=acme"]
    assert b["count"] == h["count"]
    assert b["sum"] == pytest.approx(h["sum"])
    assert b["buckets"] == {k: int(n) for k, n in h["buckets"].items()}
    # quantiles survive the round trip bucket-exactly
    for q in (0.5, 0.99):
        assert slo.quantile({**b, "min": h["min"], "max": h["max"]}, q) \
            == pytest.approx(slo.quantile(h, q))


# ------------------------------------------------------------ alerts


class _View:
    """Minimal FleetView protocol stub driving one scripted value."""

    def __init__(self, v):
        self.v = v

    def gauge_values(self, name):
        return {} if self.v is None else {"": self.v}

    def rate(self, name, labels=None):
        return self.v

    def quantile(self, name, q, labels=None):
        return self.v

    def miss_rates(self):
        if self.v is None:
            return {}
        return {"t0": {"rate": self.v, "missed": 1, "completed": 2}}


def _engine(rules):
    return alerts.AlertEngine(rules, registry=False, flight_recorder=False)


def test_alert_oscillation_never_flaps():
    """A series oscillating between the fire and clear thresholds fires
    exactly once and NEVER clears: hysteresis provably prevents flap."""
    rule = alerts.AlertRule("osc", "g", source="gauge", kind="ceiling",
                            threshold=0.5, clear=0.2, for_s=0.0)
    eng = _engine([rule])
    transitions = []
    for i, v in enumerate([0.6, 0.3] * 25):
        transitions += eng.poll(_View(v), now=float(i))
    assert [t["event"] for t in transitions] == ["fired"]
    st = eng.state("osc")
    assert st["fires"] == 1 and st["clears"] == 0
    assert eng.firing() == ["osc"]
    # only a full hysteresis crossing clears — then a new incident may fire
    eng.poll(_View(0.1), now=1000.0)
    assert eng.state("osc")["clears"] == 1
    assert eng.firing() == []
    eng.poll(_View(0.9), now=1001.0)
    assert eng.state("osc")["fires"] == 2


def test_alert_for_s_suppresses_transients():
    rule = alerts.AlertRule("slow", "g", source="gauge", kind="ceiling",
                            threshold=0.5, clear=0.2, for_s=2.5)
    eng = _engine([rule])
    # oscillation faster than for_s: pending always lapses, never fires
    for i, v in enumerate([0.6, 0.3] * 10):
        eng.poll(_View(v), now=float(i))
    assert eng.state("slow")["fires"] == 0
    # sustained breach fires once for_s is exceeded
    fired = []
    for i in range(5):
        fired += eng.poll(_View(0.7), now=100.0 + i)
    assert [t["event"] for t in fired] == ["fired"]


def test_alert_floor_kind_and_no_data_holds_state():
    rule = alerts.AlertRule("low", "overlap.fraction", source="gauge",
                            kind="floor", threshold=0.1, clear=0.15)
    eng = _engine([rule])
    eng.poll(_View(0.05), now=0.0)
    assert eng.firing() == ["low"]
    eng.poll(_View(None), now=1.0)  # no data: state held, no clear
    assert eng.firing() == ["low"]
    eng.poll(_View(0.12), now=2.0)  # above threshold but below clear
    assert eng.firing() == ["low"]
    eng.poll(_View(0.2), now=3.0)
    assert eng.firing() == []


def test_alert_one_dump_per_incident(tmp_path):
    """The ladder discipline on the alert plane: an incident dumps the
    armed flight recorder exactly once however long it persists; a new
    incident after a clear dumps again."""
    fr = FlightRecorder(enabled=True, registry=MetricsRegistry())
    fr.arm(str(tmp_path), autodump=False)
    rule = alerts.AlertRule("burst", "g", source="gauge", kind="ceiling",
                            threshold=0.5, clear=0.2, for_s=0.0)
    eng = alerts.AlertEngine([rule], registry=False, flight_recorder=fr)
    for i in range(5):  # persisting breach: one incident
        eng.poll(_View(0.9), now=float(i))
    dumps = sorted(f for f in os.listdir(tmp_path)
                   if f.startswith("flightrec_") and f.endswith(".json"))
    assert len(dumps) == 1
    full = os.path.join(str(tmp_path), dumps[0])
    assert validate_flightrec(full) == []
    rec = json.load(open(full))
    assert "alert:burst" in rec["reason"]
    assert any(ev.get("kind") == "alert.fired"
               and ev.get("rule") == "burst"
               for ev in rec["events"])
    assert eng.state("burst")["dump"] == full
    # clear, then a second incident -> a second dump
    eng.poll(_View(0.1), now=100.0)
    eng.poll(_View(0.9), now=101.0)
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("flightrec_") and f.endswith(".json")]
    assert len(dumps) == 2


def test_alert_counters_and_default_rules():
    reg = MetricsRegistry(enabled=True)
    rule = alerts.AlertRule("r", "g", source="gauge", kind="ceiling",
                            threshold=0.5, clear=0.2)
    eng = alerts.AlertEngine([rule], registry=reg, flight_recorder=False)
    eng.poll(_View(0.9), now=0.0)
    eng.poll(_View(0.1), now=1.0)
    counters = reg.report()["counters"]
    assert counters["alerts.fired"]["rule=r"] == 1
    assert counters["alerts.cleared"]["rule=r"] == 1
    # the alerts.evaluate phase is recorded (telemetry_diff allows it)
    assert "alerts.evaluate" in reg.report()["phases"]
    names = {r.name for r in alerts.default_rules()}
    assert names == {"deadline-miss-rate", "queue-depth",
                     "halo-exchanges-per-step", "worker-lost"}


def test_load_rules_and_env(tmp_path, monkeypatch):
    p = tmp_path / "rules.json"
    p.write_text(json.dumps({"rules": [
        {"name": "custom", "metric": "ensemble.queue_depth",
         "source": "gauge", "kind": "ceiling", "threshold": 9.0,
         "clear": 4.0, "for_s": 1.5},
    ]}))
    rules = alerts.load_rules(str(p))
    assert len(rules) == 1 and rules[0].name == "custom"
    assert rules[0].clear == 4.0 and rules[0].for_s == 1.5
    monkeypatch.setenv("DCCRG_ALERT_RULES", str(p))
    assert [r.name for r in alerts.rules_from_env()] == ["custom"]
    monkeypatch.setenv("DCCRG_ALERTS", "0")
    assert not alerts.alerts_enabled()
    monkeypatch.delenv("DCCRG_ALERTS")
    assert alerts.alerts_enabled()


def test_supervisor_takes_alert_signal(tmp_path):
    """A live child whose alert rules are firing climbs the ladder even
    while its heartbeat beats; a cleared engine lets it reset."""
    from dccrg_tpu.resilience.supervisor import (
        EscalationLadder,
        HeartbeatMonitor,
        Supervisor,
    )

    hb = tmp_path / "hb.jsonl"
    hb.write_text(json.dumps({"step": 1}) + "\n")
    mon = HeartbeatMonitor(str(hb), stall_after_s=1e6)

    class Engine:
        def __init__(self):
            self.rules = []

        def firing(self):
            return list(self.rules)

    eng = Engine()
    sup = Supervisor(mon, ladder=EscalationLadder(), alerts=eng)
    assert sup.poll(now=0.0)["action"] is None
    eng.rules = ["deadline-miss-rate"]
    out = sup.poll(now=1.0)
    assert out["status"] == "degraded"
    assert out["reason"] == "alert:deadline-miss-rate"
    assert out["action"] == "warn"
    out = sup.poll(now=2.0)
    assert out["action"] == "rescale_down"  # the ladder climbed
    eng.rules = []
    assert sup.poll(now=3.0)["action"] is None  # healthy again: reset
    out = sup.poll(now=4.0)
    eng.rules = ["queue-depth"]
    assert sup.poll(now=5.0)["action"] == "warn"  # back at rung one


# ------------------------------------------- stream flush + attribution


def test_maybe_flush_writes_at_step_boundaries(tmp_path, monkeypatch):
    def our_lines():
        return [ln for ln in p.read_text().splitlines() if ln] \
            if p.exists() else []

    monkeypatch.setenv("DCCRG_STREAM_FLUSH_S", "0.0")
    reg = MetricsRegistry(enabled=True)
    p = tmp_path / "s.stream.jsonl"
    s = obs_stream.TelemetryStream(str(p), period=3600.0, registry=reg)
    s.start()
    try:
        assert obs_stream.maybe_flush() == 0  # knob 0 disables the seam
        assert our_lines() == []
        monkeypatch.setenv("DCCRG_STREAM_FLUSH_S", "0.0001")
        time.sleep(0.002)
        assert obs_stream.maybe_flush() >= 1
        assert len(our_lines()) == 1
        time.sleep(0.002)
        obs_stream.maybe_flush()
        assert len(our_lines()) == 2
    finally:
        s.stop(final=False)
    obs_stream.maybe_flush()  # stopped streams drop out of the seam
    lines = our_lines()
    assert len(lines) == 2
    assert json.loads(lines[1])["seq"] == 1


def test_fleet_top_cli_json(tmp_path):
    """The console runs jax-free on a synthetic stream dir and reports
    the windowed snapshot."""
    reg = _slo_registry()
    rows = []
    now = time.time()
    for j in range(8):
        reg.observe("ensemble.e2e_s", 0.002 * (1 + j % 5), tenant="acme")
        reg.inc("ensemble.steps_served", 1, tenant="acme")
        rows.append((reg, now - 8 + j))
    _write_lines(tmp_path / "a.stream.jsonl", rows)
    out = tmp_path / "snap.json"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_top.py"),
         str(tmp_path), "--json", str(out), "--window", "3600"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    snap = json.loads(out.read_text())
    assert snap["health"]["files"] == 1
    assert snap["latency"][0]["count"] == 8
    assert snap["rates"]["ensemble.steps_served"]["tenant=acme"] > 0


def test_slo_report_live_mode(tmp_path):
    reg = _slo_registry()
    rows = []
    now = time.time()
    for j in range(6):
        reg.observe("ensemble.e2e_s", 0.003, tenant="acme")
        if j % 2 == 0:
            reg.inc("ensemble.deadline_miss", 1, tenant="acme")
        rows.append((reg, now - 6 + j))
    _write_lines(tmp_path / "a.stream.jsonl", rows)
    out = tmp_path / "live.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "slo_report.py"),
         "--live", str(tmp_path), "--window", "3600",
         "--json", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(out.read_text())
    assert rep["window_s"] == 3600.0
    assert rep["latency"][0]["count"] == 6
    assert rep["deadline_miss_rates"]["acme"]["missed"] == 3
    assert "ensemble.e2e_s" in proc.stdout


def test_live_module_loads_without_jax(tmp_path):
    """The stdlib-only contract, end to end: file-loading live.py and
    alerts.py in a fresh interpreter must not pull in jax."""
    code = (
        "import importlib.util, sys\n"
        f"for name in ('live', 'alerts'):\n"
        f"    path = {os.path.join(ROOT, 'dccrg_tpu', 'obs')!r}"
        " + '/' + name + '.py'\n"
        "    spec = importlib.util.spec_from_file_location(name, path)\n"
        "    mod = importlib.util.module_from_spec(spec)\n"
        "    spec.loader.exec_module(mod)\n"
        "assert 'jax' not in sys.modules, 'jax leaked into the loader'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
