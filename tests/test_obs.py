"""Telemetry subsystem tests: registry semantics, zero-overhead disabled
mode, nested/re-entrant phases, JSON export round-trip, the timers
back-compat shim, instrumented-seam coverage, and a ``Grid.report()``
smoke test on a refined game-of-life run (ISSUE 1 satellite).

ISSUE 2 layers: the streaming JSONL exporter, the begin/end event
timeline + Chrome trace export, per-device HBM gauges, fused-kernel
reconciliation counters, and the ``obs.profile_trace`` materialization
gate (previously only exercised manually via TensorBoard/xprof)."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from dccrg_tpu import obs
from dccrg_tpu.obs.registry import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ---------------------------------------------------------------- registry


def test_counter_semantics():
    reg = MetricsRegistry()
    reg.inc("c")
    reg.inc("c", 2)
    reg.inc("c", 5, device=1)
    reg.inc("c", np.int64(3), device=1)
    rep = reg.report()["counters"]["c"]
    assert rep[""] == 3
    assert rep["device=1"] == 8
    assert isinstance(rep["device=1"], int)  # numpy scalars unwrapped
    assert reg.counter_value("c", device=1) == 8
    assert reg.counter_value("never") == 0


def test_inc_many_and_batch():
    reg = MetricsRegistry()
    reg.inc_many([("a", 1), ("b", 2, {"k": "v"}), ("a", 3)])
    reg.inc_batch([(("a", ()), 10), (("b", (("k", "v"),)), 20)])
    rep = reg.report()["counters"]
    assert rep["a"][""] == 14
    assert rep["b"]["k=v"] == 22


def test_gauge_latest_value_wins():
    reg = MetricsRegistry()
    reg.gauge("g", 1.5)
    reg.gauge("g", 2.5)
    reg.gauge("g", 7, hood="default")
    rep = reg.report()["gauges"]["g"]
    assert rep[""] == 2.5
    assert rep["hood=default"] == 7
    assert reg.gauge_value("g") == 2.5
    assert reg.gauge_value("missing", default=-1) == -1


def test_histogram_semantics():
    reg = MetricsRegistry()
    for v in (0.5, 1.0, 3.0, 3.0, 0.0):
        reg.observe("h", v)
    rep = reg.report()["histograms"]["h"][""]
    assert rep["count"] == 5
    assert rep["sum"] == pytest.approx(7.5)
    assert rep["mean"] == pytest.approx(1.5)
    assert rep["min"] == 0.0
    assert rep["max"] == 3.0
    # power-of-two buckets: 0.5 -> le=0.5, 1.0 -> le=1.0, 3.0 x2 -> le=4.0,
    # 0.0 -> the non-positive bucket "0"
    assert rep["buckets"] == {"0": 1, "0.5": 1, "1.0": 1, "4.0": 2}


def test_disabled_mode_records_no_keys():
    reg = MetricsRegistry(enabled=False)
    reg.inc("c")
    reg.inc_many([("a", 1)])
    reg.inc_batch([(("a", ()), 1)])
    reg.gauge("g", 1)
    reg.observe("h", 1.0)
    reg.phase_add("p", 0.1)
    with reg.phase("p2"):
        pass
    rep = reg.report()
    assert rep == {"phases": {}, "counters": {}, "gauges": {},
                   "histograms": {}}


def test_nested_phase_counts_outer_span_once():
    """The pre-obs PhaseTimers double-counted a nested phase("x") inside
    phase("x"); the registry must count the outermost wall span once."""
    reg = MetricsRegistry()
    with reg.phase("x"):
        time.sleep(0.05)
        with reg.phase("x"):
            time.sleep(0.05)
    rep = reg.report()["phases"]["x"]
    assert rep["count"] == 1
    # double-counting would give >= 0.15 (outer 0.1 + inner 0.05)
    assert 0.09 <= rep["total_s"] < 0.14
    # distinct names still nest freely
    with reg.phase("outer"):
        with reg.phase("inner"):
            pass
    phases = reg.report()["phases"]
    assert phases["outer"]["count"] == 1
    assert phases["inner"]["count"] == 1


def test_registry_thread_safety():
    reg = MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.inc("t")
            with reg.phase("tp"):
                pass

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = reg.report()
    assert rep["counters"]["t"][""] == 8000
    assert rep["phases"]["tp"]["count"] == 8000


def test_export_json_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.inc("halo.bytes_moved", 1024, hood="default")
    reg.gauge("epoch.n_cells", 72)
    reg.observe("lat", 0.25)
    with reg.phase("epoch.build"):
        pass
    out = tmp_path / "telemetry.json"
    written = obs.export_json(str(out), registry=reg,
                              extra={"workload": "unit"})
    loaded = json.loads(out.read_text())
    assert loaded == json.loads(json.dumps(written))
    assert loaded["workload"] == "unit"
    assert loaded["counters"]["halo.bytes_moved"]["hood=default"] == 1024
    assert "epoch.build" in loaded["phases"]


# ------------------------------------------------------------ timers shim


def test_phase_timers_shim_over_obs():
    from dccrg_tpu.utils.timers import PhaseTimers, timers

    # independent instance: old API shape
    pt = PhaseTimers()
    with pt.phase("a"):
        pass
    rep = pt.report()
    assert rep["a"]["count"] == 1
    assert set(rep["a"]) == {"total_s", "count", "mean_s"}
    assert pt.total["a"] >= 0.0
    assert pt.count["a"] == 1
    pt.reset()
    assert pt.report() == {}
    # nested same-name: fixed (no double count)
    with pt.phase("n"):
        time.sleep(0.02)
        with pt.phase("n"):
            time.sleep(0.02)
    assert pt.report()["n"]["count"] == 1
    # the process-wide `timers` is a view over obs.metrics
    assert timers._registry is obs.metrics
    prev = timers.enabled
    try:
        with timers.phase("shim.phase"):
            pass
        assert "shim.phase" in obs.metrics.report()["phases"]
    finally:
        timers.enabled = prev


# ------------------------------------------------- instrumented seams


def _small_grid(max_ref=1, hood=1, length=(8, 8, 1)):
    from dccrg_tpu import Grid, make_mesh

    return (
        Grid()
        .set_initial_length(length)
        .set_maximum_refinement_level(max_ref)
        .set_neighborhood_length(hood)
        .set_load_balancing_method("RCB")
        .initialize(mesh=make_mesh())
    )


def test_halo_exchange_telemetry_counters():
    obs.metrics.reset()
    obs.enable()
    g = _small_grid(max_ref=0)
    spec = {"rho": ((), np.float64)}
    st = g.new_state(spec)
    m = g.telemetry
    assert m.counter_value("halo.cells_moved") == 0
    st = g.update_copies_of_remote_neighbors(st)
    pair_counts = g.epoch.hoods[None].pair_counts
    expected_cells = int(pair_counts.sum())
    assert expected_cells > 0  # 8-device board really exchanges
    assert m.counter_value("halo.cells_moved") == expected_cells
    assert m.counter_value("halo.bytes_moved") == expected_cells * 8
    # per-device counters match the schedule tables, send total == recv
    send = [int(m.counter_value("halo.send_cells", device=d, hood="default"))
            for d in range(g.n_devices)]
    recv = [int(m.counter_value("halo.recv_cells", device=d, hood="default"))
            for d in range(g.n_devices)]
    assert send == [int(v) for v in pair_counts.sum(axis=1)]
    assert recv == [int(v) for v in pair_counts.sum(axis=0)]
    assert sum(send) == sum(recv) == expected_cells
    # wire bytes >= useful bytes (ring padding), phase recorded
    assert (m.counter_value("halo.wire_bytes")
            >= m.counter_value("halo.bytes_moved"))
    assert "halo.exchange" in m.report()["phases"]


def test_halo_split_phase_telemetry():
    obs.metrics.reset()
    obs.enable()
    g = _small_grid(max_ref=0)
    st = g.new_state({"rho": ((), np.float64)})
    handle = g.start_remote_neighbor_copy_updates(st)
    st = g.wait_remote_neighbor_copy_updates(st, handle)
    m = obs.metrics
    assert m.counter_value("halo.exchanges", kind="split",
                           hood="default") == 1
    assert m.report()["phases"]["halo.exchange"]["count"] == 1


def test_disabled_telemetry_records_nothing_on_grid_paths():
    obs.metrics.reset()
    obs.disable()
    try:
        g = _small_grid()
        st = g.new_state({"rho": ((), np.float64)})
        st = g.update_copies_of_remote_neighbors(st)
        g.refine_completely(int(g.get_cells()[0]))
        g.stop_refining()
        g.balance_load()
        rep = obs.metrics.report()
        assert rep == {"phases": {}, "counters": {}, "gauges": {},
                       "histograms": {}}
    finally:
        obs.enable()


def test_grid_report_smoke_refined_game_of_life():
    """Grid.report() on a refined game-of-life run: every structural
    seam the run exercises shows up in one snapshot."""
    from dccrg_tpu.models import GameOfLife

    obs.metrics.reset()
    obs.enable()
    g = _small_grid(max_ref=1, hood=1)
    for cid in g.get_cells()[:4]:
        g.refine_completely(int(cid))
    g.stop_refining()
    g.balance_load()
    gol = GameOfLife(g)
    state = gol.new_state(alive_cells=[12, 13, 14])
    for _ in range(3):
        state = gol.step(state)
    # one explicit host-level ghost refresh ticks the halo seam even
    # when the model's own step fuses its exchange into jit
    gol_state_field = next(iter(state))
    g.update_copies_of_remote_neighbors({gol_state_field: state[gol_state_field]})

    rep = g.report()
    for phase in ("epoch.build", "amr.refine", "loadbalance.migrate",
                  "halo.exchange"):
        assert phase in rep["phases"], phase
        assert rep["phases"][phase]["count"] >= 1
    assert rep["counters"]["amr.cells_refined"][""] == 4
    assert rep["grid"]["n_cells"] == len(g.get_cells())
    assert rep["grid"]["n_devices"] == g.n_devices
    assert rep["grid"]["max_refinement_level"] == 1
    # the accessor is the process-wide registry
    assert g.telemetry is obs.metrics


def test_checkpoint_telemetry(tmp_path):
    obs.metrics.reset()
    obs.enable()
    g = _small_grid(max_ref=0, hood=1, length=(4, 4, 2))
    spec = {"rho": ((), np.float64)}
    st = g.new_state(spec)
    st = g.set_cell_data(st, "rho", g.get_cells(),
                         np.arange(1.0, len(g.get_cells()) + 1))
    path = str(tmp_path / "t.dc")
    g.save_grid_data(st, path, spec)
    m = obs.metrics
    assert m.report()["phases"]["checkpoint.write"]["count"] == 1
    n = len(g.get_cells())
    assert m.counter_value("checkpoint.bytes_written") == n * 8 + n * 16
    from dccrg_tpu.grid import Grid

    g2, st2, _ = Grid.load_grid_data(path, spec)
    assert m.report()["phases"]["checkpoint.read"]["count"] >= 1
    assert m.counter_value("checkpoint.bytes_read") == n * 8
    assert m.counter_value("checkpoint.cells_read") == n


def test_amr_induced_refines_counter():
    """A single refine on a 2-level grid forces 2:1 induction around it
    after the first pass; the repair counter must see the induced set."""
    obs.metrics.reset()
    obs.enable()
    g = _small_grid(max_ref=2, hood=1, length=(8, 8, 1))
    g.refine_completely(int(g.get_cells()[0]))
    g.stop_refining()
    base = obs.metrics.counter_value("amr.induced_refines")
    # refine a level-1 cell twice-removed from its coarse neighbors:
    # committing it drags coarser neighbors along (2:1 repairs)
    lvl = g.mapping.get_refinement_level(g.get_cells())
    fine = g.get_cells()[lvl == 1][0]
    g.refine_completely(int(fine))
    g.stop_refining()
    assert obs.metrics.counter_value("amr.induced_refines") > base
    assert obs.metrics.counter_value("amr.commits") == 2


def test_halo_counters_survive_schedule_retirement():
    """Halo telemetry is buffered per schedule; an epoch rebuild drops
    the schedule (grid._halo_cache cleared) and GC must flush — not
    lose — the pending counts."""
    import gc

    obs.metrics.reset()
    obs.enable()
    g = _small_grid(max_ref=1)
    st = g.new_state({"rho": ((), np.float64)})
    st = g.update_copies_of_remote_neighbors(st)
    moved = int(g.epoch.hoods[None].pair_counts.sum())
    # structural change retires the schedule before any report flushed it
    g.refine_completely(int(g.get_cells()[0]))
    g.stop_refining()
    gc.collect()
    assert obs.metrics.counter_value("halo.cells_moved") == moved


# ------------------------------------------------------- event timeline


def test_timeline_records_registry_phases():
    from dccrg_tpu.obs.events import EventTimeline

    reg = MetricsRegistry()
    tl = EventTimeline(enabled=True)
    reg.timeline = tl
    with reg.phase("outer"):
        with reg.phase("inner"):
            time.sleep(0.005)
    reg.phase_add("halo.exchange", 0.002)
    assert len(tl) == 3
    names = {e["name"] for e in tl.chrome_trace()["traceEvents"]}
    assert names == {"outer", "inner", "halo.exchange"}
    # a disabled registry records nothing into the timeline either
    reg.enabled = False
    with reg.phase("off"):
        pass
    reg.phase_add("off2", 0.001)
    assert len(tl) == 3


def test_timeline_chrome_trace_pairs_and_nesting():
    from dccrg_tpu.obs.events import EventTimeline

    tl = EventTimeline(enabled=True)
    with tl.span("outer", kind="test"):
        with tl.span("inner"):
            time.sleep(0.002)
    trace = tl.chrome_trace()
    evs = trace["traceEvents"]
    # matched B/E pairs in stack order: B outer, B inner, E inner, E outer
    assert [(e["ph"], e["name"]) for e in evs] == [
        ("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer"),
    ]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts) and ts[0] >= 0
    assert evs[0]["args"] == {"kind": "test"}
    assert trace["otherData"]["dropped_events"] == 0


def test_timeline_bounded_and_disabled():
    from dccrg_tpu.obs.events import EventTimeline

    tl = EventTimeline(enabled=True, max_events=3)
    for i in range(5):
        tl.add(f"e{i}", float(i), 0.5)
    assert len(tl) == 3
    assert tl.summary()["dropped"] == 2
    tl.clear()
    assert len(tl) == 0 and tl.summary()["dropped"] == 0
    tl.enabled = False
    with tl.span("nope"):
        pass
    tl.add("nope2", 0.0, 1.0)
    assert len(tl) == 0


def test_export_chrome_trace_file_validates(tmp_path):
    """Export -> file -> the check_telemetry schema validator."""
    from dccrg_tpu import obs
    from dccrg_tpu.obs.events import EventTimeline

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_telemetry
    finally:
        sys.path.pop(0)
    tl = EventTimeline(enabled=True)
    with tl.span("epoch.build"):
        with tl.span("epoch.hood_build"):
            pass
    with tl.span("halo.exchange"):
        pass
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(str(path), tl)
    data = json.loads(path.read_text())
    assert len(data["traceEvents"]) == 6
    assert check_telemetry.validate_chrome_trace(str(path)) == []


# ------------------------------------------------------ streaming export


def test_stream_snapshots_schema_and_final(tmp_path):
    from dccrg_tpu import obs

    reg = MetricsRegistry()
    reg.inc("c", 5)
    path = tmp_path / "s.jsonl"
    with obs.TelemetryStream(str(path), period=3600.0, registry=reg,
                             extra={"workload": "unit"}) as s:
        s.write_snapshot(tag="a")
        reg.inc("c", 2)
        s.write_snapshot()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    # 2 explicit + 1 final (context exit)
    assert len(lines) == 3
    assert [l["seq"] for l in lines] == [0, 1, 2]
    assert all(a["ts"] <= b["ts"] for a, b in zip(lines, lines[1:]))
    assert lines[0]["tag"] == "a" and lines[0]["workload"] == "unit"
    assert lines[0]["counters"]["c"][""] == 5
    assert lines[1]["counters"]["c"][""] == 7
    assert lines[-1]["final"] is True


def test_stream_periodic_ticker(tmp_path):
    """The daemon ticker really appends between explicit calls — the
    hung-run evidence path."""
    from dccrg_tpu import obs

    reg = MetricsRegistry()
    path = tmp_path / "tick.jsonl"
    s = obs.TelemetryStream(str(path), period=0.05, registry=reg)
    s.start()
    deadline = time.time() + 5.0
    while time.time() < deadline:
        if path.exists() and len(path.read_text().splitlines()) >= 2:
            break
        time.sleep(0.02)
    s.stop(final=False)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) >= 2
    assert [l["seq"] for l in lines] == list(range(len(lines)))


def test_stream_validator_rejects_bad_streams(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_telemetry
    finally:
        sys.path.pop(0)
    ok = {"seq": 0, "ts": 1.0, "phases": {}, "counters": {"c": {"": 1}},
          "gauges": {}, "histograms": {}}
    good = tmp_path / "good.jsonl"
    good.write_text(
        json.dumps(ok) + "\n"
        + json.dumps({**ok, "seq": 1, "ts": 2.0,
                      "counters": {"c": {"": 3}}}) + "\n"
        # killed mid-write: trailing partial line is tolerated
        + '{"seq": 2, "ts": 3.0, "pha'
    )
    assert check_telemetry.validate_stream(str(good)) == []
    bad_seq = tmp_path / "bad_seq.jsonl"
    bad_seq.write_text(json.dumps(ok) + "\n" + json.dumps(ok) + "\n")
    assert any("seq" in f
               for f in check_telemetry.validate_stream(str(bad_seq)))
    bad_ts = tmp_path / "bad_ts.jsonl"
    bad_ts.write_text(
        json.dumps({**ok, "ts": 9.0}) + "\n"
        + json.dumps({**ok, "seq": 1, "ts": 2.0}) + "\n"
    )
    assert any("ts" in f
               for f in check_telemetry.validate_stream(str(bad_ts)))
    bad_ctr = tmp_path / "bad_ctr.jsonl"
    bad_ctr.write_text(
        json.dumps(ok) + "\n"
        + json.dumps({**ok, "seq": 1, "ts": 2.0,
                      "counters": {"c": {"": 0}}}) + "\n"
    )
    assert any("decreased" in f
               for f in check_telemetry.validate_stream(str(bad_ctr)))
    missing = tmp_path / "missing.jsonl"
    missing.write_text('{"seq": 0, "ts": 1.0}\n')
    assert any("missing keys" in f
               for f in check_telemetry.validate_stream(str(missing)))


def test_trace_validator_rejects_bad_traces(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_telemetry
    finally:
        sys.path.pop(0)

    def write(events):
        p = tmp_path / "t.json"
        p.write_text(json.dumps({"traceEvents": events}))
        return str(p)

    b = {"name": "x", "ph": "B", "pid": 1, "tid": 0, "ts": 1.0}
    e = {"name": "x", "ph": "E", "pid": 1, "tid": 0, "ts": 2.0}
    assert check_telemetry.validate_chrome_trace(write([b, e])) == []
    # unmatched begin
    assert any("unmatched" in f for f in
               check_telemetry.validate_chrome_trace(write([b])))
    # E closing the wrong name
    assert any("closes" in f for f in check_telemetry.validate_chrome_trace(
        write([b, {**e, "name": "y"}])))
    # backwards in-thread timestamp
    assert any("backwards" in f
               for f in check_telemetry.validate_chrome_trace(
                   write([{**b, "ts": 5.0}, {**e, "ts": 1.0}])))
    # bare E with empty stack
    assert any("empty stack" in f
               for f in check_telemetry.validate_chrome_trace(write([e])))


# ------------------------------------------------------------ HBM gauges


def test_sample_hbm_records_per_device_gauges():
    from dccrg_tpu import obs

    class FakeDev:
        def __init__(self, i, stats):
            self.id = i
            self._stats = stats

        def memory_stats(self):
            return self._stats

    reg = MetricsRegistry()
    out = obs.sample_hbm(registry=reg, devices=[
        FakeDev(0, {"bytes_in_use": 100, "bytes_limit": 1000}),
        FakeDev(1, None),                      # CPU-style backend
        FakeDev(2, {"bytes_in_use": 300, "peak_bytes_in_use": 400}),
    ])
    assert out == {0: {"bytes_in_use": 100, "bytes_limit": 1000},
                   2: {"bytes_in_use": 300, "peak_bytes_in_use": 400}}
    assert reg.gauge_value("hbm.bytes_in_use", device=0) == 100
    assert reg.gauge_value("hbm.bytes_in_use", device=2) == 300
    assert reg.gauge_value("hbm.peak_bytes_in_use", device=2) == 400
    # disabled registry records nothing
    reg2 = MetricsRegistry(enabled=False)
    assert obs.sample_hbm(registry=reg2, devices=[
        FakeDev(0, {"bytes_in_use": 1})]) == {}
    assert reg2.report()["gauges"] == {}
    # the real backend path must never raise, whatever it reports
    obs.sample_hbm(registry=reg)


# -------------------------------------------- fused-run reconciliation


def test_fused_run_reconciliation_counters():
    """Whole-run dispatches (ghost traffic inside jit) must reconcile
    steps x schedule bytes into fused.* once per run() call."""
    from dccrg_tpu import obs
    from dccrg_tpu.models import GameOfLife

    obs.metrics.reset()
    obs.enable()
    g = _small_grid(max_ref=0, hood=1, length=(8, 8, 1))
    gol = GameOfLife(g)
    st = gol.new_state(alive_cells=[12, 13, 14])
    gol.run(st, 7)
    m = obs.metrics
    path = "dense"  # the fused GoL kernel runs on one device only
    assert m.counter_value("fused.runs", model="game_of_life",
                           path=path) == 1
    assert m.counter_value("fused.steps", model="game_of_life",
                           path=path) == 7
    expected = 7 * g.halo(None).bytes_moved({"is_alive": st["is_alive"]})
    assert m.counter_value("fused.halo_bytes_equiv", model="game_of_life",
                           path=path) == expected
    assert expected > 0  # the 8-device board really has a schedule


def test_fused_run_reconciliation_vlasov_and_advection():
    from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
    from dccrg_tpu.models import Advection, Vlasov

    obs.metrics.reset()
    obs.enable()
    n = 8
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
    v = Vlasov(g, nv=2, dtype=np.float32, use_pallas=False)
    assert v.info is not None
    s = v.initialize_state()
    v.run(s, 3, np.float32(0.2 * v.max_time_step()))
    m = obs.metrics
    assert m.counter_value("fused.steps", model="vlasov", path="xla") == 3
    # dense slab layout on >1 device: 2 ring planes per device per step
    expected = 3 * g.n_devices * 2 * v.info.ny * v.info.nx * v.B * 4
    assert m.counter_value("fused.halo_bytes_equiv", model="vlasov",
                           path="xla") == expected

    adv = Advection(g, dtype=np.float32, use_pallas=False)
    sa = adv.initialize_state()
    adv.run(sa, 4, np.float32(0.2 * adv.max_time_step(sa)))
    runs = m.report()["counters"].get("fused.runs", {})
    adv_series = {k: v for k, v in runs.items() if "model=advection" in k}
    assert sum(adv_series.values()) == 1, adv_series
    steps = m.report()["counters"]["fused.steps"]
    assert sum(v for k, v in steps.items() if "model=advection" in k) == 4


def test_fused_reconciliation_disabled_records_nothing():
    from dccrg_tpu import obs
    from dccrg_tpu.models import GameOfLife

    obs.metrics.reset()
    obs.disable()
    try:
        g = _small_grid(max_ref=0, hood=1, length=(8, 8, 1))
        gol = GameOfLife(g)
        gol.run(gol.new_state(alive_cells=[12]), 3)
        assert obs.metrics.report()["counters"] == {}
    finally:
        obs.enable()


# ------------------------------------------------------- profiler trace


def test_profile_trace_materializes_trace_dir(tmp_path):
    """obs.profile_trace must actually leave a trace on disk (previously
    only exercised manually via TensorBoard/xprof), and a registry phase
    opened inside it must be on the capture's host plane."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from dccrg_tpu import obs

    log_dir = tmp_path / "trace"
    with obs.profile_trace(str(log_dir)):
        jax.block_until_ready(jnp.ones((16, 16)) @ jnp.ones((16, 16)))
        with obs.metrics.phase("trace.probe"):
            pass
    files = [p for p in log_dir.rglob("*") if p.is_file()]
    assert files, "profiler trace directory did not materialize"
    (xplane,) = log_dir.rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events}
    assert "trace.probe" in names


# --------------------------------------------------------------- CI gate


def test_check_telemetry_tool(tmp_path):
    """The CI gate runs as a plain (not slow) pytest: phase/counter
    completeness, export round-trip, and the overhead ceiling (with
    headroom over the standalone 5% for CI timing noise)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_telemetry
    finally:
        sys.path.pop(0)
    failures = check_telemetry.run_check(
        str(tmp_path / "telemetry.json"), steps=10, reps=3, threshold=1.5,
    )
    assert failures == []
    data = json.loads((tmp_path / "telemetry.json").read_text())
    for phase in check_telemetry.REQUIRED_PHASES:
        assert phase in data["phases"]
