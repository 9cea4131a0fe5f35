"""Elastic fleet (ISSUE 8): supervised rescale bit-identity, policy
hysteresis/cooldown (no flap under oscillating load), watchdog stall
detection + escalation ladder, injected ``device.lost`` / ``step.hang``
handling, and the fresh-process persistent-cache warm start asserting
``epoch.recompiles == 0`` on a held ShapeSignature."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
from dccrg_tpu.models import Advection, GameOfLife
from dccrg_tpu.resilience import (
    CheckpointLineage,
    DeviceLostError,
    ElasticPolicy,
    EscalationLadder,
    HeartbeatMonitor,
    Supervisor,
    available_devices,
    plane,
    rescale,
    step_latency_signal,
    utilization_signal,
)
from dccrg_tpu.resilience import inject

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    plane.disarm()


def make_adv_grid(n_dev, n=4, seed=0):
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_geometry(CartesianGeometry, start=(0.0, 0.0, 0.0),
                      level_0_cell_length=(1.0 / n,) * 3)
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    rng = np.random.default_rng(seed)
    ids = np.sort(g.get_cells())
    for cid in rng.choice(ids, size=max(1, len(ids) // 5), replace=False):
        g.refine_completely(int(cid))
    g.stop_refining()
    return g, rng


ADV_SPEC = {k: ((), np.float64) for k in ("density", "vx", "vy", "vz")}


def land_advection(g, spec_state):
    """Rebuild model + full state from a (grid, spec-field state) pair —
    the same landing path the soak child uses."""
    ids = np.sort(g.get_cells())
    adv = Advection(g)
    s = adv.initialize_state()
    for f in ADV_SPEC:
        s = adv.set_cell_data(s, f, ids, g.get_cell_data(spec_state, f, ids))
    s = g.update_copies_of_remote_neighbors(s)
    return adv, s


# ------------------------------------------------------- rescale mechanism


def test_rescale_gol_bit_identity_1_to_8():
    """GoL stepped across rescales 1 -> 8 -> 1 must finish exactly equal
    to the fixed-mesh run (checkpoint round trip exact, GoL exact on any
    count)."""
    rng = np.random.default_rng(7)
    runs = {}
    for elastic in (False, True):
        g = (Grid().set_initial_length((8, 8, 1)).set_neighborhood_length(1)
             .set_periodic(True, True, False)
             .initialize(mesh=make_mesh(n_devices=1)))
        cells = g.get_cells()
        alive = cells[np.random.default_rng(42).random(len(cells)) < 0.4]
        gol = GameOfLife(g)
        s = gol.new_state(alive_cells=alive)
        with tempfile.TemporaryDirectory() as td:
            for step in range(9):
                if elastic and step in (3, 6):
                    target = 8 if step == 3 else 1
                    r = rescale(g, s, GameOfLife.SPEC, target,
                                directory=td, user_header=b"t")
                    assert r.n_devices_after == target
                    g, s = r.grid, r.state
                    gol = GameOfLife(g)
                s = gol.run(s, 1)
            runs[elastic] = set(gol.alive_cells(s).tolist())
    assert runs[True] == runs[False]


def test_rescale_advection_converges_across_counts():
    """Advection stepped across 1 -> 8 -> 2 rescales matches the
    fixed-mesh run within the documented cross-layout tolerance."""
    finals = {}
    for elastic in (False, True):
        g, rng = make_adv_grid(1)
        ids = np.sort(g.get_cells())
        adv = Advection(g)
        s = adv.initialize_state()
        s = adv.set_cell_data(s, "density", ids,
                              rng.uniform(1, 2, len(ids)))
        for f in ("vx", "vy", "vz"):
            s = adv.set_cell_data(s, f, ids,
                                  rng.uniform(-0.2, 0.2, len(ids)))
        s = g.update_copies_of_remote_neighbors(s)
        dt = 0.3 * adv.max_time_step(s)
        with tempfile.TemporaryDirectory() as td:
            for step in range(6):
                if elastic and step in (2, 4):
                    r = rescale(g, s, ADV_SPEC, 8 if step == 2 else 2,
                                directory=td, user_header=b"t")
                    g = r.grid
                    adv, s = land_advection(g, r.state)
                s = adv.step(s, dt)
        finals[elastic] = np.asarray(
            g.get_cell_data(s, "density", ids), np.float64)
    np.testing.assert_allclose(finals[True], finals[False],
                               rtol=1e-11, atol=0)


def test_rescale_counters_phase_and_result():
    g, rng = make_adv_grid(2)
    spec = {"q": ((), np.float64)}
    s = g.new_state(spec)
    ids = g.get_cells()
    s = g.set_cell_data(s, "q", ids, rng.uniform(0, 1, len(ids)))
    up0 = obs.metrics.counter_value("elastic.rescales", direction="up")
    down0 = obs.metrics.counter_value("elastic.rescales", direction="down")
    with tempfile.TemporaryDirectory() as td:
        r = rescale(g, s, spec, 4, directory=td)
        assert (r.direction, r.n_devices_before, r.n_devices_after) == \
            ("up", 2, 4)
        assert r.commit_s > 0 and r.reland_s > 0
        r2 = rescale(r.grid, r.state, spec, 1, directory=td)
        assert r2.direction == "down" and r2.n_devices_after == 1
        # payload survives both re-landings bit-identically
        np.testing.assert_array_equal(
            np.asarray(r2.grid.get_cell_data(r2.state, "q", ids)),
            np.asarray(g.get_cell_data(s, "q", ids)))
    assert obs.metrics.counter_value("elastic.rescales",
                                     direction="up") == up0 + 1
    assert obs.metrics.counter_value("elastic.rescales",
                                     direction="down") == down0 + 1
    assert obs.metrics.gauge_value("elastic.n_devices") == 1
    assert "elastic.rescale" in obs.metrics.phase_names()


def test_rescale_rejects_bad_targets():
    g, rng = make_adv_grid(1)
    spec = {"q": ((), np.float64)}
    s = g.new_state(spec)
    with tempfile.TemporaryDirectory() as td:
        with pytest.raises(ValueError, match="lineage"):
            rescale(g, s, spec, 2)
        with pytest.raises(ValueError, match="devices"):
            rescale(g, s, spec, 0, directory=td)
        with pytest.raises(DeviceLostError, match="visible"):
            rescale(g, s, spec, available_devices() + 1, directory=td)


def test_rescaled_grids_share_signature_and_executables():
    """Two re-landings of the same lineage generation at the same count
    build equal ShapeSignatures (rings included) — the satellite claim
    that the signature alone predicts executable-cache behavior."""
    g, rng = make_adv_grid(2)
    spec = {"q": ((), np.float64)}
    s = g.new_state(spec)
    with tempfile.TemporaryDirectory() as td:
        lineage = CheckpointLineage(td, keep=2)
        lineage.commit(g, s, spec)
        grids = []
        for _ in range(2):
            g2, s2, _h, _gen = lineage.latest_valid(spec, n_devices=4)
            s2 = g2.update_copies_of_remote_neighbors(s2)  # build halos
            grids.append(g2)
    sig_a, sig_b = (gr.shape_signature() for gr in grids)
    assert sig_a == sig_b
    assert sig_a.rings, "ring hints missing from the grid signature"


# ---------------------------------------------------------------- policy


def test_policy_oscillating_load_never_flaps():
    p = ElasticPolicy(4, high=0.8, low=0.3, patience=2, cooldown_s=0.0,
                      max_devices=8)
    decisions = [p.observe(load, now=float(i))
                 for i, load in enumerate([0.95, 0.05] * 10)]
    assert decisions == [None] * 20


def test_policy_patience_then_grow_and_clamp():
    p = ElasticPolicy(4, high=0.8, low=0.3, patience=3, cooldown_s=0.0,
                      max_devices=8)
    assert p.observe(0.9, now=0.0) is None
    assert p.observe(0.9, now=1.0) is None
    assert p.observe(0.9, now=2.0) == 8
    p.committed(8, now=2.0)
    # at max: sustained high load cannot grow further
    for i in range(5):
        assert p.observe(0.99, now=3.0 + i) is None


def test_policy_shrink_with_floor():
    p = ElasticPolicy(4, min_devices=2, high=0.8, low=0.3, patience=2,
                      cooldown_s=0.0, max_devices=8)
    assert p.observe(0.1, now=0.0) is None
    assert p.observe(0.1, now=1.0) == 2
    p.committed(2, now=1.0)
    assert p.observe(0.1, now=2.0) is None  # floor: patience restarts
    assert p.observe(0.1, now=3.0) is None  # 2 == min_devices


def test_policy_cooldown_blocks_then_releases():
    p = ElasticPolicy(2, high=0.8, low=0.3, patience=1, cooldown_s=10.0,
                      max_devices=8)
    assert p.observe(0.9, now=0.0) == 4
    p.committed(4, now=0.0)
    assert p.observe(0.9, now=5.0) is None       # inside cooldown
    assert p.observe(0.9, now=10.5) == 8         # released
    # in-between load resets streaks (hysteresis band)
    p2 = ElasticPolicy(4, high=0.8, low=0.3, patience=2, cooldown_s=0.0)
    assert p2.observe(0.9, now=0.0) is None
    assert p2.observe(0.5, now=1.0) is None
    assert p2.observe(0.9, now=2.0) is None      # streak restarted


def test_policy_env_knobs(monkeypatch):
    monkeypatch.setenv("DCCRG_ELASTIC_HIGH", "0.6")
    monkeypatch.setenv("DCCRG_ELASTIC_LOW", "0.2")
    monkeypatch.setenv("DCCRG_ELASTIC_PATIENCE", "1")
    monkeypatch.setenv("DCCRG_ELASTIC_COOLDOWN", "0")
    p = ElasticPolicy(2, max_devices=8)
    assert (p.high, p.low, p.patience, p.cooldown_s) == (0.6, 0.2, 1, 0.0)
    assert p.observe(0.7, now=0.0) == 4
    with pytest.raises(ValueError, match="low < high"):
        ElasticPolicy(2, high=0.3, low=0.5)


def test_signals_from_registry():
    from dccrg_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    assert utilization_signal(reg) is None
    reg.gauge("hbm.bytes_in_use", 750, device=0)
    reg.gauge("hbm.bytes_limit", 1000, device=0)
    reg.gauge("hbm.bytes_in_use", 100, device=1)
    reg.gauge("hbm.bytes_limit", 1000, device=1)
    assert utilization_signal(reg) == pytest.approx(0.75)
    assert step_latency_signal(0.5, registry=reg) is None
    reg.phase_add("halo.exchange", 1.0)
    assert step_latency_signal(0.5, registry=reg) == pytest.approx(2.0)


# -------------------------------------------------------------- watchdog


def _stream(path, registry=None):
    return obs.TelemetryStream(path, period=3600.0, registry=registry,
                               truncate=True)


def test_heartbeat_monitor_detects_silence(tmp_path):
    hb = str(tmp_path / "hb.jsonl")
    mon = HeartbeatMonitor(hb, stall_after_s=5.0, now=0.0)
    assert mon.poll(now=1.0) == ("waiting", None)
    assert mon.poll(now=6.0) == ("stalled", "no-heartbeat")
    s = _stream(hb)
    s.write_snapshot(step=0)
    mon = HeartbeatMonitor(hb, stall_after_s=5.0, now=0.0)
    assert mon.poll(now=1.0) == ("ok", None)
    assert mon.poll(now=4.0) == ("ok", None)
    assert mon.poll(now=7.0) == ("stalled", "no-heartbeat")


def test_heartbeat_monitor_detects_frozen_progress(tmp_path):
    """Lines keep arriving (the stream ticker survived) but the step
    marker and counters are frozen — the step.hang shape."""
    from dccrg_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    hb = str(tmp_path / "hb.jsonl")
    s = _stream(hb, registry=reg)
    reg.inc("work.done")
    s.write_snapshot(step=0)
    mon = HeartbeatMonitor(hb, stall_after_s=3.0, now=0.0)
    assert mon.poll(now=0.5) == ("ok", None)
    reg.inc("work.done")
    s.write_snapshot(step=1)
    assert mon.poll(now=2.0) == ("ok", None)
    for now in (4.0, 6.0):
        s.write_snapshot(step=1)           # beats WITHOUT progress
        status = mon.poll(now=now)
    assert status == ("stalled", "no-progress")
    # progress resumes -> healthy again
    reg.inc("work.done")
    s.write_snapshot(step=2)
    assert mon.poll(now=7.0) == ("ok", None)


def test_heartbeat_monitor_tolerates_torn_tail(tmp_path):
    hb = tmp_path / "hb.jsonl"
    s = _stream(str(hb))
    s.write_snapshot(step=0)
    with open(hb, "a") as f:
        f.write('{"seq": 1, "truncated')   # killed mid-write
    mon = HeartbeatMonitor(str(hb), stall_after_s=5.0, now=0.0)
    assert mon.poll(now=1.0) == ("ok", None)
    assert mon.beats == 1


def test_escalation_ladder_order_counters_and_reset():
    warn0 = obs.metrics.counter_value("supervisor.warnings",
                                      reason="unit")
    deg0 = obs.metrics.counter_value("elastic.degraded")
    lad = EscalationLadder()
    assert [lad.escalate("unit") for _ in range(4)] == \
        ["warn", "rescale_down", "restart", "restart"]
    assert obs.metrics.counter_value("supervisor.warnings",
                                     reason="unit") == warn0 + 1
    assert obs.metrics.counter_value("elastic.degraded") == deg0 + 1
    assert obs.metrics.counter_value("supervisor.escalations",
                                     action="restart") >= 2
    lad.reset()
    assert lad.escalate("unit") == "warn"
    # patience absorbs strikes per rung
    lad2 = EscalationLadder(patience=2)
    assert [lad2.escalate("x") for _ in range(4)] == \
        ["warn", "warn", "rescale_down", "rescale_down"]
    # a dead child enters at the degraded rung
    lad3 = EscalationLadder()
    assert lad3.escalate("child-dead", minimum="rescale_down") == \
        "rescale_down"


def test_supervisor_escalates_and_recovers(tmp_path):
    hb = str(tmp_path / "hb.jsonl")
    s = _stream(hb)
    s.write_snapshot(step=0)
    sup = Supervisor(HeartbeatMonitor(hb, stall_after_s=2.0, now=0.0))
    assert sup.poll(now=0.5)["action"] is None
    acts = [sup.poll(now=10.0 + i)["action"] for i in range(3)]
    assert acts == ["warn", "rescale_down", "restart"]
    # a fresh beat resets the ladder
    s.write_snapshot(step=1)
    assert sup.poll(now=13.5)["action"] is None
    assert sup.poll(now=20.0)["action"] == "warn"
    assert "supervisor.poll" in obs.metrics.phase_names()


def test_supervisor_dead_child_goes_degraded(tmp_path):
    hb = str(tmp_path / "hb.jsonl")
    _stream(hb).write_snapshot(step=0)
    sup = Supervisor(HeartbeatMonitor(hb, stall_after_s=30.0, now=0.0),
                     child_alive=lambda: False)
    out = sup.poll(now=1.0)
    assert (out["status"], out["action"]) == ("dead", "rescale_down")
    assert sup.poll(now=2.0)["action"] == "restart"


# ------------------------------------------------------------ fault sites


def test_device_lost_site_raises_and_counts():
    before = obs.metrics.counter_value("resilience.injected",
                                       site="device.lost",
                                       where="discovery")
    plane.arm("device.lost", prob=1.0, seed=0, count=1)
    with pytest.raises(DeviceLostError):
        available_devices()
    assert available_devices() >= 1   # budget spent: back to normal
    assert obs.metrics.counter_value(
        "resilience.injected", site="device.lost", where="discovery"
    ) == before + 1


def test_device_lost_aborts_rescale():
    g, rng = make_adv_grid(1)
    spec = {"q": ((), np.float64)}
    s = g.new_state(spec)
    plane.arm("device.lost", prob=1.0, seed=0, count=1)
    with tempfile.TemporaryDirectory() as td:
        with pytest.raises(DeviceLostError):
            rescale(g, s, spec, 2, directory=td)
        plane.disarm()
        r = rescale(g, s, spec, 2, directory=td)   # plane clear: works
        assert r.n_devices_after == 2


def test_step_hang_site_sleeps_and_counts():
    import time

    assert not inject.maybe_hang("step.hang", seconds=0.01)
    plane.arm("step.hang", prob=1.0, seed=0, count=1)
    t0 = time.perf_counter()
    assert inject.maybe_hang("step.hang", seconds=0.05)
    assert time.perf_counter() - t0 >= 0.05
    assert not inject.maybe_hang("step.hang", seconds=0.05)  # budget spent


# ------------------------------------------- persistent-cache warm start


WARM_CHILD = textwrap.dedent("""\
    import sys, os, json
    lineage_dir, nd, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    import jax
    jax.config.update('jax_platforms', 'cpu')
    os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')
        + ' --xla_force_host_platform_device_count=8').strip()
    jax.config.update('jax_enable_x64', True)
    import numpy as np
    sys.path.insert(0, %r)
    from dccrg_tpu import Grid, obs
    from dccrg_tpu.models import Advection
    from dccrg_tpu.parallel.exec_cache import (persistent_cache_counts,
                                               persistent_cache_dir)
    from dccrg_tpu.resilience import CheckpointLineage

    SPEC = {k: ((), np.float64) for k in ('density', 'vx', 'vy', 'vz')}
    lineage = CheckpointLineage(lineage_dir, keep=2)
    g, s2, hdr, gen = lineage.latest_valid(SPEC, n_devices=nd)
    ids = np.sort(g.get_cells())
    adv = Advection(g)
    s = adv.initialize_state()
    for f in SPEC:
        s = adv.set_cell_data(s, f, ids, g.get_cell_data(s2, f, ids))
    s = g.update_copies_of_remote_neighbors(s)
    dt = 0.25 * adv.max_time_step(s)
    s = adv.step(s, dt)
    # first churn cycle: rebuild + re-land + step, the warm-start claim
    lvl = g.mapping.get_refinement_level(ids)
    cand = ids[lvl < g.mapping.max_refinement_level]
    g.refine_completely(int(cand[len(cand) // 2]))
    g.stop_refining()
    s = g.remap_state(s)
    s = g.update_copies_of_remote_neighbors(s)
    adv = Advection(g)
    s = adv.step(s, dt)
    jax.block_until_ready(s['density'])
    rep = obs.metrics.report()
    json.dump({
        'signature': repr(g.shape_signature()),
        'cache_dir': persistent_cache_dir(),
        'recompiles': sum(
            rep['counters'].get('epoch.recompiles', {}).values()),
        'warm_compiles': sum(
            rep['counters'].get('epoch.warm_compiles', {}).values()),
        'persistent_cache': persistent_cache_counts(),
    }, open(out, 'w'))
""" % ROOT)


def test_fresh_process_warm_start_zero_recompiles(tmp_path):
    """The zero-cold-start proof: two fresh processes resume the same
    lineage under a shared ``JAX_COMPILATION_CACHE_DIR`` and run one churn
    cycle; the second must land on the first's ShapeSignature with
    ``epoch.recompiles == 0`` — every compile a persistent-cache hit."""
    g, rng = make_adv_grid(2, seed=3)
    adv = Advection(g)
    s = adv.initialize_state()
    ids = np.sort(g.get_cells())
    s = adv.set_cell_data(s, "density", ids, rng.uniform(1, 2, len(ids)))
    s = g.update_copies_of_remote_neighbors(s)
    lineage_dir = str(tmp_path / "lineage")
    CheckpointLineage(lineage_dir, keep=2).commit(g, s, ADV_SPEC)

    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["JAX_PLATFORMS"] = "cpu"
    reports = []
    for i in range(2):
        out = str(tmp_path / f"proof_{i}.json")
        r = subprocess.run(
            [sys.executable, "-c", WARM_CHILD, lineage_dir, "2", out],
            env=env, capture_output=True, text=True, timeout=300,
            cwd=ROOT,
        )
        assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
        with open(out) as f:
            reports.append(json.load(f))
    a, b = reports
    assert b["cache_dir"] == str(tmp_path / "cache")
    assert b["signature"] == a["signature"], (a, b)
    assert b["recompiles"] == 0, b
    assert b["warm_compiles"] > 0, b
    assert b["persistent_cache"]["hits"] > 0, b


CACHE_CHILD = textwrap.dedent("""
    import sys
    import jax
    sys.path.insert(0, %r)
    from dccrg_tpu.parallel import exec_cache
    path = exec_cache.enable_persistent_cache()
    print(path + "|" + str(jax.config.jax_compilation_cache_dir))
""" % ROOT)


def _cache_child(env):
    env = {**env, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", CACHE_CHILD], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-1500:]
    wired, configured = r.stdout.strip().splitlines()[-1].split("|")
    return wired, configured


def test_persistent_cache_honours_jax_env(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: the cache lives there and no
    other directory is configured in code."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jc")
    wired, configured = _cache_child(env)
    assert wired == configured == str(tmp_path / "jc")
    assert (tmp_path / "jc").is_dir()


def test_persistent_cache_defaults_inside_checkout():
    """Unset: one fixed path inside the checkout (listed in .gitignore),
    the same on every run so entries are found again."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    wired, configured = _cache_child(env)
    assert wired == configured == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------- signature satellite


def test_ring_signature_canonical_form():
    from dccrg_tpu.parallel.shapes import ring_signature

    assert ring_signature({}) == ()
    assert ring_signature(None) == ()
    hints = {(None, None, 1): 44, (2, "density", 3): 16,
             (None, None, 2): 8}
    assert ring_signature(hints) == (
        (-1, "", 1, 44), (-1, "", 2, 8), (2, "density", 3, 16))


def test_grid_signature_surfaces_ring_hints():
    g, _rng = make_adv_grid(2)
    spec = {"q": ((), np.float64)}
    s = g.new_state(spec)
    sig0 = g.shape_signature()
    g.update_copies_of_remote_neighbors(s)   # builds the halo schedule
    sig1 = g.shape_signature()
    assert sig1.rings, "halo build left no ring hints in the signature"
    assert sig1._replace(rings=()) == sig0._replace(rings=())
    # held hints are sticky: a second identical exchange changes nothing
    g.update_copies_of_remote_neighbors(s)
    assert g.shape_signature() == sig1


def test_check_telemetry_artifact_routing(tmp_path):
    """Bench byproducts route to tools/ only for the repo-root
    telemetry.json; everything else stays beside --out."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_telemetry
    finally:
        sys.path.pop(0)
    root_out = os.path.join(ROOT, "telemetry.json")
    assert check_telemetry.artifact_path(root_out, ".stream.jsonl") == \
        os.path.join(ROOT, "tools", "telemetry.json.stream.jsonl")
    tmp_out = str(tmp_path / "t.json")
    assert check_telemetry.artifact_path(tmp_out, ".trace.json") == \
        str(tmp_path / "t.json.trace.json")
    assert check_telemetry.artifact_path(
        root_out, ".x", artifact_dir=str(tmp_path)
    ) == str(tmp_path / "telemetry.json.x")
