"""Program spans on small grids on the CPU: the set-up phases the
registry holds after ``Grid()...initialize()`` and ``Advection(...)``,
the per-call marks of ``Advection.run`` in a ``jax.profiler`` capture,
the ``traced_jit`` labels of the whole-run functions, and the halo-byte
cache of ``Advection._record_run``."""
import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation

from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
from dccrg_tpu.models import Advection
from dccrg_tpu.parallel.exec_cache import trace_counts
from dccrg_tpu.parallel.halo import HaloExchange


def _uniform(n=(16, 16, 8)):
    return (
        Grid()
        .set_initial_length(n)
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_geometry(CartesianGeometry, start=(0.0, 0.0, 0.0),
                      level_0_cell_length=tuple(1.0 / x for x in n))
        .initialize(mesh=make_mesh(n_devices=1))
    )


def _refined(n=8):
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_geometry(CartesianGeometry, start=(0.0, 0.0, 0.0),
                      level_0_cell_length=(1.0 / n,) * 3)
        .initialize(mesh=make_mesh(n_devices=1))
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    g.refine_completely_many(ids[np.linalg.norm(c - 0.5, axis=1) < 0.3])
    g.stop_refining()
    return g


GRID_SPANS = ("grid.initialize", "grid.partition", "epoch.build",
              "epoch.hood_build", "epoch.row_layout", "epoch.finish_hood",
              "epoch.detect_dense", "epoch.tables")
#: the set-up spans each path's build leaves in the registry
SETUP_SPANS = {
    "uniform": GRID_SPANS + ("advection.init", "advection.init.dense",
                             "advection.init_state"),
    # interpret mode builds every flat probe (the chip's path) on the CPU
    "refined": GRID_SPANS + (
        "advection.init", "advection.init.tables", "advection.init.step",
        "advection.init.boxed", "advection.init.flat",
        "advection.init.flat.ml_tables",
        "advection.init.flat.sharded_tables",
        "advection.init.flat.amr_tables", "advection.init_state"),
}
BUILD = {
    "uniform": (_uniform, {}),
    "refined": (_refined, {"allow_dense": False,
                           "use_pallas": "interpret"}),
}


@pytest.fixture(scope="module")
def setup_phases():
    """{path: the registry's phase table right after that path's grid,
    model and initial state were built, and the epoch's tables read once
    (the dense path's epoch builds them on first read)}."""
    out = {}
    for path, (make, kw) in BUILD.items():
        obs.enable()
        obs.metrics.reset()
        adv = Advection(make(), dtype=np.float32, **kw)
        adv.initialize_state()
        assert adv.grid.epoch.R > 0
        out[path] = obs.metrics.report()["phases"]
    return out


@pytest.mark.parametrize("path,span", [
    (path, span) for path, spans in SETUP_SPANS.items() for span in spans
])
def test_setup_span_recorded(setup_phases, path, span):
    rec = setup_phases[path].get(span)
    assert rec is not None, f"{span} missing after the {path} set-up"
    assert rec["count"] >= 1 and rec["total_s"] >= 0.0


def test_setup_spans_nest(setup_phases):
    """A parent phase lasts at least as long as the children it holds."""
    for phases in setup_phases.values():
        assert phases["grid.initialize"]["total_s"] >= (
            phases["grid.partition"]["total_s"])
        assert phases["advection.init"]["total_s"] >= sum(
            rec["total_s"] for name, rec in phases.items()
            if name.count(".") == 2 and name.startswith("advection.init."))
    flat = setup_phases["refined"]
    assert flat["advection.init.flat"]["total_s"] >= sum(
        rec["total_s"] for name, rec in flat.items()
        if name.startswith("advection.init.flat."))


@pytest.mark.parametrize("make,kw", [BUILD["uniform"], BUILD["refined"]],
                         ids=["uniform", "refined"])
def test_set_cell_data_is_not_set_up(make, kw):
    """``set_cell_data`` is runtime API (restores, parks): it adds
    nothing to the ``advection.init_state`` set-up phase."""
    obs.enable()
    obs.metrics.reset()
    adv = Advection(make(), dtype=np.float32, **kw)
    state = adv.initialize_state()
    before = obs.metrics.report()["phases"]["advection.init_state"]["count"]
    ids = adv.grid.get_cells()[:4]
    adv.set_cell_data(state, "density", ids, np.ones(len(ids)))
    after = obs.metrics.report()["phases"]["advection.init_state"]["count"]
    assert after == before == 1


# ------------------------------------------------- per-call spans


RUN_SPANS = ("advection.run", "advection.run.record", "advection.run.args",
             "advection.run.launch")


def _host_events(log_dir):
    """[(name, start_ns, end_ns)] of the capture's host-plane events."""
    (path,) = log_dir.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns)))
    return out


@pytest.fixture(scope="module")
def run_capture(tmp_path_factory):
    """{path: host events of a capture of two ``run()`` calls, each
    inside a ``dispatch`` span as the benchmark harness writes it}."""
    out = {}
    for path, make in (("dense", _uniform), ("boxed", _refined)):
        adv = Advection(make(), dtype=np.float32, allow_dense=path == "dense")
        state = adv.initialize_state()
        dt = np.float32(0.4 * adv.max_time_step(state))
        state = adv.run(state, 2, dt)          # compile outside the capture
        jax.block_until_ready(state["density"])
        log_dir = tmp_path_factory.mktemp(f"capture_{path}")
        jax.profiler.start_trace(str(log_dir))
        try:
            for _ in range(2):
                with TraceAnnotation("dispatch"):
                    state = adv.run(state, 2, dt)
                jax.block_until_ready(state["density"])
        finally:
            jax.profiler.stop_trace()
        out[path] = _host_events(log_dir)
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("path", ["dense", "boxed"])
@pytest.mark.parametrize("span", RUN_SPANS)
def test_run_span_in_capture(run_capture, path, span):
    events = run_capture[path]
    found = [e for e in events if e[0] == span]
    assert len(found) == 2, f"{span}: {len(found)} events for 2 calls"
    parent = "dispatch" if span == "advection.run" else "advection.run"
    parents = [e for e in events if e[0] == parent]
    for e in found:
        assert any(_inside(e, p) for p in parents), (
            f"{span} at {e[1]} lies in no {parent} span")


# ------------------------------------------------ whole-run labels


LABELS = {
    # the uniform grid in the Pallas interpreter: the whole-block kernel
    "fused": (_uniform, {"use_pallas": "interpret"},
              "advection.fused_run"),
    # no Pallas on the CPU: the dense XLA step in the general loop
    "dense_xla": (_uniform, {}, "advection.general_run"),
    "boxed": (_refined, {"allow_dense": False}, "advection.boxed_run"),
    "flat": (_refined, {"allow_dense": False, "use_pallas": "interpret"},
             "advection.flat_run"),
    "general": (_refined, {"allow_dense": False, "allow_boxed": False},
                "advection.general_run"),
    "split": (_refined, {"allow_dense": False, "overlap": True},
              "advection.split_run"),
}


@pytest.mark.parametrize("case", sorted(LABELS))
def test_run_traced_under_its_label(case):
    """Each whole-run function is a ``traced_jit`` kernel: its first
    call traces under ``advection.<path>_run``, the label its module
    (``jit_advection_<path>_run``) and the recompile counters share."""
    make, kw, label = LABELS[case]
    adv = Advection(make(), dtype=np.float32, **kw)
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    before = trace_counts().get(label, 0)
    jax.block_until_ready(adv.run(state, 2, dt)["density"])
    assert trace_counts().get(label, 0) > before


# ------------------------------------------------- _record_run cache


def test_record_run_computes_halo_bytes_once(monkeypatch):
    """The halo bytes per step are computed once per schedule and
    payload; every call still counts its run and steps."""
    calls = []
    orig = HaloExchange.bytes_moved

    def counted(self, state):
        calls.append(1)
        return orig(self, state)

    monkeypatch.setattr(HaloExchange, "bytes_moved", counted)
    obs.enable()
    obs.metrics.reset()
    adv = Advection(_refined(), dtype=np.float32, allow_dense=False)
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    calls.clear()      # the initial ghost refresh's own halo record
    for _ in range(3):
        state = adv.run(state, 2, dt)
    jax.block_until_ready(state["density"])
    assert len(calls) == 1
    rep = obs.metrics.report()["counters"]
    assert rep["fused.runs"]["model=advection,path=boxed"] == 3
    assert rep["fused.steps"]["model=advection,path=boxed"] == 6
