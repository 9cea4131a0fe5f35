"""Exchange-amortized deep dispatch (ISSUE 14): wide-halo cohort bodies
that pay one depth-g exchange per g interior steps.

The contracts under test: a wide-halo dispatch is BIT-IDENTICAL to
exchange-every-step stepping on every owned row at every (g, k) —
including members retiring mid-exchange-block and heterogeneous
same-signature cohorts; hood-0 grids (budget 1) disengage and ride the
unchanged legacy body; occupancy churn at a held (signature, width, k,
g) retraces nothing and changing ONLY g compiles exactly one new body;
``Scheduler.select_k`` clamps scheduled depths to the exchange budget
so a scheduled dispatch pays exactly ONE exchange; the host-side
``halo.exchanges_per_step`` gauge reads ~1/k when wide halos engage;
and the solo ``run()`` donation satellite is env-gated with MEASURED
effectiveness."""
import numpy as np
import pytest

import jax

from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
from dccrg_tpu.models import Advection, GameOfLife, Vlasov
from dccrg_tpu.parallel import halo
from dccrg_tpu.parallel.exec_cache import cohort_key
from dccrg_tpu.parallel.wide_halo import get_wide_plan, halo_depth_cap
from dccrg_tpu.serve import Ensemble, Scenario, Scheduler

MOORE = [(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1)
         for k in (-1, 0, 1) if (i, j, k) != (0, 0, 0)]
GOL_HOOD = 7


def make_grid(n=6, hood=2, max_ref=0, refine_seed=None):
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(hood)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(max_ref)
        .set_geometry(CartesianGeometry, start=(0.0, 0.0, 0.0),
                      level_0_cell_length=(1.0 / n,) * 3)
        .initialize(mesh=make_mesh(n_devices=8))
    )
    if refine_seed is not None:
        rng = np.random.default_rng(refine_seed)
        ids = np.sort(g.get_cells())
        for cid in rng.choice(ids, size=max(1, len(ids) // 6),
                              replace=False):
            g.refine_completely(int(cid))
    g.stop_refining()
    return g


def make_gol(n=6, hood=2):
    g = make_grid(n=n, hood=hood)
    assert g.add_neighborhood(GOL_HOOD, MOORE)
    return g, GameOfLife(g, hood_id=GOL_HOOD, allow_dense=False)


def counter_total(name: str) -> int:
    rep = obs.metrics.report()
    return int(sum(rep["counters"].get(name, {}).values()))


def assert_local_rows_equal(model, solo, got):
    """Byte-compare owned rows (the wide-halo correctness contract);
    ghost replica rows legitimately hold block-stale values."""
    lm = model.batch_step_spec().wide.local_mask
    for name in sorted(solo):
        a, b = np.asarray(solo[name]), np.asarray(got[name])
        if a.shape[:2] == lm.shape:
            a, b = a[lm], b[lm]
        assert a.tobytes() == b.tobytes(), name


# ------------------------------------------------- (g, k) bit-identity


@pytest.mark.parametrize("hood,k", [(2, 1), (2, 2), (2, 4), (3, 3)])
def test_gol_wide_bit_identical_at_g_k(hood, k):
    """Every (ghost depth, dispatch depth) combination serves owned
    rows bit-identical to exchange-every-step solo stepping, with the
    always-on oracle byte-clean."""
    g, gol = make_gol(hood=hood)
    spec = gol.batch_step_spec()
    assert spec.wide is not None and spec.wide.budget >= 2
    rng = np.random.default_rng(11)
    cells = g.get_cells()
    states = [gol.new_state(alive_cells=cells[rng.random(len(cells)) < 0.3])
              for _ in range(3)]
    m0 = counter_total("ensemble.verify_mismatches")
    ens = Ensemble(verify=True, steps_per_dispatch=k)
    tickets = [ens.submit(gol, s, steps=2 * k + 1) for s in states]
    ens.run()
    cohort = next(iter(ens.cohorts.values()))
    assert cohort._wide is not None
    for t, s0 in zip(tickets, states):
        solo = s0
        for _ in range(2 * k + 1):
            solo = gol.step(solo)
        assert_local_rows_equal(gol, solo, t.result)
    assert counter_total("ensemble.verify_mismatches") == m0


@pytest.mark.parametrize("hood,k", [(2, 4), (3, 5)])
def test_advection_wide_bit_identical_at_g_k(hood, k):
    g = make_grid(n=8, hood=hood)
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    spec = adv.batch_step_spec()
    assert spec.wide is not None and spec.wide.budget >= hood
    s0 = adv.initialize_state()
    dt = np.float64(0.4 * adv.max_time_step(s0))
    m0 = counter_total("ensemble.verify_mismatches")
    ens = Ensemble(verify=True, steps_per_dispatch=k)
    t = ens.submit(adv, s0, steps=k + 1, dt=dt)
    ens.run()
    solo = s0
    for _ in range(k + 1):
        solo = adv.step(solo, dt)
    assert_local_rows_equal(adv, solo, t.result)
    assert counter_total("ensemble.verify_mismatches") == m0


def test_vlasov_wide_bit_identical(vl_nv=2):
    g = make_grid(n=6, hood=2)
    vl = Vlasov(g, nv=vl_nv, dtype=np.float32)
    assert vl.info is None, "multi-device grid must take the general path"
    spec = vl.batch_step_spec()
    assert spec.wide is not None and spec.wide.budget >= 2
    s0 = vl.initialize_state()
    dt = np.float32(0.5 * vl.max_time_step())
    m0 = counter_total("ensemble.verify_mismatches")
    ens = Ensemble(verify=True, steps_per_dispatch=4)
    t = ens.submit(vl, s0, steps=5, dt=dt)
    ens.run()
    solo = s0
    for _ in range(5):
        solo = vl.step(solo, dt)
    assert_local_rows_equal(vl, solo, t.result)
    assert counter_total("ensemble.verify_mismatches") == m0


def test_mid_block_retirement_and_direct_deep_step():
    """A direct ``cohort.step(k)`` past the exchange budget runs
    multiple exchange blocks, and a member retiring mid-block stays
    bit-identical to its clamped solo advance."""
    g = make_grid(n=8, hood=2)
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    s0 = adv.initialize_state()
    dt = np.float64(0.4 * adv.max_time_step(s0))
    s1 = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(), s0)
    s1["density"] = s1["density"] * 1.5
    m0 = counter_total("ensemble.verify_mismatches")
    sched = Scheduler(verify=True)
    t5 = sched.submit(Scenario(adv, s0, steps=5, dt=dt))
    t3 = sched.submit(Scenario(adv, s1, steps=3, dt=dt))
    sched.admit()
    cohort = next(iter(sched.cohorts.values()))
    assert cohort._wide is not None and cohort._wide_budget == 2
    served = cohort.step(5)       # ceil(5/2) = 3 exchange blocks
    assert served == 5 + 3
    for slot in cohort.finished_slots():
        sched.completed.append(cohort.retire(int(slot)))
    for t, start, n in ((t5, s0, 5), (t3, s1, 3)):
        solo = start
        for _ in range(n):
            solo = adv.step(solo, dt)
        assert_local_rows_equal(adv, solo, t.result)
    assert counter_total("ensemble.verify_mismatches") == m0


def test_heterogeneous_same_signature_wide_cohort():
    """Two refined grids at one signature with different AMR patterns
    share one wide cohort: admission promotes to the stacked tables,
    the oracle audits each member against ITS OWN local rows, and both
    members retire bit-identical to solo."""
    g1 = make_grid(n=4, hood=2, max_ref=1, refine_seed=1)
    g2 = make_grid(n=4, hood=2, max_ref=1, refine_seed=2)
    a1 = Advection(g1, dtype=np.float64, allow_dense=False)
    a2 = Advection(g2, dtype=np.float64, allow_dense=False)
    assert g1.shape_signature() == g2.shape_signature()
    assert a1.batch_step_spec().wide is not None
    assert a2.batch_step_spec().wide is not None
    s1, s2 = a1.initialize_state(), a2.initialize_state()
    dt = np.float64(0.4 * min(a1.max_time_step(s1), a2.max_time_step(s2)))
    m0 = counter_total("ensemble.verify_mismatches")
    ens = Ensemble(verify=True, steps_per_dispatch=2)
    t1 = ens.submit(a1, s1, steps=4, dt=dt)
    t2 = ens.submit(a2, s2, steps=4, dt=dt)
    ens.run()
    assert len(ens.cohorts) == 1
    cohort = next(iter(ens.cohorts.values()))
    assert cohort._wide is not None
    assert not cohort.shared_args, "different tables must promote"
    for t, a, s0 in ((t1, a1, s1), (t2, a2, s2)):
        solo = s0
        for _ in range(4):
            solo = a.step(solo, dt)
        assert_local_rows_equal(a, solo, t.result)
    assert counter_total("ensemble.verify_mismatches") == m0


# ---------------------------------------------------- (dis)engagement


def test_hood0_grids_disengage():
    """The pre-ISSUE-14 fleet: hood-0 grids have a budget of 1 (one
    exchange funds one step — the legacy body), so no wide spec ships
    and the cohort runs the unchanged per-step path."""
    g = make_grid(hood=0)
    gol = GameOfLife(g, allow_dense=False)
    assert gol.batch_step_spec().wide is None
    cells = g.get_cells()
    s0 = gol.new_state(alive_cells=cells[::3])
    ens = Ensemble(steps_per_dispatch=4)
    ens.submit(gol, s0, steps=4)
    ens.run()
    cohort = next(iter(ens.cohorts.values()))
    assert cohort._wide is None and cohort._wide_g(4) == 0


def test_env_gate_disables_wide(monkeypatch):
    monkeypatch.setenv("DCCRG_ENSEMBLE_WIDE", "0")
    _, gol = make_gol()
    assert gol.batch_step_spec().wide is None


# ----------------------------------------------- compile accounting


def test_zero_retrace_churn_at_held_sig_width_k_g():
    g, gol = make_gol(hood=2)
    rng = np.random.default_rng(3)
    cells = g.get_cells()
    states = [gol.new_state(alive_cells=cells[rng.random(len(cells)) < 0.3])
              for _ in range(12)]
    ens = Ensemble(steps_per_dispatch=2)
    for s in states[:4]:
        ens.submit(gol, s, steps=4)
    ens.run()                             # warm the (W=4, k=2, g=2) body
    before = counter_total("epoch.recompiles")
    for wave in (states[4:8], states[8:10], states[10:12]):
        for i, s in enumerate(wave):
            ens.submit(gol, s, steps=2 * (i + 1))
        ens.run()
    assert counter_total("epoch.recompiles") == before, (
        "churn at a held (signature, width, k, g) must not retrace")
    assert len(ens.completed) == 12


def test_changing_only_g_compiles_exactly_one_body(monkeypatch):
    g = make_grid(n=8, hood=3)
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    spec = adv.batch_step_spec()
    assert spec.wide is not None and spec.wide.budget >= 3
    s0 = adv.initialize_state()
    dt = np.float64(0.4 * adv.max_time_step(s0))
    sched = Scheduler()
    sched.submit(Scenario(adv, s0, steps=64, dt=dt))
    sched.admit()
    cohort = next(iter(sched.cohorts.values()))
    cohort.step(3)                        # warm (k=3, g=3)
    before = counter_total("epoch.recompiles")
    cohort.step(3)                        # held (k, g): re-dispatch
    assert counter_total("epoch.recompiles") == before
    monkeypatch.setenv("DCCRG_HALO_DEPTH", "2")
    assert halo_depth_cap() == 2
    cohort.step(3)                        # same k, g drops to 2: ONE body
    assert counter_total("epoch.recompiles") == before + 1
    monkeypatch.delenv("DCCRG_HALO_DEPTH")
    cohort.step(3)                        # g=3 body still cached
    assert counter_total("epoch.recompiles") == before + 1
    # the cache key really carries g
    assert (cohort_key(spec, cohort.W, 3, wide_g=3)
            != cohort_key(spec, cohort.W, 3, wide_g=2))


# --------------------------------------------------------- scheduling


def test_select_k_clamps_to_exchange_budget():
    """A scheduled wide dispatch pays exactly ONE exchange: select_k
    clamps the configured depth to the cohort's member-min budget."""
    g, gol = make_gol(hood=2)             # budget 2
    cells = g.get_cells()
    s0 = gol.new_state(alive_cells=cells[::2])
    sched = Scheduler(steps_per_dispatch=16)
    sched.submit(Scenario(gol, s0, steps=64))
    sched.admit()
    cohort = next(iter(sched.cohorts.values()))
    assert cohort._wide is not None and cohort._wide_budget == 2
    assert sched.select_k(cohort) == 2
    # remaining-budget clamp still applies on top
    cohort._remaining[:] = np.where(cohort._occupied, 1, 0)
    assert sched.select_k(cohort) == 1


# ---------------------------------------------------------- telemetry


def test_exchanges_per_step_gauge_drops_to_one_over_k():
    halo._amortization.clear()
    g, gol = make_gol(hood=2)
    cells = g.get_cells()
    s0 = gol.new_state(alive_cells=cells[::2])
    sched = Scheduler()
    sched.submit(Scenario(gol, s0, steps=64))
    sched.admit()
    cohort = next(iter(sched.cohorts.values()))
    cohort.step(2)                        # wide: 1 exchange / 2 steps
    rep = obs.metrics.report()
    assert rep["gauges"]["halo.exchanges_per_step"]["model=gol"] == 0.5
    cohort.step(4)                        # 2 exchanges / 4 steps
    rep = obs.metrics.report()
    assert rep["gauges"]["halo.exchanges_per_step"]["model=gol"] == 0.5
    halo._amortization.clear()
    halo.record_dispatch_exchanges("gol", 4, 4)   # legacy body: 1.0
    rep = obs.metrics.report()
    assert rep["gauges"]["halo.exchanges_per_step"]["model=gol"] == 1.0


# ------------------------------------------------------------ solo run()


def test_run_equals_k_steps_advection_and_vlasov():
    """The solo ``run()`` of Advection and Vlasov advances the state
    exactly as ``k`` calls of ``step()`` do, and leaves its input state
    readable (no donation)."""
    g = make_grid(hood=0)
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    s0 = adv.initialize_state()
    dt = np.float64(0.4 * adv.max_time_step(s0))
    out = adv.run(s0, 3, dt)
    solo = s0
    for _ in range(3):
        solo = adv.step(solo, dt)
    np.testing.assert_array_equal(np.asarray(solo["density"]),
                                  np.asarray(out["density"]))

    g2 = make_grid(hood=0)
    vl = Vlasov(g2, nv=2, dtype=np.float32)
    sv = vl.initialize_state()
    dtv = np.float32(0.5 * vl.max_time_step())
    out2 = vl.run(sv, 3, dtv)
    solo = sv
    for _ in range(3):
        solo = vl.step(solo, dtv)
    np.testing.assert_array_equal(np.asarray(solo["f"]),
                                  np.asarray(out2["f"]))


# --------------------------------------------------------- wide plans


def test_wide_plan_budget_matches_hood_depth():
    """A depth-g default hood funds g face-stencil steps; the Moore
    sub-hood (whole-neighborhood relevance) funds g radius-1 steps."""
    g = make_grid(n=8, hood=2)
    assert get_wide_plan(g, None, relevance="face").budget == 2
    g2, gol = make_gol(n=8, hood=2)
    assert get_wide_plan(g2, GOL_HOOD, relevance="all").budget == 2
