"""Advection workload tests: mass conservation, device-count invariance,
agreement with a dense serial oracle (the reference validates with a serial
implementation for poisson; advection here gets the same treatment)."""
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh
from dccrg_tpu.models import Advection


def make_adv(n=20, n_dev=None, max_ref=0):
    g = (
        Grid()
        .set_initial_length((n, n, 1))
        .set_maximum_refinement_level(max_ref)
        .set_neighborhood_length(0)
        .set_periodic(True, True, False)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n, 1.0 / n, 1.0 / n),
        )
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    return g, Advection(g)


def dense_oracle_step(rho, vx, vy, dx, dt):
    """Dense periodic upwind step with the reference's flux form on a
    uniform 2-D grid (area = dx, volume = dx*dx in the z-thin limit all
    cells share the z length so it cancels)."""
    area = dx * dx  # face area with unit-per-cell z length dx
    vol = dx * dx * dx
    new = rho.copy()
    for axis, v in ((0, vx), (1, vy)):
        vface = 0.5 * (v + np.roll(v, -1, axis=axis))  # face between i and i+1
        up = np.where(vface >= 0, rho, np.roll(rho, -1, axis=axis))
        flux = up * dt * vface * area
        new -= flux / vol
        new += np.roll(flux, 1, axis=axis) / vol
    return new


def test_max_time_step():
    g, adv = make_adv(n=20)
    state = adv.initialize_state()
    dt = adv.max_time_step(state)
    # max |v| ~ 0.5*sqrt(2) near corners; dt = dx / max|v_dim| >= dx / 0.5
    assert 0 < dt < 1.0
    assert dt == pytest.approx((1.0 / 20) / max(abs(-0.025 + 0.5), 0.475), rel=0.2)


def test_mass_conservation():
    g, adv = make_adv(n=16)
    state = adv.initialize_state()
    m0 = adv.total_mass(state)
    dt = 0.5 * adv.max_time_step(state)
    for _ in range(20):
        state = adv.step(state, dt)
    m1 = adv.total_mass(state)
    assert m1 == pytest.approx(m0, rel=1e-12)


def test_matches_dense_oracle():
    n = 16
    g, adv = make_adv(n=n)
    state = adv.initialize_state()
    cells = g.get_cells()
    dx = 1.0 / n

    # dense arrays indexed [x, y]
    def to_dense(field):
        vals = adv.get_cell_data(state, field, cells)
        idx = g.mapping.get_indices(cells)
        dense = np.zeros((n, n))
        dense[idx[:, 0], idx[:, 1]] = vals
        return dense

    rho = to_dense("density")
    vx = to_dense("vx")
    vy = to_dense("vy")

    dt = 0.25 * adv.max_time_step(state)
    for _ in range(5):
        state = adv.step(state, dt)
        rho = dense_oracle_step(rho, vx, vy, dx, dt)

    got = adv.get_cell_data(state, "density", cells)
    idx = g.mapping.get_indices(cells)
    np.testing.assert_allclose(got, rho[idx[:, 0], idx[:, 1]], rtol=1e-12, atol=1e-15)


def test_device_count_invariance():
    """Results must be independent of the device count.  The neighbor
    reduction order is fixed (ordered_sum) so the only residual source of
    difference is XLA choosing different FMA contractions for different
    block shapes — ulp-level, bounded here at 1e-13 relative.  Halo copies
    themselves are bit-identical (test_grid_halo), and a fixed device count
    is fully deterministic (asserted below)."""
    results = []
    for n_dev in (1, 4, 8):
        g, adv = make_adv(n=12, n_dev=n_dev)
        state = adv.initialize_state()
        dt = 0.5 * adv.max_time_step(state)
        for _ in range(10):
            state = adv.step(state, dt)
        results.append(adv.get_cell_data(state, "density", g.get_cells()))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-13, atol=1e-16)
    np.testing.assert_allclose(results[0], results[2], rtol=1e-13, atol=1e-16)

    # same device count, fresh build: bit-identical
    g2, adv2 = make_adv(n=12, n_dev=4)
    state = adv2.initialize_state()
    dt = 0.5 * adv2.max_time_step(state)
    for _ in range(10):
        state = adv2.step(state, dt)
    again = g2.get_cell_data(state, "density", g2.get_cells())
    np.testing.assert_array_equal(again, results[1])


def test_hump_rotates():
    n = 24
    g, adv = make_adv(n=n)
    state = adv.initialize_state()
    # the reference's default CFL is 0.5 (2d.cpp:124-126); 0.9 is unstable
    # for the dimension-split first-order upwind scheme
    dt = 0.45 * adv.max_time_step(state)
    # rotate ~90 degrees: t = pi/2
    steps = int(np.ceil((np.pi / 2) / dt))
    for _ in range(steps):
        state = adv.step(state, dt)
    cells = g.get_cells()
    rho = adv.get_cell_data(state, "density", cells)
    centers = g.geometry.get_center(cells)
    peak = centers[np.argmax(rho)]
    # hump starts at (0.25, 0.5); after quarter turn about (0.5, 0.5) it
    # should be near (0.5, 0.25) (numerical diffusion allows slack)
    assert abs(peak[0] - 0.5) < 0.15
    assert abs(peak[1] - 0.25) < 0.15


def test_max_diff_indicator():
    g, adv = make_adv(n=16)
    state = adv.initialize_state()
    state = adv.compute_max_diff(state, diff_threshold=0.025)
    md = adv.get_cell_data(state, "max_diff", g.get_cells())
    assert (md >= 0).all()
    # steep hump edge -> some large indicators; far field flat -> zeros
    assert md.max() > 1.0
    assert (md < 1e-12).sum() > len(md) / 4


def _path_grid(n=8, n_dev=1, radii=(), center=(0.45, 0.45, 0.45),
               stretched=False):
    """An n^3 periodic grid, a ball around ``center`` refined once per
    radius; ``stretched``: level 0 on a non-uniform mesh."""
    from dccrg_tpu.geometry.stretched import StretchedCartesianGeometry

    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(len(radii))
    )
    if stretched:
        g = g.set_geometry(
            StretchedCartesianGeometry,
            coordinates=[np.linspace(0.0, 1.0, n + 1) ** 1.3] * 3,
        )
    else:
        g = g.set_geometry(CartesianGeometry, start=(0.0, 0.0, 0.0),
                           level_0_cell_length=(1.0 / n,) * 3)
    g = g.initialize(mesh=make_mesh(n_devices=n_dev))
    for rad in radii:
        ids = g.get_cells()
        r = np.linalg.norm(g.geometry.get_center(ids) - np.asarray(center),
                           axis=1)
        lv = g.mapping.get_refinement_level(ids)
        for cid in ids[(r < rad) & (lv == lv.max())]:
            g.refine_completely(int(cid))
        g.stop_refining()
    return g


F32_INTERPRET = {"dtype": np.float32, "use_pallas": "interpret"}


@pytest.mark.parametrize("grid_kw, adv_kw, path, flat_kind", [
    ({}, F32_INTERPRET, "fused", None),
    ({}, dict(F32_INTERPRET, fused_budget=0), "dense", None),
    ({}, {"dtype": np.float32, "use_pallas": False}, "general", None),
    ({"radii": (0.28,)}, F32_INTERPRET, "flat", "pallas_interpret"),
    ({"radii": (0.3, 0.15), "n_dev": 2}, F32_INTERPRET, "boxed", "ml"),
    ({"n": 6, "radii": (0.6, 0.55)}, {}, "flat", "ml"),
    ({"radii": (0.3, 0.15)}, {"allow_boxed": False}, "general", None),
    ({"n": 6, "stretched": True}, {}, "general", None),
    ({"radii": (0.28,)}, {"overlap": True}, "split", None),
    ({"radii": (0.28,), "n_dev": 4}, {"dtype": np.float32}, "flat",
     "sharded"),
], ids=["uniform_fused", "uniform_past_fused_budget", "uniform_no_pallas",
        "ball_2_levels", "ball_3_levels_2_devices", "broad_ball_3_levels_f64",
        "ball_3_levels_no_boxed",
        "stretched", "overlap", "ball_2_levels_4_devices"])
def test_advection_path_decision(monkeypatch, grid_kw, adv_kw, path,
                                 flat_kind):
    """``Advection.path`` names the whole run the model chose, and one
    ``run()`` is counted under that label in ``fused.runs``."""
    from dccrg_tpu import obs
    from dccrg_tpu.ops import dense_advection

    adv_kw = dict(adv_kw)
    if "fused_budget" in adv_kw:
        # a uniform grid whose fused run does not fit its VMEM budget
        monkeypatch.setattr(dense_advection, "_FUSED_VMEM_BUDGET",
                            adv_kw.pop("fused_budget"))
    adv = Advection(_path_grid(**grid_kw), **adv_kw)
    assert adv.path == path
    assert adv.flat_kind == flat_kind
    state = adv.initialize_state()
    dt = 0.4 * adv.max_time_step(state)
    obs.enable()
    obs.metrics.reset()
    adv.run(state, 2, dt)
    runs = obs.metrics.report()["counters"].get("fused.runs", {})
    assert {k for k in runs if "model=advection" in k} == {
        f"model=advection,path={path}"}
    if path == "boxed":
        # every level moves through the Pallas moves, and is counted so
        assert adv._boxed_moved == (True,) * len(adv.boxed.boxes)
        assert obs.metrics.counter_value("boxed.kernel_runs",
                                         form="pallas") == 1
