"""Vlasov (velocity-block-per-cell) stretch workload tests."""
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh
from dccrg_tpu.models.vlasov import Vlasov


def make(n=8, nz=8, n_dev=None, periodic=(True, True, True)):
    return (
        Grid()
        .set_initial_length((n, n, nz))
        .set_neighborhood_length(0)
        .set_periodic(*periodic)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n, 1.0 / n, 1.0 / nz),
        )
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )


def test_general_path_converges_to_dense_on_uniform_grid():
    """A uniform grid whose partition is NOT slab-aligned (RCB) takes
    the general row-layout path.  The dense layout dimension-splits the
    update while the general path prices all faces unsplit (inheriting
    the oracle-validated advection face machinery), so the two differ by
    the O(dt) splitting error — the same evolved time must agree better
    as dt halves, and exactly in mass."""
    def evolve(dt_frac, steps):
        g_d = make(n=4, nz=8, n_dev=8)
        vl_d = Vlasov(g_d, nv=3, dtype=np.float64)
        assert vl_d.info is not None
        g_g = (
            Grid()
            .set_initial_length((4, 4, 8))
            .set_neighborhood_length(0)
            .set_periodic(True, True, True)
            .set_load_balancing_method("RCB")
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(0.25, 0.25, 0.125),
            )
            .initialize(mesh=make_mesh(n_devices=8))
        )
        g_g.balance_load()
        vl_g = Vlasov(g_g, nv=3, dtype=np.float64)
        assert vl_g.info is None, "RCB partition must take the general path"
        dt = dt_frac * vl_d.max_time_step()
        s_d = vl_d.run(vl_d.initialize_state(), steps, dt)
        s_g = vl_g.run(vl_g.initialize_state(), steps, dt)
        assert vl_g.total_mass(s_g) == pytest.approx(
            vl_d.total_mass(s_d), rel=1e-12
        )
        cells = np.sort(g_g.leaves.cells)
        f_g = np.asarray(g_g.get_cell_data(s_g, "f", cells), np.float64)
        f_d_grid = np.asarray(s_d["f"], np.float64).reshape(
            8, 4, 4, vl_d.B
        )
        lin = (cells - 1).astype(np.int64)
        f_d = f_d_grid[lin // 16, (lin // 4) % 4, lin % 4]
        return np.abs(f_g - f_d).max() / np.abs(f_d).max()

    err_coarse = evolve(0.4, 4)    # same evolved time: 4 x 0.4 CFL
    err_fine = evolve(0.2, 8)      # ... as 8 x 0.2 CFL
    assert err_coarse < 0.05, err_coarse
    assert err_fine < 0.62 * err_coarse, (err_fine, err_coarse)


def _refined_grid(n_dev=8):
    g = (
        Grid()
        .set_initial_length((6, 6, 6))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / 6,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    r = np.linalg.norm(c - 0.5, axis=1)
    for cid in ids[r < 0.3]:
        g.refine_completely(int(cid))
    g.stop_refining()
    return g


def test_refined_grid_per_bin_matches_advection():
    """The AMR Vlasov path vs the oracle it is built to equal: each
    velocity bin advects with a spatially-constant velocity, which is
    exactly the (validated) general advection step with constant
    velocity fields — per bin, the two must agree to f64 roundoff on a
    refined grid."""
    from dccrg_tpu.models import Advection

    g = _refined_grid()
    ids = np.sort(g.leaves.cells)
    vl = Vlasov(g, nv=2, dtype=np.float64)
    assert vl.info is None
    s = vl.initialize_state()
    dt = 0.3 * vl.max_time_step()
    steps = 5
    out = vl.run(s, steps, dt)
    f0 = np.asarray(g.get_cell_data(s, "f", ids), np.float64)
    fT = np.asarray(g.get_cell_data(out, "f", ids), np.float64)

    adv = Advection(g, dtype=np.float64, use_pallas=False,
                    allow_boxed=False)
    for b in (0, 3, 7):
        sa = adv.initialize_state()
        sa = adv.set_cell_data(sa, "density", ids, f0[:, b])
        for d, name in enumerate(("vx", "vy", "vz")):
            sa = adv.set_cell_data(
                sa, name, ids, np.full(len(ids), vl.v_bins[b, d])
            )
        sa = g.update_copies_of_remote_neighbors(sa)
        for _ in range(steps):
            sa = adv.step(sa, dt)
        want = np.asarray(g.get_cell_data(sa, "density", ids), np.float64)
        np.testing.assert_allclose(fT[:, b], want, rtol=1e-12, atol=1e-15)


def test_refined_open_boundaries_outflow():
    """Open boundaries on the general/AMR path are vacuum-inflow /
    free-outflow like the dense path — not silent zero-flux walls:
    phase-space density must LEAVE the box monotonically."""
    g = (
        Grid()
        .set_initial_length((6, 6, 6))
        .set_neighborhood_length(0)
        .set_periodic(False, False, False)
        .set_maximum_refinement_level(1)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / 6,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=8))
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    r = np.linalg.norm(c - 0.5, axis=1)
    for cid in ids[r < 0.3]:
        g.refine_completely(int(cid))
    g.stop_refining()
    vl = Vlasov(g, nv=3, dtype=np.float64)
    assert vl.info is None
    s = vl.initialize_state()
    dt = 0.5 * vl.max_time_step()
    masses = [vl.total_mass(s)]
    for _ in range(6):
        s = vl.run(s, 10, dt)
        masses.append(vl.total_mass(s))
    assert all(m1 < m0 for m0, m1 in zip(masses, masses[1:])), masses
    assert masses[-1] < 0.9 * masses[0], "mass must actually drain"
    assert (np.asarray(s["f"]) >= -1e-12).all()


def test_general_cfl_bound_is_unsplit_and_stable():
    """max_time_step on the general path uses the unsplit donor-cell
    bound (sum over dimensions), tighter than the split dense bound —
    and running AT that bound stays stable."""
    g = _refined_grid(1)
    vl = Vlasov(g, nv=3, dtype=np.float64)
    lmin = float(g.geometry.get_length(g.get_cells()).min())
    vmax = float(np.abs(vl.v_bins).max())
    split_bound = lmin / vmax
    dt_max = vl.max_time_step()
    assert dt_max < split_bound  # strictly tighter (3 active dims)
    s = vl.initialize_state()
    m0 = vl.total_mass(s)
    s = vl.run(s, 30, 0.99 * dt_max)
    f = np.asarray(s["f"], np.float64)
    assert np.isfinite(f).all()
    assert (f >= -1e-10).all(), "negative density = instability"
    assert vl.total_mass(s) == pytest.approx(m0, rel=1e-12)


def test_refined_grid_mass_conserved_and_device_invariant():
    outs = {}
    for n_dev in (1, 8):
        g = _refined_grid(n_dev)
        vl = Vlasov(g, nv=3, dtype=np.float64)
        s = vl.initialize_state()
        m0 = vl.total_mass(s)
        dt = 0.3 * vl.max_time_step()
        s = vl.run(s, 10, dt)
        assert vl.total_mass(s) == pytest.approx(m0, rel=1e-12)
        ids = np.sort(g.leaves.cells)
        outs[n_dev] = np.asarray(g.get_cell_data(s, "f", ids), np.float64)
    np.testing.assert_allclose(outs[1], outs[8], rtol=1e-12, atol=1e-15)


def test_mass_conservation():
    g = make()
    vl = Vlasov(g, nv=4, dtype=np.float64)
    state = vl.initialize_state()
    m0 = vl.total_mass(state)
    dt = 0.3 * vl.max_time_step()
    state = vl.run(state, 20, dt)
    assert vl.total_mass(state) == pytest.approx(m0, rel=1e-12)
    f = np.asarray(state["f"])
    assert (f >= -1e-12).all()


def test_single_bin_translates():
    """With all mass in one velocity bin, the density hump translates
    rigidly at that bin's velocity."""
    g = make(n=16, nz=8, n_dev=8)
    vl = Vlasov(g, nv=2, v_max=0.5, dtype=np.float64)
    state = vl.initialize_state()
    # put all mass in the bin with velocity (+0.25, +0.25, +0.25)
    vbin = np.argmin(np.abs(vl.v_bins - 0.25).sum(axis=1))
    f = np.array(state["f"])
    dens = f.sum(-1)
    f[:] = 0
    f[..., vbin] = dens
    import jax, jax.numpy as jnp
    from dccrg_tpu.parallel.mesh import shard_spec

    state = {"f": jax.device_put(jnp.asarray(f), shard_spec(g.mesh, 5))}
    peak0 = _density_peak(g, vl, state)
    dt = 0.25 * vl.max_time_step()
    steps = int(round(0.4 / dt))
    state = vl.run(state, steps, dt)
    peak1 = _density_peak(g, vl, state)
    expect = peak0 + 0.25 * steps * dt
    # upwind diffusion smears the hump; the peak still tracks the bin
    # velocity to within a cell or two
    np.testing.assert_allclose(peak1, expect, atol=0.15)
    # and mass stays exact
    assert vl.total_mass(state) == pytest.approx(
        float(dens.sum() * np.prod(g.geometry.get_level_0_cell_length())), rel=1e-12
    )


def _density_peak(g, vl, state):
    dens = vl.density(state)
    info = vl.info
    cells = g.get_cells()
    centers = g.geometry.get_center(cells)
    lin = (cells - np.uint64(1)).astype(np.int64)
    x = lin % info.nx
    y = (lin // info.nx) % info.ny
    z = lin // (info.nx * info.ny)
    w = dens[z // info.nz_local, z % info.nz_local, y, x]
    return centers[np.argmax(w)]


def test_open_boundaries_outflow():
    """Non-periodic dimensions are vacuum-inflow/free-outflow: mass leaves
    the box monotonically and never goes negative (grid.topology is
    honored, not assumed periodic)."""
    g = make(periodic=(False, False, False))
    vl = Vlasov(g, nv=4, dtype=np.float64)
    state = vl.initialize_state()
    dt = 0.3 * vl.max_time_step()
    masses = [vl.total_mass(state)]
    for _ in range(5):
        state = vl.run(state, 5, dt)
        masses.append(vl.total_mass(state))
    assert all(m1 < m0 for m0, m1 in zip(masses, masses[1:]))
    assert (np.asarray(state["f"]) >= -1e-12).all()


def test_mixed_periodicity_device_invariance():
    """Open-z boundary rides the slab ring with the wrap plane zeroed on
    the edge devices only — result must not depend on the device count."""
    res = []
    for n_dev in (1, 8):
        g = make(n_dev=n_dev, periodic=(True, True, False))
        vl = Vlasov(g, nv=3, dtype=np.float64)
        state = vl.initialize_state()
        dt = 0.3 * vl.max_time_step()
        state = vl.run(state, 10, dt)
        res.append(vl.density(state).reshape(-1, vl.info.ny, vl.info.nx))
    np.testing.assert_allclose(res[0], res[1], rtol=1e-12, atol=1e-15)


def test_device_count_invariance():
    res = []
    for n_dev in (1, 8):
        g = make(n_dev=n_dev)
        vl = Vlasov(g, nv=3, dtype=np.float64)
        state = vl.initialize_state()
        dt = 0.3 * vl.max_time_step()
        state = vl.run(state, 10, dt)
        res.append(vl.density(state).reshape(-1, vl.info.ny, vl.info.nx))
    np.testing.assert_allclose(res[0], res[1], rtol=1e-12, atol=1e-15)


def _assert_fused_matches(a, b):
    """The Pallas interpreter reproduces the XLA body bit for bit."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert np.array_equal(a, b), np.abs(a - b).max()


@pytest.mark.parametrize("n_dev,nz", [(1, 8), (2, 8), (1, 16), (2, 32)])
@pytest.mark.parametrize(
    "periodic",
    [(True, True, True), (True, False, False), (False, False, False)],
)
def test_fused_step_matches_xla(n_dev, nz, periodic):
    """The blocked fused kernel (one HBM pass, halo planes re-split in
    VMEM) matches the XLA three-split body bit for bit — including
    multi-block devices (nzl > block: interior strided halo rows and the
    cross-block zi splice) and open boundaries on every axis."""
    g = make(n=8, nz=nz, n_dev=n_dev, periodic=periodic)
    fast = Vlasov(g, nv=4, dtype=np.float32, use_pallas="interpret")
    slow = Vlasov(g, nv=4, dtype=np.float32, use_pallas=False)
    assert fast._fused_block > 0
    nzl = nz // (n_dev or 1)
    if nz >= 16:
        assert nzl > fast._fused_block, "must exercise the m>1 path"
    assert slow._fused_block == 0
    s = fast.initialize_state()
    dt = np.float32(0.4 * fast.max_time_step())
    a = np.asarray(fast.run(s, 5, dt)["f"])
    b = np.asarray(slow.run(s, 5, dt)["f"])
    _assert_fused_matches(a, b)
