"""Boxed (per-level dense) AMR advection path vs the general gather path.

The boxed layout (``parallel/boxed.py``) must reproduce the general path's
update exactly up to floating-point association order: same face set, same
upwind choices, same v_face interpolation (reference semantics
``tests/advection/solve.hpp:129-260``).  In f64 the two paths agree to
~1e-13 over tens of steps; mass conservation is exact to roundoff.
"""
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh
from dccrg_tpu.geometry.stretched import StretchedCartesianGeometry
from dccrg_tpu.models import Advection


def _grid(n=8, maxref=1, periodic=(True, True, True), n_devices=1,
          refine_center=(0.3, 0.5, 0.5), radii=(0.25,)):
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(*periodic)
        .set_maximum_refinement_level(maxref)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n, 1.0 / n, 1.0 / n),
        )
        .initialize(mesh=make_mesh(n_devices=n_devices))
    )
    for r_ref in radii:
        ids = g.get_cells()
        c = g.geometry.get_center(ids)
        r = np.linalg.norm(c - np.asarray(refine_center), axis=1)
        for cid in ids[r < r_ref]:
            g.refine_completely(int(cid))
        g.stop_refining()
    return g


def _compare(g, steps=8):
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    assert adv.boxed is not None
    state = adv.initialize_state()
    dt = np.float64(0.4 * adv.max_time_step(state))
    flat = state
    for _ in range(steps):
        flat = adv._step(flat, dt)
    boxed = adv._boxed_run(state, steps, dt)
    local = np.asarray(adv.tables.local_mask)
    a = np.asarray(flat["density"])[local]
    b = np.asarray(boxed["density"])[local]
    np.testing.assert_allclose(b, a, rtol=1e-12, atol=1e-13)
    assert np.isclose(adv.total_mass(boxed), adv.total_mass(state), rtol=1e-12)
    return adv


def test_boxed_matches_flat_full_3d_velocity():
    # the stock rotating hump has vz == 0; exercise the z-axis kernel path
    # (axis map, z areas, z face masks, z cross-level faces) with a fully
    # 3-D divergence-free-ish velocity field
    g = _grid(n=8, maxref=1)
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    assert adv.boxed is not None
    state = adv.initialize_state()
    cells = g.get_cells()
    c = g.geometry.get_center(cells)
    state = g.set_cell_data(state, "vx", cells, np.sin(2 * np.pi * c[:, 2]) + 0.1)
    state = g.set_cell_data(state, "vy", cells, np.cos(2 * np.pi * c[:, 0]) - 0.2)
    state = g.set_cell_data(state, "vz", cells, np.sin(2 * np.pi * c[:, 1]) + 0.3)
    state = adv._exchange(state)
    dt = np.float64(0.4 * adv.max_time_step(state))
    flat = state
    for _ in range(8):
        flat = adv._step(flat, dt)
    boxed = adv._boxed_run(state, 8, dt)
    local = np.asarray(adv.tables.local_mask)
    np.testing.assert_allclose(
        np.asarray(boxed["density"])[local],
        np.asarray(flat["density"])[local],
        rtol=1e-12,
        atol=1e-13,
    )
    assert np.isclose(adv.total_mass(boxed), adv.total_mass(state), rtol=1e-12)


def test_boxed_matches_flat_refined_periodic():
    adv = _compare(_grid(n=8, maxref=1))
    assert len(adv.boxed.pairs) == 1  # one adjacent level pair (1 | 0)


def test_boxed_matches_flat_wrap_corner():
    # refined region spanning the periodic corner: cross-level faces wrap
    # in every axis, exercising the wrapped upsample window and the
    # wrapped pooled-plane adds
    _compare(_grid(n=8, maxref=1, refine_center=(0.0, 0.0, 0.0), radii=(0.3,)),
             steps=12)


def test_boxed_matches_flat_wrap_high_edge():
    # refined region at the HIGH domain corner: the last pooled row wraps
    # to coarse coordinate 0, outside pool_route's main in-domain block,
    # so it must be routed by its own single-row segment
    _compare(_grid(n=8, maxref=1, refine_center=(1.0, 1.0, 1.0), radii=(0.3,)),
             steps=12)


def test_boxed_matches_flat_refined_nonperiodic():
    _compare(_grid(n=8, maxref=1, periodic=(False, False, False)))


def test_boxed_matches_flat_two_levels():
    adv = _compare(_grid(n=8, maxref=2, radii=(0.3, 0.15)))
    levels = sorted(adv.boxed.boxes)
    assert levels == [0, 1, 2]
    assert sorted((p.fine_level, p.coarse_level) for p in adv.boxed.pairs) == [
        (1, 0),
        (2, 1),
    ]


def test_boxed_uniform_single_level():
    # uniform but refinable grid: one box covering the whole domain,
    # no interface groups, pure dense rolls
    g = _grid(n=6, maxref=1, radii=())
    adv = _compare(g)
    assert len(adv.boxed.pairs) == 0
    assert list(adv.boxed.boxes) == [0]


def test_boxed_run_equals_repeated_boxed_runs():
    g = _grid(n=8, maxref=1)
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    state = adv.initialize_state()
    dt = np.float64(0.4 * adv.max_time_step(state))
    once = adv._boxed_run(state, 6, dt)
    twice = adv._boxed_run(adv._boxed_run(state, 3, dt), 3, dt)
    np.testing.assert_allclose(
        np.asarray(once["density"]), np.asarray(twice["density"]),
        rtol=1e-13, atol=1e-15,
    )


@pytest.mark.parametrize("n_devices", [2, 4, 8])
def test_boxed_multi_device_matches_flat(n_devices):
    # the z-slab boxed layout engages on any device count dividing nz;
    # every device prices the faces registered in its padded slab (cut and
    # periodic-seam faces included) and the result matches the general
    # gather path
    adv = _compare(_grid(n=8, maxref=1, n_devices=n_devices), steps=8)
    assert adv.boxed.n_devices == n_devices


def test_boxed_multi_device_wrap_corner():
    # refined region spanning the periodic corner across device cuts
    _compare(
        _grid(n=8, maxref=1, n_devices=4, refine_center=(0.0, 0.0, 0.0),
              radii=(0.3,)),
        steps=12,
    )


def test_boxed_multi_device_two_levels():
    adv = _compare(_grid(n=8, maxref=2, n_devices=2, radii=(0.3, 0.15)),
                   steps=8)
    assert sorted(adv.boxed.boxes) == [0, 1, 2]


def test_boxed_multi_device_matches_single_device():
    # same grid, 1 vs 4 devices: the boxed update is association-order
    # identical, so results agree to the last ulp
    outs = []
    for nd in (1, 4):
        g = _grid(n=8, maxref=1, n_devices=nd)
        adv = Advection(g, dtype=np.float64, allow_dense=False)
        assert adv.boxed is not None
        state = adv.initialize_state()
        out = adv._boxed_run(state, 10, np.float64(0.02))
        ids = np.sort(g.get_cells())
        outs.append(np.asarray(g.get_cell_data(out, "density", ids)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-14, atol=1e-16)


def test_boxed_disabled_non_slab_partition():
    # a non-z-slab ownership (RCB repartition) falls back to the gather path
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
            level_0_cell_length=(1.0 / n, 1.0 / n, 1.0 / n),
        )
        .initialize(mesh=make_mesh(n_devices=2))
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    r = np.linalg.norm(c - np.array([0.3, 0.5, 0.5]), axis=1)
    for cid in ids[r < 0.25]:
        g.refine_completely(int(cid))
    g.stop_refining()
    g.balance_load()
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    assert adv.boxed is None
    # ZSLAB rebalancing restores the slab ownership and the fast path
    g._lb_method = "ZSLAB"
    g.balance_load()
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    assert adv.boxed is not None and adv.boxed.n_devices == 2


def test_boxed_disabled_stretched_geometry():
    n = 6
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_geometry(
            StretchedCartesianGeometry,
            coordinates=[np.linspace(0.0, 1.0, n + 1) ** 1.3] * 3,
        )
        .initialize(mesh=make_mesh(n_devices=1))
    )
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    assert adv.boxed is None


def test_boxed_used_by_run():
    g = _grid(n=8, maxref=1)
    adv = Advection(g, dtype=np.float64, allow_dense=False)
    state = adv.initialize_state()
    dt = np.float64(0.4 * adv.max_time_step(state))
    out_run = adv.run(state, 5, dt)
    out_boxed = adv._boxed_run(state, 5, dt)
    np.testing.assert_array_equal(
        np.asarray(out_run["density"]), np.asarray(out_boxed["density"])
    )


def test_boxed_refinement_across_periodic_seam():
    """Regression: a refined region CROSSING periodic boundaries (fine
    box covering the wrapped axes) must not price phantom cross-level
    fluxes — the z-ring wrap pad of the cross-face masks used to copy
    interior registrations onto the far ring row, which local mode's
    pooled wrap segments delivered into the opposite coarse plane."""
    import jax.numpy as jnp

    def dist_periodic(c, p):
        d = np.abs(c - p)
        d = np.minimum(d, 1 - d)
        return np.linalg.norm(d, axis=1)

    n = 8
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=1))
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    r = dist_periodic(c, np.zeros(3))     # ball at the corner: wraps all axes
    for cid in ids[r < 0.28]:
        g.refine_completely(int(cid))
    g.stop_refining()
    ids = g.get_cells()

    adv = Advection(g, dtype=np.float32, use_pallas=False)
    s0 = adv.initialize_state()
    rng = np.random.default_rng(0)
    cen = g.geometry.get_center(ids)
    s0 = adv.set_cell_data(
        s0, "density", ids, rng.uniform(1, 2, len(ids)).astype(np.float32)
    )
    s0 = adv.set_cell_data(
        s0, "vz", ids, (0.3 * np.sin(2 * np.pi * cen[:, 2])).astype(np.float32)
    )
    dt = np.float32(0.3 * adv.max_time_step(s0))
    b = adv._boxed_run(s0, jnp.asarray(3, jnp.int32), dt)
    st = s0
    for _ in range(3):
        st = adv.step(st, dt)
    np.testing.assert_allclose(
        np.asarray(adv.get_cell_data(b, "density", ids)),
        np.asarray(adv.get_cell_data(st, "density", ids)),
        rtol=3e-6, atol=1e-7,
    )


@pytest.mark.parametrize("n_dev", [2, 4])
def test_boxed_slab_refinement_across_periodic_seam(n_dev):
    """Slab mode prices wrap-adjacent refinement correctly too: a
    corner-centered refined ball (crossing every periodic boundary,
    including the z seam between the wrap-adjacent slabs) matches the
    general gather path.  Velocity ghosts must be refreshed after
    set_cell_data for the general path — the reference's own usage
    pattern (examples update copies after initialization)."""
    import jax.numpy as jnp

    def dist_periodic(c, p):
        d = np.abs(c - p)
        d = np.minimum(d, 1 - d)
        return np.linalg.norm(d, axis=1)

    n = 8
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    r = dist_periodic(c, np.zeros(3))
    for cid in ids[r < 0.28]:
        g.refine_completely(int(cid))
    g.stop_refining()
    ids = g.get_cells()

    adv = Advection(g, dtype=np.float32, use_pallas=False)
    assert adv.boxed is not None
    s0 = adv.initialize_state()
    rng = np.random.default_rng(0)
    cen = g.geometry.get_center(ids)
    s0 = adv.set_cell_data(
        s0, "density", ids, rng.uniform(1, 2, len(ids)).astype(np.float32)
    )
    s0 = adv.set_cell_data(
        s0, "vz", ids, (0.3 * np.sin(2 * np.pi * cen[:, 2])).astype(np.float32)
    )
    s0 = g.update_copies_of_remote_neighbors(s0)
    dt = np.float32(0.3 * adv.max_time_step(s0))
    b = adv._boxed_run(s0, jnp.asarray(3, jnp.int32), dt)
    st = s0
    for _ in range(3):
        st = adv.step(st, dt)
    np.testing.assert_allclose(
        np.asarray(adv.get_cell_data(b, "density", ids)),
        np.asarray(adv.get_cell_data(st, "density", ids)),
        rtol=3e-6, atol=1e-7,
    )


@pytest.mark.parametrize("seed", [1, 4, 7, 11])
def test_fuzz_paths_agree(seed):
    """Differential check on random refined grids (random periodicity,
    device count, velocities, scattered refinement): the boxed and flat
    paths must match the general gather path.  Seed 4 is the regression
    for the slab-mode wrap-seam cross-face rings (mode-dependent ring
    padding)."""
    import jax.numpy as jnp

    from dccrg_tpu.models import Advection as Adv

    rng = np.random.default_rng(seed)
    n = int(rng.choice([4, 6, 8]))
    n_dev = int(rng.choice([1, 2, 4]))
    periodic = tuple(bool(b) for b in rng.integers(0, 2, 3))
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(*periodic)
        .set_maximum_refinement_level(1)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    ids = g.get_cells()
    for cid in rng.choice(ids, size=max(1, int(0.3 * len(ids))),
                          replace=False):
        g.refine_completely(int(cid))
    g.stop_refining()
    ids = g.get_cells()
    if g.mapping.get_refinement_level(ids).max() == 0:
        pytest.skip("all refinement requests vetoed")

    adv = Adv(g, dtype=np.float32, use_pallas=False)
    flat = Adv(g, dtype=np.float32,
               use_pallas="interpret" if n_dev == 1 else True)
    s0 = adv.initialize_state()
    s0 = adv.set_cell_data(
        s0, "density", ids, rng.uniform(1, 2, len(ids)).astype(np.float32)
    )
    for f in ("vx", "vy", "vz"):
        s0 = adv.set_cell_data(
            s0, f, ids, rng.uniform(-0.3, 0.3, len(ids)).astype(np.float32)
        )
    s0 = g.update_copies_of_remote_neighbors(s0)
    dt = np.float32(0.3 * adv.max_time_step(s0))
    st = s0
    for _ in range(3):
        st = adv.step(st, dt)
    ref = np.asarray(adv.get_cell_data(st, "density", ids), np.float64)
    scale = np.abs(ref).max()
    if getattr(adv, "_boxed_run", None) is not None:
        b = adv._boxed_run(s0, jnp.asarray(3, jnp.int32), dt)
        rb = np.asarray(adv.get_cell_data(b, "density", ids), np.float64)
        assert np.abs(rb - ref).max() / scale < 5e-6
    if getattr(flat, "_flat_run", None) is not None:
        a = flat.run(s0, 3, dt)
        ra = np.asarray(flat.get_cell_data(a, "density", ids), np.float64)
        assert np.abs(ra - ref).max() / scale < 5e-6


@pytest.mark.parametrize("seed", [0, 5])
def test_fuzz_three_level_boxed(seed):
    """Three-level grids (two cross-level pairs in the boxed layout):
    random scattered refinement must match the general gather path."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n = int(rng.choice([4, 6]))
    n_dev = int(rng.choice([1, 2, 4]))
    periodic = tuple(bool(b) for b in rng.integers(0, 2, 3))
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(*periodic)
        .set_maximum_refinement_level(2)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    for frac in (0.3, 0.2):
        ids = g.get_cells()
        for cid in rng.choice(ids, size=max(1, int(frac * len(ids))),
                              replace=False):
            g.refine_completely(int(cid))
        g.stop_refining()
    ids = g.get_cells()
    if g.mapping.get_refinement_level(ids).max() < 2:
        pytest.skip("refinement did not reach level 2")
    adv = Advection(g, dtype=np.float32, use_pallas=False)
    if getattr(adv, "_boxed_run", None) is None:
        pytest.skip("boxed layout ineligible for this pattern")
    s0 = adv.initialize_state()
    s0 = adv.set_cell_data(
        s0, "density", ids, rng.uniform(1, 2, len(ids)).astype(np.float32)
    )
    for f in ("vx", "vy", "vz"):
        s0 = adv.set_cell_data(
            s0, f, ids, rng.uniform(-0.3, 0.3, len(ids)).astype(np.float32)
        )
    s0 = g.update_copies_of_remote_neighbors(s0)
    dt = np.float32(0.3 * adv.max_time_step(s0))
    st = s0
    for _ in range(3):
        st = adv.step(st, dt)
    ref = np.asarray(adv.get_cell_data(st, "density", ids), np.float64)
    b = adv._boxed_run(s0, jnp.asarray(3, jnp.int32), dt)
    rb = np.asarray(adv.get_cell_data(b, "density", ids), np.float64)
    assert np.abs(rb - ref).max() / np.abs(ref).max() < 5e-6


@pytest.mark.parametrize(
    "kw, steps",
    [
        (dict(), 8),                                          # refined periodic
        (dict(refine_center=(0.0, 0.0, 0.0), radii=(0.3,)), 12),  # wrap corner
        (dict(refine_center=(1.0, 1.0, 1.0), radii=(0.3,)), 12),  # wrap high edge
        (dict(maxref=2, radii=(0.3, 0.15)), 8),              # two levels
        (dict(radii=(0.3,)), 10),         # the ball touching x = 0, as benchmarked
        (dict(n_devices=4), 8),                               # slab mode
    ],
    ids=["refined", "wrap_corner", "wrap_high_edge", "two_levels",
         "ball_at_x0", "slab_4"],
)
def test_boxed_kernel_matches_xla_f32(kw, steps):
    """The boxed run with its Pallas moves (interpret mode) against the
    XLA form, both float32.  Every level of these grids is one ascending
    range of epoch rows on each device, so the moves carry every level;
    in slab mode the devices hold different numbers of a level's leaves,
    which the move back merges.  The moves only move values; the two
    programs agree to rounding (XLA's CPU compiler fuses multiply-adds
    differently in them)."""
    from dccrg_tpu import obs

    kw = {"n": 8, "maxref": 1, **kw}
    g = _grid(**kw)
    xla = Advection(g, dtype=np.float32, allow_dense=False, use_pallas=False)
    kern = Advection(g, dtype=np.float32, allow_dense=False,
                     use_pallas="interpret")
    L = len(kern.boxed.boxes)
    assert xla._boxed_moved == (False,) * L
    assert kern._boxed_moved == (True,) * L
    if kw.get("n_devices", 1) > 1:
        D = kw["n_devices"]
        counts = [b.leaf_mask.reshape(D, -1).sum(axis=1)
                  for b in kern.boxed.boxes.values()]
        assert any(len(set(c.tolist())) > 1 for c in counts)
    kern._flat_run = None           # dispatch the boxed run
    # signed velocities on every axis, so each face is upwind either way
    rng = np.random.default_rng(steps + len(kw))
    ids = g.get_cells()
    state = xla.initialize_state()
    state = xla.set_cell_data(
        state, "density", ids, rng.uniform(1, 2, len(ids)).astype(np.float32)
    )
    for f in ("vx", "vy", "vz"):
        state = xla.set_cell_data(
            state, f, ids, rng.uniform(-0.3, 0.3, len(ids)).astype(np.float32)
        )
    state = g.update_copies_of_remote_neighbors(state)
    dt = np.float32(0.4 * xla.max_time_step(state))
    obs.enable()
    obs.metrics.reset()
    want = xla.run(state, steps, dt)
    got = kern.run(state, steps, dt)
    assert obs.metrics.counter_value("boxed.kernel_runs", form="pallas") == 1
    assert obs.metrics.counter_value("boxed.kernel_runs", form="xla") == 1
    a = np.asarray(g.get_cell_data(got, "density", ids))
    b = np.asarray(g.get_cell_data(want, "density", ids))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6 * np.abs(b).max())
    assert np.isclose(kern.total_mass(got), kern.total_mass(state), rtol=1e-6)
