"""Flat inflated two-level AMR kernel (ops/flat_amr.py) vs the boxed
per-level path: same physics to f32 rounding, exact mass conservation,
working open boundaries."""
import jax.numpy as jnp
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh
from dccrg_tpu.models import Advection


def make(periodic=(True, True, True), n=8):
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
        .initialize(mesh=make_mesh(n_devices=1))
    )
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    r = np.linalg.norm(c - 0.45, axis=1)
    for cid in ids[r < 0.28]:
        g.refine_completely(int(cid))
    g.stop_refining()
    return g


def seeded_state(adv, g):
    s0 = adv.initialize_state()
    ids = g.get_cells()
    cen = g.geometry.get_center(ids)
    vz = 0.3 * np.sin(2 * np.pi * cen[:, 2])
    vy = 0.2 + 0.1 * np.cos(2 * np.pi * cen[:, 1])
    s0 = adv.set_cell_data(s0, "vz", ids, vz.astype(np.float32))
    s0 = adv.set_cell_data(s0, "vy", ids, vy.astype(np.float32))
    return s0, ids


def lvl_mass(g, ids, rho):
    lvl = g.mapping.get_refinement_level(ids)
    return float(np.sum(np.asarray(rho, np.float64) * (1.0 / 8.0) ** lvl))


@pytest.mark.parametrize(
    "periodic", [(True, True, True), (True, False, True)]
)
def test_flat_matches_boxed(periodic):
    g = make(periodic)
    flat = Advection(g, dtype=np.float32, use_pallas="interpret")
    boxed = Advection(g, dtype=np.float32, use_pallas=False)
    assert flat.path == "flat"
    assert boxed.flat_kind is None  # gated on use_pallas
    s0, ids = seeded_state(flat, g)
    dt = np.float32(0.3 * flat.max_time_step(s0))

    a = flat.run(s0, 7, dt)  # dispatches to the flat kernel
    b = boxed.run(s0, 7, dt)
    ra = np.asarray(flat.get_cell_data(a, "density", ids), np.float64)
    rb = np.asarray(boxed.get_cell_data(b, "density", ids), np.float64)
    err = np.abs(ra - rb).max() / np.abs(rb).max()
    assert err < 2e-6, err

    m0 = lvl_mass(g, ids, flat.get_cell_data(s0, "density", ids))
    ma = lvl_mass(g, ids, ra)
    assert ma == pytest.approx(m0, rel=1e-6)


def test_flat_open_boundary_differs_from_periodic():
    """The weight-zeroed wrap faces really turn the boundary off."""

    def run(periodic):
        g = make(periodic)
        adv = Advection(g, dtype=np.float32, use_pallas="interpret")
        s0, ids = seeded_state(adv, g)
        dt = np.float32(0.3 * adv.max_time_step(s0))
        out = adv.run(s0, 7, dt)
        return np.asarray(adv.get_cell_data(out, "density", ids))

    ra = run((True, True, True))
    rb = run((True, False, True))
    assert np.abs(ra - rb).max() > 1e-4


def test_flat_gating():
    """f64, uniform grids, and multi-device stay off the flat path."""
    g = make()
    assert Advection(g).flat_kind is None  # f64 default

    n = 8
    gu = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=1))
    )
    adv = Advection(gu, dtype=np.float32, use_pallas="interpret")
    assert adv.dense is not None  # uniform grids take the dense path


@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize(
    "periodic", [(True, True, True), (True, False, True)]
)
def test_flat_sharded_matches_boxed(n_dev, periodic):
    """The multi-device flat path (z-slab-sharded voxel domain, two
    ppermuted planes per step, collective-free coarse pool) matches the
    boxed path and conserves mass; use_pallas=False opts out to the boxed
    numerics."""
    n = 8
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
    c = g.geometry.get_center(ids)
    r = np.linalg.norm(c - 0.45, axis=1)
    for cid in ids[r < 0.28]:
        g.refine_completely(int(cid))
    g.stop_refining()

    flat = Advection(g, dtype=np.float32)
    boxed = Advection(g, dtype=np.float32, use_pallas=False)
    assert (flat.path, flat.flat_kind) == ("flat", "sharded")  # no Pallas
    assert boxed.flat_kind is None  # opt-out honored
    s0, ids = seeded_state(flat, g)
    dt = np.float32(0.3 * flat.max_time_step(s0))
    a = flat.run(s0, 7, dt)
    b = boxed.run(s0, 7, dt)
    ra = np.asarray(flat.get_cell_data(a, "density", ids), np.float64)
    rb = np.asarray(boxed.get_cell_data(b, "density", ids), np.float64)
    assert np.abs(ra - rb).max() / np.abs(rb).max() < 2e-6
    m0 = lvl_mass(g, ids, flat.get_cell_data(s0, "density", ids))
    assert lvl_mass(g, ids, ra) == pytest.approx(m0, rel=1e-6)


def test_flat_sharded_device_count_invariant():
    """1-device (interpret kernel) and 4-device (sharded XLA) flat runs
    agree on the same grid and inputs."""

    def run(n_dev):
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
        r = np.linalg.norm(c - 0.45, axis=1)
        for cid in ids[r < 0.28]:
            g.refine_completely(int(cid))
        g.stop_refining()
        adv = Advection(
            g, dtype=np.float32,
            use_pallas="interpret" if n_dev == 1 else True,
        )
        assert adv.path == "flat"
        s0, ids = seeded_state(adv, g)
        dt = np.float32(0.3 * adv.max_time_step(s0))
        out = adv.run(s0, 7, dt)
        return np.asarray(adv.get_cell_data(out, "density", ids))

    r1 = run(1)
    r4 = run(4)
    np.testing.assert_allclose(r1, r4, rtol=2e-7, atol=1e-9)


def test_flat_run_feeds_adaptation_cycle():
    """A flat-path run's state drives check_for_adaptation/adapt_grid
    without conversion (the run returns the row layout), and the new
    model rebuilds its fast paths for the adapted grid."""
    g = make()
    adv = Advection(g, dtype=np.float32, use_pallas="interpret")
    assert adv.path == "flat"
    s0, ids = seeded_state(adv, g)
    dt = np.float32(0.3 * adv.max_time_step(s0))
    state = adv.run(s0, 5, dt)
    m0 = lvl_mass(g, ids, adv.get_cell_data(state, "density", ids))

    adv.check_for_adaptation(state)
    adv2, state2, _new, _removed = adv.adapt_grid(state)
    ids2 = adv2.grid.get_cells()
    m1 = lvl_mass(adv2.grid, ids2, adv2.get_cell_data(state2, "density", ids2))
    assert m1 == pytest.approx(m0, rel=1e-5)
    # the new model runs (flat rebuilt if the grid still qualifies,
    # boxed otherwise)
    out = adv2.run(state2, 3, np.float32(0.3 * adv2.max_time_step(state2)))
    m2 = lvl_mass(adv2.grid, ids2, adv2.get_cell_data(out, "density", ids2))
    assert m2 == pytest.approx(m1, rel=1e-5)


def test_refused_flat_kernel_falls_back_and_path_names_it():
    """A flat kernel the compiler refuses (a typed rejection) falls back
    to the path chosen without it, on the same inputs, and from then on
    ``path`` names that path and ``run()`` takes it."""
    from dccrg_tpu import obs

    g = make()
    adv = Advection(g, dtype=np.float32, use_pallas="interpret")
    assert (adv.path, adv.flat_kind) == ("flat", "pallas_interpret")

    def refused(state, steps, dt):
        raise NotImplementedError("Mosaic: unsupported lowering")

    adv._flat_run = refused
    s0, ids = seeded_state(adv, g)
    dt = np.float32(0.3 * adv.max_time_step(s0))
    obs.enable()
    obs.metrics.reset()
    out = adv.run(s0, 4, dt)
    assert (adv.path, adv.flat_kind) == ("boxed", None)
    assert obs.metrics.counter_value("kernel.fallbacks",
                                     label="flat AMR kernel",
                                     kind="disabled") == 1
    assert obs.metrics.counter_value("fused.runs", model="advection",
                                     path="boxed") == 1
    want = adv._boxed_run(s0, jnp.asarray(4, jnp.int32), dt)
    np.testing.assert_array_equal(np.asarray(out["density"]),
                                  np.asarray(want["density"]))
    adv.run(s0, 4, dt)
    assert obs.metrics.counter_value("fused.runs", model="advection",
                                     path="boxed") == 2
    assert obs.metrics.counter_value("fused.runs", model="advection",
                                     path="flat") == 1


def test_pad_lane_extent():
    from dccrg_tpu.ops.flat_amr import pad_lane_extent

    assert pad_lane_extent(128) == 128      # aligned: untouched
    assert pad_lane_extent(256) == 256
    assert pad_lane_extent(96) == 128       # the refined-bench extent
    assert pad_lane_extent(200) == 256
    assert pad_lane_extent(16) == 16        # pad would cost > max_factor
    assert pad_lane_extent(126) == 128      # needs 2 halo columns -> 256?
    # 126 + 2 = 128 exactly: fits the next multiple
    assert pad_lane_extent(127) == 256 or pad_lane_extent(127) == 127


@pytest.mark.parametrize("nx_extra", [2, 6])
@pytest.mark.parametrize(
    "periodic", [(True, True, True), (False, True, True)]
)
def test_flat_padded_kernel_bit_identical(periodic, nx_extra):
    """The lane-padded kernel (explicit wrap-halo columns) reproduces the
    unpadded kernel bit for bit: same operand values reach every flux."""
    from dccrg_tpu.ops.flat_amr import (
        build_flat_amr_tables,
        compute_flat_weights,
        make_flat_amr_run,
    )

    g = make(periodic)
    t = build_flat_amr_tables(g)
    assert t is not None
    nz1, ny1, nx1 = t["shape"]
    adv = Advection(g, dtype=np.float32, use_pallas="interpret")
    s0, ids = seeded_state(adv, g)
    rows = t["rows"]

    def field(name):
        return jnp.asarray(s0[name][0])[rows].reshape(nz1, ny1, nx1)

    V = field("density").astype(jnp.float32)
    (wpx, wnx), (wpy, wny), (wpz, wnz) = compute_flat_weights(
        t, field("vx"), field("vy"), field("vz")
    )
    leaf = t["leaf_fine"]
    updf = jnp.asarray(leaf.astype(np.float64) / t["vol_f"], jnp.float32)
    updc = jnp.asarray((~leaf).astype(np.float64) / t["vol_c"], jnp.float32)
    dt = np.float32(0.3 * adv.max_time_step(s0))

    k0 = make_flat_amr_run(nz1, ny1, nx1, interpret=True)
    kp = make_flat_amr_run(nz1, ny1, nx1, nx_pad=nx1 + nx_extra,
                           interpret=True)
    for steps in (4, 7):  # even + odd (ping-pong final copy)
        a = np.asarray(k0(V, wpx, wnx, wpy, wny, wpz, wnz,
                          updf, updc, dt, steps))
        b = np.asarray(kp(V, wpx, wnx, wpy, wny, wpz, wnz,
                          updf, updc, dt, steps))
        assert np.array_equal(a, b), np.abs(a - b).max()
