"""Dense fast path vs general gather path: same physics, same results."""
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh
from dccrg_tpu.models import Advection


def make(n=8, nz=8, periodic=(True, True, True), allow_dense=True, n_dev=None):
    g = (
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
    return g, Advection(g, allow_dense=allow_dense)


def test_dense_detected():
    g, adv = make()
    assert adv.dense is not None
    assert adv.dense.nz_local == 1
    g2, adv2 = make(nz=4)  # 4 planes over 8 devices -> not slab-aligned
    assert adv2.dense is None


@pytest.mark.parametrize("periodic", [(True, True, True), (True, False, False)])
def test_dense_matches_general(periodic):
    g1, dense = make(periodic=periodic)
    g2, general = make(periodic=periodic, allow_dense=False)
    assert dense.dense is not None and general.dense is None

    s1 = dense.initialize_state()
    s2 = general.initialize_state()
    cells = g1.get_cells()
    # seed a z-velocity so all six faces carry flux
    vz = 0.3 * np.sin(2 * np.pi * g1.geometry.get_center(cells)[:, 2])
    s1 = dense.set_cell_data(s1, "vz", cells, vz)
    s2 = general.set_cell_data(s2, "vz", cells, vz)
    s2 = g2.update_copies_of_remote_neighbors(s2)

    np.testing.assert_allclose(
        dense.get_cell_data(s1, "density", cells),
        general.get_cell_data(s2, "density", cells),
        rtol=0, atol=0,
    )
    dt = 0.4 * min(dense.max_time_step(s1), general.max_time_step(s2))
    for _ in range(8):
        s1 = dense.step(s1, dt)
        s2 = general.step(s2, dt)
    np.testing.assert_allclose(
        dense.get_cell_data(s1, "density", cells),
        general.get_cell_data(s2, "density", cells),
        rtol=1e-13, atol=1e-16,
    )


def test_dense_mass_conservation():
    g, adv = make()
    state = adv.initialize_state()
    m0 = adv.total_mass(state)
    dt = 0.4 * adv.max_time_step(state)
    for _ in range(20):
        state = adv.step(state, dt)
    assert adv.total_mass(state) == pytest.approx(m0, rel=1e-12)


def test_dense_single_device():
    g, adv = make(n_dev=1)
    assert adv.dense is not None
    state = adv.initialize_state()
    dt = 0.4 * adv.max_time_step(state)
    m0 = adv.total_mass(state)
    for _ in range(5):
        state = adv.step(state, dt)
    assert adv.total_mass(state) == pytest.approx(m0, rel=1e-12)


@pytest.mark.parametrize("periodic", [(True, True, True), (True, True, False)])
def test_pallas_integration_interpret(periodic):
    """The full Advection Pallas wiring (blocked per-step kernel in
    step(), fused whole-block kernel in run(), mask reshapes, device-dim
    handling) runs via the Pallas interpreter on CPU and matches the XLA
    dense path."""
    g, _ = make(periodic=periodic, n_dev=1)
    pal = Advection(g, dtype=np.float32, use_pallas="interpret")
    xla = Advection(g, dtype=np.float32, use_pallas=False)
    assert pal.path == "fused" and xla.path == "general"

    s0 = pal.initialize_state()
    cells = g.get_cells()
    vz = 0.3 * np.sin(2 * np.pi * g.geometry.get_center(cells)[:, 2])
    s0 = pal.set_cell_data(s0, "vz", cells, vz.astype(np.float32))
    dt = np.float32(0.4 * pal.max_time_step(s0))

    a = pal.step(s0, dt)
    b = xla.step(s0, dt)
    np.testing.assert_allclose(
        np.asarray(a["density"]), np.asarray(b["density"]), rtol=2e-7, atol=1e-9
    )

    a = pal.run(s0, 5, dt)
    b = s0
    for _ in range(5):
        b = xla.step(b, dt)
    np.testing.assert_allclose(
        np.asarray(a["density"]), np.asarray(b["density"]), rtol=1e-6, atol=1e-9
    )


def test_plane_kernel_interpret():
    """The fallback plane kernel (make_flux_update) still engages and
    matches XLA when no block size divides nzl (odd z extent) — the
    blocked kernel cannot be built there."""
    from dccrg_tpu.ops.dense_advection import pick_step_block

    g, _ = make(nz=7, n_dev=1)
    assert pick_step_block(7, 8, 8) == 0
    pal = Advection(g, dtype=np.float32, use_pallas="interpret")
    xla = Advection(g, dtype=np.float32, use_pallas=False)
    assert pal.dense_kind == ("plane",)  # blocked path did not engage

    s0 = pal.initialize_state()
    cells = g.get_cells()
    vz = 0.3 * np.sin(2 * np.pi * g.geometry.get_center(cells)[:, 2])
    s0 = pal.set_cell_data(s0, "vz", cells, vz.astype(np.float32))
    dt = np.float32(0.4 * pal.max_time_step(s0))
    a = pal.step(s0, dt)
    b = xla.step(s0, dt)
    np.testing.assert_allclose(
        np.asarray(a["density"]), np.asarray(b["density"]), rtol=2e-7, atol=1e-9
    )


@pytest.mark.parametrize("periodic", [(True, True, True), (True, True, False)])
@pytest.mark.parametrize("nz,n_dev", [(32, 1), (32, 4)])
@pytest.mark.parametrize("steps", [0, 1, 2, 5, 6])
def test_blocked_kernel_interpret(periodic, nz, n_dev, steps):
    """The blocked per-step kernel (multi-plane z-blocks, halo stacks
    spliced in VMEM) matches the XLA dense path — with several blocks per
    device (m>1, interior strided-slice halo rows) and across devices
    (ppermute-received edge rows) — and the two-buffer whole run equals
    ``steps`` successive steps, even and odd counts alike."""
    from dccrg_tpu.ops.dense_advection import pick_step_block

    g, _ = make(nz=nz, periodic=periodic, n_dev=n_dev)
    pal = Advection(g, dtype=np.float32, use_pallas="interpret")
    xla = Advection(g, dtype=np.float32, use_pallas=False)
    nzl = nz // n_dev
    assert pick_step_block(nzl, 8, 8) >= 2  # blocked path engages
    assert pal.dense_kind[0] == "blocked_direct"

    s0 = pal.initialize_state()
    cells = g.get_cells()
    vz = 0.3 * np.sin(2 * np.pi * g.geometry.get_center(cells)[:, 2])
    s0 = pal.set_cell_data(s0, "vz", cells, vz.astype(np.float32))
    dt = np.float32(0.4 * pal.max_time_step(s0))

    a = pal.step(s0, dt)
    b = xla.step(s0, dt)
    np.testing.assert_allclose(
        np.asarray(a["density"]), np.asarray(b["density"]), rtol=2e-7, atol=1e-9
    )

    # the hoisted multi-step run is ``steps`` of the same kernel's steps,
    # bit for bit (called directly: on one device run() would prefer the
    # whole-block fused kernel), and hands every other field back as given
    import jax.numpy as jnp

    a = pal._dense_run(s0, jnp.asarray(steps, jnp.int32), dt)
    b = c = s0
    for _ in range(steps):
        b = pal.step(b, dt)
        c = pal._dense_run(c, jnp.asarray(1, jnp.int32), dt)
    np.testing.assert_array_equal(
        np.asarray(a["density"]), np.asarray(c["density"])
    )
    if n_dev == 1:
        np.testing.assert_array_equal(
            np.asarray(a["density"]), np.asarray(b["density"])
        )
    else:
        # across CPU devices XLA contracts step()'s arithmetic in its own
        # module differently from the run's (~1 ulp, with a one-buffer
        # fori_loop run as well); on the chip both are the same kernel
        np.testing.assert_allclose(
            np.asarray(a["density"]), np.asarray(b["density"]),
            rtol=2e-7, atol=1e-9,
        )
    assert a.keys() == s0.keys()
    for name in s0:
        assert (a[name].shape, a[name].dtype) == (s0[name].shape, s0[name].dtype)
        if name != "density":
            np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(s0[name]))


@pytest.mark.parametrize("periodic", [(True, True, True), (True, True, False)])
@pytest.mark.parametrize("steps", [4, 7])
def test_fused_run_kernel_matches_steps(periodic, steps):
    """The whole-block multi-step kernel (interpret mode on CPU) advances
    exactly like `steps` sequential XLA dense steps (f32)."""
    import jax.numpy as jnp

    from dccrg_tpu.ops.dense_advection import make_fused_run

    n, nz = 8, 8
    g, adv = make(n=n, nz=nz, periodic=periodic, n_dev=1)
    adv32 = Advection(g, dtype=np.float32)
    assert adv32.dense is not None and adv32.dense.n_devices == 1
    state = adv32.initialize_state()
    cells = g.get_cells()
    vz = 0.3 * np.sin(2 * np.pi * g.geometry.get_center(cells)[:, 2])
    state = adv32.set_cell_data(state, "vz", cells, vz.astype(np.float32))
    dt = np.float32(0.4 * adv32.max_time_step(state))

    l0 = g.geometry.get_level_0_cell_length()
    area = np.array([l0[1] * l0[2], l0[0] * l0[2], l0[0] * l0[1]])
    fused = make_fused_run(nz, n, n, area, 1.0 / float(l0.prod()), interpret=True)

    mask_x = np.ones(n, np.float32)
    mask_y = np.ones(n, np.float32)
    zface_up = np.ones(nz, np.float32)
    if not periodic[2]:
        zface_up[-1] = 0.0
    zface_dn = np.roll(zface_up, 1)
    got = fused(
        state["density"][0], state["vx"][0], state["vy"][0], state["vz"][0],
        jnp.asarray(mask_x).reshape(1, 1, n),
        jnp.asarray(mask_y).reshape(1, n, 1),
        jnp.asarray(zface_up).reshape(nz, 1, 1),
        jnp.asarray(zface_dn).reshape(nz, 1, 1),
        dt, steps,
    )

    ref = state
    for _ in range(steps):
        ref = adv32.step(ref, dt)
    # on real TPU the fused run is bit-identical to stepping; interpret
    # mode (XLA CPU) applies FMA contraction differently per path, so
    # allow ~1 ulp here
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref["density"][0]), rtol=2e-7, atol=1e-9
    )
