"""Multi-level (3+ level) flat AMR advection — the VERDICT-r4 extension
of the flat fast path beyond levels {0, 1} (reference AMR allows 21
levels, ``dccrg_mapping.hpp:316-329``).  The multi-level form inflates
every leaf onto finest-level voxels and runs the whole multi-step loop
as rolls/multiplies/adds with a hierarchical pool/broadcast for the
coarse updates; these tests pin it against the general gather path
(reference ``solve.hpp`` semantics) in f64."""
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh
from dccrg_tpu.models import Advection


def ball_grid(n_dev, n=8, levels=2, periodic=(True, True, True),
              cell_length=None):
    # non-power-of-two default cell lengths: the ml volume tables must
    # carry f64 inverse volumes into an f64 run (f32-quantized tables
    # would pass only for power-of-two cell sizes)
    cl = cell_length if cell_length is not None else (
        0.1, 0.07, 0.13,
    )
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(*periodic)
        .set_maximum_refinement_level(levels)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=cl,
        )
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    for rad in (0.3, 0.15):
        ids = g.get_cells()
        c = g.geometry.get_center(ids)
        r = np.linalg.norm(c - 0.5, axis=1)
        lv = g.mapping.get_refinement_level(ids)
        for cid in ids[(r < rad) & (lv == lv.max())]:
            g.refine_completely(int(cid))
        g.stop_refining()
    lv = g.mapping.get_refinement_level(g.get_cells())
    assert lv.max() == 2, "test grid must span 3 levels"
    return g


@pytest.mark.parametrize("n_dev", [1, 8])
def test_ml_flat_matches_general_path(n_dev):
    g = ball_grid(n_dev)
    ids = np.sort(g.leaves.cells)
    adv_ml = Advection(g, dtype=np.float64)
    assert adv_ml.flat_kind == "ml", "3-level grid must engage the ml path"
    adv_gen = Advection(g, dtype=np.float64, use_pallas=False,
                        allow_boxed=False)
    s_ml = adv_ml.initialize_state()
    s = adv_gen.initialize_state()
    dt = 0.3 * adv_gen.max_time_step(s)
    steps = 10
    out = adv_ml._flat_run(s_ml, steps, dt)
    for _ in range(steps):
        s = adv_gen.step(s, dt)
    a = np.asarray(g.get_cell_data(out, "density", ids), np.float64)
    b = np.asarray(g.get_cell_data(s, "density", ids), np.float64)
    err = np.abs(a - b).max() / np.abs(b).max()
    assert err < 1e-11, err
    # mass conservation (periodic domain): exact up to f64 rounding
    vol = np.prod(g.geometry.get_length(ids), axis=-1)
    np.testing.assert_allclose((a * vol).sum(), (b * vol).sum(), rtol=1e-12)


def test_ml_flat_nonperiodic_boundaries():
    g = ball_grid(1, periodic=(False, False, False))
    ids = np.sort(g.leaves.cells)
    adv_ml = Advection(g, dtype=np.float64)
    assert adv_ml.flat_kind == "ml"
    adv_gen = Advection(g, dtype=np.float64, use_pallas=False,
                        allow_boxed=False)
    rng = np.random.default_rng(0)
    s_ml = adv_ml.initialize_state()
    s = adv_gen.initialize_state()
    rho = rng.uniform(1.0, 2.0, len(ids))
    s_ml = adv_ml.set_cell_data(s_ml, "density", ids, rho)
    s = adv_gen.set_cell_data(s, "density", ids, rho)
    s = g.update_copies_of_remote_neighbors(s)
    dt = 0.3 * adv_gen.max_time_step(s)
    steps = 8
    out = adv_ml._flat_run(s_ml, steps, dt)
    for _ in range(steps):
        s = adv_gen.step(s, dt)
    a = np.asarray(g.get_cell_data(out, "density", ids), np.float64)
    b = np.asarray(g.get_cell_data(s, "density", ids), np.float64)
    assert np.abs(a - b).max() / np.abs(b).max() < 1e-11


def test_ml_pallas_kernel_matches_general_path():
    """The VMEM-resident multi-level Pallas kernel (interpret mode on
    CPU) must agree with the general gather path — the hierarchical
    roll-chain capture/broadcast vs the reference semantics."""
    g = ball_grid(1)
    ids = np.sort(g.leaves.cells)
    adv_k = Advection(g, dtype=np.float32, use_pallas="interpret")
    assert adv_k.flat_kind == "ml_pallas_interpret", adv_k.flat_kind
    adv_gen = Advection(g, dtype=np.float32, use_pallas=False,
                        allow_boxed=False)
    s_k = adv_k.initialize_state()
    s = adv_gen.initialize_state()
    dt = np.float32(0.3 * adv_gen.max_time_step(s))
    steps = 6
    out = adv_k._flat_run(s_k, steps, dt)
    for _ in range(steps):
        s = adv_gen.step(s, dt)
    a = np.asarray(g.get_cell_data(out, "density", ids), np.float64)
    b = np.asarray(g.get_cell_data(s, "density", ids), np.float64)
    assert np.abs(a - b).max() / np.abs(b).max() < 5e-6


def test_two_level_grids_keep_the_tuned_paths():
    """Levels {0, 1} must still dispatch to the existing 2-level flat
    forms (Pallas kernel / sharded XLA), not the ml generalization."""
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
        .initialize(mesh=make_mesh(n_devices=8))
    )
    g.refine_completely(1)
    g.stop_refining()
    adv = Advection(g, dtype=np.float32)
    assert adv.flat_kind != "ml"


def test_ml_run_dispatch_and_fallback_shape():
    """run() routes a 3-level grid through the flat ml form (or boxed by
    the cost edge) and produces the same physics as step()-stepping."""
    g = ball_grid(1, n=6)
    ids = np.sort(g.leaves.cells)
    adv = Advection(g, dtype=np.float64)
    s = adv.initialize_state()
    dt = 0.3 * adv.max_time_step(s)
    out = adv.run(s, 6, dt)
    s2 = s
    adv_gen = Advection(g, dtype=np.float64, use_pallas=False,
                        allow_boxed=False)
    for _ in range(6):
        s2 = adv_gen.step(s2, dt)
    a = np.asarray(g.get_cell_data(out, "density", ids), np.float64)
    b = np.asarray(g.get_cell_data(s2, "density", ids), np.float64)
    assert np.abs(a - b).max() / np.abs(b).max() < 5e-11
