"""Game-of-life end-to-end tests, mirroring the reference's blinker
verification (examples/simple_game_of_life.cpp:122-158) and the
device-count-invariance expectation of its test suite."""
import numpy as np
import pytest

from dccrg_tpu import Grid, make_mesh
from dccrg_tpu.models import GameOfLife


def make_gol(n_dev=None):
    g = (
        Grid()
        .set_initial_length((10, 10, 1))
        .set_maximum_refinement_level(0)
        .set_neighborhood_length(1)
        .set_load_balancing_method("RCB")
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    return g, GameOfLife(g)


def test_blinker_oscillates():
    grid, gol = make_gol()
    # blinker at cells 54, 55, 56 (a horizontal row in the 10x10 grid)
    state = gol.new_state(alive_cells=[54, 55, 56])
    for turn in range(1, 21):
        state = gol.step(state)
        alive = set(gol.alive_cells(state).tolist())
        assert 55 in alive, f"turn {turn}"
        if turn % 2 == 1:  # after odd number of steps: vertical
            assert alive == {45, 55, 65}, f"turn {turn}"
        else:  # back to horizontal
            assert alive == {54, 55, 56}, f"turn {turn}"


def test_block_still_life():
    grid, gol = make_gol()
    block = [44, 45, 54, 55]
    state = gol.new_state(alive_cells=block)
    state = gol.run(state, 5)
    assert set(gol.alive_cells(state).tolist()) == set(block)


def test_glider_moves():
    grid, gol = make_gol()
    # glider in the upper-left corner: cells (x,y): (1,0),(2,1),(0,2),(1,2),(2,2)
    ids = [1 + 1 + 0 * 10, 1 + 2 + 1 * 10, 1 + 0 + 2 * 10, 1 + 1 + 2 * 10, 1 + 2 + 2 * 10]
    state = gol.new_state(alive_cells=ids)
    state = gol.run(state, 4)
    # after 4 steps a glider translates by (1, 1)
    expect = {i + 1 + 1 * 10 for i in ids}
    assert set(gol.alive_cells(state).tolist()) == expect


def test_device_count_invariance():
    """Rank-count-invariant results, the reference suite's core property."""
    finals = []
    rng = np.random.default_rng(11)
    alive0 = (rng.random(100) < 0.35).nonzero()[0] + 1
    for n_dev in (1, 3, 8):
        grid, gol = make_gol(n_dev=n_dev)
        state = gol.new_state(alive_cells=alive0.astype(np.uint64))
        state = gol.run(state, 10)
        finals.append(frozenset(gol.alive_cells(state).tolist()))
    assert finals[0] == finals[1] == finals[2]


def test_periodic_gol_wraps():
    g = (
        Grid()
        .set_initial_length((8, 8, 1))
        .set_periodic(True, True, False)
        .set_neighborhood_length(1)
        .initialize(mesh=make_mesh())
    )
    gol = GameOfLife(g)
    # blinker crossing the x boundary: row y=3, cells x = 7, 0, 1
    ids = [1 + 7 + 3 * 8, 1 + 0 + 3 * 8, 1 + 1 + 3 * 8]
    state = gol.new_state(alive_cells=ids)
    state = gol.step(state)
    alive = set(gol.alive_cells(state).tolist())
    # vertical blinker at x=0: y = 2,3,4
    assert alive == {1 + 0 + 2 * 8, 1 + 0 + 3 * 8, 1 + 0 + 4 * 8}
    state = gol.step(state)
    assert set(gol.alive_cells(state).tolist()) == set(ids)


@pytest.mark.parametrize(
    "n_dev,use_pallas", [(1, "interpret"), (1, False), (2, True), (5, True)]
)
@pytest.mark.parametrize(
    "periodic", [(False, False, False), (True, True, False)]
)
def test_dense2d_matches_general(n_dev, use_pallas, periodic):
    """The dense y-slab fast path (whole-run device loop, 8-neighbor
    count as shifted bands) produces identical alive sets and neighbor
    counts to the general gather path, at any device count — including
    the single-device fused Pallas kernel via the interpreter and the
    XLA dense loop it falls back to."""
    g = (
        Grid()
        .set_initial_length((10, 10, 1))
        .set_maximum_refinement_level(0)
        .set_neighborhood_length(1)
        .set_periodic(*periodic)
        .initialize(mesh=make_mesh(n_devices=n_dev))
    )
    rng = np.random.default_rng(0)
    cells = g.get_cells()
    alive0 = cells[rng.random(len(cells)) < 0.35]
    fast = GameOfLife(g, use_pallas=use_pallas)
    slow = GameOfLife(g, allow_dense=False)
    assert fast.dense2d is not None
    assert slow.dense2d is None
    s = fast.run(fast.new_state(alive_cells=alive0), 13)
    r = slow.run(slow.new_state(alive_cells=alive0), 13)
    assert set(fast.alive_cells(s).tolist()) == set(slow.alive_cells(r).tolist())
    np.testing.assert_array_equal(
        g.get_cell_data(s, "live_neighbor_count", cells),
        g.get_cell_data(r, "live_neighbor_count", cells),
    )


def test_gol_padded_kernel_bit_identical():
    """Tile-padding (explicit wrap-halo rows/columns) reproduces the
    unpadded fused kernel bit for bit on both axes, all periodicities."""
    import jax.numpy as jnp

    from dccrg_tpu.ops.gol_kernel import make_gol_run

    rng = np.random.default_rng(3)
    ny, nx = 12, 20
    a = jnp.asarray((rng.random((ny, nx)) < 0.35).astype(np.float32))
    for px, py in [(True, True), (False, False), (True, False)]:
        k0 = make_gol_run(ny, nx, px, py, interpret=True)
        for ny_pad, nx_pad in [(16, None), (None, 24), (16, 24)]:
            kp = make_gol_run(ny, nx, px, py, ny_pad=ny_pad, nx_pad=nx_pad,
                              interpret=True)
            for turns in (4, 7):
                o0, c0 = k0(a, turns)
                op, cp = kp(a, turns)
                assert np.array_equal(np.asarray(o0), np.asarray(op)), (
                    px, py, ny_pad, nx_pad, turns)
                assert np.array_equal(np.asarray(c0), np.asarray(cp))


def test_gol_model_y_padding_engages():
    """A 30x12 board pads y 12->16 through the model dispatch and still
    matches the general gather path exactly."""
    g = (
        Grid()
        .set_initial_length((30, 12, 1))
        .set_neighborhood_length(1)
        .set_periodic(True, True, False)
        .initialize(mesh=make_mesh(n_devices=1))
    )
    rng = np.random.default_rng(1)
    cells = g.get_cells()
    alive0 = cells[rng.random(len(cells)) < 0.35]
    fast = GameOfLife(g, use_pallas="interpret")
    slow = GameOfLife(g, allow_dense=False)
    assert fast.dense2d is not None
    s = fast.run(fast.new_state(alive_cells=alive0), 9)
    r = slow.run(slow.new_state(alive_cells=alive0), 9)
    assert set(fast.alive_cells(s).tolist()) == set(
        slow.alive_cells(r).tolist())
