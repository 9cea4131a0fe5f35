"""The boxed AMR path's Pallas moves between epoch rows and a level's box
(``ops/boxed_moves.py``) in interpret mode, against the per-element
gather and scatter they replace, and the choice in
``models/boxed_advection.py::_level_moves`` of which levels they carry.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from dccrg_tpu.models.boxed_advection import _level_moves
from dccrg_tpu.ops.boxed_moves import (
    MAX_RUNS,
    box_runs,
    make_box_gather,
    make_box_scatter,
    rows_of,
)


def _random_runs(rng, shape, runs):
    """A leaf mask with at most ``runs`` random runs per box row."""
    nz, by, bx = shape
    x = np.arange(bx)
    cuts = np.sort(rng.integers(0, bx + 1, (nz, by, 2 * runs)), axis=2)
    leaf = np.zeros(shape, dtype=bool)
    for k in range(runs):
        leaf |= ((x >= cuts[..., 2 * k:2 * k + 1])
                 & (x < cuts[..., 2 * k + 1:2 * k + 2]))
    return leaf


@pytest.mark.parametrize(
    "shape, runs",
    [
        ((5, 6, 7), 1),        # one run a row, rows and lanes padded
        ((4, 10, 130), 3),     # runs across 128-lane chunks
        ((3, 9, 200), 2),
        ((2, 8, 128), 0),      # no leaves at all
        ((2, 16, 256), 4),     # planes already whole tiles
        ((3, 7, 300), MAX_RUNS),
        ((2, 5, 129), 1),      # one lane past a chunk
        ((4, 12, 64), 5),
    ],
)
def test_box_moves_match_gather_scatter(shape, runs):
    """Leaves on ``runs`` random runs per box row, one ascending range of
    epoch rows in box order: the Pallas gather gives
    ``where(leaf, flat[rows], 0)`` and the scatter puts each leaf's value
    back on its row, bit for bit; the pad stays zero."""
    rng = np.random.default_rng(sum(shape) + runs)
    nz, by, bx = shape
    leaf = _random_runs(rng, shape, runs)
    ny, nx = -(-by // 8) * 8, -(-bx // 128) * 128
    tab, K = box_runs(leaf, ny, nx)
    assert K <= max(runs, 1)
    front, n = nx, int(leaf.sum())
    r0 = 5
    flat = rng.normal(size=r0 + n + 3).astype(np.float32)
    rows = np.full(shape, len(flat) - 1)
    rows[leaf] = r0 + np.arange(n)
    n_rows = -(-(front + max(n, 1) + nx) // 128) + 1

    gather = make_box_gather(nz, ny, nx, K, n_rows, interpret=True)
    box = np.asarray(gather(jnp.asarray(tab), rows_of(
        jnp.asarray(flat[r0:r0 + n]), front, n_rows)))
    np.testing.assert_array_equal(box[:, :by, :bx],
                                  np.where(leaf, flat[rows], 0))
    assert not box[:, by:].any() and not box[:, :, bx:].any()

    vals = np.full((nz, ny, nx), 9.0, np.float32)
    vals[:, :by, :bx] = rng.normal(size=shape)
    scatter = make_box_scatter(nz, ny, nx, K, n_rows, interpret=True)
    back = np.asarray(scatter(jnp.asarray(tab), jnp.asarray(vals))).ravel()
    np.testing.assert_array_equal(back[front:front + n],
                                  vals[:, :by, :bx][leaf])
    assert not back[:front].any() and not back[front + n:].any()


@pytest.mark.parametrize("case", ["too_many_runs", "padded_to_n_runs"])
def test_box_runs_tables(case):
    """Past ``MAX_RUNS`` runs in a box row there are no tables; asked for
    more runs than a box has (another device's count), the extra runs
    are empty."""
    leaf = np.zeros((2, 3, 2 * MAX_RUNS + 4), dtype=bool)
    if case == "too_many_runs":
        leaf[1, 2, 0:2 * (MAX_RUNS + 1):2] = True       # MAX_RUNS + 1 runs
        assert box_runs(leaf, 8, 128) is None
        return
    leaf[0, 1, 3:6] = True
    tab, K = box_runs(leaf, 8, 128, n_runs=3)
    assert K == 3
    t = tab.reshape(2, 8, K, 4)
    x0, x1 = t[..., 2], t[..., 3]
    assert (x0[0, 1, 0], x1[0, 1, 0]) == (3, 6)
    used = np.zeros(x0.shape, dtype=bool)
    used[0, 1, 0] = True
    assert (x0[~used] == x1[~used]).all()


@pytest.mark.parametrize(
    "case, moved, ragged",
    [
        ("one_range", True, False),
        ("gap_in_rows", False, None),     # leaves not one range of rows
        ("rows_descending", False, None),
        ("ragged_devices", True, True),   # devices hold different counts
    ],
)
def test_level_moves_choice(case, moved, ragged):
    """``_level_moves`` takes a level whose leaves are, on every device,
    one ascending range of epoch rows in box order, and leaves any other
    to the per-element gather and scatter."""
    D, shape = 2, (3, 4, 5)
    rng = np.random.default_rng(len(case))
    leaf = np.stack([_random_runs(rng, shape, 2) for _ in range(D)])
    leaf[:, 0, 0, :] = True                   # every device has leaves
    if case != "ragged_devices":
        leaf[1] = leaf[0]
    rows = np.full(leaf.shape, 999, dtype=np.int64)
    for k in range(D):
        n = int(leaf[k].sum())
        r = np.arange(7, 7 + n)
        if case == "gap_in_rows":
            r[n // 2:] += 1
        if case == "rows_descending":
            r = r[::-1]
        rows[k][leaf[k]] = r
    mv = _level_moves(leaf, rows, 1000, True)
    assert (mv is not None) == moved
    if mv is None:
        return
    consts, statics = mv
    counts = leaf.reshape(D, -1).sum(axis=1)
    assert consts["ragged"] == ragged
    assert consts["n"] == counts.max()
    np.testing.assert_array_equal(statics["mv_span"],
                                  np.stack([np.full(D, 7), counts], axis=1))
