"""Pallas TPU kernels that move one refinement level's leaves between the
epoch's flat rows and the level's dense box on the boxed AMR path.

``models/boxed_advection.py`` runs its steps on dense per-level boxes but
takes and returns the density as epoch rows.  A per-element gather
``flat[rows]`` into the box (and a gather plus scatter back) costs the TPU
~6-7 ns per box position, one scalar index at a time: on a TPU v5e, at
the refined benchmark grid, these moves took ~87 ms of a ~102 ms 50-step
call.  They need no per-element index.  Epoch rows are sorted by cell id, and a
level's cell ids run in the box's [z, y, x] order, so a level's leaves are
one contiguous range of rows, in box order.  Each box row (one z, one y)
then holds at most a few runs of consecutive leaves, and each run is a
contiguous slice of that range.

The range is laid out as 128-lane rows (``rows_of``: ``front`` zeros,
the range, zeros; ``front`` is the padded plane's width), held whole in
VMEM, and each run moves as one unaligned two-row window: a dynamic two-row load, two lane rotations and a select
per 128 lanes of the run (``boxed_move_gather``), or the inverse
read-modify-write of the two rows (``boxed_move_scatter``).  Per box
row and run the tables hold the window's row ``q``, its lane offset ``m``
and the run's ``[x0, x1)``; ``box_runs`` builds them.  Both kernels only
move values, so the box equals ``where(leaf, flat[rows], 0)`` and the
rows written back equal a scatter of the leaves, bit for bit.  The
kernels run once per call, outside the step loop, so their names stay
out of the ``advection_*`` names of the per-step kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["box_runs", "rows_of", "make_box_gather", "make_box_scatter",
           "moves_fit"]

_LANE = 128
#: scoped-VMEM cap: the whole range (double-buffered) and two box planes
_MOVES_VMEM_BUDGET = 64 * 1024 * 1024
#: most runs of leaves per box row the tables take; more falls back to
#: the per-element gather
MAX_RUNS = 8


def moves_fit(n_rows: int, ny: int, nx: int) -> bool:
    """Whether ``n_rows`` 128-lane rows and the box planes fit VMEM."""
    return (2 * n_rows * _LANE + 4 * ny * nx) * 4 <= _MOVES_VMEM_BUDGET


def box_runs(leaf, ny: int, nx: int, n_runs: int = 0):
    """Run tables of one box's leaves, or None past ``MAX_RUNS`` runs per
    row.  ``leaf`` is the (nz, by, bx) leaf mask; ``(ny, nx)`` the padded
    plane (multiples of 8 and 128).  Returns ``(tab, n_runs)``: ``tab``
    int32 (nz, 1, ny * n_runs * 4), per box row and run ``(q, m, x0,
    x1)`` with ``q * 128 + m = nx + c - x0`` for the run's first leaf at
    compact index ``c`` (``rows_of`` puts ``front = nx`` zeros first);
    unused runs are empty (``x0 == x1``).  ``n_runs`` is at least the
    given value, so that devices' tables share a shape."""
    nz, by, bx = leaf.shape
    lr = np.zeros((nz, ny, bx + 2), dtype=np.int8)
    lr[:, :by, 1:-1] = leaf
    step = np.diff(lr, axis=2)
    starts = np.argwhere(step == 1)                 # (z, y, x0)
    ends = np.argwhere(step == -1)                  # (z, y, x1), same order
    count = np.zeros((nz, ny), dtype=np.int64)
    np.add.at(count, (starts[:, 0], starts[:, 1]), 1)
    K = max(int(count.max(initial=0)), n_runs, 1)
    if K > MAX_RUNS:
        return None
    # compact index of each run's first leaf: leaves before it in box order
    x0, x1 = starts[:, 2], ends[:, 2]
    c = np.concatenate([[0], np.cumsum(x1 - x0)[:-1]])
    k = np.arange(len(starts)) - np.repeat(
        np.concatenate([[0], np.cumsum(count.ravel())[:-1]]), count.ravel()
    )
    s = nx + c - x0
    tab = np.zeros((nz, ny, K, 4), dtype=np.int32)
    tab[starts[:, 0], starts[:, 1], k] = np.stack(
        [s // _LANE, s % _LANE, x0, x1], axis=1
    )
    return tab.reshape(nz, 1, ny * K * 4), K


def rows_of(compact, front: int, n_rows: int):
    """``compact`` (1-D) as ``(n_rows, 128)``: ``front`` zeros first."""
    back = n_rows * _LANE - front - compact.shape[0]
    return jnp.pad(compact, (front, back)).reshape(n_rows, _LANE)


def _lane_masks(lo, hi):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    return (lane >= lo) & (lane < hi)


def _rolls(interpret: bool):
    if interpret:
        return lambda x, s: jnp.roll(x, s, 1)
    return lambda x, s: pltpu.roll(x, s, 1)


def _params(interpret: bool):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_MOVES_VMEM_BUDGET,
    )}


def make_box_gather(nz: int, ny: int, nx: int, n_runs: int, n_rows: int,
                    *, interpret: bool = False):
    """``gather(tab, rows) -> box``: the (nz, ny, nx) box (zero off the
    runs) from the ``(n_rows, 128)`` range ``rows``, one plane per grid
    step (``boxed_move_gather``)."""
    K, nch = n_runs, nx // _LANE
    roll = _rolls(interpret)

    def kernel(tab_ref, rows_ref, out_ref):
        def row(y, carry):
            chunks = [jnp.zeros((1, _LANE), jnp.float32)] * nch
            for k in range(K):
                i = (y * K + k) * 4
                q, m = tab_ref[0, 0, i], tab_ref[0, 0, i + 1]
                x0, x1 = tab_ref[0, 0, i + 2], tab_ref[0, 0, i + 3]
                sh = (_LANE - m) % _LANE
                low = _lane_masks(0, _LANE - m)
                for c in range(nch):
                    two = rows_ref[pl.ds(q + c, 2), :]
                    win = jnp.where(low, roll(two[0:1], sh), roll(two[1:2], sh))
                    run = _lane_masks(x0 - c * _LANE, x1 - c * _LANE)
                    chunks[c] = jnp.where(run, win, chunks[c])
            out_ref[0, pl.ds(y, 1), :] = jnp.concatenate(chunks, axis=1)
            return carry

        jax.lax.fori_loop(0, ny, row, 0)

    call = pl.pallas_call(
        kernel,
        name="boxed_move_gather",
        grid=(nz,),
        in_specs=[
            pl.BlockSpec((1, 1, ny * K * 4), lambda z: (z, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n_rows, _LANE), lambda z: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, ny, nx), lambda z: (z, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), jnp.float32),
        interpret=interpret,
        **_params(interpret),
    )
    return call


def make_box_scatter(nz: int, ny: int, nx: int, n_runs: int, n_rows: int,
                     *, interpret: bool = False):
    """``scatter(tab, box) -> rows``: the inverse of ``make_box_gather``,
    the box's runs written into a zeroed ``(n_rows, 128)`` range
    (``boxed_move_scatter``)."""
    K, nch = n_runs, nx // _LANE
    roll = _rolls(interpret)

    def kernel(tab_ref, box_ref, rows_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            rows_ref[...] = jnp.zeros_like(rows_ref)

        def row(y, carry):
            vals = box_ref[0, pl.ds(y, 1), :]
            for k in range(K):
                i = (y * K + k) * 4
                q, m = tab_ref[0, 0, i], tab_ref[0, 0, i + 1]
                x0, x1 = tab_ref[0, 0, i + 2], tab_ref[0, 0, i + 3]
                for c in range(nch):
                    # box lane l of chunk c lands at lane (m + l) % 128 of
                    # row q + c, or of row q + c + 1 past the row's end
                    moved = roll(vals[:, c * _LANE:(c + 1) * _LANE], m)
                    lo, hi = x0 - c * _LANE + m, x1 - c * _LANE + m
                    two = rows_ref[pl.ds(q + c, 2), :]
                    first = jnp.where(_lane_masks(jnp.maximum(lo, m), hi),
                                      moved, two[0:1])
                    second = jnp.where(
                        _lane_masks(lo - _LANE, jnp.minimum(hi - _LANE, m)),
                        moved, two[1:2],
                    )
                    rows_ref[pl.ds(q + c, 2), :] = jnp.concatenate(
                        [first, second], axis=0
                    )
            return carry

        jax.lax.fori_loop(0, ny, row, 0)

    call = pl.pallas_call(
        kernel,
        name="boxed_move_scatter",
        grid=(nz,),
        in_specs=[
            pl.BlockSpec((1, 1, ny * K * 4), lambda z: (z, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, ny, nx), lambda z: (z, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n_rows, _LANE), lambda z: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, _LANE), jnp.float32),
        interpret=interpret,
        **_params(interpret),
    )
    return call
