"""Dense fast path for uniform (refinement-level-0) grids.

The reference treats every grid — even a fully regular one — through its
per-cell object machinery.  On TPU the idiomatic move is the opposite: when
every leaf is at level 0 and the partition is z-slab aligned, each device's
cells form a dense ``[nz_local, ny, nx]`` block (cell ids are x-fastest /
z-slowest, ``dccrg_mapping.hpp:180-207``), stencils become shifted slices
XLA fuses into single HBM passes, and the halo exchange collapses to two
``lax.ppermute`` plane transfers over ICI.  AMR or irregular partitions fall
back to the general gather path transparently.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .mesh import SHARD_AXIS

__all__ = ["DenseInfo", "detect_dense", "detect_dense2d", "HaloExtend"]


@dataclass(frozen=True)
class DenseInfo:
    nx: int
    ny: int
    nz: int
    nz_local: int          # z planes per device
    n_devices: int
    periodic: tuple


def detect_dense(mapping, topology, leaves, n_devices: int) -> DenseInfo | None:
    """A grid is dense-eligible iff every leaf is level 0 and ownership is
    the id-order slab partition with D | nz."""
    nx, ny, nz = mapping.length
    if len(leaves) != nx * ny * nz:
        return None  # something is refined
    if nz % n_devices != 0:
        return None
    per = len(leaves) // n_devices
    if not all((leaves.owner[d * per:(d + 1) * per] == d).all()
               for d in range(n_devices)):
        return None
    # leaves must be exactly the level-0 cells 1..n in order
    if leaves.cells[0] != 1 or leaves.cells[-1] != nx * ny * nz:
        return None
    return DenseInfo(
        nx=nx,
        ny=ny,
        nz=nz,
        nz_local=nz // n_devices,
        n_devices=n_devices,
        periodic=topology.periodic,
    )


def detect_dense2d(grid, hood_id):
    """Dense ``[D, ny_local, nx]`` y-slab layout for uniform 2-D grids —
    the 2-D sibling of :func:`detect_dense` (the reference's hello-world
    shape, ``simple_game_of_life.cpp``: an (N, N, 1) grid with the full
    length-1 vertex neighborhood).

    Under the id-order block partition the dense view is a pure reshape
    of the row layout (ids are x-fastest, rows ascend in id order), so no
    gather tables are needed; the halo is two ppermuted boundary rows.
    Returns None unless: default hood of length 1, nz == 1 with
    non-periodic z (a periodic z of extent 1 would make every cell its
    own neighbor), all leaves level 0, and ownership the exact y-slab
    block striping."""
    if hood_id is not None:
        return None
    epoch = grid.epoch
    mapping = epoch.mapping
    nx, ny, nz = (int(v) for v in mapping.length)
    if nz != 1 or grid.topology.is_periodic(2):
        return None
    leaves = epoch.leaves
    N = len(leaves)
    if N != nx * ny or N == 0:
        return None
    if int(leaves.cells[0]) != 1 or int(leaves.cells[-1]) != N:
        return None
    D = epoch.n_devices
    if ny % D != 0:
        return None
    per = N // D
    expected = np.repeat(np.arange(D, dtype=leaves.owner.dtype), per)
    if not np.array_equal(leaves.owner, expected):
        return None
    hood = np.asarray(grid.neighborhoods[None])
    if len(hood) != 26 or np.abs(hood).max() != 1:
        return None
    return dict(
        nx=nx, ny=ny, nyl=ny // D, D=D,
        periodic=(grid.topology.is_periodic(0), grid.topology.is_periodic(1)),
    )


class HaloExtend:
    """Per-device leading-axis halo: extend a ``[n_loc, ...]`` block to
    ``[n_loc+2, ...]`` with neighbor devices' boundary slices (ppermute up
    and down the slab ring) — z planes for the 3-D slab layout, y rows for
    the 2-D one.  Intended for use *inside* shard_map bodies."""

    def __init__(self, info):
        """``info``: a DenseInfo, or a plain device count."""
        self.info = info
        D = info if isinstance(info, int) else info.n_devices
        self.n_devices = D
        self.up = [(i, (i + 1) % D) for i in range(D)]
        self.down = [(i, (i - 1) % D) for i in range(D)]

    def __call__(self, blk):
        """blk: [nzl, ny, nx] (or with trailing dims). Returns [nzl+2, ...].
        For a single device the ring degenerates to a local wrap."""
        recv_below, recv_above = self.planes(blk)
        return jnp.concatenate([recv_below, blk, recv_above], axis=0)

    def planes(self, blk):
        """The two received halo planes ``(below, above)`` without
        materializing the concatenated extension — for kernels that splice
        the halo in VMEM instead of re-reading an extended copy from HBM."""
        top = blk[-1:]                       # plane sent upward
        bot = blk[:1]                        # plane sent downward
        if self.n_devices == 1:
            return top, bot
        recv_below = jax.lax.ppermute(top, SHARD_AXIS, self.up)
        recv_above = jax.lax.ppermute(bot, SHARD_AXIS, self.down)
        return recv_below, recv_above
