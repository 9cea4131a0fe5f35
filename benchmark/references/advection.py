"""Plain reference of the advection configurations: dccrg ``tests/advection``'s
upwind finite-volume scheme, written from its description and nothing of the
program (no import of ``dccrg_tpu``, no table the program built).

A grid of at most two levels (level 0 and, inside a refined region, level 1)
is held as a voxel field at the finest resolution, indexed ``[z, y, x]``: every
voxel of an unrefined level-0 cell carries that cell's values.  Per step and
axis, each face patch between two voxels of different cells carries the flux

    v_face = (L_a * v_b + L_b * v_a) / (L_a + L_b)      (solve.hpp:168-175)
    F      = upwind(rho) * dt * v_face * A_patch

with ``a`` the lower and ``b`` the upper cell, ``L`` a cell's length along the
axis, ``v`` its velocity component, and the upwind density that of ``a`` when
``v_face >= 0``, else that of ``b``.  ``F`` leaves ``a`` and enters ``b``; a
cell's change is the sum over its patches divided by its volume.  A face
between two level-0 cells is four patches of a quarter of its area with one
and the same flux, so the sum is the coarse face's flux; a face between a
level-0 cell and a level-1 cell is one patch.  All boundaries are periodic.

The velocity field is the rotation of ``initialize.hpp`` about the axis
x = y = 0.5 (vx = 0.5 - y, vy = x - 0.5) at each cell's centre, plus the
configuration's uniform drift ``vz``.
"""
from __future__ import annotations

import functools

import numpy as np

#: axis (0 = x, 1 = y, 2 = z) -> array axis of a [z, y, x] field
_ARRAY_AXIS = (2, 1, 0)


class Layout:
    """The cells of a configuration, worked out from its ``grid`` entry:
    level-0 shape, which level-0 cells are refined, and the map between
    dccrg cell ids and voxels."""

    def __init__(self, grid: dict):
        if grid["kind"] == "uniform":
            nx, ny, nz = grid["shape"]
            self.f = 1
            self.refined = None
        elif grid["kind"] == "ball_refined":
            if grid["max_level"] != 1 or len(grid["radii"]) != 1:
                raise ValueError("the reference holds two levels at most")
            n = grid["level0"]
            nx = ny = nz = n
            self.f = 2
            z, y, x = np.meshgrid(*(np.arange(m) for m in (nz, ny, nx)),
                                  indexing="ij")
            cx, cy, cz = grid["center"]
            r = np.sqrt(((x + 0.5) / nx - cx) ** 2 + ((y + 0.5) / ny - cy) ** 2
                        + ((z + 0.5) / nz - cz) ** 2)
            self.refined = r < grid["radii"][0]            # [nz, ny, nx]
        else:
            raise ValueError(f"unknown grid kind {grid['kind']!r}")
        self.n0 = (nx, ny, nz)
        self.h0 = np.array([1.0 / nx, 1.0 / ny, 1.0 / nz])
        self.shape = (nz * self.f, ny * self.f, nx * self.f)

    # ------------------------------------------------------------ cells

    @property
    def n_cells(self) -> int:
        """Leaf cells: a refined level-0 cell is 8 level-1 cells."""
        n = int(np.prod(self.n0))
        return n if self.refined is None else n + 7 * int(self.refined.sum())

    def cell_ids(self) -> np.ndarray:
        """Sorted dccrg ids of the leaf cells: level 0 ids 1..N0 in x-fastest
        order, level 1 ids after them on the grid twice as fine."""
        nx, ny, nz = self.n0
        lin0 = np.arange(nx * ny * nz, dtype=np.int64)
        if self.refined is None:
            return (lin0 + 1).astype(np.uint64)
        ref = self.refined.reshape(-1)
        ids0 = lin0[~ref] + 1
        z, y, x = np.nonzero(self.refined)
        c = np.stack([z, y, x], 1)[:, None, :] * 2 + np.array(
            [(a, b, d) for a in (0, 1) for b in (0, 1) for d in (0, 1)])
        fz, fy, fx = c[..., 0].ravel(), c[..., 1].ravel(), c[..., 2].ravel()
        ids1 = (nx * ny * nz + 1) + fx + 2 * nx * (fy + 2 * ny * fz)
        return np.sort(np.concatenate([ids0, ids1])).astype(np.uint64)

    def voxel_index(self, ids):
        """Voxel slices (z, y, x start and extent) of each cell id."""
        nx, ny, nz = self.n0
        ids = np.asarray(ids, np.int64)
        n0 = nx * ny * nz
        lvl1 = ids > n0
        lin = np.where(lvl1, ids - 1 - n0, ids - 1)
        wx = np.where(lvl1, 2 * nx, nx)
        wy = np.where(lvl1, 2 * ny, ny)
        x, y, z = lin % wx, (lin // wx) % wy, lin // (wx * wy)
        scale = np.where(lvl1, 1, self.f)
        return z * scale, y * scale, x * scale, scale

    def to_voxels(self, ids, values) -> np.ndarray:
        """Host voxel field [z, y, x] from per-cell values."""
        out = np.zeros(self.shape, np.float32)
        z, y, x, s = self.voxel_index(ids)
        values = np.asarray(values, np.float32)
        for w in np.unique(s):
            m = s == w
            for dz in range(w):
                for dy in range(w):
                    for dx in range(w):
                        out[z[m] + dz, y[m] + dy, x[m] + dx] = values[m]
        return out

    def centres(self, ids) -> np.ndarray:
        """[N, 3] (x, y, z) centres of cell ids."""
        z, y, x, s = self.voxel_index(ids)
        hv = self.h0 / self.f
        return np.stack([(x + s / 2) * hv[0], (y + s / 2) * hv[1],
                         (z + s / 2) * hv[2]], 1)

    # ------------------------------------------------------- voxel tables

    def fine_voxel(self) -> np.ndarray | None:
        """[Z, Y, X] bool: voxel belongs to a level-1 cell (None: uniform)."""
        if self.refined is None:
            return None
        r = self.refined
        return np.repeat(np.repeat(np.repeat(r, 2, 0), 2, 1), 2, 2)

    def velocity(self, drift_vz: float):
        """(vx, vy, vz) at each voxel's cell centre, as arrays that
        broadcast against [Z, Y, X]."""
        Z, Y, X = self.shape
        hv = self.h0 / self.f
        xs = (np.arange(X) + 0.5) * hv[0]
        ys = (np.arange(Y) + 0.5) * hv[1]
        if self.refined is None:
            vx = (0.5 - ys)[None, :, None]
            vy = (xs - 0.5)[None, None, :]
            return vx, vy, np.full((1, 1, 1), drift_vz)
        # a level-0 cell's voxels take its centre, not their own
        xc = ((np.arange(X) // 2) + 0.5) * self.h0[0]
        yc = ((np.arange(Y) // 2) + 0.5) * self.h0[1]
        fine = self.fine_voxel()
        vx = np.where(fine, (0.5 - ys)[None, :, None],
                      (0.5 - yc)[None, :, None])
        vy = np.where(fine, (xs - 0.5)[None, None, :],
                      (xc - 0.5)[None, None, :])
        return vx, vy, np.full((1, 1, 1), drift_vz)

    def max_time_step(self, drift_vz: float) -> float:
        """min over cells and axes of length / |velocity|."""
        vs = self.velocity(drift_vz)
        fine = self.fine_voxel()
        best = np.inf
        for d, v in enumerate(vs):
            v = np.abs(np.broadcast_to(v, self.shape))
            h = (np.where(fine, self.h0[d] / 2, self.h0[d])
                 if fine is not None else self.h0[d])
            with np.errstate(divide="ignore"):
                best = min(best, float(np.min(h / v)))
        return best


class Reference:
    """``run(rho, steps, dt)`` on [Z, Y, X] voxel fields, computed in
    ``dtype`` (float32 for the reference, bfloat16 for its control)."""

    def __init__(self, layout: Layout, drift_vz: float, dtype):
        import jax.numpy as jnp

        self.layout = lay = layout
        self.dtype = dtype
        fine = lay.fine_voxel()
        self._v = tuple(jnp.asarray(v, dtype) for v in lay.velocity(drift_vz))
        hv = lay.h0 / lay.f
        self._area = tuple(float(hv[(d + 1) % 3] * hv[(d + 2) % 3])
                           for d in range(3))
        if fine is None:
            self._len = None
            self._same = None
            self._fine = None
            self._inv_vol = float(1.0 / np.prod(lay.h0))
        else:
            Z, Y, X = lay.shape
            pos = (np.arange(X)[None, None, :], np.arange(Y)[None, :, None],
                   np.arange(Z)[:, None, None])
            # two voxels of one level-0 cell along an axis: the lower at
            # an even position, both coarse
            self._same = tuple(
                jnp.asarray((~fine) & (pos[d] % 2 == 0)) for d in range(3))
            self._len = tuple(
                jnp.asarray(np.where(fine, hv[d], lay.h0[d]), dtype)
                for d in range(3))
            self._fine = jnp.asarray(fine)
            self._inv_vol = (float(1.0 / np.prod(hv)),
                             float(1.0 / np.prod(lay.h0)))
        self._run = self._build()

    def _build(self):
        import jax
        import jax.numpy as jnp

        dtype = self.dtype

        def step(rho, dt, vel, lens, same, fine):
            net = jnp.zeros_like(rho)
            for d in range(3):
                ax = _ARRAY_AXIS[d]
                v = vel[d]
                vn = jnp.roll(v, -1, ax)
                if lens is None:
                    vf = (v + vn) * dtype(0.5)
                else:
                    L, Ln = lens[d], jnp.roll(lens[d], -1, ax)
                    vf = (L * vn + Ln * v) / (L + Ln)
                rn = jnp.roll(rho, -1, ax)
                up = jnp.where(vf >= 0, rho, rn)
                flux = up * (dt * vf * dtype(self._area[d]))
                if same is not None:
                    flux = jnp.where(same[d], dtype(0), flux)
                net = net - flux + jnp.roll(flux, 1, ax)
            if fine is None:
                return rho + net * dtype(self._inv_vol)
            Z, Y, X = rho.shape
            coarse = net.reshape(Z // 2, 2, Y // 2, 2, X // 2, 2).sum(
                (1, 3, 5))
            up_c = jnp.repeat(jnp.repeat(jnp.repeat(coarse, 2, 0), 2, 1), 2, 2)
            inv_f, inv_c = self._inv_vol
            return rho + jnp.where(fine, net * dtype(inv_f),
                                   up_c * dtype(inv_c))

        @functools.partial(jax.jit, static_argnums=2)
        def bench_reference(rho, dt, steps, vel, lens, same, fine):
            rho = rho.astype(dtype)
            dt = jnp.asarray(dt, dtype)
            out = jax.lax.fori_loop(
                0, steps, lambda i, r: step(r, dt, vel, lens, same, fine), rho)
            return out.astype(jnp.float32)

        return bench_reference

    def run(self, rho, steps: int, dt):
        """Advance a voxel density field by ``steps`` steps of ``dt``."""
        return self._run(rho, dt, int(steps), self._v, self._len, self._same,
                         self._fine)


def max_rel_err(a, b) -> float:
    """max |a - b| / max |b| over two voxel fields, on the device."""
    import jax.numpy as jnp

    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
