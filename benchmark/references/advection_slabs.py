"""The plain reference of ``references/advection.py``, computed in z-blocks:
for a uniform periodic grid held as ``[D, nz/D, ny, nx]`` z-slabs, one slab
on each device, each slab is advanced on the device that holds it.

The upwind step reaches one plane per step along z.  So ``steps`` steps of
the whole field, restricted to one slab, are ``steps`` steps of the slab
extended by ``steps`` planes of the periodic field on each side, run as a
field of its own (``Reference`` rolls it periodically in z): the wrapped
planes spoil at most ``steps`` planes at each end, all of them halo.  The
cell lengths, areas and volume stay the whole grid's.

Nothing of the program is imported here; the slabs are plain sharded arrays.
"""
from __future__ import annotations

from references.advection import Layout, Reference


class SlabLayout(Layout):
    """A window of ``planes`` z-planes of a uniform grid: the grid's cell
    lengths and its x and y extent, a field of ``planes`` planes."""

    def __init__(self, grid: dict, planes: int):
        super().__init__(grid)
        if self.refined is not None:
            raise ValueError("z-blocks hold uniform grids only")
        nx, ny, _ = self.n0
        self.shape = (planes, ny, nx)


class SlabReference:
    """``run(rho, dt)``: ``steps`` steps of a ``[D, nz/D, ny, nx]`` density
    sharded over ``D`` devices, in ``dtype``, each slab advanced on its own
    device; the result is sharded as ``rho``."""

    def __init__(self, grid: dict, drift_vz: float, dtype, n_slabs: int,
                 steps: int):
        import jax

        nx, ny, nz = grid["shape"]
        if nz % n_slabs:
            raise ValueError(f"{nz} z-planes do not split into {n_slabs}")
        self.nzl = nz // n_slabs
        self.n_slabs = n_slabs
        self.steps = h = int(steps)
        ref = Reference(SlabLayout(grid, self.nzl + 2 * h), drift_vz, dtype)

        # one program over the stacked windows: each device advances its
        # own, the slab axis is only batched
        @jax.jit
        def bench_slab_reference(ext, dt):
            out = jax.vmap(lambda r: ref.run(r, h, dt))(ext)
            return out[:, h:h + self.nzl]

        self._run = bench_slab_reference

    def window(self, shards, d, device):
        """Planes ``[d nzl - steps, (d + 1) nzl + steps)`` of the periodic
        field on ``device``: slab ``d`` and its halo, taken from the slabs
        that hold them (``shards[k]``: slab ``k``, ``[1, nzl, ny, nx]``)."""
        import jax
        import jax.numpy as jnp

        nzl = self.nzl
        nz = nzl * self.n_slabs
        z, end = d * nzl - self.steps, (d + 1) * nzl + self.steps
        pieces = []
        while z < end:
            owner, lo = divmod(z % nz, nzl)
            hi = min(nzl, lo + end - z)
            pieces.append(jax.device_put(shards[owner][0, lo:hi], device))
            z += hi - lo
        return jnp.concatenate(pieces, axis=0)

    def run(self, rho, dt):
        import jax

        by_slab = {s.index[0].start or 0: s for s in rho.addressable_shards}
        shards = {d: s.data for d, s in by_slab.items()}
        ext = [self.window(shards, d, s.device)[None]
               for d, s in sorted(by_slab.items())]
        ext = jax.make_array_from_single_device_arrays(
            (self.n_slabs,) + ext[0].shape[1:], rho.sharding, ext)
        return self._run(ext, dt)

