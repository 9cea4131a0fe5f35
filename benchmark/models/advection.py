"""Driver of the advection configurations: builds the system under test
through its normal entry points, makes the data from the seed, issues one
call of the window, snapshots what a sampled call took and gave, and
compares those samples with the plain reference once the window has closed.

The program under test is ``dccrg_tpu``: ``Grid`` (through
``benchmark/grids.py``) and ``models.Advection``; its ``run(state, steps,
dt)`` is the timed path.
"""
from __future__ import annotations

import numpy as np

from references.advection import Layout, Reference, max_rel_err


def initial_fields(cfg: dict, seed: int):
    """The seed's data: hump centre and per-cell perturbation.

    Returns ``(centre_xy, key)``: the hump's centre and a 32-bit key for
    the perturbation, both drawn from ``seed`` alone."""
    init = cfg["initial"]
    rng = np.random.default_rng([int(seed), 0])
    jit = init["centre_jitter"]
    cx = init["centre"][0] + rng.uniform(-jit, jit)
    cy = init["centre"][1] + rng.uniform(-jit, jit)
    return (cx, cy), int(rng.integers(0, 2**31 - 1))


def hump(cfg: dict, centre, x, y, noise, xp=np):
    """Cosine hump of ``initialize.hpp`` at (x, y) plus the perturbation:
    0.25 (1 + cos(pi r)) with r the distance to the centre over the
    radius, capped at 1; ``noise`` in [0, 1) scaled by the amplitude.
    ``xp`` is numpy or jax.numpy."""
    init = cfg["initial"]
    radius = init["radius"]
    r = xp.minimum(xp.sqrt((x - centre[0]) ** 2 + (y - centre[1]) ** 2),
                   radius) / radius
    return 0.25 * (1 + xp.cos(xp.pi * r)) + init["perturbation"] * noise


class Sim:
    """One configuration's system under test, built for ``n_devices``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, n_devices: int,
                 clock):
        import jax
        import jax.numpy as jnp

        from dccrg_tpu.models import Advection

        import grids

        if cfg["partitions"] != n_devices:
            raise SystemExit(f"configuration {cfg['name']!r} states "
                             f"{cfg['partitions']} partition(s), the cell "
                             f"runs on {n_devices} chip(s)")
        self.cfg = cfg
        self.steps = int(traffic["steps_per_call"])
        self.layout = Layout(cfg["grid"])
        self.drift = float(cfg["initial"]["drift_vz"])
        with clock("grid_build"):
            self.grid = grids.build(cfg["grid"], n_devices)
        with clock("model_init"):
            self.adv = Advection(self.grid, dtype=np.float32,
                                 allow_dense=cfg["allow_dense"])
        with clock("initial_state"):
            self.state = self._initial_state(seed)
            self.dt = np.float32(cfg["cfl"] * self.adv.max_time_step(
                self.state))
        self.n_cells = self.layout.n_cells
        self.updates_per_call = self.n_cells * self.steps
        self.bytes_per_update = float(cfg["useful_bytes_per_update"])

        @jax.jit
        def bench_density_sum(rho):
            return jnp.sum(rho, dtype=jnp.float32)

        @jax.jit
        def bench_snapshot(rho):
            return jnp.copy(rho)

        self._sum = bench_density_sum
        self._snap = bench_snapshot

    # --------------------------------------------------------- the data

    def _initial_state(self, seed):
        """Velocity and seeded density in the program's own layout."""
        centre, key = initial_fields(self.cfg, seed)
        if self.adv.dense is not None:
            return self._dense_state(centre, key)
        adv, grid = self.adv, self.grid
        ids = grid.get_cells()
        c = self.layout.centres(ids)
        noise = np.random.default_rng(key).random(len(ids))
        state = adv.initialize_state()
        for name, vals in (("vx", 0.5 - c[:, 1]), ("vy", c[:, 0] - 0.5),
                           ("vz", np.full(len(ids), self.drift)),
                           ("density", hump(self.cfg, centre, c[:, 0],
                                            c[:, 1], noise))):
            state = adv.set_cell_data(state, name, ids, vals)
        # ghost copies of the new velocities and density (the model's own
        # initial state refreshes them the same way)
        return grid.halo(None)(state)

    def _dense_state(self, centre, key):
        """The uniform grid's [D, nz/D, ny, nx] z-slab blocks, made on the
        device in one jitted call."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        info = self.adv.dense
        nx, ny, nz = self.layout.n0
        D = info.n_devices
        shape = (D, nz // D, ny, nx)
        mesh = self.grid.mesh
        sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        names = tuple(self.adv.spec)
        cfg, drift = self.cfg, self.drift

        # the seed's values enter as arguments, not constants: one
        # program for every seed, found in the compile cache
        def make(k, centre):
            x = (jnp.arange(nx, dtype=jnp.float32) + 0.5) / nx
            y = (jnp.arange(ny, dtype=jnp.float32) + 0.5) / ny
            noise = jax.random.uniform(k, shape, jnp.float32)
            out = {n: jnp.zeros(shape, jnp.float32) for n in names}
            out["density"] = hump(cfg, centre, x[None, None, None, :],
                                  y[None, None, :, None], noise,
                                  jnp).astype(jnp.float32)
            out["vx"] = jnp.broadcast_to((0.5 - y)[None, None, :, None],
                                         shape).astype(jnp.float32)
            out["vy"] = jnp.broadcast_to((x - 0.5)[None, None, None, :],
                                         shape).astype(jnp.float32)
            out["vz"] = jnp.full(shape, drift, jnp.float32)
            return out

        fn = jax.jit(make, out_shardings={n: sharding for n in names})
        return fn(jax.random.key(key), jnp.asarray(centre, jnp.float32))

    # ------------------------------------------------------- the window

    def call(self):
        """One call of the window: ``steps`` steps through the program's
        one-dispatch ``run``, then the density sum the host reads back."""
        self.state = self.adv.run(self.state, self.steps, self.dt)
        return self._sum(self.state["density"])

    def snapshot(self):
        """A copy of the current density (the program may donate it)."""
        return self._snap(self.state["density"])

    def info(self) -> dict:
        """What ran: the engaged path and the halo transport."""
        from dccrg_tpu.obs import metrics

        runs = metrics.report()["counters"].get("fused.runs", {})
        paths = sorted({k.split("path=")[1].split(",")[0].rstrip("}")
                        for k in runs if "model=advection" in k})
        out = {"cells": self.n_cells, "steps_per_call": self.steps,
               "dt": float(self.dt), "paths": paths}
        if self.adv.dense is not None:
            out["dense_kind"] = list(self.adv.dense_kind)
            out["halo"] = ("z-plane ppermute (dense path)"
                           if self.adv.dense.n_devices > 1 else "none")
        else:
            out["halo"] = (self.grid.halo(None).backend
                           if self.grid.mesh.devices.size > 1 else "none")
        return out

    def check_setup(self):
        """Guards on the configuration's size, from the configuration."""
        exp = self.cfg.get("expect", {})
        if exp.get("fused_run_fits") is False:
            from dccrg_tpu.ops.dense_advection import fused_run_fits

            info = self.adv.dense
            if info is None or fused_run_fits(info.nz_local, info.ny,
                                              info.nx):
                raise SystemExit("configuration fits the whole-run fused "
                                 "kernel: it would not measure streaming")

    # ------------------------------------------------------ correctness

    def free(self):
        """Drop the program's state before the reference runs."""
        self.state = None

    def _voxels(self, rho):
        """A sampled density in the reference's [Z, Y, X] voxel layout."""
        import jax

        if self.adv.dense is not None:
            dev = jax.devices()[0]
            return jax.device_put(rho, dev).reshape(self.layout.shape)
        ids = self.grid.get_cells()
        vals = self.grid.get_cell_data({"density": rho}, "density", ids)
        return self.layout.to_voxels(ids, vals)

    def compare(self, samples) -> dict:
        """Numbers compared, each ``(value, limit)``: the cell set, and the
        widest relative gap of each sampled call's output to the reference
        advanced from that call's input."""
        lim = self.cfg["limits"]
        ids = np.sort(np.asarray(self.grid.get_cells(), np.uint64))
        want = self.layout.cell_ids()
        mismatch = (int(len(np.setxor1d(ids, want)))
                    if len(ids) != len(want) or not np.array_equal(ids, want)
                    else 0)
        out = {"cells_mismatched": (mismatch, lim["cells_mismatched"])}
        if mismatch:
            return out
        ref = Reference(self.layout, self.drift, np.float32)
        worst = 0.0
        for x_in, x_out in samples:
            a = self._voxels(x_out)
            b = ref.run(self._voxels(x_in), self.steps, self.dt)
            worst = max(worst, max_rel_err(a, b))
            del a, b
        out["max_rel_err"] = (worst, lim["max_rel_err"])
        return out

