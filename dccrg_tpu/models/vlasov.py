"""Vlasiator-style Vlasov advection: a velocity-space block per spatial
cell — BASELINE's stretch configuration ("large f(v) block per spatial
cell"), the payload shape of the Vlasiator space-plasma code that the
reference grid underlies (reference CREDITS:4-6).

Solves df/dt + v·∇_x f = 0: each velocity bin advects through space with
its own constant velocity.  Payload per cell is the flattened [B = nv³]
distribution block; the step is the dimension-split upwind scheme of the
advection workload applied to every bin at once — on TPU this turns the
reference's per-cell block loops into one fused [D, nz, ny, nx, B] array
program where B rides the vectorized minor dimension.

Uniform slab-partitioned grids use the dense layout (parallel/dense.py)
with fused Pallas kernels and a dimension-SPLIT update (x, then y, then
z per step — the TPU-efficient form).  AMR or arbitrarily-partitioned
grids run the general row-layout path over the gather tables — the
reference's actual Vlasiator shape (an AMR spatial grid with one
velocity block per leaf) — pricing all faces UNSPLIT so each bin's
update is exactly the oracle-validated advection step with that bin's
constant velocity (the only available correctness anchor for 2:1 AMR
faces).  The two layouts therefore differ by the O(dt) splitting error
(tests pin the convergence); mass is conserved exactly on both.  Either
way the halo moves whole f(v) blocks (B doubles per ghost cell), which
is exactly the bandwidth profile the Vlasiator use case stresses.

Boundaries follow ``grid.topology``: periodic dimensions wrap; open
dimensions use vacuum inflow (f = 0 outside the domain) with free
outflow, the standard open-boundary closure for an upwind scheme — mass
then decreases monotonically as phase-space density leaves the box.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.dense import HaloExtend
from ..parallel.mesh import SHARD_AXIS, shard_spec
from ..utils.collectives import fetch
from ..utils.fallback import fallback_call

__all__ = ["Vlasov"]


class Vlasov:
    def __init__(self, grid, nv: int = 4, v_max: float = 1.0,
                 dtype=np.float32, use_pallas=True, overlap: bool = False):
        self.grid = grid
        #: split-phase stepping (ISSUE 7): run the general gather-path
        #: update as the fused start → interior → finish → boundary body.
        #: Forces the row layout even on slab grids — the split form
        #: exists to overlap the halo seam, which the dense ring hides
        #: inside its own shard_map.
        self.overlap = bool(overlap)
        self.info = grid.epoch.dense if not overlap else None
        self.nv = nv
        self.v_max = float(v_max)
        self.B = nv**3
        self.dtype = dtype
        self.use_pallas = use_pallas
        centers = (np.arange(nv) + 0.5) / nv * 2 * v_max - v_max
        vz, vy, vx = np.meshgrid(centers, centers, centers, indexing="ij")
        #: velocity of each bin, [B, 3]
        self.v_bins = np.stack([vx.ravel(), vy.ravel(), vz.ravel()], axis=-1)
        if self.info is not None:
            self._build_step()
        else:
            # AMR / non-slab grids: the general row-layout path — one
            # f(v) block per leaf over the gather tables, the
            # Vlasiator-on-dccrg configuration (AMR spatial grid with a
            # velocity block per cell)
            self._fused_block = 0
            self._build_general_step()

    def spec(self):
        return {"f": ((self.B,), self.dtype)}

    # ------------------------------------------------------------- kernels

    def _build_step(self):
        """Dense-layout kernels, cached as one bundle: every compiled
        artifact is a pure function of (mesh, dims, periodicity, cell
        size, velocity grid, dtype, pallas mode)."""
        from ..parallel.exec_cache import mesh_key

        info = self.info
        l0 = self.grid.geometry.get_level_0_cell_length()
        pallas_mode = (self.use_pallas if isinstance(self.use_pallas, str)
                       else bool(self.use_pallas))
        key = (
            "vlasov.dense", mesh_key(self.grid.mesh), info.n_devices,
            info.nz_local, info.ny, info.nx, self.nv, self.v_max,
            tuple(bool(p) for p in info.periodic),
            str(np.dtype(self.dtype)), pallas_mode,
            tuple(np.asarray(l0, np.float64).tolist()),
        )
        self._dense_key = key
        bundle = self.grid.exec_cache.get(key, self._build_dense_bundle)
        self._fused_block = bundle["fused_block"]
        self._step_xla, self._run_xla = bundle["step_xla"], bundle["run_xla"]
        self._step, self._run = bundle["step"], bundle["run"]

    def _build_dense_bundle(self) -> dict:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        info = self.info
        grid = self.grid
        dtype = self.dtype
        D = info.n_devices
        l0 = grid.geometry.get_level_0_cell_length()
        inv_dx = (1.0 / l0).astype(np.float64)
        extend = HaloExtend(info)
        v = jnp.asarray(self.v_bins, dtype)          # [B, 3]
        mesh = grid.mesh
        data_spec = P(SHARD_AXIS)

        def split_dim(f, f_lo, f_hi, vd, inv_dxd, dt, axis):
            """One dimension's upwind update for all bins.  f: [nzl, ny,
            nx, B]; f_lo/f_hi: neighbor values on the low/high side."""
            flux_hi = jnp.where(vd >= 0, f, f_hi) * vd      # at i+1/2
            flux_lo = jnp.where(vd >= 0, f_lo, f) * vd      # at i-1/2
            return f - dt * inv_dxd * (flux_hi - flux_lo)

        periodic = tuple(bool(p) for p in info.periodic)

        def body(f, dt):
            f = f[0]                                  # [nzl, ny, nx, B]
            # x and y wrap inside the block; open dimensions get vacuum
            # inflow (zero the wrapped-in plane) per grid.topology
            f_lo, f_hi = jnp.roll(f, 1, 2), jnp.roll(f, -1, 2)
            if not periodic[0]:
                f_lo = f_lo.at[:, :, 0].set(0)
                f_hi = f_hi.at[:, :, -1].set(0)
            f = split_dim(f, f_lo, f_hi, v[:, 0], dtype(inv_dx[0]), dt, 2)
            f_lo, f_hi = jnp.roll(f, 1, 1), jnp.roll(f, -1, 1)
            if not periodic[1]:
                f_lo = f_lo.at[:, 0].set(0)
                f_hi = f_hi.at[:, -1].set(0)
            f = split_dim(f, f_lo, f_hi, v[:, 1], dtype(inv_dx[1]), dt, 1)
            # z goes through the slab halo ring; for an open z boundary the
            # ring's wrap-around planes on the first/last device are vacuum
            fe = extend(f)
            if not periodic[2]:
                d = jax.lax.axis_index(SHARD_AXIS)
                fe = fe.at[0].multiply(jnp.where(d == 0, 0, 1).astype(dtype))
                fe = fe.at[-1].multiply(jnp.where(d == D - 1, 0, 1).astype(dtype))
            f = split_dim(f, fe[:-2], fe[2:], v[:, 2], dtype(inv_dx[2]), dt, 0)
            return (f[None],)

        # ---- blocked fused Pallas step (ops/vlasov_kernel.py): all three
        # dimension splits in one HBM pass, bit-identical to `body`.  An
        # optimization layered over the always-built XLA step: a Mosaic
        # rejection at first call disables it for the instance (the
        # flat-AMR / fused-GoL fallback pattern)
        fused_block = 0
        from ..ops.dense_advection import pallas_available
        from ..ops.vlasov_kernel import (
            make_vlasov_step_blocked,
            pick_vlasov_block,
        )

        interpret = self.use_pallas == "interpret"
        nzl, ny, nx, B = info.nz_local, info.ny, info.nx, self.B
        blk = pick_vlasov_block(nzl, ny, nx, B)
        body_fast = None
        if (
            self.use_pallas
            and np.dtype(dtype) == np.float32
            and blk
            and (interpret or pallas_available(np.float32))
        ):
            fused_block = blk
            kern = make_vlasov_step_blocked(
                nzl, ny, nx, B, inv_dx, periodic, block=blk,
                interpret=interpret,
            )
            vb = jnp.asarray(self.v_bins, jnp.float32)
            vxb = vb[:, 0].reshape(1, 1, 1, B)
            vyb = vb[:, 1].reshape(1, 1, 1, B)
            vzb = vb[:, 2].reshape(1, 1, 1, B)

            def body_fast(f, dt):
                f = f[0]
                lo, hi = extend.planes(f)
                if not periodic[2]:
                    # open z: the wrap-received device-edge planes are
                    # vacuum — below device 0, above device D-1
                    d = jax.lax.axis_index(SHARD_AXIS)
                    lo = lo * jnp.where(d == 0, 0, 1).astype(dtype)
                    hi = hi * jnp.where(d == D - 1, 0, 1).astype(dtype)
                return (kern(f, lo, hi, vxb, vyb, vzb, dt)[None],)

        def make_pair(b):
            fn = shard_map(
                b,
                mesh=mesh,
                in_specs=(data_spec, P()),
                out_specs=(data_spec,),
                check_vma=False,
            )

            @jax.jit
            def step(state, dt):
                (f,) = fn(state["f"], jnp.asarray(dt, dtype))
                return {"f": f}

            @jax.jit
            def run(state, steps, dt):
                dt = jnp.asarray(dt, dtype)
                return jax.lax.fori_loop(
                    0, steps, lambda i, st: step(st, dt), state
                )

            return step, run

        step_xla, run_xla = make_pair(body)
        if body_fast is not None:
            step_fast, run_fast = make_pair(body_fast)
        else:
            step_fast, run_fast = step_xla, run_xla
        return {
            "fused_block": fused_block,
            "step_xla": step_xla,
            "run_xla": run_xla,
            "step": step_fast,
            "run": run_fast,
        }

    def _disable_fused(self):
        self._fused_block = 0
        self._step, self._run = self._step_xla, self._run_xla

    # --------------------------------------------------- general (AMR)

    def _build_general_step(self):
        """Row-layout Vlasov over the gather tables — the reference's
        actual Vlasiator shape: an AMR spatial grid with one f(v) block
        per leaf.  Per-face semantics mirror the advection workload's
        (``solve.hpp:129-260`` via the shared face tables) with the
        bin's CONSTANT velocity as the face velocity (spatially constant
        fields make the reference's length-weighted interpolation the
        identity), applied to every bin at once on the ``[D, R, B]``
        payload."""
        from ..parallel.stencil import (
            StencilTables,
            gather_neighbors,
            ordered_sum,
        )
        from .advection import build_face_tables

        from ..parallel.mesh import put_table

        grid = self.grid
        dtype = self.dtype
        self.tables = StencilTables(grid, None, with_geometry=True)
        self._exchange = grid.halo(None)
        host_face, dev = build_face_tables(grid, None, self.tables, dtype)
        t = self.tables.tree()

        # open-boundary face areas per cell per axis/side: the dense
        # path's vacuum-inflow/free-outflow closure (zero incoming, full
        # upwind outgoing) — a boundary face emits no hood entry, so its
        # outflow must be priced explicitly or open boundaries silently
        # degrade to zero-flux walls
        epoch = grid.epoch
        mapping = epoch.mapping
        leaves = epoch.leaves
        cells = leaves.cells
        idxs = mapping.get_indices(cells).astype(np.int64)
        clen = mapping.get_cell_length_in_indices(cells).astype(np.int64)
        lengths = np.asarray(grid.geometry.get_length(cells), np.float64)
        extent = (np.asarray(mapping.length, np.int64)
                  << mapping.max_refinement_level)
        D, R = epoch.n_devices, epoch.R
        bnd_pos = np.zeros((3, D, R))
        bnd_neg = np.zeros((3, D, R))
        devs, rows = epoch.global_rows(np.arange(len(cells)))
        for d3 in range(3):
            if grid.topology.is_periodic(d3):
                continue
            area = lengths[:, (d3 + 1) % 3] * lengths[:, (d3 + 2) % 3]
            hi = (idxs[:, d3] + clen) == extent[d3]
            lo = idxs[:, d3] == 0
            bnd_pos[d3][devs, rows] = np.where(hi, area, 0.0)
            bnd_neg[d3][devs, rows] = np.where(lo, area, 0.0)
        has_open = bool(bnd_pos.any() or bnd_neg.any())
        # one (D, R) table per axis/side: put_table shards the leading
        # (device) axis
        bnd_pos_dev = tuple(put_table(bnd_pos[d3], grid.mesh, dtype)
                            for d3 in range(3))
        bnd_neg_dev = tuple(put_table(bnd_neg[d3], grid.mesh, dtype)
                            for d3 in range(3))

        from ..parallel.exec_cache import traced_jit

        ex = self._exchange
        ex_body = ex.raw_body
        rings = tuple(ex.ring_send) + tuple(ex.ring_recv)

        def build():
            def step(rings, t, dev, vbT, bnd_pos_dev, bnd_neg_dev,
                     state, dt):
                state = {**state, **ex_body(*rings, {"f": state["f"]})}
                f = state["f"]                            # [D, R, B]
                f_n = gather_neighbors(f, t["nbr_rows"])  # [D, R, K, B]
                sgn = jnp.sign(dev["face_dir"]).astype(f.dtype)[..., None]
                ai = dev["axis_idx"].astype(jnp.int32)    # [D, R, K]
                v_face = vbT[ai]                          # [D, R, K, B]
                f_c = f[:, :, None, :]
                up_pos = jnp.where(v_face >= 0, f_c, f_n)
                up_neg = jnp.where(v_face >= 0, f_n, f_c)
                upwind = jnp.where(sgn > 0, up_pos, up_neg)
                face_flux = (upwind * (dt * v_face)
                             * dev["min_area"][..., None])
                contrib = jnp.where(
                    (dev["face_dir"] != 0)[..., None], -sgn * face_flux,
                    0.0,
                )
                total = ordered_sum(contrib, axis=-2)
                if has_open:
                    # outgoing-only boundary faces (incoming is vacuum)
                    rate = sum(
                        bnd_pos_dev[d3][..., None]
                        * jnp.maximum(vbT[d3], 0)
                        + bnd_neg_dev[d3][..., None]
                        * jnp.maximum(-vbT[d3], 0)
                        for d3 in range(3)
                    )
                    total = total - dt * f * rate
                flux = total * dev["inv_volume"][..., None]
                local = t["local_mask"][..., None]
                return {**state, "f": jnp.where(local, f + flux, f)}

            step_k = traced_jit("vlasov.step", step)

            def run(rings, t, dev, vbT, bnd_pos_dev, bnd_neg_dev,
                    state, steps, dt):
                dt_ = jnp.asarray(dt, dtype)
                return jax.lax.fori_loop(
                    0, steps,
                    lambda i, st: step_k(rings, t, dev, vbT, bnd_pos_dev,
                                         bnd_neg_dev, st, dt_),
                    state,
                )

            return step_k, traced_jit("vlasov.run", run)

        step_fn, run_fn = self.grid.exec_cache.get(
            ("vlasov.step", ex.structure_key, str(np.dtype(dtype)),
             has_open), build
        )
        vbT = jnp.asarray(self.v_bins.T, dtype)
        args = (rings, t, dev, vbT, bnd_pos_dev, bnd_neg_dev)
        self._has_open = has_open
        self._gen_fn, self._gen_args = step_fn, args
        self._step = self._step_xla = (
            lambda state, dt: step_fn(*args, state, dt)
        )
        self._run = self._run_xla = (
            lambda state, steps, dt: run_fn(*args, state, steps, dt)
        )
        if self.overlap:
            # the eager kernels above stay on _step_xla/_run_xla (the
            # in-process oracle); step()/run() take the fused split form
            self._build_split_general(host_face, bnd_pos, bnd_neg,
                                      has_open)

    def _build_split_general(self, host_face, bnd_pos, bnd_neg, has_open):
        """Fused split-phase step on the row layout (ISSUE 7): halo
        start → interior bins (compacted inner rows, no data dependence
        on the in-flight f blocks) → ghost merge → boundary bins.  The
        flux math is the eager general step's verbatim, restricted per
        row set — see Advection._build_split_step for the bit-identity
        argument (invalid slots masked by ``face_dir == 0``)."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.exec_cache import traced_jit
        from ..parallel.halo import HaloExchange
        from ..parallel.stencil import ordered_sum
        from jax import shard_map
        from .advection import _table_specs, build_split_tables

        grid = self.grid
        dtype = self.dtype
        extra = {}
        for d3 in range(3):
            extra[f"bnd_pos{d3}"] = bnd_pos[d3]
            extra[f"bnd_neg{d3}"] = bnd_neg[d3]
        inner, outer, local = build_split_tables(
            grid, None, host_face, dtype, extra=extra
        )
        ex = self._exchange
        ring_start = ex.make_ring_start()
        ks = tuple(ex.ring_ks)
        mesh = grid.mesh
        rings = tuple(ex.ring_send) + tuple(ex.ring_recv)

        def build():
            nk = len(ks)
            data_spec = P(SHARD_AXIS)
            idx_spec = P(SHARD_AXIS, None)

            def side_update(f, t, vbT, dt):
                rows = t["rows"]
                f_c = f[rows]                               # [W, B]
                f_n = f[t["nbr_rows"]]                      # [W, K, B]
                sgn = jnp.sign(t["face_dir"]).astype(f.dtype)[..., None]
                ai = t["axis_idx"].astype(jnp.int32)
                v_face = vbT[ai]                            # [W, K, B]
                fc = f_c[:, None, :]
                up_pos = jnp.where(v_face >= 0, fc, f_n)
                up_neg = jnp.where(v_face >= 0, f_n, fc)
                upwind = jnp.where(sgn > 0, up_pos, up_neg)
                face_flux = (upwind * (dt * v_face)
                             * t["min_area"][..., None])
                contrib = jnp.where(
                    (t["face_dir"] != 0)[..., None], -sgn * face_flux,
                    0.0,
                )
                total = ordered_sum(contrib, axis=-2)
                if has_open:
                    rate = sum(
                        t[f"bnd_pos{d3}"][..., None]
                        * jnp.maximum(vbT[d3], 0)
                        + t[f"bnd_neg{d3}"][..., None]
                        * jnp.maximum(-vbT[d3], 0)
                        for d3 in range(3)
                    )
                    total = total - dt * f_c * rate
                return f_c + total * t["inv_volume"][..., None]

            def body(*args):
                sends = [a[0] for a in args[:nk]]
                recvs = [a[0] for a in args[nk:2 * nk]]
                ti, to, local, vbT, f, dt = args[2 * nk:]
                sub = lambda t: {k: v[0] for k, v in t.items()}
                ti, to = sub(ti), sub(to)
                fb = f[0]
                payloads = ring_start(fb, sends)
                new_i = side_update(fb, ti, vbT, dt)
                f2 = HaloExchange.ring_finish(fb, recvs, payloads)
                new_o = side_update(f2, to, vbT, dt)
                out = f2.at[ti["rows"]].set(new_i).at[to["rows"]].set(new_o)
                out = jnp.where(local[0][..., None], out, f2)
                return out[None]

            fn = shard_map(
                body,
                mesh=mesh,
                in_specs=(idx_spec,) * (2 * nk)
                + (_table_specs(inner), _table_specs(outer), idx_spec,
                   P())
                + (data_spec, P()),
                out_specs=data_spec,
                check_vma=False,
            )

            def step(rings, ti, to, local, vbT, state, dt):
                return {**state, "f": fn(*rings, ti, to, local, vbT,
                                         state["f"], dt)}

            step_k = traced_jit("vlasov.split_step", step)

            def run(rings, ti, to, local, vbT, state, steps, dt):
                dt_ = jnp.asarray(dt, dtype)
                return jax.lax.fori_loop(
                    0, steps,
                    lambda i, st: step_k(rings, ti, to, local, vbT, st,
                                         dt_),
                    state,
                )

            return step_k, traced_jit("vlasov.split_run", run)

        step_fn, run_fn = self.grid.exec_cache.get(
            ("vlasov.split_step", ex.structure_key, str(np.dtype(dtype)),
             has_open), build
        )
        vbT = jnp.asarray(self.v_bins.T, dtype)
        args = (rings, inner, outer, local, vbT)
        self._split_fn_k, self._split_args = step_fn, args
        self._step = lambda state, dt: step_fn(*args, state, dt)
        self._run = lambda state, steps, dt: run_fn(*args, state, steps, dt)

    # ------------------------------------------------------------ user API

    def initialize_state(self, thermal_v: float = 0.35):
        info = self.info
        grid = self.grid
        cells = grid.get_cells()
        centers = grid.geometry.get_center(cells)
        # spatial density hump (advection workload's cosine bump in 3-D)
        r = np.minimum(
            np.sqrt(((centers - 0.5) ** 2).sum(axis=1)), 0.25
        ) / 0.25
        rho = 0.25 * (1 + np.cos(np.pi * r)) + 0.01
        maxwell = np.exp(-((self.v_bins**2).sum(axis=1)) / (2 * thermal_v**2))
        maxwell /= maxwell.sum()
        f = rho[:, None] * maxwell[None, :]

        if info is None:
            # general row layout: one [B] block per leaf row
            state = grid.new_state(self.spec())
            state = grid.set_cell_data(state, "f", cells, f)
            return grid.update_copies_of_remote_neighbors(state)

        shape = (info.n_devices, info.nz_local, info.ny, info.nx, self.B)
        host = np.zeros(shape, self.dtype)
        lin = (cells - np.uint64(1)).astype(np.int64)
        x = lin % info.nx
        y = (lin // info.nx) % info.ny
        z = lin // (info.nx * info.ny)
        host[z // info.nz_local, z % info.nz_local, y, x] = f
        return {
            "f": jax.device_put(jnp.asarray(host), shard_spec(self.grid.mesh, 5))
        }

    def step(self, state, dt):
        if self._fused_block:
            return fallback_call(
                "fused Vlasov kernel", self._step, self._step_xla,
                self._disable_fused, state, dt,
            )
        return self._step(state, dt)

    def _wide_spec(self):
        """Exchange-amortized step split (ISSUE 14; see
        ``Advection._wide_spec`` — same face-relevance argument, applied
        per velocity bin).  The open-boundary face areas are scattered to
        EVERY replica row (``wide_halo.scatter_rows``), since interior
        steps update live ghost rows too and the owner-rows-only scatter
        of ``_build_general_step`` would silently zero their outflow."""
        from ..parallel.exec_cache import WideStepSpec, traced_jit
        from ..parallel.mesh import put_table
        from ..parallel.stencil import gather_neighbors, ordered_sum
        from ..parallel.wide_halo import (
            get_wide_plan,
            scatter_rows,
            wide_enabled,
        )
        from .advection import build_face_tables

        if not wide_enabled() or self.info is not None:
            return None
        cached = getattr(self, "_wide_cached", None)
        if cached is not None and cached[0] is self.grid.epoch:
            return cached[1]
        grid = self.grid
        plan = get_wide_plan(grid, None, relevance="face")
        spec = None
        if plan.budget >= 2:
            dtype = self.dtype
            wex = grid.halo(None)
            wex_body = wex.raw_body
            wrings = tuple(wex.ring_send) + tuple(wex.ring_recv)
            mesh = grid.mesh
            _, wdev = build_face_tables(
                grid, None, self.tables, dtype,
                hood_arrays=(plan.nbr_offset, plan.nbr_len,
                             plan.nbr_rows, plan.nbr_valid),
            )
            wt = dict(wdev)
            wt["nbr_rows"] = put_table(plan.nbr_rows, mesh)
            wt["steps_ok"] = put_table(plan.steps_ok, mesh)

            epoch = grid.epoch
            mapping = epoch.mapping
            cells = epoch.leaves.cells
            idxs = mapping.get_indices(cells).astype(np.int64)
            clen = mapping.get_cell_length_in_indices(cells)
            clen = clen.astype(np.int64)
            lengths = np.asarray(
                grid.geometry.get_length(cells), np.float64
            )
            extent = (np.asarray(mapping.length, np.int64)
                      << mapping.max_refinement_level)
            has_open = self._has_open
            for d3 in range(3):
                pos_leaf = np.zeros(len(cells))
                neg_leaf = np.zeros(len(cells))
                if not grid.topology.is_periodic(d3):
                    area = (lengths[:, (d3 + 1) % 3]
                            * lengths[:, (d3 + 2) % 3])
                    hi = (idxs[:, d3] + clen) == extent[d3]
                    pos_leaf = np.where(hi, area, 0.0)
                    neg_leaf = np.where(idxs[:, d3] == 0, area, 0.0)
                wt[f"bnd_pos{d3}"] = put_table(
                    scatter_rows(epoch, pos_leaf), mesh, dtype
                )
                wt[f"bnd_neg{d3}"] = put_table(
                    scatter_rows(epoch, neg_leaf), mesh, dtype
                )

            def build():
                def interior(wt, vbT, state, dt, j):
                    f = state["f"]                            # [D, R, B]
                    f_n = gather_neighbors(f, wt["nbr_rows"])
                    sgn = jnp.sign(wt["face_dir"]).astype(
                        f.dtype
                    )[..., None]
                    ai = wt["axis_idx"].astype(jnp.int32)
                    v_face = vbT[ai]
                    f_c = f[:, :, None, :]
                    up_pos = jnp.where(v_face >= 0, f_c, f_n)
                    up_neg = jnp.where(v_face >= 0, f_n, f_c)
                    upwind = jnp.where(sgn > 0, up_pos, up_neg)
                    face_flux = (upwind * (dt * v_face)
                                 * wt["min_area"][..., None])
                    contrib = jnp.where(
                        (wt["face_dir"] != 0)[..., None],
                        -sgn * face_flux, 0.0,
                    )
                    total = ordered_sum(contrib, axis=-2)
                    if has_open:
                        rate = sum(
                            wt[f"bnd_pos{d3}"][..., None]
                            * jnp.maximum(vbT[d3], 0)
                            + wt[f"bnd_neg{d3}"][..., None]
                            * jnp.maximum(-vbT[d3], 0)
                            for d3 in range(3)
                        )
                        total = total - dt * f * rate
                    flux = total * wt["inv_volume"][..., None]
                    live = (wt["steps_ok"] > j)[..., None]
                    return {**state, "f": jnp.where(live, f + flux, f)}

                return traced_jit("vlasov.wide_step", interior)

            fn = self.grid.exec_cache.get(
                ("vlasov.wide_step", wex.structure_key,
                 str(np.dtype(dtype)), has_open, self.nv), build
            )
            vbT = jnp.asarray(self.v_bins.T, dtype)
            spec = WideStepSpec(
                exchange=lambda args, wargs, state: {
                    **state, **wex_body(*wargs[0], {"f": state["f"]})
                },
                interior=lambda args, wargs, state, dt, j: fn(
                    wargs[1], wargs[2], state, dt, j
                ),
                budget=plan.budget,
                args=(wrings, wt, vbT),
                local_mask=plan.local_mask,
            )
        self._wide_cached = (self.grid.epoch, spec)
        return spec

    def batch_step_spec(self):
        """Cohort-batchable step entry point (ISSUE 9; see
        ``Advection.batch_step_spec``).  ``nv`` rides the kernel key:
        two cohorts with different velocity-space resolutions compile
        different member programs even at one spatial signature."""
        from ..parallel.exec_cache import (
            BatchStepSpec,
            default_steps_per_dispatch,
        )

        k = default_steps_per_dispatch()
        dtype = np.dtype(self.dtype)
        if self.info is not None:
            step = self._step
            return BatchStepSpec(
                kind="vlasov.dense", kernel_key=self._dense_key,
                call=lambda args, state, dt: step(state, dt),
                args=(), dt_dtype=dtype, steps_per_dispatch=k,
            )
        ex = self._exchange
        wide = self._wide_spec()
        if self.overlap:
            fn = self._split_fn_k
            return BatchStepSpec(
                kind="vlasov.split",
                kernel_key=("vlasov.split_step", ex.structure_key,
                            str(dtype), self._has_open, self.nv),
                call=lambda args, state, dt: fn(*args, state, dt),
                args=self._split_args, dt_dtype=dtype,
                steps_per_dispatch=k, wide=wide,
            )
        fn = self._gen_fn
        return BatchStepSpec(
            kind="vlasov",
            kernel_key=("vlasov.step", ex.structure_key, str(dtype),
                        self._has_open, self.nv),
            call=lambda args, state, dt: fn(*args, state, dt),
            args=self._gen_args, dt_dtype=dtype, steps_per_dispatch=k,
            wide=wide,
        )

    def _record_run(self, path: str, steps, state) -> None:
        """Post-run reconciliation (obs.fused): the device-loop runs keep
        their ghost traffic inside jit.  Dense layout: each step's slab
        ring ships two [ny, nx, B] planes per device (none on a single
        device, where the wrap is local); general layout: the full-f
        halo schedule."""
        from ..obs import fused

        if not self.grid.telemetry.enabled:
            return
        try:
            if self.info is not None:
                D = self.grid.n_devices
                itemsize = np.dtype(self.dtype).itemsize
                bps = (
                    D * 2 * self.info.ny * self.info.nx * self.B * itemsize
                    if D > 1 else 0
                )
            else:
                bps = self.grid.halo(None).bytes_moved({"f": state["f"]})
        except Exception:  # noqa: BLE001 — telemetry must never raise
            bps = 0
        fused.record_run("vlasov", path, steps, bps)

    def run(self, state, steps: int, dt):
        if self._fused_block:
            self._record_run("fused", steps, state)
            return fallback_call(
                "fused Vlasov kernel", self._run, self._run_xla,
                self._disable_fused, state, steps, dt,
            )
        self._record_run(
            "xla" if self.info is not None
            else ("split" if self.overlap else "general"),
            steps, state,
        )
        return self._run(state, steps, dt)

    def max_time_step(self) -> float:
        if self.info is None:
            # the general path's update is UNSPLIT: all three dimensions'
            # donor-cell fluxes accumulate in one step, so the stability
            # bound is dt <= 1 / max_cells sum_d |v|max_d / len_d — up
            # to 3x tighter than the per-dimension bound the split dense
            # update obeys
            lengths = np.asarray(
                self.grid.geometry.get_length(self.grid.get_cells()),
                np.float64,
            )
            vmax_d = np.abs(self.v_bins).max(axis=0)       # (3,)
            courant = (vmax_d / np.maximum(lengths, 1e-300)).sum(axis=1)
            return float(1.0 / max(courant.max(), 1e-30))
        l0 = self.grid.geometry.get_level_0_cell_length()
        vmax = np.abs(self.v_bins).max()
        return float(l0.min() / max(vmax, 1e-30))

    def density(self, state) -> np.ndarray:
        """Velocity-space integral per spatial cell: [D, nzl, ny, nx]
        on the dense layout, [D, R] rows on the general layout."""
        return fetch(state["f"], dtype=np.float64).sum(axis=-1)

    def total_mass(self, state) -> float:
        if self.info is None:
            grid = self.grid
            cells = np.sort(grid.leaves.cells)
            rho = np.asarray(
                grid.get_cell_data(state, "f", cells), np.float64
            ).sum(axis=-1)
            vol = np.prod(grid.geometry.get_length(cells), axis=-1)
            return float((rho * vol).sum())
        l0 = self.grid.geometry.get_level_0_cell_length()
        return float(self.density(state).sum() * np.prod(l0))
