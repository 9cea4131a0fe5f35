"""Particle-in-cell support: variable-size per-cell payloads.

Reference: ``tests/particles`` — each cell owns a list of particle
coordinates; ``get_mpi_datatype`` switches between transferring the count
and the coordinates (2-phase ragged exchange,
``tests/particles/cell.hpp:50-84``, ``simple.cpp:285-294``), and particles
that leave a cell are handed to whichever cell now contains them
(``simple.cpp:52-97``).

TPU-native formulation: ragged lists become padded ``[D, R, P, 3]`` arrays
plus an ``[D, R]`` count — the padding-based ragged-buffer strategy the
build plan prescribes.  The push is a jitted array op; the ghost update
moves counts first and coordinates second through the same halo engine
(both are exact copies).  Re-bucketing particles into their new cells is
fully device-side on uniform-Cartesian grids — refined, mixed-periodicity,
and arbitrarily partitioned included: a per-device sort over the padded
slots inside ``shard_map``, keyed on the epoch's sorted row-id tables via
the jittable cell-id algebra, claims the particles of local + ghost rows
that land in this device's own cells (the array form of the reference's
neighbor handoff), with ``run()`` advancing whole histories in one
dispatch; stretched geometries re-bucket through the host path, like
every structural mutation in this design.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import SHARD_AXIS, put_table, shard_spec
from ..parallel.stencil import StencilTables
from ..utils.collectives import fetch

__all__ = ["Particles"]


class Particles:
    def __init__(self, grid, max_particles_per_cell: int = 64, hood_id=None,
                 dtype=None):
        self.grid = grid
        self.P = int(max_particles_per_cell)
        self.hood_id = hood_id
        # coordinate dtype: f64 where x64 is enabled (the reference stores
        # doubles), otherwise f32 up front — requesting f64 under default
        # jax settings would silently truncate with a warning per alloc
        if dtype is None:
            import jax

            dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
        self.dtype = np.dtype(dtype)
        self.tables = StencilTables(grid, hood_id)
        self._exchange = grid.halo(hood_id)
        self._push = self._build_push()
        self._dev_rebucket = self._build_device_rebucket()

    def spec(self):
        return {
            "particles": ((self.P, 3), self.dtype),
            "number_of_particles": ((), np.int32),
        }

    # ------------------------------------------------------------ lifecycle

    def new_state(self, positions: np.ndarray):
        """Bucket given particle positions (M, 3) into their cells."""
        state = self.grid.new_state(self.spec())
        return self._scatter(state, np.asarray(positions, dtype=np.float64))

    def _scatter(self, state, positions):
        """Bucket (M, 3) positions into their cells' padded slots — one
        sort + one scatter, no per-particle Python (the reference's
        per-particle list appends, ``tests/particles/simple.cpp:52-97``,
        become array ops)."""
        grid = self.grid
        D, R = grid.n_devices, grid.epoch.R
        pos_arr = np.zeros((D, R, self.P, 3))
        cnt = np.zeros((D, R), dtype=np.int32)
        if len(positions):
            cells = grid.get_existing_cell(positions)
            if not (cells != 0).all():
                raise ValueError("particles outside the grid")
            lpos = grid.leaves.position(cells)
            dev = grid.leaves.owner[lpos].astype(np.int64)
            row = grid.epoch.row_of[lpos].astype(np.int64)
            key = dev * R + row
            cnt_flat = np.bincount(key, minlength=D * R)
            if cnt_flat.max() > self.P:
                raise ValueError(
                    f"cell capacity exceeded ({self.P} particles/cell)"
                )
            cnt = cnt_flat.reshape(D, R).astype(np.int32)
            # stable sort groups particles by cell, preserving input order
            # within each cell; the slot is the rank within the group
            from ..utils.setops import ragged_arange

            order = np.argsort(key, kind="stable")
            ks = key[order]
            slot = ragged_arange(cnt_flat[cnt_flat > 0])
            pos_arr.reshape(D * R, self.P, 3)[ks, slot] = positions[order]
        put = lambda a: jax.device_put(
            jnp.asarray(a), shard_spec(self.grid.mesh, np.ndim(a))
        )
        return {
            **state,
            "particles": put(pos_arr),
            "number_of_particles": put(cnt),
        }

    # ---------------------------------------------------------------- step

    def _build_push(self):
        from ..parallel.exec_cache import traced_jit

        def build():
            def push(local, state, velocity, dt):
                P = state["particles"].shape[2]
                slot = jnp.arange(P, dtype=jnp.int32)[None, None, :]
                valid = slot < state["number_of_particles"][..., None]
                v = jnp.asarray(velocity)
                if v.ndim == 3:          # per-cell field [D, R, 3]
                    v = v[:, :, None, :]
                moved = state["particles"] + v * dt
                new = jnp.where(
                    (valid & local[..., None])[..., None], moved,
                    state["particles"],
                )
                return {**state, "particles": new}

            return traced_jit("particles.push", push)

        fn = self.grid.exec_cache.get(("particles.push",), build)
        local = self.tables.local_mask
        self._push_fn, self._push_args = fn, (local,)
        return lambda state, velocity, dt: fn(local, state, velocity, dt)

    # --------------------------------------------- device-side re-bucketing

    def _build_device_rebucket(self):
        """Jitted re-bucket keyed on the epoch's leaf tables: per device,
        one sort of the padded slots keys particles by target local row;
        ghost rows supply the neighbors' emigrants (so the CFL-style
        constraint is the halo width, exactly the reference's
        neighbor-handoff reach, ``tests/particles/simple.cpp:52-97``).

        The target cell of a position is found with the id algebra
        (``core/mapping.py``): the candidate cell id at every refinement
        level is pure shift/add arithmetic on the max-resolution voxel
        triple, and exactly one candidate can appear in this device's
        sorted row-id table (leaves are disjoint) — so AMR grids and any
        post-``balance_load`` ownership stay on device.  Mixed
        periodicity is handled per axis; a particle escaping through a
        non-periodic boundary or out-running the ghost halo is dropped
        and counted in the state's ``overflow`` scalar, as is capacity
        overflow of a cell's ``P`` slots.

        Returns None when the grid does not qualify (stretched geometry,
        whose per-cell sizes the voxel arithmetic cannot express, or an
        id space past the integer width jax can use) — the host path
        stays the general mechanism."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as Pspec

        grid = self.grid
        epoch = grid.epoch
        mapping = epoch.mapping
        leaves = grid.leaves
        N = len(leaves)
        if N == 0:
            return None
        # uniform Cartesian only: the device path buckets by a single
        # level-0 cell size, which a stretched geometry does not have
        if not getattr(grid.geometry, "uniform_level0", False):
            return None
        D, R, P = epoch.n_devices, epoch.R, self.P
        # candidate ids (and the dead-row sentinels past them) must fit
        # the device integer width: int32 always works on TPU; int64
        # needs jax x64 mode
        if int(mapping.last_cell) + R + 2 < 2**31:
            id_dtype = jnp.int32
        elif jax.config.jax_enable_x64 and int(mapping.last_cell) + R + 2 < 2**62:
            id_dtype = jnp.int64
        else:
            return None
        L = mapping.max_refinement_level
        geo = grid.geometry
        nx, ny, nz = (int(v) for v in mapping.length)
        start = np.asarray(geo.get_start(), np.float64)
        clen0 = np.asarray(geo.get_level_0_cell_length(), np.float64)
        dom = clen0 * np.array([nx, ny, nz], np.float64)
        # voxel = max-refinement-resolution index (the mapping's unit)
        vox_len = clen0 / (1 << L)
        vox_dims = np.array([nx << L, ny << L, nz << L], np.int64)
        periodic = np.asarray(grid.topology.periodic, dtype=bool)
        level_offsets = mapping._level_offsets.astype(np.int64)  # [L+2]

        # per-device sorted row-id table: dead rows (id 0) get a sentinel
        # past every real id so they sort last and never match
        cell_ids = np.asarray(epoch.cell_ids).astype(np.int64)   # [D, R]
        sentinel = int(mapping.last_cell) + 1
        keyed = np.where(cell_ids == 0, sentinel + np.arange(R)[None, :],
                         cell_ids)
        sort_order = np.argsort(keyed, axis=1)
        ids_sorted = np.take_along_axis(keyed, sort_order, axis=1)
        rows_sorted = sort_order.astype(np.int32)
        local_rows = np.asarray(self.tables.local_mask)          # [D, R]
        # only levels that actually occur need a candidate search
        levels_present = sorted(
            int(v) for v in
            np.unique(mapping.get_refinement_level(leaves.cells))
        )

        def body(pos, cnt, ids_s, rows_s, local):
            pos, cnt = pos[0], cnt[0]                 # [R,P,3], [R]
            ids_s, rows_s, local = ids_s[0], rows_s[0], local[0]
            R, P = pos.shape[0], pos.shape[1]
            dt_ = pos.dtype
            valid = (jnp.arange(P, dtype=jnp.int32)[None, :]
                     < cnt[:, None]).reshape(-1)
            p = pos.reshape(R * P, 3)
            # the domain is CLOSED ([start, end] per axis), exactly like
            # the host path's geometry: a coordinate sitting on the upper
            # edge belongs to the last cell, so wrap a periodic axis only
            # when the raw coordinate is strictly outside (a plain mod
            # would fold end onto start and diverge from the host bucket)
            lo = jnp.asarray(start, dt_)
            hi = jnp.asarray(start + dom, dt_)
            raw_in = (p >= lo) & (p <= hi)
            wrapped = lo + jnp.mod(p - lo, jnp.asarray(dom, dt_))
            wp = jnp.where(jnp.asarray(periodic) & ~raw_in, wrapped, p)
            # only a non-periodic axis can lose a particle
            in_dom = (jnp.asarray(periodic) | raw_in).all(axis=1)
            rel = (wp - lo) / jnp.asarray(vox_len, dt_)
            ivox = jnp.floor(rel).astype(id_dtype)
            ivox = jnp.clip(ivox, 0, jnp.asarray(vox_dims - 1, id_dtype))
            # candidate cell id at each level PRESENT in the leaf set:
            # shift the voxel triple to level resolution, linearize
            # x-fastest, add the level block offset
            # (mapping.get_cell_from_indices, jittable form)
            row = jnp.zeros(R * P, jnp.int32)
            found = jnp.zeros(R * P, bool)
            for lvl in levels_present:
                s = L - lvl
                cx, cy, cz = ivox[:, 0] >> s, ivox[:, 1] >> s, ivox[:, 2] >> s
                lx = id_dtype(nx << lvl)
                ly = id_dtype(ny << lvl)
                cand = id_dtype(level_offsets[lvl]) + cx + lx * (cy + ly * cz)
                pos_s = jnp.searchsorted(ids_s, cand)
                hit = ids_s[jnp.minimum(pos_s, R - 1)] == cand
                row = jnp.where(hit & ~found,
                                rows_s[jnp.minimum(pos_s, R - 1)], row)
                found = found | hit
            claimed = valid & in_dom & found & local[row]
            key = jnp.where(claimed, row, R)          # R = drop sentinel
            order = jnp.argsort(key)
            ks = key[order]
            ws = wp[order]
            slot = (jnp.arange(R * P, dtype=jnp.int32)
                    - jnp.searchsorted(ks, ks, side="left"))
            counts = jnp.zeros(R + 1, jnp.int32).at[key].add(1)[:R]
            new_pos = (
                jnp.zeros((R, P, 3), dt_)
                .at[ks, slot]
                .set(ws, mode="drop")
            )
            new_cnt = jnp.minimum(counts, P)
            # lost = canonical population before (local rows only; ghost
            # rows are duplicates) minus population after — catches
            # capacity overflow, non-periodic escapes, and particles that
            # out-ran the ghost halo (the device path's reach limit, like
            # the reference's neighbor handoff)
            before = jax.lax.psum(
                jnp.sum(cnt * local, dtype=jnp.int32), SHARD_AXIS
            )
            after = jax.lax.psum(
                jnp.sum(new_cnt, dtype=jnp.int32), SHARD_AXIS
            )
            return new_pos[None], new_cnt[None], before - after

        from ..parallel.exec_cache import mesh_key, traced_jit

        def build():
            fn = shard_map(
                body,
                mesh=grid.mesh,
                in_specs=(Pspec(SHARD_AXIS),) * 5,
                out_specs=(Pspec(SHARD_AXIS), Pspec(SHARD_AXIS), Pspec()),
                check_vma=False,
            )

            def rebucket_fn(ids_arr, rows_arr, local_arr, state):
                new_pos, new_cnt, lost = fn(
                    state["particles"], state["number_of_particles"],
                    ids_arr, rows_arr, local_arr,
                )
                return {
                    **state,
                    "particles": new_pos,
                    "number_of_particles": new_cnt,
                    "overflow": state.get("overflow", jnp.int32(0)) + lost,
                }

            return traced_jit("particles.rebucket", rebucket_fn)

        # every constant baked into the body's trace (voxel metrics,
        # level offsets, periodicity, the present refinement levels) is
        # pinned by this key; the sorted row-id tables enter as runtime
        # arguments, so churn that keeps the key re-dispatches the
        # compiled program
        key = (
            "particles.rebucket", mesh_key(grid.mesh), D,
            str(np.dtype(id_dtype)), L, (nx, ny, nz),
            tuple(np.asarray(start, np.float64).tolist()),
            tuple(np.asarray(clen0, np.float64).tolist()),
            tuple(bool(p) for p in periodic), tuple(levels_present),
        )
        fn = self.grid.exec_cache.get(key, build)
        ids_arr = put_table(ids_sorted, grid.mesh, id_dtype)
        rows_arr = put_table(rows_sorted, grid.mesh, jnp.int32)
        local_arr = put_table(local_rows, grid.mesh, bool)
        self._rebucket_fn = fn
        self._rebucket_key = key
        self._rebucket_args = (ids_arr, rows_arr, local_arr)
        return lambda state: fn(ids_arr, rows_arr, local_arr, state)

    def velocity_field(self, fn) -> np.ndarray:
        """Per-cell velocity array ``[D, R, 3]`` from a function of cell
        centers (``fn((M, 3)) -> (M, 3)``) — the reference's per-cell
        velocity data (``tests/particles/simple.cpp:52-97``) as one dense
        field the push broadcasts over each cell's particles."""
        ids = np.asarray(self.grid.epoch.cell_ids)
        D, R = ids.shape
        out = np.zeros((D, R, 3))
        live = ids.ravel() != 0
        if live.any():
            centers = self.grid.geometry.get_center(ids.ravel()[live])
            out.reshape(D * R, 3)[live] = np.asarray(fn(centers))
        return out

    def step(self, state, velocity=(0.1, 0.0, 0.0), dt: float = 1.0):
        """Push particles, refresh ghost copies (counts then coordinates —
        the reference's 2-phase idiom), then hand particles to the cells
        that now contain them.  ``velocity`` is a global (3,) vector or a
        per-cell ``[D, R, 3]`` field (see ``velocity_field``).  On
        qualifying grids every phase is device-side — no host transfer."""
        state = self._push(state, np.asarray(velocity, dtype=np.float64), dt)
        # phase 1: counts; phase 2: coordinates
        state = {**state, **self._exchange({"number_of_particles": state["number_of_particles"]})}
        state = {**state, **self._exchange({"particles": state["particles"]})}
        return self.rebucket(state)

    def run(self, state, steps: int, velocity=(0.1, 0.0, 0.0),
            dt: float = 1.0):
        """Advance ``steps`` push/exchange/re-bucket cycles in ONE
        device-side loop (requires the device re-bucket path; falls back
        to per-step host orchestration otherwise)."""
        if self._dev_rebucket is None:
            for _ in range(int(steps)):
                state = self.step(state, velocity, dt)
            return state
        if not hasattr(self, "_run"):
            from ..parallel.exec_cache import traced_jit

            ex = self._exchange
            ex_body = ex.raw_body
            rings = tuple(ex.ring_send) + tuple(ex.ring_recv)
            push_fn, rebucket_fn = self._push_fn, self._rebucket_fn

            def build():
                def run_fn(rings, local, rb_args, state, steps,
                           velocity, dt):
                    def one(_, st):
                        st = push_fn(local, st, velocity, dt)
                        st = {**st, **ex_body(*rings, {
                            "number_of_particles":
                                st["number_of_particles"],
                        })}
                        st = {**st, **ex_body(
                            *rings, {"particles": st["particles"]}
                        )}
                        return rebucket_fn(*rb_args, st)

                    return jax.lax.fori_loop(0, steps, one, state)

                return traced_jit("particles.run", run_fn)

            fn = self.grid.exec_cache.get(
                ("particles.run", ex.structure_key, self._rebucket_key),
                build,
            )
            rb_args = self._rebucket_args
            local = self._push_args[0]
            self._run = lambda state, steps, velocity, dt: fn(
                rings, local, rb_args, state, steps, velocity, dt
            )
        state = {**state, "overflow": state.get("overflow", jnp.int32(0))}
        return self._run(
            state, jnp.asarray(steps, jnp.int32),
            jnp.asarray(np.asarray(velocity, dtype=np.float64)),
            jnp.asarray(dt),
        )

    def rebucket(self, state):
        """Reassignment of particles to the cells that contain them
        (periodic wrapping included) — the device sort path when the grid
        qualifies, host-orchestrated otherwise."""
        if self._dev_rebucket is not None:
            return self._dev_rebucket(state)
        positions = self.positions(state)
        wrapped = self.grid.geometry.get_real_coordinate(positions)
        if np.isnan(wrapped).any():
            raise ValueError("particle left a non-periodic boundary")
        return self._scatter(state, wrapped)

    # ------------------------------------------------------------- queries

    def positions(self, state) -> np.ndarray:
        """All particles of local cells, (M, 3), in (device, row, slot)
        order — one boolean gather, no per-row Python."""
        pos = fetch(state["particles"])
        cnt = fetch(state["number_of_particles"])
        local = np.asarray(self.tables.local_mask)
        valid = (
            np.arange(self.P)[None, None, :] < cnt[..., None]
        ) & local[..., None]
        return pos[valid]

    def count(self, state) -> int:
        cnt = fetch(state["number_of_particles"])
        return int((cnt * np.asarray(self.tables.local_mask)).sum())

    def particles_of(self, state, cell) -> np.ndarray:
        pos = int(self.grid.leaves.position(np.uint64(cell)))
        d = int(self.grid.leaves.owner[pos])
        r = int(self.grid.epoch.row_of[pos])
        n = int(fetch(state["number_of_particles"])[d, r])
        return fetch(state["particles"])[d, r, :n]

    def remap(self, state):
        """Carry particles across a structural change (AMR or load
        balance): simply re-bucket every particle into the current grid —
        the array-level equivalent of the reference shipping unrefined
        cells' particle lists to their parents."""
        pts = self.positions(state)  # read with the OLD layout's tables
        self.tables = StencilTables(self.grid, self.hood_id)
        self._exchange = self.grid.halo(self.hood_id)
        self._push = self._build_push()
        self._dev_rebucket = self._build_device_rebucket()
        if hasattr(self, "_run"):
            del self._run
        fresh = self.grid.new_state(self.spec())
        if "overflow" in state:
            fresh["overflow"] = state["overflow"]
        return self._scatter(fresh, pts)
