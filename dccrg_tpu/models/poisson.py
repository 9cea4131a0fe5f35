"""Poisson solver on the (possibly AMR-refined) grid.

Reproduces the discretization and algorithm of the reference's parallel
Poisson solver (``tests/poisson/poisson_solve.hpp``):

* geometric factors per face direction from cell-center distances,
  ``f_side = ±2 / (offset_side * total_offset)`` with missing neighbors
  giving factor 0 (Neumann walls) and the diagonal ``scaling_factor =
  -sum(f)`` (``poisson_solve.hpp:691-822``);
* a finer face neighbor's contribution is divided by 4 — its 4 sub-faces
  share one coarse face (``poisson_solve.hpp:332-336``);
* the biconjugate-gradient iteration of Numerical Recipes 2.7.6 with both
  ``A·p`` and ``Aᵀ·p`` applied matrix-free (``poisson_solve.hpp:251-520``);
* the reference's three cell roles (``poisson_solve.hpp:146-150, 829-965``):
  cells listed in ``solve_cells`` are solved; cells in ``skip_cells`` are
  treated as missing neighbors (factor 0 toward them); remaining cells are
  *boundary* cells whose rhs/solution feed the solver (Dirichlet data) but
  are never updated — boundary-boundary neighbor pairs are dropped.

TPU-native formulation: the per-entry forward and transpose multipliers are
precomputed host-side into ``[D, R, K]`` tables, so each BiCG iteration is
two gathers + ordered reductions and two global dot products, all inside
one jitted ``lax.while_loop`` (a single device dispatch per solve).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.stencil import StencilTables, gather_neighbors, ordered_sum
from ..utils.collectives import fetch

__all__ = ["Poisson"]


class Poisson:
    SPEC = {
        "rhs": ((), np.float64),
        "solution": ((), np.float64),
    }

    #: cell roles, same codes as the reference (poisson_solve.hpp:146-150)
    SOLVE_CELL = 0
    BOUNDARY_CELL = 1
    SKIP_CELL = 2

    def __init__(self, grid, hood_id=None, dtype=None,
                 solve_cells=None, skip_cells=None, allow_flat=True,
                 use_pallas=True, allow_rolled=None):
        #: use_pallas follows the Advection convention: True = compiled
        #: kernels on TPU only; "interpret" = Pallas interpreter
        #: (CI/CPU coverage); False = XLA only
        self.grid = grid
        self.hood_id = hood_id
        # default dtype: f64 where x64 is enabled (the reference solves in
        # doubles), otherwise f32 up front instead of a per-alloc
        # truncation warning
        if dtype is None:
            import jax

            dtype = (np.float64 if jax.config.jax_enable_x64
                     else np.float32)
        self.dtype = dtype
        self.use_pallas = use_pallas
        self.spec = {k: (s, dtype) for k, (s, _) in self.SPEC.items()}
        self.tables = StencilTables(grid, hood_id, with_geometry=True)
        self._exchange = grid.halo(hood_id)
        self._full_solve = solve_cells is None
        self._build_cell_types(solve_cells, skip_cells)
        self._build_factors()
        self._flat_tables = None
        self._flat = self._build_flat() if allow_flat else None
        # rolled static-offset matvec (ops/rolled_gather.py): replaces
        # the [R, K] row gather in the general-path solver when the flat
        # operator does not engage; the raw gather (_apply) remains the
        # operator oracle and the residual() diagnostic.  Default
        # (None): accelerator backends only — XLA CPU's gather is
        # already vectorized (measured 2.1x FASTER than the roll chain
        # on the refined bench config), while the TPU lowering
        # scalarizes it (the 0.13x-vs-CPU showing the decomposition
        # replaces).  Pass True/False to pin either way.
        if allow_rolled is None:
            import jax

            allow_rolled = jax.default_backend() != "cpu"
        self._rolled = (self._build_rolled()
                        if allow_rolled and self._flat is None else None)
        self._solve = self._build_solver()
        self._solve_fast = self._build_fast_solver()

    def _build_flat(self):
        """Dense flat-voxel operator (ops/flat_poisson.py) — engaged when
        the grid qualifies (Cartesian, leaf levels ≤ flat_amr._ML_MAX_VL
        = 4 via the inflated-voxel layout; multi-device when ownership is
        the voxel z-slab partition); the gather tables remain the general
        path and the oracle for deeper refinement."""
        from ..ops.flat_poisson import (
            build_flat_poisson,
            make_flat_poisson_apply,
        )

        t = build_flat_poisson(
            self.grid,
            self._f_pos_leaf,
            self._f_neg_leaf,
            self._scaling_leaf,
            self._cell_type_leaf,
            self.SOLVE_CELL,
            self.SKIP_CELL,
            self.BOUNDARY_CELL,
        )
        if t is None:
            return None
        self._flat_tables = t
        return make_flat_poisson_apply(
            t, jnp.dtype(self.dtype), mesh=self.grid.mesh
        )

    def _build_cell_types(self, solve_cells, skip_cells):
        """Per-leaf role array (reference cache_system_info,
        ``poisson_solve.hpp:829-965``): everything not solved or skipped is
        a boundary cell; solve membership wins over skip."""
        leaves = self.grid.epoch.leaves
        N = len(leaves)
        if solve_cells is None:
            types = np.full(N, self.SOLVE_CELL, dtype=np.int8)
            if skip_cells is not None and len(skip_cells):
                pos = leaves.position(np.asarray(skip_cells, dtype=np.uint64))
                types[pos] = self.SKIP_CELL
        else:
            types = np.full(N, self.BOUNDARY_CELL, dtype=np.int8)
            if skip_cells is not None and len(skip_cells):
                pos = leaves.position(np.asarray(skip_cells, dtype=np.uint64))
                types[pos] = self.SKIP_CELL
            pos = leaves.position(np.asarray(solve_cells, dtype=np.uint64))
            types[pos] = self.SOLVE_CELL
        self._cell_type_leaf = types

    # ---------------------------------------------------------- factors

    def _build_factors(self):
        """Factors are computed over the GLOBAL leaf arrays (so transpose
        multipliers can reference any neighbor's factors, local or ghost)
        and then scattered into the per-device [D, R, K] tables."""
        grid = self.grid
        epoch = grid.epoch
        hood = epoch.hoods[self.hood_id]
        lists = hood.lists
        leaves = epoch.leaves
        N = len(leaves)
        D, R, K = hood.nbr_rows.shape

        counts = np.diff(lists.start)
        src = np.repeat(np.arange(N, dtype=np.int64), counts)
        nbr = lists.nbr_pos
        off = lists.offset                               # (E, 3) index units
        clen_i = grid.mapping.get_cell_length_in_indices(leaves.cells).astype(np.int64)
        nlen_i = clen_i[nbr]
        slen_i = clen_i[src]

        # face classification per entry (solve.hpp:71-123 offset logic)
        overlap = (off < slen_i[:, None]) & (off > -nlen_i[:, None])
        n_overlap = overlap.sum(axis=1)
        direction = np.zeros(len(src), dtype=np.int8)
        for d in range(3):
            direction = np.where(
                (n_overlap == 2) & (off[:, d] == slen_i), d + 1, direction
            )
            direction = np.where(
                (n_overlap == 2) & (off[:, d] == -nlen_i), -(d + 1), direction
            )

        # pairs involving a skip cell act as missing neighbors, and
        # boundary-boundary pairs are dropped (poisson_solve.hpp:896-965)
        types = self._cell_type_leaf
        active_pair = (
            (types[src] != self.SKIP_CELL)
            & (types[nbr] != self.SKIP_CELL)
            & ~(
                (types[src] == self.BOUNDARY_CELL)
                & (types[nbr] == self.BOUNDARY_CELL)
            )
        )

        half = 0.5 * grid.geometry.get_length(leaves.cells)   # (N, 3)
        # per-leaf center offsets toward face neighbors; missing neighbors
        # default to own size but give factor 0 (poisson_solve.hpp:716-724)
        pos_off = 2.0 * half.copy()
        neg_off = -2.0 * half.copy()
        has_pos = np.zeros((N, 3), dtype=bool)
        has_neg = np.zeros((N, 3), dtype=bool)
        for d in range(3):
            m = (direction == d + 1) & active_pair
            pos_off[src[m], d] = half[src[m], d] + half[nbr[m], d]
            has_pos[src[m], d] = True
            m = (direction == -(d + 1)) & active_pair
            neg_off[src[m], d] = -(half[src[m], d] + half[nbr[m], d])
            has_neg[src[m], d] = True

        total = pos_off - neg_off                        # (N, 3)
        f_pos = np.where(has_pos, 2.0 / (pos_off * total), 0.0)
        f_neg = np.where(has_neg, -2.0 / (neg_off * total), 0.0)
        scaling_leaf = -(f_pos.sum(-1) + f_neg.sum(-1))  # (N,)

        # per-entry multipliers at leaf level
        e_fwd = np.zeros(len(src))
        e_rev = np.zeros(len(src))
        for d in range(3):
            m = direction == d + 1
            e_fwd[m] = f_pos[src[m], d]
            e_rev[m] = f_neg[nbr[m], d]   # from n's view, c sits at -d
            m = direction == -(d + 1)
            e_fwd[m] = f_neg[src[m], d]
            e_rev[m] = f_pos[nbr[m], d]
        finer = nlen_i < slen_i           # neighbor finer than cell
        e_fwd = np.where(finer, e_fwd / 4.0, e_fwd)
        coarser = nlen_i > slen_i         # cell finer than neighbor
        e_rev = np.where(coarser, e_rev / 4.0, e_rev)
        nonface = (direction == 0) | ~active_pair
        e_fwd[nonface] = 0.0
        e_rev[nonface] = 0.0

        # scatter into [D, R, K] aligned with the epoch's gather tables
        ecol = np.arange(int(lists.start[-1]), dtype=np.int64) - np.repeat(
            lists.start[:-1], counts
        )
        owner = leaves.owner.astype(np.int64)
        mult_fwd = np.zeros((D, R, K))
        mult_rev = np.zeros((D, R, K))
        for d in range(D):
            sel = owner[src] == d
            rows = epoch.row_of[src[sel]]
            cols = ecol[sel]
            mult_fwd[d, rows, cols] = e_fwd[sel]
            mult_rev[d, rows, cols] = e_rev[sel]

        # diagonal + cell role for every row (ghosts included)
        scaling_rows = np.zeros((D, R))
        type_rows = np.full((D, R), self.SKIP_CELL, dtype=np.int8)
        for d in range(D):
            lp, gp = epoch.local_pos[d], epoch.ghost_pos[d]
            scaling_rows[d, : len(lp)] = scaling_leaf[lp]
            scaling_rows[d, len(lp) : len(lp) + len(gp)] = scaling_leaf[gp]
            type_rows[d, : len(lp)] = types[lp]
            type_rows[d, len(lp) : len(lp) + len(gp)] = types[gp]

        from ..parallel.mesh import put_table

        put = lambda a: put_table(a, self.grid.mesh, self.dtype)
        self._scaling = put(scaling_rows)
        # the [D, R, K] multiplier tables are only uploaded when the
        # gather path actually runs (solver fallback or residual()); when
        # the flat fast path engages they would otherwise pin
        # O(R*K) * 2 device memory as a diagnostics-only oracle
        self._mult_np = (mult_fwd, mult_rev)
        self._mult_dev = None
        self._scaling_np = scaling_rows
        self._volume = put(np.asarray(self.tables.length).prod(-1))
        solve_rows = np.asarray(self.tables.local_mask) & (
            type_rows == self.SOLVE_CELL
        )
        self._solve_mask = put_table(solve_rows, self.grid.mesh)
        # leaf-level factors kept for the flat dense fast path
        # (ops/flat_poisson.py): per-(leaf, axis) side factors + diagonal
        self._f_pos_leaf = f_pos
        self._f_neg_leaf = f_neg
        self._scaling_leaf = scaling_leaf

    # ----------------------------------------------------------- solver

    def _mult_table(self, i):
        """Device copy of the [D, R, K] multiplier table ``i`` (0 = fwd,
        1 = rev/transpose), uploaded on first gather-path use — per
        table, so residual() diagnostics on a flat-path solver only pin
        the forward one."""
        if self._mult_dev is None:
            self._mult_dev = [None, None]
        if self._mult_dev[i] is None:
            from ..parallel.mesh import put_table

            self._mult_dev[i] = put_table(
                self._mult_np[i], self.grid.mesh, self.dtype
            )
        return self._mult_dev[i]

    def _mult_tables(self):
        return self._mult_table(0), self._mult_table(1)

    def _apply(self, x, mult):
        """A·x (or Aᵀ·x with the transpose table): ghost-refresh then
        gather + ordered reduction."""
        x = self._exchange({"v": x})["v"]
        xn = gather_neighbors(x, self.tables.nbr_rows)
        return self._scaling * x + ordered_sum(mult * xn, axis=-1), x

    def _build_rolled(self):
        """(apply_fwd, apply_rev) on the rolled static-offset operator
        (ops/rolled_gather.py), or None when any device's offset
        histogram refuses the decomposition.  Each device's row block
        (local + ghost + scratch, ghosts refreshed by the halo exchange
        first — same contract as ``_apply``) is its own roll space;
        the union offset set keeps roll amounts trace-time constants.
        Semantically identical to ``_apply`` up to fp association
        (per-offset accumulation instead of the slot-ordered
        reduction)."""
        from ..ops.rolled_gather import (
            build_rolled_matvec_multi,
            make_rolled_apply_multi,
        )

        nbr = np.asarray(self.tables.nbr_rows)
        applies = []
        for mult in self._mult_np:
            t = build_rolled_matvec_multi(nbr, mult, self._scaling_np)
            if t is None:
                return None
            applies.append(make_rolled_apply_multi(
                t, jnp.dtype(self.dtype), mesh=self.grid.mesh))

        def wrap(ap):
            def run(x):
                x = self._exchange({"v": x})["v"]
                return ap(x)

            return run

        return wrap(applies[0]), wrap(applies[1])

    def _build_solver(self):
        """The BiCG loop, built over one of two operator spaces: the
        general gather tables ([1, R] rows) or the flat voxel grid when
        it qualifies — same algorithm, same stopping rules.  The plain
        gather-table form (no flat layout, no rolled decomposition — the
        AMR-churn shape) is pulled from the grid's executable cache with
        every table as a runtime argument, so rebuilds with the same
        shape signature never recompile the solve loop."""
        if self._flat is None and self._rolled is None:
            return self._build_gather_solver()
        local = self.tables.local_mask
        if self._flat is not None:
            apply_fwd, apply_rev, voxelize, writeback, masks = self._flat
            solve_mask = masks["solve"]
            dot_mask = masks["dot"]
            lift = voxelize
            project = writeback
        else:
            solve_mask = self._solve_mask
            dot_mask = solve_mask
            if self._rolled is not None:
                apply_fwd, apply_rev = self._rolled
            else:
                mult_fwd, mult_rev = self._mult_tables()
                apply_fwd = lambda v: self._apply(v, mult_fwd)[0]
                apply_rev = lambda v: self._apply(v, mult_rev)[0]
            # boundary cells keep their given solution values: they feed
            # the initial residual (Dirichlet lifting) but never change
            lift = lambda row_arr: jnp.where(local, row_arr, 0.0)
            project = lambda v: v

        def dot(a, b):
            w = jnp.where(dot_mask, a * b, 0.0)
            return jnp.sum(w, dtype=w.dtype)

        @jax.jit
        def solve(state, max_iterations, stop_residual, stop_after_increase):
            rhs = jnp.where(solve_mask, lift(state["rhs"]), 0.0)
            x = lift(state["solution"])

            Ax = apply_fwd(x)
            r0 = jnp.where(solve_mask, rhs - Ax, 0.0)
            r1 = r0
            p0, p1 = r0, r1
            dot_r = dot(r0, r1)
            res0 = jnp.sqrt(jnp.abs(dot(r0, r0)))

            # the reference keeps the minimum-residual solution and stops if
            # the residual grows a factor past it (AMR systems are
            # non-normal; BiCG semi-converges) — poisson_solve.hpp:246-250,
            # 655-683
            def cond(carry):
                i, x, r0, r1, p0, p1, dot_r, res, best_res, best_x = carry
                return (
                    (i < max_iterations)
                    & (res > stop_residual)
                    & (dot_r != 0)
                    & (res <= best_res * stop_after_increase)
                )

            def body(carry):
                i, x, r0, r1, p0, p1, dot_r, _, best_res, best_x = carry
                # restrict the operator to solve rows: boundary/skip rows
                # are local and never ghost-refreshed, so unmasked values
                # would leak into r and p (reference updates SOLVE cells
                # only, poisson_solve.hpp:405-520)
                Ap0 = jnp.where(solve_mask, apply_fwd(p0), 0.0)
                ATp1 = jnp.where(solve_mask, apply_rev(p1), 0.0)
                dot_p = dot(p1, Ap0)
                alpha = jnp.where(dot_p != 0, dot_r / dot_p, 0.0)
                x = x + alpha * p0
                r0 = r0 - alpha * Ap0
                r1 = r1 - alpha * ATp1
                new_dot_r = dot(r0, r1)
                beta = jnp.where(dot_r != 0, new_dot_r / dot_r, 0.0)
                p0 = r0 + beta * p0
                p1 = r1 + beta * p1
                res = jnp.sqrt(jnp.abs(dot(r0, r0)))
                better = res < best_res
                best_res = jnp.where(better, res, best_res)
                best_x = jnp.where(better, x, best_x)
                return (i + 1, x, r0, r1, p0, p1, new_dot_r, res, best_res, best_x)

            carry = (jnp.int32(0), x, r0, r1, p0, p1, dot_r, res0, res0, x)
            i, x, r0, r1, p0, p1, dot_r, res, best_res, best_x = jax.lax.while_loop(
                cond, body, carry
            )
            sol = jnp.where(local, project(best_x), 0.0)
            return {**state, "solution": sol}, best_res, i

        return solve

    def _build_gather_solver(self):
        """The cached-executable form of the gather-table BiCG solve:
        identical algorithm to :meth:`_build_solver`'s gather branch,
        with the halo schedule, gather table, masks and multiplier
        tables entering as jit arguments."""
        from ..parallel.exec_cache import traced_jit

        ex = self._exchange
        ex_body = ex.raw_body
        rings = tuple(ex.ring_send) + tuple(ex.ring_recv)

        def build():
            def solve(rings, nbr_rows, local, solve_mask, scaling,
                      mult_fwd, mult_rev, state, max_iterations,
                      stop_residual, stop_after_increase):
                def apply_mult(v, mult):
                    v = ex_body(*rings, {"v": v})["v"]
                    vn = gather_neighbors(v, nbr_rows)
                    return scaling * v + ordered_sum(mult * vn, axis=-1)

                def dot(a, b):
                    w = jnp.where(solve_mask, a * b, 0.0)
                    return jnp.sum(w, dtype=w.dtype)

                def lift(row_arr):
                    # boundary cells keep their given solution values:
                    # they feed the initial residual (Dirichlet lifting)
                    # but never change
                    return jnp.where(local, row_arr, 0.0)

                rhs = jnp.where(solve_mask, lift(state["rhs"]), 0.0)
                x = lift(state["solution"])

                Ax = apply_mult(x, mult_fwd)
                r0 = jnp.where(solve_mask, rhs - Ax, 0.0)
                r1 = r0
                p0, p1 = r0, r1
                dot_r = dot(r0, r1)
                res0 = jnp.sqrt(jnp.abs(dot(r0, r0)))

                def cond(carry):
                    (i, x, r0, r1, p0, p1, dot_r, res, best_res,
                     best_x) = carry
                    return (
                        (i < max_iterations)
                        & (res > stop_residual)
                        & (dot_r != 0)
                        & (res <= best_res * stop_after_increase)
                    )

                def body(carry):
                    i, x, r0, r1, p0, p1, dot_r, _, best_res, best_x = carry
                    Ap0 = jnp.where(
                        solve_mask, apply_mult(p0, mult_fwd), 0.0
                    )
                    ATp1 = jnp.where(
                        solve_mask, apply_mult(p1, mult_rev), 0.0
                    )
                    dot_p = dot(p1, Ap0)
                    alpha = jnp.where(dot_p != 0, dot_r / dot_p, 0.0)
                    x = x + alpha * p0
                    r0 = r0 - alpha * Ap0
                    r1 = r1 - alpha * ATp1
                    new_dot_r = dot(r0, r1)
                    beta = jnp.where(dot_r != 0, new_dot_r / dot_r, 0.0)
                    p0 = r0 + beta * p0
                    p1 = r1 + beta * p1
                    res = jnp.sqrt(jnp.abs(dot(r0, r0)))
                    better = res < best_res
                    best_res = jnp.where(better, res, best_res)
                    best_x = jnp.where(better, x, best_x)
                    return (i + 1, x, r0, r1, p0, p1, new_dot_r, res,
                            best_res, best_x)

                carry = (jnp.int32(0), x, r0, r1, p0, p1, dot_r, res0,
                         res0, x)
                (i, x, r0, r1, p0, p1, dot_r, res, best_res,
                 best_x) = jax.lax.while_loop(cond, body, carry)
                sol = jnp.where(local, best_x, 0.0)
                return {**state, "solution": sol}, best_res, i

            return traced_jit("poisson.solve", solve)

        fn = self.grid.exec_cache.get(
            ("poisson.solve", ex.structure_key, str(np.dtype(self.dtype))),
            build,
        )
        mult_fwd, mult_rev = self._mult_tables()
        args = (rings, self.tables.nbr_rows, self.tables.local_mask,
                self._solve_mask, self._scaling, mult_fwd, mult_rev)
        return lambda state, mi, sr, si: fn(*args, state, mi, sr, si)

    def _build_fast_solver(self):
        """Whole-solve fused BiCG kernel (ops/poisson_kernel.py): the
        entire masked iteration loop in one Pallas launch with every
        array VMEM-resident.  None when ineligible (no flat layout,
        multi-device, f64, too large, no Pallas); the XLA solver stays
        the fallback and the oracle (solutions agree to solver
        tolerance — the in-kernel dot association differs)."""
        from ..ops.dense_advection import pallas_available
        from ..ops.poisson_kernel import bicg_fits, make_bicg_solve

        t = self._flat_tables
        interpret = self.use_pallas == "interpret"
        if (
            not self.use_pallas
            or t is None
            or t["n_devices"] != 1
            # the whole-solve kernel's pool/broadcast is the 2-level
            # roll chain; 3+ level grids stay on the XLA flat matvec
            # (reshape-pyramid accumulation)
            or t.get("vl", 1) > 1
            or np.dtype(self.dtype) != np.float32
            or not bicg_fits(int(np.prod(t["shape"])))
            or not (interpret or pallas_available(np.float32))
        ):
            return None
        _fwd, _rev, voxelize, writeback, masks = self._flat
        local = self.tables.local_mask
        kern = make_bicg_solve(
            t["shape"], t["has_coarse"], interpret=interpret
        )
        f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)
        statics = (
            [f32(w) for pair in t["weights"] for w in pair]
            + [f32(t["scaling"]), f32(t["fine"]), f32(~t["fine"]),
               f32(t["orig"]), f32(t["solve"]), f32(t["dot_mask"])]
        )
        solve_mask = masks["solve"]

        @jax.jit
        def solve_fast(state, max_iterations, stop_residual, stop_increase):
            rhs = jnp.where(solve_mask, voxelize(state["rhs"]), 0.0)
            x = voxelize(state["solution"])
            best_x, best_res, it = kern(
                rhs.astype(jnp.float32), x.astype(jnp.float32), *statics,
                max_iterations, stop_residual, stop_increase,
            )
            sol = jnp.where(local, writeback(best_x.astype(self.dtype)), 0.0)
            return {**state, "solution": sol}, best_res[0], it[0]

        return solve_fast

    def _disable_fast(self):
        self._solve_fast = None

    # ---------------------------------------------------------- user API

    def initialize_state(self, rhs_by_cell):
        grid = self.grid
        state = grid.new_state(self.spec)
        cells = grid.get_cells()
        rhs = np.asarray(rhs_by_cell, dtype=np.float64)
        # zero-mean the charge like the reference tests do for all-periodic
        # grids (volume-weighted so AMR stays consistent)
        vol = np.prod(grid.geometry.get_length(cells), axis=-1)
        if all(grid.topology.periodic) and self._full_solve:
            rhs = rhs - (rhs * vol).sum() / vol.sum()
        return grid.set_cell_data(state, "rhs", cells, rhs)

    def solve(
        self,
        state,
        max_iterations: int = 1000,
        stop_residual: float = 1e-12,
        stop_after_residual_increase: float = 10.0,
        restarts: int = 0,
    ):
        """Returns (state, best_residual, iterations).

        ``restarts``: BiCG on non-normal systems (AMR + mixed cell
        roles) can break down mid-Krylov-space and stop at the
        semi-convergence rule far from the target; re-invoking from the
        best solution rebuilds the space and recovers (the reference's
        drivers re-invoke solve for exactly this).  With ``restarts=N``
        the solve re-enters up to N more times until ``stop_residual``
        is met or an attempt makes no progress; iterations accumulate.
        Default 0 = the reference's single-trajectory behavior."""
        if restarts > 0:
            total_it = 0
            prev_res = float("inf")
            for _ in range(restarts + 1):
                state, res, it = self.solve(
                    state, max_iterations, stop_residual,
                    stop_after_residual_increase,
                )
                total_it += it
                if res <= stop_residual or not res < prev_res:
                    break  # converged, or the attempt made no progress
                prev_res = res
            return state, res, total_it
        # threshold dtype: f64 under x64, f32 otherwise — canonicalized
        # without the per-call truncation warning jnp.float64() emits
        import jax

        td = jax.dtypes.canonicalize_dtype(np.float64)
        if self._solve_fast is not None:
            from ..utils.fallback import fallback_call

            state, res, it = fallback_call(
                "fused Poisson BiCG kernel",
                lambda: self._solve_fast(
                    state, jnp.int32(max_iterations),
                    jnp.float32(stop_residual),
                    jnp.float32(stop_after_residual_increase),
                ),
                lambda: self._solve(
                    state, jnp.int32(max_iterations),
                    jnp.asarray(stop_residual, td),
                    jnp.asarray(stop_after_residual_increase, td),
                ),
                self._disable_fast,
            )
            return state, float(res), int(it)
        state, res, it = self._solve(
            state,
            jnp.int32(max_iterations),
            jnp.asarray(stop_residual, td),
            jnp.asarray(stop_after_residual_increase, td),
        )
        return state, float(res), int(it)

    def residual(self, state) -> float:
        Ax, _ = self._apply(state["solution"], self._mult_table(0))
        r = fetch(jnp.where(self._solve_mask, state["rhs"] - Ax, 0.0))
        return float(np.sqrt((r * r).sum()))
