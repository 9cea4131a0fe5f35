"""Conway's game of life on the distributed grid — the framework's
"hello world", matching the reference's
``examples/simple_game_of_life.cpp`` / ``examples/game_of_life.cpp``:
full-vertex neighborhood, count live neighbors of every local cell after a
ghost update, then apply the 2/3 rule.

The per-cell loop of the reference becomes one jitted array program: a
neighbor gather + masked reduction feeding an elementwise rule, sharded over
the device mesh with the halo exchange fused into the same XLA computation.

With ``overlap=True`` the step is the split-phase form of the reference's
canonical overlap pattern (``examples/game_of_life.cpp:124-138``): launch
the ghost collective, count neighbors of INNER cells (no remote
neighbors — no data dependence on the transfer, so XLA's latency-hiding
scheduler runs them concurrently), merge the ghosts, then count the OUTER
cells.  Inner/outer row sets are compacted per device, so the split also
computes exactly the local cells instead of all rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.stencil import StencilTables, compact_rows, gather_neighbors
from ..utils.fallback import fallback_call

__all__ = ["GameOfLife"]


def _life_rule(count, alive):
    """The 2/3 rule (examples/simple_game_of_life.cpp:95-106)."""
    return jnp.where(
        count == 3,
        jnp.uint32(1),
        jnp.where(count != 2, jnp.uint32(0), alive),
    )


class GameOfLife:
    #: the payload declaration — the reference's ``game_of_life_cell`` with
    #: its ``get_mpi_datatype`` seam (examples/simple_game_of_life.cpp:20-32)
    SPEC = {
        "is_alive": ((), np.uint32),
        "live_neighbor_count": ((), np.uint32),
    }

    def __init__(self, grid, hood_id=None, overlap: bool = False,
                 allow_dense: bool = True, use_pallas=True):
        #: use_pallas follows the Advection convention: True = compiled
        #: kernels on TPU only; "interpret" = force the Pallas
        #: interpreter (CI/CPU integration coverage); False = XLA only
        self.use_pallas = use_pallas
        self.grid = grid
        self.hood_id = hood_id
        self._exchange = grid.halo(hood_id)
        if overlap:
            # the overlap step derives compacted tables straight from the
            # epoch; the full [D, R, K] StencilTables would sit unused
            self.tables = None
            self._step = self._build_overlap_step()
        else:
            self.tables = StencilTables(grid, hood_id)
            self._step = self._build_step()
        # overlap=True exists to exercise/measure the split-phase step, so
        # it keeps the per-step loop
        from ..parallel.dense import detect_dense2d

        self.dense2d = (
            detect_dense2d(grid, hood_id) if allow_dense and not overlap
            else None
        )
        #: whole-run fused Pallas kernel (set by _build_dense_run when it
        #: qualifies); _dense_run is the XLA dense loop beneath it
        self._fused_run = None
        self._dense_run = (
            self._build_dense_run() if self.dense2d is not None else None
        )

    def new_state(self, alive_cells=()):
        state = self.grid.new_state(self.SPEC)
        if len(alive_cells):
            state = self.grid.set_cell_data(
                state,
                "is_alive",
                np.asarray(alive_cells, dtype=np.uint64),
                np.ones(len(alive_cells), dtype=np.uint32),
            )
        return state

    def _build_step(self):
        from ..parallel.exec_cache import traced_jit

        ex = self._exchange
        ex_body = ex.raw_body
        rings = tuple(ex.ring_send) + tuple(ex.ring_recv)

        def build():
            def step(rings, tables, state):
                state = ex_body(*rings, state)
                alive = state["is_alive"]
                nbr_alive = gather_neighbors(
                    alive, tables["nbr_rows"]
                )                                                   # [D,R,K]
                # dtype pinned to the SPEC's uint32 (like the overlap
                # step): without it jnp.sum promotes to uint64 under
                # x64, so the step's OUTPUT state has a different aval
                # than its input and the second dispatch of any program
                # taking the state re-traces once
                count = jnp.sum(
                    jnp.where(tables["nbr_valid"],
                              (nbr_alive > 0).astype(jnp.uint32), 0),
                    axis=-1, dtype=jnp.uint32,
                )
                new_alive = _life_rule(count, alive)
                local = tables["local_mask"]
                return {
                    "is_alive": jnp.where(local, new_alive, alive),
                    "live_neighbor_count": jnp.where(
                        local, count, jnp.uint32(0)
                    ),
                }

            return traced_jit("gol.step", step)

        fn = self.grid.exec_cache.get(("gol.step", ex.structure_key), build)
        tables = self.tables.tree()
        self._step_fn = fn
        self._step_args = (rings, tables)
        return lambda state: fn(rings, tables, state)

    def _build_overlap_step(self):
        """Split-phase step: collective and inner compute are dataflow-
        independent inside one XLA program; outer compute depends on the
        merged ghosts.  Bit-identical results to the blocking step."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import SHARD_AXIS, put_table, shard_spec

        from ..parallel.shapes import bucket_rows

        grid = self.grid
        epoch = grid.epoch
        hood = epoch.hoods[self.hood_id]
        halo = self._exchange
        scratch = epoch.R - 1
        D = epoch.n_devices
        ar = np.arange(D)[:, None]
        # compacted widths ride the bucket ladder with grid-persistent
        # hints (see models/advection.py build_split_tables): churn must
        # not retrace the fused body while the signature holds
        hints = getattr(grid, "_ring_hints", {})

        def rows_of(side, mask):
            natural = max(int(mask.sum(axis=1).max()) if D else 0, 1)
            key = (self.hood_id, f"split.{side}", 0)
            W = bucket_rows(natural, hints.get(key))
            hints[key] = W
            return compact_rows(mask, scratch, width=W)

        irows = rows_of("inner", hood.inner_mask)            # [D, Wi]
        orows = rows_of("outer", hood.outer_mask)            # [D, Wo]
        # gather tables restricted to the compacted row sets
        nri, nvi = hood.nbr_rows[ar, irows], hood.nbr_valid[ar, irows]
        nro, nvo = hood.nbr_rows[ar, orows], hood.nbr_valid[ar, orows]
        mesh = grid.mesh
        put = lambda a: put_table(a, mesh)
        tabs = tuple(put(a) for a in (irows, orows, nri, nvi, nro, nvo))
        local = put(epoch.local_mask)
        rings = tuple(halo.ring_send) + tuple(halo.ring_recv)
        ks = tuple(halo.ring_ks)
        # backend-selected transport (collective ppermute or Pallas
        # async-DMA ring), a pure function of halo.structure_key
        ring_start = halo.make_ring_start()

        from ..parallel.exec_cache import traced_jit
        from ..parallel.halo import HaloExchange

        def build():
            nk = len(ks)
            data_spec = P(SHARD_AXIS)
            rule = _life_rule

            def body(*args):
                # args: ring send tabs (nk), ring recv tabs (nk), then
                # the compute tables and the alive array
                sends = [a[0] for a in args[:nk]]
                recvs = [a[0] for a in args[nk:2 * nk]]
                irows, orows, nri, nvi, nro, nvo, local, alive = (
                    args[2 * nk:]
                )
                a = alive[0]                                     # [R]
                # --- start: ghost payloads in flight (depend on `a`)
                payloads = ring_start(a, sends)
                # --- inner compute: no remote neighbors, no dep on
                # payloads
                cnt_i = jnp.sum(
                    jnp.where(nvi[0], (a[nri[0]] > 0).astype(jnp.uint32),
                              0),
                    -1, dtype=jnp.uint32,
                )
                new_i = rule(cnt_i, a[irows[0]])
                # --- wait: merging the payloads IS the synchronization
                a2 = HaloExchange.ring_finish(a, recvs, payloads)
                # --- outer compute: needs fresh ghosts
                cnt_o = jnp.sum(
                    jnp.where(nvo[0],
                              (a2[nro[0]] > 0).astype(jnp.uint32), 0),
                    -1, dtype=jnp.uint32,
                )
                new_o = rule(cnt_o, a2[orows[0]])
                out_a = a2.at[irows[0]].set(new_i).at[orows[0]].set(new_o)
                out_a = jnp.where(local[0], out_a, a2)   # clean scratch
                cnt = (
                    jnp.zeros_like(a)
                    .at[irows[0]].set(cnt_i).at[orows[0]].set(cnt_o)
                )
                cnt = jnp.where(local[0], cnt, jnp.uint32(0))
                return out_a[None], cnt[None]

            fn = shard_map(
                body,
                mesh=mesh,
                in_specs=(P(SHARD_AXIS, None),) * (2 * nk)
                + (P(SHARD_AXIS, None),) * 2
                + (P(SHARD_AXIS, None, None),) * 4
                + (P(SHARD_AXIS, None), data_spec),
                out_specs=(data_spec, data_spec),
                check_vma=False,
            )

            def step(rings, tabs, local, alive):
                return fn(*rings, *tabs, local, alive)

            return traced_jit("gol.overlap_step", step)

        fn = self.grid.exec_cache.get(
            ("gol.overlap_step", halo.structure_key), build
        )
        self._overlap_fn = fn
        self._overlap_args = (rings, tabs, local)

        def step(state):
            out_a, cnt = fn(rings, tabs, local, state["is_alive"])
            return {"is_alive": out_a, "live_neighbor_count": cnt}

        return step

    def _build_dense_run(self):
        """Whole-run device-side loop on the dense y-slab layout: the
        8-neighbor count is three shifted row bands x three x-rolls, the
        halo two ppermuted boundary rows — one dispatch for any number of
        turns (the reference's scalability configuration,
        ``tests/game_of_life/scalability.cpp``, without its per-turn
        message machinery).

        The bundle is a pure function of (mesh, dims, periodicity,
        pallas mode), so it is cached under that key and survives
        rebuilds that return to the same uniform shape."""
        from ..parallel.exec_cache import mesh_key

        info = self.dense2d
        pallas_mode = (self.use_pallas if isinstance(self.use_pallas, str)
                       else bool(self.use_pallas))
        key = ("gol.dense", mesh_key(self.grid.mesh), info["D"],
               info["nyl"], info["nx"],
               tuple(bool(p) for p in info["periodic"]), pallas_mode)
        fused, run = self.grid.exec_cache.get(key, self._build_dense_bundle)
        self._fused_run = fused
        return run

    def _build_dense_bundle(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.dense import HaloExtend
        from ..parallel.mesh import SHARD_AXIS

        info = self.dense2d
        nx, nyl, D = info["nx"], info["nyl"], info["D"]
        per = nyl * nx
        px, py = info["periodic"]
        mesh = self.grid.mesh
        ring = HaloExtend(D)

        # single device + VMEM fit: the whole run in one Pallas launch
        from ..ops.dense_advection import pallas_available
        from ..ops.gol_kernel import gol_run_fits, make_gol_run

        interpret = self.use_pallas == "interpret"
        fused_run = None
        if (
            self.use_pallas
            and D == 1
            and gol_run_fits(nyl, nx)
            and (interpret or pallas_available(np.float32))
        ):
            from ..ops.flat_amr import pad_extent

            # tile-align both axes when the pad fits VMEM (x: 128 lanes,
            # y: 8 sublanes) — the reference example's 500x500 board
            # becomes 504x512 and every per-turn roll is aligned
            nxp, nyp = pad_extent(nx, 128), pad_extent(nyl, 8)
            if not gol_run_fits(nyp, nxp):
                # near the VMEM ceiling: drop the costlier x pad first,
                # keeping the nearly-free sublane alignment if it fits
                nxp = nx
                if not gol_run_fits(nyp, nxp):
                    nyp = nyl
            kern = make_gol_run(
                nyl, nx, px, py,
                ny_pad=nyp if nyp != nyl else None,
                nx_pad=nxp if nxp != nx else None,
                interpret=interpret,
            )

            @jax.jit
            def fused_fn(state, turns):
                a = state["is_alive"][0, :per].reshape(nyl, nx)
                out, cnt = kern((a > 0).astype(jnp.float32), turns)
                out_a = state["is_alive"][0].at[:per].set(
                    out.reshape(-1).astype(jnp.uint32)
                )
                out_c = jnp.zeros_like(out_a).at[:per].set(
                    cnt.reshape(-1).astype(jnp.uint32)
                )
                return {
                    "is_alive": out_a[None],
                    "live_neighbor_count": out_c[None],
                }

            # the Pallas kernel is an optimization over the XLA dense
            # loop built below — keep both so a TPU-generation Mosaic
            # rejection at first call can fall back (see run())
            fused_run = fused_fn
        # x-wrap validity columns: neighbor at x+1 invalid for x = nx-1 on
        # open x; at x-1 invalid for x = 0
        vx_hi = np.ones(nx, np.uint32)
        vx_lo = np.ones(nx, np.uint32)
        if not px:
            vx_hi[-1] = 0
            vx_lo[0] = 0
        vx_of = {-1: jnp.asarray(vx_lo), 0: None, 1: jnp.asarray(vx_hi)}

        def body(alive_rows, turns):
            a0 = alive_rows[0, :per].reshape(nyl, nx)
            dev = jax.lax.axis_index(SHARD_AXIS)
            # boundary-row validity on open y: device 0's below-row and
            # device D-1's above-row come from the ring wrap and must be
            # dropped
            ok_below = jnp.uint32(1 if py else 0) | (dev != 0).astype(jnp.uint32)
            ok_above = jnp.uint32(1 if py else 0) | (dev != D - 1).astype(jnp.uint32)

            def one(carry):
                a, _ = carry
                below, above = ring.planes(a)
                ext = jnp.concatenate(
                    [below * ok_below, a, above * ok_above], axis=0
                )
                cnt = jnp.zeros((nyl, nx), jnp.uint32)
                for dy in (0, 1, 2):
                    band = (ext[dy:dy + nyl] > 0).astype(jnp.uint32)
                    for dx in (-1, 0, 1):
                        if dy == 1 and dx == 0:
                            continue
                        t = jnp.roll(band, -dx, 1) if dx else band
                        v = vx_of[dx]
                        cnt = cnt + (t * v[None, :] if v is not None else t)
                return _life_rule(cnt, a), cnt

            a, cnt = jax.lax.fori_loop(
                0, turns, lambda i, c: one(c), (a0, jnp.zeros_like(a0))
            )
            out_a = alive_rows[0].at[:per].set(a.reshape(-1))
            out_c = jnp.zeros_like(out_a).at[:per].set(cnt.reshape(-1))
            return out_a[None], out_c[None]

        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(SHARD_AXIS), P()),
            out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
            check_vma=False,
        )

        @jax.jit
        def run_fn(state, turns):
            out_a, cnt = fn(state["is_alive"], turns)
            return {"is_alive": out_a, "live_neighbor_count": cnt}

        return fused_run, run_fn

    def _disable_fused(self):
        self._fused_run = None

    def step(self, state):
        return self._step(state)

    def _wide_spec(self):
        """Exchange-amortized step split (ISSUE 14).  The life rule reads
        the WHOLE neighborhood, so stencil relevance is ``"all"``: on the
        default hood the budget collapses to 1 (the rule genuinely has
        the hood's radius) and wide stepping disengages; amortization
        engages when this model steps on a radius-1 sub-hood of a deeper
        default hood — the exchange then refills the full-depth ghost
        zone while ``steps_ok`` meters its shell-by-shell consumption."""
        from ..parallel.exec_cache import WideStepSpec, traced_jit
        from ..parallel.mesh import put_table
        from ..parallel.wide_halo import get_wide_plan, wide_enabled

        if not wide_enabled():
            return None
        cached = getattr(self, "_wide_cached", None)
        if cached is not None and cached[0] is self.grid.epoch:
            return cached[1]
        plan = get_wide_plan(self.grid, self.hood_id, relevance="all")
        spec = None
        if plan.budget >= 2:
            wex = self.grid.halo(None)
            wex_body = wex.raw_body
            wrings = tuple(wex.ring_send) + tuple(wex.ring_recv)
            mesh = self.grid.mesh
            wtabs = {
                "nbr_rows": put_table(plan.nbr_rows, mesh),
                "nbr_valid": put_table(plan.nbr_valid, mesh),
                "steps_ok": put_table(plan.steps_ok, mesh),
                "local_mask": put_table(plan.local_mask, mesh),
            }

            def build():
                def interior(wtabs, state, j):
                    alive = state["is_alive"]
                    nbr_alive = gather_neighbors(
                        alive, wtabs["nbr_rows"]
                    )
                    count = jnp.sum(
                        jnp.where(wtabs["nbr_valid"],
                                  (nbr_alive > 0).astype(jnp.uint32), 0),
                        axis=-1, dtype=jnp.uint32,
                    )
                    new_alive = _life_rule(count, alive)
                    live = wtabs["steps_ok"] > j
                    # local rows (live through the whole budget) match
                    # the blocking step bitwise: same gather/count/rule
                    # over identical table rows; the stale fringe keeps
                    # its exchanged values
                    return {
                        "is_alive": jnp.where(live, new_alive, alive),
                        "live_neighbor_count": jnp.where(
                            live & wtabs["local_mask"], count,
                            jnp.where(live, jnp.uint32(0),
                                      state["live_neighbor_count"]),
                        ),
                    }

                return traced_jit("gol.wide_step", interior)

            fn = self.grid.exec_cache.get(
                ("gol.wide_step", wex.structure_key), build
            )
            spec = WideStepSpec(
                exchange=lambda args, wargs, state: wex_body(
                    *wargs[0], state
                ),
                interior=lambda args, wargs, state, dt, j: fn(
                    wargs[1], state, j
                ),
                budget=plan.budget,
                args=(wrings, wtabs),
                local_mask=plan.local_mask,
            )
        self._wide_cached = (self.grid.epoch, spec)
        return spec

    def batch_step_spec(self):
        """Cohort-batchable step entry point (ISSUE 9; see
        ``Advection.batch_step_spec``).  GoL takes no dt — the cohort's
        per-member dt operand is ignored.  ``steps_per_dispatch``
        declares the deep-dispatch default (ISSUE 11)."""
        from ..parallel.exec_cache import (
            BatchStepSpec,
            default_steps_per_dispatch,
        )

        k = default_steps_per_dispatch()
        ex = self._exchange
        wide = self._wide_spec()
        if self.tables is None:          # overlap=True split-phase form
            fn = self._overlap_fn

            def call(args, state, dt):
                out_a, cnt = fn(args[0], args[1], args[2],
                                state["is_alive"])
                return {"is_alive": out_a, "live_neighbor_count": cnt}

            return BatchStepSpec(
                kind="gol.overlap",
                kernel_key=("gol.overlap_step", ex.structure_key),
                call=call, args=self._overlap_args,
                steps_per_dispatch=k, wide=wide,
            )
        fn = self._step_fn
        return BatchStepSpec(
            kind="gol", kernel_key=("gol.step", ex.structure_key),
            call=lambda args, state, dt: fn(args[0], args[1], state),
            args=self._step_args, steps_per_dispatch=k, wide=wide,
        )

    def run(self, state, turns: int, sync_every: int = 16):
        """Advance ``turns`` steps.  On the dense 2-D fast path the whole
        run is one device-side loop (single dispatch).  Otherwise the
        dispatch queue is drained every ``sync_every`` turns: unbounded
        async pipelines of collective programs trip XLA:CPU's rendezvous
        watchdog on oversubscribed hosts (virtual-device meshes), and a
        depth-16 pipeline already hides dispatch latency on real chips."""
        if self._fused_run is not None and turns > 0:
            self._record_run("fused", turns, state)
            return fallback_call(
                "fused GoL kernel", self._fused_run, self._dense_run,
                self._disable_fused, state, jnp.asarray(turns, jnp.int32),
            )
        if self._dense_run is not None and turns > 0:
            self._record_run("dense", turns, state)
            return self._dense_run(state, jnp.asarray(turns, jnp.int32))
        for i in range(turns):
            state = self._step(state)
            if sync_every and (i + 1) % sync_every == 0:
                jax.block_until_ready(state)
        return state

    def _record_run(self, path: str, turns, state) -> None:
        """Whole-run dispatches keep their ghost traffic inside jit —
        reconcile ``turns x schedule bytes`` on the host (obs.fused).
        Only ``is_alive`` crosses the wire, like the reference's
        ``get_mpi_datatype`` (examples/simple_game_of_life.cpp:20-32)."""
        from ..obs import fused

        if not self.grid.telemetry.enabled:
            return
        try:
            bps = self._exchange.bytes_moved(
                {"is_alive": state["is_alive"]}
            )
        except Exception:  # noqa: BLE001 — telemetry must never raise
            bps = 0
        fused.record_run("game_of_life", path, turns, bps)

    def alive_cells(self, state) -> np.ndarray:
        cells = self.grid.get_cells()
        alive = self.grid.get_cell_data(state, "is_alive", cells)
        return cells[alive > 0]
