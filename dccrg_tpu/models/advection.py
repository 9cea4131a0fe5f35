"""3-D upwind finite-volume advection — the framework's north-star workload
(reference ``tests/advection``: cell layout ``cell.hpp:36-44``, flux solver
``solve.hpp:43-260``, initial condition ``initialize.hpp:36-80``, rotating
velocity field ``solve.hpp:336-346``).

TPU-native formulation: instead of the reference's per-cell loop that
scatters flux into both cells of each face pair (skipping local negative
directions), every cell accumulates its *own* flux from all of its
face-neighbor entries in fixed slot order.  That makes the kernel a pure
gather + masked reduction — deterministic (fixed left-to-right flux
association via ``ordered_sum``; halo copies are bit-exact, and results
across device counts agree to the last ulp, where the residual is XLA
instruction selection varying with local array shapes, not data flow) and
scatter-free — at the cost of computing each face's flux twice,
which on TPU is free relative to the HBM traffic.

Face classification (direction, shared area, volumes) depends only on grid
structure, so it is precomputed host-side per epoch and shipped as device
tables; the jitted step touches only density (1 f64 per ghost cell per step,
matching the reference's density-only ``get_mpi_datatype``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..obs.registry import metrics
from ..parallel.exec_cache import traced_jit
from ..parallel.mesh import put_table, shard_spec
from ..parallel.stencil import StencilTables, gather_neighbors, ordered_sum
from ..utils.collectives import fetch
from ..utils.fallback import fallback_call

__all__ = ["Advection"]


#: flat-vs-boxed dispatch edge per flat form: prefer the boxed per-level
#: passes when ``flat_n_vox > edge * boxed_vol``.  Not yet measured on
#: chip: 2.0 is the ~2x per-voxel advantage the 2-level kernel showed in
#: round 2 (the multi-level kernel is assumed to be of the same class);
#: 1.5 gives the streaming XLA pyramid modest slack for the boxed
#: passes' per-level pass/concat overhead
_BOXED_EDGE = {"pallas": 2.0, "ml_pallas": 2.0, "ml": 1.5}


def build_face_tables(grid, hood_id, tables, dtype, hood_arrays=None):
    """Classify each neighbor entry as a face neighbor with a signed
    direction, reproducing the offset logic of ``solve.hpp:71-123``
    (overlap in exactly 2 dims + contact in 1), plus the physical
    factors every finite-volume workload prices faces with.  Shared by
    Advection and the AMR Vlasov path.  Returns ``(host, dev)``: numpy
    tables {face_dir, min_area, cell_axis_len, nbr_axis_len,
    inv_volume} and the device dict (axis_idx included) for jitted
    steps.

    ``hood_arrays`` overrides the neighbor tables the classification
    reads: an ``(nbr_offset, nbr_len, nbr_rows, nbr_valid)`` tuple, e.g.
    a wide-halo plan's device-extended tables (ISSUE 14) whose ghost
    rows also carry gather entries.  The geometry side
    (``tables.length``, ``epoch.cell_len``) already covers ghost rows,
    so the same pricing applies; owner-local rows stay bitwise equal to
    the default-hood result."""
    from ..core.neighbors import face_directions

    epoch = grid.epoch
    if hood_arrays is None:
        hood = epoch.hoods[hood_id]
        hood_arrays = (hood.nbr_offset, hood.nbr_len, hood.nbr_rows,
                       hood.nbr_valid)
    h_off, h_nlen, nb, valid = hood_arrays
    off = np.asarray(h_off).astype(np.int64)        # [D, R, K, 3]
    nlen = np.asarray(h_nlen).astype(np.int64)      # [D, R, K]
    clen = epoch.cell_len.astype(np.int64)[..., None]  # [D, R, 1]
    valid = np.asarray(valid)

    direction = np.where(
        valid, face_directions(off, clen, nlen), 0
    ).astype(np.int8)                                # [D, R, K] signed axis or 0

    # physical areas/volumes from geometry tables
    length = np.asarray(tables.length)               # [D, R, 3]
    vol = length.prod(axis=-1)                       # [D, R]
    # gather neighbor physical lengths host-side
    D, R, K = np.asarray(nb).shape
    nlen_phys = length[np.arange(D)[:, None, None], nb]  # [D, R, K, 3]

    axis_idx = np.abs(direction).astype(np.int64) - 1    # [D, R, K]
    ai = np.maximum(axis_idx, 0)
    other = np.stack([(ai + 1) % 3, (ai + 2) % 3], axis=-1)
    cell_area = np.take_along_axis(
        np.broadcast_to(length[:, :, None], nlen_phys.shape), other, axis=-1
    ).prod(axis=-1)
    nbr_area = np.take_along_axis(nlen_phys, other, axis=-1).prod(axis=-1)
    min_area = np.minimum(cell_area, nbr_area)
    is_face = direction != 0
    host = {
        "face_dir": direction,
        "min_area": np.where(is_face, min_area, 0.0),
        # axis lengths for face-velocity interpolation
        "cell_axis_len": np.take_along_axis(
            np.broadcast_to(length[:, :, None], nlen_phys.shape),
            ai[..., None], axis=-1,
        )[..., 0],
        "nbr_axis_len": np.take_along_axis(
            nlen_phys, ai[..., None], axis=-1
        )[..., 0],
        "inv_volume": np.where(vol > 0, 1.0 / vol, 0.0),
    }
    mesh = grid.mesh
    put = lambda a, dt: put_table(a, mesh, dt)
    dev = {
        "face_dir": put(host["face_dir"], jnp.int8),
        "min_area": put(host["min_area"], dtype),
        "cell_axis_len": put(host["cell_axis_len"], dtype),
        "nbr_axis_len": put(host["nbr_axis_len"], dtype),
        "inv_volume": put(host["inv_volume"], dtype),
        "axis_idx": put(ai, jnp.int8),
    }
    return host, dev


def build_split_tables(grid, hood_id, host_face, dtype, extra=None):
    """Compacted inner/outer row sets with the gather + face tables
    restricted to them — the runtime-argument pack of a fused
    split-phase step (shared by Advection and Vlasov).

    ``host_face`` is the host dict :func:`build_face_tables` returned;
    ``extra`` maps names to additional ``[D, R]`` host tables restricted
    per side and shipped at ``dtype`` (Vlasov's open-boundary face
    areas).  Returns ``(inner, outer, local)`` device pytrees; padding
    rows point at the scratch row, whose face entries are all masked
    (``face_dir == 0``), so padded lanes contribute exactly nothing."""
    from ..parallel.shapes import bucket_rows
    from ..parallel.stencil import compact_rows

    epoch = grid.epoch
    hood = epoch.hoods[hood_id]
    scratch = epoch.R - 1
    D = epoch.n_devices
    ar = np.arange(D)[:, None]
    mesh = grid.mesh
    put = lambda a, dt=None: put_table(a, mesh, dt)
    # compacted widths ride the bucket ladder with grid-persistent
    # hysteresis hints (the ring-size discipline of parallel/shapes.py):
    # inner/outer counts wiggling with churn must not retrace the fused
    # split kernels — pad slots are scratch rows whose face entries are
    # all masked, so they contribute exactly nothing
    hints = getattr(grid, "_ring_hints", {})
    sides = []
    for side, mask in (("inner", hood.inner_mask),
                       ("outer", hood.outer_mask)):
        counts = mask.sum(axis=1)
        natural = max(int(counts.max()) if D else 0, 1)
        hint_key = (hood_id, f"split.{side}", 0)
        W = bucket_rows(natural, hints.get(hint_key))
        hints[hint_key] = W
        rows = compact_rows(mask, scratch, width=W)
        fd = host_face["face_dir"][ar, rows]
        sub = {
            "rows": put(rows),
            "nbr_rows": put(hood.nbr_rows[ar, rows]),
            "face_dir": put(fd, jnp.int8),
            "axis_idx": put(
                np.maximum(np.abs(fd.astype(np.int64)) - 1, 0), jnp.int8
            ),
            "min_area": put(host_face["min_area"][ar, rows], dtype),
            "cell_axis_len": put(
                host_face["cell_axis_len"][ar, rows], dtype
            ),
            "nbr_axis_len": put(host_face["nbr_axis_len"][ar, rows], dtype),
            "inv_volume": put(host_face["inv_volume"][ar, rows], dtype),
        }
        for name, arr in (extra or {}).items():
            sub[name] = put(arr[ar, rows], dtype)
        sides.append(sub)
    return sides[0], sides[1], put(epoch.local_mask)


def _table_specs(tabs):
    """shard_map in_specs pytree for a split-table pack: every leaf is a
    ``[D, ...]`` array sharded on the device axis."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import SHARD_AXIS

    return jax.tree_util.tree_map(
        lambda x: P(SHARD_AXIS, *([None] * (x.ndim - 1))), tabs
    )


class Advection:
    #: the reference's 9-double cell (density, velocity, flux, max_diff;
    #: lengths live in the geometry tables instead of per-cell storage)
    SPEC = {
        "density": ((), np.float64),
        "vx": ((), np.float64),
        "vy": ((), np.float64),
        "vz": ((), np.float64),
        "flux": ((), np.float64),
        "max_diff": ((), np.float64),
    }

    def __init__(self, grid, hood_id=None, dtype=np.float64, allow_dense=True,
                 use_pallas=True, allow_boxed=True, overlap=False):
        self.grid = grid
        self.hood_id = hood_id
        self.dtype = dtype
        self.use_pallas = use_pallas
        #: split-phase stepping (ISSUE 7): ``step``/``run`` use the fused
        #: start → interior → finish → boundary body on the general
        #: gather path, bit-identical to the blocking step.  Like GoL's
        #: ``overlap=True``, this pins the general path (the split form
        #: exists to overlap the halo seam the fast paths do not have).
        self.overlap = bool(overlap)
        self.spec = {k: (s, dtype) for k, (s, _) in self.SPEC.items()}
        self.dense = (grid.epoch.dense if allow_dense and not overlap
                      else None)
        self.boxed = None
        #: (epoch, dtype, shape) -> halo bytes per step (``_record_run``)
        self._bps_key, self._bps = None, 0
        with metrics.phase("advection.init"):
            self._init_paths(allow_boxed)

    def _init_paths(self, allow_boxed):
        """Build every candidate whole-run path, each under its own
        ``advection.init.<part>`` span (dense; tables, step, boxed, flat),
        so set-up time spent on a path that does not engage shows."""
        grid, hood_id = self.grid, self.hood_id
        if self.dense is not None:
            with metrics.phase("advection.init.dense"):
                self._init_dense()
            return
        with metrics.phase("advection.init.tables"):
            self.tables = StencilTables(grid, hood_id, with_geometry=True)
            self._exchange = grid.halo(hood_id)
            # halo schedule tables ride into the cached kernels as runtime
            # arguments (parallel/exec_cache.py): an epoch rebuild with the
            # same shape signature reuses every compiled step
            self._rings = (tuple(self._exchange.ring_send)
                           + tuple(self._exchange.ring_recv))
            self._build_face_tables()
        with metrics.phase("advection.init.step"):
            self._step = self._build_step()
            self._max_dt = self._build_max_dt()
            self._max_diff = self._build_max_diff()
            if self.overlap:
                self._step = self._build_split_step()
        if allow_boxed and not self.overlap:
            from ..parallel.boxed import build_boxed

            with metrics.phase("advection.init.boxed"):
                self.boxed = build_boxed(grid, hood_id)
                if self.boxed is not None:
                    self._boxed_run = self._build_boxed_run(self.boxed)
            # the flat two-level scheme qualifies independently of the
            # boxed layout (e.g. wrap-adjacent refinement is gated out of
            # slab-mode boxed but handled exactly by the flat rolls)
            with metrics.phase("advection.init.flat"):
                self._flat_run = self._build_flat_run()
            # cost-based choice when both fast paths qualify: prefer
            # boxed only when the flat form's voxel inflation exceeds
            # its per-voxel rate advantage over the boxed passes (one
            # edge constant per compiled form, _BOXED_EDGE).  Interpret
            # mode (tests) and the 2-level sharded XLA form keep the
            # flat preference so the flat numerics stay exercised
            if (
                self._flat_kind in ("pallas", "ml", "ml_pallas")
                and self._flat_run is not None
                and self.boxed is not None
            ):
                boxed_vol = sum(
                    int(np.prod(b.shape)) for b in self.boxed.boxes.values()
                )
                edge = _BOXED_EDGE[self._flat_kind]
                self._prefer_boxed = self._flat_n_vox > edge * boxed_vol

    # ------------------------------------------------------ static tables

    def _build_face_tables(self):
        host, dev = build_face_tables(
            self.grid, self.hood_id, self.tables, self.dtype
        )
        self.face_dir = host["face_dir"]
        self.min_area = host["min_area"]
        self.cell_axis_len = host["cell_axis_len"]
        self.nbr_axis_len = host["nbr_axis_len"]
        self.inv_volume = host["inv_volume"]
        self._dev = dev

    # -------------------------------------------------------------- kernels

    def _kernel_key(self, name: str) -> tuple:
        return (name, self._exchange.structure_key,
                str(np.dtype(self.dtype)))

    def _build_step(self):
        ex_body = self._exchange.raw_body

        def build():
            def step(rings, t, dev, state, dt):
                # ghost refresh: density only, like the reference's
                # default get_mpi_datatype (cell.hpp:46-55)
                state = {
                    **state,
                    **ex_body(*rings, {"density": state["density"]}),
                }

                rho = state["density"]
                nbr = t["nbr_rows"]
                rho_n = gather_neighbors(rho, nbr)           # [D, R, K]
                vx_n = gather_neighbors(state["vx"], nbr)
                vy_n = gather_neighbors(state["vy"], nbr)
                vz_n = gather_neighbors(state["vz"], nbr)

                sgn = jnp.sign(dev["face_dir"]).astype(rho.dtype)
                ai = dev["axis_idx"]
                v_cell = jnp.where(
                    ai == 0, state["vx"][..., None],
                    jnp.where(ai == 1, state["vy"][..., None],
                              state["vz"][..., None]),
                )
                v_nbr = jnp.where(
                    ai == 0, vx_n, jnp.where(ai == 1, vy_n, vz_n)
                )
                cl, nl = dev["cell_axis_len"], dev["nbr_axis_len"]
                # velocity interpolated to the shared face
                # (solve.hpp:168-175)
                v_face = (cl * v_nbr + nl * v_cell) / (cl + nl)

                upwind_pos = jnp.where(v_face >= 0, rho[..., None], rho_n)
                upwind_neg = jnp.where(v_face >= 0, rho_n, rho[..., None])
                upwind = jnp.where(sgn > 0, upwind_pos, upwind_neg)
                face_flux = upwind * dt * v_face * dev["min_area"]
                # +dir face: outflow subtracts; -dir face: adds
                # (solve.hpp:227-233)
                contrib = jnp.where(
                    dev["face_dir"] != 0, -sgn * face_flux, 0.0
                )
                flux = ordered_sum(contrib, axis=-1) * dev["inv_volume"]

                local = t["local_mask"]
                new_rho = jnp.where(local, rho + flux, rho)
                return {**state, "density": new_rho,
                        "flux": jnp.zeros_like(flux)}

            return traced_jit("advection.step", step)

        fn = self.grid.exec_cache.get(self._kernel_key("advection.step"),
                                      build)
        self._step_fn = fn
        rings, t, dev = self._rings, self.tables.tree(), self._dev
        return lambda state, dt: fn(rings, t, dev, state, dt)

    def _build_split_step(self):
        """Fused split-phase step (ISSUE 7; the reference's
        ``dccrg.hpp:5010-5367`` overlap pattern as ONE compiled
        program): dispatch the ghost payloads, compute the flux of the
        compacted inner rows with no data dependence on the transfer,
        merge the ghosts (the wait), then the outer rows.  The XLA
        scheduler — or the Pallas DMA engine when the halo backend is
        ``pallas`` — overlaps the transfer with interior compute without
        relying on host async dispatch.

        Bit-identical to the blocking step: inner rows gather only local
        rows, which the exchange never writes, and invalid-slot gathers
        (scratch-row padding the exchange DOES write) are masked by
        ``face_dir == 0`` in both forms before the ordered reduction."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.halo import HaloExchange
        from ..parallel.mesh import SHARD_AXIS
        from jax import shard_map

        ex = self._exchange
        host_face = {
            "face_dir": self.face_dir,
            "min_area": self.min_area,
            "cell_axis_len": self.cell_axis_len,
            "nbr_axis_len": self.nbr_axis_len,
            "inv_volume": self.inv_volume,
        }
        inner, outer, local = build_split_tables(
            self.grid, self.hood_id, host_face, self.dtype
        )
        ring_start = ex.make_ring_start()
        mesh = self.grid.mesh
        ks = tuple(ex.ring_ks)

        def build():
            nk = len(ks)
            data_spec = P(SHARD_AXIS)
            idx_spec = P(SHARD_AXIS, None)

            def side_update(rho, vx, vy, vz, t, dt):
                # the blocking step's flux math verbatim, restricted to
                # one compacted row set (same ops, same slot order —
                # that is the bit-identity argument)
                rows = t["rows"]
                rho_c = rho[rows]                            # [W]
                nbr = t["nbr_rows"]
                rho_n = rho[nbr]                             # [W, K]
                vx_n, vy_n, vz_n = vx[nbr], vy[nbr], vz[nbr]
                sgn = jnp.sign(t["face_dir"]).astype(rho.dtype)
                ai = t["axis_idx"]
                v_cell = jnp.where(
                    ai == 0, vx[rows][..., None],
                    jnp.where(ai == 1, vy[rows][..., None],
                              vz[rows][..., None]),
                )
                v_nbr = jnp.where(
                    ai == 0, vx_n, jnp.where(ai == 1, vy_n, vz_n)
                )
                cl, nl = t["cell_axis_len"], t["nbr_axis_len"]
                v_face = (cl * v_nbr + nl * v_cell) / (cl + nl)
                upwind_pos = jnp.where(v_face >= 0, rho_c[..., None], rho_n)
                upwind_neg = jnp.where(v_face >= 0, rho_n, rho_c[..., None])
                upwind = jnp.where(sgn > 0, upwind_pos, upwind_neg)
                face_flux = upwind * dt * v_face * t["min_area"]
                contrib = jnp.where(
                    t["face_dir"] != 0, -sgn * face_flux, 0.0
                )
                return rho_c + ordered_sum(contrib, axis=-1) * t["inv_volume"]

            def body(*args):
                sends = [a[0] for a in args[:nk]]
                recvs = [a[0] for a in args[nk:2 * nk]]
                ti, to, local, rho, vx, vy, vz, dt = args[2 * nk:]
                sub = lambda t: {k: v[0] for k, v in t.items()}
                ti, to = sub(ti), sub(to)
                a = rho[0]
                vx, vy, vz = vx[0], vy[0], vz[0]
                # --- start: ghost payloads in flight (depend on `a`)
                payloads = ring_start(a, sends)
                # --- interior: no remote neighbors, no dep on payloads
                new_i = side_update(a, vx, vy, vz, ti, dt)
                # --- wait: merging the payloads IS the synchronization
                a2 = HaloExchange.ring_finish(a, recvs, payloads)
                # --- boundary: needs the fresh ghosts
                new_o = side_update(a2, vx, vy, vz, to, dt)
                out = a2.at[ti["rows"]].set(new_i).at[to["rows"]].set(new_o)
                out = jnp.where(local[0], out, a2)       # clean scratch
                return out[None]

            fn = shard_map(
                body,
                mesh=mesh,
                in_specs=(idx_spec,) * (2 * nk)
                + (_table_specs(inner), _table_specs(outer), idx_spec)
                + (data_spec,) * 4 + (P(),),
                out_specs=data_spec,
                check_vma=False,
            )

            def step(rings, ti, to, local, state, dt):
                new_rho = fn(
                    *rings, ti, to, local, state["density"], state["vx"],
                    state["vy"], state["vz"], dt,
                )
                return {**state, "density": new_rho,
                        "flux": jnp.zeros_like(new_rho)}

            return traced_jit("advection.split_step", step)

        fn = self.grid.exec_cache.get(
            self._kernel_key("advection.split_step"), build
        )
        self._split_fn = fn
        self._split_args = (self._rings, inner, outer, local)
        args = self._split_args
        return lambda state, dt: fn(*args, state, dt)

    def _build_max_dt(self):
        def build():
            def max_dt(t, state):
                # CFL: min over local cells of length/|v| per dim, global
                # min (solve.hpp:284-330)
                length = t["length"]
                steps = jnp.stack(
                    [
                        length[..., 0] / jnp.abs(state["vx"]),
                        length[..., 1] / jnp.abs(state["vy"]),
                        length[..., 2] / jnp.abs(state["vz"]),
                    ],
                    axis=-1,
                )
                ok = (jnp.isfinite(steps) & (steps > 0)
                      & t["local_mask"][..., None])
                steps = jnp.where(ok, steps, jnp.inf)
                return jnp.min(steps)

            return traced_jit("advection.max_dt", max_dt)

        fn = self.grid.exec_cache.get(
            ("advection.max_dt", str(np.dtype(self.dtype))), build
        )
        t = self.tables.tree()
        return lambda state: fn(t, state)

    def _build_max_diff(self):
        ex_body = self._exchange.raw_body

        def build():
            def max_diff(rings, t, dev, state, diff_threshold):
                """Max relative density difference to face neighbors
                (adapter.hpp:71-110) — the AMR refinement indicator."""
                state = {
                    **state,
                    **ex_body(*rings, {"density": state["density"]}),
                }
                rho = state["density"]
                rho_n = gather_neighbors(rho, t["nbr_rows"])
                diff = jnp.abs(rho[..., None] - rho_n) / (
                    jnp.minimum(rho[..., None], rho_n) + diff_threshold
                )
                diff = jnp.where(dev["face_dir"] != 0, diff, 0.0)
                md = diff.max(axis=-1)
                return {**state,
                        "max_diff": jnp.where(t["local_mask"], md, 0.0)}

            return traced_jit("advection.max_diff", max_diff)

        fn = self.grid.exec_cache.get(
            self._kernel_key("advection.max_diff"), build
        )
        rings, t, dev = self._rings, self.tables.tree(), self._dev
        return lambda state, thr: fn(rings, t, dev, state, thr)

    # ------------------------------------------------------ boxed AMR path

    def _build_flat_run(self):
        """Whole-run fused kernel for two-level AMR on the flat inflated
        grid (ops/flat_amr.py): the entire run loop in VMEM, one launch.
        None when the grid/device/dtype does not qualify; the boxed path
        remains the general fallback (and the step()/indicator path)."""
        from ..ops.dense_advection import pallas_available
        from ..ops.flat_amr import (
            build_flat_amr_sharded,
            build_flat_amr_tables,
            build_flat_ml_tables,
            compute_flat_weights,
            flat_amr_fits,
            make_flat_amr_run,
            make_flat_amr_run_sharded,
            make_flat_ml_run,
            pad_lane_extent,
        )

        # use_pallas doubles as the fast-path opt-out: False always means
        # the reference boxed numerics
        self._flat_kind = None
        if not self.use_pallas:
            return None

        # 3+ leaf levels: the multi-level flat whole-run forms — the
        # VMEM-resident Pallas kernel when a single device, f32, and the
        # budget allow, else the XLA pyramid form (any device count) —
        # VERDICT-r4's extension of the fast path past levels {0, 1}
        with metrics.phase("advection.init.flat.ml_tables"):
            tml = build_flat_ml_tables(self.grid)
        if tml is not None:
            from ..ops.flat_amr import flat_ml_kernel_fits

            self._flat_n_vox = int(tml["n_vox"])
            interpret = self.use_pallas == "interpret"
            if (
                tml["n_devices"] == 1
                and np.dtype(self.dtype) == np.float32
                and (interpret or pallas_available(self.dtype))
                and flat_ml_kernel_fits(self._flat_n_vox, tml["vl"])
            ):
                self._flat_kind = ("ml_pallas_interpret" if interpret
                                   else "ml_pallas")
                return self._build_ml_pallas_run(tml, interpret)
            jdt = (
                jnp.float32
                if np.dtype(self.dtype) == np.float32
                else jnp.float64
            )
            self._flat_kind = "ml"
            return make_flat_ml_run(self.grid, tml, dtype=jdt)

        # multi-device: z-slab-sharded XLA form (no Pallas requirement)
        with metrics.phase("advection.init.flat.sharded_tables"):
            ts = build_flat_amr_sharded(self.grid)
        if ts is not None:
            jdt = (
                jnp.float32
                if np.dtype(self.dtype) == np.float32
                else jnp.float64
            )
            self._flat_n_vox = int(np.prod(ts["shape"])) * ts["n_devices"]
            self._flat_kind = "sharded"
            return make_flat_amr_run_sharded(self.grid, ts, dtype=jdt)

        interpret = self.use_pallas == "interpret"
        if np.dtype(self.dtype) != np.float32:
            return None
        if not (interpret or pallas_available(self.dtype)):
            return None
        with metrics.phase("advection.init.flat.amr_tables"):
            t = build_flat_amr_tables(self.grid)
        if t is None:
            return None
        nz1, ny1, nx1 = t["shape"]
        self._flat_n_vox = nz1 * ny1 * nx1
        self._flat_kind = "pallas_interpret" if interpret else "pallas"
        # lane-align the x extent when the pad fits VMEM: Mosaic pads
        # registers to 128 lanes regardless, so the explicit pad costs no
        # extra compute and turns the 12 per-step x rolls lane-aligned
        nxp = pad_lane_extent(nx1)
        if nxp != nx1 and not flat_amr_fits(nz1 * ny1 * nxp):
            nxp = nx1
        self._flat_nx_pad = nxp if nxp != nx1 else None
        kernel = make_flat_amr_run(nz1, ny1, nx1, nx_pad=self._flat_nx_pad,
                                   interpret=interpret)
        leaf = t["leaf_fine"]
        # runtime-argument tables (not closed over): the jitted body is
        # content-independent, so regridding rebuilds only this pytree
        tabs = {
            "rows": jnp.asarray(t["rows"]),
            "updf": jnp.asarray(
                leaf.astype(np.float64) / t["vol_f"], jnp.float32
            ),
            "updc": jnp.asarray(
                (~leaf).astype(np.float64) / t["vol_c"], jnp.float32
            ),
            "wb_rows": jnp.asarray(t["wb_rows"]),
            "wb_valid": jnp.asarray(t["wb_valid"]),
        }

        def run_fn(tabs, state, steps, dt):
            def field(name):
                return state[name][0][tabs["rows"]].reshape(nz1, ny1, nx1)

            V = field("density")
            w = compute_flat_weights(
                t, field("vx"), field("vy"), field("vz")
            )
            (wpx, wnx), (wpy, wny), (wpz, wnz) = w
            out = kernel(
                V, wpx, wnx, wpy, wny, wpz, wnz,
                tabs["updf"], tabs["updc"],
                jnp.asarray(dt, jnp.float32), steps,
            )
            rho = jnp.where(
                tabs["wb_valid"], out.reshape(-1)[tabs["wb_rows"]],
                state["density"][0],
            )
            return {
                **state,
                "density": rho[None].astype(state["density"].dtype),
                "flux": jnp.zeros_like(state["flux"]),
            }

        run_fn = traced_jit("advection.flat_run", run_fn)
        return lambda state, steps, dt: run_fn(tabs, state, steps, dt)

    def _build_ml_pallas_run(self, t, interpret):
        """VMEM-resident whole-run for a 3+-level grid on one device:
        voxelize, compute the per-face weights once, run every step
        inside one Pallas launch (ops/flat_amr.make_flat_ml_run_pallas),
        write back leaf rows."""
        from ..ops.flat_amr import (
            compute_flat_ml_weights,
            make_flat_ml_run_pallas,
        )

        nzl, nyv, nxv = t["shape"]
        kernel = make_flat_ml_run_pallas(
            nzl, nyv, nxv, t["vl"], t["cap_active"], interpret=interpret
        )
        rows = jnp.asarray(t["rows"][0])
        updf = jnp.asarray(t["updf"][0], jnp.float32)
        pool = jnp.asarray(t["pool"][0], jnp.float32)
        caps = [jnp.asarray(c[0], jnp.float32) for c in t["cap_origin"]]
        wb_rows = jnp.asarray(t["wb_rows"][0])
        wb_valid = jnp.asarray(t["wb_valid"][0])

        def run_fn(state, steps, dt):
            def field(name):
                return (state[name][0][rows]
                        .reshape(nzl, nyv, nxv).astype(jnp.float32))

            V = field("density")
            w = compute_flat_ml_weights(
                t, field("vx"), field("vy"), field("vz")
            )
            (wpx, wnx), (wpy, wny), (wpz, wnz) = w
            out = kernel(
                V, wpx, wnx, wpy, wny, wpz, wnz, updf, pool, caps,
                jnp.asarray(dt, jnp.float32), steps,
            )
            rho = jnp.where(
                wb_valid, out.reshape(-1)[wb_rows], state["density"][0]
            )
            return {
                **state,
                "density": rho[None].astype(state["density"].dtype),
                "flux": jnp.zeros_like(state["flux"]),
            }

        return traced_jit("advection.ml_pallas_run", run_fn)

    def _build_boxed_run(self, layout):
        """Multi-step run over the boxed per-level AMR layout — one unified
        dense pass per level per step, z-slab sharded over the device mesh
        with circular ppermute plane rings.  See
        ``models/boxed_advection.py`` for the full scheme and the
        multi-device correctness argument.  Sets ``_boxed_moved``, per
        level whether the Pallas moves carry it; ``_record_run`` counts
        each boxed dispatch as ``boxed.kernel_runs{form=pallas}`` where
        any level's do, else ``{form=xla}``."""
        from .boxed_advection import build_boxed_run

        run, self._boxed_moved = build_boxed_run(self, layout)
        return run

    # ------------------------------------------------------ dense fast path

    def _init_dense(self):
        """Uniform-grid specialization (parallel/dense.py): payloads as
        dense [D, nzl, ny, nx] z-slab blocks, the halo as two ppermute plane
        transfers, and every face flux as shifted slices that XLA fuses into
        one HBM pass — the layout the reference's per-cell object model
        cannot express but the one a TPU needs.

        Every compiled artifact is a pure function of (mesh, dims,
        periodicity, cell size, dtype, pallas mode), so the whole kernel
        bundle is cached under that key — an adapt cycle that returns to
        the same uniform shape redispatches the existing executables."""
        from ..parallel.exec_cache import mesh_key

        info = self.dense
        l0 = self.grid.geometry.get_level_0_cell_length()
        self._dx = l0.astype(np.float64)
        self._vol = float(l0.prod())
        pallas_mode = (self.use_pallas if isinstance(self.use_pallas, str)
                       else bool(self.use_pallas))
        key = (
            "advection.dense", mesh_key(self.grid.mesh), info.n_devices,
            info.nz_local, info.ny, info.nx,
            tuple(bool(p) for p in info.periodic),
            str(np.dtype(self.dtype)), pallas_mode,
            tuple(np.asarray(l0, np.float64).tolist()),
        )
        self._dense_key = key
        bundle = self.grid.exec_cache.get(key, self._build_dense_bundle)
        self._step = bundle["step"]
        self._fused_run = bundle["fused_run"]
        self._dense_run = bundle["dense_run"]
        self._max_dt = bundle["max_dt"]
        self._max_diff = bundle["max_diff"]
        self.dense_kind = bundle["dense_kind"]

    def _build_dense_bundle(self) -> dict:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.dense import HaloExtend
        from ..parallel.mesh import SHARD_AXIS, shard_spec

        info = self.dense
        grid = self.grid
        dtype = self.dtype
        D, nzl, ny, nx = info.n_devices, info.nz_local, info.ny, info.nx
        l0 = grid.geometry.get_level_0_cell_length()
        area = np.array([l0[1] * l0[2], l0[0] * l0[2], l0[0] * l0[1]])
        vol = float(l0.prod())
        px, py, pz = info.periodic
        extend = HaloExtend(info)
        mesh = grid.mesh
        data_spec = P(SHARD_AXIS)

        # Face validity masks for non-periodic boundaries.  "Face i" along a
        # dimension sits between cell i and cell (i+1) mod n; the wrapping
        # face is invalid unless that dimension is periodic (a neighborhood
        # slot outside the grid has no neighbor, hence no flux).
        mask_x = np.ones(nx)
        mask_y = np.ones(ny)
        if not px:
            mask_x[-1] = 0.0
        if not py:
            mask_y[-1] = 0.0
        # z-face validity per (device, local plane): face above plane g is
        # invalid for the global top plane unless periodic
        zface_up = np.ones((D, nzl))
        if not pz:
            zface_up[-1, -1] = 0.0
        # validity of the face *below* plane g = validity of the face above
        # plane g-1
        zface_dn = np.roll(zface_up.reshape(-1), 1).reshape(D, nzl)
        put = lambda a: put_table(a, mesh, dtype)
        zf_up_dev, zf_dn_dev = put(zface_up), put(zface_dn)
        mx = jnp.asarray(mask_x, dtype)[None, None, :]
        my = jnp.asarray(mask_y, dtype)[None, :, None]
        area = area.astype(dtype)

        def face_flux(rho_c, rho_n, v_c, v_n, area_d, dt):
            # uniform cells: the reference's length-weighted face velocity
            # (solve.hpp:168-175) reduces to the plain average
            v_face = (v_c + v_n) * dtype(0.5)
            up = jnp.where(v_face >= 0, rho_c, rho_n)
            return up * (dt * v_face * area_d)

        # Optional fused Pallas kernel (TPU + f32): same update, one VMEM
        # pass per z-slab instead of XLA-materialized rolls
        from ..ops.dense_advection import (
            flux_update_fits,
            fused_run_fits,
            make_flux_update,
            make_flux_update_blocked_direct,
            make_fused_run,
            pallas_available,
            pick_step_block,
            run_ping_pong,
        )

        pallas_update = None
        blocked_update = None
        step_block = 0
        #: which per-step dense kernel engaged — ("blocked_direct", B) /
        #: ("plane",) / ("xla",) — so the bench's HBM-traffic model can
        #: count the bytes the engaged path actually moves
        dense_kind = ("xla",)
        use_pallas = getattr(self, "use_pallas", True)
        # use_pallas="interpret" forces the kernels through the Pallas
        # interpreter so CI (CPU) exercises the full integration path
        interpret = use_pallas == "interpret"
        if use_pallas and (interpret or pallas_available(dtype)):
            step_block = pick_step_block(nzl, ny, nx)
            if step_block >= 2:
                blocked_update = make_flux_update_blocked_direct(
                    nzl, ny, nx, step_block, area, 1.0 / vol,
                    interpret=interpret,
                )
                dense_kind = ("blocked_direct", step_block)
            elif interpret or flux_update_fits(ny, nx):
                pallas_update = make_flux_update(
                    nzl, ny, nx, area, 1.0 / vol, interpret=interpret
                )
                dense_kind = ("plane",)
            if blocked_update is not None or pallas_update is not None:
                mx3 = jnp.asarray(mask_x, dtype).reshape(1, 1, nx)
                my3 = jnp.asarray(mask_y, dtype).reshape(1, ny, 1)


        # Negative-side x/y faces: the flux through cell i's negative face
        # equals the positive-side face flux of cell i-1, i.e.
        # jnp.roll(f, 1, axis) — the boundary mask is already baked into f.
        # Accumulation follows the general path's slot order (z-, y-, x-,
        # x+, y+, z+); negative-side face flux enters the cell with +,
        # positive-side leaves with - (solve.hpp:227-233).
        def blocked_step(rho, vx, vy, vz, v_lo, v_hi, mzu, mzd, dt):
            """One blocked-kernel step given the vz device-edge planes —
            shared by step() (planes rebuilt per call: vz is an input)
            and the multi-step run (planes hoisted out of the loop).
            rho's interior neighbor planes are read in-kernel through the
            direct index maps; only its two ppermute edge planes are
            produced here."""
            r_lo, r_hi = extend.planes(rho)
            return blocked_update(
                rho, r_lo, r_hi, vx, vy, vz, v_lo, v_hi, mx3, my3,
                mzu, mzd, dt,
            )

        def body(zf_up, zf_dn, rho, vx, vy, vz, dt):
            rho, vx, vy, vz = rho[0], vx[0], vy[0], vz[0]
            mz_up = zf_up[0][:, None, None]
            mz_dn = zf_dn[0][:, None, None]

            if blocked_update is not None:
                v_lo, v_hi = extend.planes(vz)
                new_rho = blocked_step(
                    rho, vx, vy, vz, v_lo, v_hi, mz_up, mz_dn, dt
                )
                return (new_rho[None],)

            rho_e = extend(rho)
            vz_e = extend(vz)

            if pallas_update is not None:
                new_rho = pallas_update(
                    rho_e, vx, vy, vz_e, mx3, my3, mz_up, mz_dn, dt,
                )
                return (new_rho[None],)

            fx = face_flux(rho, jnp.roll(rho, -1, 2), vx, jnp.roll(vx, -1, 2), area[0], dt) * mx
            fy = face_flux(rho, jnp.roll(rho, -1, 1), vy, jnp.roll(vy, -1, 1), area[1], dt) * my
            fz = face_flux(rho, rho_e[2:], vz, vz_e[2:], area[2], dt) * mz_up
            fz_dn = face_flux(rho_e[:-2], rho, vz_e[:-2], vz, area[2], dt) * mz_dn

            flux = fz_dn
            flux = flux + jnp.roll(fy, 1, 1)
            flux = flux + jnp.roll(fx, 1, 2)
            flux = flux - fx
            flux = flux - fy
            flux = flux - fz
            return ((rho + flux * dtype(1.0 / vol))[None],)

        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(data_spec, data_spec, data_spec, data_spec, data_spec, data_spec, P()),
            out_specs=(data_spec,),
            check_vma=False,
        )

        # z-face masks as runtime-argument tables (ROADMAP item 4): the
        # jitted bodies are table-content-independent; only the plain
        # wrappers below close over the device copies
        @jax.jit
        def step_fn(zf_up, zf_dn, state, dt):
            (new_rho,) = fn(
                zf_up, zf_dn,
                state["density"], state["vx"], state["vy"], state["vz"],
                jnp.asarray(dt, dtype),
            )
            return {**state, "density": new_rho}

        def step(state, dt):
            return step_fn(zf_up_dev, zf_dn_dev, state, dt)


        # Whole-block multi-step kernel (single device, block fits VMEM):
        # the entire run loop executes inside one kernel launch with zero
        # HBM traffic between steps — compute-bound instead of HBM-bound
        fused_run = None
        have_pallas = pallas_update is not None or blocked_update is not None
        if have_pallas and D == 1 and fused_run_fits(nzl, ny, nx):
            fused = make_fused_run(
                nzl, ny, nx, area, 1.0 / vol, interpret=interpret
            )
            mzu3 = jnp.asarray(zface_up[0], dtype).reshape(nzl, 1, 1)
            mzd3 = jnp.asarray(zface_dn[0], dtype).reshape(nzl, 1, 1)

            # face masks as runtime-argument tables (ROADMAP item 4):
            # the jitted body is table-content-independent — the masks
            # are plain pallas-kernel operands either way, so lifting
            # them through the jit boundary cannot perturb the kernel —
            # and only the plain wrapper closes over the device copies
            def fused_run_fn(masks, state, steps, dt):
                fmx, fmy, fmzu, fmzd = masks
                new_rho = fused(
                    state["density"][0], state["vx"][0], state["vy"][0],
                    state["vz"][0], fmx, fmy, fmzu, fmzd, dt, steps,
                )
                return {**state, "density": new_rho[None]}

            fused_run_fn = traced_jit("advection.fused_run", fused_run_fn)

            def fused_run(state, steps, dt):
                return fused_run_fn(
                    (mx3, my3, mzu3, mzd3), state, steps, dt)

        # Blocked multi-step run: the whole loop inside one shard_map so
        # the constant vz halo stacks are built once per run call, not
        # once per step (the generic run path re-derives them every
        # iteration because the step body cannot know vz is loop-invariant);
        # two steps per loop iteration (run_ping_pong) so no step copies the
        # density
        dense_run = None
        if blocked_update is not None:

            def run_body(zf_up, zf_dn, rho, vx, vy, vz, dt, steps):
                rho, vx, vy, vz = rho[0], vx[0], vy[0], vz[0]
                mzu = zf_up[0][:, None, None]
                mzd = zf_dn[0][:, None, None]
                v_lo, v_hi = extend.planes(vz)

                def one(r):
                    return blocked_step(
                        r, vx, vy, vz, v_lo, v_hi, mzu, mzd, dt
                    )

                return (run_ping_pong(one, rho, steps)[None],)

            run_sm = shard_map(
                run_body,
                mesh=mesh,
                in_specs=(data_spec,) * 6 + (P(), P()),
                out_specs=(data_spec,),
                check_vma=False,
            )

            # returns the new density alone: every other field handed back
            # through the jit would cost a full device copy per call
            def dense_run_fn(zf_up, zf_dn, rho, vx, vy, vz, steps, dt):
                (new_rho,) = run_sm(
                    zf_up, zf_dn, rho, vx, vy, vz,
                    jnp.asarray(dt, dtype), jnp.asarray(steps, jnp.int32),
                )
                return new_rho

            dense_run_fn = traced_jit("advection.dense_run", dense_run_fn)

            def dense_run(state, steps, dt):
                new_rho = dense_run_fn(
                    zf_up_dev, zf_dn_dev, state["density"], state["vx"],
                    state["vy"], state["vz"], steps, dt,
                )
                return {**state, "density": new_rho}

        dx = self._dx

        @jax.jit
        def max_dt(state):
            s = jnp.stack(
                [
                    dtype(dx[0]) / jnp.abs(state["vx"]),
                    dtype(dx[1]) / jnp.abs(state["vy"]),
                    dtype(dx[2]) / jnp.abs(state["vz"]),
                ],
                axis=-1,
            )
            s = jnp.where(jnp.isfinite(s) & (s > 0), s, jnp.inf)
            return jnp.min(s)


        # AMR refinement indicator on the dense layout (adapter.hpp:71-110
        # runs on the same data the solver uses — so does this): max
        # relative density difference to the 6 face neighbors as shifted
        # slices, with open-boundary faces masked out (the solver's own
        # mx/my masks; mxn/myn are their negative-side rolls) and z
        # through the slab halo ring
        mxp, myp = mx, my
        mxn = jnp.roll(mxp, 1, 2)
        myn = jnp.roll(myp, 1, 1)

        def md_body(zf_up, zf_dn, rho, thr):
            rho = rho[0]

            def rel(a, b):
                return jnp.abs(a - b) / (jnp.minimum(a, b) + thr)

            rho_e = extend(rho)
            md = rel(rho, jnp.roll(rho, -1, 2)) * mxp
            md = jnp.maximum(md, rel(rho, jnp.roll(rho, 1, 2)) * mxn)
            md = jnp.maximum(md, rel(rho, jnp.roll(rho, -1, 1)) * myp)
            md = jnp.maximum(md, rel(rho, jnp.roll(rho, 1, 1)) * myn)
            md = jnp.maximum(md, rel(rho, rho_e[2:]) * zf_up[0][:, None, None])
            md = jnp.maximum(md, rel(rho, rho_e[:-2]) * zf_dn[0][:, None, None])
            return (md[None],)

        fn_md = shard_map(
            md_body,
            mesh=mesh,
            in_specs=(data_spec, data_spec, data_spec, P()),
            out_specs=(data_spec,),
            check_vma=False,
        )

        @jax.jit
        def max_diff_fn(zf_up, zf_dn, state, diff_threshold):
            (md,) = fn_md(
                zf_up, zf_dn, state["density"],
                jnp.asarray(diff_threshold, dtype),
            )
            return {**state, "max_diff": md}

        def dense_max_diff(state, diff_threshold):
            return max_diff_fn(zf_up_dev, zf_dn_dev, state, diff_threshold)

        return {
            "step": step,
            "fused_run": fused_run,
            "dense_run": dense_run,
            "max_dt": max_dt,
            "max_diff": dense_max_diff,
            "dense_kind": dense_kind,
        }

    def _dense_to_rows(self, state):
        """Dense [D, nzl, ny, nx] state -> general [D, R] row-layout state
        (vectorized per field)."""
        grid = self.grid
        cells = grid.get_cells()
        row_state = grid.new_state(self.spec)
        for name in self.spec:
            vals = self.get_cell_data(state, name, cells)
            row_state = grid.set_cell_data(row_state, name, cells, vals)
        return row_state

    def _dense_coords(self, ids):
        """(device, local z, y, x) of given cell ids in the dense layout."""
        ids = np.asarray(ids, dtype=np.uint64)
        i = self.dense
        lin = (ids - np.uint64(1)).astype(np.int64)
        x = lin % i.nx
        y = (lin // i.nx) % i.ny
        z = lin // (i.nx * i.ny)
        return z // i.nz_local, z % i.nz_local, y, x

    # ----------------------------------------------------------- user API

    def initialize_state(self):
        """Rotating-hump initial condition (initialize.hpp:36-80): solid-body
        rotation about the domain center, cosine density hump.  Timed as
        ``advection.init_state``."""
        with metrics.phase("advection.init_state"):
            return self._initialize_state()

    def _initialize_state(self):
        grid = self.grid
        cells = grid.get_cells()
        centers = grid.geometry.get_center(cells)
        vx = -centers[:, 1] + 0.5
        vy = centers[:, 0] - 0.5
        vz = np.zeros(len(cells))
        radius = 0.15
        r = np.minimum(
            np.sqrt((centers[:, 0] - 0.25) ** 2 + (centers[:, 1] - 0.5) ** 2), radius
        ) / radius
        rho = 0.25 * (1 + np.cos(np.pi * r))

        if self.dense is not None:
            from ..parallel.mesh import shard_spec

            i = self.dense
            shape = (i.n_devices, i.nz_local, i.ny, i.nx)
            state = {}
            for name in self.spec:
                state[name] = jnp.zeros(shape, dtype=self.dtype)
            d, zl, y, x = self._dense_coords(cells)
            for name, vals in (("density", rho), ("vx", vx), ("vy", vy), ("vz", vz)):
                host = np.zeros(shape, dtype=self.dtype)
                host[d, zl, y, x] = vals
                state[name] = jax.device_put(
                    jnp.asarray(host), shard_spec(self.grid.mesh, 4)
                )
            return state

        state = grid.new_state(self.spec)
        state = grid.set_cell_data(state, "vx", cells, vx)
        state = grid.set_cell_data(state, "vy", cells, vy)
        state = grid.set_cell_data(state, "vz", cells, vz)
        state = grid.set_cell_data(state, "density", cells, rho)
        # ghosts need velocities once (the reference transfers all data at
        # init); densities refresh every step
        state = self._exchange(state)
        return state

    def get_cell_data(self, state, field: str, ids):
        """Layout-aware per-cell read (dense or row layout)."""
        if self.dense is not None:
            d, zl, y, x = self._dense_coords(ids)
            return fetch(state[field])[d, zl, y, x]
        return self.grid.get_cell_data(state, field, ids)

    def set_cell_data(self, state, field: str, ids, values):
        if self.dense is not None:
            from ..parallel.mesh import shard_spec

            d, zl, y, x = self._dense_coords(ids)
            host = fetch(state[field]).copy()
            host[d, zl, y, x] = values
            return {
                **state,
                field: jax.device_put(
                    jnp.asarray(host), shard_spec(self.grid.mesh, 4)
                ),
            }
        return self.grid.set_cell_data(state, field, ids, values)

    def step(self, state, dt):
        return self._step(state, dt)

    def _wide_spec(self):
        """Exchange-amortized step split (ISSUE 14): one full-depth
        default-hood density exchange funds ``budget`` interior steps.
        Stencil relevance is ``"face"`` — the flux kernel masks every
        non-face entry to an exact 0.0 via ``face_dir``, so a depth-g
        default hood funds g face-stencil steps even though corner
        neighbors of deep ghost rows are absent on the replica.  Ghost
        velocities are valid forever (``initialize_state`` ends with a
        full-state exchange and the fields are static), so only density
        staleness meters the budget."""
        from ..parallel.exec_cache import WideStepSpec
        from ..parallel.mesh import put_table
        from ..parallel.wide_halo import get_wide_plan, wide_enabled

        if not wide_enabled() or self.tables is None:
            return None
        cached = getattr(self, "_wide_cached", None)
        if cached is not None and cached[0] is self.grid.epoch:
            return cached[1]
        plan = get_wide_plan(self.grid, self.hood_id, relevance="face")
        spec = None
        if plan.budget >= 2:
            wex = self.grid.halo(None)
            wex_body = wex.raw_body
            wrings = tuple(wex.ring_send) + tuple(wex.ring_recv)
            mesh = self.grid.mesh
            _, wdev = build_face_tables(
                self.grid, self.hood_id, self.tables, self.dtype,
                hood_arrays=(plan.nbr_offset, plan.nbr_len,
                             plan.nbr_rows, plan.nbr_valid),
            )
            wt = dict(wdev)
            wt["nbr_rows"] = put_table(plan.nbr_rows, mesh)
            wt["steps_ok"] = put_table(plan.steps_ok, mesh)

            def build():
                def interior(wt, state, dt, j):
                    rho = state["density"]
                    nbr = wt["nbr_rows"]
                    rho_n = gather_neighbors(rho, nbr)
                    vx_n = gather_neighbors(state["vx"], nbr)
                    vy_n = gather_neighbors(state["vy"], nbr)
                    vz_n = gather_neighbors(state["vz"], nbr)

                    sgn = jnp.sign(wt["face_dir"]).astype(rho.dtype)
                    ai = wt["axis_idx"]
                    v_cell = jnp.where(
                        ai == 0, state["vx"][..., None],
                        jnp.where(ai == 1, state["vy"][..., None],
                                  state["vz"][..., None]),
                    )
                    v_nbr = jnp.where(
                        ai == 0, vx_n, jnp.where(ai == 1, vy_n, vz_n)
                    )
                    cl, nl = wt["cell_axis_len"], wt["nbr_axis_len"]
                    v_face = (cl * v_nbr + nl * v_cell) / (cl + nl)

                    upwind_pos = jnp.where(
                        v_face >= 0, rho[..., None], rho_n
                    )
                    upwind_neg = jnp.where(
                        v_face >= 0, rho_n, rho[..., None]
                    )
                    upwind = jnp.where(sgn > 0, upwind_pos, upwind_neg)
                    face_flux = upwind * dt * v_face * wt["min_area"]
                    contrib = jnp.where(
                        wt["face_dir"] != 0, -sgn * face_flux, 0.0
                    )
                    flux = ordered_sum(contrib, axis=-1) * wt["inv_volume"]

                    # live = rows whose stencil inputs are still exact at
                    # interior step j; identical flux math as the fused
                    # step over bitwise-equal table rows, so live local
                    # rows match the exchange-every-step path exactly
                    live = wt["steps_ok"] > j
                    new_rho = jnp.where(live, rho + flux, rho)
                    return {**state, "density": new_rho,
                            "flux": jnp.zeros_like(flux)}

                return traced_jit("advection.wide_step", interior)

            fn = self.grid.exec_cache.get(
                ("advection.wide_step", wex.structure_key,
                 str(np.dtype(self.dtype))), build
            )
            spec = WideStepSpec(
                exchange=lambda args, wargs, state: {
                    **state,
                    **wex_body(*wargs[0], {"density": state["density"]}),
                },
                interior=lambda args, wargs, state, dt, j: fn(
                    wargs[1], state, dt, j
                ),
                budget=plan.budget,
                args=(wrings, wt),
                local_mask=plan.local_mask,
            )
        self._wide_cached = (self.grid.epoch, spec)
        return spec

    def batch_step_spec(self):
        """This model's step entry point in cohort-batchable form
        (ISSUE 9): the compiled member program plus its runtime-argument
        tables, so ``dccrg_tpu/serve`` can stack many same-signature
        scenarios on a leading axis and vmap one jitted cohort body over
        them.  Works for the dense fast path (tables are closed-over
        pure functions of the kernel key) and both general gather forms
        (tables ride along per member as stacked arguments).  The
        spec's ``steps_per_dispatch`` declares the default deep-dispatch
        depth (``DCCRG_ENSEMBLE_K``, ISSUE 11): the serving tier wraps
        ``call`` in a device-side ``fori_loop`` advancing that many
        interior steps per host dispatch — each step's halo exchange
        runs inside the loop body, so the in-kernel protocol is
        identical to ``step`` called k times."""
        from ..parallel.exec_cache import (
            BatchStepSpec,
            default_steps_per_dispatch,
        )

        k = default_steps_per_dispatch()
        dtype = np.dtype(self.dtype)
        if self.dense is not None:
            step = self._step
            return BatchStepSpec(
                kind="advection.dense", kernel_key=self._dense_key,
                call=lambda args, state, dt: step(state, dt),
                args=(), dt_dtype=dtype, steps_per_dispatch=k,
            )
        wide = self._wide_spec()
        if self.overlap:
            fn = self._split_fn
            return BatchStepSpec(
                kind="advection.split",
                kernel_key=self._kernel_key("advection.split_step"),
                call=lambda args, state, dt: fn(*args, state, dt),
                args=self._split_args, dt_dtype=dtype,
                steps_per_dispatch=k, wide=wide,
            )
        fn = self._step_fn
        return BatchStepSpec(
            kind="advection",
            kernel_key=self._kernel_key("advection.step"),
            call=lambda args, state, dt: fn(*args, state, dt),
            args=(self._rings, self.tables.tree(), self._dev),
            dt_dtype=dtype, steps_per_dispatch=k, wide=wide,
        )

    def _record_run(self, path: str, steps, state) -> None:
        """Post-run reconciliation (obs.fused): the whole-run paths keep
        their ghost traffic inside jit, so the host seam sees nothing —
        record ``steps x bytes per step`` once per dispatch instead.  On
        the dense path a step sends one z-plane of density each way
        around the slab ring (none on one device); elsewhere the bytes
        per step are the halo schedule's, computed once per schedule
        (one per epoch) and density dtype and shape, not on every call."""
        from ..obs import fused

        if not self.grid.telemetry.enabled:
            return
        rho = state["density"]
        if self.dense is not None:
            i = self.dense
            planes = 2 * i.n_devices if i.n_devices > 1 else 0
            bps = planes * i.ny * i.nx * np.dtype(rho.dtype).itemsize
            fused.record_run("advection", path, steps, bps)
            return
        try:
            ex = self.grid.halo(None)
            key = (ex, rho.dtype, rho.shape)
            if key != self._bps_key:
                self._bps = ex.bytes_moved({"density": rho})
                self._bps_key = key
        except Exception:  # noqa: BLE001 — telemetry must never raise
            self._bps_key, self._bps = None, 0
        fused.record_run("advection", path, steps, self._bps)
        if path == "boxed":
            metrics.inc("boxed.kernel_runs",
                        form="pallas" if any(self._boxed_moved) else "xla")

    def run(self, state, steps: int, dt):
        """Advance ``steps`` timesteps in a single device-side loop
        (``lax.fori_loop``) — one dispatch for the whole run, the
        compiler-friendly form of the reference's while-loop driver
        (2d.cpp:321+).  Use this for tight stepping; ``step`` for loops
        interleaved with host logic (AMR, load balancing, IO).

        Each call is marked on the profiler's host plane as
        ``advection.run``, holding ``advection.run.record`` (the
        ``fused.*`` counters), ``advection.run.args`` (steps and dt made
        device scalars) and ``advection.run.launch`` (the jitted
        whole-run call, ``jit_advection_<path>_run`` on the device)."""
        with TraceAnnotation("advection.run"):
            path, launch = self._launcher()
            with TraceAnnotation("advection.run.record"):
                self._record_run(path, steps, state)
            with TraceAnnotation("advection.run.args"):
                # the gather-path loops take steps as a Python int (jit's
                # own scalar path); the whole-run kernels a device scalar
                n = (steps if path in ("general", "split")
                     else jnp.asarray(steps, jnp.int32))
                d = jnp.asarray(dt, self.dtype)
            with TraceAnnotation("advection.run.launch"):
                return launch(state, n, d)

    def _launcher(self):
        """``(path, fn(state, steps, dt))`` of the whole-run dispatch."""
        if getattr(self, "_fused_run", None) is not None:
            return "fused", self._fused_run
        if (
            getattr(self, "_prefer_boxed", False)
            and getattr(self, "_boxed_run", None) is not None
        ):
            return "boxed", self._boxed_run
        if getattr(self, "_flat_run", None) is not None:
            return "flat", self._flat_or_general
        return self._general_launcher()

    def _flat_or_general(self, state, steps, dt):
        # the flat kernel is an optimization; if the TPU compiler
        # rejects it (op support varies by generation), fall back to
        # the boxed/general dispatch permanently for this instance —
        # but only after the fallback succeeds on the same inputs
        # (utils/fallback.py's policy), so a caller error propagates
        return fallback_call(
            "flat AMR kernel",
            lambda: self._flat_run(state, steps, dt),
            lambda: self._run_general(state, steps, dt),
            self._disable_flat,
        )

    def _disable_flat(self):
        self._flat_run = None

    def _run_general(self, state, steps, dt):
        """The non-flat whole-run dispatch, recorded under its own path
        (the flat kernel's fallback)."""
        path, fn = self._general_launcher()
        self._record_run(path, steps, state)
        return fn(state, steps, dt)

    def _general_launcher(self):
        """The non-flat dispatch: boxed, dense, or the general
        gather-path fori_loop (built on first use)."""
        if getattr(self, "_boxed_run", None) is not None:
            return "boxed", self._boxed_run
        if getattr(self, "_dense_run", None) is not None:
            return "dense", self._dense_run
        if not hasattr(self, "_run"):
            self._run = self._build_general_run()
        return ("split" if self.overlap else "general"), self._run

    def _build_general_run(self):
        """The gather-path whole run: ``steps`` of the (split-phase or
        blocking) step in one ``fori_loop``."""
        from ..parallel.exec_cache import (
            record_run_donation,
            run_donate_enabled,
        )

        donate = run_donate_enabled()

        def probe_wrap(dispatch):
            """Measure donation effectiveness per dispatch via the
            ``is_deleted`` probe, like the ensemble's stacked-state
            donation path."""
            if not donate:
                return dispatch

            def wrapped(state, steps, dt):
                probe = state["density"]
                out = dispatch(state, steps, dt)
                record_run_donation("advection", probe)
                return out

            return wrapped

        if getattr(self, "_split_fn", None) is not None:
            inner = self._split_fn

            def build():
                def run_fn(rings, ti, to, local, state, steps, dt):
                    return jax.lax.fori_loop(
                        0, steps,
                        lambda i, st: inner(rings, ti, to, local, st, dt),
                        state,
                    )

                # state is positional arg 4; donation joins the
                # cache key so flipping DCCRG_RUN_DONATE re-keys
                return traced_jit(
                    "advection.split_run", run_fn,
                    donate_argnums=(4,) if donate else (),
                )

            fn = self.grid.exec_cache.get(
                self._kernel_key("advection.split_run") + (donate,),
                build,
            )
            args = self._split_args
            return probe_wrap(lambda state, steps, dt: fn(
                *args, state, steps, dt
            ))
        if hasattr(self, "_step_fn"):
            inner = self._step_fn

            def build():
                def run_fn(rings, t, dev, state, steps, dt):
                    return jax.lax.fori_loop(
                        0, steps,
                        lambda i, st: inner(rings, t, dev, st, dt),
                        state,
                    )

                # state is positional arg 3
                return traced_jit(
                    "advection.general_run", run_fn,
                    donate_argnums=(3,) if donate else (),
                )

            fn = self.grid.exec_cache.get(
                self._kernel_key("advection.general_run") + (donate,),
                build,
            )
            rings, t, dev = self._rings, self.tables.tree(), self._dev
            return probe_wrap(lambda state, steps, dt: fn(
                rings, t, dev, state, steps, dt
            ))
        # dense XLA-only path: the step came from the cached dense
        # bundle (plain (state, dt) signature)
        inner = self._step

        def run_fn(state, steps, dt):
            return jax.lax.fori_loop(
                0, steps, lambda i, st: inner(st, dt), state
            )

        return traced_jit("advection.general_run", run_fn)

    def max_time_step(self, state) -> float:
        return float(self._max_dt(state))

    def compute_max_diff(self, state, diff_threshold: float):
        """AMR refinement indicator on whatever layout the model runs
        (dense shifted-slice or general gather) — no rebuild needed to
        decide adaptation, matching the reference running its indicator on
        the solver's own data (adapter.hpp:71-110)."""
        return self._max_diff(state, diff_threshold)

    # --------------------------------------------------------- AMR driver

    def check_for_adaptation(
        self,
        state,
        diff_increase: float = 0.025,
        diff_threshold: float = 0.25,
        unrefine_sensitivity: float = 0.5,
    ):
        """The reference's adaptation criterion (adapter.hpp:47-178): refine
        where the max relative density difference to face neighbors exceeds
        (level+1)*diff_increase, unrefine where it falls below
        unrefine_sensitivity times that; queues requests on the grid."""
        grid = self.grid
        if grid.mapping.max_refinement_level == 0:
            return state
        state = self.compute_max_diff(state, diff_threshold)
        cells = grid.get_cells()
        md = self.get_cell_data(state, "max_diff", cells)
        lvl = grid.mapping.get_refinement_level(cells)
        refine_diff = (lvl + 1) * diff_increase
        unrefine_diff = unrefine_sensitivity * refine_diff
        # bulk request storms (grid.py: identical queue state to the
        # scalar per-cell calls, vectorized)
        grid.refine_completely_many(cells[md > refine_diff])
        hold = (md <= refine_diff) & (md >= unrefine_diff)
        grid.dont_unrefine_many(cells[hold & (lvl > 0)])
        grid.unrefine_completely_many(cells[(md < unrefine_diff) & (lvl > 0)])
        return state

    def adapt_grid(self, state):
        """Commit queued adaptation and carry the state over: children
        inherit the parent's density, new parents average their children
        (adapter.hpp:230-292); velocities are re-derived from the rotation
        field at the new cell centers (adapter.hpp:300-310).  Returns a NEW
        Advection bound to the new grid structure plus the remapped state."""
        grid = self.grid
        if self.dense is not None:
            # decide from the GLOBAL queues: another controller may have
            # queued requests this process hasn't seen (sync is idempotent
            # and called symmetrically on every process)
            from ..utils.collectives import sync_adaptation

            sync_adaptation(grid.amr)
            if not (grid.amr.to_refine or grid.amr.to_unrefine):
                # nothing queued anywhere: the grid stays uniform, the
                # (empty) commit keeps the current epoch, and this model —
                # dense tables, jitted kernels and all — remains valid; a
                # no-op adapt cycle must not degrade or recompile anything
                new_cells = grid.stop_refining(presynced=True)
                return self, state, new_cells, grid.get_removed_cells()
            # the dense z-slab layout is about to stop existing (the grid
            # refines): convert to the row layout remap_state speaks,
            # while the pre-commit epoch is still current
            state = self._dense_to_rows(state)
            new_cells = grid.stop_refining(presynced=True)
        else:
            new_cells = grid.stop_refining()
        removed = grid.get_removed_cells()
        state = grid.remap_state(
            state,
            policy={
                "density": {"refine": "inherit", "unrefine": "mean"},
                "flux": {"refine": "zero", "unrefine": "zero"},
                "max_diff": {"refine": "zero", "unrefine": "zero"},
            },
        )
        adv = Advection(grid, self.hood_id, self.dtype, allow_dense=False)
        cells = grid.get_cells()
        centers = grid.geometry.get_center(cells)
        state = grid.set_cell_data(state, "vx", cells, -centers[:, 1] + 0.5)
        state = grid.set_cell_data(state, "vy", cells, centers[:, 0] - 0.5)
        state = grid.set_cell_data(state, "vz", cells, np.zeros(len(cells)))
        state = adv._exchange(state)
        return adv, state, new_cells, removed

    def total_mass(self, state) -> float:
        if self.dense is not None:
            return float(fetch(state["density"], dtype=np.float64).sum() * self._vol)
        rho = fetch(state["density"])
        vol = 1.0 / np.where(self.inv_volume > 0, self.inv_volume, np.inf)
        local = np.asarray(self.tables.local_mask)
        return float((rho * vol * local).sum())
