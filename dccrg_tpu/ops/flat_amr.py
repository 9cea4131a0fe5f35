"""Whole-run fused kernel for two-level AMR advection on a flat inflated
grid — the VMEM-resident counterpart of the boxed per-level path
(``models/boxed_advection.py``).

Scheme: replicate every level-0 (coarse) leaf onto its 2x2x2 block of
level-1 voxels, giving ONE dense array ``V`` at level-1 resolution over
the whole domain.  Every face the reference prices (``solve.hpp:129-260``
semantics) then appears as voxel pairs of ``V``:

* fine-fine faces — one voxel pair, face velocity = plain average;
* coarse-fine faces — one voxel pair per fine sub-face (exactly how the
  reference iterates the 4 finer neighbors across a coarse face), face
  velocity = the 2:1 length-weighted mix ``(2 v_fine + v_coarse)/3``
  (``solve.hpp:168-175`` with ``nl == 2 cl``);
* coarse-coarse faces — 4 voxel pairs carrying identical replicated
  values and velocities, each weighted by a quarter of the coarse face
  area (which equals the fine face area), so their sum reproduces the
  single coarse flux exactly;
* intra-block pairs (inside one replicated coarse cell) — weight 0.

Because the upwind side is fixed by the (loop-invariant) face velocity,
the flux needs no select at all: with ``w+ = w·[v_face >= 0]`` and
``w- = w·[v_face < 0]`` precomputed per voxel face,
``F = V·w+ + roll(V,-1)·w-``.  The coarse update is a roll-chain 2x2x2
block sum (pool) masked to block origins, then a roll-chain broadcast
back over the block — all of it rolls/multiplies/adds, the same op set
as the uniform whole-block kernel (``dense_advection.make_fused_run``),
so the entire multi-step AMR run executes in one kernel launch with
every array resident in VMEM and zero HBM traffic between steps.

Periodic boundaries are the rolls themselves (the array covers the whole
domain); non-periodic wrap faces get weight 0.  Single device,
levels ⊆ {0, 1}, f32.  Compute cost is ~(inflation factor) more
voxel-updates than true leaves — the price of losing every gather,
concat, and kernel-launch boundary of the boxed path.
"""
from __future__ import annotations

import jax
from jax import shard_map
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from ..parallel.exec_cache import traced_jit
from .dense_advection import _make_rolls

__all__ = [
    "build_flat_amr_tables",
    "make_flat_amr_run",
    "flat_amr_fits",
    "flat_voxel_layout",
    "build_flat_amr_sharded",
    "make_flat_amr_run_sharded",
    "build_flat_ml_tables",
    "make_flat_ml_run",
    "make_flat_ml_run_pallas",
    "compute_flat_ml_weights",
    "flat_ml_kernel_fits",
    "pad_lane_extent",
]

#: VMEM cap: ~18 resident arrays (ping/pong state, 6 weights, 2 update
#: masks, temporaries) — see make_fused_run's budget reasoning
_FLAT_VMEM_BUDGET = 96 * 1024 * 1024
_FLAT_ARRAYS = 18


def flat_amr_fits(n_voxels: int) -> bool:
    return _FLAT_ARRAYS * n_voxels * 4 <= _FLAT_VMEM_BUDGET


#: TPU vector lane width: the last-dim extent Mosaic tiles registers by
_LANE = 128


def pad_extent(n: int, unit: int, max_factor: float = 1.5) -> int:
    """Physical extent for a tile-padded kernel axis: the smallest
    multiple of ``unit`` holding ``n`` real positions plus the two halo
    positions the periodic wrap needs.  An extent that is not
    tile-aligned makes Mosaic pad every register to the tile anyway AND
    lowers the per-step rolls as unaligned shuffles — so when the memory
    cost is modest (``<= max_factor * n``) spending the pad explicitly
    buys aligned rolls.  Returns ``n`` unchanged when already aligned or
    when padding would inflate memory beyond ``max_factor``."""
    if n % unit == 0:
        return n
    np_ = ((n + 2 + unit - 1) // unit) * unit
    return np_ if np_ <= max_factor * n else n


def pad_lane_extent(nx1: int, max_factor: float = 1.5) -> int:
    """:func:`pad_extent` for the 128-lane (last) axis."""
    return pad_extent(nx1, _LANE, max_factor)


def flat_voxel_layout(grid, allow_uniform=False, max_voxels=None,
                      allow_multi_device=False, max_vl=1):
    """The shared flat voxel layout, or None if the grid does not qualify
    (Cartesian, leaf levels ⊆ [0, max_vl]; single device unless
    ``allow_multi_device`` and the ownership equals the voxel z-slab
    partition with coarse blocks never straddling slabs).

    Returns a dict:
      shape        (nzv, nyv, nxv) voxel grid at max-leaf-level resolution
      vox_level    max leaf level (0 = uniform)
      n_devices    D
      leaf_idx     (n_vox,) int32 global leaf index per voxel (coarser
                   leaves replicated over their 2^d x 2^d x 2^d block)
      leaf_level   (nzv, nyv, nxv) int32 — owning leaf's refinement level
      leaf_fine    (nzv, nyv, nxv) bool — voxel is a max-level leaf
      rows         D == 1: (n_vox,) int32 epoch row per voxel;
                   D > 1:  (D, n_vox_loc) int32 per-device epoch rows of
                   the device's z-slab voxels
      wb_rows      D == 1: (R,) int32 — representative flat voxel per
                   epoch row (fine: its voxel; coarse: block origin);
                   D > 1: (D, R) slab-local flat voxel per row.  Scratch
                   and invalid rows point at voxel 0
      wb_valid     (R,) / (D, R) bool
    """

    epoch = grid.epoch
    D = epoch.n_devices
    if D != 1 and not allow_multi_device:
        return None
    if not getattr(grid.geometry, "uniform_level0", False):
        return None
    mapping = epoch.mapping
    leaves = epoch.leaves
    N = len(leaves)
    if N == 0:
        return None
    lvl = mapping.get_refinement_level(leaves.cells).astype(np.int64)
    vl = int(lvl.max())
    if vl > max_vl or (vl == 0 and not allow_uniform):
        return None
    L = mapping.max_refinement_level
    nxv, nyv, nzv = (int(v) << vl for v in mapping.length)
    n_vox = nxv * nyv * nzv
    if max_voxels is not None and n_vox > max_voxels:
        return None

    idx = mapping.get_indices(leaves.cells).astype(np.int64)  # (N,3) x,y,z
    vox = idx >> (L - vl)                # voxel-resolution origin
    flat0 = (vox[:, 2] * nyv + vox[:, 1]) * nxv + vox[:, 0]

    if D > 1:
        if nzv % D != 0:
            return None
        slab = nzv // D
        if vl > 0 and slab % (1 << vl) != 0:
            return None  # coarse blocks would straddle slab boundaries
        owner_expected = (vox[:, 2] // slab).astype(leaves.owner.dtype)
        if not np.array_equal(leaves.owner, owner_expected):
            return None

    leaf_idx = np.zeros(n_vox, dtype=np.int32)
    leaf_level = np.zeros(n_vox, dtype=np.int32)
    leaf_fine = np.zeros(n_vox, dtype=bool)
    fine = lvl == vl
    lin = np.arange(N, dtype=np.int32)
    leaf_idx[flat0[fine]] = lin[fine]
    leaf_level[flat0[fine]] = vl
    leaf_fine[flat0[fine]] = True
    for l in range(vl):
        sel = np.flatnonzero(lvl == l)
        if not len(sel):
            continue
        B = 1 << (vl - l)
        dz, dy, dx = np.meshgrid(
            np.arange(B), np.arange(B), np.arange(B), indexing="ij"
        )
        off = ((dz.ravel() * nyv + dy.ravel()) * nxv + dx.ravel())
        tgt = flat0[sel][:, None] + off[None, :]
        leaf_idx[tgt] = lin[sel][:, None]
        leaf_level[tgt] = l

    R = epoch.R
    row_of = epoch.row_of
    if D == 1:
        rows = row_of[leaf_idx].astype(np.int32)
        wb_rows = np.zeros(R, dtype=np.int32)
        wb_valid = np.zeros(R, dtype=bool)
        wb_rows[row_of] = flat0
        wb_valid[row_of] = True
    else:
        slab = nzv // D
        n_loc = slab * nyv * nxv
        rows = (
            row_of[leaf_idx].astype(np.int32).reshape(D, n_loc)
        )
        wb_rows = np.zeros((D, R), dtype=np.int32)
        wb_valid = np.zeros((D, R), dtype=bool)
        dev = leaves.owner.astype(np.int64)
        loc0 = flat0 - dev * n_loc
        wb_rows[dev, row_of] = loc0
        wb_valid[dev, row_of] = True

    return dict(
        shape=(nzv, nyv, nxv),
        vox_level=vl,
        n_devices=D,
        leaf_idx=leaf_idx,
        leaf_level=leaf_level.reshape(nzv, nyv, nxv),
        leaf_fine=leaf_fine.reshape(nzv, nyv, nxv),
        rows=rows,
        wb_rows=wb_rows,
        wb_valid=wb_valid,
    )


def build_flat_amr_tables(grid):
    """Static tables for the flat advection layout, or None if the grid
    does not qualify (the shared layout's rules, plus: some refinement —
    uniform grids take the dense path — and VMEM fit).

    Adds to :func:`flat_voxel_layout`: area_f, vol_f, vol_c, periodic.
    """
    lay = flat_voxel_layout(
        grid,
        allow_uniform=False,
        max_voxels=_FLAT_VMEM_BUDGET // (_FLAT_ARRAYS * 4),
    )
    if lay is None:
        return None
    if lay["leaf_fine"].all():
        return None  # every leaf refined: no coarse level, boxed handles it

    l1 = np.asarray(grid.geometry.get_level_0_cell_length(), np.float64) / 2.0
    return dict(
        lay,
        area_f=np.array([l1[1] * l1[2], l1[0] * l1[2], l1[0] * l1[1]]),
        vol_f=float(l1.prod()),
        vol_c=float(l1.prod() * 8.0),
        periodic=tuple(bool(grid.topology.is_periodic(d)) for d in range(3)),
    )


def _face_weights(vl, vh, fl, fh, pos, area_d, dtype, extra_invalid=None):
    """Signed upwind weight pair for the faces pairing (low, high) voxel
    planes: face velocity with the reference's 2:1 length weighting
    (``solve.hpp:168-175``), intra-coarse-block pairs (low side at even
    position) carry no face, ``extra_invalid`` masks e.g. non-periodic
    wrap faces.  Shared by the single-device kernel weights and the
    sharded run so the numerics cannot drift apart."""
    third = dtype(1.0 / 3.0)
    vface = jnp.where(
        fl == fh,
        dtype(0.5) * (vl + vh),               # same-kind: plain average
        jnp.where(
            fl,                                # fine low, coarse high
            (dtype(2.0) * vl + vh) * third,
            (vl + dtype(2.0) * vh) * third,
        ),
    )
    valid = ~((~fl) & (~fh) & (pos % 2 == 0))
    if extra_invalid is not None:
        valid = valid & ~extra_invalid
    w = jnp.where(valid, vface * dtype(area_d), dtype(0.0))
    wp = jnp.where(vface >= 0, w, dtype(0.0))
    return wp, w - wp


def compute_flat_weights(tables, VX, VY, VZ, dtype=jnp.float32):
    """Per-voxel-face upwind weights (jittable; velocities are run inputs
    but loop-invariant, so this runs once per run call).

    For each axis d the face above voxel p pairs (p, p+e_d).  Returns
    ``(wp, wn)`` per axis with ``F = V*wp + roll(V,-1,ax)*wn`` the signed
    outgoing flux (no dt; both consumers — make_flat_amr_run's wrapper
    and the sharded XLA body — premultiply dt into these weight arrays,
    the shared association that keeps the two forms rounding
    identically)."""
    nz1, ny1, nx1 = tables["shape"]
    leaf = jnp.asarray(tables["leaf_fine"])
    area = tables["area_f"]
    periodic = tables["periodic"]
    vels = (VX, VY, VZ)
    out = []
    for d in range(3):
        ax = 2 - d
        n = (nx1, ny1, nz1)[d]
        v = vels[d].astype(dtype)
        pos = jax.lax.broadcasted_iota(jnp.int32, (nz1, ny1, nx1), ax)
        extra = None if periodic[d] else (pos == n - 1)
        out.append(_face_weights(
            v, jnp.roll(v, -1, ax), leaf, jnp.roll(leaf, -1, ax),
            pos, area[d], dtype, extra,
        ))
    return out


def make_flat_amr_run(nz1: int, ny1: int, nx1: int, *,
                      nx_pad: int | None = None,
                      interpret: bool = False):
    """Returns ``run(V, wpx, wnx, wpy, wny, wpz, wnz, upd_f, upd_c, dt,
    steps) -> V'`` advancing the flat two-level grid ``steps`` timesteps
    in one kernel launch (ping-pong scratch, runtime step count — the
    same shell as ``make_fused_run``).

    ``upd_f = leaf_fine/vol_f`` and ``upd_c = (~leaf_fine)/vol_c`` fold
    the level-dependent volume division into per-voxel constants; the run
    wrapper premultiplies ``dt`` into the six face-weight arrays outside
    the kernel (``dt*v_face*area`` is the per-face swept volume — the
    same order of magnitude as the cell volume under CFL, so the
    premultiply never drives intermediates toward the f32 subnormal
    range the way scaling the ~1/vol update constants would).

    ``nx_pad`` (from :func:`pad_lane_extent`): physical lane extent.
    When larger than ``nx1``, the arrays carry ``nx_pad - nx1`` extra x
    columns so every x roll is lane-aligned: column ``nx1`` is a +x halo
    holding column 0's value and column ``nx_pad-1`` is a -x halo holding
    column ``nx1-1``'s, so the two wrap-face fluxes read the same operand
    values as the unpadded rolls and the update stays BIT-identical;
    interior pad columns carry weight 0 everywhere and never update.  The
    halo columns are refreshed at the end of each step (two lane-slice
    selects — noise next to the 12 rolls they align).  The wrapper takes
    and returns unpadded arrays either way.

    VMEM discipline: weight/mask refs are read inside the step body (the
    reads are transient stack temporaries the allocator reuses) rather
    than hoisted into loop-carried copies — hoisting all six weight
    arrays pushed the scoped-VMEM stack past the 96 MiB default on a
    96^3 voxel grid and forced spills."""
    roll_m1, roll_p1 = _make_rolls(interpret)
    nxp = nx1 if nx_pad is None else int(nx_pad)
    if nxp != nx1 and nxp < nx1 + 2:
        raise ValueError("nx_pad must leave room for the two halo columns")
    padded = nxp != nx1

    def kernel(steps_ref, v_ref, wpx, wnx, wpy, wny, wpz, wnz,
               updf_ref, updc_ref, out_ref, scr_ref):
        steps = steps_ref[0]
        # pool mask = coarse voxels; the roll-chain pool below must only
        # sum coarse deltas, so mask with (updc != 0) — exact since updc
        # is 0 or 1/vol_c (pad columns: 0, so pads never pool)
        pool = (updc_ref[...] != 0).astype(jnp.float32)

        def one_step(src_ref, dst_ref):
            v = src_ref[...]
            fx = v * wpx[...] + roll_m1(v, 2) * wnx[...]
            delta = roll_p1(fx, 2) - fx
            fy = v * wpy[...] + roll_m1(v, 1) * wny[...]
            delta = delta + roll_p1(fy, 1) - fy
            fz = v * wpz[...] + roll_m1(v, 0) * wnz[...]
            delta = delta + roll_p1(fz, 0) - fz
            # 2x2x2 block sum of coarse deltas at block origins: blocks
            # are even-aligned, so the -1-roll chain puts sum_{e in
            # {0,1}^3} s[p+e] at p, correct exactly at origins
            s = delta * pool
            s = s + roll_m1(s, 2)
            s = s + roll_m1(s, 1)
            s = s + roll_m1(s, 0)
            # keep origins only (origin = even position on every axis AND
            # coarse: updc masks fine leaves later; zero odd positions —
            # and, when padded, never a pad column: the -1 x roll above
            # wraps s[0] into the last pad column)
            s = s * orig
            # broadcast origin values over their blocks: non-origin
            # positions hold 0, so b += roll(+1) duplicates along each
            # axis without selects
            s = s + roll_p1(s, 2)
            s = s + roll_p1(s, 1)
            s = s + roll_p1(s, 0)
            res = v + delta * updf_ref[...] + s * updc_ref[...]
            if padded:
                # refresh the two wrap halo columns from this step's result
                res = jnp.where(xi == nx1, res[:, :, 0:1], res)
                res = jnp.where(xi == nxp - 1, res[:, :, nx1 - 1:nx1], res)
            dst_ref[...] = res

        # origin parity mask, built once from iota (static shapes)
        ex = jax.lax.broadcasted_iota(jnp.int32, (nz1, ny1, nxp), 2) % 2 == 0
        ey = jax.lax.broadcasted_iota(jnp.int32, (nz1, ny1, nxp), 1) % 2 == 0
        ez = jax.lax.broadcasted_iota(jnp.int32, (nz1, ny1, nxp), 0) % 2 == 0
        orig = (ex & ey & ez).astype(jnp.float32)
        if padded:
            xi = jax.lax.broadcasted_iota(jnp.int32, (nz1, ny1, nxp), 2)
            orig = orig * (xi < nx1).astype(jnp.float32)

        out_ref[...] = v_ref[...]

        def body(i, _):
            even = (i % 2) == 0

            @pl.when(even)
            def _():
                one_step(out_ref, scr_ref)

            @pl.when(jnp.logical_not(even))
            def _():
                one_step(scr_ref, out_ref)

            return 0

        jax.lax.fori_loop(0, steps, body, 0)

        @pl.when((steps % 2) == 1)
        def _():
            out_ref[...] = scr_ref[...]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_FLAT_VMEM_BUDGET
        )
    call = pl.pallas_call(
        kernel,
        name="advection_flat_run",
        in_specs=[smem] + [vmem] * 9,
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((nz1, ny1, nxp), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((nz1, ny1, nxp), jnp.float32),
        interpret=interpret,
        **kwargs,
    )

    def _embed(a, lo=None, hi=None):
        """Pad ``a`` to nxp x columns: zeros, except column nx1 = ``lo``
        and column nxp-1 = ``hi`` when given (lane slices of ``a``)."""
        z = jnp.zeros((nz1, ny1, nxp - nx1), a.dtype)
        if lo is not None:
            z = z.at[:, :, 0:1].set(lo)
        if hi is not None:
            z = z.at[:, :, -1:].set(hi)
        return jnp.concatenate([a, z], axis=2)

    def run(V, wpx, wnx, wpy, wny, wpz, wnz, upd_f, upd_c, dt, steps):
        dt = jnp.asarray(dt, jnp.float32)
        steps_arr = jnp.asarray(steps, jnp.int32).reshape(1)
        args = (V, wpx * dt, wnx * dt, wpy * dt, wny * dt,
                wpz * dt, wnz * dt, upd_f, upd_c)
        if padded:
            V, wpx, wnx, wpy, wny, wpz, wnz, upd_f, upd_c = args
            # x-face weights: the wrap face's weight sits at column nx1-1
            # (pairing it with the +x halo) AND at column nxp-1 (pairing
            # the -x halo with column 0 via the aligned roll wrap) — each
            # copy feeds a different cell's delta, exactly the two reads
            # the unpadded roll pair makes of the single wrap face
            args = (
                _embed(V, lo=V[:, :, 0:1], hi=V[:, :, nx1 - 1:nx1]),
                _embed(wpx, hi=wpx[:, :, nx1 - 1:nx1]),
                _embed(wnx, hi=wnx[:, :, nx1 - 1:nx1]),
                _embed(wpy), _embed(wny), _embed(wpz), _embed(wnz),
                _embed(upd_f), _embed(upd_c),
            )
        out = call(steps_arr, *args)
        return out[:, :, :nx1] if padded else out

    return run


def build_flat_amr_sharded(grid):
    """Multi-device flat layout: the level-1-resolution domain z-slab
    sharded over the mesh, one slab per device — the multi-chip form of
    the flat scheme, with the per-step halo two ppermuted voxel planes
    (the same wire pattern as the uniform dense path).

    Requires the shared layout's multi-device rules (levels {0, 1} with
    refinement, Cartesian, slabs holding whole coarse blocks, ownership
    equal to the voxel-slab partition).  Returns the static tables dict
    or None."""
    epoch = grid.epoch
    D = epoch.n_devices
    if D == 1:
        return None
    lay = flat_voxel_layout(grid, allow_uniform=False,
                            allow_multi_device=True)
    if lay is None or lay["leaf_fine"].all():
        return None
    nz1, ny1, nx1 = lay["shape"]
    nzl1 = nz1 // D
    n_loc = nzl1 * ny1 * nx1
    n_vox = nz1 * ny1 * nx1
    N = len(epoch.leaves)
    # cost guards (mirroring the boxed path's max_expand and the
    # single-device flat_amr_fits): the 8x inflation must stay within a
    # modest factor of the real leaf count, and the ~12 per-device
    # voxel-resolution arrays must fit comfortably in HBM — otherwise the
    # boxed path (cost proportional to real leaves) is the better choice
    if n_vox > max(8 * N, 1 << 22):
        return None
    if 12 * n_loc * 4 > (2 << 30):
        return None

    # ringed leaf mask: the z-neighbor devices' edge planes (static data
    # needs no collective — build it globally and slice)
    lf_global = lay["leaf_fine"]
    leaf_ext = np.stack([
        np.concatenate([
            lf_global[(d * nzl1 - 1) % nz1][None],
            lf_global[d * nzl1:(d + 1) * nzl1],
            lf_global[((d + 1) * nzl1) % nz1][None],
        ])
        for d in range(D)
    ])

    l1 = np.asarray(grid.geometry.get_level_0_cell_length(), np.float64) / 2.0
    return dict(
        shape=(nzl1, ny1, nx1),
        n_devices=D,
        rows=lay["rows"],
        leaf_fine=lf_global.reshape(D, nzl1, ny1, nx1),
        leaf_ext=leaf_ext,
        wb_rows=lay["wb_rows"],
        wb_valid=lay["wb_valid"],
        area_f=np.array([l1[1] * l1[2], l1[0] * l1[2], l1[0] * l1[1]]),
        vol_f=float(l1.prod()),
        vol_c=float(l1.prod() * 8.0),
        periodic=tuple(bool(grid.topology.is_periodic(d)) for d in range(3)),
    )


def make_flat_amr_run_sharded(grid, tables, dtype=jnp.float32):
    """The jitted multi-device flat run: one shard_map around the whole
    fori_loop; per step two ppermuted voxel planes and one weighted flux
    pass + intra-slab pool/broadcast (coarse blocks never straddle slabs,
    so the coarse update is collective-free).  Weight arrays are computed
    once per run from the (ringed) velocity fields."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.dense import HaloExtend
    from ..parallel.mesh import SHARD_AXIS, put_table, shard_spec

    nzl1, ny1, nx1 = tables["shape"]
    D = tables["n_devices"]
    px, py, pz = tables["periodic"]
    area = tables["area_f"]
    inv_vf = dtype(1.0 / tables["vol_f"])
    inv_vc = dtype(1.0 / tables["vol_c"])
    mesh = grid.mesh
    ring = HaloExtend(D)

    def body(rows, leaf, leaf_ext, wbr, wbv, rho_rows, vx_rows, vy_rows,
             vz_rows, dt, steps):
        rows, leaf, leaf_ext = rows[0], leaf[0], leaf_ext[0]
        wbr, wbv = wbr[0], wbv[0]
        dev = jax.lax.axis_index(SHARD_AXIS)

        def field(arr_rows):
            return arr_rows[0][rows].reshape(nzl1, ny1, nx1).astype(dtype)

        V = field(rho_rows)
        VX, VY, VZ = field(vx_rows), field(vy_rows), field(vz_rows)

        # ---- x/y weights via the shared helper (full-domain extents,
        # rolls = wrap)
        w_xy = []
        for d2, vel, n in ((0, VX, nx1), (1, VY, ny1)):
            ax = 2 - d2
            pos = jax.lax.broadcasted_iota(jnp.int32, (nzl1, ny1, nx1), ax)
            periodic_d = px if d2 == 0 else py
            extra = None if periodic_d else (pos == n - 1)
            w_xy.append(_face_weights(
                vel, jnp.roll(vel, -1, ax), leaf, jnp.roll(leaf, -1, ax),
                pos, area[d2], dtype, extra,
            ))
        (wpx, wnx), (wpy, wny) = w_xy

        # ---- z weights on the nzl1+1 faces of the ringed slab: face j
        # pairs ext planes (j, j+1); global face index dev*nzl1 - 1 + j
        # (the shared helper's parity mask needs the GLOBAL position)
        below_v, above_v = ring.planes(VZ)
        VZe = jnp.concatenate([below_v, VZ, above_v], axis=0)
        gface = (
            dev * nzl1 - 1
            + jax.lax.broadcasted_iota(jnp.int32, (nzl1 + 1, ny1, nx1), 0)
        )
        extra_z = (
            None if pz else (gface == -1) | (gface == D * nzl1 - 1)
        )
        wzp, wzn = _face_weights(
            VZe[:-1], VZe[1:], leaf_ext[:-1], leaf_ext[1:],
            gface, area[2], dtype, extra_z,
        )

        # premultiply dt into the face weights — the same association the
        # single-device Pallas wrapper uses, so both forms round
        # identically step for step
        dtc = jnp.asarray(dt, dtype)
        wpx, wnx = wpx * dtc, wnx * dtc
        wpy, wny = wpy * dtc, wny * dtc
        wzp, wzn = wzp * dtc, wzn * dtc

        # ---- static update masks
        updf = leaf.astype(dtype) * inv_vf
        pool = (~leaf).astype(dtype)
        updc = pool * inv_vc
        ex = jax.lax.broadcasted_iota(jnp.int32, (nzl1, ny1, nx1), 2) % 2 == 0
        ey = jax.lax.broadcasted_iota(jnp.int32, (nzl1, ny1, nx1), 1) % 2 == 0
        ez = jax.lax.broadcasted_iota(jnp.int32, (nzl1, ny1, nx1), 0) % 2 == 0
        orig = (ex & ey & ez).astype(dtype)

        def one(i, Vc):
            fx = Vc * wpx + jnp.roll(Vc, -1, 2) * wnx
            fy = Vc * wpy + jnp.roll(Vc, -1, 1) * wny
            below, above = ring.planes(Vc)
            Ve = jnp.concatenate([below, Vc, above], axis=0)
            fz_faces = Ve[:-1] * wzp + Ve[1:] * wzn      # (nzl1+1, ...)
            delta = jnp.roll(fx, 1, 2) - fx
            delta = delta + jnp.roll(fy, 1, 1) - fy
            delta = delta + fz_faces[:-1] - fz_faces[1:]
            s = delta * pool
            s = s + jnp.roll(s, -1, 2)
            s = s + jnp.roll(s, -1, 1)
            s = s + jnp.roll(s, -1, 0)
            s = s * orig
            s = s + jnp.roll(s, 1, 2)
            s = s + jnp.roll(s, 1, 1)
            s = s + jnp.roll(s, 1, 0)
            return Vc + (delta * updf + s * updc)

        out = jax.lax.fori_loop(0, steps, one, V)
        rho = jnp.where(wbv, out.reshape(-1)[wbr], rho_rows[0])
        return rho[None]

    data_spec = P(SHARD_AXIS)
    spec2 = P(SHARD_AXIS, None)
    spec4 = P(SHARD_AXIS, None, None, None)
    sm = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec2, spec4, spec4, spec2, spec2,
                  data_spec, data_spec, data_spec, data_spec, P(), P()),
        out_specs=data_spec,
        check_vma=False,
    )

    # the Tables seam (parallel/mesh.put_table): sharded device arrays
    # under one controller, host numpy under many — the tables enter
    # the jitted body as RUNTIME arguments (same-shape tables share one
    # executable; closing over arrays spanning other processes' devices
    # is rejected by JAX)
    statics = tuple(put_table(tables[k], mesh) for k in
                    ("rows", "leaf_fine", "leaf_ext", "wb_rows", "wb_valid"))

    def run_impl(statics_arg, state, steps, dt):
        rho = sm(
            *statics_arg,
            state["density"], state["vx"], state["vy"], state["vz"],
            jnp.asarray(dt, dtype), jnp.asarray(steps, jnp.int32),
        )
        return {
            **state,
            "density": rho.astype(state["density"].dtype),
            "flux": jnp.zeros_like(state["flux"]),
        }

    run_impl = traced_jit("advection.flat_run", run_impl)

    def run_fn(state, steps, dt):
        return run_impl(statics, state, steps, dt)

    return run_fn


# --------------------------------------------------------- multi-level

#: deepest leaf level the multi-level flat scheme inflates to: 8^4 voxel
#: inflation of a level-0 leaf is already past any sensible budget, and
#: the reference's own AMR workloads live at 2-4 levels
_ML_MAX_VL = 4


def build_flat_ml_tables(grid):
    """Multi-level flat layout (3+ leaf levels) for the XLA whole-run
    form, or None when the grid does not qualify — the VERDICT-r4
    extension of the two-level flat scheme past levels {0, 1}
    (reference AMR allows 21 levels, ``dccrg_mapping.hpp:316-329``).

    Same inflated-voxel idea as the two-level scheme: every leaf is
    replicated over its 2^d-cube of finest-level voxels, faces become
    voxel pairs with the reference's length-weighted face velocities
    (adjacent leaves differ by at most one level under 2:1 balance, so
    the two-point mix covers every face), and each coarse leaf's update
    is the block sum of its voxel deltas over its own volume.  The
    block sums run down a reshape pyramid (one 2x2x2 reduction per
    level doubling — contiguous reductions, far cheaper than shifted
    copies), each level's leaves are captured at their own reduced
    resolution, and the accumulated coarse updates broadcast back up
    one doubling at a time — so the whole multi-step run stays one
    fused XLA dispatch (single device or z-slab sharded; slabs hold
    whole coarse blocks so pooling is collective-free)."""
    epoch = grid.epoch
    D = epoch.n_devices
    if len(epoch.leaves) == 0:
        return None
    # cheap level screen BEFORE the O(n_vox) layout build: the tuned
    # two-level paths own levels {0, 1}, so a 2-level grid must not pay
    # for (and then discard) the inflated layout here
    vl = int(
        epoch.mapping.get_refinement_level(epoch.leaves.cells).max()
    )
    if vl < 2:
        return None
    lay = flat_voxel_layout(grid, allow_uniform=False,
                            allow_multi_device=True, max_vl=_ML_MAX_VL)
    if lay is None:
        return None
    nzv, nyv, nxv = lay["shape"]
    nzl = nzv // D
    n_vox = nzv * nyv * nxv
    N = len(epoch.leaves)
    # cost guards: inflation within a modest factor of the real leaf
    # count, per-device residency within HBM comfort
    if n_vox > max(16 * N, 1 << 22):
        return None
    if 14 * (n_vox // D) * 4 > (2 << 30):
        return None

    lev = lay["leaf_level"]                         # (nzv, nyv, nxv)
    lidx = lay["leaf_idx"].reshape(nzv, nyv, nxv)

    def ringed(a):
        """Per-device slab with the z-neighbor devices' edge planes."""
        return np.stack([
            np.concatenate([
                a[(d * nzl - 1) % nzv][None],
                a[d * nzl:(d + 1) * nzl],
                a[((d + 1) * nzl) % nzv][None],
            ])
            for d in range(D)
        ])

    rows = lay["rows"]
    wb_rows, wb_valid = lay["wb_rows"], lay["wb_valid"]
    if D == 1:
        rows = rows[None, :]
        wb_rows = wb_rows[None, :]
        wb_valid = wb_valid[None, :]

    l0 = np.asarray(grid.geometry.get_level_0_cell_length(), np.float64)
    lf = l0 / (1 << vl)                             # finest cell lengths
    vol_f = float(lf.prod())

    # static per-voxel update tables (slab-local)
    lev_loc = lev.reshape(D, nzl, nyv, nxv)
    # volume tables in f64: the run casts them to ITS dtype, so an f64
    # run must not inherit f32-quantized inverse volumes (the lf.prod()
    # is a power of two only for power-of-two domain lengths)
    updf = (lev_loc == vl).astype(np.float64) / vol_f
    pool = (lev_loc < vl).astype(np.float64)
    # per-level capture masks at the REDUCED resolution of that level's
    # blocks: the run pools delta down a reshape pyramid, so level
    # vl-1-k's leaves are read at stride 2^(k+1) — a stride-f origin
    # whose leaf level equals l marks exactly that leaf's block (leaves
    # of level l are always aligned to their own block size)
    caps = []
    cap_origin = []
    if D == 1:
        # full-resolution origin masks are only consumed by the
        # single-device Pallas whole-run kernel; sharded grids must not
        # pay vl extra full-resolution f64 arrays for nothing
        zi, yi, xi = np.meshgrid(np.arange(nzl), np.arange(nyv),
                                 np.arange(nxv), indexing="ij")
    for k in range(vl):
        l = vl - 1 - k
        f = 1 << (k + 1)
        lev_red = lev_loc[:, ::f, ::f, ::f]
        inv_vol = 1.0 / (vol_f * float(8 ** (k + 1)))
        caps.append((lev_red == l).astype(np.float64) * inv_vol)
        if D == 1:
            # roll-chain capture points for the Pallas whole-run kernel
            aligned = (zi % f == 0) & (yi % f == 0) & (xi % f == 0)
            cap_origin.append(
                ((lev_loc == l) & aligned[None]).astype(np.float64)
                * inv_vol
            )

    return dict(
        shape=(nzl, nyv, nxv),
        vl=vl,
        n_devices=D,
        rows=rows,
        wb_rows=wb_rows,
        wb_valid=wb_valid,
        lev=lev_loc,
        lev_ext=ringed(lev),
        lidx=lidx.reshape(D, nzl, nyv, nxv),
        lidx_ext=ringed(lidx),
        updf=updf,
        pool=pool,
        caps=caps,
        cap_origin=cap_origin,
        cap_active=[bool(c.any()) for c in caps],
        area_f=np.array([lf[1] * lf[2], lf[0] * lf[2], lf[0] * lf[1]]),
        periodic=tuple(bool(grid.topology.is_periodic(d)) for d in range(3)),
        n_vox=n_vox,
    )


def _face_weights_ml(va, vb, la, lb, ia, ib, area_d, dtype, extra_invalid):
    """Signed upwind weight pair for voxel faces pairing (a, b) planes in
    the multi-level scheme: the reference's length-weighted face velocity
    (``solve.hpp:168-175``; 2:1 balance keeps level differences <= 1 so
    the two-point mix is exact), intra-leaf pairs (same leaf id on both
    sides) carry no face."""
    third = dtype(1.0 / 3.0)
    vface = jnp.where(
        la == lb,
        dtype(0.5) * (va + vb),
        jnp.where(
            la > lb,                      # a finer than b
            (dtype(2.0) * va + vb) * third,
            (va + dtype(2.0) * vb) * third,
        ),
    )
    valid = ia != ib
    if extra_invalid is not None:
        valid = valid & ~extra_invalid
    w = jnp.where(valid, vface * dtype(area_d), dtype(0.0))
    wp = jnp.where(vface >= 0, w, dtype(0.0))
    return wp, w - wp


def make_flat_ml_run(grid, tables, dtype=jnp.float32):
    """The jitted multi-level flat run: one shard_map (D >= 1) around the
    whole fori_loop; per step two ppermuted voxel planes, one weighted
    flux pass, and the reshape-pyramid pool/broadcast for the
    coarse-leaf updates."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.dense import HaloExtend
    from ..parallel.mesh import SHARD_AXIS, put_table

    nzl, nyv, nxv = tables["shape"]
    D = tables["n_devices"]
    vl = tables["vl"]
    px, py, pz = tables["periodic"]
    area = tables["area_f"]
    cap_active = tables["cap_active"]
    # pooling only needs to reach the coarsest level actually present
    kmax = max((k for k in range(vl) if cap_active[k]), default=-1)
    mesh = grid.mesh
    ring = HaloExtend(D)

    def body(rows, lev, lev_ext, lidx, lidx_ext, updf, pool, *rest):
        caps = [c[0] for c in rest[:vl]]
        wbr, wbv = rest[vl][0], rest[vl + 1][0]
        rho_rows, vx_rows, vy_rows, vz_rows, dt, steps = rest[vl + 2:]
        rows, lev, lev_ext = rows[0], lev[0], lev_ext[0]
        lidx, lidx_ext = lidx[0], lidx_ext[0]
        updf, pool = updf[0], pool[0]
        dev = jax.lax.axis_index(SHARD_AXIS)

        def field(arr_rows):
            return arr_rows[0][rows].reshape(nzl, nyv, nxv).astype(dtype)

        V = field(rho_rows)
        VX, VY, VZ = field(vx_rows), field(vy_rows), field(vz_rows)

        # ---- x/y face weights (full extents locally; rolls = wrap)
        w_xy = []
        for d2, vel, n in ((0, VX, nxv), (1, VY, nyv)):
            ax = 2 - d2
            pos = jax.lax.broadcasted_iota(jnp.int32, (nzl, nyv, nxv), ax)
            periodic_d = px if d2 == 0 else py
            extra = None if periodic_d else (pos == n - 1)
            w_xy.append(_face_weights_ml(
                vel, jnp.roll(vel, -1, ax),
                lev, jnp.roll(lev, -1, ax),
                lidx, jnp.roll(lidx, -1, ax),
                area[d2], dtype, extra,
            ))
        (wpx, wnx), (wpy, wny) = w_xy

        # ---- z weights on the nzl+1 ringed faces (global face index
        # dev*nzl - 1 + j for the non-periodic mask)
        below_v, above_v = ring.planes(VZ)
        VZe = jnp.concatenate([below_v, VZ, above_v], axis=0)
        gface = (
            dev * nzl - 1
            + jax.lax.broadcasted_iota(jnp.int32, (nzl + 1, nyv, nxv), 0)
        )
        extra_z = (
            None if pz else (gface == -1) | (gface == D * nzl - 1)
        )
        wzp, wzn = _face_weights_ml(
            VZe[:-1], VZe[1:], lev_ext[:-1], lev_ext[1:],
            lidx_ext[:-1], lidx_ext[1:], area[2], dtype, extra_z,
        )

        dtc = jnp.asarray(dt, dtype)
        wpx, wnx = wpx * dtc, wnx * dtc
        wpy, wny = wpy * dtc, wny * dtc
        wzp, wzn = wzp * dtc, wzn * dtc
        updf_c = updf.astype(dtype)
        pool_c = pool.astype(dtype)
        caps_c = [c.astype(dtype) for c in caps]

        def down2(a):
            nz_, ny_, nx_ = a.shape
            return a.reshape(
                nz_ // 2, 2, ny_ // 2, 2, nx_ // 2, 2
            ).sum(axis=(1, 3, 5))

        def up2(a):
            nz_, ny_, nx_ = a.shape
            return jnp.broadcast_to(
                a[:, None, :, None, :, None], (nz_, 2, ny_, 2, nx_, 2)
            ).reshape(nz_ * 2, ny_ * 2, nx_ * 2)

        def one(i, Vc):
            fx = Vc * wpx + jnp.roll(Vc, -1, 2) * wnx
            fy = Vc * wpy + jnp.roll(Vc, -1, 1) * wny
            below, above = ring.planes(Vc)
            Ve = jnp.concatenate([below, Vc, above], axis=0)
            fz_faces = Ve[:-1] * wzp + Ve[1:] * wzn      # (nzl+1, ...)
            delta = jnp.roll(fx, 1, 2) - fx
            delta = delta + jnp.roll(fy, 1, 1) - fy
            delta = delta + fz_faces[:-1] - fz_faces[1:]
            out_add = delta * updf_c
            if kmax >= 0:
                # reshape pyramid: pooling level k holds exact 2^(k+1)
                # block sums (blocks never straddle slabs since
                # slab % 2^vl == 0); each level's leaves are captured at
                # their own resolution (inv volume folded into the mask)
                # and the accumulated coarse updates are broadcast back
                # up one doubling at a time
                subs = []
                cur = delta * pool_c
                for _k in range(kmax + 1):
                    cur = down2(cur)
                    subs.append(cur)
                acc = None
                for k in range(kmax, -1, -1):
                    if acc is not None:
                        acc = up2(acc)
                    if cap_active[k]:
                        contrib = subs[k] * caps_c[k]
                        acc = contrib if acc is None else acc + contrib
                if acc is not None:
                    out_add = out_add + up2(acc)
            return Vc + out_add

        out = jax.lax.fori_loop(0, steps, one, V)
        rho = jnp.where(wbv, out.reshape(-1)[wbr], rho_rows[0])
        return rho[None]

    data_spec = P(SHARD_AXIS)
    spec2 = P(SHARD_AXIS, None)
    spec4 = P(SHARD_AXIS, None, None, None)
    sm = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec2,) + (spec4,) * 6 + (spec4,) * vl + (spec2, spec2)
        + (data_spec,) * 4 + (P(), P()),
        out_specs=data_spec,
        check_vma=False,
    )

    statics = (
        put_table(tables["rows"], mesh),
        put_table(tables["lev"], mesh),
        put_table(tables["lev_ext"], mesh),
        put_table(tables["lidx"], mesh),
        put_table(tables["lidx_ext"], mesh),
        # volume tables shipped in the RUN dtype (stored f64 so an f64
        # run never sees f32-quantized inverse volumes)
        put_table(tables["updf"], mesh, dtype),
        put_table(tables["pool"], mesh, dtype),
        *(put_table(c, mesh, dtype) for c in tables["caps"]),
        put_table(tables["wb_rows"], mesh),
        put_table(tables["wb_valid"], mesh),
    )

    # tables as runtime args (not closed over): same-shape meshes reuse
    # the executable and multi-controller tables stay legal
    def run_impl(statics_arg, state, steps, dt):
        rho = sm(
            *statics_arg,
            state["density"], state["vx"], state["vy"], state["vz"],
            jnp.asarray(dt, dtype), jnp.asarray(steps, jnp.int32),
        )
        return {
            **state,
            "density": rho.astype(state["density"].dtype),
            "flux": jnp.zeros_like(state["flux"]),
        }

    run_impl = traced_jit("advection.flat_run", run_impl)

    def run_fn(state, steps, dt):
        return run_impl(statics, state, steps, dt)

    return run_fn


def compute_flat_ml_weights(tables, VX, VY, VZ, dtype=jnp.float32):
    """Per-voxel-face upwind weights for the multi-level layout on a
    single device (full-domain rolls = periodic wrap), mirroring the
    sharded body's ringed-face math: level-weighted face velocities and
    intra-leaf masking from the per-voxel leaf levels/ids."""
    nzl, nyv, nxv = tables["shape"]
    assert tables["n_devices"] == 1
    lev = jnp.asarray(tables["lev"][0])
    lidx = jnp.asarray(tables["lidx"][0])
    area = tables["area_f"]
    periodic = tables["periodic"]
    out = []
    for d, vel, n in ((0, VX, nxv), (1, VY, nyv), (2, VZ, nzl)):
        ax = 2 - d
        v = vel.astype(dtype)
        pos = jax.lax.broadcasted_iota(jnp.int32, (nzl, nyv, nxv), ax)
        extra = None if periodic[d] else (pos == n - 1)
        out.append(_face_weights_ml(
            v, jnp.roll(v, -1, ax),
            lev, jnp.roll(lev, -1, ax),
            lidx, jnp.roll(lidx, -1, ax),
            area[d], dtype, extra,
        ))
    return out


def flat_ml_kernel_fits(n_voxels: int, vl: int) -> bool:
    """VMEM budget for the multi-level whole-run kernel: the 2-level
    kernel's ~18 resident arrays plus one capture mask per doubling."""
    return (_FLAT_ARRAYS + vl) * n_voxels * 4 <= _FLAT_VMEM_BUDGET


def make_flat_ml_run_pallas(nz1: int, ny1: int, nx1: int, vl: int,
                            cap_active, *, interpret: bool = False):
    """Whole-run fused Pallas kernel for MULTI-level flat AMR — the
    VMEM-resident counterpart of :func:`make_flat_ml_run` for a single
    device: the entire multi-step loop in one launch, with the coarse
    updates as the hierarchical roll-chain (``pltpu.roll`` takes
    arbitrary shifts, so pooling distance doubles per level).

    Returns ``run(V, wpx, wnx, wpy, wny, wpz, wnz, updf, pool,
    *caps, dt, steps) -> V'`` where ``updf`` folds 1/vol_fine into the
    finest-voxel mask, ``pool`` masks non-finest voxels, and ``caps[k]``
    marks level ``vl-1-k`` leaves' block ORIGINS with 1/vol folded (the
    roll-chain capture points, full resolution)."""
    if interpret:
        roll_m = lambda x, h, a: jnp.roll(x, -h, a)
        roll_p = lambda x, h, a: jnp.roll(x, h, a)
    else:
        roll_m = lambda x, h, a: pltpu.roll(x, x.shape[a] - h, a)
        roll_p = lambda x, h, a: pltpu.roll(x, h, a)
    kmax = max((k for k in range(vl) if cap_active[k]), default=-1)
    n_caps = kmax + 1

    def kernel(steps_ref, v_ref, wpx, wnx, wpy, wny, wpz, wnz,
               updf_ref, pool_ref, *rest):
        cap_refs = rest[:n_caps]
        out_ref, scr_ref = rest[n_caps], rest[n_caps + 1]
        steps = steps_ref[0]

        def one_step(src_ref, dst_ref):
            v = src_ref[...]
            fx = v * wpx[...] + roll_m(v, 1, 2) * wnx[...]
            delta = roll_p(fx, 1, 2) - fx
            fy = v * wpy[...] + roll_m(v, 1, 1) * wny[...]
            delta = delta + roll_p(fy, 1, 1) - fy
            fz = v * wpz[...] + roll_m(v, 1, 0) * wnz[...]
            delta = delta + roll_p(fz, 1, 0) - fz
            res_add = delta * updf_ref[...]
            # hierarchical pool: after step k, position p holds the sum
            # of s over its 2^(k+1)-cube; capture masks read it only at
            # level-aligned block origins, so wrap artifacts never land
            # on a captured value, and each captured origin broadcasts
            # its total (scaled by 1/vol, folded into the mask) over its
            # own block via shifts summing to < block size
            s = delta * pool_ref[...]
            for k in range(kmax + 1):
                h = 1 << k
                s = s + roll_m(s, h, 2)
                s = s + roll_m(s, h, 1)
                s = s + roll_m(s, h, 0)
                if not cap_active[k]:
                    continue
                c = s * cap_refs[k][...]
                for j in range(k, -1, -1):
                    hj = 1 << j
                    c = c + roll_p(c, hj, 2)
                    c = c + roll_p(c, hj, 1)
                    c = c + roll_p(c, hj, 0)
                res_add = res_add + c
            dst_ref[...] = v + res_add

        out_ref[...] = v_ref[...]

        def body(i, _):
            even = (i % 2) == 0

            @pl.when(even)
            def _():
                one_step(out_ref, scr_ref)

            @pl.when(jnp.logical_not(even))
            def _():
                one_step(scr_ref, out_ref)

            return 0

        jax.lax.fori_loop(0, steps, body, 0)

        @pl.when((steps % 2) == 1)
        def _():
            out_ref[...] = scr_ref[...]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_FLAT_VMEM_BUDGET
        )
    call = pl.pallas_call(
        kernel,
        name="advection_flat_ml_run",
        in_specs=[smem] + [vmem] * (9 + n_caps),
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((nz1, ny1, nx1), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((nz1, ny1, nx1), jnp.float32),
        interpret=interpret,
        **kwargs,
    )

    def run(V, wpx, wnx, wpy, wny, wpz, wnz, updf, pool, caps, dt, steps):
        dt = jnp.asarray(dt, jnp.float32)
        steps_arr = jnp.asarray(steps, jnp.int32).reshape(1)
        return call(
            steps_arr, V, wpx * dt, wnx * dt, wpy * dt, wny * dt,
            wpz * dt, wnz * dt, updf, pool, *caps[:n_caps],
        )

    return run
