"""Dense flat-voxel Poisson matvec — the TPU fast path for the BiCG
solver on uniform and two-level AMR grids.

The general Poisson path applies A (and Aᵀ) through per-row gather tables
(models/poisson.py), which lowers to XLA gathers — on TPU those retire
roughly one element per cycle, so a ~50k-cell refined system costs ~ms per
iteration.  This module re-expresses the matvec on the flat inflated voxel
grid (the layout of ops/flat_amr.py: every leaf either is a fine voxel or
is replicated over its 2x2x2 fine block), where neighbor access is six
array rolls and coarse-row accumulation is the even-parity pool/broadcast
roll chain — no gathers anywhere.

Semantics reproduced exactly (reference ``tests/poisson/poisson_solve.hpp``):

* per-face factors ``f_side`` from cell-center offsets with missing /
  inactive neighbors giving 0 (``poisson_solve.hpp:691-822``) — taken
  from the leaf-level arrays the model already computes;
* a finer face neighbor's contribution divided by 4
  (``poisson_solve.hpp:332-336``) — on the voxel grid this is uniform:
  every face of a COARSE leaf spans 4 voxel sub-faces, so its per-voxel
  weight is ``f/4`` and the pooled block sum restores ``f`` (same-level)
  or ``f/4 * sum(fine values)`` (finer neighbor) exactly;
* skip cells act as missing neighbors and boundary-boundary pairs are
  dropped (``poisson_solve.hpp:896-965``) — folded into the per-voxel
  face weights;
* the transpose multiplier table (``poisson_solve.hpp:405-520``) needs no
  second weight set here: with ``A = S·C·E`` (E = replicate leaves onto
  voxels, S = Eᵀ = block sum, C = the voxel face operator), ``Aᵀ =
  S·Cᵀ·E`` and ``Cᵀ`` is the same six weights applied with reversed
  rolls.

Qualifies: (possibly degenerate) Cartesian geometry, leaf levels ⊆
{0, 1}; any device count whose ownership equals the voxel z-slab
partition — multi-device meshes shard the voxel arrays by z-slab, the
matvec's z-rolls lower to collective permutes over the device ring, and
the pool/broadcast chain runs slab-local.  The gather path remains the
general fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["build_flat_poisson", "make_flat_poisson_apply"]

#: HBM-side cap: the solver keeps ~10 voxel-resolution arrays alive
_MAX_VOXELS = 1 << 24


def build_flat_poisson(grid, f_pos, f_neg, scaling_leaf, types_leaf,
                       solve_code, skip_code, boundary_code):
    """Static tables for the flat Poisson operator, or None if the grid
    does not qualify.

    ``f_pos``/``f_neg``: (N, 3) per-leaf per-axis side factors;
    ``scaling_leaf``: (N,) diagonal; ``types_leaf``: (N,) cell roles.
    """
    from .flat_amr import _ML_MAX_VL, flat_voxel_layout

    lay = flat_voxel_layout(
        grid, allow_uniform=True, max_voxels=_MAX_VOXELS,
        allow_multi_device=True, max_vl=_ML_MAX_VL,
    )
    if lay is None:
        return None
    shape = lay["shape"]
    leaf_idx = lay["leaf_idx"]
    vl = int(lay["vox_level"])

    t_vox = np.asarray(types_leaf)[leaf_idx]
    f_pos_vox = np.asarray(f_pos)[leaf_idx]        # (n_vox, 3)
    f_neg_vox = np.asarray(f_neg)[leaf_idx]
    scaling_vox = np.asarray(scaling_leaf)[leaf_idx]

    nz1, ny1, nx1 = shape
    rows3 = leaf_idx.reshape(shape)   # same-leaf face detection
    fine3 = lay["leaf_fine"]
    lev3 = lay["leaf_level"]
    t3 = t_vox.reshape(shape)
    # a level-l leaf's face spans 4^(vl-l) voxel sub-faces, so its
    # per-voxel face weight is f / 4^(vl-l) and the leaf-block sum
    # restores exactly the reference's factors: full f toward same or
    # coarser neighbors, f/4 toward each finer face neighbor
    # (poisson_solve.hpp:332-336) — at any level spread (2:1 balance
    # keeps adjacent leaves within one level)
    sub = 0.25 ** (vl - lev3).astype(np.float64)

    def active(ta, tb):
        return (
            (ta != skip_code)
            & (tb != skip_code)
            & ~((ta == boundary_code) & (tb == boundary_code))
        )

    weights = []
    for d, ax in ((0, 2), (1, 1), (2, 0)):
        fp = f_pos_vox[:, d].reshape(shape)
        fn = f_neg_vox[:, d].reshape(shape)
        rb_p = np.roll(rows3, -1, ax)
        rb_n = np.roll(rows3, 1, ax)
        # same-row faces are interior to a coarse block (no leaf face
        # there) and must drop — EXCEPT when the roll wrapped around a
        # periodic axis back into the same leaf (domain extent of one
        # leaf along the axis): that is the leaf's genuine periodic face
        # and the reference couples the cell to itself through it.
        # Non-periodic domain edges are harmless to keep: their factors
        # are already 0.
        pos = np.arange(shape[ax])
        at_max = (pos == shape[ax] - 1).reshape(
            [-1 if a == ax else 1 for a in range(3)]
        )
        at_min = (pos == 0).reshape(
            [-1 if a == ax else 1 for a in range(3)]
        )
        wp = fp * sub * active(t3, np.roll(t3, -1, ax)) * (
            (rows3 != rb_p) | at_max
        )
        wn = fn * sub * active(t3, np.roll(t3, 1, ax)) * (
            (rows3 != rb_n) | at_min
        )
        weights.append((wp, wn))

    ex = (np.arange(nx1) % 2 == 0)[None, None, :]
    ey = (np.arange(ny1) % 2 == 0)[None, :, None]
    ez = (np.arange(nz1) % 2 == 0)[:, None, None]
    orig = ex & ey & ez
    solve3 = t3 == solve_code

    # leaf-origin mask: the one voxel per leaf whose coordinates are
    # aligned to ITS leaf's block size — the generalized "each leaf
    # counted once" selector for dots and writeback at any level spread
    zi, yi, xi = np.meshgrid(np.arange(nz1), np.arange(ny1),
                             np.arange(nx1), indexing="ij")
    B3 = 1 << (vl - lev3)
    leaf_origin = ((zi % B3 == 0) & (yi % B3 == 0) & (xi % B3 == 0))

    # multi-level accumulation tables (reshape pyramid): per-doubling
    # capture masks at their own reduced resolution; 2-level grids keep
    # the tuned roll-chain (these stay unused there)
    cap_masks, cap_active = [], []
    for k in range(vl):
        f = 1 << (k + 1)
        lev_red = lev3[::f, ::f, ::f]
        m = (lev_red == vl - 1 - k)
        cap_masks.append(m.astype(np.float64))
        cap_active.append(bool(m.any()))

    return dict(
        shape=shape,
        n_devices=lay["n_devices"],
        vl=vl,
        rows=lay["rows"],
        fine=fine3,
        has_coarse=bool((~fine3).any()),
        weights=weights,
        scaling=scaling_vox.reshape(shape),
        solve=solve3,
        # dot weights: each leaf counted once at its own origin voxel
        dot_mask=solve3 & leaf_origin,
        orig=orig,
        cap_masks=cap_masks,
        cap_active=cap_active,
        wb_rows=lay["wb_rows"],
        wb_valid=lay["wb_valid"],
    )


def make_flat_poisson_apply(tables, dtype, mesh=None):
    """Returns ``(apply_fwd, apply_rev, voxelize, writeback, masks)``.

    ``apply_*`` map a voxel array to A·v / Aᵀ·v in voxel layout (coarse
    rows' results replicated over their blocks).  ``voxelize`` lifts a
    ``[D, R]`` row array onto the voxel grid; ``writeback`` projects a
    voxel array onto ``[D, R]`` rows.

    Multi-device: the voxel arrays are z-slab sharded over the mesh
    (leading axis); the matvec's z-rolls cross slab boundaries, which
    XLA lowers to collective permutes over the device ring — the same
    wire pattern as the dense halo — while the pool/broadcast chain
    stays slab-local (coarse blocks never straddle slabs by
    construction).  Lift/project run per device inside ``shard_map``.
    """
    D = tables["n_devices"]
    shape = tables["shape"]
    if D > 1:
        # the Tables seam (parallel/mesh.put_table): sharded device
        # arrays under one controller; host numpy under many — jit
        # embeds replicated constants freely, while closing over a
        # device array spanning other processes' devices is rejected
        from ..parallel.mesh import put_table

        put = lambda a, dt=None: put_table(a, mesh, dtype=dt)
    else:
        put = lambda a, dt=None: jnp.asarray(a, dt)
    fine_f = put(tables["fine"], dtype)
    coarse_f = put(~tables["fine"], dtype)
    orig_f = put(tables["orig"], dtype)
    scaling = put(tables["scaling"], dtype)
    W = [(put(wp, dtype), put(wn, dtype)) for wp, wn in tables["weights"]]
    has_coarse = tables["has_coarse"]

    def _accum_math(C, coarse, orig, fine):
        """Leaf-row totals from per-voxel face contributions: fine voxels
        keep theirs; coarse blocks pool (even-aligned -1-roll chain), park
        the total at the block origin, then broadcast it back over the
        block (the ops/flat_amr.py coarse-update scheme).  The z-roll
        wrap planes only ever land on positions the orig/odd-z masking
        zeroes (blocks are 2-aligned and never straddle the wrap), so the
        chain is exact with slab-local rolls."""
        s = C * coarse
        s = s + jnp.roll(s, -1, 2)
        s = s + jnp.roll(s, -1, 1)
        s = s + jnp.roll(s, -1, 0)
        s = s * orig
        s = s + jnp.roll(s, 1, 2)
        s = s + jnp.roll(s, 1, 1)
        s = s + jnp.roll(s, 1, 0)
        return fine * C + s

    vl = int(tables.get("vl", 1))
    cap_active = tables.get("cap_active") or []
    kmax = max((k for k in range(len(cap_active)) if cap_active[k]),
               default=-1)
    caps_dev = [put(m, dtype) for m in (tables.get("cap_masks") or [])]

    def _accum_ml(C, coarse, _orig, fine, *caps):
        """Multi-level leaf-row totals: the flat_amr reshape pyramid
        (plain sums, no volume factors — the Poisson S operator is a
        block SUM).  Blocks never straddle slabs (slab % 2^vl == 0), so
        the pyramid is slab-local."""
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

        cur = C * coarse
        subs = []
        for _k in range(kmax + 1):
            cur = down2(cur)
            subs.append(cur)
        acc = None
        for k in range(kmax, -1, -1):
            if acc is not None:
                acc = up2(acc)
            if cap_active[k]:
                contrib = subs[k] * caps[k]
                acc = contrib if acc is None else acc + contrib
        out = fine * C
        if acc is not None:
            out = out + up2(acc)
        return out

    _accum_fn = _accum_ml if vl >= 2 else _accum_math
    _accum_extra = tuple(caps_dev) if vl >= 2 else ()
    if D > 1 and has_coarse:
        # run the whole chain per slab inside shard_map: the
        # pooling/broadcast stays slab-local (coarse blocks never
        # straddle slabs), so no collective permutes enter the solver's
        # hot loop for it
        from jax import shard_map
        from ..parallel.mesh import SHARD_AXIS as _AX
        from jax.sharding import PartitionSpec as _P

        _vox_spec = _P(_AX, None, None)
        _accum_sharded = shard_map(
            _accum_fn, mesh=mesh,
            in_specs=(_vox_spec,) * (4 + len(_accum_extra)),
            out_specs=_vox_spec,
            check_vma=False,
        )

        def _accumulate(C):
            return _accum_sharded(C, coarse_f, orig_f, fine_f,
                                  *_accum_extra)
    else:
        def _accumulate(C):
            if not has_coarse:
                return C
            return _accum_fn(C, coarse_f, orig_f, fine_f, *_accum_extra)

    def apply_fwd(v):
        C = jnp.zeros(shape, dtype)
        for (wp, wn), ax in zip(W, (2, 1, 0)):
            C = C + wp * jnp.roll(v, -1, ax) + wn * jnp.roll(v, 1, ax)
        return scaling * v + _accumulate(C)

    def apply_rev(v):
        C = jnp.zeros(shape, dtype)
        for (wp, wn), ax in zip(W, (2, 1, 0)):
            C = C + jnp.roll(wp * v, 1, ax) + jnp.roll(wn * v, -1, ax)
        return scaling * v + _accumulate(C)

    if D == 1:
        rows = jnp.asarray(tables["rows"])
        wb_rows = jnp.asarray(tables["wb_rows"])
        wb_valid = jnp.asarray(tables["wb_valid"])

        def voxelize(row_arr):
            return row_arr[0][rows].reshape(shape).astype(dtype)

        def writeback(vox_arr):
            flat = vox_arr.reshape(-1)
            return jnp.where(wb_valid, flat[wb_rows], 0)[None]
    else:
        from jax import shard_map

        nzv, nyv, nxv = shape
        slab = nzv // D
        rows_d = put(tables["rows"])        # [D, n_loc]
        wb_rows = put(tables["wb_rows"])    # [D, R]
        wb_valid = put(tables["wb_valid"])

        def _lift(row_arr, rmap):
            return row_arr[0][rmap[0]].reshape(slab, nyv, nxv).astype(dtype)

        def _proj(vox, wb, valid):
            flat = vox.reshape(-1)
            return jnp.where(valid[0], flat[wb[0]], 0)[None].astype(dtype)

        from ..parallel.mesh import SHARD_AXIS
        from jax.sharding import PartitionSpec as Pspec

        lift_fn = shard_map(
            _lift, mesh=mesh,
            in_specs=(Pspec(SHARD_AXIS), Pspec(SHARD_AXIS)),
            out_specs=Pspec(SHARD_AXIS, None, None),
            check_vma=False,
        )
        proj_fn = shard_map(
            _proj, mesh=mesh,
            in_specs=(
                Pspec(SHARD_AXIS, None, None),
                Pspec(SHARD_AXIS),
                Pspec(SHARD_AXIS),
            ),
            out_specs=Pspec(SHARD_AXIS),
            check_vma=False,
        )

        def voxelize(row_arr):
            return lift_fn(row_arr, rows_d)

        def writeback(vox_arr):
            return proj_fn(vox_arr, wb_rows, wb_valid)

    masks = dict(
        solve=put(tables["solve"]),
        dot=put(tables["dot_mask"]),
    )
    return apply_fwd, apply_rev, voxelize, writeback, masks
