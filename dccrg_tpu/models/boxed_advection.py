"""Multi-step advection over the boxed per-level AMR layout
(``parallel/boxed.py``) — single- OR multi-device, one unified dense pass
per level per step.

Layout recap (see ``parallel/boxed.py``): every refinement level's leaves
live in a dense box — the tight leaf bounding box on one device, or (multi-
device) the full domain in z and the bounding box in x/y, z-slab
partitioned over the device mesh with one equal slab per device.  Each
device's slab is extended by a one-voxel ring:

* z ring: the neighbor devices' edge planes via a circular
  ``lax.ppermute`` (the circular ring IS the periodic z wrap; with one
  device it degenerates to a local wrap — exact when the box covers a
  periodic z axis, masked out otherwise);
* x/y ring: a local pad — wrap where the box covers a periodic axis, zero
  otherwise.

Every ring voxel carries ``val = use_rho ? rho : upsampled-coarse``; a
single per-axis upwind flux pass over ``val`` with combined static weights
prices same-level AND coarse|fine faces together (the 2:1 face velocity
``(2*v_fine + v_coarse)/3`` — the reference interpolation
``(cl*v_nbr + nl*v_cell)/(cl+nl)`` with ``nl == 2*cl``, solve.hpp:168-175 —
is baked into the weight).  Fine cells read their own deltas directly; the
deltas accumulated on NON-leaf voxels are exactly the coarse receivers'
mass fluxes, recovered by a parity-aligned 2x sum-pool per pair.

The z axis runs in one of two statically chosen modes (the step body is a
single code path; only mask construction, the upsample window, and the
pooled routing differ):

* **local** (one device): z is just another axis — tight extent, cross
  faces register on ring rows where they fall off the box, and pooled
  fluxes route by contiguous segments with modulo wrap, exactly like x/y;
* **slab** (multi-device): full-domain extent, cut at equal per-device
  slabs.  z-wrap mask images register at their true modulo coordinate, so
  every device prices every face REGISTERED in its padded slab — cut and
  periodic-seam faces are priced by BOTH adjacent devices from
  bit-identical inputs (shard_map compiles one program for all devices).
  A device keeps only deltas landing on its interior rows and only pooled
  rows mapping into its own coarse slab interior; the boundary pooled
  rows are exact duplicates of a z-neighbor's local sums and are dropped.
  Each face is thus delivered exactly once per receiving cell with zero
  cross-device flux traffic — the per-step collectives are just 2
  ppermuted rho planes per level, the same wire pattern as the uniform
  dense path (``parallel/dense.py``), generalized per level.

Velocities are loop-invariant inside a run, so all weights and upwind
selections are computed once at run start; the loop body touches only
density.  Produces the same update as the general gather path
(solve.hpp:129-260 semantics) with a different — but fixed —
floating-point association order.

The run takes and returns the state as epoch rows; each call moves every
level's density and velocities into its box and the density back.  Where
``use_pallas`` is set, the dtype is float32 and Pallas runs (on a TPU, or
``use_pallas="interpret"``), ``boxed_move_gather`` / ``boxed_move_scatter``
(``ops/boxed_moves.py``) move each level whose leaves are one ascending
range of rows, by runs of leaves; other levels, and every level in f64,
on the CPU or with ``use_pallas=False``, move by per-element gather and
scatter.  The moves only move values, so both give the same density bit
for bit.
"""
from __future__ import annotations

import jax
from jax import shard_map
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.boxed_moves import (
    box_runs,
    make_box_gather,
    make_box_scatter,
    moves_fit,
    rows_of,
)
from ..ops.dense_advection import pallas_available
from ..parallel.exec_cache import traced_jit
from ..parallel.mesh import SHARD_AXIS, put_table, shard_spec

__all__ = ["build_boxed_run"]


def _round_up(n, unit):
    return -(-n // unit) * unit


def _clip(v, lo, hi):
    return int(min(max(v, lo), hi))


def _runs(idx):
    """Split an index vector into maximal stride-1 runs -> [(start, stop)]
    half-open slices of the source array."""
    cuts = np.flatnonzero(np.diff(idx) != 1) + 1
    return [(int(p[0]), int(p[0]) + len(p)) for p in np.split(idx, cuts)]


def _route_segments(g, gm, n_valid):
    """Contiguous segments of pooled rows mapping to contiguous target
    coordinates under modulo wrap: the main in-domain block plus one
    single-row segment per wrapped edge row (a box touching but not
    covering a periodic axis wraps to the far side of the domain); either
    way each segment gets its own slice-add, so no pooled flux is ever
    dropped."""
    inside = (gm >= 0) & (gm < n_valid)
    main = (g >= 0) & (g < n_valid)
    segs = []
    if main.any():
        i0 = int(np.argmax(main))
        i1 = int(len(g) - np.argmax(main[::-1]))
        segs.append((i0, i1, int(g[i0])))
    for i in np.flatnonzero(inside & ~main):
        segs.append((int(i), int(i) + 1, int(gm[i])))
    return segs


def _level_moves(leaf_s, rows_s, R, interpret):
    """The Pallas moves of one level between the epoch rows and its box
    (``ops/boxed_moves.py``): ``(consts, statics)``, or None where a
    device's leaves are not one ascending range of rows in box order, a
    box row holds too many runs, or the range does not fit VMEM (the
    per-element gather and scatter move that level)."""
    D, nzl, by, bx = leaf_s.shape
    spans = []
    for k in range(D):
        r = rows_s[k][leaf_s[k]]
        if len(r) and not (np.diff(r) == 1).all():
            return None
        spans.append((int(r[0]) if len(r) else 0, len(r)))
    ny, nx = _round_up(by, 8), _round_up(bx, 128)
    runs = [box_runs(leaf_s[k], ny, nx) for k in range(D)]
    if any(t is None for t in runs):
        return None
    K = max(t[1] for t in runs)
    if any(t[1] != K for t in runs):
        runs = [box_runs(leaf_s[k], ny, nx, K) for k in range(D)]
    n = max(1, max(c for _, c in spans))
    n_rows = -(-(nx + n + nx) // 128) + 1     # front zeros, range, one window
    if not moves_fit(n_rows, ny, nx):
        return None
    consts = dict(
        n=n, front=nx, n_rows=n_rows, plane_pad=((0, 0), (0, ny - by),
                                                 (0, nx - bx)),
        # rows past the flat's end that a device's window may reach
        tail=max(0, max(r0 for r0, _ in spans) + n - R),
        ragged=any(c != n for _, c in spans),
        gather=make_box_gather(nzl, ny, nx, K, n_rows, interpret=interpret),
        scatter=make_box_scatter(nzl, ny, nx, K, n_rows, interpret=interpret),
    )
    statics = dict(mv_tab=np.stack([t[0] for t in runs]),
                   mv_span=np.array(spans, dtype=np.int32))
    return consts, statics


def build_boxed_run(adv, layout):
    """Build ``(run, moved)`` for ``adv`` (an ``Advection`` model) over
    ``layout`` (a ``BoxedLayout``): the jitted ``run(state, steps, dt) ->
    state`` and, per level, whether the Pallas moves carry it."""
    dtype = adv.dtype
    grid = adv.grid
    mapping = grid.mapping
    topology = grid.topology
    mesh = grid.mesh
    D = layout.n_devices
    slab_z = D > 1
    scratch = grid.epoch.R - 1
    periodic = [topology.is_periodic(d) for d in range(3)]
    boxes = sorted(layout.boxes.values(), key=lambda b: b.level)
    lvl_index = {b.level: i for i, b in enumerate(boxes)}
    pair_of_fine = {pr.fine_level: pr for pr in layout.pairs}
    L = len(boxes)
    interpret = adv.use_pallas == "interpret"
    pallas = (
        bool(adv.use_pallas) and np.dtype(dtype) == np.float32
        and (interpret or pallas_available(dtype))
    )
    moves = []       # per level: the Pallas moves' consts, or None

    # ---------------------------------------------- per-level static tables
    consts = []      # python-side metadata per level
    statics = []     # device-stacked arrays per level (shipped via shard_map)
    for b in boxes:
        lvl = b.level
        lo = b.lo.astype(np.int64)                  # (3,) x,y,z
        bz, by, bx = b.shape
        nzl = bz // D
        dims = np.array([bx, by, bz])
        n_dom = np.array(mapping.length) << lvl     # domain extent, x,y,z
        covers = [
            bool(periodic[d] and lo[d] == 0 and dims[d] == n_dom[d])
            for d in range(3)
        ]
        # how mask ring rows are filled along z: slab mode needs the
        # circularly consistent wrap whenever z is periodic (the device
        # ring); local mode wraps only when the box covers the axis
        z_mask_wrap = periodic[2] if slab_z else covers[2]

        def pad3(arr, xy_wrap, fill=False, z_wrap=z_mask_wrap):
            """Ring-pad (bz, by, bx) -> (bz+2, by+2, bx+2)."""
            out = arr
            for a, cov in ((0, z_wrap), (1, xy_wrap and covers[1]),
                           (2, xy_wrap and covers[0])):
                pw = [(0, 0)] * 3
                pw[a] = (1, 1)
                if cov:
                    out = np.pad(out, pw, mode="wrap")
                else:
                    out = np.pad(out, pw, mode="constant", constant_values=fill)
            return out

        use_rho = pad3(b.leaf_mask, xy_wrap=True)
        m_same = np.stack([pad3(b.face_valid[d], xy_wrap=True)
                           for d in range(3)])
        # cross-face masks: fine-low (mask_plus at the fine voxel) and
        # fine-high (mask_minus registered at the coarse voxel p - e_d).
        # Shifts falling off the box either fold to their true modulo
        # coordinate (slab z: required so the device owning the periodic
        # seam's coarse side prices the wrap face locally) or stay on the
        # ring row and are delivered by the pooled wrap segments (local
        # mode and x/y).
        m_lowf_i = np.zeros((3, bz, by, bx), dtype=bool)
        m_highf_i = np.zeros((3, bz, by, bx), dtype=bool)
        edge_planes = {}                            # d -> ring-row-0 plane
        pr = pair_of_fine.get(lvl)
        if pr is not None:
            for d in range(3):
                m_lowf_i[d] = pr.mask_plus[d]
                ax = 2 - d
                mm = pr.mask_minus[d]
                src = [slice(None)] * 3
                dst = [slice(None)] * 3
                src[ax] = slice(1, None)
                dst[ax] = slice(0, -1)
                m_highf_i[d][tuple(dst)] = mm[tuple(src)]
                edge_sl = [slice(None)] * 3
                edge_sl[ax] = 0
                edge = mm[tuple(edge_sl)]
                if not edge.any():
                    continue
                if d == 2 and slab_z:
                    # register at the true coordinate bz-1
                    assert periodic[2], "cross face below a non-periodic floor"
                    m_highf_i[d][-1] |= edge
                else:
                    edge_planes[d] = edge
        # Cross-face mask ring padding is MODE-dependent along z:
        # * slab mode wrap-pads — the global rings must be circularly
        #   consistent so each device's ring rows carry the seam faces it
        #   must price (the re-registered fine-below-the-floor faces at
        #   interior bz-1 reach the wrap-adjacent device through its ring
        #   row; same-level seam faces ride m_same's wrap the same way);
        # * local mode constant-pads — its box-edge faces are placed
        #   explicitly on ring row 0 below, and a wrap pad would copy
        #   interior cross-face registrations onto the opposite ring row
        #   as spurious faces, which the pooled wrap segments then deliver
        #   as phantom fluxes into the far-side coarse cells.
        cross_z_wrap = z_mask_wrap if slab_z else False
        m_lowf = np.stack([
            pad3(m_lowf_i[d], xy_wrap=False, z_wrap=cross_z_wrap)
            for d in range(3)
        ])
        m_highf = np.stack([
            pad3(m_highf_i[d], xy_wrap=False, z_wrap=cross_z_wrap)
            for d in range(3)
        ])
        for d, edge in edge_planes.items():
            ax = 2 - d
            sl = [slice(1, 1 + bz), slice(1, 1 + by), slice(1, 1 + bx)]
            sl[ax] = 0
            m_highf[d][tuple(sl)] = edge
        # no face may pair the last ring voxel with the (rolled) first;
        # x/y here, the z edge below (per slab, since every slab's last
        # padded row pairs with a nonexistent row under the rolled pass)
        for m in (m_same, m_lowf, m_highf):
            for d in range(2):
                ax = 2 - d
                sl = [slice(None)] * 3
                sl[ax] = slice(-1, None)
                m[d][tuple(sl)] = False

        # z-slab stacking: device k's padded rows are [k*nzl, k*nzl+nzl+2)
        # of the global padded array (its ring rows are the neighbors'
        # interior rows / the circularly consistent global ring rows);
        # one device: the whole padded box
        def slab_pad(arr_g):                        # padded global -> [D, ...]
            return np.stack([arr_g[..., k * nzl:k * nzl + nzl + 2, :, :]
                             for k in range(D)])

        def slab_int(arr_g):                        # interior global -> [D, ...]
            return np.stack([arr_g[..., k * nzl:(k + 1) * nzl, :, :]
                             for k in range(D)])

        m_same_s = slab_pad(m_same)                 # [D, 3, nzl+2, by+2, bx+2]
        m_lowf_s = slab_pad(m_lowf)
        m_highf_s = slab_pad(m_highf)
        use_rho_s = slab_pad(use_rho)
        for m in (m_same_s, m_lowf_s, m_highf_s):
            m[:, :, -1] = False
        any_face_s = m_same_s | m_lowf_s | m_highf_s

        rows_s = slab_int(b.rows.reshape(bz, by, bx))
        leaf_s = slab_int(b.leaf_mask)
        mv = _level_moves(leaf_s, rows_s, grid.epoch.R, interpret) \
            if pallas else None
        if mv is not None:
            moves.append(mv[0])
            move_tables = mv[1]
        else:
            # per-element moves: per device, the flat slab positions of
            # its leaves and their local epoch rows (padded to a common
            # length; pads write into the scratch row)
            moves.append(None)
            flats, rowss = [], []
            for k in range(D):
                fl = np.flatnonzero(leaf_s[k].ravel())
                flats.append(fl)
                rowss.append(rows_s[k].ravel()[fl])
            M = max((len(f) for f in flats), default=0) or 1
            leaf_flat_s = np.zeros((D, M), dtype=np.int32)
            leaf_rows_s = np.full((D, M), scratch, dtype=np.int32)
            for k in range(D):
                leaf_flat_s[k, : len(flats[k])] = flats[k]
                leaf_rows_s[k, : len(rowss[k])] = rowss[k]
            move_tables = dict(rows=rows_s.astype(np.int32),
                               leaf_flat=leaf_flat_s, leaf_rows=leaf_rows_s)

        area = np.array(
            [
                b.length[1] * b.length[2],
                b.length[0] * b.length[2],
                b.length[0] * b.length[1],
            ]
        )
        consts.append(
            dict(
                covers=covers,
                area=area.astype(dtype),
                inv_vol=dtype(1.0 / float(np.prod(b.length))),
            )
        )
        statics.append(
            dict(
                leaf=leaf_s,
                use_rho=use_rho_s,
                m_same=m_same_s,
                m_lowf=m_lowf_s,
                m_highf=m_highf_s,
                any_face=any_face_s,
                pool_mask=~use_rho_s,
                **move_tables,
            )
        )

    # ------------------------------------------ per-pair static plumbing
    # Window segments for the coarse->fine upsample and routing segments
    # for the pooled fine->coarse fluxes.  x/y (and local-mode z) go
    # through clip/wrap segment decomposition; slab-mode z needs neither —
    # alignment makes the window the whole ringed coarse slab and the
    # routing an interior crop.
    pconsts = {}
    for pr in layout.pairs:
        fb = layout.boxes[pr.fine_level]
        cb = layout.boxes[pr.coarse_level]
        fi, ci = lvl_index[pr.fine_level], lvl_index[pr.coarse_level]
        lo_f = fb.lo.astype(np.int64)
        lo_c = cb.lo.astype(np.int64)
        bz, by, bx = fb.shape
        dims_f = np.array([bx, by, bz])
        cz, cy, cx = cb.shape
        dims_c = np.array([cx, cy, cz])
        nzl_f = bz // D
        nzc = cz // D
        n_c = np.array(mapping.length) << pr.coarse_level
        clo = (lo_f - 1) >> 1
        chi = ((lo_f + dims_f) >> 1) + 1
        # upsample window: per axis, maximal stride-1 runs — the window
        # becomes a concat of static slices, no gather op anywhere
        # (gathers are the single most expensive lowering on TPU for this
        # access pattern).  Indices are into the z-RINGED coarse slab
        # (z + 1 shift); slab-mode z uses the whole ringed slab.
        win_segs = []
        for d in range(3):
            if d == 2 and slab_z:
                win_segs.append([(0, nzc + 2)])
                continue
            coords = np.arange(clo[d], chi[d])
            if periodic[d]:
                coords = coords % n_c[d]
            idx = np.clip(coords - lo_c[d], 0, dims_c[d] - 1)
            if d == 2:
                idx = idx + 1                       # into the ringed slab
            win_segs.append(_runs(idx))
        off = lo_f - 1 - 2 * clo                    # 0/1 per axis
        off_z = 1 if slab_z else int(off[2])

        def upsample(c_rz, win_segs=win_segs, off=off, off_z=off_z,
                     nzl=nzl_f, shape=(by, bx)):
            """(nzc+2, cy, cx) z-ringed coarse -> (nzl+2, by+2, bx+2)."""
            win = c_rz
            for a in range(3):
                segs = win_segs[2 - a]
                if len(segs) == 1 and segs[0] == (0, win.shape[a]):
                    continue
                parts = [
                    jax.lax.slice_in_dim(win, i0, i1, axis=a)
                    for i0, i1 in segs
                ]
                win = parts[0] if len(parts) == 1 else jnp.concatenate(
                    parts, axis=a
                )
            up = win
            for a in range(3):
                up = jnp.repeat(up, 2, axis=a)
            by_, bx_ = shape
            return up[
                off_z:off_z + nzl + 2,
                off[1]:off[1] + by_ + 2,
                off[0]:off[0] + bx_ + 2,
            ]

        # pooled routing: pad the ringed fine slab to global-even parity,
        # 2x sum-pool, then slice-add per cartesian combination of
        # per-axis segments.  Each segment is (src_start, length,
        # target_start) with clipping against the coarse box already
        # applied; slab-mode z contributes the single interior crop (the
        # boundary pooled rows are dropped — each is an exact duplicate of
        # a z-neighbor device's local sums, or of the wrap image priced by
        # the owning device).
        go = lo_f - 1
        plo_pad = [int(go[d] & 1) for d in range(3)]
        if slab_z:
            plo_pad[2] = 1                          # slab start is even
        psz = [int(dims_f[d]) + 2 + plo_pad[d] for d in range(3)]
        psz[2] = nzl_f + 2 + plo_pad[2]
        phi_pad = [psz[d] % 2 for d in range(3)]
        npool = [(psz[d] + phi_pad[d]) // 2 for d in range(3)]
        cplo = go >> 1
        segments = []                               # per axis: (s0, len, t0)
        for d in range(3):
            if d == 2 and slab_z:
                segments.append([(1, nzc, 0)])
                continue
            g = cplo[d] + np.arange(npool[d])
            gm = g % n_c[d] if periodic[d] else g
            segs = []
            for i0, i1, gt in _route_segments(g, gm, int(n_c[d])):
                t0 = gt - int(lo_c[d])
                c0 = _clip(t0, 0, int(dims_c[d]))
                c1 = _clip(t0 + (i1 - i0), 0, int(dims_c[d]))
                if c1 > c0:
                    segs.append((i0 + c0 - t0, c1 - c0, c0))
            segments.append(segs)

        def pool_route(delta_c_pad, P_src, plo_pad=plo_pad, phi_pad=phi_pad,
                       segments=segments):
            """2x sum-pool the masked ring-grid deltas and add them into the
            coarse level's padded slab delta (wrap images of the same
            coarse row accumulate — they carry different faces'
            fluxes)."""
            Pp = jnp.pad(
                P_src,
                ((plo_pad[2], phi_pad[2]), (plo_pad[1], phi_pad[1]),
                 (plo_pad[0], phi_pad[0])),
            )
            # 2x sum-pool as three strided-slice adds (XLA fuses these into
            # one pass; the 6-D reshape+reduce form does not tile as well)
            Q = Pp
            for a in range(3):
                lo_sl = [slice(None)] * 3
                hi_sl = [slice(None)] * 3
                lo_sl[a] = slice(0, None, 2)
                hi_sl[a] = slice(1, None, 2)
                Q = Q[tuple(lo_sl)] + Q[tuple(hi_sl)]
            for z0, lz, tz in segments[2]:
                for y0, ly, ty in segments[1]:
                    for x0, lx, tx in segments[0]:
                        Ps = Q[z0:z0 + lz, y0:y0 + ly, x0:x0 + lx]
                        delta_c_pad = delta_c_pad.at[
                            1 + tz:1 + tz + lz,
                            1 + ty:1 + ty + ly,
                            1 + tx:1 + tx + lx,
                        ].add(Ps)
            return delta_c_pad

        pconsts[fi] = dict(ci=ci, upsample=upsample, pool_route=pool_route)

    # --------------------------------------------------- the sharded body
    up_perm = [(i, (i + 1) % D) for i in range(D)]
    down_perm = [(i, (i - 1) % D) for i in range(D)]

    def zring(x):
        """(nz_loc, ...) -> (nz_loc+2, ...): neighbor edge planes over the
        circular device ring (one device: local wrap)."""
        top, bot = x[-1:], x[:1]
        if D == 1:
            rb, ra = top, bot
        else:
            rb = jax.lax.ppermute(top, SHARD_AXIS, up_perm)
            ra = jax.lax.ppermute(bot, SHARD_AXIS, down_perm)
        return jnp.concatenate([rb, x, ra], axis=0)

    def pad_xy(x, covers):
        """(nz+2, by, bx) -> (nz+2, by+2, bx+2)."""
        for a, cov in ((1, covers[1]), (2, covers[0])):
            pw = [(0, 0)] * 3
            pw[a] = (1, 1)
            x = jnp.pad(x, pw, mode="wrap" if cov else "constant")
        return x

    def body(rho_b, vx_b, vy_b, vz_b, dt, steps, st):
        rho_flat = rho_b[0]
        v_flat = (vx_b[0], vy_b[0], vz_b[0])
        C = [{k: v[0] for k, v in s.items()} for s in st]  # strip dev axis

        def to_slab(flat, li):
            mv = moves[li]
            if mv is None:
                vals = flat[C[li]["rows"]]
                return jnp.where(C[li]["leaf"], vals, 0)
            bz_, by_, bx_ = C[li]["leaf"].shape
            compact = jax.lax.dynamic_slice_in_dim(
                jnp.pad(flat, (0, mv["tail"])), C[li]["mv_span"][0], mv["n"]
            )
            box = mv["gather"](C[li]["mv_tab"],
                               rows_of(compact, mv["front"], mv["n_rows"]))
            return box[:, :by_, :bx_]

        def to_rows(out, li, rho):
            mv = moves[li]
            if mv is None:
                return out.at[C[li]["leaf_rows"]].set(
                    rho.reshape(-1)[C[li]["leaf_flat"]]
                )
            r0, cnt = C[li]["mv_span"][0], C[li]["mv_span"][1]
            rows = mv["scatter"](C[li]["mv_tab"], jnp.pad(rho, mv["plane_pad"]))
            new = rows.reshape(-1)[mv["front"]:mv["front"] + mv["n"]]
            out_p = jnp.pad(out, (0, mv["tail"]))
            if mv["ragged"]:
                old = jax.lax.dynamic_slice_in_dim(out_p, r0, mv["n"])
                pos = jnp.arange(mv["n"], dtype=jnp.int32)
                new = jnp.where(pos < cnt, new, old)
            out_p = jax.lax.dynamic_update_slice_in_dim(out_p, new, r0, 0)
            return out_p[:out.shape[0]]

        rhos = tuple(to_slab(rho_flat, li) for li in range(L))
        vels = [tuple(to_slab(v, li) for v in v_flat) for li in range(L)]

        # static per-level face weights and upwind selections (velocity is
        # loop-invariant; the ring exchanges here run once per run)
        stat = []
        for li, c in enumerate(consts):
            p = pconsts.get(li)
            ups = (
                [p["upsample"](zring(vels[p["ci"]][d])) for d in range(3)]
                if p is not None
                else None
            )
            per_axis = []
            for d in range(3):
                ax = 2 - d
                vv = pad_xy(zring(vels[li][d]), c["covers"])
                if ups is not None:
                    vv = jnp.where(C[li]["use_rho"], vv, ups[d])
                vl, vh = vv, jnp.roll(vv, -1, ax)
                v_face = jnp.where(
                    C[li]["m_same"][d], 0.5 * (vl + vh),
                    jnp.where(
                        C[li]["m_lowf"][d], (2 * vl + vh) / 3,
                        (vl + 2 * vh) / 3,
                    ),
                )
                w = jnp.where(
                    C[li]["any_face"][d], dt * v_face * c["area"][d], 0
                )
                per_axis.append((v_face >= 0, w))
            stat.append(per_axis)

        def step(i, rhos):
            rz = [zring(r) for r in rhos]
            deltas = []
            for li, c in enumerate(consts):
                p = pconsts.get(li)
                val = pad_xy(rz[li], c["covers"])
                if p is not None:
                    val = jnp.where(
                        C[li]["use_rho"], val, p["upsample"](rz[p["ci"]])
                    )
                delta = jnp.zeros_like(val)
                for d in range(3):
                    ax = 2 - d
                    upsel, w = stat[li][d]
                    F = jnp.where(upsel, val, jnp.roll(val, -1, ax)) * w
                    delta = delta + (jnp.roll(F, 1, ax) - F)
                deltas.append(delta)
            # route non-leaf voxel deltas (= coarse receivers' fluxes)
            # fine-to-coarse, finest level first
            for li in range(L - 1, -1, -1):
                p = pconsts.get(li)
                if p is None:
                    continue
                deltas[p["ci"]] = p["pool_route"](
                    deltas[p["ci"]], deltas[li] * C[li]["pool_mask"]
                )
            new = []
            for li, c in enumerate(consts):
                d_in = deltas[li][1:-1, 1:-1, 1:-1]
                new.append(
                    jnp.where(
                        C[li]["leaf"], rhos[li] + d_in * c["inv_vol"], 0
                    )
                )
            return tuple(new)

        rhos = jax.lax.fori_loop(0, steps, step, rhos)
        out = rho_flat
        for li in range(L):
            out = to_rows(out, li, rhos[li])
        return out[None]

    statics_dev = [
        {k: put_table(v, mesh) for k, v in s.items()}
        for s in statics
    ]
    st_specs = [
        {k: P(SHARD_AXIS, *([None] * (v.ndim - 1))) for k, v in s.items()}
        for s in statics
    ]
    data_spec = P(SHARD_AXIS)
    sm = shard_map(
        body,
        mesh=mesh,
        in_specs=(data_spec, data_spec, data_spec, data_spec, P(), P(),
                  st_specs),
        out_specs=data_spec,
        # pallas_call outputs carry no varying axes
        check_vma=not any(mv is not None for mv in moves),
    )

    # the boxed tables ride into the jit as a RUNTIME argument pytree
    # (not closed over): same-shape boxings share one executable
    def run_impl(statics_arg, state, steps, dt):
        dt = jnp.asarray(dt, dtype)
        steps = jnp.asarray(steps, jnp.int32)
        density = sm(
            state["density"], state["vx"], state["vy"], state["vz"],
            dt, steps, statics_arg,
        )
        return {
            **state,
            "density": density,
            "flux": jnp.zeros_like(state["flux"]),
        }

    run_impl = traced_jit("advection.boxed_run", run_impl)

    def run(state, steps, dt):
        return run_impl(statics_dev, state, steps, dt)

    return run, tuple(mv is not None for mv in moves)
