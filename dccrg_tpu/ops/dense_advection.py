"""Fused Pallas TPU kernel for the dense advection step.

The XLA version of the dense step (models/advection.py::_init_dense)
materializes rolled copies and face-flux intermediates in HBM; this kernel
keeps the whole 6-face upwind update in VMEM per z-slab tile, so the HBM
traffic per step drops to the 8 input planesets + 1 output (the x/y
neighbor values are VMEM rotations, never touching HBM).

The z-direction neighbors arrive as pre-sliced arrays (``rho_lo/rho_hi``
from the halo-extended block), keeping every BlockSpec non-overlapping.
Float32 only (TPU Pallas has no f64); the f64 path stays on XLA and is the
parity reference in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pallas_available",
    "make_flux_update",
    "make_flux_update_blocked_direct",
    "pick_step_block",
    "run_ping_pong",
    "make_fused_run",
    "fused_run_fits",
]

# VMEM footprint cap for the whole-block fused-run kernel (v5e has ~128 MB
# of VMEM; the kernel's resident set is ~17 block-sized arrays — in, out,
# scratch, 3 velocities, 4 face velocities + 4 weights + select masks,
# ~3 live temporaries)
_FUSED_VMEM_BUDGET = 72 * 1024 * 1024
_FUSED_ARRAYS = 17


def pallas_available(dtype) -> bool:
    if np.dtype(dtype) != np.float32:
        return False
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:  # pragma: no cover
        return False


def _make_rolls(interpret: bool):
    """(roll_m1, roll_p1): element i sees i+1 / i-1 (wrapping).  pltpu.roll
    only takes non-negative shifts (-1 is size-1); interpret mode uses
    jnp.roll, which has identical semantics."""
    if interpret:
        return (lambda x, a: jnp.roll(x, -1, a)), (lambda x, a: jnp.roll(x, 1, a))
    return (
        lambda x, a: pltpu.roll(x, x.shape[a] - 1, a),
        lambda x, a: pltpu.roll(x, 1, a),
    )


#: per-plane VMEM residency of the one-step kernel: ~13 plane-sized blocks
#: double-buffered by Mosaic (measured 18 MB at 512x512 planes, above the
#: 16 MB default scoped limit)
_STEP_PLANE_ARRAYS = 30


def flux_update_fits(ny: int, nx: int) -> bool:
    """Whether the per-step kernel's plane working set fits the raised
    scoped-VMEM budget (large x/y extents fall back to the XLA path)."""
    return _STEP_PLANE_ARRAYS * ny * nx * 4 <= _FUSED_VMEM_BUDGET


def make_flux_update(nzl: int, ny: int, nx: int, area, inv_vol: float,
                     *, interpret: bool = False):
    """Returns ``update(rho_ext, vx, vy, vz_ext, mx, my, mz_up, mz_dn, dt)
    -> new_rho`` over one device's block, as a fused Pallas call tiled over
    z-slabs.  The z-neighbor planes are read straight out of the
    halo-extended arrays through offset block index maps — no sliced copies
    are materialized in HBM."""
    area_x, area_y, area_z = (float(a) for a in area)
    inv_vol = float(inv_vol)
    _roll_m1, _roll_p1 = _make_rolls(interpret)

    def kernel(dt_ref, r_lo, r_c, r_hi, vx, vy, vz_lo, vz_c, vz_hi,
               mx, my, mzu, mzd, out):
        dt = dt_ref[0]
        r = r_c[...]

        rxp = _roll_m1(r, 2)
        vfx = (vx[...] + _roll_m1(vx[...], 2)) * 0.5
        fx = jnp.where(vfx >= 0, r, rxp) * (dt * vfx * area_x)
        fx = fx * mx[...]

        ryp = _roll_m1(r, 1)
        vfy = (vy[...] + _roll_m1(vy[...], 1)) * 0.5
        fy = jnp.where(vfy >= 0, r, ryp) * (dt * vfy * area_y)
        fy = fy * my[...]

        vfz_hi = (vz_c[...] + vz_hi[...]) * 0.5
        fz = jnp.where(vfz_hi >= 0, r, r_hi[...]) * (dt * vfz_hi * area_z)
        fz = fz * mzu[...]
        vfz_lo = (vz_lo[...] + vz_c[...]) * 0.5
        fzd = jnp.where(vfz_lo >= 0, r_lo[...], r) * (dt * vfz_lo * area_z)
        fzd = fzd * mzd[...]

        # accumulate in the XLA body's slot order: z-, y-, x-, x+, y+, z+
        flux = fzd
        flux = flux + _roll_p1(fy, 1)
        flux = flux + _roll_p1(fx, 2)
        flux = flux - fx
        flux = flux - fy
        flux = flux - fz
        out[...] = r + flux * inv_vol

    # Plane-granularity blocks: program k handles one z plane; the three
    # views of each extended array are the same buffer read at block
    # offsets k, k+1, k+2 (the +-1 z-neighbors), so no sliced copies ever
    # materialize and Mosaic double-buffers the plane DMAs.
    pspec = lambda off: pl.BlockSpec(
        (1, ny, nx), lambda k, *_: (k + off, 0, 0), memory_space=pltpu.VMEM
    )
    vspec = pl.BlockSpec((1, ny, nx), lambda k, *_: (k, 0, 0), memory_space=pltpu.VMEM)
    mxspec = pl.BlockSpec((1, 1, nx), lambda k, *_: (0, 0, 0), memory_space=pltpu.VMEM)
    myspec = pl.BlockSpec((1, ny, 1), lambda k, *_: (0, 0, 0), memory_space=pltpu.VMEM)
    mzspec = pl.BlockSpec((1, 1, 1), lambda k, *_: (k, 0, 0), memory_space=pltpu.VMEM)

    kwargs = {}
    if not interpret:
        # large planes exceed the 16 MB default scoped-VMEM limit (the
        # blocks are plane-granular and Mosaic double-buffers them);
        # flux_update_fits() gates entry against the raised budget
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_VMEM_BUDGET
        )
    call = pl.pallas_call(
        kernel,
        name="advection_plane",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nzl,),
            in_specs=[
                pspec(0), pspec(1), pspec(2),      # rho_ext views lo/c/hi
                vspec, vspec,                       # vx, vy
                pspec(0), pspec(1), pspec(2),      # vz_ext views
                mxspec, myspec, mzspec, mzspec,
            ],
            out_specs=vspec,
        ),
        out_shape=jax.ShapeDtypeStruct((nzl, ny, nx), jnp.float32),
        interpret=interpret,
        **kwargs,
    )

    def update(rho_ext, vx, vy, vz_ext, mx, my, mz_up, mz_dn, dt):
        dt_arr = jnp.asarray(dt, jnp.float32).reshape(1)
        return call(
            dt_arr, rho_ext, rho_ext, rho_ext, vx, vy,
            vz_ext, vz_ext, vz_ext, mx, my, mz_up, mz_dn,
        )

    return update


#: scoped-VMEM cap for the blocked per-step kernel (v5e has ~128 MB)
_STEP_VMEM_BUDGET = 100 * 1024 * 1024


def pick_step_block(nzl: int, ny: int, nx: int) -> int:
    """Largest z-block size B (a divisor of nzl, >=2) whose blocked-kernel
    VMEM residency fits the raised scoped budget; 0 if none does.

    Residency model (the direct-neighbor-plane kernel,
    ``make_flux_update_blocked_direct``): the 4 input + 1 output center
    blocks double-buffered (10B planes) plus ~6B planes of kernel
    temporaries plus the 8 single-plane neighbor/edge inputs
    double-buffered (16 planes) — ~(16B + 16) plane-sized arrays.
    Larger B amortizes the neighbor-plane re-reads: HBM traffic per step
    is ~(5 + 4/B) full arrays instead of the plane kernel's ~13 (which
    re-reads the +-1 z views of rho and vz three times each and
    re-materializes both halo-extended copies every step)."""
    plane = ny * nx * 4
    for b in (16, 8, 4, 2):
        if nzl % b == 0 and (16 * b + 16) * plane <= _STEP_VMEM_BUDGET:
            return b
    return 0


def make_flux_update_blocked_direct(nzl: int, ny: int, nx: int, block: int,
                                    area, inv_vol: float, *,
                                    interpret: bool = False):
    """Blocked per-step kernel with DIRECT z-neighbor plane reads:
    ``update(rho, edge_lo, edge_hi, vx, vy, vz, vz_edge_lo, vz_edge_hi,
    mx, my, mz_up, mz_dn, dt) -> new_rho``.

    Rather than consuming per-block halo stacks a host-side slice pass
    must rebuild from rho EVERY step (read 2/B + write 2/B
    arrays-worth, then read them again in-kernel — the retired stacked
    variant's cost), this kernel reads the block-edge neighbor planes
    straight out of ``rho`` through shifted plane-shaped block index
    maps — block k's low/high
    neighbor planes are rho planes ``k*B-1`` / ``(k+1)*B`` (mod nzl).
    Only the two ppermute-received device-boundary planes remain inputs,
    spliced at programs 0 and m-1.  Per-step HBM traffic drops from
    ``5 + 8/B`` to ``5 + 4/B`` full arrays."""
    assert nzl % block == 0 and block >= 2
    m = nzl // block
    area_x, area_y, area_z = (float(a) for a in area)
    inv_vol = float(inv_vol)
    roll_m1, roll_p1 = _make_rolls(interpret)

    def kernel(dt_ref, r_c, r_lop, r_hip, e_lo, e_hi, vx, vy,
               vz_c, vz_lop, vz_hip, ve_lo, ve_hi,
               mx, my, mzu, mzd, out):
        dt = dt_ref[0]
        k = pl.program_id(0)
        r = r_c[...]
        zidx = jax.lax.broadcasted_iota(jnp.int32, (block, ny, nx), 0)
        # block-edge neighbor planes: direct reads of the adjacent rho
        # planes, except at the device boundary where the ppermute
        # plane substitutes (for one device it equals the wrap)
        lo_plane = jnp.where(k == 0, e_lo[...], r_lop[...])
        hi_plane = jnp.where(k == m - 1, e_hi[...], r_hip[...])
        r_up = jnp.where(zidx == block - 1, hi_plane, roll_m1(r, 0))
        r_dn = jnp.where(zidx == 0, lo_plane, roll_p1(r, 0))
        vz = vz_c[...]
        v_lo_plane = jnp.where(k == 0, ve_lo[...], vz_lop[...])
        v_hi_plane = jnp.where(k == m - 1, ve_hi[...], vz_hip[...])
        vz_up = jnp.where(zidx == block - 1, v_hi_plane, roll_m1(vz, 0))
        vz_dn = jnp.where(zidx == 0, v_lo_plane, roll_p1(vz, 0))

        rxp = roll_m1(r, 2)
        vfx = (vx[...] + roll_m1(vx[...], 2)) * 0.5
        fx = jnp.where(vfx >= 0, r, rxp) * (dt * vfx * area_x)
        fx = fx * mx[...]

        ryp = roll_m1(r, 1)
        vfy = (vy[...] + roll_m1(vy[...], 1)) * 0.5
        fy = jnp.where(vfy >= 0, r, ryp) * (dt * vfy * area_y)
        fy = fy * my[...]

        vfz_hi = (vz + vz_up) * 0.5
        fz = jnp.where(vfz_hi >= 0, r, r_up) * (dt * vfz_hi * area_z)
        fz = fz * mzu[...]
        vfz_lo = (vz_dn + vz) * 0.5
        fzd = jnp.where(vfz_lo >= 0, r_dn, r) * (dt * vfz_lo * area_z)
        fzd = fzd * mzd[...]

        # accumulate in the XLA body's slot order: z-, y-, x-, x+, y+, z+
        flux = fzd
        flux = flux + roll_p1(fy, 1)
        flux = flux + roll_p1(fx, 2)
        flux = flux - fx
        flux = flux - fy
        flux = flux - fz
        out[...] = r + flux * inv_vol

    cspec = pl.BlockSpec(
        (block, ny, nx), lambda k, *_: (k, 0, 0), memory_space=pltpu.VMEM
    )
    lospec = pl.BlockSpec(
        (1, ny, nx), lambda k, *_: ((k * block - 1) % nzl, 0, 0),
        memory_space=pltpu.VMEM,
    )
    hispec = pl.BlockSpec(
        (1, ny, nx), lambda k, *_: (((k + 1) * block) % nzl, 0, 0),
        memory_space=pltpu.VMEM,
    )
    espec = pl.BlockSpec(
        (1, ny, nx), lambda k, *_: (0, 0, 0), memory_space=pltpu.VMEM
    )
    mxspec = pl.BlockSpec((1, 1, nx), lambda k, *_: (0, 0, 0), memory_space=pltpu.VMEM)
    myspec = pl.BlockSpec((1, ny, 1), lambda k, *_: (0, 0, 0), memory_space=pltpu.VMEM)
    mzspec = pl.BlockSpec((block, 1, 1), lambda k, *_: (k, 0, 0), memory_space=pltpu.VMEM)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_STEP_VMEM_BUDGET
        )
    call = pl.pallas_call(
        kernel,
        name="advection_blocked_direct",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[
                cspec, lospec, hispec, espec, espec,   # rho + neighbor planes
                cspec, cspec,                          # vx, vy
                cspec, lospec, hispec, espec, espec,   # vz + neighbor planes
                mxspec, myspec, mzspec, mzspec,
            ],
            out_specs=cspec,
        ),
        out_shape=jax.ShapeDtypeStruct((nzl, ny, nx), jnp.float32),
        interpret=interpret,
        **kwargs,
    )

    def update(rho, edge_lo, edge_hi, vx, vy, vz, vz_edge_lo, vz_edge_hi,
               mx, my, mz_up, mz_dn, dt):
        dt_arr = jnp.asarray(dt, jnp.float32).reshape(1)
        return call(dt_arr, rho, rho, rho, edge_lo, edge_hi, vx, vy,
                    vz, vz, vz, vz_edge_lo, vz_edge_hi,
                    mx, my, mz_up, mz_dn)

    return update


def run_ping_pong(step, x, steps):
    """``steps`` successive ``step``s of ``x`` (``steps`` a traced int32),
    two per loop iteration: an odd count's first step runs in a
    ``lax.cond``, then ``steps // 2`` iterations each run
    ``step(step(a))``.

    A kernel cannot write its result into the buffer it is still
    reading, so one step per iteration (``fori_loop(0, steps, step, x)``)
    makes XLA copy the whole carry before every step.  With two, the
    first step writes a loop-local buffer and the second writes back
    into the carry the first has finished reading: the buffers ping-pong
    and the loop body holds no copy.  (An explicit ``(a, b)`` carry
    compiles to the same module; the compiler drops the unread ``b``.)
    The odd step goes before the loop rather than after it: after it,
    the TPU compile copies the loop's result twice more on every call to
    feed the ``cond``."""
    x = jax.lax.cond(steps % 2 == 1, step, lambda r: r, x)
    return jax.lax.fori_loop(0, steps // 2, lambda _, a: step(step(a)), x)


def fused_run_fits(nzl: int, ny: int, nx: int) -> bool:
    """Whether the whole-block multi-step kernel's VMEM resident set fits."""
    return _FUSED_ARRAYS * nzl * ny * nx * 4 <= _FUSED_VMEM_BUDGET


def make_fused_run(nzl: int, ny: int, nx: int, area, inv_vol: float,
                   *, interpret: bool = False):
    """Returns ``run(rho, vx, vy, vz, mx, my, mz_up, mz_dn, dt, steps) ->
    new_rho`` advancing ``steps`` timesteps in ONE kernel launch with every
    array resident in VMEM (temporal blocking taken to its limit: zero HBM
    traffic inside the step loop, so the stencil runs compute-bound instead
    of bandwidth-bound).

    Single-device blocks only: z-neighbors are whole-array rolls, which is
    exactly the one-device degenerate ring of parallel/dense.py::HaloExtend
    (wrapping planes; non-periodic z is handled by the same face masks).
    Per-step arithmetic mirrors make_flux_update with the loop-invariant
    parts (face velocities, upwind masks, dt*v_face*area*mask weights)
    hoisted out of the step loop; the hoists are value-preserving (masks
    are exactly 0/1), so the result matches applying the one-step kernel
    ``steps`` times bit for bit (up to the sign of zero on masked faces).
    ``steps`` is a runtime scalar — no retrace per step count."""
    area_x, area_y, area_z = (float(a) for a in area)
    inv_vol = float(inv_vol)
    roll_m1, roll_p1 = _make_rolls(interpret)

    def kernel(dt_ref, steps_ref, rho_ref, vx_ref, vy_ref, vz_ref,
               mx_ref, my_ref, mzu_ref, mzd_ref, out_ref, scr_ref):
        dt = dt_ref[0]
        steps = steps_ref[0]
        mx, my = mx_ref[...], my_ref[...]
        mzu, mzd = mzu_ref[...], mzd_ref[...]
        vx, vy, vz = vx_ref[...], vy_ref[...], vz_ref[...]
        # loop-invariant hoists: face velocities, their upwind-side masks,
        # and the full face weight dt*v_face*area*mask — per step only the
        # upwind select and one multiply remain per direction (values match
        # the one-step kernel: masks are exactly 0/1, so folding them into
        # the weight is exact)
        vfx = (vx + roll_m1(vx, 2)) * 0.5
        vfy = (vy + roll_m1(vy, 1)) * 0.5
        vfz_hi = (vz + roll_m1(vz, 0)) * 0.5
        vfz_lo = (roll_p1(vz, 0) + vz) * 0.5
        sel_x, sel_y = vfx >= 0, vfy >= 0
        sel_zhi, sel_zlo = vfz_hi >= 0, vfz_lo >= 0
        wx = (dt * vfx * area_x) * mx
        wy = (dt * vfy * area_y) * my
        wzu = (dt * vfz_hi * area_z) * mzu
        wzd = (dt * vfz_lo * area_z) * mzd

        def one_step(src_ref, dst_ref):
            r = src_ref[...]
            fx = jnp.where(sel_x, r, roll_m1(r, 2)) * wx
            fy = jnp.where(sel_y, r, roll_m1(r, 1)) * wy
            fz = jnp.where(sel_zhi, r, roll_m1(r, 0)) * wzu
            fzd = jnp.where(sel_zlo, roll_p1(r, 0), r) * wzd
            flux = fzd
            flux = flux + roll_p1(fy, 1)
            flux = flux + roll_p1(fx, 2)
            flux = flux - fx
            flux = flux - fy
            flux = flux - fz
            dst_ref[...] = r + flux * inv_vol

        out_ref[...] = rho_ref[...]

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
        # the resident set intentionally exceeds the default 16 MB scoped
        # limit — v5e+ has ~128 MB of VMEM and fused_run_fits() gates entry
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_VMEM_BUDGET + 24 * 1024 * 1024
        )
    call = pl.pallas_call(
        kernel,
        name="advection_fused_run",
        in_specs=[smem, smem] + [vmem] * 8,
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((nzl, ny, nx), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((nzl, ny, nx), jnp.float32),
        interpret=interpret,
        **kwargs,
    )

    def run(rho, vx, vy, vz, mx, my, mz_up, mz_dn, dt, steps):
        dt_arr = jnp.asarray(dt, jnp.float32).reshape(1)
        steps_arr = jnp.asarray(steps, jnp.int32).reshape(1)
        return call(dt_arr, steps_arr, rho, vx, vy, vz, mx, my, mz_up, mz_dn)

    return run
