"""Blocked fused Vlasov step: all three dimension-split upwind updates
in ONE HBM pass over the phase-space array.

The XLA form (``models/vlasov.py``) materializes the intermediate
distribution after the x and the y split — at Vlasiator-scale payloads
(B = nv^3 f32 per spatial cell) every materialization is a full HBM
round trip, and the step runs ~3x the unavoidable traffic.  This kernel
tiles the spatial z axis into blocks like
the blocked advection kernel (``dense_advection``): each program reads its
``block`` z planes of f plus the two adjacent halo planes, recomputes
the (plane-local) x/y splits on the halo planes in VMEM, and splices
them into the z split — so f is read ~(1 + 2/block) times and written
once per step, with zero intermediate arrays in HBM.

Semantics are the XLA body's exactly (same op order, same scalar
associations), asserted bit-identical by ``tests/test_vlasov.py``.  The
velocity-bin axis B rides the 128-lane minor dimension, x the sublanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dense_advection import _make_rolls

__all__ = ["make_vlasov_step_blocked", "pick_vlasov_block"]

#: scoped-VMEM cap (v5e ~128 MB): per program ~(7*block + 10) plane-sized
#: arrays (double-buffered center in/out, the xy-split recompute of the
#: block + the 4 neighbor/edge planes, and step temporaries)
_VLASOV_VMEM_BUDGET = 100 * 1024 * 1024


def pick_vlasov_block(nzl: int, ny: int, nx: int, B: int) -> int:
    """Largest z-block size (a divisor of nzl, >= 2) whose working set
    fits the scoped-VMEM budget; 0 if none does."""
    plane = ny * nx * B * 4
    for b in (8, 4, 2):
        if nzl % b == 0 and (7 * b + 10) * plane <= _VLASOV_VMEM_BUDGET:
            return b
    return 0


def make_vlasov_step_blocked(nzl: int, ny: int, nx: int, B: int, inv_dx,
                             periodic, *, block: int,
                             interpret: bool = False):
    """Returns ``step(f, edge_lo, edge_hi, vx, vy, vz, dt) -> f'`` over
    one device's ``[nzl, ny, nx, B]`` phase-space block.

    Block-edge neighbor planes are read straight out of ``f`` through
    shifted plane block index maps (planes ``k*block-1`` / ``(k+1)*block``
    mod nzl); ``edge_lo``/``edge_hi`` are the two ppermute-received
    device-boundary planes ``[1, ny, nx, B]``, spliced at programs 0 and
    m-1 (open-z zeroing is the caller's, exactly as the XLA body zeroes
    the extended array's end planes).  ``vx/vy/vz``: ``[1, 1, 1, B]``
    per-bin velocities."""
    assert nzl % block == 0 and block >= 2
    m = nzl // block
    px, py = bool(periodic[0]), bool(periodic[1])
    inv_x, inv_y, inv_z = (float(v) for v in inv_dx)
    roll_m1, roll_p1 = _make_rolls(interpret)

    def kernel(dt_ref, f_c, f_lop, f_hip, e_lo, e_hi,
               vx_ref, vy_ref, vz_ref, out):
        dt = dt_ref[0]
        k = pl.program_id(0)
        vx, vy, vz = vx_ref[...], vy_ref[...], vz_ref[...]

        def split(f, lo, hi, vd, inv_d):
            # the XLA body's split_dim, verbatim association
            flux_hi = jnp.where(vd >= 0, f, hi) * vd
            flux_lo = jnp.where(vd >= 0, lo, f) * vd
            return f - dt * jnp.float32(inv_d) * (flux_hi - flux_lo)

        def xy(f):
            """Plane-local x then y split of ``[p, ny, nx, B]`` planes."""
            p = f.shape[0]
            lo, hi = roll_p1(f, 2), roll_m1(f, 2)
            if not px:
                xi = jax.lax.broadcasted_iota(jnp.int32, (p, ny, nx, B), 2)
                lo = jnp.where(xi == 0, jnp.float32(0.0), lo)
                hi = jnp.where(xi == nx - 1, jnp.float32(0.0), hi)
            f = split(f, lo, hi, vx, inv_x)
            lo, hi = roll_p1(f, 1), roll_m1(f, 1)
            if not py:
                yi = jax.lax.broadcasted_iota(jnp.int32, (p, ny, nx, B), 1)
                lo = jnp.where(yi == 0, jnp.float32(0.0), lo)
                hi = jnp.where(yi == ny - 1, jnp.float32(0.0), hi)
            return split(f, lo, hi, vy, inv_y)

        g = xy(f_c[...])
        # neighbor planes: direct reads of the adjacent f planes, except
        # at the device boundary where the ppermute plane substitutes
        gl = xy(jnp.where(k == 0, e_lo[...], f_lop[...]))
        gh = xy(jnp.where(k == m - 1, e_hi[...], f_hip[...]))
        zi = jax.lax.broadcasted_iota(jnp.int32, (block, ny, nx, B), 0)
        g_up = jnp.where(zi == block - 1, gh, roll_m1(g, 0))
        g_dn = jnp.where(zi == 0, gl, roll_p1(g, 0))
        out[...] = split(g, g_dn, g_up, vz, inv_z)

    cspec = pl.BlockSpec(
        (block, ny, nx, B), lambda k, *_: (k, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    lospec = pl.BlockSpec(
        (1, ny, nx, B), lambda k, *_: ((k * block - 1) % nzl, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    hispec = pl.BlockSpec(
        (1, ny, nx, B), lambda k, *_: (((k + 1) * block) % nzl, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    espec = pl.BlockSpec(
        (1, ny, nx, B), lambda k, *_: (0, 0, 0, 0), memory_space=pltpu.VMEM
    )
    vspec = pl.BlockSpec(
        (1, 1, 1, B), lambda k, *_: (0, 0, 0, 0), memory_space=pltpu.VMEM
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_VLASOV_VMEM_BUDGET
        )
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[cspec, lospec, hispec, espec, espec,
                      vspec, vspec, vspec],
            out_specs=cspec,
        ),
        out_shape=jax.ShapeDtypeStruct((nzl, ny, nx, B), jnp.float32),
        interpret=interpret,
        **kwargs,
    )

    def step(f, edge_lo, edge_hi, vx, vy, vz, dt):
        dt_arr = jnp.asarray(dt, jnp.float32).reshape(1)
        return call(dt_arr, f, f, f, edge_lo, edge_hi, vx, vy, vz)

    return step
