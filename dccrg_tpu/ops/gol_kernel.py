"""Whole-run fused game-of-life kernel: a single-device 2-D board
resident in VMEM for the entire run — the hello-world analogue of the
advection whole-block kernel (``dense_advection.make_fused_run``).

The 8-neighbor count is eight rolls of the alive mask (wrap = periodic
boundary; open boundaries zero the wrapped row/column contributions via
iota masks built once), the 2/3 rule two selects, and ``turns`` is a
runtime scalar — one kernel launch for any number of turns with zero HBM
traffic between them.  f32 internally (counts ≤ 8 are exact).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dense_advection import _make_rolls

__all__ = ["make_gol_run", "gol_run_fits"]

_GOL_VMEM_BUDGET = 96 * 1024 * 1024
_GOL_ARRAYS = 8


def gol_run_fits(ny: int, nx: int) -> bool:
    return _GOL_ARRAYS * ny * nx * 4 <= _GOL_VMEM_BUDGET


def make_gol_run(ny: int, nx: int, periodic_x: bool, periodic_y: bool,
                 *, ny_pad: int | None = None, nx_pad: int | None = None,
                 interpret: bool = False):
    """Returns ``run(alive, turns) -> (alive', count')`` over a
    ``(ny, nx)`` f32 board (0.0/1.0); ``count'`` is the neighbor count
    of the final turn (the general path's ``live_neighbor_count``).

    ``ny_pad``/``nx_pad`` (from ``flat_amr.pad_extent``): physical
    extents carrying tile-alignment padding.  Position ``n`` is a high
    halo holding position 0's value and position ``np-1`` a low halo
    holding ``n-1``'s, so every wrap read of the aligned rolls sees the
    same operand the unpadded roll saw — bit-identical updates (the
    flat-AMR kernel's scheme; interior pads evolve separately but are 2+
    positions away from any real read).  Halos refresh at the end of each
    step, x before y so the y-halo rows copy corner values too.  The
    wrapper takes and returns unpadded boards either way."""
    roll_m1, roll_p1 = _make_rolls(interpret)
    nyp = ny if ny_pad is None else int(ny_pad)
    nxp = nx if nx_pad is None else int(nx_pad)
    if (nyp != ny and nyp < ny + 2) or (nxp != nx and nxp < nx + 2):
        raise ValueError("padding must leave room for the two halos")
    pad_x, pad_y = nxp != nx, nyp != ny

    def kernel(turns_ref, a_ref, out_ref, cnt_ref, scr_ref):
        turns = turns_ref[0]
        # wrap-contribution validity, built once (iota needs >= 2 dims)
        xpos = jax.lax.broadcasted_iota(jnp.int32, (nyp, nxp), 1)
        ypos = jax.lax.broadcasted_iota(jnp.int32, (nyp, nxp), 0)
        one = jnp.float32(1.0)
        # neighbor at x+1 invalid for x = nx-1 on open x, etc.
        vxh = one if periodic_x else (xpos != nx - 1).astype(jnp.float32)
        vxl = one if periodic_x else (xpos != 0).astype(jnp.float32)
        vyh = one if periodic_y else (ypos != ny - 1).astype(jnp.float32)
        vyl = one if periodic_y else (ypos != 0).astype(jnp.float32)

        def count(a):
            # rows shifted so each cell sees its y-1 / y / y+1 band
            up = roll_m1(a, 0) * vyh          # neighbor at y+1
            dn = roll_p1(a, 0) * vyl          # neighbor at y-1
            c = up + dn                       # the two dx = 0 neighbors
            for band in (up, a, dn):          # dx = +-1 of all three bands
                c = c + roll_m1(band, 1) * vxh
                c = c + roll_p1(band, 1) * vxl
            return c

        def one_step(src_ref, dst_ref):
            a = src_ref[...]
            c = count(a)
            new = jnp.where(
                c == 3.0, one, jnp.where(c != 2.0, jnp.float32(0.0), a)
            )
            if pad_x:
                new = jnp.where(xpos == nx, new[:, 0:1], new)
                new = jnp.where(xpos == nxp - 1, new[:, nx - 1:nx], new)
            if pad_y:
                new = jnp.where(ypos == ny, new[0:1, :], new)
                new = jnp.where(ypos == nyp - 1, new[ny - 1:ny, :], new)
            dst_ref[...] = new
            cnt_ref[...] = c

        out_ref[...] = a_ref[...]
        cnt_ref[...] = jnp.zeros((nyp, nxp), jnp.float32)

        def body(i, _):
            even = (i % 2) == 0

            @pl.when(even)
            def _():
                one_step(out_ref, scr_ref)

            @pl.when(jnp.logical_not(even))
            def _():
                one_step(scr_ref, out_ref)

            return 0

        jax.lax.fori_loop(0, turns, body, 0)

        @pl.when((turns % 2) == 1)
        def _():
            out_ref[...] = scr_ref[...]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_GOL_VMEM_BUDGET
        )
    call = pl.pallas_call(
        kernel,
        in_specs=[smem, vmem],
        out_specs=[vmem, vmem],
        scratch_shapes=[pltpu.VMEM((nyp, nxp), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((nyp, nxp), jnp.float32),
            jax.ShapeDtypeStruct((nyp, nxp), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )

    def _pad(alive):
        a = jnp.zeros((nyp, nxp), alive.dtype).at[:ny, :nx].set(alive)
        if pad_x:
            a = a.at[:ny, nx].set(alive[:, 0])
            a = a.at[:ny, nxp - 1].set(alive[:, nx - 1])
        if pad_y:
            a = a.at[ny, :].set(a[0, :])
            a = a.at[nyp - 1, :].set(a[ny - 1, :])
        return a

    def run(alive, turns):
        turns_arr = jnp.asarray(turns, jnp.int32).reshape(1)
        if not (pad_x or pad_y):
            return call(turns_arr, alive)
        out, cnt = call(turns_arr, _pad(alive))
        return out[:ny, :nx], cnt[:ny, :nx]

    return run
