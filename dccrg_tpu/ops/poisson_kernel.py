"""Whole-solve fused BiCG kernel: the flat-voxel Poisson iteration with
every array resident in VMEM for the entire solve.

The XLA flat path (``ops/flat_poisson.py`` inside ``models/poisson.py``'s
``lax.while_loop``) is one dispatch per solve, but each iteration still
runs as a chain of small XLA kernels with HBM round trips between them —
at the bench's 64^3 voxel arrays (1 MiB) the iteration is launch/latency
bound, not bandwidth bound.  This kernel runs the whole loop in one
Pallas launch: the six-roll matvec (and its transpose), the even-parity
pool/broadcast chain for coarse rows, the BiCG dots as in-kernel full
reductions, and the reference's stopping rules (residual target, dot_r
breakdown, best-solution tracking with the semi-convergence stop —
``tests/poisson/poisson_solve.hpp:246-250, 655-683``) — via a masked
``fori_loop``: once the while-condition fails every update freezes, so
the runtime bound is ``max_iterations`` with converged iterations free.

Numerics note: the in-kernel dots reduce in a different association than
XLA's, so solutions agree with the XLA flat path to solver tolerance
(both solve the same system), not bit for bit — unlike the advection /
GoL / Vlasov kernels, whose step arithmetic is association-identical.

Single device, f32, VMEM-resident sizes only; the XLA paths remain the
fallback and the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dense_advection import _make_rolls

__all__ = ["make_bicg_solve", "bicg_fits"]

#: VMEM residency: 6 state arrays + 6 weights + rhs + scaling + 4 masks
#: + ~2 matvec temporaries, double-counted for safety margin
_BICG_ARRAYS = 26
_BICG_VMEM_BUDGET = 96 * 1024 * 1024


def bicg_fits(n_voxels: int) -> bool:
    return _BICG_ARRAYS * n_voxels * 4 <= _BICG_VMEM_BUDGET


def make_bicg_solve(shape, has_coarse: bool, *, interpret: bool = False):
    """Returns ``solve(rhs, x0, wpx, wnx, wpy, wny, wpz, wnz, scaling,
    fine, coarse, orig, solve_m, dot_m, max_iter, stop_res, stop_inc)
    -> (best_x, best_res[1], iters[1])`` over ``shape`` voxel arrays.

    Inputs mirror ``ops/flat_poisson.py``'s tables: the six per-voxel
    face-weight arrays, the diagonal, the fine/coarse/origin masks (f32
    0/1), and the solve/dot masks.  ``rhs``/``x0`` are the pre-lifted
    voxel arrays (already masked the way the model's solve() does)."""
    nz1, ny1, nx1 = shape
    roll_m1, roll_p1 = _make_rolls(interpret)

    def kernel(mi_ref, sr_ref, si_ref, rhs_ref, x0_ref,
               wpx, wnx, wpy, wny, wpz, wnz, scal_ref,
               fine_ref, coarse_ref, orig_ref, solve_ref, dot_ref,
               out_ref, res_ref, it_ref,
               x_s, r0_s, r1_s, p0_s, p1_s, bx_s):
        max_iter = mi_ref[0]
        stop_res = sr_ref[0]
        stop_inc = si_ref[0]
        scaling = scal_ref[...]
        solve_m = solve_ref[...]
        dot_m = dot_ref[...]

        def accumulate(C):
            if not has_coarse:
                return C
            fine = fine_ref[...]
            coarse = coarse_ref[...]
            orig = orig_ref[...]
            s = C * coarse
            s = s + roll_m1(s, 2)
            s = s + roll_m1(s, 1)
            s = s + roll_m1(s, 0)
            s = s * orig
            s = s + roll_p1(s, 2)
            s = s + roll_p1(s, 1)
            s = s + roll_p1(s, 0)
            return fine * C + s

        def apply_fwd(v):
            C = wpx[...] * roll_m1(v, 2) + wnx[...] * roll_p1(v, 2)
            C = C + wpy[...] * roll_m1(v, 1) + wny[...] * roll_p1(v, 1)
            C = C + wpz[...] * roll_m1(v, 0) + wnz[...] * roll_p1(v, 0)
            return scaling * v + accumulate(C)

        def apply_rev(v):
            C = roll_p1(wpx[...] * v, 2) + roll_m1(wnx[...] * v, 2)
            C = C + roll_p1(wpy[...] * v, 1) + roll_m1(wny[...] * v, 1)
            C = C + roll_p1(wpz[...] * v, 0) + roll_m1(wnz[...] * v, 0)
            return scaling * v + accumulate(C)

        def dot(a, b):
            return jnp.sum(jnp.where(dot_m != 0, a * b, jnp.float32(0.0)))

        x = x0_ref[...]
        Ax = apply_fwd(x)
        r0 = jnp.where(solve_m != 0, rhs_ref[...] - Ax, jnp.float32(0.0))
        x_s[...] = x
        bx_s[...] = x
        r0_s[...] = r0
        r1_s[...] = r0
        p0_s[...] = r0
        p1_s[...] = r0
        dot_r0 = dot(r0, r0)
        res0 = jnp.sqrt(jnp.abs(dot_r0))

        def body(t, carry):
            dot_r, res, best_res, it = carry
            # the while-loop condition, evaluated at the top of each
            # iteration; once false every update freezes (active = 0)
            active = (
                (res > stop_res)
                & (dot_r != 0)
                & (res <= best_res * stop_inc)
            )
            a = jnp.where(active, jnp.float32(1.0), jnp.float32(0.0))
            p0 = p0_s[...]
            p1 = p1_s[...]
            Ap0 = jnp.where(solve_m != 0, apply_fwd(p0), jnp.float32(0.0))
            ATp1 = jnp.where(solve_m != 0, apply_rev(p1), jnp.float32(0.0))
            dot_p = dot(p1, Ap0)
            alpha = jnp.where(dot_p != 0, dot_r / dot_p, jnp.float32(0.0))
            alpha = alpha * a
            x = x_s[...] + alpha * p0
            r0 = r0_s[...] - alpha * Ap0
            r1 = r1_s[...] - alpha * ATp1
            new_dot_r = dot(r0, r1)
            beta = jnp.where(dot_r != 0, new_dot_r / dot_r, jnp.float32(0.0))
            # frozen iterations keep p unchanged: p = r + beta*p only
            # when active (r equals its old value then, but beta may
            # differ — freeze explicitly)
            p0n = r0 + beta * p0
            p1n = r1 + beta * p1
            x_s[...] = x
            r0_s[...] = r0
            r1_s[...] = r1
            p0_s[...] = jnp.where(active, p0n, p0)
            p1_s[...] = jnp.where(active, p1n, p1)
            res_new = jnp.sqrt(jnp.abs(dot(r0, r0)))
            res = jnp.where(active, res_new, res)
            better = active & (res_new < best_res)
            bf = jnp.where(better, jnp.float32(1.0), jnp.float32(0.0))
            bx_s[...] = bf * x + (jnp.float32(1.0) - bf) * bx_s[...]
            best_res = jnp.where(better, res_new, best_res)
            it = it + jnp.where(active, jnp.int32(1), jnp.int32(0))
            return (
                jnp.where(active, new_dot_r, dot_r), res, best_res, it,
            )

        carry = (dot_r0, res0, res0, jnp.int32(0))
        _dot_r, _res, best_res, it = jax.lax.fori_loop(
            0, max_iter, body, carry
        )
        out_ref[...] = bx_s[...]
        res_ref[0] = best_res
        it_ref[0] = it

    smem_i = pl.BlockSpec(memory_space=pltpu.SMEM)
    smem_f = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_BICG_VMEM_BUDGET
        )
    call = pl.pallas_call(
        kernel,
        in_specs=[smem_i, smem_f, smem_f] + [vmem] * 14,
        out_specs=[
            vmem,
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[pltpu.VMEM((nz1, ny1, nx1), jnp.float32)] * 6,
        out_shape=[
            jax.ShapeDtypeStruct((nz1, ny1, nx1), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )

    def solve(rhs, x0, wpx, wnx, wpy, wny, wpz, wnz, scaling,
              fine, coarse, orig, solve_m, dot_m,
              max_iter, stop_res, stop_inc):
        return call(
            jnp.asarray(max_iter, jnp.int32).reshape(1),
            jnp.asarray(stop_res, jnp.float32).reshape(1),
            jnp.asarray(stop_inc, jnp.float32).reshape(1),
            rhs, x0, wpx, wnx, wpy, wny, wpz, wnz, scaling,
            fine, coarse, orig, solve_m, dot_m,
        )

    return solve
