"""Each main-path Pallas kernel compiles for a *described* TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e
2x2 host and the TPU compiler (installed with jax) lowers and compiles
the kernels for it at the widths the chip smoke (``chip_smoke.py``) and
``bench.py`` run them at.  This catches what the Pallas interpreter
cannot — VMEM over the scoped limit, unaligned slices, unsupported
lowerings — before any chip time is spent.

The topology is described only inside the module-scoped fixture below
(never at import or collection): one process at a time may load the
TPU library, and every xdist worker imports this file.
"""
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the kernels run in f32 with 32-bit indices on the chip; conftest's
    # x64 mode makes Mosaic's lowering of their int32 loop counters recurse
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel=None):
    """Compile for the described chip; ``kernel`` is the Pallas name the
    custom call must carry (the name a profiler capture gives its op)."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if kernel is not None:
        assert f"%{kernel}" in text
    return compiled


def test_fused_run_64x128x128(one_chip):
    from dccrg_tpu.ops.dense_advection import fused_run_fits, make_fused_run

    nz, ny, nx = 64, 128, 128
    assert fused_run_fits(nz, ny, nx)
    run = make_fused_run(nz, ny, nx, np.ones(3), 1.0)
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    _compile(run, s(nz, ny, nx), s(nz, ny, nx), s(nz, ny, nx),
             s(nz, ny, nx), s(1, 1, nx), s(1, ny, 1), s(nz, 1, 1),
             s(nz, 1, 1), s(), _spec(one_chip, (), jnp.int32),
             kernel="advection_fused_run")


def test_blocked_direct_128x512x512(one_chip):
    from dccrg_tpu.ops.dense_advection import (
        make_flux_update_blocked_direct,
        pick_step_block,
    )

    nz, ny, nx = 128, 512, 512
    block = pick_step_block(nz, ny, nx)
    assert block >= 2
    upd = make_flux_update_blocked_direct(nz, ny, nx, block, np.ones(3), 1.0)
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    c, p = s(nz, ny, nx), s(1, ny, nx)
    _compile(upd, c, p, p, c, c, c, p, p, s(1, 1, nx), s(1, ny, 1),
             s(nz, 1, 1), s(nz, 1, 1), s(), kernel="advection_blocked_direct")


def _computation(text, name):
    """The instruction lines of HLO computation ``name`` in ``text``."""
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if re.match(rf"(ENTRY )?%{re.escape(name)} ", line))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return lines[start + 1:end]


def test_dense_run_loop_128x512x512(one_chip):
    """The uniform whole run's loop (``run_ping_pong`` around the blocked
    kernel, as in Advection's dense run on one chip): the ``while`` body
    runs the kernel twice and copies no full-size array — one step per
    iteration copies the density before every step."""
    from dccrg_tpu.ops.dense_advection import (
        make_flux_update_blocked_direct,
        pick_step_block,
        run_ping_pong,
    )

    nz, ny, nx = 128, 512, 512
    block = pick_step_block(nz, ny, nx)
    upd = make_flux_update_blocked_direct(nz, ny, nx, block, np.ones(3), 1.0)

    def run(rho, vx, vy, vz, mx, my, mzu, mzd, dt, steps):
        # one device: the halo planes are the block's own wrapped edges
        v_lo, v_hi = vz[-1:], vz[:1]

        def one(r):
            return upd(r, r[-1:], r[:1], vx, vy, vz, v_lo, v_hi,
                       mx, my, mzu, mzd, dt)

        return run_ping_pong(one, rho, steps)

    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    c = s(nz, ny, nx)
    text = _compile(run, c, c, c, c, s(1, 1, nx), s(1, ny, 1), s(nz, 1, 1),
                    s(nz, 1, 1), s(), _spec(one_chip, (), jnp.int32),
                    kernel="advection_blocked_direct").as_text()
    (body_name,) = re.findall(r" while\(.*?body=%([\w.\-]+)", text)
    body = _computation(text, body_name)
    kernels = [ln for ln in body
               if re.match(r"\s*%advection_blocked_direct\S* = .*custom-call\(", ln)]
    copies = [ln for ln in body
              if re.search(rf"= f32\[{nz},{ny},{nx}\]\S* copy\(", ln)]
    assert len(kernels) == 2
    assert copies == []


def test_plane_flux_update(one_chip):
    from dccrg_tpu.ops.dense_advection import make_flux_update

    nz, ny, nx = 32, 128, 128
    upd = make_flux_update(nz, ny, nx, np.ones(3), 1.0)
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    _compile(upd, s(nz + 2, ny, nx), s(nz, ny, nx), s(nz, ny, nx),
             s(nz + 2, ny, nx), s(1, 1, nx), s(1, ny, 1), s(nz, 1, 1),
             s(nz, 1, 1), s(), kernel="advection_plane")


@pytest.mark.parametrize("box, runs", [((116, 116, 116), 1), ((96, 96, 96), 2)])
def test_boxed_moves_refined_levels(one_chip, box, runs):
    """The boxed path's moves between epoch rows and a level's box at the
    refined benchmark grid's two levels: the ball's 116^3 level-1 box
    (one run of leaves a row, ~0.8 M leaves) and the 96^3 level-0 box
    around the ball's hole (two runs a row), planes padded to 8 x 128."""
    from dccrg_tpu.ops.boxed_moves import (
        make_box_gather,
        make_box_scatter,
        moves_fit,
    )

    nz, by, bx = box
    ny, nx = -(-by // 8) * 8, -(-bx // 128) * 128
    n_rows = -(-(nx + 800_992 + nx) // 128) + 1
    assert moves_fit(n_rows, ny, nx)
    tab = _spec(one_chip, (nz, 1, ny * runs * 4), jnp.int32)
    _compile(make_box_gather(nz, ny, nx, runs, n_rows), tab,
             _spec(one_chip, (n_rows, 128)), kernel="boxed_move_gather")
    _compile(make_box_scatter(nz, ny, nx, runs, n_rows), tab,
             _spec(one_chip, (nz, ny, nx)), kernel="boxed_move_scatter")


def test_flat_amr_refined(one_chip):
    """bench.py's 48^3 ball-refined grid: a 96^3 fine-voxel layout, the
    x extent lane-padded to 128 (it fits VMEM).  The unpadded form takes
    ~150 s to compile here (unaligned lane rolls) and is not the
    dispatched one at this size."""
    from dccrg_tpu.ops.flat_amr import (
        flat_amr_fits,
        make_flat_amr_run,
        pad_lane_extent,
    )

    n = 96
    nxp = pad_lane_extent(n)
    assert nxp == 128 and flat_amr_fits(n * n * nxp)
    run = make_flat_amr_run(n, n, n, nx_pad=nxp)
    a = _spec(one_chip, (n, n, n))
    _compile(run, *([a] * 9), _spec(one_chip, ()),
             _spec(one_chip, (), jnp.int32), kernel="advection_flat_run")


def test_flat_ml_pallas(one_chip):
    """The 3-level whole-run kernel at bench.py's refined3 size (16^3
    level 0 refined twice: a 64^3 finest-voxel layout)."""
    from dccrg_tpu.ops.flat_amr import (
        flat_ml_kernel_fits,
        make_flat_ml_run_pallas,
    )

    n, vl = 64, 2
    assert flat_ml_kernel_fits(n ** 3, vl)
    run = make_flat_ml_run_pallas(n, n, n, vl, (True, True))
    a = _spec(one_chip, (n, n, n))
    _compile(lambda *x: run(*x[:9], x[9:11], x[11], x[12]),
             *([a] * 11), _spec(one_chip, ()),
             _spec(one_chip, (), jnp.int32), kernel="advection_flat_ml_run")


def test_gol_500(one_chip):
    from dccrg_tpu.ops.flat_amr import pad_extent
    from dccrg_tpu.ops.gol_kernel import gol_run_fits, make_gol_run

    n = 500
    nxp, nyp = pad_extent(n, 128), pad_extent(n, 8)
    assert gol_run_fits(nyp, nxp)
    run = make_gol_run(n, n, False, False, ny_pad=nyp, nx_pad=nxp)
    _compile(run, _spec(one_chip, (n, n)), _spec(one_chip, (), jnp.int32))


def test_poisson_bicg(one_chip):
    from dccrg_tpu.ops.poisson_kernel import bicg_fits, make_bicg_solve

    shape = (64, 64, 64)
    assert bicg_fits(int(np.prod(shape)))
    solve = make_bicg_solve(shape, True)
    a = _spec(one_chip, shape)
    _compile(solve, *([a] * 14), _spec(one_chip, (), jnp.int32),
             _spec(one_chip, ()), _spec(one_chip, ()))


def test_vlasov_blocked(one_chip):
    from dccrg_tpu.ops.vlasov_kernel import (
        make_vlasov_step_blocked,
        pick_vlasov_block,
    )

    n, B = 32, 8 ** 3
    block = pick_vlasov_block(n, n, n, B)
    assert block >= 2
    step = make_vlasov_step_blocked(n, n, n, B, (n, n, n),
                                    (True, True, True), block=block)
    v = _spec(one_chip, (1, 1, 1, B))
    _compile(step, _spec(one_chip, (n, n, n, B)),
             _spec(one_chip, (1, n, n, B)), _spec(one_chip, (1, n, n, B)),
             v, v, v, _spec(one_chip, ()))


def test_halo_dma_ring_4_chips(topo):
    """The async-DMA halo ring (the default TPU halo transport) on a
    4-chip mesh: each device ships its payload to (d + k) % 4."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dccrg_tpu.parallel.halo_dma import ring_copy
    from dccrg_tpu.parallel.mesh import SHARD_AXIS

    D = 4
    mesh = Mesh(np.array(topo.devices[:D]), (SHARD_AXIS,))
    spec = P(SHARD_AXIS)

    def body(x):
        return ring_copy(x[0], 1, D, interpret=False)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
    x = jax.ShapeDtypeStruct((D, 1024, 128), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    _compile(fn, x)


def test_dense_run_4_chips_512_slabs(topo):
    """The four-chip uniform cell's whole run: a 512x512x2048 grid as four
    z-slabs of 512^3, Advection's dense run body (``run_ping_pong`` around
    the blocked kernel, each slab's edge planes ppermuted around the slab
    ring by ``HaloExtend``) on the 2x2 v5e mesh.  Each chip's share fits
    its 16 GB, the halo goes by collective-permute, and the ``while``
    body runs the kernel twice and copies no full-size slab."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dccrg_tpu.ops.dense_advection import (
        make_flux_update_blocked_direct,
        pick_step_block,
        run_ping_pong,
    )
    from dccrg_tpu.parallel.dense import DenseInfo, HaloExtend
    from dccrg_tpu.parallel.mesh import SHARD_AXIS

    D, nzl, ny, nx = 4, 512, 512, 512
    extend = HaloExtend(DenseInfo(nx=nx, ny=ny, nz=D * nzl, nz_local=nzl,
                                  n_devices=D, periodic=(True,) * 3))
    block = pick_step_block(nzl, ny, nx)
    assert block >= 2
    upd = make_flux_update_blocked_direct(nzl, ny, nx, block, np.ones(3), 1.0)

    def body(zf_up, zf_dn, rho, vx, vy, vz, mx, my, dt, steps):
        rho, vx, vy, vz = rho[0], vx[0], vy[0], vz[0]
        mzu, mzd = zf_up[0][:, None, None], zf_dn[0][:, None, None]
        v_lo, v_hi = extend.planes(vz)

        def one(r):
            r_lo, r_hi = extend.planes(r)
            return upd(r, r_lo, r_hi, vx, vy, vz, v_lo, v_hi, mx, my,
                       mzu, mzd, dt)

        return run_ping_pong(one, rho, steps)[None]

    mesh = Mesh(np.array(topo.devices[:D]), (SHARD_AXIS,))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(SHARD_AXIS),) * 6 + (P(),) * 4,
                       out_specs=P(SHARD_AXIS), check_vma=False)
    slab = NamedSharding(mesh, P(SHARD_AXIS))
    every = NamedSharding(mesh, P())
    c, z = _spec(slab, (D, nzl, ny, nx)), _spec(slab, (D, nzl))
    compiled = _compile(fn, z, z, c, c, c, c, _spec(every, (1, 1, nx)),
                        _spec(every, (1, ny, 1)), _spec(every, ()),
                        _spec(every, (), jnp.int32),
                        kernel="advection_blocked_direct")
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert per_chip <= 16e9
    text = compiled.as_text()
    assert "collective-permute" in text
    (body_name,) = re.findall(r" while\(.*?body=%([\w.\-]+)", text)
    loop = _computation(text, body_name)
    kernels = [ln for ln in loop
               if re.match(r"\s*%advection_blocked_direct\S* = .*custom-call\(", ln)]
    copies = [ln for ln in loop
              if re.search(rf"= f32\[{nzl},{ny},{nx}\]\S* copy\(", ln)]
    assert len(kernels) == 2
    assert copies == []
