#!/usr/bin/env python
"""North-star benchmark: 3-D advection cell-updates/sec/chip.

Runs the advection workload (models/advection.py, semantics of the
reference's tests/advection) on the available accelerator and compares
against the CPU denominator required by BASELINE.md: the reference itself
(dccrg + MPI + Zoltan) cannot be built in this image, so the denominator is
tools/cpu_baseline.cpp — the same per-cell upwind scheme with the
reference's AoS 9-double cell layout and neighbor indirection, g++ -O3
-fopenmp over all host cores (documented in BASELINE.md's protocol as the
locally-measured stand-in).

Measurements (BASELINE.md "Measurement protocol" steps 2-3), all in this
one process on the TPU (no child process touches JAX):

* headline: uniform 128x128x64 grid, whole-block fused Pallas kernel;
* refined / refined3: two- and three-level AMR grids (the reference's
  flagship configuration, tests/game_of_life/refined_scalability3d.cpp
  analogue) on the fast path the dispatch picks;
* large: a >VMEM 512x512x128 grid on the per-step path (no whole-block
  fusion possible — measures the streaming regime);
* gol, pic, poisson, poisson3, vlasov: the other workloads at bench size.

With no TPU it exits non-zero and prints no headline.  Prints one JSON
line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": ...}
"""
import json
import os
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# benchmark configuration: 3-D advection, f32 on accelerator (the reference
# is f64-on-CPU; f32 is the TPU-native precision choice and is recorded)
NX, NY, NZ = 128, 128, 64
STEPS = 5000
REFINED_N = 48          # 48^3 level-0, ball refined -> ~198k cells, 2 levels
REFINED_STEPS = 2000
POISSON_N = 32          # 32^3 level-0, centered ball r 0.25 refined once
REFINED3_N = 16         # 16^3 level-0, broad ball refined twice -> 3 levels
REFINED3_STEPS = 1000
REFINED3_RADII = (0.6, 0.55)  # deep refinement over most of the domain
LARGE = (512, 512, 128)  # f32 density alone is 128 MiB: cannot fit VMEM
LARGE_STEPS = 200
GOL_N = 500              # the reference example's board (game_of_life.cpp)
VLASOV_N = 32            # spatial grid (BASELINE.md config 5)
PIC_N = 1_000_000        # particles (BASELINE.md config 4)
PIC_GRID = 32            # uniform PIC grid edge
PIC_REFINED_N = 200_000  # particles for the refined+balanced variant
PIC_REFINED_GRID = 16    # coarse edge of the refined PIC grid
VLASOV_NV = 8            # velocity bins per dimension (nv^3 per cell)
GOL_TURNS = 20000


#: HBM peak bandwidth per chip generation (GB/s), for roofline fractions
_HBM_PEAK_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5p": 2765.0,
    "TPU v5": 2765.0,
    "TPU v6 lite": 1640.0,
}


def _timed_runs(f, n):
    """Run f n times; returns (all_times, last_out)."""
    import jax

    times = []
    out = None
    for _ in range(n):
        t0 = time.perf_counter()
        out = f()
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return times, out


def _median_of(f, n=8):
    """Run f n times; returns (median_secs, all_times, last_out).

    The median, not the min: a min-of-few estimator swings with any
    rare fast mode of the device timing; the median is the stable
    tenant-visible throughput."""
    import statistics

    times, out = _timed_runs(f, n)
    return statistics.median(times), times, out


def uniform_grid(shape, n_devices=None):
    from dccrg_tpu import CartesianGeometry, Grid, make_mesh

    nx, ny, nz = shape
    return (
        Grid()
        .set_initial_length((nx, ny, nz))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / nx, 1.0 / ny, 1.0 / nz),
        )
        .initialize(mesh=make_mesh(n_devices=n_devices))
    )


def measure_tpu() -> dict:
    import jax
    import numpy as np

    from dccrg_tpu.models import Advection

    g = uniform_grid((NX, NY, NZ))
    n_dev = g.mesh.devices.size
    adv = Advection(g, dtype=np.float32)
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))  # D2H: sync is armed

    jax.block_until_ready(adv.run(state, 2, dt))     # warmup + compile
    secs, times, out = _median_of(lambda: adv.run(state, STEPS, dt), n=8)

    n_cells = NX * NY * NZ
    updates_per_s = n_cells * STEPS / secs
    halo = g.halo(None)
    halo_bytes = halo.bytes_moved({"density": out["density"]}) * STEPS
    return {
        "updates_per_s": updates_per_s,
        "updates_per_s_per_chip": updates_per_s / n_dev,
        "best_updates_per_s_per_chip": n_cells * STEPS / min(times) / n_dev,
        "n_devices": n_dev,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "halo_GBps": halo_bytes / secs / 1e9,
        "secs": secs,
        "times": [round(t, 4) for t in times],
    }


def measure_refined() -> dict:
    """Two-level AMR grid on the refined fast path the dispatch picks —
    the reference's actual use case (cell-by-cell adaptive
    refinement)."""
    import jax
    import numpy as np

    from dccrg_tpu.models import Advection

    g = refined_grid(REFINED_N)
    n_cells = len(g.get_cells())

    adv = Advection(g, dtype=np.float32, allow_dense=False)
    assert adv.boxed is not None, "boxed fast path must engage"
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    jax.block_until_ready(adv.run(state, 2, dt))
    secs, times, _ = _median_of(lambda: adv.run(state, REFINED_STEPS, dt), n=5)
    return {
        "n_cells": n_cells,
        "levels": sorted(adv.boxed.boxes),
        "path": ("boxed" if getattr(adv, "_prefer_boxed", False)
                 else "flat" if adv._flat_run is not None else "boxed"),
        "boxed_vol": sum(int(np.prod(b.shape))
                         for b in adv.boxed.boxes.values()),
        "flat_n_vox": int(getattr(adv, "_flat_n_vox", 0)),
        "updates_per_s": n_cells * REFINED_STEPS / secs,
        "secs": secs,
        "times": [round(t, 4) for t in times],
    }


def ball_refined_grid(n: int, radii: tuple, max_ref: int,
                      center=(0.5, 0.5, 0.5), n_devices=None):
    """Periodic n^3 grid with a ball around ``center`` refined once per
    radius — the shared AMR benchmark construction (one definition
    keeps refined, refined3, poisson, poisson3 and chip_smoke.py
    measuring the same grid family)."""
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh

    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(max_ref)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=n_devices))
    )
    for rad in radii:
        ids = g.get_cells()
        c = g.geometry.get_center(ids)
        r = np.linalg.norm(c - np.asarray(center), axis=1)
        lv = g.mapping.get_refinement_level(ids)
        for cid in ids[(r < rad) & (lv == lv.max())]:
            g.refine_completely(int(cid))
        g.stop_refining()
    return g


def refined_grid(n: int, n_devices=None):
    """The refined configuration: the ball of radius 0.3 around
    (0.3, 0.5, 0.5) of an n^3 periodic grid refined once."""
    return ball_refined_grid(n, (0.3,), 1, center=(0.3, 0.5, 0.5),
                             n_devices=n_devices)


def measure_refined3() -> dict:
    """Three-level AMR grid (VERDICT-r4 item 5's 'done' config): ball
    refined twice, on the path the cost edge picks between the
    multi-level flat whole-run forms (``ops/flat_amr``) and the boxed
    per-level passes — the reference's deep-AMR regime
    (``dccrg_mapping.hpp:316-329`` allows 21 levels)."""
    import jax
    import numpy as np

    from dccrg_tpu.models import Advection

    g = ball_refined_grid(REFINED3_N, REFINED3_RADII, 2)
    ids = g.get_cells()
    n_cells = len(ids)
    levels = sorted(
        int(v) for v in np.unique(g.mapping.get_refinement_level(ids))
    )

    adv = Advection(g, dtype=np.float32, allow_dense=False)
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    steps = REFINED3_STEPS
    runner = lambda: adv.run(state, steps, dt)  # noqa: E731
    path = ("boxed" if getattr(adv, "_prefer_boxed", False)
            else adv._flat_kind or "general")
    jax.block_until_ready(runner())
    secs, times, _ = _median_of(runner, n=5)
    return {
        "n_cells": n_cells,
        "levels": levels,
        "path": path,
        "flat_n_vox": int(getattr(adv, "_flat_n_vox", 0)),
        "boxed_vol": (sum(int(np.prod(b.shape))
                          for b in adv.boxed.boxes.values())
                      if adv.boxed is not None else 0),
        "updates_per_s": n_cells * steps / secs,
        "secs": secs,
        "times": [round(t, 4) for t in times],
    }


def measure_large() -> dict:
    """>VMEM grid: the whole-block fused kernel cannot engage; measures
    the per-step streaming path (HBM-bandwidth regime)."""
    import jax
    import numpy as np

    from dccrg_tpu.models import Advection
    from dccrg_tpu.ops.dense_advection import fused_run_fits

    nx, ny, nz = LARGE
    g = uniform_grid(LARGE)
    adv = Advection(g, dtype=np.float32)
    assert adv.dense is not None
    assert not fused_run_fits(nz // g.mesh.devices.size, ny, nx), (
        "large grid unexpectedly fits VMEM; raise LARGE"
    )
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    jax.block_until_ready(adv.run(state, 2, dt))
    secs, times, _ = _median_of(lambda: adv.run(state, LARGE_STEPS, dt), n=5)
    n_cells = nx * ny * nz
    # HBM roofline against the bytes the ENGAGED kernel actually moves
    # per step, in units of full f32 arrays (n_cells each):
    # * blocked_direct(B): rho+vx+vy+vz in, rho out (5) + the in-kernel
    #   neighbor-plane re-reads of rho and vz (2/B each) = 5 + 4/B;
    # * plane kernel: re-reads the +-1 z views of rho and vz and
    #   re-materializes both halo-extended copies — ~13;
    # * XLA: rolled copies + flux intermediates materialize — ~13 too
    #   (XLA fuses some, the model is the documented upper structure).
    # The useful-work model (what a perfect kernel would move) stays 5;
    # both fractions are reported so the roofline statement is honest.
    kind = adv.dense_kind
    if kind[0] == "blocked_direct":
        arrays_per_step = 5 + 4 / kind[1]
    else:
        arrays_per_step = 13
    moved_bytes = arrays_per_step * 4 * n_cells * LARGE_STEPS
    useful_bytes = 5 * 4 * n_cells * LARGE_STEPS
    peak = _HBM_PEAK_GBPS[jax.devices()[0].device_kind]
    moved_gbps = moved_bytes / secs / 1e9
    useful_gbps = useful_bytes / secs / 1e9
    return {
        "grid": list(LARGE),
        "updates_per_s": n_cells * LARGE_STEPS / secs,
        "secs": secs,
        "times": [round(t, 4) for t in times],
        "dense_kind": list(kind),
        "arrays_per_step_moved": round(arrays_per_step, 2),
        "achieved_HBM_GBps": round(useful_gbps, 1),
        "moved_HBM_GBps": round(moved_gbps, 1),
        "hbm_peak_GBps": peak,
        # useful bytes (the perfect kernel's 5 arrays) over peak
        "hbm_fraction_of_peak": round(useful_gbps / peak, 3),
        # what the engaged kernel actually pushed through HBM over peak —
        # how close the hardware is to its roofline
        "moved_fraction_of_peak": round(moved_gbps / peak, 3),
    }


def gol_grid(n: int, n_devices=None):
    """The reference example's n x n board with the length-1 vertex
    neighborhood (examples/game_of_life.cpp)."""
    from dccrg_tpu import Grid, make_mesh

    return (
        Grid()
        .set_initial_length((n, n, 1))
        .set_neighborhood_length(1)
        .initialize(mesh=make_mesh(n_devices=n_devices))
    )


def measure_gol() -> dict:
    """BASELINE.md config 1: the reference's hello-world —
    examples/game_of_life.cpp's 500x500 board with the length-1 vertex
    neighborhood — on the fused whole-run GoL kernel (ops/gol_kernel.py).
    Reports cell-updates/s vs the C++ CPU denominator
    (tools/cpu_gol_baseline.cpp)."""
    import jax
    import numpy as np

    from dccrg_tpu.models import GameOfLife

    n = GOL_N
    g = gol_grid(n)
    rng = np.random.default_rng(0)
    cells = g.get_cells()
    alive0 = cells[rng.random(len(cells)) < 0.3]
    gol = GameOfLife(g)
    state = gol.new_state(alive_cells=alive0)
    jax.block_until_ready(gol.run(state, 2))
    secs, times, _ = _median_of(lambda: gol.run(state, GOL_TURNS), n=5)
    return {
        "grid": [n, n],
        "turns": GOL_TURNS,
        "fused_kernel": gol._fused_run is not None,
        "updates_per_s": n * n * GOL_TURNS / secs,
        "times_s": [round(t, 4) for t in times],
    }


def measure_pic() -> dict:
    """BASELINE.md config 4: particle push + cell migration — the full
    push/exchange/re-bucket cycle (tests/particles/simple.cpp:285-294) as
    one device-side loop (sort-based re-bucketing, no host round trips)."""
    import jax
    import numpy as np

    from benchmarks.microbench import pic_setup

    length = PIC_GRID
    n_particles = PIC_N
    pc, pts, vel = pic_setup(n_particles, length)
    assert pc._dev_rebucket is not None, "device re-bucket must engage"
    state = pc.new_state(pts)
    steps = 50
    dt = 0.2 / length
    jax.block_until_ready(pc.run(state, 2, velocity=vel, dt=dt)["particles"])

    def one():
        return pc.run(state, steps, velocity=vel, dt=dt)

    secs, times, out = _median_of(one, n=3)
    # a physically valid run: every particle accounted for, none dropped
    assert pc.count(out) == n_particles, "particle conservation violated"
    assert int(np.asarray(out["overflow"])) == 0, "particles dropped"
    result = {
        "n_particles": n_particles,
        "steps": steps,
        "pushes_per_s_incl_migration": n_particles * steps / secs,
        "times_s": [round(t, 4) for t in times],
    }
    # refined + load-balanced variant: the generalized device re-bucket
    # (keyed on the epoch row-id tables) on the reference's actual
    # particle use case — AMR grid, non-block ownership
    # (tests/particles/simple.cpp runs under balance_load as a matter of
    # course).
    n_ref = PIC_REFINED_N
    pr, pts_r, vel_r = pic_setup(
        n_ref, PIC_REFINED_GRID, max_ref=1, refine_ball=0.25,
        balance_method="HSFC", seed=1,
    )
    assert pr._dev_rebucket is not None, (
        "refined+balanced grid must stay on the device re-bucket"
    )
    sr = pr.new_state(pts_r)
    dt_r = 0.1 / PIC_REFINED_GRID
    jax.block_until_ready(
        pr.run(sr, 2, velocity=vel_r, dt=dt_r)["particles"]
    )
    secs_r, times_r, out_r = _median_of(
        lambda: pr.run(sr, steps, velocity=vel_r, dt=dt_r), n=3
    )
    assert pr.count(out_r) == n_ref
    assert int(np.asarray(out_r["overflow"])) == 0
    result["refined_lb"] = {
        "n_cells": len(pr.grid.get_cells()),
        "n_particles": n_ref,
        "n_devices": 1,
        "pushes_per_s_incl_migration": n_ref * steps / secs_r,
        "times_s": [round(t, 4) for t in times_r],
    }
    return result


def measure_poisson(allow_flat: bool = True, use_pallas: bool = True,
                    include_uniform: bool = True,
                    allow_rolled: bool = True) -> dict:
    """BASELINE.md config 3: iterative Poisson solve on a refined grid —
    reports solver cell-iterations/s (matrix-free BiCG sweeps are the
    reference's hot loop, tests/poisson/poisson_solve.hpp).

    ``allow_flat=False, use_pallas=False, allow_rolled=False`` measures
    the raw general gather-table path on the SAME config (the VERDICT-r3
    attribution); with ``allow_rolled=True`` it measures the rolled
    static-offset decomposition of the same operator
    (ops/rolled_gather.py).  The kwargs keep this function the single
    source of truth for the configuration."""
    import jax
    import numpy as np

    from dccrg_tpu.models import Poisson

    g = ball_refined_grid(POISSON_N, (0.25,), 1)
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    rhs = np.sin(2 * np.pi * c[:, 0]) * np.cos(2 * np.pi * c[:, 1])
    rhs -= rhs.mean()

    p = Poisson(g, dtype=np.float32, allow_flat=allow_flat,
                use_pallas=use_pallas,  # f32: the TPU-native precision
                allow_rolled=allow_rolled)
    state = p.initialize_state(rhs)
    iters = 60
    # warmup/compile
    jax.block_until_ready(p.solve(state, max_iterations=2,
                                  stop_residual=0.0)[0]["solution"])

    def one():
        # keep the actual iteration count: the BiCG loop can exit early
        # (dot_r breakdown / residual-increase stop), and the rate must
        # count the iterations that really ran
        out, _res, it = p.solve(state, max_iterations=iters,
                                stop_residual=0.0,
                                stop_after_residual_increase=float("inf"))
        return out["solution"], it

    secs, times, (_, it_ran) = _median_of(one, n=3)
    it_ran = max(int(it_ran), 1)
    n_cells = len(ids)
    out = {
        "n_cells": n_cells,
        "iterations": it_ran,
        "cell_iterations_per_s": n_cells * it_ran / secs,
        "times_s": [round(t, 4) for t in times],
        "path": ("fused" if p._solve_fast is not None
                 else "flat" if p._flat is not None
                 else "rolled" if p._rolled is not None else "gather"),
    }
    if p._flat is None:
        # gather-path attribution data: the table shapes that set the
        # per-iteration gather work
        out["R"] = int(g.epoch.R)
        out["table_DRK"] = list(np.asarray(p.tables.nbr_rows).shape)
    if not include_uniform:
        return out
    # uniform 64^3 variant with a like-for-like C++ BiCG denominator
    # (tools/cpu_poisson_baseline.cpp: same iteration structure, AoS +
    # neighbor indirection, all cores)
    nu = 64
    gu = uniform_grid((nu, nu, nu))
    cu = gu.geometry.get_center(gu.get_cells())
    rhs_u = np.sin(2 * np.pi * cu[:, 0]) * np.cos(2 * np.pi * cu[:, 1])
    pu = Poisson(gu, dtype=np.float32)
    su = pu.initialize_state(rhs_u)
    jax.block_until_ready(pu.solve(su, max_iterations=2,
                                   stop_residual=0.0)[0]["solution"])

    def one_u():
        out_u, _res, it = pu.solve(su, max_iterations=iters,
                                   stop_residual=0.0,
                                   stop_after_residual_increase=float("inf"))
        return out_u["solution"], it

    secs_u, times_u, (_, it_u) = _median_of(one_u, n=3)
    it_u = max(int(it_u), 1)
    try:
        cpu = _cpu_denominator(
            f"poisson_{nu}^3", "cpu_poisson_baseline", [nu, nu, nu, 30]
        )
    except Exception as e:  # noqa: BLE001
        print(f"poisson cpu baseline failed: {e}", file=sys.stderr)
        cpu = None
    rate_u = nu ** 3 * it_u / secs_u
    out["uniform"] = {
        "n_cells": nu ** 3,
        "iterations": it_u,
        "cell_iterations_per_s": rate_u,
        "path": "flat" if pu._flat is not None else "gather",
        "cpu_baseline_cell_iterations_per_s": cpu,
        "vs_baseline": round(rate_u / cpu, 3) if cpu else -1,
        "times_s": [round(t, 4) for t in times_u],
    }
    return out


def measure_poisson3() -> dict:
    """Three-level Poisson on the flat multi-level operator (VERDICT-r4
    item 3: multi-level solves must not fall to the gather path)."""
    import jax
    import numpy as np

    from dccrg_tpu.models import Poisson

    g = ball_refined_grid(16, (0.35, 0.25), 2)
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    rhs = np.sin(2 * np.pi * c[:, 0]) * np.cos(2 * np.pi * c[:, 1])
    rhs -= rhs.mean()
    p = Poisson(g, dtype=np.float32)
    assert p._flat is not None, "3-level grid must stay on the flat path"
    assert p._flat_tables["vl"] == 2
    state = p.initialize_state(rhs)
    iters = 60
    jax.block_until_ready(p.solve(state, max_iterations=2,
                                  stop_residual=0.0)[0]["solution"])

    def one():
        out, _res, it = p.solve(state, max_iterations=iters,
                                stop_residual=0.0,
                                stop_after_residual_increase=float("inf"))
        return out["solution"], it

    secs, times, (_, it_ran) = _median_of(one, n=3)
    it_ran = max(int(it_ran), 1)
    n_cells = len(ids)
    return {
        "n_cells": n_cells,
        "levels": sorted(int(v) for v in np.unique(
            g.mapping.get_refinement_level(ids))),
        "iterations": it_ran,
        "path": "flat_ml",
        "cell_iterations_per_s": n_cells * it_ran / secs,
        "times_s": [round(t, 4) for t in times],
    }


def measure_vlasov() -> dict:
    """BASELINE.md config 5 (Vlasiator stretch): 6-D Vlasov — a velocity
    block per spatial cell; reports phase-space cell-updates/s."""
    import jax
    import numpy as np

    from dccrg_tpu.models import Vlasov

    g = uniform_grid((VLASOV_N,) * 3)
    nv = VLASOV_NV
    v = Vlasov(g, nv=nv, dtype=np.float32)
    state = v.initialize_state()
    dt = np.float32(0.4 * v.max_time_step())
    steps = 50
    jax.block_until_ready(v.run(state, 2, dt)["f"])
    secs, times, _ = _median_of(lambda: v.run(state, steps, dt)["f"], n=3)
    n_phase = VLASOV_N ** 3 * nv ** 3
    try:
        cpu = measure_cpu_vlasov_baseline()
    except Exception as e:  # noqa: BLE001
        print(f"vlasov cpu baseline failed: {e}", file=sys.stderr)
        cpu = None
    rate = n_phase * steps / secs
    return {
        "n_spatial": VLASOV_N ** 3,
        "nv": nv,
        "phase_space_cells": n_phase,
        "phase_updates_per_s": rate,
        "cpu_baseline_phase_updates_per_s": cpu,
        "vs_baseline": round(rate / cpu, 3) if cpu else -1,
        "times_s": [round(t, 4) for t in times],
    }


def _cpu_denominator(key: str, src_name: str, argv: list) -> float:
    """Build (on this host, from the committed source) and run a C++ CPU
    denominator; the measured value is cached in BASELINE_LOCAL.json
    under ``key`` and the binary's host-keyed name, so a value measured
    on another host is never reused."""
    from dccrg_tpu.native import build_keyed

    exe = build_keyed(ROOT / "tools" / (src_name + ".cpp"), src_name,
                      ("-O3", "-march=native", "-fopenmp"))
    key = f"{key}@{exe.name}"
    cache = ROOT / "BASELINE_LOCAL.json"
    data = json.loads(cache.read_text()) if cache.exists() else {}
    if key in data:
        return data[key]
    out = subprocess.run(
        [str(exe)] + [str(a) for a in argv],
        check=True,
        capture_output=True,
        text=True,
    )
    data[key] = float(out.stdout.strip())
    cache.write_text(json.dumps(data, indent=1))
    return data[key]


def measure_cpu_baseline() -> float:
    return _cpu_denominator(
        f"advection_{NX}x{NY}x{NZ}", "cpu_baseline", [NX, NY, NZ, 10]
    )


def measure_cpu_gol_baseline() -> float:
    return _cpu_denominator(
        f"gol_{GOL_N}x{GOL_N}", "cpu_gol_baseline", [GOL_N, GOL_N, 200]
    )


def measure_cpu_vlasov_baseline() -> float:
    """Reference-pattern per-cell f(v) block loops (see
    tools/cpu_vlasov_baseline.cpp) on the measure_vlasov config."""
    return _cpu_denominator(
        f"vlasov_{VLASOV_N}^3_nv{VLASOV_NV}", "cpu_vlasov_baseline",
        [VLASOV_N, VLASOV_N, VLASOV_N, VLASOV_NV, 50],
    )


def _summarize(d: dict) -> dict:
    """Tiny per-workload summary for the compact headline line."""
    s: dict = {"full": "BENCH_DETAIL.json"}

    def pick(name, *path):
        x = d
        for p in path:
            if not isinstance(x, dict) or p not in x:
                return
            x = x[p]
        if isinstance(x, (int, float)) and not isinstance(x, bool):
            s[name] = round(float(x), 3 if abs(x) < 1000 else 1)

    pick("refined_upd_s", "refined", "updates_per_s")
    pick("refined_vs", "refined", "vs_baseline")
    pick("large_upd_s", "large", "updates_per_s")
    pick("large_vs", "large", "vs_baseline")
    pick("gol_upd_s", "gol", "updates_per_s")
    pick("gol_vs", "gol", "vs_baseline")
    pick("poisson_iters_s", "poisson", "cell_iterations_per_s")
    pick("poisson_vs", "poisson", "uniform", "vs_baseline")
    pick("vlasov_upd_s", "vlasov", "phase_updates_per_s")
    pick("vlasov_vs", "vlasov", "vs_baseline")
    pick("pic_push_s", "pic", "pushes_per_s_incl_migration")
    return s


def _emit(record: dict):
    """Persist the full record to BENCH_DETAIL.json (a run artifact,
    not committed); print a compact (<1 kB) headline JSON as the FINAL
    stdout line."""
    (ROOT / "BENCH_DETAIL.json").write_text(json.dumps(record, indent=1))
    compact = {
        "metric": record.get("metric"),
        "value": record.get("value"),
        "unit": record.get("unit"),
        "vs_baseline": record.get("vs_baseline"),
        "detail": _summarize(record.get("detail") or {}),
    }
    line = json.dumps(compact)
    if len(line) > 1000:
        compact["detail"] = {"full": "BENCH_DETAIL.json"}
        line = json.dumps(compact)
    print(line)


#: the per-workload measurements after the headline, in the order they run
_EXTRAS = (("poisson", measure_poisson),
           ("gol", measure_gol),
           ("refined", measure_refined),
           ("refined3", measure_refined3),
           ("pic", measure_pic),
           ("poisson3", measure_poisson3),
           ("vlasov", measure_vlasov),
           ("large", measure_large))


def main() -> int:
    """Measure every workload on the accelerator, in this one process.

    No TPU, or a TPU whose ``device_kind`` has no HBM peak in
    ``_HBM_PEAK_GBPS``: exit non-zero with the reason and print no
    headline.  A failing measurement raises: the bench never reports a
    number it did not measure."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU (jax found {dev.platform}); no measurement",
              file=sys.stderr)
        return 1
    if dev.device_kind not in _HBM_PEAK_GBPS:
        print(f"bench: no HBM peak known for {dev.device_kind!r}",
              file=sys.stderr)
        return 1
    from dccrg_tpu.parallel.exec_cache import enable_persistent_cache

    enable_persistent_cache()
    tpu = measure_tpu()
    extras = {name: fn() for name, fn in _EXTRAS}
    _emit(_build_record(tpu, extras))
    return 0


def _build_record(tpu, extras):
    cpu = measure_cpu_baseline()
    vs = tpu["updates_per_s_per_chip"] / cpu
    detail = {
        "grid": [NX, NY, NZ],
        "steps": STEPS,
        "platform": tpu["platform"],
        "device_kind": tpu.get("device_kind"),
        "n_devices": tpu["n_devices"],
        "halo_GBps": round(tpu["halo_GBps"], 3),
        "cpu_baseline_updates_per_s": cpu,
        "dtype": "float32",
        # run-to-run variance of the headline (value = median of these)
        "headline_times_s": tpu.get("times"),
        "headline_estimator": "median",
        "best_observed_updates_per_s_per_chip": round(
            tpu["best_updates_per_s_per_chip"], 1
        ),
    }
    ref = extras["refined"]
    detail["refined"] = {
        "n_cells": ref["n_cells"],
        "levels": ref["levels"],
        "path": ref["path"],
        "updates_per_s": round(ref["updates_per_s"], 1),
        "vs_baseline": round(ref["updates_per_s"] / cpu, 3),
        "times_s": ref.get("times"),
    }
    r3 = extras["refined3"]
    detail["refined3"] = {
        **{k: r3[k] for k in ("n_cells", "levels", "path",
                              "flat_n_vox", "boxed_vol")},
        "updates_per_s": round(r3["updates_per_s"], 1),
        "vs_baseline": round(r3["updates_per_s"] / cpu, 3),
        "times_s": r3.get("times"),
    }
    lg = extras["large"]
    detail["large"] = {
        "grid": lg["grid"],
        "updates_per_s": round(lg["updates_per_s"], 1),
        "vs_baseline": round(lg["updates_per_s"] / cpu, 3),
        "times_s": lg.get("times"),
        "achieved_HBM_GBps": lg.get("achieved_HBM_GBps"),
        "hbm_peak_GBps": lg.get("hbm_peak_GBps"),
        "hbm_fraction_of_peak": lg.get("hbm_fraction_of_peak"),
    }
    for name in ("poisson", "poisson3", "vlasov", "pic"):
        detail[name] = {
            k: (round(v, 1) if isinstance(v, float) else v)
            for k, v in extras[name].items()
        }
    gl = extras["gol"]
    gol_cpu = measure_cpu_gol_baseline()
    detail["gol"] = {
        "grid": gl["grid"],
        "turns": gl["turns"],
        "fused_kernel": gl["fused_kernel"],
        "updates_per_s": round(gl["updates_per_s"], 1),
        "cpu_baseline_updates_per_s": gol_cpu,
        "vs_baseline": round(gl["updates_per_s"] / gol_cpu, 3),
        "times_s": gl.get("times_s"),
    }
    return {
        "metric": "3d_advection_cell_updates_per_sec_per_chip",
        "value": round(tpu["updates_per_s_per_chip"], 1),
        "unit": "cell-updates/s/chip",
        "vs_baseline": round(vs, 3),
        "detail": detail,
    }


if __name__ == "__main__":
    sys.exit(main())
