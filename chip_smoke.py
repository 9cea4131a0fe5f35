#!/usr/bin/env python
"""Bring-up smoke: the ``Grid`` -> ``Advection`` main path on a real TPU.

One process drives the chip throughout.  Each phase builds its grid
with the fluent ``Grid()`` setters and ``initialize(mesh=make_mesh())``,
runs a model through its ``run()`` entry point, and checks the result
against an independent path computed on the same chip:

* ``uniform_large``  512x512x128 periodic advection (33.5 M cells, the
  streaming regime): the blocked-direct Pallas kernel vs the XLA path;
* ``uniform_fused``  128x128x64: the whole-run fused kernel vs XLA;
* ``refined``        48^3 ball-refined two-level grid: the path the
  dispatch picks (flat or boxed) vs the general gather path;
* ``models``         GoL 500^2, Poisson on the fused flat BiCG, Vlasov
  32^3 x 8^3 and PIC 1 M particles, each vs its XLA or host path.

``--chips 4`` runs only the multi-chip path: the ``uniform_large`` and
``refined`` configurations sharded over four chips, under each halo
transport in turn, against the same run on one chip.

Each phase prints one ``phase {...}`` JSON line (engaged path, error,
timings, peak device memory).  The last stdout line is the ok record,
printed only when every phase passed on a TPU and no kernel fell back
to its slower path.  Timings here are smoke timings (compile included
in ``first_s``), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

#: uniform grid of the fused whole-run kernel (fits VMEM)
FUSED = (128, 128, 64)
#: the streaming regime: f32 density alone is 128 MiB, past VMEM
LARGE = (512, 512, 128)
REFINED_N = 48          # 48^3 level-0, ball refined -> ~198k cells, 2 levels
POISSON_N = 32          # 32^3 level-0, centered ball r 0.25 refined once
GOL_N = 500             # the reference example's board (game_of_life.cpp)
VLASOV_N = 32           # spatial grid (BASELINE.md config 5)
VLASOV_NV = 8           # velocity bins per dimension (nv^3 per cell)
PIC_N = 1_000_000       # particles (BASELINE.md config 4)
PIC_GRID = 32           # uniform PIC grid edge
#: relative tolerance of each advection comparison (f32, same scheme)
ADV_TOL = 1e-5
F32_EPS = float(np.finfo(np.float32).eps)


class SmokeFailure(RuntimeError):
    """A phase missed its check: the smoke must not report ok."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ grids


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


def ball_refined_grid(n: int, radii: tuple, max_ref: int,
                      center=(0.5, 0.5, 0.5), n_devices=None):
    """Periodic n^3 grid with a ball around ``center`` refined once per
    radius (the refined advection and the Poisson grids)."""
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


def pic_setup(n_particles: int, length: int):
    """Periodic ``length``^3 grid on one device, uniformly random
    particles, capacity from the actual max occupancy (doubled for drift
    during the run), and the rotating velocity field of the reference's
    particle test.  Returns ``(particles_model, points, velocity)``."""
    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models.particles import Particles

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(1)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(0)
        .set_load_balancing_method("RCB")
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / length,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=1))
    )
    pts = np.random.default_rng(0).uniform(0.0, 1.0, size=(n_particles, 3))
    occ = np.bincount(g.leaves.position(g.get_existing_cell(pts)))
    pc = Particles(g, max_particles_per_cell=2 * int(occ.max()))
    vel = pc.velocity_field(
        lambda c: np.stack(
            [0.5 - c[:, 1], c[:, 0] - 0.5, np.full(len(c), 0.05)], axis=-1
        )
    )
    return pc, pts, vel


# ---------------------------------------------------------------- helpers


def _counters(name: str) -> dict:
    from dccrg_tpu.obs import metrics

    return dict(metrics.report()["counters"].get(name, {}))


def fallback_count() -> int:
    """Kernel falls to a slower path counted so far in this process."""
    return int(sum(_counters("kernel.fallbacks").values()))


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _engaged(model: str, fn):
    """Run ``fn``; returns (output, seconds, path) where path is the
    whole-run path ``run()`` dispatched (``fused.runs{model,path}``)."""
    before = _counters("fused.runs")
    out, secs = _timed(fn)
    after = _counters("fused.runs")
    paths = sorted(
        k.split("path=")[1].split(",")[0]
        for k, v in after.items()
        if f"model={model}" in k and v > before.get(k, 0)
    )
    return out, secs, "+".join(paths) or "step"


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-300))


def _density(adv, state) -> np.ndarray:
    """Per-cell density in sorted cell order, whatever the layout: the
    dense [D, nzl, ny, nx] z-slabs concatenate to global z order."""
    from dccrg_tpu.utils.collectives import fetch

    if adv.dense is not None:
        return fetch(state["density"]).reshape(-1)
    cells = np.sort(adv.grid.get_cells())
    return np.asarray(adv.get_cell_data(state, "density", cells))


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def halo_backend(name: str):
    """Pin ``DCCRG_HALO_BACKEND`` for the halo schedules built inside."""
    prev = os.environ.get("DCCRG_HALO_BACKEND")
    os.environ["DCCRG_HALO_BACKEND"] = name
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("DCCRG_HALO_BACKEND", None)
        else:
            os.environ["DCCRG_HALO_BACKEND"] = prev


def _advect(grid, steps, **kw):
    """Build an f32 Advection on ``grid``, run ``steps`` twice through
    ``run()`` (cold, then warm); returns (model, state0, out, record).
    ``setup_s`` is the host time to build the model and its state;
    ``path`` the whole run the model took (``Advection.path``)."""
    from dccrg_tpu.models import Advection

    t0 = time.perf_counter()
    adv = Advection(grid, dtype=np.float32, **kw)
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    setup = time.perf_counter() - t0
    out, first = _timed(lambda: adv.run(state, steps, dt))
    out, warm = _timed(lambda: adv.run(state, steps, dt))
    return adv, state, out, {"path": adv.path, "setup_s": setup,
                             "first_s": first, "run_s": warm}


def _built(build):
    """(grid, host seconds ``build()`` took)."""
    t0 = time.perf_counter()
    g = build()
    return g, time.perf_counter() - t0


def _compare_advection(name, grid, steps, tol, *, use_pallas, ref_kw,
                       expect=None):
    """Main-path advection on ``grid`` vs ``ref_kw``'s path on the same
    grid: max relative error of density and conservation of mass to f32
    rounding (each step rounds each cell once)."""
    adv, s0, out, rec = _advect(grid, steps, use_pallas=use_pallas)
    if expect is not None:
        expect(adv, rec)
    ref, _, ref_out, ref_rec = _advect(grid, steps, **ref_kw)
    if ref.dense_kind is not None:
        ref_rec["path"] += f"/{ref.dense_kind[0]}"
    m0, m1 = adv.total_mass(s0), adv.total_mass(out)
    rec.update(
        name=name, cells=len(grid.get_cells()), steps=steps,
        ref_path=ref_rec["path"], ref_setup_s=ref_rec["setup_s"],
        ref_first_s=ref_rec["first_s"], ref_run_s=ref_rec["run_s"],
        max_rel_err=_rel_err(_density(adv, out), _density(ref, ref_out)),
        tol=tol, mass_rel_drift=abs(m1 - m0) / abs(m0),
        mass_tol=steps * F32_EPS,
    )
    if adv.dense_kind is not None:
        rec["dense_kind"] = list(adv.dense_kind)
    _check(rec["max_rel_err"] <= tol,
           f"{name}: max rel err {rec['max_rel_err']:.3e} > {tol}")
    _check(rec["mass_rel_drift"] <= rec["mass_tol"],
           f"{name}: mass drift {rec['mass_rel_drift']:.3e}")
    return rec


# ----------------------------------------------------------------- phases


def phase_uniform_large(shape=LARGE, steps=20, *, use_pallas=True,
                        n_devices=None):
    """Streaming regime: the blocked-direct per-step kernel vs XLA."""
    def expect(adv, rec):
        _check(adv.dense_kind[0] == "blocked_direct",
               f"uniform_large: dense kernel {adv.dense_kind}, "
               "expected blocked_direct")
        _check(rec["path"] == "dense", f"uniform_large ran {rec['path']}")

    g, build_s = _built(lambda: uniform_grid(shape, n_devices))
    return dict(_compare_advection(
        "uniform_large", g, steps, ADV_TOL, use_pallas=use_pallas,
        ref_kw={"use_pallas": False}, expect=expect), grid_build_s=build_s)


def phase_uniform_fused(shape=FUSED, steps=200, *, use_pallas=True,
                        n_devices=None):
    """The old headline: the whole-run fused kernel vs XLA."""
    def expect(adv, rec):
        _check(rec["path"] == "fused",
               f"uniform_fused ran {rec['path']}, expected fused")

    g, build_s = _built(lambda: uniform_grid(shape, n_devices))
    return dict(_compare_advection(
        "uniform_fused", g, steps, ADV_TOL, use_pallas=use_pallas,
        ref_kw={"use_pallas": False}, expect=expect), grid_build_s=build_s)


def phase_refined(n=REFINED_N, steps=50, *, use_pallas=True,
                  n_devices=None):
    """Two-level AMR: whatever fast path the dispatch picks vs the
    general gather path."""
    def expect(adv, rec):
        _check(rec["path"] in ("flat", "boxed"),
               f"refined ran {rec['path']}, expected flat or boxed")

    g, build_s = _built(lambda: refined_grid(n, n_devices))
    return dict(_compare_advection(
        "refined", g, steps, ADV_TOL, use_pallas=use_pallas,
        ref_kw={"use_pallas": False, "allow_boxed": False}, expect=expect),
        grid_build_s=build_s)


def _gol(n, turns, use_pallas):
    from dccrg_tpu.models import GameOfLife

    g = gol_grid(n, n_devices=1)
    cells = g.get_cells()
    alive0 = cells[np.random.default_rng(0).random(len(cells)) < 0.3]
    fast = GameOfLife(g, use_pallas=use_pallas)
    ref = GameOfLife(g, use_pallas=False)
    s0 = fast.new_state(alive_cells=alive0)
    out, first, path = _engaged("game_of_life", lambda: fast.run(s0, turns))
    _check(path == "fused", f"gol ran {path}, expected fused")
    ref_out, ref_s, ref_path = _engaged("game_of_life",
                                        lambda: ref.run(s0, turns))
    a, b = set(fast.alive_cells(out)), set(ref.alive_cells(ref_out))
    rec = {"name": "gol", "cells": n * n, "steps": turns, "path": path,
           "first_s": first, "ref_path": ref_path, "ref_first_s": ref_s,
           "alive": len(a), "mismatched_cells": len(a ^ b), "tol": 0}
    _check(not (a ^ b), f"gol: {len(a ^ b)} cells differ from XLA")
    return rec


def _poisson(n, iters, use_pallas):
    """The Poisson configuration: the fused flat BiCG kernel vs
    the XLA flat BiCG, a fixed number of iterations from the same start."""
    import jax

    from dccrg_tpu.models import Poisson

    g = ball_refined_grid(n, (0.25,), 1, n_devices=1)
    ids = np.sort(g.get_cells())
    c = g.geometry.get_center(ids)
    rhs = np.sin(2 * np.pi * c[:, 0]) * np.cos(2 * np.pi * c[:, 1])
    rhs -= rhs.mean()
    fast = Poisson(g, dtype=np.float32, use_pallas=use_pallas)
    ref = Poisson(g, dtype=np.float32, use_pallas=False)
    _check(fast._solve_fast is not None, "poisson: fused BiCG not built")
    _check(ref._flat is not None and ref._solve_fast is None,
           "poisson: reference is not the XLA flat BiCG")
    s0 = fast.initialize_state(rhs)
    kw = dict(max_iterations=iters, stop_residual=0.0,
              stop_after_residual_increase=float("inf"))
    (out, res, it), first = _timed(lambda: fast.solve(s0, **kw))
    _check(fast._solve_fast is not None, "poisson: fused BiCG disabled")
    (ref_out, ref_res, ref_it), ref_s = _timed(lambda: ref.solve(s0, **kw))
    jax.block_until_ready(ref_out)
    sf = np.asarray(g.get_cell_data(out, "solution", ids))
    sr = np.asarray(g.get_cell_data(ref_out, "solution", ids))
    rec = {"name": "poisson", "cells": len(ids), "steps": int(it),
           "path": "fused_bicg", "first_s": first, "ref_path": "xla_flat",
           "ref_first_s": ref_s, "residual": res, "ref_residual": ref_res,
           "max_rel_err": _rel_err(sf, sr), "tol": 1e-3}
    _check(it == ref_it, f"poisson: {it} vs {ref_it} iterations")
    _check(rec["max_rel_err"] <= rec["tol"],
           f"poisson: max rel err {rec['max_rel_err']:.3e}")
    return rec


def _vlasov(n, nv, steps, use_pallas):
    from dccrg_tpu.models import Vlasov
    from dccrg_tpu.utils.collectives import fetch

    g = uniform_grid((n, n, n), n_devices=1)
    fast = Vlasov(g, nv=nv, dtype=np.float32, use_pallas=use_pallas)
    ref = Vlasov(g, nv=nv, dtype=np.float32, use_pallas=False)
    _check(bool(fast._fused_block), "vlasov: blocked kernel not built")
    s0 = fast.initialize_state()
    dt = np.float32(0.4 * fast.max_time_step())
    out, first, path = _engaged("vlasov", lambda: fast.run(s0, steps, dt))
    _check(path == "fused" and fast._fused_block,
           f"vlasov ran {path}, expected fused")
    ref_out, ref_s, ref_path = _engaged("vlasov",
                                        lambda: ref.run(s0, steps, dt))
    m0, m1 = fast.total_mass(s0), fast.total_mass(out)
    rec = {"name": "vlasov", "cells": n ** 3 * nv ** 3, "steps": steps,
           "path": path, "first_s": first, "ref_path": ref_path,
           "ref_first_s": ref_s,
           "max_rel_err": _rel_err(fetch(out["f"]), fetch(ref_out["f"])),
           "tol": ADV_TOL, "mass_rel_drift": abs(m1 - m0) / abs(m0),
           "mass_tol": steps * F32_EPS}
    _check(rec["max_rel_err"] <= ADV_TOL,
           f"vlasov: max rel err {rec['max_rel_err']:.3e}")
    _check(rec["mass_rel_drift"] <= rec["mass_tol"],
           f"vlasov: mass drift {rec['mass_rel_drift']:.3e}")
    return rec


def _pic(n_particles, length, steps):
    """Device-side push + re-bucket loop vs the host-orchestrated
    re-bucket: the same particles, positions equal up to the rounding
    of one fused push per step (positions lie in [0, 1))."""
    pc, pts, vel = pic_setup(n_particles, length)
    _check(pc._dev_rebucket is not None, "pic: device re-bucket not built")
    dt = 0.2 / length
    s0 = pc.new_state(pts)
    out, first = _timed(lambda: pc.run(s0, steps, velocity=vel, dt=dt))
    dev = np.sort(pc.positions(out), axis=0)
    pc._dev_rebucket = None  # the host mechanism, on the same grid
    host_s, t0 = s0, time.perf_counter()
    for _ in range(steps):
        host_s = pc.step(host_s, velocity=vel, dt=dt)
    host = np.sort(pc.positions(host_s), axis=0)
    rec = {"name": "pic", "cells": length ** 3, "particles": n_particles,
           "steps": steps, "path": "device_rebucket", "first_s": first,
           "ref_path": "host_rebucket",
           "ref_first_s": time.perf_counter() - t0,
           "count": pc.count(out),
           "overflow": int(np.asarray(out["overflow"])),
           "max_abs_diff": float(np.abs(dev - host).max()),
           "tol": 4 * steps * float(np.finfo(pc.dtype).eps)}
    _check(rec["count"] == n_particles and rec["overflow"] == 0,
           f"pic: {rec['count']} particles, overflow {rec['overflow']}")
    _check(dev.shape == host.shape and rec["max_abs_diff"] <= rec["tol"],
           f"pic: positions differ by {rec['max_abs_diff']:.3e}")
    return rec


def phase_models(*, gol_n=GOL_N, gol_turns=200, poisson_n=POISSON_N,
                 poisson_iters=30, vlasov_n=VLASOV_N, vlasov_nv=VLASOV_NV,
                 vlasov_steps=10, pic_n=PIC_N, pic_grid=PIC_GRID, pic_steps=3,
                 use_pallas=True):
    """One short call of every other workload, at its chip size, each
    against its XLA or host path."""
    return [
        _gol(gol_n, gol_turns, use_pallas),
        _poisson(poisson_n, poisson_iters, use_pallas),
        _vlasov(vlasov_n, vlasov_nv, vlasov_steps, use_pallas),
        _pic(pic_n, pic_grid, pic_steps),
    ]


def _check_spans(grid, state, n_devices):
    mesh = grid.mesh
    ids = {d.id for d in mesh.devices.flat}
    _check(len(ids) == n_devices,
           f"mesh spans {len(ids)} devices, expected {n_devices}")
    placed = {d.id for d in state["density"].sharding.device_set}
    _check(placed == ids, f"state lives on devices {sorted(placed)}")


def phase_multichip(shape=LARGE, refined_n=REFINED_N, steps=20, *,
                    n_devices=4, backends=("collective", "pallas"),
                    use_pallas=True):
    """Device-count invariance: each configuration sharded over
    ``n_devices`` vs the same run on one device, under each halo
    transport.  The refined grid runs both the path the dispatch picks
    and the general gather path, whose ghost rows cross the ring."""
    recs = []
    one = {}
    for backend in backends:
        with halo_backend(backend):
            for name, build, kws in (
                ("uniform_large", lambda nd: uniform_grid(shape, nd),
                 ({"use_pallas": use_pallas},)),
                ("refined", lambda nd: refined_grid(refined_n, nd),
                 ({"use_pallas": use_pallas},
                  {"use_pallas": False, "allow_boxed": False})),
            ):
                g = build(n_devices)
                g1 = build(1)
                for kw in kws:
                    adv, s0, out, rec = _advect(g, steps, **kw)
                    _check_spans(g, out, n_devices)
                    key = (name, tuple(sorted(kw.items())))
                    if key not in one:
                        one[key] = _advect(g1, steps, **kw)
                    adv1, _, out1, rec1 = one[key]
                    m0, m1 = adv.total_mass(s0), adv.total_mass(out)
                    rec.update(
                        name=f"{name}/{backend}", devices=n_devices,
                        cells=len(g.get_cells()), steps=steps,
                        ref_path=f"{rec1['path']} on 1 device",
                        ref_run_s=rec1["run_s"],
                        max_rel_err=_rel_err(_density(adv, out),
                                             _density(adv1, out1)),
                        tol=ADV_TOL, mass_rel_drift=abs(m1 - m0) / abs(m0),
                        mass_tol=steps * F32_EPS,
                    )
                    if adv.dense is None:
                        rec["halo_backend"] = adv.grid.halo(None).backend
                    _check(rec["max_rel_err"] <= ADV_TOL,
                           f"{rec['name']} {rec['path']}: max rel err "
                           f"{rec['max_rel_err']:.3e} vs 1 device")
                    _check(rec["mass_rel_drift"] <= rec["mass_tol"],
                           f"{rec['name']}: mass drift "
                           f"{rec['mass_rel_drift']:.3e}")
                    recs.append(rec)
    return recs


# ------------------------------------------------------------------- main


def _print_phase(rec: dict) -> None:
    rec = dict(rec, peak_bytes=_peak_bytes(), fallbacks=fallback_count())
    print("phase " + json.dumps(rec), flush=True)
    _check(rec["fallbacks"] == 0,
           f"{rec['name']}: {rec['fallbacks']} kernel fallback(s) counted")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip sharded path")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); refusing to "
              "report a device result", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, "
              f"{len(jax.devices())} found", file=sys.stderr)
        return 1

    from dccrg_tpu import obs
    from dccrg_tpu.parallel import exec_cache

    obs.enable()
    cache = exec_cache.enable_persistent_cache()
    print(f"device {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            for rec in phase_multichip(n_devices=4):
                _print_phase(rec)
        else:
            _print_phase(phase_uniform_large())
            _print_phase(phase_uniform_fused())
            _print_phase(phase_refined())
            for rec in phase_models():
                _print_phase(rec)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print("persistent cache " + json.dumps(
        exec_cache.persistent_cache_counts())
        + f", total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
