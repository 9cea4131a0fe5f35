#!/usr/bin/env python
"""Microbenchmarks mirroring the reference's runtime-printed speed tests:

- geometry query speed (coord->cell, cell->center) — the analogue of
  tests/geometry/cartesian_grid_speed.cpp and
  stretched_cartesian_grid_speed.cpp
- refinement throughput (cells refined/s through the full commit
  pipeline) — the analogue of tests/refine/scalability.cpp

Prints one JSON line per metric.  Host-side work: runs the same anywhere
(the cell-id algebra and AMR commit are host components by design).

Usage: python benchmarks/microbench.py [--n 1000000] [--refine-length 32]
"""
import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax

if __name__ == "__main__":
    # host-side measurements: force the CPU backend like
    # tests/conftest.py — but only when run AS the script: bench.py and
    # chip_smoke.py import pieces of this module (pic_setup) on the TPU
    # and must not be flipped to CPU by an import side effect
    jax.config.update("jax_platforms", "cpu")

import numpy as np


def bench_geometry(n: int):
    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.geometry.stretched import StretchedCartesianGeometry

    g = (
        Grid()
        .set_initial_length((64, 64, 64))
        .set_maximum_refinement_level(3)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0, 1.0, 1.0),
        )
        .initialize(mesh=make_mesh(n_devices=1))
    )
    rng = np.random.default_rng(0)
    coords = rng.uniform(0.0, 64.0, size=(n, 3))
    cells = g.get_cells()
    ids = rng.choice(cells, size=n)

    t0 = time.perf_counter()
    found = g.geometry.get_cell(0, coords)
    t_coord = time.perf_counter() - t0
    assert (found > 0).all()

    t0 = time.perf_counter()
    centers = g.geometry.get_center(ids)
    t_center = time.perf_counter() - t0
    assert np.isfinite(centers).all()

    for name, secs in (("coord_to_cell", t_coord), ("cell_to_center", t_center)):
        print(json.dumps({
            "metric": f"geometry_{name}_queries_per_sec",
            "value": round(n / secs, 1),
            "unit": "queries/s",
        }))

    bounds = [np.linspace(0.0, 64.0, 65) ** 1.1 for _ in range(3)]
    gs = (
        Grid()
        .set_initial_length((64, 64, 64))
        .set_geometry(StretchedCartesianGeometry, coordinates=bounds)
        .initialize(mesh=make_mesh(n_devices=1))
    )
    coords = rng.uniform(0.0, float(bounds[0][-1]), size=(n, 3))
    t0 = time.perf_counter()
    found = gs.geometry.get_cell(0, coords)
    t_s = time.perf_counter() - t0
    assert (found > 0).all()
    print(json.dumps({
        "metric": "stretched_geometry_coord_to_cell_queries_per_sec",
        "value": round(n / t_s, 1),
        "unit": "queries/s",
    }))


def bench_refinement(length: int):
    from dccrg_tpu import Grid, make_mesh

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_maximum_refinement_level(1)
        .set_neighborhood_length(1)
        .initialize(mesh=make_mesh(n_devices=1))
    )
    cells = g.get_cells()
    t0 = time.perf_counter()
    for c in cells:
        g.refine_completely(int(c))
    created = g.stop_refining()
    secs = time.perf_counter() - t0
    print(json.dumps({
        "metric": "refinement_cells_created_per_sec",
        "value": round(len(created) / secs, 1),
        "unit": "cells/s",
        "detail": {"refined": len(cells), "created": len(created), "secs": round(secs, 3)},
    }))

    leaves = g.get_cells()
    t0 = time.perf_counter()
    for c in leaves:
        g.unrefine_completely(int(c))
    g.stop_refining()
    removed = g.get_removed_cells()
    secs = time.perf_counter() - t0
    print(json.dumps({
        "metric": "unrefinement_cells_removed_per_sec",
        "value": round(len(removed) / secs, 1),
        "unit": "cells/s",
        "detail": {"requested": len(leaves), "removed": len(removed), "secs": round(secs, 3)},
    }))

    # the same storms through the vectorized bulk request APIs
    # (identical queue semantics; what adaptation drivers use)
    cells = g.get_cells()
    t0 = time.perf_counter()
    g.refine_completely_many(cells)
    created = g.stop_refining()
    secs = time.perf_counter() - t0
    print(json.dumps({
        "metric": "bulk_refinement_cells_created_per_sec",
        "value": round(len(created) / secs, 1),
        "unit": "cells/s",
        "detail": {"requested": len(cells), "created": len(created),
                   "secs": round(secs, 3)},
    }))
    leaves = g.get_cells()
    t0 = time.perf_counter()
    g.unrefine_completely_many(leaves)
    g.stop_refining()
    removed = g.get_removed_cells()
    secs = time.perf_counter() - t0
    print(json.dumps({
        "metric": "bulk_unrefinement_cells_removed_per_sec",
        "value": round(len(removed) / secs, 1),
        "unit": "cells/s",
        "detail": {"requested": len(leaves), "removed": len(removed),
                   "secs": round(secs, 3)},
    }))


def bench_checkpoint(length: int):
    """Million-cell checkpoint round trip (reference save_grid_data /
    load_grid_data, dccrg.hpp:1089-1716) — payload packing must be
    offset-indexed scatter, not per-cell Python."""
    import os
    import tempfile

    from dccrg_tpu import Grid, make_mesh
    from dccrg_tpu.io.checkpoint import save_grid_data

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(1)
        .initialize(mesh=make_mesh(n_devices=1))
    )
    spec = {"rho": ((), np.float32), "mom": ((3,), np.float32)}
    state = g.new_state(spec)
    cells = g.get_cells()
    rho = np.sin(cells.astype(np.float64)).astype(np.float32)
    state = g.set_cell_data(state, "rho", cells, rho)
    n = len(cells)
    tmpdir = tempfile.TemporaryDirectory()
    path = os.path.join(tmpdir.name, "bench.dc")

    from dccrg_tpu.io.checkpoint import start_loading_grid_data

    t0 = time.perf_counter()
    save_grid_data(g, state, path, spec)
    t_save = time.perf_counter() - t0
    t0 = time.perf_counter()
    loader = start_loading_grid_data(path, spec, n_devices=1)
    t_structure = time.perf_counter() - t0
    t0 = time.perf_counter()
    while loader.continue_loading_grid_data():
        pass
    g2, state2, _ = loader.finish_loading_grid_data()
    t_payload = time.perf_counter() - t0
    np.testing.assert_array_equal(g2.get_cell_data(state2, "rho", cells), rho)
    file_mb = os.path.getsize(path) / 2**20
    tmpdir.cleanup()
    print(json.dumps({
        "metric": "checkpoint_roundtrip_cells_per_sec",
        "value": round(n / (t_save + t_structure + t_payload), 1),
        "unit": "cells/s",
        "detail": {
            "n_cells": n,
            "save_s": round(t_save, 3),
            # grid re-initialization (epoch/neighbor tables) — paid by any
            # 1M-cell grid build, not a property of the file format
            "load_structure_s": round(t_structure, 3),
            # payload read + unpack + device scatter (the format's cost)
            "load_payload_s": round(t_payload, 3),
            "file_mb": round(file_mb, 1),
        },
    }))


def bench_epoch_rebuild(length: int = 64):
    """Full derived-state rebuild (neighbor lists, inverse lists, halo
    schedules, gather tables, iteration masks) — the host-side cost every
    AMR commit and load balance pays (reference: the tails of
    dccrg.hpp:3461-3485 / 3741-4147)."""
    from dccrg_tpu import Grid, make_mesh

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(1)
        .initialize(mesh=make_mesh(n_devices=1))
    )
    n = length**3
    # time the rebuild itself (balance_load skips it when no cell moves,
    # which is guaranteed on the single device this may run on)
    t0 = time.perf_counter()
    g._rebuild()
    secs = time.perf_counter() - t0
    print(json.dumps({
        "metric": "epoch_rebuild_cells_per_sec",
        "value": round(n / secs, 1),
        "unit": "cells/s",
        "detail": {"n_cells": n, "hood": 26, "secs": round(secs, 3)},
    }))


def bench_epoch_churn(length: int = 48,
                      fractions=(0.002, 0.005, 0.01, 0.05), seed: int = 0):
    """Randomized refine/unrefine storms on a refined ball: full
    ``build_epoch`` vs incremental ``build_epoch_delta`` wall time over
    a storm-size sweep (ISSUE 3's acceptance workload).  Storms are
    spatially clustered (a random sub-ball), the shape real AMR churn
    takes — a tracked feature refines where it is, not uniformly at
    random.  Every incremental epoch is asserted table-for-table
    identical to the full build before its timing is reported.

    ``touched_fraction`` in the detail is the delta path's own closure
    accounting (added + removed + one-hood-radius survivors): a storm
    REFINING f of the cells touches ~9f after children and closure
    expansion, and the path falls back above
    ``DCCRG_EPOCH_DELTA_MAX_FRACTION`` (default 25%) of the grid."""
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
    from dccrg_tpu.amr.refinement import commit_adaptation
    from dccrg_tpu.parallel.epoch import build_epoch
    from dccrg_tpu.parallel.epoch_delta import build_epoch_delta
    from dccrg_tpu.utils.verify import compare_epochs

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(1)
        .set_maximum_refinement_level(2)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / length,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=1))
    )
    rng = np.random.default_rng(seed)
    ids = g.get_cells()
    ctr = g.geometry.get_center(ids)
    g.refine_completely_many(ids[np.linalg.norm(ctr - 0.5, axis=1) < 0.2])
    g.stop_refining()

    def full(g):
        return build_epoch(
            g.mapping, g.topology, g.leaves, g.n_devices, g.neighborhoods,
            uniform_geometry=g._uniform_geometry(),
        )

    for frac in fractions:
        ids = g.get_cells()
        n_cells = len(ids)
        ctr = g.geometry.get_center(ids)
        rr = np.linalg.norm(ctr - rng.uniform(0.3, 0.7, 3), axis=1)
        storm = ids[rr < np.quantile(rr, frac)]
        lvl = g.mapping.get_refinement_level(storm)
        # randomized mix: refine what can refine, unrefine a slice of
        # what is already fine
        g.refine_completely_many(storm[lvl < 2])
        fine = storm[lvl == 2]
        if len(fine):
            g.unrefine_completely_many(fine[: max(1, len(fine) // 4)])
        old = g.epoch
        commit_adaptation(g)
        t_delta, t_full = [], []
        e_delta = e_full = None
        touched0 = obs.metrics.counter_value(
            "epoch.delta_cells_touched") or 0
        for _ in range(3):
            t0 = time.perf_counter()
            e_delta = build_epoch_delta(
                old, g.leaves, g.n_devices, g.neighborhoods,
                uniform_geometry=g._uniform_geometry(),
            )
            t_delta.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            e_full = full(g)
            t_full.append(time.perf_counter() - t0)
        touched = ((obs.metrics.counter_value("epoch.delta_cells_touched")
                    or 0) - touched0) // 3
        fell_back = e_delta is None
        if not fell_back:
            compare_epochs(e_delta, e_full)  # bit-identical, always
        g.epoch = e_full
        g._halo_cache = {}
        g._unrefine_cache = None
        d, f = float(np.median(t_delta)), float(np.median(t_full))
        print(json.dumps({
            "metric": f"epoch_churn_speedup_{frac:g}",
            "value": round(f / d, 2) if not fell_back else 1.0,
            "unit": "x (full/delta)",
            "detail": {
                "n_cells": n_cells,
                "storm_cells": int(len(storm)),
                "storm_fraction": round(len(storm) / n_cells, 4),
                "touched_cells": int(touched),
                "touched_fraction": round(touched / max(len(g.leaves), 1), 4),
                "delta_s": round(d, 3),
                "full_s": round(f, 3),
                "fell_back": fell_back,
                "native": os.environ.get("DCCRG_TPU_NATIVE", "1") != "0",
            },
        }))


def churn_compile_summary(length: int = 12, cycles: int = 6, seed: int = 0,
                          n_devices: int = 1) -> dict:
    """Rebuild→first-step latency + cumulative kernel compiles across a
    churn storm sweep (ISSUE 5's acceptance workload).

    Runs the same randomized refine/unrefine churn twice — shape buckets
    + executable cache ON (the default) vs forced-exact shapes
    (``DCCRG_EPOCH_BUCKETS=0``, fresh per-epoch shapes) — and reports,
    per cycle, the wall time from committing the structural change to
    the first model step completing, plus the cumulative trace count.
    With sticky shapes every post-warmup cycle should re-dispatch cached
    executables (near-zero compile cost); with exact shapes every cycle
    retraces."""
    import jax

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import Advection
    from dccrg_tpu.parallel.exec_cache import trace_counts

    def run_variant(bucketed: bool) -> dict:
        prev = os.environ.get("DCCRG_EPOCH_BUCKETS")
        os.environ["DCCRG_EPOCH_BUCKETS"] = "1" if bucketed else "0"
        try:
            g = (
                Grid()
                .set_initial_length((length, length, length))
                .set_neighborhood_length(1)
                .set_periodic(True, True, True)
                .set_maximum_refinement_level(2)
                .set_geometry(
                    CartesianGeometry,
                    start=(0.0, 0.0, 0.0),
                    level_0_cell_length=(1.0 / length,) * 3,
                )
                .initialize(mesh=make_mesh(n_devices=n_devices))
            )
            rng = np.random.default_rng(seed)
            ids = g.get_cells()
            ctr = g.geometry.get_center(ids)
            g.refine_completely_many(
                ids[np.linalg.norm(ctr - 0.5, axis=1) < 0.25]
            )
            g.stop_refining()
            adv = Advection(g, dtype=np.float32, allow_dense=False)
            state = adv.initialize_state()
            dt = np.float32(0.25 * adv.max_time_step(state))
            state = adv.step(state, dt)
            jax.block_until_ready(state["density"])

            lat, compiles, steps_s = [], [], []
            for _ in range(cycles):
                # volume-balanced storm: every refined family is offset
                # by an unrefined one, so the churn exercises rebuilds
                # without monotonic growth (real AMR tracks a feature;
                # it does not inflate the grid 25% per commit)
                ids = g.get_cells()
                lvl = g.mapping.get_refinement_level(ids)
                coarse = ids[lvl < 2]
                pick = rng.choice(len(coarse), size=min(6, len(coarse)),
                                  replace=False)
                g.refine_completely_many(coarse[pick])
                fine = ids[lvl == 2]
                if len(fine):
                    # whole families only, so the unrefine volume really
                    # lands (a lone sibling request cannot commit)
                    parents = np.unique(g.mapping.get_parent(fine))
                    sibs = g.mapping.get_all_children(parents)
                    whole = np.isin(sibs, fine).all(axis=1)
                    fams = sibs[whole]
                    if len(fams):
                        fpick = rng.choice(len(fams),
                                           size=min(6, len(fams)),
                                           replace=False)
                        g.unrefine_completely_many(
                            fams[fpick].reshape(-1)
                        )
                c0 = sum(trace_counts().values())
                t0 = time.perf_counter()
                g.stop_refining()
                adv = Advection(g, dtype=np.float32, allow_dense=False)
                state = adv.initialize_state()
                state = adv.step(state, dt)
                jax.block_until_ready(state["density"])
                lat.append(time.perf_counter() - t0)
                compiles.append(sum(trace_counts().values()) - c0)
                # steady-state step time (post-compile)
                t0 = time.perf_counter()
                state = adv.step(state, dt)
                jax.block_until_ready(state["density"])
                steps_s.append(time.perf_counter() - t0)
            return {
                "rebuild_to_first_step_s": [round(v, 4) for v in lat],
                "compiles_per_cycle": compiles,
                "steady_step_s": [round(v, 5) for v in steps_s],
                "total_compiles": int(sum(compiles)),
                "n_cells": int(len(g.get_cells())),
            }
        finally:
            if prev is None:
                os.environ.pop("DCCRG_EPOCH_BUCKETS", None)
            else:
                os.environ["DCCRG_EPOCH_BUCKETS"] = prev

    out = {
        "length": length,
        "cycles": cycles,
        "n_devices": n_devices,
        "bucketed": run_variant(True),
        "exact_shapes": run_variant(False),
    }
    b, e = out["bucketed"], out["exact_shapes"]
    out["warm_latency_ratio"] = round(
        float(np.median(e["rebuild_to_first_step_s"][1:]))
        / max(float(np.median(b["rebuild_to_first_step_s"][1:])), 1e-9), 2,
    )
    return out


def bench_churn_compile(length: int = 12, cycles: int = 6):
    """Print the :func:`churn_compile_summary` sweep as a bench metric:
    value = warm-cycle latency advantage of sticky shapes (exact-shape
    rebuild→first-step time over bucketed+cached)."""
    s = churn_compile_summary(length=length, cycles=cycles)
    print(json.dumps({
        "metric": "epoch_churn_rebuild_to_first_step_speedup",
        "value": s["warm_latency_ratio"],
        "unit": "x (exact/bucketed, median warm cycle)",
        "detail": s,
    }))


def elastic_summary(length: int = 6, seed: int = 0) -> dict:
    """The cost of elasticity (ISSUE 8): rescale latency from
    checkpoint-commit to the first post-rescale step, split cold vs
    warm persistent-compile-cache, importable so ``bench.py`` folds it
    into ``detail.telemetry.elastic``.

    Four legs on a refined advection grid: full → half → full are the
    FIRST landings of a checkpoint-replayed grid at each device count
    (cold: every landing compiles), then half → full repeats both
    landings with the persistent compilation cache primed (warm:
    ``epoch.recompiles`` stays 0, compiles served from disk).  Requires
    ``JAX_COMPILATION_CACHE_DIR`` in the environment for the warm legs
    to actually warm — without it
    every leg reports cold and ``cache_enabled`` is False.
    """
    import tempfile

    import jax

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
    from dccrg_tpu.models import Advection
    from dccrg_tpu.parallel.exec_cache import persistent_cache_dir
    from dccrg_tpu.resilience import rescale

    spec = {k: ((), np.float32)
            for k in ("density", "vx", "vy", "vz")}

    def build():
        g = (
            Grid()
            .set_initial_length((length, length, length))
            .set_neighborhood_length(0)
            .set_periodic(True, True, True)
            .set_maximum_refinement_level(1)
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(1.0 / length,) * 3,
            )
            .initialize(mesh=make_mesh())
        )
        rng = np.random.default_rng(seed)
        ids = np.sort(g.get_cells())
        for cid in rng.choice(ids, size=max(1, len(ids) // 6),
                              replace=False):
            g.refine_completely(int(cid))
        g.stop_refining()
        adv = Advection(g, dtype=np.float32, allow_dense=False)
        st = adv.initialize_state()
        ids = np.sort(g.get_cells())
        st = adv.set_cell_data(st, "density", ids,
                               rng.uniform(1, 2, len(ids))
                               .astype(np.float32))
        st = g.update_copies_of_remote_neighbors(st)
        return g, adv, st

    def totals():
        rep = obs.metrics.report()
        return (sum(rep["counters"].get("epoch.recompiles", {})
                    .values()),
                sum(rep["counters"].get("epoch.warm_compiles", {})
                    .values()))

    def leg(g, st, target, lineage_dir):
        r0, w0 = totals()
        res = rescale(g, st, spec, target, directory=lineage_dir,
                      user_header=b"bench")
        adv2 = Advection(res.grid, dtype=np.float32, allow_dense=False)
        st2 = adv2.initialize_state()
        ids2 = np.sort(res.grid.get_cells())
        st2 = adv2.set_cell_data(
            st2, "density", ids2,
            np.asarray(res.grid.get_cell_data(res.state, "density",
                                              ids2)))
        st2 = res.grid.update_copies_of_remote_neighbors(st2)
        dt = np.float32(0.25 * adv2.max_time_step(st2))
        t0 = time.perf_counter()
        out = adv2.step(st2, dt)
        jax.block_until_ready(out["density"])
        first_step = time.perf_counter() - t0
        r1, w1 = totals()
        return res.grid, st2, {
            "direction": res.direction,
            "n_devices": res.n_devices_after,
            "commit_s": round(res.commit_s, 4),
            "reland_s": round(res.reland_s, 4),
            "first_step_s": round(first_step, 4),
            "commit_to_first_step_s": round(
                res.commit_s + res.reland_s + first_step, 4),
            "recompiles": int(r1 - r0),
            "warm_compiles": int(w1 - w0),
        }

    g, adv, st = build()
    dt = np.float32(0.25 * adv.max_time_step(st))
    st = adv.step(st, dt)
    jax.block_until_ready(st["density"])
    full = g.n_devices
    half = max(1, full // 2)
    with tempfile.TemporaryDirectory() as td:
        g, st, cold_down = leg(g, st, half, td)   # first landing at half
        g, st, cold_up = leg(g, st, full, td)     # first replayed landing
        g, st, warm_down = leg(g, st, half, td)   # cache primed from here
        g, st, warm_up = leg(g, st, full, td)
    return {
        "length": length,
        "full_devices": full,
        "half_devices": half,
        "cache_enabled": persistent_cache_dir() is not None,
        "cold_down": cold_down,
        "cold_up": cold_up,
        "warm_down": warm_down,
        "warm_up": warm_up,
    }


def ensemble_summary(length: int = 4, steps: int = 16,
                     sizes=(1, 64, 256), ks=(1, 4, 16),
                     seed: int = 0) -> dict:
    """Scenario-multiplexing throughput (ISSUE 9 + 11):
    scenarios·steps/sec per chip for cohort sizes ``sizes`` at deep-
    dispatch depths ``ks`` vs solo stepping, importable so ``bench.py``
    folds it into ``detail.telemetry.ensemble``.

    One GoL grid on the general gather path (the representative
    runtime-argument form); ``B`` independent initial conditions
    admitted into one cohort and stepped through the single compiled
    cohort body, ``k`` interior steps per host dispatch (ISSUE 11's
    deep dispatch — the ``fori_loop`` bodies pay the host round-trip
    once per k steps).  ``solo`` is the same model's own step loop —
    the baseline a tenant would get with the hardware to itself.
    ``amortization`` is the cohort's scenarios·steps/sec over solo's.
    Each (B, k) cell also reports the measured per-member cohort
    memory (``hbm_bytes_per_member`` — broadcast-shared tables counted
    once) beside the pre-ISSUE-11 stacked-tables equivalent, and a
    small oracle-armed round per k reports verify check/mismatch
    counts (``verify``) so the throughput table never outruns the
    bit-identity anchor."""
    import jax

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import GameOfLife
    from dccrg_tpu.serve import Scenario, Scheduler

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / length,) * 3,
        )
        .initialize(mesh=make_mesh())
    )
    g.stop_refining()
    gol = GameOfLife(g, allow_dense=False)
    cells = g.get_cells()
    rng = np.random.default_rng(seed)

    def fresh_state():
        return gol.new_state(
            alive_cells=cells[rng.random(len(cells)) < 0.3]
        )

    def sync(sched):
        for cohort in sched.cohorts.values():
            jax.block_until_ready(cohort._state)

    # solo baseline: the model's own step loop, one scenario
    state = fresh_state()
    s = gol.step(state)
    jax.block_until_ready(s["is_alive"])          # warm the compile
    t0 = time.perf_counter()
    s = state
    for _ in range(steps):
        s = gol.step(s)
    jax.block_until_ready(s["is_alive"])
    solo_s = (time.perf_counter() - t0) / steps
    chips = max(g.n_devices, 1)
    solo_rate = 1.0 / max(solo_s, 1e-12) / chips

    out: dict = {
        "model": "gol",
        "n_devices": g.n_devices,
        "n_cells": int(len(cells)),
        "steps": steps,
        "ks": [int(k) for k in ks],
        "solo_step_s": round(solo_s, 6),
        "solo_scenario_steps_per_s_per_chip": round(solo_rate, 1),
        "cohorts": {},
        "verify": {},
    }
    for B in sizes:
        ent: dict = {"k": {}}
        for k in ks:
            sched = Scheduler(steps_per_dispatch=k)
            iters = max(1, steps // k)
            for i in range(B):
                sched.submit(Scenario(gol, fresh_state(),
                                      k * (iters + 1), tenant=f"t{i}"))
            sched.admit()
            sched.step_once()                 # warm the depth-k body
            sync(sched)
            t0 = time.perf_counter()
            for _ in range(iters):
                sched.step_once()
            sync(sched)
            elapsed = time.perf_counter() - t0
            rate = B * k * iters / max(elapsed, 1e-12) / chips
            cohort = next(iter(sched.cohorts.values()))
            ent["k"][str(k)] = {
                "dispatch_s": round(elapsed / iters, 6),
                "step_s": round(elapsed / (iters * k), 6),
                "scenarios_steps_per_s_per_chip": round(rate, 1),
                "amortization_vs_solo": round(
                    rate / max(solo_rate, 1e-12), 2),
                "hbm_bytes_per_member": cohort.member_hbm_bytes(),
                "hbm_bytes_per_member_stacked_tables":
                    cohort.member_hbm_bytes_stacked_tables(),
                "shared_tables": bool(cohort.shared_args),
            }
        # headline row per cohort size = its deepest dispatch
        deepest = ent["k"][str(max(ks))]
        ent.update({
            "cohort_step_s": deepest["step_s"],
            "scenarios_steps_per_s_per_chip":
                deepest["scenarios_steps_per_s_per_chip"],
            "amortization_vs_solo": deepest["amortization_vs_solo"],
        })
        out["cohorts"][str(B)] = ent
    # oracle sanity per depth: a tiny verified round (the bit-identity
    # anchor must hold at every k the sweep reports numbers for)
    def _verify_totals() -> tuple:
        rep = _registry_report()
        return tuple(
            int(sum(rep["counters"].get(name, {}).values()))
            for name in ("ensemble.verify_checks",
                         "ensemble.verify_mismatches")
        )

    for k in ks:
        c0, m0 = _verify_totals()
        sched = Scheduler(steps_per_dispatch=k, verify=True)
        for i in range(2):
            sched.submit(Scenario(gol, fresh_state(), 2 * k,
                                  tenant=f"v{i}"))
        sched.run()
        c1, m1 = _verify_totals()
        out["verify"][str(k)] = {"checks": c1 - c0,
                                 "mismatches": m1 - m0}
    return out


def wide_halo_summary(length: int = 6, steps: int = 16, B: int = 16,
                      gs=(2, 4), ks=(4, 16), seed: int = 0) -> dict:
    """Exchange amortization sweep (ISSUE 14): scenarios·steps/sec per
    chip for wide-halo cohort bodies (ONE depth-g exchange per g
    interior steps) vs the legacy per-step-exchange bodies, over ghost
    depths ``gs`` × dispatch depths ``ks``.

    Each g gets its own grid (``set_neighborhood_length(g)`` fixes the
    ghost-zone depth) with GoL on a radius-1 Moore sub-hood, so the
    wide budget is exactly g; dispatches run ``cohort.step(k)``
    directly so k past the budget exercises the multi-block form
    (``ceil(k/g)`` exchanges).  The legacy variant is the SAME grid
    and cohort shape with ``DCCRG_ENSEMBLE_WIDE=0`` — the measured
    difference is purely exchange amortization.  Each cell reports the
    cumulative ``halo.exchanges_per_step`` ratio beside the rates; a
    tiny oracle-armed round per g keeps the sweep honest."""
    import os

    import jax

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import GameOfLife
    from dccrg_tpu.parallel import halo
    from dccrg_tpu.serve import Scenario, Scheduler

    moore = [(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1)
             for k in (-1, 0, 1) if (i, j, k) != (0, 0, 0)]
    rng = np.random.default_rng(seed)
    out: dict = {"model": "gol", "B": int(B), "steps": int(steps),
                 "gs": [int(g) for g in gs], "ks": [int(k) for k in ks],
                 "g": {}, "verify": {}}

    def run_cells(gol, wide: bool) -> dict:
        cells = gol.grid.get_cells()
        res: dict = {}
        for k in ks:
            sched = Scheduler()
            iters = max(1, steps // k)
            for i in range(B):
                sched.submit(Scenario(
                    gol,
                    gol.new_state(alive_cells=cells[
                        rng.random(len(cells)) < 0.3]),
                    k * (iters + 1), tenant=f"t{i}"))
            sched.admit()
            cohort = next(iter(sched.cohorts.values()))
            cohort.step(k)                 # warm the (k, g) body
            jax.block_until_ready(cohort._state)
            halo._amortization.clear()
            t0 = time.perf_counter()
            for _ in range(iters):
                cohort.step(k)
            jax.block_until_ready(cohort._state)
            elapsed = time.perf_counter() - t0
            chips = max(gol.grid.n_devices, 1)
            rep = _registry_report()
            res[str(k)] = {
                "dispatch_s": round(elapsed / iters, 6),
                "scenarios_steps_per_s_per_chip": round(
                    B * k * iters / max(elapsed, 1e-12) / chips, 1),
                "exchanges_per_step": rep["gauges"].get(
                    "halo.exchanges_per_step", {}).get("model=gol"),
                "wide": bool(cohort._wide is not None) if wide
                else False,
            }
        return res

    prev = os.environ.get("DCCRG_ENSEMBLE_WIDE")
    for gdepth in gs:
        grid = (
            Grid()
            .set_initial_length((length, length, length))
            .set_neighborhood_length(int(gdepth))
            .set_periodic(True, True, True)
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(1.0 / length,) * 3,
            )
            .initialize(mesh=make_mesh())
        )
        grid.stop_refining()
        grid.add_neighborhood(7, moore)
        try:
            os.environ.pop("DCCRG_ENSEMBLE_WIDE", None)
            wide_gol = GameOfLife(grid, hood_id=7, allow_dense=False)
            wide_cells = run_cells(wide_gol, wide=True)
            os.environ["DCCRG_ENSEMBLE_WIDE"] = "0"
            legacy_gol = GameOfLife(grid, hood_id=7, allow_dense=False)
            legacy_cells = run_cells(legacy_gol, wide=False)
        finally:
            if prev is None:
                os.environ.pop("DCCRG_ENSEMBLE_WIDE", None)
            else:
                os.environ["DCCRG_ENSEMBLE_WIDE"] = prev
        ent: dict = {"k": {}}
        for k in ks:
            w, l = wide_cells[str(k)], legacy_cells[str(k)]
            ent["k"][str(k)] = {
                "wide": w, "legacy": l,
                "speedup": round(
                    w["scenarios_steps_per_s_per_chip"]
                    / max(l["scenarios_steps_per_s_per_chip"], 1e-12),
                    3),
            }
        out["g"][str(gdepth)] = ent
        # oracle-armed round at this depth: the sweep's numbers must
        # never outrun the owned-row bit-identity anchor
        c0 = _counter_total("ensemble.verify_checks")
        m0 = _counter_total("ensemble.verify_mismatches")
        vs = Scheduler(steps_per_dispatch=min(int(gdepth), 4),
                       verify=True)
        cells = wide_gol.grid.get_cells()
        for i in range(2):
            vs.submit(Scenario(
                wide_gol,
                wide_gol.new_state(alive_cells=cells[
                    rng.random(len(cells)) < 0.3]),
                2 * int(gdepth), tenant=f"v{i}"))
        vs.run()
        out["verify"][str(gdepth)] = {
            "checks": _counter_total("ensemble.verify_checks") - c0,
            "mismatches":
                _counter_total("ensemble.verify_mismatches") - m0,
        }
    return out


def bench_wide_halo(length: int = 6, steps: int = 16):
    """Print the :func:`wide_halo_summary` sweep as a bench metric: the
    deepest (g, k) cell's wide-over-legacy throughput ratio."""
    s = wide_halo_summary(length=length, steps=steps)
    gmax, kmax = str(max(int(g) for g in s["gs"])), \
        str(max(int(k) for k in s["ks"]))
    cell = s["g"][gmax]["k"][kmax]
    print(json.dumps({
        "bench": "wide_halo",
        "metric": "wide_over_legacy_speedup",
        "value": cell["speedup"],
        "detail": s,
    }))


def _counter_total(name: str) -> int:
    rep = _registry_report()
    return int(sum(rep["counters"].get(name, {}).values()))


def _registry_report() -> dict:
    from dccrg_tpu import obs

    return obs.metrics.report()


def bench_ensemble(length: int = 4, steps: int = 16):
    """Print the :func:`ensemble_summary` sweep as a bench metric:
    value = scenarios·steps/sec/chip at the largest cohort size and
    deepest dispatch — the serving-throughput headline beside
    cell-updates/sec."""
    s = ensemble_summary(length=length, steps=steps)
    largest = max(s["cohorts"], key=int)
    deepest = max(s["ks"])
    print(json.dumps({
        "metric": "ensemble_scenarios_steps_per_sec_per_chip",
        "value": s["cohorts"][largest]["scenarios_steps_per_s_per_chip"],
        "unit": (f"scenarios*steps/s/chip (cohort {largest}, "
                 f"k={deepest})"),
        "detail": s,
    }))


def cost_summary(length: int = 4, steps: int = 16, B: int = 8,
                 k: int = 4, seed: int = 0) -> dict:
    """Model-priced vs EMA-only scheduling (ISSUE 17): the same
    deadline-mixed burst served twice — once with the fleet cost model
    pricing ``select_k``'s slack clamp (``DCCRG_COST_MODEL=1``, the
    default) and once on the pre-cost cohort-local EMA path
    (``DCCRG_COST_MODEL=0``) — importable so ``bench.py`` folds it into
    ``detail.telemetry.cost``.  The switch is read per call, so the two
    arms flip mid-process with no respawn.

    Per arm: a warm wave compiles the depth-k body (and, armed, trains
    the exact ``(model, sig, k, g, W)`` key past
    ``DCCRG_COST_MIN_SAMPLES``), a solo pace round measures per-step
    seconds, then a burst of ``B`` scenarios — half with deadlines
    affording roughly half their steps at the measured pace, half
    generous — runs under the deadline policy.  Reported per arm:
    deadline misses / miss rate, scenarios·steps/sec per chip, and the
    answering prediction's level and sample count.  The acceptance
    direction: the armed arm must not miss MORE than EMA-only."""
    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import GameOfLife
    from dccrg_tpu.obs import cost
    from dccrg_tpu.serve import Scenario, Scheduler

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / length,) * 3,
        )
        .initialize(mesh=make_mesh())
    )
    g.stop_refining()
    gol = GameOfLife(g, allow_dense=False)
    cells = g.get_cells()
    rng = np.random.default_rng(seed)

    def fresh_state():
        return gol.new_state(
            alive_cells=cells[rng.random(len(cells)) < 0.3]
        )

    chips = max(g.n_devices, 1)

    def run_arm(armed: bool) -> dict:
        os.environ["DCCRG_COST_MODEL"] = "1" if armed else "0"
        cost.model.reset()
        cost.tracker.reset()
        # warm both dispatch depths the burst can reach at the burst's
        # width: the configured k AND the depth-1 body a blown deadline
        # clamps to — otherwise the first arm pays that compile inside
        # its timed window and the arms stop being comparable
        for depth in (k, 1):
            warm = Scheduler(steps_per_dispatch=depth)
            for _ in range(max(cost.min_samples(), 4)):
                warm.submit(Scenario(gol, fresh_state(),
                                     steps if depth == k else 2,
                                     tenant="warm"))
            warm.run()
        # throwaway solo round first: the width-1 body compiles here in
        # whichever arm runs first, so both arms measure a warm pace
        for timed in (False, True):
            pace_sched = Scheduler(steps_per_dispatch=k)
            pace_sched.submit(Scenario(gol, fresh_state(), steps,
                                       tenant="pace"))
            t0 = time.perf_counter()
            pace_sched.run()
            if timed:
                pace = (time.perf_counter() - t0) / steps
        m0 = _counter_total("ensemble.deadline_miss")
        sched = Scheduler(policy="deadline", steps_per_dispatch=k)
        now = time.perf_counter()
        for i in range(B):
            tight = i % 2 == 0
            sched.submit(Scenario(
                gol, fresh_state(), steps, tenant=f"c{i % 2}",
                deadline=now + steps * pace * (0.5 if tight else 50.0),
            ))
        t0 = time.perf_counter()
        sched.run()
        elapsed = time.perf_counter() - t0
        misses = _counter_total("ensemble.deadline_miss") - m0
        est = cost.model.predict("gol") if armed else None
        return {
            "deadline_misses": int(misses),
            "miss_rate": round(misses / B, 3),
            "scenarios_steps_per_s_per_chip": round(
                B * steps / max(elapsed, 1e-12) / chips, 1),
            "elapsed_s": round(elapsed, 6),
            "pace_step_s": round(pace, 6),
            "predict_level": est.level if est is not None else None,
            "predict_n": est.n if est is not None else 0,
        }

    prev = os.environ.get("DCCRG_COST_MODEL")
    try:
        out = {
            "model": "gol",
            "n_devices": g.n_devices,
            "B": B, "k": int(k), "steps": steps,
            "armed": run_arm(True),
            "ema_only": run_arm(False),
        }
    finally:
        if prev is None:
            os.environ.pop("DCCRG_COST_MODEL", None)
        else:
            os.environ["DCCRG_COST_MODEL"] = prev
    out["miss_delta_armed_minus_ema"] = (
        out["armed"]["deadline_misses"]
        - out["ema_only"]["deadline_misses"])
    return out


def bench_cost(length: int = 4, steps: int = 16):
    """Print the :func:`cost_summary` comparison as a bench metric:
    value = deadline misses with the cost model armed (the unit string
    carries the EMA-only count — the acceptance is armed <= EMA)."""
    s = cost_summary(length=length, steps=steps)
    print(json.dumps({
        "metric": "cost_model_deadline_misses",
        "value": s["armed"]["deadline_misses"],
        "unit": (f"misses of {s['B']} (EMA-only "
                 f"{s['ema_only']['deadline_misses']}, k={s['k']})"),
        "detail": s,
    }))


def halo_overlap_summary(steps: int = 20, length: int = 8, reps: int = 3,
                         seed: int = 0) -> dict:
    """Eager vs host-split vs fused split-phase stepping per model
    (gol / advection / vlasov) on the current device mesh (ISSUE 7).

    Three forms of advancing one step:

    * ``eager`` — the blocking step (ghost exchange fused into the
      model's program);
    * ``host_split`` — the source paper's host-orchestrated pattern
      (``start_remote_neighbor_copies`` / eager step / ``wait``): one
      EXTRA host-level refresh rides along per step, so this column is
      an upper bound showing the dispatch overhead the fused form
      removes;
    * ``fused`` — the model's ``overlap=True`` step: start → interior →
      finish → boundary inside ONE compiled program."""
    import jax

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import Advection, GameOfLife, Vlasov

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(1)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_load_balancing_method("RCB")
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / length,) * 3,
        )
        .initialize(mesh=make_mesh())
    )
    ids = g.get_cells()
    ctr = g.geometry.get_center(ids)
    g.refine_completely_many(ids[np.linalg.norm(ctr - 0.5, axis=1) < 0.3])
    g.stop_refining()
    g.balance_load()
    rng = np.random.default_rng(seed)
    cells = g.get_cells()

    def median_step(step, state):
        s = step(state)
        jax.block_until_ready(s)                      # warm the compiles
        times = []
        for _ in range(reps):
            s = state
            t0 = time.perf_counter()
            for _ in range(steps):
                s = step(s)
            jax.block_until_ready(s)
            times.append((time.perf_counter() - t0) / steps)
        return float(np.median(times))

    out: dict = {"n_devices": g.n_devices, "steps": steps,
                 "n_cells": int(len(cells)),
                 "halo_backend": g.halo().backend, "models": {}}

    for model in ("gol", "advection", "vlasov"):
        if model == "gol":
            eager = GameOfLife(g, allow_dense=False)
            fused = GameOfLife(g, overlap=True)
            alive0 = cells[rng.random(len(cells)) < 0.3]
            state_e = eager.new_state(alive_cells=alive0)
            state_f = fused.new_state(alive_cells=alive0)
            field = "is_alive"
            step_e = eager.step
            step_f = fused.step
        elif model == "advection":
            eager = Advection(g, dtype=np.float32, allow_dense=False)
            fused = Advection(g, dtype=np.float32, allow_dense=False,
                              overlap=True)
            state_e = eager.initialize_state()
            state_f = fused.initialize_state()
            dt = np.float32(0.4 * eager.max_time_step(state_e))
            field = "density"
            step_e = lambda s: eager.step(s, dt)
            step_f = lambda s: fused.step(s, dt)
        else:
            eager = Vlasov(g, nv=2, dtype=np.float32)
            fused = Vlasov(g, nv=2, dtype=np.float32, overlap=True)
            state_e = eager.initialize_state()
            state_f = fused.initialize_state()
            dt = np.float32(0.5 * eager.max_time_step())
            field = "f"
            step_e = lambda s, _e=eager, _dt=dt: _e.step(s, _dt)
            step_f = lambda s, _f=fused, _dt=dt: _f.step(s, _dt)

        def step_split(s, _step=step_e, _field=field):
            fields = {_field: s[_field]}
            handle = g.start_remote_neighbor_copy_updates(fields)
            interior = _step(s)
            fields = g.wait_remote_neighbor_copy_updates(fields, handle)
            return {**interior, **fields, _field: interior[_field]}

        rec = {
            "eager_step_s": round(median_step(step_e, state_e), 6),
            "host_split_step_s": round(median_step(step_split, state_e),
                                       6),
            "fused_step_s": round(median_step(step_f, state_f), 6),
        }
        rec["fused_vs_eager"] = round(
            rec["eager_step_s"] / max(rec["fused_step_s"], 1e-12), 3
        )
        out["models"][model] = rec
    return out


def bench_halo_overlap(steps: int = 20, length: int = 8):
    """Print the :func:`halo_overlap_summary` sweep as a bench metric:
    value = the worst fused-vs-eager step ratio across models (>= 1.0
    means the fused split-phase step regressed nothing)."""
    s = halo_overlap_summary(steps=steps, length=length)
    ratios = [m["fused_vs_eager"] for m in s["models"].values()]
    print(json.dumps({
        "metric": "halo_overlap_fused_vs_eager",
        "value": round(min(ratios), 3),
        "unit": "x (eager/fused step latency, worst model)",
        "detail": s,
    }))


def pic_setup(n_particles: int, length: int = 32, *, max_ref: int = 0,
              refine_ball: float | None = None,
              balance_method: str | None = None, seed: int = 0):
    """Shared PIC benchmark fixture (also used by the root bench.py):
    periodic grid, uniformly-random particles, capacity from the actual
    max occupancy (Poisson tails overflow any fixed multiple of the
    mean — doubled for drift during the run), and the rotating velocity
    field of the reference's particle test.  Returns
    ``(particles_model, initial_points, velocity_field)``.

    ``refine_ball``: refine every cell within that radius of the domain
    center (requires ``max_ref >= 1``); ``balance_method``: run a
    ``balance_load`` under the given partitioner after refinement — the
    reference's actual particle use case (AMR + non-block ownership,
    ``tests/particles/simple.cpp``)."""
    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models.particles import Particles

    g = (
        Grid()
        .set_initial_length((length, length, length))
        .set_neighborhood_length(1)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(max_ref)
        .set_load_balancing_method(balance_method or "RCB")
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / length,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=1))
    )
    if refine_ball is not None:
        ids = g.get_cells()
        ctr = g.geometry.get_center(ids)
        rr = np.linalg.norm(ctr - 0.5, axis=1)
        for cid in ids[rr < refine_ball]:
            g.refine_completely(int(cid))
        g.stop_refining()
    if balance_method is not None:
        g.balance_load()
    rng = np.random.default_rng(seed)
    pts = rng.uniform(0.0, 1.0, size=(n_particles, 3))
    occ = np.bincount(g.leaves.position(g.get_existing_cell(pts)))
    pc = Particles(g, max_particles_per_cell=2 * int(occ.max()))
    vel = pc.velocity_field(
        lambda c: np.stack(
            [0.5 - c[:, 1], c[:, 0] - 0.5, np.full(len(c), 0.05)], axis=-1
        )
    )
    return pc, pts, vel


def bench_particles(n_particles: int, length: int = 32):
    """PIC pushes/s INCLUDING migration (ghost exchange + re-bucketing) —
    the full per-step cost of the reference's particle test
    (tests/particles/simple.cpp:285-294), not just the position update."""
    pc, pts, vel = pic_setup(n_particles, length)

    t0 = time.perf_counter()
    state = pc.new_state(pts)
    t_bucket = time.perf_counter() - t0
    steps = 5
    import jax

    state = pc.run(state, 1, velocity=vel, dt=0.2 / length)  # compile
    jax.block_until_ready(state["particles"])
    t0 = time.perf_counter()
    state = pc.run(state, steps, velocity=vel, dt=0.2 / length)
    jax.block_until_ready(state["particles"])
    secs = time.perf_counter() - t0
    assert pc.count(state) == n_particles
    assert int(np.asarray(state.get("overflow", 0))) == 0
    print(json.dumps({
        "metric": "pic_pushes_per_sec_incl_migration",
        "value": round(n_particles * steps / secs, 1),
        "unit": "pushes/s",
        "detail": {
            "n_particles": n_particles,
            "steps": steps,
            "secs": round(secs, 3),
            "initial_bucket_s": round(t_bucket, 3),
            "grid": [length] * 3,
        },
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--refine-length", type=int, default=32)
    ap.add_argument("--checkpoint-length", type=int, default=100)
    ap.add_argument("--particles", type=int, default=1_000_000)
    ap.add_argument("--churn-length", type=int, default=48,
                    help="level-0 edge for the epoch-churn sweep "
                         "(48^3 + refined ball > 130k cells)")
    args = ap.parse_args()
    bench_geometry(args.n)
    bench_refinement(args.refine_length)
    bench_checkpoint(args.checkpoint_length)
    bench_epoch_rebuild()
    bench_epoch_churn(args.churn_length)
    bench_churn_compile()
    bench_halo_overlap()
    bench_ensemble()
    bench_wide_halo()
    bench_cost()
    bench_particles(args.particles)


if __name__ == "__main__":
    main()
