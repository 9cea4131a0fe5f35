#!/usr/bin/env python
"""Scalability sweep: throughput vs device count — the analogue of the
reference's tests/scalability family and its sweep driver
(tests/scalability/run_tests.py:27-39), which runs ``mpirun -np N`` for a
range of N.  Here N is a virtual CPU device count (the same mechanism the
test suite uses) unless run on a real multi-chip mesh.

Usage: python benchmarks/scalability.py [gol|advection] [--devices 1 2 4 8]
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def run_sweep(workload: str, counts, size: int, turns: int):
    # jax may already be imported; force the virtual CPU mesh via
    # jax.config exactly like tests/conftest.py
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", max(counts))
    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import Advection, GameOfLife

    results = []
    for n_dev in counts:
        mesh = make_mesh(n_devices=n_dev)
        if workload == "gol":
            grid = (
                Grid()
                .set_initial_length((size, size, 1))
                .set_neighborhood_length(1)
                .initialize(mesh=mesh)
            )
            gol = GameOfLife(grid)
            rng = np.random.default_rng(0)
            cells = grid.get_cells()
            state = gol.new_state(alive_cells=cells[rng.random(len(cells)) < 0.3])
            jax.block_until_ready(gol.run(state, 2))
            t0 = time.perf_counter()
            state = gol.run(state, turns)
            jax.block_until_ready(state)
            secs = time.perf_counter() - t0
            n_cells = size * size
        elif workload == "refined":
            # the reference's refined_scalability3d.cpp analogue: a
            # two-level AMR advection sweep (boxed per-level path)
            n = max(8, size // 16)
            nz = max(n_dev * 2, 8)
            grid = (
                Grid()
                .set_initial_length((n, n, nz))
                .set_neighborhood_length(0)
                .set_periodic(True, True, True)
                .set_maximum_refinement_level(1)
                .set_geometry(
                    CartesianGeometry,
                    start=(0.0, 0.0, 0.0),
                    level_0_cell_length=(1.0 / n, 1.0 / n, 1.0 / nz),
                )
                .initialize(mesh=mesh)
            )
            ids = grid.get_cells()
            c = grid.geometry.get_center(ids)
            r = np.linalg.norm(c - 0.5, axis=1)
            for cid in ids[r < 0.3]:
                grid.refine_completely(int(cid))
            grid.stop_refining()
            adv = Advection(grid, dtype=np.float32, allow_dense=False)
            state = adv.initialize_state()
            dt = np.float32(0.4 * adv.max_time_step(state))
            jax.block_until_ready(adv.run(state, 2, dt))
            t0 = time.perf_counter()
            state = adv.run(state, turns, dt)
            jax.block_until_ready(state)
            secs = time.perf_counter() - t0
            n_cells = len(grid.get_cells())
        else:
            grid = (
                Grid()
                .set_initial_length((size, size, n_dev))
                .set_neighborhood_length(0)
                .set_periodic(True, True, True)
                .set_geometry(
                    CartesianGeometry,
                    start=(0.0, 0.0, 0.0),
                    level_0_cell_length=(1.0 / size, 1.0 / size, 1.0 / n_dev),
                )
                .initialize(mesh=mesh)
            )
            adv = Advection(grid, dtype=np.float32)
            state = adv.initialize_state()
            dt = np.float32(0.4 * adv.max_time_step(state))
            jax.block_until_ready(adv.run(state, 2, dt))
            t0 = time.perf_counter()
            state = adv.run(state, turns, dt)
            jax.block_until_ready(state)
            secs = time.perf_counter() - t0
            n_cells = size * size * n_dev
        # halo traffic per count (reference sweep logs report message
        # volume alongside throughput): useful ghost bytes and actual
        # wire bytes of the general ring schedule for a one-f32-field
        # exchange, times the turn count, over the measured wall time
        halo = grid.halo(None)
        probe = {"f": np.zeros((n_dev, grid.epoch.R), np.float32)}
        useful_b = halo.bytes_moved(probe) * turns
        wire_b = halo.wire_bytes(probe) * turns
        row = {
            "devices": n_dev,
            "cells": n_cells,
            "turns": turns,
            "secs": round(secs, 4),
            "cell_updates_per_s": round(n_cells * turns / secs, 1),
            "per_device_per_s": round(n_cells * turns / secs / n_dev, 1),
            "halo_GBps": round(useful_b / secs / 1e9, 4),
            "halo_wire_GBps": round(wire_b / secs / 1e9, 4),
            "ring_distances": len(halo.ring_ks),
        }
        results.append(row)
        print(json.dumps(row))
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("workload", nargs="?", default="gol",
                    choices=["gol", "advection", "refined"])
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--turns", type=int, default=20)
    a = ap.parse_args()
    run_sweep(a.workload, a.devices, a.size, a.turns)
