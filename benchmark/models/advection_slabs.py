"""The four-chip uniform configuration's model: ``models/advection.py``'s
``Sim``, with what cannot hold a grid that no one chip holds replaced.

- A guard before the grid build: it times the build of a small grid of
  the same kind on the cell's devices and refuses the run, within
  seconds, where that time scaled to the cell's cells would leave no room
  for the window in the run's allowance (a program that builds every
  per-cell table at once takes ~0.65 us and ~300 B of host memory per
  cell, ~6 min and ~160 GB here), rather than be killed for time.
- The cell-set check reads the program's cell ids in chunks, never
  sorting them or building the grid's whole set of ids.
- The comparison advances each sampled input's z-slab on the chip that
  holds it (``references/advection_slabs.py``), never the whole field on
  one chip: ``max_rel_err`` is the whole field's ratio, max over slabs of
  |program - reference| over max over slabs of |reference|.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

from references.advection import max_rel_err
from references.advection_slabs import SlabReference

#: cell ids per chunk of the cell-set check
CHUNK = 1 << 24
#: the guard's grids: a small one that takes the first build's fixed
#: costs, then the timed one
GUARD_WARM = (16, 16, 16)
GUARD_SHAPE = (128, 128, 128)
#: the most seconds a grid build may be projected to take: the run's
#: allowance is run_seconds + 60 s, of which start-up to the devices takes
#: ~20 s and the window 20 s
BUILD_LIMIT_S = 40.0


def _sibling(name: str):
    """``benchmark/models/<name>.py`` as a module."""
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_bench_models_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def guard(n_devices: int, n_cells: int) -> float:
    """Time the build of a ``GUARD_SHAPE`` uniform periodic grid on the
    cell's devices and refuse the run where that time, scaled linearly to
    ``n_cells``, passes ``BUILD_LIMIT_S``.  Returns the projection."""
    import grids

    grids.uniform_grid(GUARD_WARM, n_devices)
    t = time.perf_counter()
    grids.uniform_grid(GUARD_SHAPE, n_devices)
    took = time.perf_counter() - t
    projected = took * n_cells / np.prod(GUARD_SHAPE)
    if projected > BUILD_LIMIT_S:
        raise SystemExit(
            f"refused: a {'x'.join(map(str, GUARD_SHAPE))} grid took "
            f"{took:.3f} s to build, so this configuration's {n_cells} cells "
            f"would take ~{projected:.0f} s, past the {BUILD_LIMIT_S:.0f} s "
            "that the run's allowance leaves for the grid build")
    return projected


class Sim(_sibling("advection").Sim):
    """``models/advection.py``'s ``Sim`` behind the guard, with the cell-set
    check and the comparison done in blocks."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, n_devices: int,
                 clock):
        with clock("guard"):
            guard(n_devices, int(np.prod(cfg["grid"]["shape"])))
        super().__init__(cfg, traffic, seed, n_devices, clock)

    def cells_mismatched(self) -> int:
        """Ids in one of the program's cell set and the grid's level-0
        cells and not in the other.  ``n`` ids that rise strictly from 1
        to ``n`` are exactly the level-0 ids, so the ids are checked for
        their count, their ends and their rise, chunk by chunk; the two
        sets are differenced only where that fails."""
        ids = self.grid.get_cells()
        n = self.layout.n_cells
        same = len(ids) == n > 0 and ids[0] == 1 and ids[-1] == n
        for a in range(0, n - 1, CHUNK):
            if not same:
                break
            hi = min(a + CHUNK, n - 1)
            same = bool((ids[a + 1:hi + 1] > ids[a:hi]).all())
        if same:
            return 0
        return int(len(np.setxor1d(ids, self.layout.cell_ids())))

    def compare(self, samples) -> dict:
        """As ``models/advection.py``'s, the reference in z-blocks; the
        chips advance the first sample while the host checks the cell set.
        The parts' seconds go to the run's log."""
        lim = self.cfg["limits"]
        t0 = time.perf_counter()
        ref = SlabReference(self.cfg["grid"], self.drift, np.float32,
                            self.adv.dense.n_devices, self.steps)
        wants = [ref.run(samples[0][0], self.dt)] if samples else []
        t1 = time.perf_counter()
        mismatch = self.cells_mismatched()
        t2 = time.perf_counter()
        out = {"cells_mismatched": (mismatch, lim["cells_mismatched"])}
        if not mismatch:
            worst = 0.0
            for x_in, x_out in samples:
                want = wants.pop() if wants else ref.run(x_in, self.dt)
                worst = max(worst, max_rel_err(x_out, want))
                del want
            out["max_rel_err"] = (worst, lim["max_rel_err"])
        t3 = time.perf_counter()
        print("compare " + json.dumps(
            {"first_dispatch_s": t1 - t0, "cells_check_s": t2 - t1,
             "references_s": t3 - t2, "compare_s": t3 - t0,
             "end_unix": time.time()}), file=sys.stderr, flush=True)
        return out
