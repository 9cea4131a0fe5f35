"""Grid builders of the benchmark's configurations.

Copied from ``bench.py`` (``uniform_grid``, ``ball_refined_grid``) as of
PR 22, so that a later change to ``bench.py`` cannot move the yardstick.
Both go through the program's normal entry points: the fluent ``Grid()``
setters, then ``initialize(mesh=make_mesh(n_devices=...))``.
"""
from __future__ import annotations

import numpy as np


def uniform_grid(shape, n_devices):
    """Periodic uniform grid of ``shape`` = (nx, ny, nz) cells over the
    unit cube, no refinement."""
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


def ball_refined_grid(n, radii, max_ref, center, n_devices):
    """Periodic n^3 grid over the unit cube with the ball of each radius
    around ``center`` refined once more (cells of the deepest level whose
    centre lies inside it)."""
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


def build(spec, n_devices):
    """The grid a configuration's ``grid`` entry describes."""
    if spec["kind"] == "uniform":
        return uniform_grid(tuple(spec["shape"]), n_devices)
    if spec["kind"] == "ball_refined":
        return ball_refined_grid(spec["level0"], tuple(spec["radii"]),
                                 spec["max_level"], tuple(spec["center"]),
                                 n_devices)
    raise ValueError(f"unknown grid kind {spec['kind']!r}")
