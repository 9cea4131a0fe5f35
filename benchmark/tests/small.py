"""The benchmark's cells at a size a CPU test holds: only the grid's
scale is cut, and the whole-run fused guard is left out."""
import run

GRIDS = {
    "advection3d_uniform": {"kind": "uniform", "shape": [32, 32, 16]},
    "advection3d_refined": {"kind": "ball_refined", "level0": 12,
                            "radii": [0.3], "max_level": 1,
                            "center": [0.3, 0.5, 0.5]},
}


def patch(monkeypatch):
    """Make ``run.load_cell`` hand out the small configurations."""
    orig = run.load_cell

    def load_cell(workload):
        bench, cell, cfg, traffic = orig(workload)
        cfg = {**cfg, "grid": GRIDS[cfg["name"]], "expect": {}}
        return bench, cell, cfg, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)


def run_cell(workload, seed=2**33 + 5, seconds=0.5, trace=False):
    return run.run(workload, seed, seconds, trace, require_tpu=False)
