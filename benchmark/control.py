#!/usr/bin/env python
"""The control of a cell's comparison: the plain reference put in the
program's place, computed one precision below the configuration's, and
driven through the harness's own run.

    python benchmark/control.py --workload <cell> --seeds <n> [<n> ...] [--seconds <s>]

For each seed ``Advection.run`` is replaced by ``control_run``, and
``run.run`` makes a whole run of the cell at its own size: set-up, warm-up,
a short window at the cell's load, and the comparison of the sampled calls
with the float32 reference.  Each seed prints its ``correct`` and its
checks; the exit code is 0 only where every seed came out not correct with
``max_rel_err`` over its limit.  The benchmark's own runs never run it.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent))

#: the nearest precision below each one a configuration can state
LOWER = {"float64": "float32", "float32": "bfloat16"}


def control_run(cfg):
    """A stand-in for ``Advection.run(self, state, steps, dt)``: the plain
    reference, in the precision below ``cfg["dtype"]``, advances the
    state's density in the program's own layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from references.advection import Layout, Reference

    layout = Layout(cfg["grid"])
    ref = Reference(layout, float(cfg["initial"]["drift_vz"]),
                    getattr(jnp, LOWER[cfg["dtype"]]))

    def run(self, state, steps, dt):
        rho = state["density"]
        if self.dense is not None:
            vox = jax.device_put(rho, jax.devices()[0]).reshape(layout.shape)
            out = ref.run(vox, steps, dt).reshape(rho.shape)
            return {**state, "density": jax.device_put(out, rho.sharding)}
        ids = self.grid.get_cells()
        vals = self.grid.get_cell_data(state, "density", ids)
        out = np.asarray(ref.run(layout.to_voxels(ids, vals), steps, dt))
        z, y, x, _ = layout.voxel_index(ids)
        return self.set_cell_data(state, "density", ids, out[z, y, x])

    return run


def failed_as_it_should(result) -> bool:
    err = result["checks"].get("max_rel_err")
    return (not result["correct"] and err is not None
            and not err["value"] <= err["limit"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=3.0)
    a = p.parse_args(argv)

    import run
    from dccrg_tpu.models import Advection

    cfg = run.load_cell(a.workload)[2]
    Advection.run = control_run(cfg)
    ok = True
    for seed in a.seeds:
        r = run.run(a.workload, seed, a.seconds, False)
        ok &= failed_as_it_should(r)
        print("control " + json.dumps({
            "workload": a.workload, "seed": seed, "correct": r["correct"],
            "attempted": r["attempted"], "checks": r["checks"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
