"""The four-chip uniform cell at a small size on four CPU devices: the
z-block reference against the whole-field one, ``correct`` for a sound run
and not for a broken one, the guard before the grid build, and the
``grid.tables_s`` reader."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import run
from references.advection import Layout, Reference
from references.advection_slabs import SlabReference

HERE = Path(__file__).resolve().parents[1]
CELL = "adv_uniform_x4"
#: the cell's configuration at a size a CPU test holds: only the grid's
#: scale is cut, to the same proportions (4 z-planes per x or y cell, so
#: the cell's CFL factor keeps the step stable), and the whole-run fused
#: guard is left out
SMALL = {"kind": "uniform", "shape": [32, 32, 128],
         "periodic": [True, True, True]}
SEED = 2**33 + 7


def _patch(monkeypatch):
    orig = run.load_cell

    def load_cell(workload):
        bench, cell, cfg, traffic = orig(workload)
        return bench, cell, {**cfg, "grid": SMALL, "expect": {}}, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)


def _run(trace=False, seconds=0.5):
    return run.run(CELL, SEED, seconds, trace, require_tpu=False)


def test_slab_reference_equals_whole_field():
    """32x32x128 over 4 slabs, 5 steps: each slab advanced with its own
    5-plane halo equals the whole field advanced at once."""
    steps, drift = 5, 0.25
    grid = SMALL
    nx, ny, nz = grid["shape"]
    rho = np.random.default_rng(3).random((nz, ny, nx), np.float32)
    layout = Layout(grid)
    dt = np.float32(0.4 * layout.max_time_step(drift))
    want = np.asarray(Reference(layout, drift, np.float32).run(rho, steps, dt))

    mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
    slabs = jax.device_put(rho.reshape(4, nz // 4, ny, nx),
                           NamedSharding(mesh, P("d")))
    got = SlabReference(grid, drift, np.float32, 4, steps).run(slabs, dt)
    assert got.sharding == slabs.sharding
    got = np.asarray(got).reshape(nz, ny, nx)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) <= 1e-6


def test_sound_run_is_correct(monkeypatch):
    _patch(monkeypatch)
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 3
    assert r["device"]["count"] == 4
    assert r["checks"]["max_rel_err"]["value"] < 1e-5
    assert set(r["metrics"]) == {"cell_updates_per_s", "setup_s"}


def _wrap_each_slab(self, blk):
    """The halo left out: each slab's edge planes taken from itself."""
    return blk[-1:], blk[:1]


def _unchanged(monkeypatch):
    from dccrg_tpu.models import Advection

    monkeypatch.setattr(Advection, "run",
                        lambda self, state, steps, dt: state)


def _no_halo(monkeypatch):
    from dccrg_tpu.parallel.dense import HaloExtend

    monkeypatch.setattr(HaloExtend, "planes", _wrap_each_slab)


def _bf16_control(monkeypatch):
    import control
    from dccrg_tpu.models import Advection

    cfg = run.load_cell(CELL)[2]
    monkeypatch.setattr(Advection, "run", control.control_run(cfg))


@pytest.mark.parametrize("fault", [_no_halo, _unchanged, _bf16_control])
def test_broken_run_is_not_correct(monkeypatch, fault):
    _patch(monkeypatch)
    fault(monkeypatch)
    r = _run()
    assert not r["correct"] and r["failed"] >= 1
    err = r["checks"]["max_rel_err"]
    assert err["value"] > 10 * err["limit"]


@pytest.mark.parametrize("eager", [True, False])
def test_guard_refuses_a_slow_grid_build(monkeypatch, eager):
    """The guard times a 128^3 grid build and scales it to the cell's
    cells: a program whose epoch builds every per-cell table at once is
    refused before the cell's grid is built, the program as it is passes."""
    from dccrg_tpu.parallel.epoch import Epoch

    slabs = run._module("models", "advection_slabs")
    if eager:
        monkeypatch.setattr(
            Epoch, "deferred",
            classmethod(lambda cls, mapping, topology, leaves, n, build:
                        build()))
    n_cells = int(np.prod(run.load_cell(CELL)[2]["grid"]["shape"]))
    if eager:
        with pytest.raises(SystemExit) as e:
            slabs.guard(4, n_cells)
        assert str(e.value.code).startswith("refused: a 128x128x128 grid")
    else:
        assert slabs.guard(4, n_cells) < slabs.BUILD_LIMIT_S / 2


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_tables_s_reads_zero_on_the_dense_cell(monkeypatch):
    from dccrg_tpu import obs

    obs.metrics.reset()
    _patch(monkeypatch)
    assert _run()["correct"]
    assert _reader("grid.tables_s")(None) == 0.0


def test_tables_s_reads_the_phase_or_nothing():
    from dccrg_tpu import obs

    read = _reader("grid.tables_s")
    obs.metrics.reset()
    assert read(None) is None        # neither phase nor counter: nothing
    with obs.metrics.phase("epoch.tables"):
        jnp.zeros(4).block_until_ready()
    rec = obs.metrics.report()["phases"]["epoch.tables"]
    assert read(None) == rec["total_s"] > 0
