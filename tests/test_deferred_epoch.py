"""Per-cell epoch tables built on first read (``parallel/epoch.py``).

A snapshot the dense fast path takes gets an epoch without its row layout
and per-hood tables; the first read builds them with the eager build's
code.  Checked here: the tables a read builds equal an eager build's array
for array, whichever read comes first; a refined grid still builds at once,
to the same arrays as before; a dense ``Advection`` set-up and ``run()``
build no tables and count the z-planes they send; ``epoch.tables`` opens
once per built epoch and ``epoch.tables_deferred`` counts the rest."""
import hashlib

import jax
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
from dccrg_tpu.models import Advection
from dccrg_tpu.parallel.epoch import build_epoch
from dccrg_tpu.parallel.shapes import epoch_shape_hints
from dccrg_tpu.utils.verify import compare_epochs

DEVICES = [1, 4]


def _grid(n=(8, 8, 8), n_devices=1, max_level=0):
    return (
        Grid()
        .set_initial_length(n)
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(max_level)
        .set_geometry(CartesianGeometry, start=(0.0, 0.0, 0.0),
                      level_0_cell_length=tuple(1.0 / x for x in n))
        .initialize(mesh=make_mesh(n_devices=n_devices))
    )


def _refine_ball(g):
    ids = g.get_cells()
    c = g.geometry.get_center(ids)
    g.refine_completely_many(ids[np.linalg.norm(c - 0.5, axis=1) < 0.3])
    g.stop_refining()
    return g


def _eager(g, hints=None):
    """The eager build of a grid's snapshot: the same tables, built at
    once (a geometry the dense path may not take defers nothing)."""
    return build_epoch(
        g.mapping, g.topology, g.leaves, g.n_devices, g.neighborhoods,
        uniform_geometry=False,
        shape_hints=hints,
    )


def _deferred(epoch) -> bool:
    return "_build_tables" in vars(epoch)


def _tables_built():
    rec = obs.metrics.report()["phases"].get("epoch.tables")
    return 0 if rec is None else rec["count"]


def _read_halo(g):
    g.halo(None)


def _read_neighbors(g):
    ids, _ = g.get_neighbors_of(int(g.get_cells()[5]))
    assert len(ids) == 6


READS = {"halo": _read_halo, "neighbors": _read_neighbors}


@pytest.mark.parametrize("n_devices", DEVICES)
@pytest.mark.parametrize("read", sorted(READS))
def test_deferred_tables_equal_eager(read, n_devices):
    obs.enable()
    g = _grid(n_devices=n_devices)
    assert g.epoch.dense is not None and _deferred(g.epoch)
    want = _eager(g)
    want.dense = g.epoch.dense
    READS[read](g)
    assert not _deferred(g.epoch)
    compare_epochs(g.epoch, want)


@pytest.mark.parametrize("n_devices", DEVICES)
def test_refinement_reads_deferred_tables(n_devices):
    """Refining a dense grid reads its tables (the rebuild takes their
    shapes as hints): the refined epoch equals an eager rebuild from the
    hints of an eager first epoch."""
    obs.enable()
    g = _grid(n_devices=n_devices, max_level=1)
    assert _deferred(g.epoch)
    hints = epoch_shape_hints(_eager(g))
    _refine_ball(g)
    assert g.epoch.dense is None and not _deferred(g.epoch)
    compare_epochs(g.epoch, _eager(g, hints=hints))


@pytest.mark.parametrize("n_devices", DEVICES)
def test_added_neighborhood_leaves_old_tables_alone(n_devices):
    """A neighborhood added before the tables were read is not in them:
    the old epoch, read by the rebuild for its shapes, holds the hoods of
    its own snapshot."""
    obs.enable()
    g = _grid(n_devices=n_devices)
    old = g.epoch
    want = _eager(g)
    want.dense = old.dense
    faces = [[-1, 0, 0], [1, 0, 0], [0, -1, 0], [0, 1, 0], [0, 0, -1],
             [0, 0, 1]]
    assert g.add_neighborhood(7, faces)
    assert not _deferred(old) and set(old.hoods) == {None}
    compare_epochs(old, want)
    assert 7 in g.epoch.hoods


def _digest(epoch) -> str:
    """sha256 (first 16 hex digits) of every array of an epoch."""
    h = hashlib.sha256()

    def add(a):
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())

    add(np.array([epoch.R, epoch.n_devices]))
    for name in ("n_local", "n_ghost", "row_of", "cell_len", "cell_level",
                 "cell_ids", "local_mask"):
        add(getattr(epoch, name))
    for d in range(epoch.n_devices):
        add(epoch.local_pos[d])
        add(epoch.ghost_pos[d])
    for hid in sorted(epoch.hoods, key=lambda k: -1 if k is None else k):
        hood = epoch.hoods[hid]
        for name in ("offsets", "to_start", "to_src", "send_rows",
                     "recv_rows", "pair_counts", "inner_mask", "outer_mask",
                     "nbr_rows", "nbr_valid", "nbr_offset", "nbr_len",
                     "nbr_slot"):
            add(getattr(hood, name))
        for name in ("start", "nbr_pos", "nbr_cell", "offset", "slot"):
            add(getattr(hood.lists, name))
    return h.hexdigest()[:16]


#: digests of the 8^3 grid with its central ball refined, as the eager
#: build made them before any epoch was deferred
REFINED_DIGEST = {1: "00697908aaf8a195", 4: "ddcbf0edb10c8833"}


@pytest.mark.parametrize("n_devices", DEVICES)
def test_refined_grid_builds_at_once(n_devices):
    obs.enable()
    g = _grid(n_devices=n_devices, max_level=1)
    deferred = obs.metrics.counter_value("epoch.tables_deferred")
    _refine_ball(g)
    assert not _deferred(g.epoch)
    assert obs.metrics.counter_value("epoch.tables_deferred") == deferred
    assert _digest(g.epoch) == REFINED_DIGEST[n_devices]


@pytest.mark.parametrize("n_devices", DEVICES)
def test_dense_run_builds_no_tables(n_devices):
    """Set-up, the initial state, dt and ``run()`` on the dense path build
    no per-cell table; each step counts one density z-plane sent each way
    per device (none on one device)."""
    obs.enable()
    obs.metrics.reset()
    n = (16, 8, 8)
    adv = Advection(_grid(n=n, n_devices=n_devices), dtype=np.float32)
    assert adv.dense is not None
    state = adv.initialize_state()
    dt = np.float32(0.4 * adv.max_time_step(state))
    for _ in range(2):
        state = adv.run(state, 3, dt)
    jax.block_until_ready(state["density"])
    rep = obs.metrics.report()
    assert "epoch.tables" not in rep["phases"]
    assert _deferred(adv.grid.epoch)
    (sent,) = rep["counters"]["fused.halo_bytes_equiv"].values()
    planes = 2 * n_devices if n_devices > 1 else 0
    assert sent == 2 * 3 * planes * n[0] * n[1] * 4


def test_tables_deferred_counts_and_tables_open_once():
    obs.enable()
    obs.metrics.reset()
    dense = [_grid(n_devices=d) for d in DEVICES]
    _grid(n=(8, 8, 7), n_devices=4)      # 7 z-planes: no slab partition
    assert obs.metrics.counter_value("epoch.tables_deferred") == 2
    assert _tables_built() == 1
    for g in dense:
        for _ in range(2):
            assert g.epoch.R > 0 and g.epoch.hoods[None] is not None
    assert _tables_built() == 3
    gauges = obs.metrics.report()["gauges"]
    assert "epoch.rows_per_device" in gauges and "epoch.bucket_K" in gauges
