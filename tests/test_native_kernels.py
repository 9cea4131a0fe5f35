"""Native C++ neighbor kernel vs the numpy reference implementation."""
import numpy as np
import pytest

from dccrg_tpu.core import Mapping, Topology
from dccrg_tpu.core.neighborhood import default_neighborhood
from dccrg_tpu.core.neighbors import LeafSet, find_all_neighbors
from dccrg_tpu.native import native_available, native_find_neighbors

from test_neighbors import make_leafset

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native kernels not built"
)


@pytest.mark.parametrize("periodic", [(False,) * 3, (True, False, True)])
@pytest.mark.parametrize("hood_len", [0, 1, 2])
@pytest.mark.parametrize("refine", [[], [14], [1, 14, 27]])
def test_native_matches_numpy(periodic, hood_len, refine):
    m = Mapping(length=(3, 3, 3), max_refinement_level=2)
    t = Topology(periodic=periodic)
    leaves = make_leafset(m, refine_cells=refine)
    hood = default_neighborhood(hood_len)

    nat = native_find_neighbors(m, t, leaves.cells, hood, leaves.cells, True)
    assert nat is not None
    start, nbr_cell, nbr_pos, offset, slot = nat

    import os

    os.environ["DCCRG_TPU_NATIVE"] = "0"
    try:
        import dccrg_tpu.native as native_mod

        native_mod._tried, native_mod._lib = True, None
        ref = find_all_neighbors(m, t, leaves, hood)
    finally:
        del os.environ["DCCRG_TPU_NATIVE"]
        native_mod._tried = False

    np.testing.assert_array_equal(start, ref.start)
    np.testing.assert_array_equal(nbr_cell, ref.nbr_cell)
    np.testing.assert_array_equal(nbr_pos, ref.nbr_pos)
    np.testing.assert_array_equal(offset, ref.offset)
    np.testing.assert_array_equal(slot, ref.slot)


def test_native_strict_error():
    m = Mapping(length=(2, 1, 1), max_refinement_level=2)
    t = Topology()
    # broken leaf set: cell 1 missing entirely
    leaves = LeafSet(
        cells=np.array([2], dtype=np.uint64), owner=np.zeros(1, dtype=np.int32)
    )
    with pytest.raises(RuntimeError, match="no neighbor leaf|not an existing leaf"):
        find_all_neighbors(m, t, leaves, default_neighborhood(0))


@pytest.mark.parametrize("periodic", [(True, False, True), (True, True, True)])
def test_native_epoch_matches_numpy(periodic):
    """The fused C++ epoch pass (hood_invert_and_pairs + hood_fill_tables
    + uniform-grid position fast path) builds a bit-identical HoodState to
    the pure-numpy reference path, on a refined multi-device grid."""
    import os

    import numpy as np

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh

    def build():
        n = 12
        g = (
            Grid()
            .set_initial_length((n, n, n))
            .set_neighborhood_length(1)
            .set_periodic(*periodic)
            .set_maximum_refinement_level(1)
            .set_geometry(
                CartesianGeometry,
                start=(0.0, 0.0, 0.0),
                level_0_cell_length=(1.0 / n,) * 3,
            )
            .initialize(mesh=make_mesh(n_devices=4))
        )
        ids = g.get_cells()
        c = g.geometry.get_center(ids)
        r = np.linalg.norm(c - 0.5, axis=1)
        for cid in ids[r < 0.25]:
            g.refine_completely(int(cid))
        g.stop_refining()
        return g

    import dccrg_tpu.native as native_mod

    g_nat = build()
    os.environ["DCCRG_TPU_NATIVE"] = "0"
    try:
        native_mod._tried, native_mod._lib = True, None
        g_ref = build()
    finally:
        del os.environ["DCCRG_TPU_NATIVE"]
        native_mod._tried = False

    h_nat = g_nat.epoch.hoods[None]
    h_ref = g_ref.epoch.hoods[None]
    for f in (
        "to_start", "to_src", "send_rows", "recv_rows", "pair_counts",
        "inner_mask", "outer_mask", "nbr_rows", "nbr_valid", "nbr_offset",
        "nbr_len", "nbr_slot",
    ):
        np.testing.assert_array_equal(
            getattr(h_nat, f), getattr(h_ref, f), err_msg=f
        )


def test_native_sort_unique_matches_numpy():
    from dccrg_tpu.native import native_available, native_sort_unique_u64

    rng = np.random.default_rng(7)
    keys = rng.integers(0, 1 << 48, size=100_000, dtype=np.uint64)
    keys = np.concatenate([keys, keys[:5000]])  # force duplicates
    want = np.unique(keys)
    if native_available():
        got = native_sort_unique_u64(keys.copy())
        np.testing.assert_array_equal(got, want)


def test_setops_helpers():
    from dccrg_tpu.utils.setops import counts_to_start, csr_take, unique_pairs

    a = np.array([3, 1, 3, 1, 0, 3])
    b = np.array([2, 0, 2, 5, 1, 0])
    ua, ub = unique_pairs(a, b, 8)
    want = np.unique(np.stack([a, b], axis=1), axis=0)
    np.testing.assert_array_equal(np.stack([ua, ub], axis=1), want)

    start = counts_to_start(np.array([0, 0, 2, 2, 2]), 4)
    np.testing.assert_array_equal(start, [0, 2, 2, 5, 5])

    data = np.arange(10) * 10
    start = np.array([0, 3, 3, 7, 10])
    got = csr_take(start, data, np.array([2, 0, 3]))
    np.testing.assert_array_equal(got, [30, 40, 50, 60, 0, 10, 20, 70, 80, 90])


def test_build_keyed_names_binary_by_source_flags_and_host(tmp_path,
                                                          monkeypatch):
    """A build is keyed on source + flags + host CPU: an unchanged
    input reuses the binary, any change (including another host's CPU,
    e.g. a tree copied to another machine) builds a fresh one."""
    from dccrg_tpu import native

    src = tmp_path / "k.cpp"
    src.write_text("extern \"C\" int f() { return 1; }\n")
    flags = ("-O1", "-shared", "-fPIC")
    a = native.build_keyed(src, "libk", flags, suffix=".so")
    assert a.exists() and a.name.startswith("libk-")
    assert native.build_keyed(src, "libk", flags, suffix=".so") == a
    b = native.build_keyed(src, "libk", ("-O2",) + flags[1:], suffix=".so")
    monkeypatch.setattr(native, "_host_cpu", lambda: "another cpu")
    c = native.build_keyed(src, "libk", flags, suffix=".so")
    assert len({a, b, c}) == 3
    assert not list(tmp_path.glob("*.tmp"))
