"""Halo backend tests (ISSUE 7): the Pallas async-DMA ring bodies
(``parallel/halo_dma.py``, run under the interpreter on this CPU suite)
must be bit-identical to the collective ``ppermute`` path — which stays
the always-available oracle (``DCCRG_HALO_VERIFY=1``) — and the fused
split-phase advection/vlasov steps must reproduce their eager
counterparts while riding the executable cache with zero retraces on a
seen shape signature."""
import jax
import numpy as np
import pytest

from dccrg_tpu import CartesianGeometry, Grid, make_mesh, obs
from dccrg_tpu.models import Advection, GameOfLife, Vlasov
from dccrg_tpu.parallel import halo_dma


def make_grid(n_dev=8, length=(10, 10, 1), max_ref=0, hood_len=1,
              refine_ball=None, periodic=False, geometry=False):
    g = Grid().set_initial_length(length)
    g.set_maximum_refinement_level(max_ref)
    g.set_neighborhood_length(hood_len)
    g.set_periodic(periodic, periodic, periodic)
    g.set_load_balancing_method("RCB")
    if geometry or refine_ball is not None:
        g.set_geometry(
            CartesianGeometry, start=(0.0, 0.0, 0.0),
            level_0_cell_length=tuple(1.0 / n for n in length),
        )
    g.initialize(mesh=make_mesh(n_devices=n_dev))
    if refine_ball is not None:
        ids = g.get_cells()
        ctr = g.geometry.get_center(ids)
        g.refine_completely_many(
            ids[np.linalg.norm(ctr - 0.5, axis=1) < refine_ball]
        )
        g.stop_refining()
        g.balance_load()
    return g


def rand_state(g, spec, seed=0):
    rng = np.random.default_rng(seed)
    state = g.new_state(spec)
    cells = g.get_cells()
    for name, (shape, dtype) in spec.items():
        if np.issubdtype(dtype, np.floating):
            vals = rng.normal(size=(len(cells),) + shape).astype(dtype)
        else:
            vals = rng.integers(0, 7, size=(len(cells),) + shape
                                ).astype(dtype)
        state = g.set_cell_data(state, name, cells, vals)
    return state


def assert_states_bitwise(a, b):
    for name in a:
        assert (np.asarray(a[name]).tobytes()
                == np.asarray(b[name]).tobytes()), name


def assert_ulp_close(a, b, n_ulp):
    a, b = np.asarray(a), np.asarray(b)
    ulp = np.spacing(np.maximum(np.abs(a), np.abs(b)))
    bad = np.abs(a - b) > n_ulp * ulp
    assert not bad.any(), (
        f"{int(bad.sum())} elements beyond {n_ulp} ULP; max diff "
        f"{np.abs(a - b).max()}"
    )


# ------------------------------------------------------ backend selection


def test_backend_resolution(monkeypatch):
    monkeypatch.delenv("DCCRG_HALO_BACKEND", raising=False)
    # auto on a CPU suite: the collective path stays the default
    assert halo_dma.resolve_backend() == "collective"
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    assert halo_dma.resolve_backend() == "pallas"
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "collective")
    assert halo_dma.resolve_backend() == "collective"
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "auto")
    assert halo_dma.resolve_backend() == "collective"


def test_invalid_backend_env_raises(monkeypatch):
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "quantum")
    with pytest.raises(ValueError, match="DCCRG_HALO_BACKEND"):
        halo_dma.resolve_backend()


def test_backend_enters_structure_key(monkeypatch):
    # the backend is resolved when the schedule is CONSTRUCTED (the
    # first halo() call), so snapshot each key under its own env
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "collective")
    g1 = make_grid()
    k1 = g1.halo().structure_key
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    g2 = make_grid()
    k2 = g2.halo().structure_key
    assert k1[-1] == "collective" and k2[-1] == "pallas"
    assert k1[:-1] == k2[:-1]


# ------------------------------------------------- DMA body bit-identity


@pytest.mark.parametrize("n_dev", [1, 8])
@pytest.mark.parametrize(
    "spec",
    [
        {"v": ((), np.float64)},
        {"rho": ((), np.float32), "mom": ((3,), np.float32)},
        {"alive": ((), np.uint32)},
    ],
    ids=["f64-scalar", "f32-multifield", "u32"],
)
def test_pallas_exchange_bit_identical(monkeypatch, n_dev, spec):
    """The interpreted DMA ring body leaves ghost rows byte-for-byte
    equal to the collective path, per dtype and trailing shape, on one
    ring distance and on the refined multi-ring schedule."""
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    gp = make_grid(n_dev=n_dev, length=(8, 8, 8), max_ref=1,
                   refine_ball=0.3, periodic=True)
    assert gp.halo().backend == "pallas"
    if n_dev > 1:
        assert len(gp.halo().ring_ks) >= 2, "want a multi-ring schedule"
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "collective")
    gc = make_grid(n_dev=n_dev, length=(8, 8, 8), max_ref=1,
                   refine_ball=0.3, periodic=True)
    sp = rand_state(gp, spec)
    sc = rand_state(gc, spec)
    assert_states_bitwise(
        gp.update_copies_of_remote_neighbors(sp),
        gc.update_copies_of_remote_neighbors(sc),
    )


def test_pallas_split_matches_blocking(monkeypatch):
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    g = make_grid()
    state = rand_state(g, {"v": ((), np.float64)})
    blocking = g.update_copies_of_remote_neighbors(state)
    handle = g.start_remote_neighbor_copy_updates(state)
    merged = g.wait_remote_neighbor_copy_updates(state, handle)
    assert_states_bitwise(blocking, merged)


# ------------------------------------------------------- verify oracle


def test_verify_counts_and_detects_mismatch(monkeypatch):
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    monkeypatch.setenv("DCCRG_HALO_VERIFY", "1")
    obs.enable()
    g = make_grid()
    ex = g.halo()
    state = rand_state(g, {"v": ((), np.float64)})
    checks0 = obs.metrics.counter_value("halo.verify_checks")
    out = g.update_copies_of_remote_neighbors(state)
    assert obs.metrics.counter_value("halo.verify_checks") == checks0 + 1
    assert obs.metrics.counter_value("halo.verify_mismatches",
                                     field="v") == 0
    # a corrupted payload must be detected AND counted, not raised
    tampered = {"v": np.asarray(out["v"]).copy()}
    tampered["v"][0, 0] += 1.0
    assert ex._verify_oracle(state, tampered) == 1
    assert obs.metrics.counter_value("halo.verify_mismatches",
                                     field="v") == 1
    # the clean result verifies to zero mismatches
    assert ex._verify_oracle(state, out) == 0


def test_verify_env_gates_the_check(monkeypatch):
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    monkeypatch.delenv("DCCRG_HALO_VERIFY", raising=False)
    obs.enable()
    g = make_grid()
    state = rand_state(g, {"v": ((), np.float64)})
    checks0 = obs.metrics.counter_value("halo.verify_checks")
    g.update_copies_of_remote_neighbors(state)
    assert obs.metrics.counter_value("halo.verify_checks") == checks0


def test_verify_noop_on_collective_backend(monkeypatch):
    """The oracle IS the collective path: verifying it against itself
    would double every exchange for nothing, so the gate stays off."""
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "collective")
    monkeypatch.setenv("DCCRG_HALO_VERIFY", "1")
    obs.enable()
    g = make_grid()
    state = rand_state(g, {"v": ((), np.float64)})
    checks0 = obs.metrics.counter_value("halo.verify_checks")
    g.update_copies_of_remote_neighbors(state)
    assert obs.metrics.counter_value("halo.verify_checks") == checks0


# --------------------------------------------- fused split-phase steps


@pytest.mark.parametrize("n_dev", [1, 8])
@pytest.mark.parametrize("backend", ["collective", "pallas"])
def test_split_advection_bit_identical(monkeypatch, n_dev, backend):
    """The fused start → interior → finish → boundary advection step is
    bit-identical to the eager step; the whole-run fori_loop form stays
    within 2 ULP (XLA instruction selection varies with the row-set
    shapes inside the loop — the residual class the module docstring
    already licenses across device counts)."""
    monkeypatch.setenv("DCCRG_HALO_BACKEND", backend)
    g = make_grid(n_dev=n_dev, length=(8, 8, 8), max_ref=1,
                  refine_ball=0.3, periodic=True)
    eager = Advection(g, dtype=np.float64, allow_dense=False)
    fused = Advection(g, dtype=np.float64, allow_dense=False,
                      overlap=True)
    se = eager.initialize_state()
    sf = fused.initialize_state()
    dt = 0.4 * eager.max_time_step(se)
    for _ in range(4):
        se = eager.step(se, dt)
        sf = fused.step(sf, dt)
        assert_states_bitwise({"density": se["density"]},
                              {"density": sf["density"]})
    re = eager.run(se, 3, dt)
    rf = fused.run(sf, 3, dt)
    assert_ulp_close(re["density"], rf["density"], 2)


@pytest.mark.parametrize("n_dev", [1, 8])
@pytest.mark.parametrize("periodic", [True, False],
                         ids=["periodic", "open"])
def test_split_vlasov_matches_eager(monkeypatch, n_dev, periodic):
    """The fused vlasov step matches the eager general step bitwise
    (the split form reorders nothing)."""
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    g = make_grid(n_dev=n_dev, length=(8, 8, 8), max_ref=1,
                  refine_ball=0.3, periodic=periodic)
    eager = Vlasov(g, nv=3, dtype=np.float32)
    fused = Vlasov(g, nv=3, dtype=np.float32, overlap=True)
    assert eager.info is None and fused.info is None
    se = eager.initialize_state()
    sf = fused.initialize_state()
    dt = np.float32(0.5 * eager.max_time_step())
    for _ in range(3):
        se = eager.step(se, dt)
        sf = fused.step(sf, dt)
        assert_ulp_close(se["f"], sf["f"], 4)
    assert np.asarray(se["f"]).tobytes() == np.asarray(sf["f"]).tobytes()
    re = eager.run(se, 3, dt)
    rf = fused.run(sf, 3, dt)
    assert_ulp_close(re["f"], rf["f"], 4)


def test_split_vlasov_forces_row_layout(monkeypatch):
    """overlap=True pins the general row layout even on a slab grid —
    the split form exists to overlap the gather-path halo seam."""
    monkeypatch.delenv("DCCRG_HALO_BACKEND", raising=False)
    g = make_grid(n_dev=8, length=(4, 4, 8), periodic=True,
                  geometry=True)
    assert Vlasov(g, nv=2).info is not None
    vl = Vlasov(g, nv=2, overlap=True)
    assert vl.info is None
    state = vl.initialize_state()
    m0 = vl.total_mass(state)
    state = vl.run(state, 4, 0.5 * vl.max_time_step())
    assert abs(vl.total_mass(state) - m0) < 1e-6


def test_gol_overlap_rides_pallas_backend(monkeypatch):
    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    g = make_grid()
    glider = [35, 36, 37, 27, 16]
    gol_b = GameOfLife(g)
    gol_o = GameOfLife(g, overlap=True)
    sb = gol_b.new_state(alive_cells=glider)
    so = gol_o.new_state(alive_cells=glider)
    for _ in range(6):
        sb = gol_b.step(sb)
        so = gol_o.step(so)
    assert set(gol_b.alive_cells(sb).tolist()) == set(
        gol_o.alive_cells(so).tolist()
    )


# --------------------------------------------------- zero-retrace churn


def test_zero_retrace_churn_split_and_dma(monkeypatch):
    """A structural commit landing on a seen shape signature must
    re-dispatch every ISSUE 7 kernel — the DMA halo bodies and the
    fused split-phase steps — with ZERO retraces (the shape-stable
    epoch contract of PR 5, extended to the new bodies)."""
    from dccrg_tpu.parallel.exec_cache import trace_counts

    monkeypatch.setenv("DCCRG_HALO_BACKEND", "pallas")
    # the check_telemetry churn probe's proven recipe: on the 8^3
    # refined-ball grid a one-cell commit stays inside every held
    # bucket (R, Kmax, ring sizes, split widths); a smaller grid can
    # legitimately outgrow a ring bucket and retrace
    g = make_grid(n_dev=8, length=(8, 8, 8), max_ref=1, hood_len=0,
                  refine_ball=0.3, periodic=True)

    def cycle(i):
        cells = g.get_cells()
        lvl = g.mapping.get_refinement_level(cells)
        cand = cells[lvl < 1]
        g.refine_completely(int(cand[(i * 13) % len(cand)]))
        g.stop_refining()
        adv = Advection(g, dtype=np.float32, allow_dense=False,
                        overlap=True)
        vl = Vlasov(g, nv=2, dtype=np.float32, overlap=True)
        sa = adv.initialize_state()
        sv = vl.initialize_state()
        sa = adv.step(sa, np.float32(0.25 * adv.max_time_step(sa)))
        sv = vl.step(sv, np.float32(0.25 * vl.max_time_step()))
        jax.block_until_ready((sa["density"], sv["f"]))

    cycle(0)
    sig = g.shape_signature()
    counts0 = dict(trace_counts())
    # the new bodies actually traced at least once in cycle 0
    for label in ("halo.dma.body", "advection.split_step",
                  "vlasov.split_step"):
        assert counts0.get(label, 0) >= 1, label
    cycle(1)
    assert g.shape_signature() == sig, (
        "one-cell commit flipped the shape signature — bucket "
        "hysteresis broke"
    )
    changed = {
        k: v - counts0.get(k, 0)
        for k, v in trace_counts().items() if v != counts0.get(k, 0)
    }
    assert not changed, f"second same-signature cycle retraced {changed}"
