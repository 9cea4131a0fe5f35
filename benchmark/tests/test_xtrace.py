"""The arithmetic of the trace readers, pinned on a hand-made reduced
trace (``xtrace.load``'s output form) of one device."""
import importlib.util
from pathlib import Path

import pytest

import xtrace

HERE = Path(__file__).resolve().parents[1]

#: one 300 ns stretch: the step's loop 0-200 holding two fusions and a
#: halo permute 100-120 between them, the harness's density sum 250-260
TRACE = {
    "devices": {"/device:TPU:0": [
        ["%while.1 while", 0.0, 200.0, "jit_dense_run_fn"],
        ["fusion.1", 0.0, 100.0, "jit_dense_run_fn"],
        ["collective-permute-done", 100.0, 120.0, "jit_dense_run_fn"],
        ["fusion.2", 120.0, 200.0, "jit_dense_run_fn"],
        ["reduce.3", 250.0, 260.0, "jit_bench_density_sum"],
    ]},
    "host": [["loop", 0.0, 300.0], ["dispatch", 0.0, 10.0],
             ["readback", 200.0, 300.0]],
}


def _read(name, **ctx):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    class Ctx:
        pass

    c = Ctx()
    c.__dict__.update(ctx)
    return mod.read(c)


def test_interval_arithmetic():
    assert xtrace.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert xtrace.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert xtrace.clip([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]


def test_busy_window_and_idle_share():
    assert xtrace.window_s(TRACE) == pytest.approx(300e-9)
    assert xtrace.busy_s(TRACE) == pytest.approx(210e-9)
    assert _read("device.idle_share", trace=TRACE) == pytest.approx(30.0)


def test_step_time_leaves_out_the_harness():
    assert xtrace.step_s(TRACE) == {"/device:TPU:0": pytest.approx(200e-9)}


def test_hbm_roofline():
    class Sim:
        updates_per_call = 10
        bytes_per_update = 20.0

    # 2 calls x 10 updates x 20 B = 400 B in 200 ns = 2e9 B/s of 8e9
    got = _read("kernel.hbm_roofline", trace=TRACE, sim=Sim, traced_calls=2,
                peaks={"hbm_bytes_per_s": 8e9})
    assert got == pytest.approx(25.0)


def test_exposed_halo():
    # the permute runs alone 100-120 ns of the 300 ns stretch: the loop
    # that holds it does not hide it
    assert _read("halo.exposed_share", trace=TRACE) == pytest.approx(
        100 * 20 / 300)
    no_halo = {"devices": {"/device:TPU:0": TRACE["devices"][
        "/device:TPU:0"][:2]}, "host": TRACE["host"]}
    assert _read("halo.exposed_share", trace=no_halo) is None


def test_breakdown():
    ops = xtrace.top_ops(TRACE)
    assert ops[0] == ["fusion.1", pytest.approx(100e-9)]
    gaps = xtrace.idle_gaps(TRACE)
    assert gaps == [["readback (2 gaps)", pytest.approx(90e-9)]]


def test_recorded_chip_trace():
    """Three calls of ``adv_uniform_stream`` as traced on a TPU v5e
    (PR 22), reduced by ``xtrace.load`` and cut to those calls."""
    import json

    rec = json.loads((HERE / "tests" / "data"
                      / "trace_uniform_3calls.json").read_text())
    ops = rec["devices"]["/device:TPU:0"]
    assert {o[3].split("(")[0] for o in ops} == {
        "jit_dense_run_fn", "jit_bench_density_sum"}
    assert xtrace.window_s(rec) == pytest.approx(0.109270733)
    assert xtrace.busy_s(rec) == pytest.approx(0.100006921)
    # the harness's density sums are not the step
    assert xtrace.step_s(rec)["/device:TPU:0"] == pytest.approx(0.099472181)
    assert _read("device.idle_share", trace=rec) == pytest.approx(
        100 * (1 - 0.100006921 / 0.109270733))

    class Sim:
        updates_per_call = 512 * 512 * 128 * 20
        bytes_per_update = 20.0

    got = _read("kernel.hbm_roofline", trace=rec, sim=Sim, traced_calls=3,
                peaks={"hbm_bytes_per_s": 819e9})
    assert got == pytest.approx(
        100 * 3 * Sim.updates_per_call * 20 / 0.099472181 / 819e9)
    assert 45 < got < 55
    assert _read("halo.exposed_share", trace=rec) is None
    assert xtrace.top_ops(rec, 1)[0][0] == "%body.3 custom-call tpu_custom_call"
