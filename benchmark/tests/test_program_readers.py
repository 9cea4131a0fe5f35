"""The readers of the program's spans, counters and kernel names, checked
on traces recorded on a TPU v5e (``xtrace.load``'s reduction of one
traced run of each cell, cut to whole calls, with the program's
host-plane spans kept under ``program``) and on the program's own
registry."""
import importlib.util
import json
from pathlib import Path

import pytest

import xtrace

HERE = Path(__file__).resolve().parents[1]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _recorded(name):
    return json.loads((HERE / "tests" / "data" / name).read_text())


def test_step_share_on_recorded_uniform_trace():
    """Three 100-step calls of ``adv_uniform_stream``: the named
    blocked_direct kernel against everything its run module ran (the
    per-step density copy ``%copy.23`` is most of the rest)."""
    rec = _recorded("trace_uniform_spans.json")
    ops = rec["devices"]["/device:TPU:0"]
    assert {o[3].split("(")[0] for o in ops} == {
        "jit_advection_dense_run", "jit_bench_density_sum",
        "jit_bench_snapshot"}
    got = _reader("kernel.step_share")(Ctx(trace=rec))
    leaves = xtrace.leaves(ops)
    run = [o for o in leaves if o[3].startswith("jit_advection_dense_run")]
    kernel = sum(o[2] - o[1] for o in run
                 if o[0].startswith("%advection_blocked_direct."))
    copy = sum(o[2] - o[1] for o in run if o[0] == "%copy.23 copy")
    total = xtrace.measure(xtrace.union([(o[1], o[2]) for o in run]))
    assert got == pytest.approx(100 * kernel / total)
    assert 70 < got < 75
    assert 24 < 100 * copy / total < 27


def test_step_share_reads_nothing_without_a_named_kernel():
    """The refined cell's boxed path steps through XLA fusions: its run
    module holds no named kernel, and the parent's module names are not
    the program's run modules."""
    read = _reader("kernel.step_share")
    assert read(Ctx(trace=_recorded("trace_refined_spans.json"))) is None
    assert read(Ctx(trace=_recorded("trace_uniform_3calls.json"))) is None
    assert read(Ctx(trace=None)) is None


@pytest.mark.parametrize("name", ["trace_uniform_spans.json",
                                  "trace_refined_spans.json"])
def test_program_spans_nest_in_the_harness_dispatch(name):
    """Each ``advection.run`` lies inside one harness ``dispatch`` span,
    and its record, args and launch parts inside it, in that order: the
    program's spans share the harness's host clock."""
    rec = _recorded(name)
    dispatch = [s for s in rec["host"] if s[0] == "dispatch"]
    runs = [s for s in rec["program"] if s[0] == "advection.run"]
    assert runs and len(runs) == len(dispatch)
    for run, d in zip(runs, dispatch):
        assert d[1] <= run[1] and run[2] <= d[2]
        parts = [s for s in rec["program"]
                 if s[0].startswith("advection.run.")
                 and run[1] <= s[1] and s[2] <= run[2]]
        assert [p[0] for p in parts] == ["advection.run.record",
                                         "advection.run.args",
                                         "advection.run.launch"]


@pytest.fixture
def registry():
    from dccrg_tpu.obs import enable, metrics

    enable()
    metrics.reset()
    yield metrics
    metrics.reset()


def test_epoch_build_reads_the_phase_total(registry):
    read = _reader("grid.epoch_build_s")
    assert read(Ctx()) is None
    registry.phase_add("epoch.build", 1.5)
    registry.phase_add("epoch.build", 0.25)
    assert read(Ctx()) == pytest.approx(1.75)


#: set-up phases of a refined grid's model: the gather path's tables and
#: step, then the boxed and flat candidates
REFINED = {"tables": 11.0, "step": 0.5, "boxed": 2.0, "flat": 5.0}


@pytest.mark.parametrize("built,engaged,unused", [
    (REFINED, ("boxed",), 16.5),         # flat, tables and step unused
    (REFINED, ("flat",), 13.5),          # boxed, tables and step unused
    (REFINED, ("flat", "general"), 2.0),  # the flat kernel fell back
    (REFINED, ("split",), 7.0),          # both whole-run kernels unused
    ({"dense": 3.0}, ("fused",), 0.0),
    ({"dense": 3.0}, ("general",), 0.0),  # the dense bundle's XLA step
])
def test_unused_init_sums_paths_that_never_ran(registry, built, engaged,
                                                unused):
    read = _reader("model.unused_init_s")
    assert read(Ctx()) is None       # a program without the spans
    for part, seconds in built.items():
        registry.phase_add(f"advection.init.{part}", seconds)
    registry.phase_add("advection.init.flat.amr_tables", 4.0)  # in flat
    for path in engaged:
        registry.inc("fused.runs", model="advection", path=path)
    registry.inc("fused.runs", model="game_of_life", path="boxed")
    assert read(Ctx()) == pytest.approx(unused)
