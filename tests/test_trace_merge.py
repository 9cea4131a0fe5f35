"""Host-timeline trace tests: the cross-process fleet merge and its
validator, and the timeline context/truncation satellites (ISSUE 6)."""
import json

import numpy as np
import pytest

from dccrg_tpu import obs
from dccrg_tpu.obs.events import (
    EventTimeline,
    merge_chrome_traces,
    validate_merged_trace,
)


def _one_proc(tmp_path, origin, name):
    """One process's timeline export, anchored at wall time ``origin``."""
    tl = EventTimeline(enabled=True)
    tl.rebase(0.0, origin)
    tl.add("halo.exchange", 1e-3, 1e-3)
    tr = tl.chrome_trace()
    p = tmp_path / name
    p.write_text(json.dumps(tr))
    return str(p)


def test_validate_merged_trace_catches_breakage(tmp_path):
    fleet = merge_chrome_traces([_one_proc(tmp_path, 100.0, "a.json"),
                                 _one_proc(tmp_path, 100.5, "b.json")])
    assert validate_merged_trace(fleet) == []
    # a span that never ends
    bad = json.loads(json.dumps(fleet))
    bad["traceEvents"] = [e for e in bad["traceEvents"]
                          if e.get("ph") != "E"]
    assert any("unmatched B" in f for f in validate_merged_trace(bad))
    # an end that closes another span's begin
    bad2 = json.loads(json.dumps(fleet))
    for e in bad2["traceEvents"]:
        if e.get("ph") == "E":
            e["name"] = "other"
            break
    assert any("closes" in f for f in validate_merged_trace(bad2))
    # a complete event of negative length
    bad3 = json.loads(json.dumps(fleet))
    bad3["traceEvents"].append({"name": "x", "ph": "X", "pid": 1,
                                "tid": 0, "ts": 5.0, "dur": -5})
    assert any("negative dur" in f for f in validate_merged_trace(bad3))


# --------------------------------------------------------- fleet merge


def test_fleet_merge_shifts_onto_shared_epoch_zero(tmp_path):
    p1 = _one_proc(tmp_path, 100.0, "a.trace.json")
    p2 = _one_proc(tmp_path, 100.5, "b.trace.json")   # 500 ms later
    fleet = merge_chrome_traces([p1, p2],
                                out_path=str(tmp_path / "fleet.json"))
    assert fleet["otherData"]["origin_unix_s"] == 100.0
    assert validate_merged_trace(fleet) == []
    spans = [e for e in fleet["traceEvents"] if e.get("ph") == "B"]
    assert len(spans) == 2
    ts = sorted(e["ts"] for e in spans)
    # second process's identical span lands 500 ms later on the shared
    # epoch-zero
    assert ts[1] - ts[0] == pytest.approx(500_000, abs=1)
    # pids renumbered per process — no collision even though both
    # processes exported the same os pid
    assert len({e["pid"] for e in spans}) == 2
    # a source without the anchor is rejected loudly
    (tmp_path / "bad.json").write_text(json.dumps({"traceEvents": []}))
    with pytest.raises(ValueError, match="origin_unix_s"):
        merge_chrome_traces([str(tmp_path / "bad.json")])


# ---------------------------------------- timeline satellites (ISSUE 6)


def test_timeline_context_args_layering():
    tl = EventTimeline(enabled=True)
    with tl.context(grid_id=7):
        with tl.span("outer"):
            pass
        with tl.context(step=3):
            with tl.span("inner", extra="x"):
                pass
    with tl.span("outside"):
        pass
    spans = {s["name"]: s["args"] for s in tl.spans()}
    assert spans["outer"] == {"grid_id": 7}
    assert spans["inner"] == {"grid_id": 7, "step": 3, "extra": "x"}
    assert spans["outside"] is None


def test_timeline_drop_counter_and_truncation_marker():
    obs.metrics.reset()
    obs.enable()
    tl = EventTimeline(enabled=True, max_events=2)
    for i in range(5):
        tl.add(f"e{i}", float(i), 0.5)
    assert tl.summary()["dropped"] == 3
    assert tl.summary()["max_events"] == 2
    assert obs.metrics.counter_value("timeline.dropped") == 3
    trace = tl.chrome_trace()
    markers = [e for e in trace["traceEvents"]
               if e.get("name") == "timeline.truncated"]
    assert len(markers) == 1
    assert markers[0]["ph"] == "i"
    assert markers[0]["args"]["dropped_events"] == 3
    # a truncated timeline still validates (instant events are legal)
    assert validate_merged_trace(trace) == []


def test_concurrent_grids_separable_by_grid_id():
    from test_obs import _small_grid

    obs.metrics.reset()
    obs.enable()
    obs.timeline.clear()
    obs.enable_timeline()
    g1 = _small_grid(max_ref=0, length=(4, 4, 1))
    g2 = _small_grid(max_ref=0, length=(4, 4, 1))
    assert g1.grid_id != g2.grid_id
    st1 = g1.new_state({"rho": ((), np.float64)})
    st2 = g2.new_state({"rho": ((), np.float64)})
    obs.timeline.clear()
    g1.update_copies_of_remote_neighbors(st1)
    g2.update_copies_of_remote_neighbors(st2)
    halo_args = [s["args"] for s in obs.timeline.spans()
                 if s["name"] == "halo.exchange"]
    assert {a["grid_id"] for a in halo_args} == {g1.grid_id, g2.grid_id}
    assert g1.report()["grid"]["grid_id"] == g1.grid_id
