"""The perf-regression gate (tools/telemetry_diff.py): verdict logic on
synthetic phase tables (deterministic — no timing in CI), input-shape
loaders, CLI exit codes, and the allowlist knob."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def diff():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import telemetry_diff
    finally:
        sys.path.pop(0)
    return telemetry_diff


def _phases(**means):
    """Phase table with count=10 and the given per-phase means."""
    return {
        name: {"total_s": m * 10, "count": 10, "mean_s": m}
        for name, m in means.items()
    }


BASE = _phases(**{
    "halo.exchange": 0.010,
    "epoch.build": 0.020,
    "amr.refine": 0.005,
})


def test_identical_rounds_pass(diff):
    v = diff.compare(BASE, BASE)
    assert v["verdict"] == "PASS"
    assert v["failures"] == []
    assert all(r["status"] in ("ok", "below-noise-floor")
               for r in v["rows"])


def test_regression_fails_and_names_the_phase(diff):
    cur = _phases(**{
        "halo.exchange": 0.020,      # 2.0x: regression
        "epoch.build": 0.021,        # 1.05x: inside threshold
        "amr.refine": 0.005,
    })
    v = diff.compare(cur, BASE, threshold=0.35)
    assert v["verdict"] == "FAIL"
    assert len(v["failures"]) == 1
    assert "halo.exchange" in v["failures"][0]
    by_phase = {r["phase"]: r for r in v["rows"]}
    assert by_phase["halo.exchange"]["status"] == "REGRESSED"
    assert by_phase["halo.exchange"]["ratio"] == pytest.approx(2.0)
    assert by_phase["epoch.build"]["status"] == "ok"


def test_allowlist_knob_suppresses_failure(diff):
    cur = _phases(**{
        "halo.exchange": 0.030,
        "epoch.build": 0.020,
        "amr.refine": 0.005,
    })
    v = diff.compare(cur, BASE, allow=("halo.exchange",))
    assert v["verdict"] == "PASS"
    statuses = {r["phase"]: r["status"] for r in v["rows"]}
    assert statuses["halo.exchange"] == "allowed-regression"


def test_missing_gated_phase_is_coverage_loss(diff):
    cur = _phases(**{"halo.exchange": 0.010, "epoch.build": 0.020})
    v = diff.compare(cur, BASE)
    assert v["verdict"] == "FAIL"
    assert any("amr.refine" in f and "missing" in f for f in v["failures"])
    # ... unless allowlisted
    assert diff.compare(cur, BASE, allow=("amr.refine",))["verdict"] == "PASS"


def test_noise_floor_skips_tiny_phases(diff):
    base = _phases(**{"checkpoint.write": 0.00005})
    cur = _phases(**{"checkpoint.write": 0.00050})  # 10x, but microseconds
    v = diff.compare(cur, base, min_total=1e-3)
    assert v["verdict"] == "PASS"
    assert v["rows"][0]["status"] == "below-noise-floor"


def test_new_and_ungated_phases_inform_only(diff):
    cur = {**BASE, **_phases(**{"brand.new_phase": 5.0})}
    v = diff.compare(cur, BASE)
    assert v["verdict"] == "PASS"
    assert {r["phase"]: r["status"] for r in v["rows"]}[
        "brand.new_phase"] == "new"
    # a phase outside the gated set regresses without failing
    cur2 = dict(BASE)
    cur2 = {**cur2, **_phases(**{"halo.exchange": 0.010,
                                 "epoch.build": 0.020,
                                 "amr.refine": 0.100})}
    v2 = diff.compare(cur2, BASE, phases=("halo.exchange",))
    assert v2["verdict"] == "PASS"
    assert {r["phase"]: r["status"] for r in v2["rows"]}[
        "amr.refine"] == "ungated"


# ----------------------------------------------------------- input shapes


def test_load_phases_all_shapes(diff, tmp_path):
    # telemetry.json shape
    t = tmp_path / "telemetry.json"
    t.write_text(json.dumps({"phases": BASE, "counters": {}}))
    assert diff.load_phases(str(t)) == BASE
    # bench-record shape
    b = tmp_path / "BENCH_DETAIL.json"
    b.write_text(json.dumps(
        {"metric": "x", "detail": {"telemetry": {"phases": BASE}}}))
    assert diff.load_phases(str(b)) == BASE
    # streaming JSONL: the LAST complete snapshot wins, a trailing
    # truncated line (killed mid-write) is skipped
    s = tmp_path / "stream.jsonl"
    early = {"seq": 0, "ts": 1.0, "phases": _phases(**{"halo.exchange": 1.0})}
    late = {"seq": 1, "ts": 2.0, "phases": BASE}
    s.write_text(json.dumps(early) + "\n" + json.dumps(late)
                 + "\n" + '{"seq": 2, "trunc')
    assert diff.load_phases(str(s)) == BASE
    # shape with no phases anywhere
    n = tmp_path / "nothing.json"
    n.write_text(json.dumps({"metric": "x"}))
    with pytest.raises(ValueError):
        diff.load_phases(str(n))


def test_cli_verdict_and_exit_codes(diff, tmp_path):
    base_f = tmp_path / "base.json"
    base_f.write_text(json.dumps({"phases": BASE}))
    cur_pass = tmp_path / "cur_pass.json"
    cur_pass.write_text(json.dumps({"phases": BASE}))
    cur_fail = tmp_path / "cur_fail.json"
    cur_fail.write_text(json.dumps(
        {"phases": _phases(**{"halo.exchange": 0.050,
                              "epoch.build": 0.020,
                              "amr.refine": 0.005})}))
    out = tmp_path / "verdict.json"
    hist = ["--history", str(tmp_path / "history.jsonl")]
    assert diff.main(["--current", str(cur_pass), "--baseline", str(base_f),
                      "--json", str(out)] + hist) == 0
    assert json.loads(out.read_text())["verdict"] == "PASS"
    assert diff.main(["--current", str(cur_fail), "--baseline", str(base_f),
                      "--json", str(out)] + hist) == 1
    rec = json.loads(out.read_text())
    assert rec["verdict"] == "FAIL"
    assert any("halo.exchange" in f for f in rec["failures"])
    # the allowlist flag flips it back to PASS
    assert diff.main(["--current", str(cur_fail), "--baseline", str(base_f),
                      "--allow", "halo.exchange"] + hist) == 0
    # unreadable input is a distinct exit code (2), not a crash
    assert diff.main(["--current", str(tmp_path / "absent.json"),
                      "--baseline", str(base_f)] + hist) == 2


def test_gate_on_repo_telemetry_round_trip(diff, tmp_path):
    """The real repo telemetry.json diffed against itself must PASS —
    the shape the per-round bench gate exercises."""
    tel = os.path.join(ROOT, "telemetry.json")
    if not os.path.exists(tel):
        pytest.skip("no telemetry.json in repo root")
    assert diff.main(["--current", tel, "--baseline", tel,
                      "--no-history"]) == 0


# ------------------------------------------------- history + drift gate


def test_drift_gate_catches_slow_creep(diff):
    """+12% per round stays inside a 35% step threshold forever; the
    cumulative check against the oldest retained round fails it."""
    rounds = [_phases(**{"epoch.delta_build": 0.010 * (1.12 ** i),
                         "halo.exchange": 0.010})
              for i in range(8)]
    # every consecutive pair passes the step gate
    for a, b in zip(rounds, rounds[1:]):
        assert diff.compare(b, a, threshold=0.35)["verdict"] == "PASS"
    v = diff.check_drift(rounds[-1], rounds[0], threshold=0.75)
    assert v["verdict"] == "FAIL"
    assert any("epoch.delta_build" in f and "drift" in f
               for f in v["failures"])
    statuses = {r["phase"]: r["status"] for r in v["rows"]}
    assert statuses["epoch.delta_build"] == "DRIFT"
    assert statuses["halo.exchange"] == "ok"
    # a missing phase is the step gate's business, not drift's
    v2 = diff.check_drift(_phases(**{"halo.exchange": 0.010}), rounds[0])
    assert v2["verdict"] == "PASS"


def test_history_file_rolls_and_feeds_drift(diff, tmp_path):
    hist = tmp_path / "history.jsonl"
    base_f = tmp_path / "base.json"
    base_f.write_text(json.dumps({"phases": BASE}))
    # 12 rounds with slow creep in one phase; keep window of 5
    for i in range(12):
        cur = tmp_path / f"cur{i}.json"
        cur.write_text(json.dumps({"phases": _phases(**{
            "halo.exchange": 0.010,
            "epoch.build": 0.020 * (1.10 ** i),
            "amr.refine": 0.005,
        })}))
        rc = diff.main(["--current", str(cur), "--baseline", str(base_f),
                        "--history", str(hist), "--history-keep", "5",
                        "--allow", "epoch.build"])
        assert rc == 0  # creeping phase allowlisted: gate stays green
    history = diff.load_history(str(hist))
    assert len(history) == 5  # rolled to the retained window
    assert history[-1]["source"].endswith("cur11.json")
    # without the allowlist the drift over the window (1.1^4 = 1.46x
    # at default 1.75x) still passes, but a steeper creep fails
    steep = tmp_path / "steep.json"
    steep.write_text(json.dumps({"phases": _phases(**{
        "halo.exchange": 0.010,
        "epoch.build": 0.200,
        "amr.refine": 0.005,
    })}))
    rc = diff.main(["--current", str(steep), "--baseline", str(steep),
                    "--history", str(hist)])
    assert rc == 1  # cumulative drift vs oldest retained round


def test_gauge_floor_gate(diff, tmp_path):
    """A floor-gated gauge (GATED_GAUGES_MIN: the cohort peak occupancy)
    fails on a drop below (1 - threshold) x baseline; rises, vacuous
    sides and zero-baseline values never do; a labeled series vanishing
    is a coverage loss."""
    g = "ensemble.cohort_peak_occupancy"
    assert g in diff.GATED_GAUGES_MIN
    base = {g: {"sig=a": 0.6}}
    assert diff.compare_gauges({g: {"sig=a": 0.55}}, base)["verdict"] \
        == "PASS"
    assert diff.compare_gauges({g: {"sig=a": 0.9}}, base)["verdict"] \
        == "PASS"
    bad = diff.compare_gauges({g: {"sig=a": 0.2}}, base, threshold=0.35)
    assert bad["verdict"] == "FAIL"
    assert "0.2" in bad["failures"][0]
    missing = diff.compare_gauges({g: {}}, base)
    assert missing["verdict"] == "FAIL"
    assert "coverage loss" in missing["failures"][0]
    assert diff.compare_gauges(None, base)["verdict"] == "PASS"
    assert diff.compare_gauges({}, None)["verdict"] == "PASS"
    assert diff.compare_gauges(
        {}, {g: {"sig=a": 0}}
    )["verdict"] == "FAIL"  # label present with value 0 still must exist


def test_load_gauges_shapes(diff, tmp_path):
    tel = tmp_path / "telemetry.json"
    tel.write_text(json.dumps({
        "phases": {}, "counters": {},
        "gauges": {"overlap.fraction": {"phase=halo": 0.5}},
    }))
    assert diff.load_gauges(str(tel)) == {
        "overlap.fraction": {"phase=halo": 0.5}
    }
    stream = tmp_path / "s.jsonl"
    stream.write_text(
        json.dumps({"gauges": {"g": {"": 1}}}) + "\n"
        + json.dumps({"gauges": {"g": {"": 2}}}) + "\n"
    )
    assert diff.load_gauges(str(stream)) == {"g": {"": 2}}  # last line wins
    nothing = tmp_path / "n.json"
    nothing.write_text(json.dumps({"phases": {}}))
    assert diff.load_gauges(str(nothing)) is None
