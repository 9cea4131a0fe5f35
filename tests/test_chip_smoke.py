"""chip_smoke.py on the CPU: every phase, run tiny on the virtual mesh
with the Pallas kernels in interpret mode, agrees with its comparison
path; with no TPU the script refuses to report a result.
"""
import contextlib
import io

import pytest

import chip_smoke as cs

PALLAS = "interpret"


def _run_main(argv):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cs.main(list(argv))
    return rc, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("argv", [(), ("--chips", "4")],
                         ids=["one_chip", "four_chips"])
def test_main_without_tpu_fails_and_prints_no_ok(argv):
    rc, out, err = _run_main(argv or ["--chips", "1"])
    assert rc != 0
    assert '"ok"' not in out
    assert "no TPU" in err


def test_uniform_large_phase():
    rec = cs.phase_uniform_large((16, 16, 32), 4, use_pallas=PALLAS,
                                 n_devices=8)
    assert rec["dense_kind"][0] == "blocked_direct"
    assert rec["max_rel_err"] <= rec["tol"]
    assert rec["mass_rel_drift"] <= rec["mass_tol"]


def test_uniform_fused_phase():
    rec = cs.phase_uniform_fused((16, 16, 8), 10, use_pallas=PALLAS,
                                 n_devices=1)
    assert rec["path"] == "fused"
    assert rec["max_rel_err"] <= rec["tol"]


@pytest.mark.parametrize("n_devices", [1, 8])
def test_refined_phase(n_devices):
    rec = cs.phase_refined(8, 5, use_pallas=PALLAS, n_devices=n_devices)
    assert rec["path"] in ("flat", "boxed")
    assert rec["ref_path"] == "general"
    assert rec["max_rel_err"] <= rec["tol"]


@pytest.mark.parametrize("name", ["gol", "poisson", "vlasov", "pic"])
def test_model_phases(name):
    calls = {
        "gol": lambda: cs._gol(16, 10, PALLAS),
        "poisson": lambda: cs._poisson(8, 10, PALLAS),
        "vlasov": lambda: cs._vlasov(8, 2, 3, PALLAS),
        "pic": lambda: cs._pic(2000, 8, 2),
    }
    before = cs.fallback_count()
    rec = calls[name]()
    assert rec["name"] == name
    assert cs.fallback_count() == before


def test_multichip_phase_matches_one_device():
    """The ``--chips 4`` path on 4 virtual devices: both configurations
    under both halo transports agree with their 1-device runs, and the
    state really spans the 4 devices."""
    recs = cs.phase_multichip((16, 16, 32), 8, 3, n_devices=4,
                              use_pallas=PALLAS)
    names = {r["name"] for r in recs}
    assert names == {f"{c}/{b}" for c in ("uniform_large", "refined")
                     for b in ("collective", "pallas")}
    assert {r.get("halo_backend") for r in recs if r.get("halo_backend")} \
        == {"collective", "pallas"}
    for r in recs:
        assert r["devices"] == 4
        assert r["max_rel_err"] <= r["tol"], r


def test_phase_fails_on_a_missed_check():
    """A phase whose engaged path is not the one it demands raises
    instead of reporting: the ok line can only follow passing phases."""
    with pytest.raises(cs.SmokeFailure, match="expected fused"):
        cs.phase_uniform_fused((16, 16, 8), 2, use_pallas=False,
                               n_devices=1)
