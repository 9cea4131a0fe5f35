"""``correct`` at a small size on the CPU: sound runs pass, the control
(the reference in bfloat16 in the program's place) fails the limit, and a
run with the timed path broken underneath comes out not correct, once for
each fault the cells can have."""
import jax.numpy as jnp
import pytest

import small

CELLS = ("adv_uniform_stream", "adv_refined_static")


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(monkeypatch, workload):
    small.patch(monkeypatch)
    r = small.run_cell(workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 3
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"cell_updates_per_s", "setup_s"}


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_control_is_not_correct(monkeypatch, workload, seed):
    """The reference in bfloat16, in ``Advection.run``'s place, through
    the harness's whole run."""
    import control
    import run
    from dccrg_tpu.models import Advection

    small.patch(monkeypatch)
    cfg = run.load_cell(workload)[2]
    monkeypatch.setattr(Advection, "run", control.control_run(cfg))
    r = small.run_cell(workload, seed=seed)
    assert control.failed_as_it_should(r) and r["attempted"] > 3
    assert r["checks"]["max_rel_err"]["value"] > 10 * (
        r["checks"]["max_rel_err"]["limit"])


def _unchanged(old, new):
    return old


def _half_left_out(old, new):
    """The second half of the cells (rows, or z-planes) keeps its density."""
    n = old.shape[1]
    keep = jnp.arange(n) >= n // 2
    keep = keep.reshape((1, n) + (1,) * (old.ndim - 2))
    return jnp.where(keep, old, new)


def _answer_altered(old, new):
    return new.at[(0,) * new.ndim].add(0.01)


def _break_run(monkeypatch, fault):
    from dccrg_tpu.models import Advection

    orig = Advection.run

    def run(self, state, steps, dt):
        out = orig(self, state, steps, dt)
        return {**out, "density": fault(state["density"], out["density"])}

    monkeypatch.setattr(Advection, "run", run)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _answer_altered])
def test_broken_step_is_not_correct(monkeypatch, workload, fault):
    small.patch(monkeypatch)
    _break_run(monkeypatch, fault)
    r = small.run_cell(workload)
    assert not r["correct"] and r["failed"] >= 1

