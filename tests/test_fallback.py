"""Kernel fallback-policy unit tests (``utils/fallback.py``).

The policy: a compile/lowering rejection permanently disables the fast
path; a transient runtime fault falls back for the call only, with a
consecutive-fall cap so a deterministic-but-unrecognized failure cannot
pay a failed fast-path attempt on every step forever.
"""
import pytest

from dccrg_tpu.utils.fallback import _MAX_TRANSIENT_FALLS, fallback_call


class Kernel:
    def __init__(self):
        self.disabled = False

    def disable(self):
        self.disabled = True


def test_permanent_marker_disables_on_second_consecutive_hit():
    """A substring marker can coincidentally appear in a transient
    error's text, so a marker-classified error must recur on the next
    call before the fast path is disabled for good (ADVICE r4)."""
    k = Kernel()

    def fast():
        raise RuntimeError("Mosaic failed to compile: unsupported op")

    assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    assert not k.disabled  # first hit: could be a transient coincidence
    assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    assert k.disabled      # it recurred: deterministic rejection


def test_single_marker_hit_then_success_keeps_the_fast_path():
    k = Kernel()
    state = {"fail": True}

    def fast():
        if state["fail"]:
            raise RuntimeError("RPC cancelled while lowering in flight")
        return 42

    assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    state["fail"] = False
    assert fallback_call("k", fast, lambda: 1, k.disable) == 42
    state["fail"] = True
    assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    assert not k.disabled  # hits were not consecutive: no disable


def test_not_implemented_disables_immediately():
    k = Kernel()

    def fast():
        raise NotImplementedError("no lowering rule")

    assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    assert k.disabled


def test_transient_fault_does_not_disable():
    k = Kernel()
    attempts = []

    def fast():
        attempts.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    assert not k.disabled  # one-off fault: the kernel gets another chance


def test_consecutive_transient_falls_hit_the_cap():
    k = Kernel()
    attempts = []

    def fast():
        attempts.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    for _ in range(_MAX_TRANSIENT_FALLS + 2):
        if k.disabled:
            break
        assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    assert k.disabled
    assert len(attempts) == _MAX_TRANSIENT_FALLS


def test_fast_success_resets_the_fall_count():
    k = Kernel()
    state = {"fail": True}

    def fast():
        if state["fail"]:
            raise RuntimeError("transient blip")
        return 42

    # fail (cap-1) times, succeed, then fail (cap-1) times again: the
    # reset means the cap is never reached
    for _ in range(_MAX_TRANSIENT_FALLS - 1):
        assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    state["fail"] = False
    assert fallback_call("k", fast, lambda: 1, k.disable) == 42
    state["fail"] = True
    for _ in range(_MAX_TRANSIENT_FALLS - 1):
        assert fallback_call("k", fast, lambda: 1, k.disable) == 1
    assert not k.disabled


def test_both_paths_failing_propagates_the_fast_error():
    k = Kernel()

    def fast():
        raise RuntimeError("Mosaic rejects this")

    def slow():
        raise ValueError("bad caller input")

    with pytest.raises(RuntimeError, match="Mosaic"):
        fallback_call("k", fast, slow, k.disable)
    assert not k.disabled  # the input was bad, not the kernel


def _fallbacks(label, kind):
    from dccrg_tpu.obs import metrics

    return metrics.counter_value("kernel.fallbacks", label=label, kind=kind)


def test_transient_fall_is_counted():
    """Every fall reaches the obs registry, so a run that quietly left
    its kernel shows it (chip_smoke.py fails on a nonzero count)."""
    k = Kernel()
    before = _fallbacks("counted-t", "transient")

    def fast():
        raise RuntimeError("RESOURCE_EXHAUSTED: transient")

    assert fallback_call("counted-t", fast, lambda: 1, k.disable) == 1
    assert _fallbacks("counted-t", "transient") == before + 1
    assert not k.disabled


def test_disabling_fall_is_counted():
    k = Kernel()
    before = _fallbacks("counted-d", "disabled")

    def fast():
        raise NotImplementedError("no lowering rule")

    assert fallback_call("counted-d", fast, lambda: 1, k.disable) == 1
    assert k.disabled
    assert _fallbacks("counted-d", "disabled") == before + 1
    assert _fallbacks("counted-d", "transient") == 0
