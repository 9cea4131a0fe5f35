"""Shared fast-path fallback policy for optional compiled kernels.

Every Pallas kernel in the models is an optimization layered over an
always-available XLA form.  Whether the TPU compiler accepts a kernel
can vary by hardware generation, so the first call may raise a lowering
error — but a raise can equally be the caller's own mistake (bad state
shape, wrong dtype) or a transient runtime fault (a one-off device OOM,
a lost interconnect link).  The policy that distinguishes them: retry the
failing call on the fallback path first.  If the fallback also raises,
the error is the caller's and propagates unchanged.  If the fallback
succeeds, the fast path is disabled for the instance only when the
error is a compile/lowering rejection (which would recur on every
call): immediately for a typed ``NotImplementedError``, after two
consecutive marker-text hits otherwise (a transient error's text can
coincidentally contain a marker).  Transient runtime faults fall back
for this call only, so the kernel gets another chance next step.

Every fall is counted in the obs registry as
``kernel.fallbacks{label, kind}`` (``kind`` = ``transient`` or
``disabled``), so a run that silently left its kernel is visible:
``chip_smoke.py`` fails on any nonzero count.
"""
from __future__ import annotations

import sys
import weakref

from ..obs.registry import metrics

__all__ = ["fallback_call"]

#: consecutive transient falls before a kernel is disabled anyway — a
#: deterministic runtime failure whose message lacks the permanent
#: markers (e.g. VMEM scratch exhaustion surfacing as
#: RESOURCE_EXHAUSTED) must not pay a failed fast-path attempt on every
#: step forever
_MAX_TRANSIENT_FALLS = 3

#: per-kernel-instance consecutive-transient-fall counters, keyed by the
#: object the ``disable`` callback is bound to (the model instance) so
#: the count survives across calls and dies with the instance
_transient_falls: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()

#: per-kernel-instance consecutive marker-hit counters: a *typed*
#: rejection (NotImplementedError) disables on the first hit, but the
#: substring markers below can coincidentally appear in a transient
#: runtime/RPC error's text, so marker-classified errors must recur on
#: the immediately following call before the fast path is disabled for
#: the instance lifetime
_marker_hits: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()

#: consecutive marker hits that prove the rejection deterministic
_MARKER_HITS_TO_DISABLE = 2

#: substrings that identify a deterministic compiler rejection of the
#: kernel itself — these recur on every call, so the fast path is
#: permanently disabled once they repeat.  Anything else
#: (RESOURCE_EXHAUSTED, connection drops, cancelled RPCs) is treated as
#: transient.
_PERMANENT_MARKERS = (
    "Mosaic",            # TPU kernel compiler errors are prefixed with this
    "lowering",          # jax "unsupported lowering" / "lowering rule" paths
    "Unsupported",
    "UNIMPLEMENTED",
    "does not support",
)


def _is_permanent(e: Exception) -> bool:
    """Whether the fast path's failure looks like a deterministic
    lowering / compile rejection (vs a transient runtime fault)."""
    if isinstance(e, NotImplementedError):
        return True
    text = f"{type(e).__name__}: {e}"
    return any(m in text for m in _PERMANENT_MARKERS)


def fallback_call(label, fast, slow, disable, *args):
    """``fast(*args)``, falling back to ``slow(*args)`` on error.

    ``disable``: zero-arg callback run once when the fast path is judged
    *permanently* broken (fallback succeeded where it raised with a
    compile/lowering error) — mark the instance so subsequent calls skip
    straight to ``slow``.  Transient faults fall back without disabling,
    up to ``_MAX_TRANSIENT_FALLS`` consecutive times; a fast-path
    success resets the count.  Pass a *stable* callable — a bound method
    of the kernel's owner, not a fresh per-call lambda: the transient
    counter is keyed on ``disable.__self__`` (or the callable itself),
    so a new closure every call would reset the cap each time.

    Multi-controller SPMD runs re-raise instead of falling back: a
    per-process switch would leave this controller issuing the slow
    path's collectives while peers (whose compiler accepted the kernel)
    run the fast path's — mismatched collective programs hang the job.
    Failing loudly matches the pre-fallback behavior; kernel eligibility
    gating is deterministic, so controllers only diverge on genuinely
    heterogeneous hardware, which needs operator attention anyway."""
    key = getattr(disable, "__self__", disable)
    try:
        out = fast(*args)
    except Exception as e:  # noqa: BLE001 - classified by the retry below
        from .collectives import process_count

        if process_count() > 1:
            raise
        try:
            out = slow(*args)
        except Exception:
            raise e  # both paths fail: the input was bad, not the kernel
        falls = _transient_falls.get(key, 0) + 1
        hits = _marker_hits.get(key, 0) + 1 if _is_permanent(e) else 0
        if (isinstance(e, NotImplementedError)
                or hits >= _MARKER_HITS_TO_DISABLE
                or falls >= _MAX_TRANSIENT_FALLS):
            print(f"{label} disabled ({e!r:.200}); using the fallback path",
                  file=sys.stderr)
            metrics.inc("kernel.fallbacks", label=label, kind="disabled")
            disable()
        else:
            _transient_falls[key] = falls
            _marker_hits[key] = hits  # 0 resets: hits must be consecutive
            metrics.inc("kernel.fallbacks", label=label, kind="transient")
            print(f"{label} fell back ({falls}/{_MAX_TRANSIENT_FALLS}, "
                  f"{e!r:.200}); will retry the fast path next call",
                  file=sys.stderr)
        return out
    else:
        _transient_falls.pop(key, None)
        _marker_hits.pop(key, None)
        return out
