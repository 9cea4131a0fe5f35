"""Persistent executable cache: compiled schedules that survive rebuilds.

Before this cache, every epoch rebuild created fresh ``jax.jit`` objects
(halo bodies, model step/run kernels), and XLA's compilation cache —
keyed by Python function identity — could never hit: identical shapes
recompiled after every AMR commit or repartition.

The cache holds one jitted callable per **structure key** (everything
that shapes the traced program besides argument shapes: mesh, ring
distances, dtype, boundary structure...).  Table *contents* flow through
the callables as runtime arguments, so a rebuild that lands on the same
:class:`~dccrg_tpu.parallel.shapes.ShapeSignature` re-dispatches the
existing executable with the new tables — zero retrace, zero recompile.
jax's own per-function cache keys the argument shapes, which the bucket
ladders keep sticky.

Bounded LRU (``DCCRG_EPOCH_CACHE_SIZE``, default 64 entries): evicting
an entry drops the jitted function object and with it every executable
it compiled.  Telemetry: ``epoch.cache_hits`` / ``epoch.cache_misses``
/ ``epoch.cache_evictions`` counters and the ``epoch.cache_size`` gauge.

Recompile accounting: kernels built through :func:`traced_jit` run a
host-side marker at TRACE time (the wrapped Python body executes only
when jax traces), counting ``epoch.recompiles{kernel=...}`` and a
process-wide per-label trace count (:func:`trace_counts` — what the
shape-stability tests assert on).  Dispatches that triggered a trace are
timed into the ``compile`` phase; warm dispatches cost one counter read.

Zero-cold-start warm restart: the LRU above dies with the process, so a
restarted or rescaled worker used to pay the full compile storm on its
first churn cycle even when its :class:`~dccrg_tpu.parallel.shapes.
ShapeSignature` had been seen before.  :func:`enable_persistent_cache`
wires jax's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
when that is set (auto-enabled at import, so child processes inherit it
purely through the environment), else at the fixed in-checkout
:data:`DEFAULT_CACHE_DIR` for entry points that ask, under the
bucketed-shape discipline: fresh processes
still *trace* (host work), but XLA compiles are served from disk.  A
jax monitoring listener counts the cache's own hit/miss events
(``epoch.persistent_cache{result=hit|miss}``), and a trace whose compile
was served from the persistent cache is counted as
``epoch.warm_compiles{kernel}`` instead of ``epoch.recompiles{kernel}``
— so ``epoch.recompiles == 0`` on a warm restart is a *measured* fact
(the soak's fork-a-fresh-process proof asserts exactly that), while a
cold process keeps counting real compiles as before.
"""
from __future__ import annotations

import os
import re
import threading
import time
from collections import OrderedDict
from pathlib import Path
from typing import NamedTuple

from ..obs.registry import metrics as _metrics

__all__ = [
    "ExecutableCache",
    "BatchStepSpec",
    "WideStepSpec",
    "cohort_key",
    "default_steps_per_dispatch",
    "max_steps_per_dispatch",
    "traced_jit",
    "note_trace",
    "trace_counts",
    "reset_trace_counts",
    "mesh_key",
    "enable_persistent_cache",
    "persistent_cache_dir",
    "persistent_cache_counts",
]


class BatchStepSpec(NamedTuple):
    """A model's step entry point in cohort-batchable form (ISSUE 9).

    Post-PR 5 every epoch-derived table enters the step kernels as a
    runtime ARGUMENT, so batching independent same-shape scenarios is a
    leading-axis stack of ``(args, state, dt)`` triples — not a retrace.
    Each supported model exposes ``batch_step_spec()`` returning one of
    these; the ensemble front-end (``dccrg_tpu/serve/``) stacks the
    per-member ``args``/state and vmaps ``call`` over them inside one
    jitted cohort program.

    * ``kind`` — short model tag (``"gol"``, ``"advection"``, ...);
      rides kernel labels (``ensemble.step.<kind>``) and telemetry.
    * ``kernel_key`` — hashable identity of the member program:
      everything its trace depends on besides argument shapes (halo
      ``structure_key``, dtype, dense dims...).  Two models with EQUAL
      keys compile the same program, so a cohort may apply the template
      member's ``call`` to every member's ``(args, state, dt)`` — that
      is the admission criterion, refining the grid-level
      :class:`~dccrg_tpu.parallel.shapes.ShapeSignature` cohort key.
    * ``call`` — ``call(args, state, dt) -> state``, pure and traceable
      (vmap rides over it); models that take no dt ignore the operand.
    * ``args`` — this member's runtime-argument pytree (halo ring
      tables, gather/face tables...).  Empty for closure-based dense
      fast paths, whose tables are pure functions of the kernel_key.
    * ``dt_dtype`` — dtype the member expects dt in (None = unused).
    * ``steps_per_dispatch`` — how many interior simulation steps ONE
      host dispatch of the cohort body advances (ISSUE 11, "deep
      dispatch"): the member ``call`` is wrapped in a ``lax.fori_loop``
      stepping k times inside the single vmapped jitted program, so the
      host round-trip is paid once per k steps instead of once per
      step.  This is the model's declared default (fed by
      ``DCCRG_ENSEMBLE_K``); the scheduler may pick a different depth
      per dispatch from deadline slack and per-member remaining budgets
      — each distinct depth is its own cached executable.
    """

    kind: str
    kernel_key: tuple
    call: object
    args: tuple = ()
    dt_dtype: object = None
    steps_per_dispatch: int = 1
    #: optional :class:`WideStepSpec` — the exchange-amortized split of
    #: ``call`` (ISSUE 14).  None keeps the exchange-every-step body.
    wide: object = None


class WideStepSpec(NamedTuple):
    """Exchange-amortized split of a member step (ISSUE 14, "wide halo").

    ``call`` fuses exchange + interior update; this spec splits them so a
    deep-dispatch cohort body can pay ONE depth-g exchange per g interior
    steps instead of one per step:

    * ``exchange`` — ``exchange(args, wargs, state) -> state``: refill the
      full default-hood ghost zone (the model's field subset) once.
    * ``interior`` — ``interior(args, wargs, state, dt, j) -> state``: one
      interior step at loop index j since the last exchange, updating
      every row whose ``steps_ok`` exceeds j (the shrinking valid region)
      and freezing the stale fringe.  Local rows are bit-identical to the
      fused ``call`` at every j below ``budget``.
    * ``budget`` — interior steps one exchange funds before OWNED rows go
      stale (min ``steps_ok`` over local rows); the scheduler clamps k to
      it so a dispatch is exactly one exchange.
    * ``args`` — the wide runtime-argument pytree (full-hood ring tables,
      device-extended gather tables, ``steps_ok``, model extras); stacked
      and content-matched alongside ``BatchStepSpec.args``.
    * ``local_mask`` — host ``(D, R)`` bool of owner rows: the set the
      solo-replay oracle byte-compares (ghost rows legitimately hold
      stale or fringe-recomputed values between exchanges).
    """

    exchange: object
    interior: object
    budget: int
    args: tuple = ()
    local_mask: object = None


def max_steps_per_dispatch() -> int:
    """Cap on the deep-dispatch depth k (``DCCRG_ENSEMBLE_K_MAX``,
    default 64): bounds both compile-cache cardinality (one body per
    distinct k) and how stale the host's occupancy view may go between
    dispatches."""
    try:
        cap = int(os.environ.get("DCCRG_ENSEMBLE_K_MAX", 64))
    except ValueError:
        return 64
    return max(cap, 1)


def default_steps_per_dispatch() -> int:
    """The process-default deep-dispatch depth (``DCCRG_ENSEMBLE_K``,
    default 1 — one simulation step per host dispatch, the pre-ISSUE-11
    behavior), clamped to [1, :func:`max_steps_per_dispatch`]."""
    try:
        k = int(os.environ.get("DCCRG_ENSEMBLE_K", 1))
    except ValueError:
        return 1
    return max(1, min(k, max_steps_per_dispatch()))


def cohort_key(spec: "BatchStepSpec", width: int,
               steps_per_dispatch: int | None = None,
               shared_args: bool = False, donate: bool = False,
               wide_g: int = 0) -> tuple:
    """Executable-cache key of a cohort-batched step body: the member
    program's identity plus everything else the batched trace (or its
    buffer-aliasing contract) depends on — the stacked leading-axis
    width, the dispatch depth k (the ``fori_loop`` trip count is
    static, so each depth is one compile: changing ONLY k at a held
    (signature, width) costs exactly one new body), whether the
    runtime-argument tables are broadcast-shared (vmap ``in_axes=None``
    — a different traced program from the per-member stack), whether
    the stacked state is donated, and the wide-halo exchange depth g
    (0 = exchange-every-step; a wide body's block structure
    ``ceil(k/g)`` is static, so changing ONLY g at a held
    (signature, W, k) compiles exactly one new body).  Occupancy churn
    at a held key re-dispatches, never retraces."""
    k = int(spec.steps_per_dispatch if steps_per_dispatch is None
            else steps_per_dispatch)
    return ("ensemble.step", spec.kind, spec.kernel_key, int(width),
            max(k, 1), bool(shared_args), bool(donate), int(wide_g))


def mesh_key(mesh):
    """A hashable identity for a mesh (jax Mesh hashes by devices+axes;
    fall back to object identity if a custom mesh type does not)."""
    try:
        hash(mesh)
        return mesh
    except TypeError:
        return id(mesh)

_trace_lock = threading.Lock()
#: label -> number of times a kernel with that label was traced
_TRACE_COUNTS: dict = {}


def note_trace(label: str) -> None:
    """Record one trace of the kernel ``label`` — called from inside a
    jitted body, so it fires exactly when jax (re)traces.  The
    ``epoch.recompiles`` / ``epoch.warm_compiles`` split is attributed
    by the dispatching :class:`TracedKernel`, which can see whether the
    persistent compilation cache served the compile."""
    with _trace_lock:
        _TRACE_COUNTS[label] = _TRACE_COUNTS.get(label, 0) + 1


def trace_counts() -> dict:
    """Snapshot of per-kernel trace counts since process start (or the
    last :func:`reset_trace_counts`)."""
    with _trace_lock:
        return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    with _trace_lock:
        _TRACE_COUNTS.clear()


def _count(label: str) -> int:
    with _trace_lock:
        return _TRACE_COUNTS.get(label, 0)


#: persistent compilation cache state: the wired directory, the
#: hit/miss totals fed by jax's monitoring events, and whether the
#: listener is installed (once per process)
_PERSISTENT = {"dir": None, "hits": 0, "misses": 0, "listener": False}


def persistent_cache_dir() -> str | None:
    """The wired ``jax_compilation_cache_dir``, or None when the
    persistent cache is not enabled in this process."""
    return _PERSISTENT["dir"]


def persistent_cache_counts() -> dict:
    """Process totals of jax's persistent-compilation-cache events:
    ``{"hits": n, "misses": n}`` (both 0 until the listener sees one)."""
    with _trace_lock:
        return {"hits": _PERSISTENT["hits"],
                "misses": _PERSISTENT["misses"]}


def _on_cache_event(name: str, **kw) -> None:
    # jax._src.monitoring events; the cache records one hit or miss per
    # compiled module, which is exactly the granularity TracedKernel
    # dispatches at (one traced_jit label = one module)
    if name.endswith("/cache_hits"):
        with _trace_lock:
            _PERSISTENT["hits"] += 1
        _metrics.inc("epoch.persistent_cache", result="hit")
    elif name.endswith("/cache_misses"):
        with _trace_lock:
            _PERSISTENT["misses"] += 1
        _metrics.inc("epoch.persistent_cache", result="miss")


#: where :func:`enable_persistent_cache` keeps the cache when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed path inside the
#: checkout (the path is part of each entry's key, so it must not move
#: between runs); listed in ``.gitignore``
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Wire jax's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (no other directory is set in
    code), else :data:`DEFAULT_CACHE_DIR`.  Thresholds are dropped to
    zero so every module is cached — the bucketed-shape discipline keeps
    the entry set small (one per kernel per ShapeSignature), and a
    restarted/rescaled worker landing on a previously-seen signature
    compiles nothing.  Idempotent."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _PERSISTENT["listener"]:
        from jax._src import monitoring

        monitoring.register_event_listener(_on_cache_event)
        _PERSISTENT["listener"] = True
    _PERSISTENT["dir"] = path
    return path


class TracedKernel:
    """A jitted callable with trace accounting: dispatches that trigger
    a (re)trace are timed into the ``compile`` phase; warm dispatches
    add one dict read.  Transparent under another jit's trace — the
    marker then counts the inlined trace, which is still host compile
    work."""

    __slots__ = ("fn", "label")

    def __init__(self, fn, label: str):
        self.fn = fn
        self.label = label

    def __call__(self, *args):
        if not _metrics.enabled:
            return self.fn(*args)
        n0 = _count(self.label)
        m0 = _PERSISTENT["misses"]
        t0 = time.perf_counter()
        out = self.fn(*args)
        if _count(self.label) != n0:
            _metrics.phase_add("compile", time.perf_counter() - t0)
            # with the persistent cache wired, every real XLA compile
            # reports exactly one hit or miss event — so a trace that
            # caused NO miss paid no compile (served from disk, or an
            # inline retrace under an outer jit) and counts warm; with
            # the cache off, every trace is a cold recompile as before
            if _PERSISTENT["dir"] is not None \
                    and _PERSISTENT["misses"] == m0:
                _metrics.inc("epoch.warm_compiles", kernel=self.label)
            else:
                _metrics.inc("epoch.recompiles", kernel=self.label)
        return out


def _module_name(label: str) -> str:
    """The HLO module name a kernel labeled ``label`` compiles under:
    jax names modules ``jit_<fn.__name__>``, and :func:`traced_jit`
    renames its wrapper to the (identifier-sanitized) label."""
    return "jit_" + re.sub(r"[^0-9A-Za-z_]", "_", label)


def traced_jit(label: str, fn, **jit_kwargs) -> TracedKernel:
    """``jax.jit(fn)`` with trace accounting under ``label`` (see
    :class:`TracedKernel`).  The wrapper is renamed to the sanitized
    label so the compiled program's module — the name a profiler
    capture gives every op it ran — is ``jit_<label>``
    (``advection.dense_run`` -> ``jit_advection_dense_run``): device
    time attributes back to exactly the kernel names the recompile
    counters use."""
    import jax

    def marked(*args):
        note_trace(label)
        return fn(*args)

    module = _module_name(label)
    marked.__name__ = marked.__qualname__ = module[len("jit_"):]
    return TracedKernel(jax.jit(marked, **jit_kwargs), label)


def _default_size() -> int:
    try:
        n = int(os.environ.get("DCCRG_EPOCH_CACHE_SIZE", 64))
    except ValueError:
        return 64
    return max(n, 1)


class ExecutableCache:
    """Bounded LRU of compiled schedule callables, keyed by structure
    keys (hashable tuples).  Thread-safe; the builder runs outside the
    lock (builders may themselves consult the cache)."""

    def __init__(self, maxsize: int | None = None):
        self.maxsize = _default_size() if maxsize is None else max(int(maxsize), 1)
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()

    def get(self, key, builder):
        """The cached value for ``key``, building (and possibly evicting
        the least-recently-used entry) on a miss."""
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                val = self._entries[key]
                hit = True
            else:
                hit = False
        if hit:
            _metrics.inc("epoch.cache_hits")
            return val
        _metrics.inc("epoch.cache_misses")
        val = builder()
        with self._lock:
            self._entries[key] = val
            self._entries.move_to_end(key)
            evicted = 0
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                evicted += 1
            size = len(self._entries)
        if evicted:
            _metrics.inc("epoch.cache_evictions", evicted)
        _metrics.gauge("epoch.cache_size", size)
        return val

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


# auto-wire the persistent compilation cache when the environment names
# one — child processes receive the warm-restart cache the same way they
# receive their fault schedule (DCCRG_FAULT): purely via env
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    enable_persistent_cache()
