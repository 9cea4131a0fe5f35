"""Ensemble serving: thousands of independent scenarios per executable.

The production story for "millions of users" is not one giant grid — it
is many independent simulation instances (parameter sweeps, per-user
scenarios, Monte Carlo ensembles) multiplexed onto shared hardware, the
"rapid and flexible simulation development" use case the dccrg paper
targets (Honkonen et al., CPC 2013).  PR 5 made the multiplexing
tractable: bucketed table shapes mean independent grids land on a
*shared* :class:`~dccrg_tpu.parallel.shapes.ShapeSignature`, and PR 8's
``ShapeSignature.rings`` made ``grid.shape_signature()`` alone predict
executable-cache behavior — so ONE compiled program can serve a whole
fleet.  This module is the front-end that exploits it:

* **Cohorts** group admitted scenarios by signature (refined by the
  member program's :class:`~dccrg_tpu.parallel.exec_cache.BatchStepSpec`
  ``kernel_key``) and step every member through a single jitted cohort
  body: ``jax.vmap`` over a leading member axis of the stacked
  ``(args, state, dt)`` triples.  The tables are already kernel
  ARGUMENTS post-PR 5, so batching is a leading-axis stack — members
  may carry *different* table contents (different AMR patterns at one
  signature) without retracing anything.

* **Admission/retirement never retrace**: cohort widths ride a
  power-of-two ladder with shrink hysteresis (the
  ``parallel/shapes.py`` discipline), inactive slots are masked by a
  runtime-argument occupancy mask, and admitting or retiring a member
  is an ``.at[slot].set`` / slice on the stacked arrays — the cohort
  executable is keyed only by ``(kernel_key, width)``
  (:func:`~dccrg_tpu.parallel.exec_cache.cohort_key`), so occupancy
  churn at a held width re-dispatches, never recompiles.

* **Scheduler** runs the request queue: scenarios are admitted into the
  matching cohort, cohorts step round-robin or by earliest member
  deadline, finished members retire without disturbing the rest, and
  the backlog depth feeds :func:`~dccrg_tpu.resilience.elastic.
  queue_depth_signal` (the PR 8 follow-on).

* **Per-tenant telemetry** through ``obs/``: counters
  ``ensemble.admitted`` / ``ensemble.retired`` /
  ``ensemble.rejected{reason}`` / ``ensemble.steps_served{tenant}``,
  gauges ``ensemble.queue_depth`` and
  ``ensemble.cohort_occupancy{signature}`` (occupied fraction of the
  cohort width, labeled by the cross-process-stable
  ``ShapeSignature.label()``), the ``ensemble.queue_latency`` histogram
  (submit → admit seconds), and the ``ensemble.admit`` /
  ``ensemble.step`` phases.

* **Request-level SLO plane** (ISSUE 10): every scenario carries a
  request id and its lifecycle is recorded three ways — latency
  histograms ``ensemble.queue_wait_s{tenant}`` (submit → admit),
  ``ensemble.service_s{tenant, model}`` (admit → retire) and
  ``ensemble.e2e_s{tenant}`` (submit → retire), all log-bucketed at
  ``obs.slo.SLO_RESOLUTION`` so exported snapshots answer p50/p95/p99
  post-hoc (``tools/slo_report.py``); timeline spans
  ``request.queued`` / ``request.admit`` / ``request.step`` /
  ``request.retire`` / ``request.e2e`` carrying ``request=<id>``
  context args, so a slow request cross-references to kernel spans in
  the merged device trace; and the ``obs.flightrec`` black box, whose
  in-flight table names exactly the requests being served when a
  postmortem fires.  Deadlines are absolute ``time.perf_counter()``
  stamps (the timebase of ``submitted_at``); a member retired past its
  deadline counts ``ensemble.deadline_miss{tenant}`` and
  ``ensemble.slo_violations{class=deadline}`` — misses are COUNTED,
  never raised, like every oracle in this repo.  Optional targets
  ``DCCRG_SLO_QUEUE_S`` / ``DCCRG_SLO_E2E_S`` (seconds) count
  ``ensemble.slo_violations{class=queue_wait|e2e}`` when exceeded.

* **Deep dispatch** (ISSUE 11): the hot loop pays one host dispatch
  per **k** simulation steps, not per step.  The member ``call`` is
  wrapped in a ``lax.fori_loop`` stepping k interior steps inside the
  one vmapped jitted cohort body (the split-phase halo structure stays
  at PROGRAM level: each interior step's exchange starts and completes
  inside the loop body, exactly as the member program does solo).  k is static per compiled body (``cohort_key`` carries
  it — changing only k at a held (signature, width) compiles exactly
  one new body); per-member ``remaining`` budgets ride along as a
  runtime argument so the occupancy mask freezes a member mid-k-block
  the moment its budget is spent, the same way it freezes exhausted
  slots mid-stack.  The scheduler picks k per dispatch
  (:meth:`Scheduler.select_k`) from the configured depth
  (``DCCRG_ENSEMBLE_K``, capped by ``DCCRG_ENSEMBLE_K_MAX``), clamped
  to the deepest step any active member can still use and to the
  earliest member deadline's slack (a tight deadline must not wait out
  a 16-step block it only needed 2 steps of).

* **Exchange amortization** (ISSUE 14): deep dispatch amortized the
  HOST round-trip, but every interior step of the k-loop still ran a
  full halo exchange.  When a member program ships a
  :class:`~dccrg_tpu.parallel.exec_cache.WideStepSpec` (a depth-g
  default-hood ghost zone whose gather tables cover every replica row,
  plus the ``steps_ok`` staleness ledger — ``parallel/wide_halo.py``),
  the cohort body becomes ``ceil(k/g)`` blocks of [one exchange, then
  up to g interior steps]: each interior step consumes one
  stencil-radius shell of the exchanged zone, recomputing the shrinking
  ghost fringe redundantly instead of re-exchanging, and the next block
  refills.  g is static per compiled body (``cohort_key`` carries
  ``wide_g`` — changing only g compiles exactly one new body) and
  :meth:`Scheduler.select_k` clamps scheduled depths to the exchange
  budget so a scheduled dispatch pays exactly ONE exchange; the
  host-side ``halo.exchanges_per_step`` gauge (ceiling-gated) records
  the amortization — ~1/k when wide halos engage, 1.0 legacy.
  Correctness anchor unchanged: owner-local rows are bit-identical to
  exchange-every-step stepping (the wide gather tables keep the
  owner's slot order and ``ordered_sum`` association chain), so the
  solo-replay oracle still byte-compares them — ghost replica rows are
  the only rows allowed to go stale, and only inside a block.

* **Buffer donation**: the stacked cohort state is donated to the step
  body (``donate_argnums`` — the jit aliases input and output buffers)
  so XLA stops materializing a second copy of the fleet state every
  dispatch: the steady-state HBM cost per cohort drops from ~2x state
  to ~1x and the copy disappears from the dispatch path.  Backends
  without donation (CPU) fall back to copying with a one-time jax
  warning; ``DCCRG_ENSEMBLE_DONATE=0`` opts out.  The solo-replay
  oracle snapshots its sampled member's row BEFORE the dispatch — a
  donated input buffer must never be read after the call.

* **Broadcast-shared tables** (the PR 9 follow-on): members of one
  model instance carry byte-identical runtime-argument tables, and the
  pre-ISSUE-11 cohort stacked W copies of them.  A cohort now starts
  in shared mode — ONE broadcast copy of the tables, vmap
  ``in_axes=None`` — and admission content-checks each joiner's tables
  against the shared copy (object identity first, byte compare once on
  mismatch); a joiner with genuinely different tables promotes the
  cohort to the per-member stack (one new compile, like width growth,
  counted ``ensemble.cohort_promotions``).  Per-member HBM falls by
  ~``tables x (W-1)/W`` for the homogeneous cohorts that dominate
  parameter sweeps — measured by the
  ``ensemble.hbm_bytes_per_member{model}`` gauge (``obs/hbm.py``),
  which ``tools/telemetry_diff.py`` ceiling-gates.

Correctness anchor: a cohort-stepped scenario is **bit-identical** to
the same member stepped solo through its own model kernel (vmap batches
the member program without reassociating its arithmetic; a depth-k
dispatch must match k solo steps).  The always-available oracle —
``DCCRG_ENSEMBLE_VERIFY=1``, or ``Ensemble(verify=True)`` — replays
one sampled active member solo per cohort dispatch (k solo steps for a
depth-k dispatch, clamped to the member's own advance) and
byte-compares every field; mismatches are COUNTED
(``ensemble.verify_mismatches{field}`` under the ``ensemble.verify``
phase), never raised, mirroring the halo/epoch oracle protocol.
"""
from __future__ import annotations

import itertools
import os
import time
from collections import deque

import numpy as np

from ..obs import cost as obs_cost
from ..obs import stream as obs_stream
from ..obs.events import timeline
from ..obs.flightrec import recorder as flightrec
from ..obs.hbm import sample_ensemble_hbm
from ..obs.registry import metrics
from ..obs.slo import SLO_RESOLUTION
from ..parallel.exec_cache import (
    BatchStepSpec,
    cohort_key,
    max_steps_per_dispatch,
    traced_jit,
)
from ..parallel.halo import record_dispatch_exchanges
from ..parallel.mesh import SHARD_AXIS
from ..parallel.wide_halo import halo_depth_cap, wide_enabled

# the request-latency series resolve finer than the octave default so
# exported p99 estimates sit within one ~9% bucket (obs/slo.py); same
# registration in every serving process keeps cross-process merges exact
for _h in ("ensemble.queue_wait_s", "ensemble.service_s",
           "ensemble.e2e_s", "ensemble.queue_latency"):
    metrics.set_histogram_resolution(_h, SLO_RESOLUTION)

__all__ = [
    "Scenario",
    "Cohort",
    "Scheduler",
    "Ensemble",
    "cohort_width",
    "verify_enabled",
    "donation_enabled",
    "shared_tables_enabled",
]


def verify_enabled() -> bool:
    """Whether the solo-replay oracle is armed process-wide
    (``DCCRG_ENSEMBLE_VERIFY=1``)."""
    return os.environ.get("DCCRG_ENSEMBLE_VERIFY", "0") == "1"


def donation_enabled() -> bool:
    """Whether cohort step bodies donate the stacked state
    (``DCCRG_ENSEMBLE_DONATE``, default on).  Donation aliases the
    input and output buffers so a dispatch stops costing a second copy
    of the fleet state; backends without donation support copy as
    before (jax warns once per body)."""
    return os.environ.get("DCCRG_ENSEMBLE_DONATE", "1") != "0"


def shared_tables_enabled() -> bool:
    """Whether cohorts start with ONE broadcast-shared copy of the
    runtime-argument tables instead of a per-member stack
    (``DCCRG_ENSEMBLE_SHARED``, default on).  Heterogeneous-table
    members still work: admission promotes the cohort to the stacked
    form when a joiner's tables differ by content."""
    return os.environ.get("DCCRG_ENSEMBLE_SHARED", "1") != "0"


def _slo_target(name: str) -> float | None:
    """Optional SLO target in seconds (``DCCRG_SLO_QUEUE_S`` /
    ``DCCRG_SLO_E2E_S``); None when unset or unparsable."""
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def _shrink() -> float:
    try:
        s = float(os.environ.get("DCCRG_ENSEMBLE_SHRINK", 0.5))
    except ValueError:
        return 0.5
    return min(max(s, 0.0), 1.0)


def cohort_width(n: int, prev: int | None = None) -> int:
    """Cohort slot budget for ``n`` members: the next power of two, with
    shrink hysteresis against the held width ``prev`` — occupancy
    wiggling around a ladder boundary must not flap the stacked shapes
    (each width is its own compiled cohort body).  Idempotent, like the
    ``parallel/shapes.py`` buckets: ``cohort_width(w, w) == w``."""
    n = max(int(n), 1)
    w = 1
    while w < n:
        w *= 2
    if prev is not None and prev >= w:
        if w == prev or n >= _shrink() * prev:
            return prev
    return w


class Scenario:
    """One admitted (or pending) simulation instance.

    ``model`` is a bound workload instance (``Advection`` / ``GameOfLife``
    / ``Vlasov``) exposing ``batch_step_spec()``; ``state`` its state
    pytree; ``steps`` how many steps to serve; ``dt`` the member's own
    timestep (ignored by models that take none); ``deadline`` an
    optional absolute time used by the deadline scheduling policy.

    Lifecycle: ``queued`` → ``active`` → ``done`` (``result`` holds the
    final state pytree), or ``rejected`` (``reject_reason`` says why —
    counted, never raised).  ``id`` is the request id every lifecycle
    span, histogram sample and flight-recorder entry is stamped with;
    ``submitted_at``/``admitted_at``/``retired_at`` are
    ``time.perf_counter()`` stamps (``deadline`` lives in the same
    timebase) — the raw material of the SLO plane."""

    _ids = itertools.count()

    def __init__(self, model, state, steps: int, dt=None,
                 tenant: str = "default", deadline: float | None = None):
        self.id = next(Scenario._ids)
        self.model = model
        self.state = state
        self.steps = int(steps)
        self.dt = dt
        self.tenant = str(tenant)
        self.deadline = deadline
        self.status = "queued"
        self.reject_reason = None
        self.steps_done = 0
        self.result = None
        self.submitted_at = time.perf_counter()
        self.admitted_at = None
        self.retired_at = None
        #: filled at submit: the member program + per-member tables
        self.spec: BatchStepSpec | None = None
        self.signature = None

    @property
    def remaining(self) -> int:
        return max(self.steps - self.steps_done, 0)


def _wide_of(spec):
    """The spec's :class:`WideStepSpec` when exchange amortization
    engages for it, else None.  Engagement needs a wide plan (the model
    found a usable depth-g ghost zone), the process-wide
    ``DCCRG_ENSEMBLE_WIDE`` switch, and a budget of at least 2 interior
    steps — one exchange funding one step is exactly the legacy body,
    so budget-1 plans stay on the per-step path (every hood-0 grid
    lands here, unchanged)."""
    wide = getattr(spec, "wide", None)
    if wide is not None and wide_enabled() and int(wide.budget) >= 2:
        return wide
    return None


def _state_sig(state) -> tuple:
    """Hashable structure+shape+dtype identity of a state pytree — the
    defensive refinement of the cohort key (equal kernel keys imply
    compatible shapes, but the stacked buffers need exact equality)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(state)
    return (str(treedef),) + tuple(
        (tuple(x.shape), str(np.asarray(x).dtype) if not hasattr(x, "dtype")
         else str(x.dtype)) for x in leaves
    )


class Cohort:
    """A fleet of same-program scenarios stepping as one stacked batch.

    Holds ``[W, ...]``-stacked member args and state (leading axis =
    member slot, sharded ``[W, D, ...]`` on the device axis beneath),
    host-side occupancy bookkeeping, and the compiled cohort body from
    the template grid's executable cache.  Admission writes a member
    into a free slot; retirement slices its final state out; neither
    touches the compiled program."""

    def __init__(self, scenario: Scenario, width: int | None = None,
                 shared: bool | None = None, k: int | None = None):
        import jax
        import jax.numpy as jnp

        spec = scenario.spec
        self.spec = spec
        self.signature = scenario.signature
        self.sig_label = (self.signature.label()
                          if self.signature is not None else "unknown")
        grid = scenario.model.grid
        self.mesh = grid.mesh
        self.exec_cache = grid.exec_cache
        self.W = cohort_width(1) if width is None else int(width)
        self.state_sig = _state_sig(scenario.state)
        self.dt_dtype = np.dtype(spec.dt_dtype
                                 if spec.dt_dtype is not None
                                 else np.float32)
        #: default dispatch depth: how many interior steps one host
        #: dispatch advances unless the scheduler picks otherwise
        self.k = max(int(k if k is not None
                         else spec.steps_per_dispatch), 1)
        self._donate = donation_enabled()
        #: None until the first donated dispatch MEASURES whether the
        #: backend actually aliased the buffers (CPU does not — jax
        #: warns and copies); feeds the in-flight factor of the
        #: per-member HBM gauge
        self._donate_effective: bool | None = None
        self.members: list = [None] * self.W
        self._remaining = np.zeros(self.W, np.int64)
        self._occupied = np.zeros(self.W, bool)
        self._dts = np.zeros(self.W, self.dt_dtype)
        #: the member program's wide-halo plan when exchange
        #: amortization engages for this cohort (ISSUE 14), else None —
        #: the cohort then carries the wide exchange/interior tables
        #: alongside the legacy ones and its deep bodies pay ceil(k/g)
        #: exchanges instead of k
        self._wide = _wide_of(spec)
        #: min exchange budget over admitted members: the deepest g any
        #: dispatch may run before some member's OWNED rows would go
        #: stale (heterogeneous same-signature joiners can lower it)
        self._wide_budget = (int(self._wide.budget)
                             if self._wide is not None else 0)
        #: the template member's runtime tables, kept as submitted
        #: (host refs): the content key joiners are checked against in
        #: shared mode, and the stacking source on promotion.  With
        #: wide halos engaged this is the COMBINED (legacy, wide)
        #: pytree — both table sets ride the same stack/share/admit
        #: machinery
        self._args_src = self._combined_args(spec)
        self.shared_args = (shared_tables_enabled() if shared is None
                            else bool(shared))
        if self.shared_args:
            # ONE broadcast copy of the tables (vmap in_axes=None):
            # members of one model instance carry byte-identical
            # tables, so stacking W copies only burned HBM
            self._args = jax.tree_util.tree_map(
                lambda x: self._put_member(jnp.asarray(x)),
                self._args_src,
            )
        else:
            self._args = jax.tree_util.tree_map(
                lambda x: self._put(jnp.stack([jnp.asarray(x)] * self.W)),
                self._args_src,
            )
        # stacked state: slot 0's values replicated as padding (pad
        # slots are masked, their contents only need to be
        # shape-compatible and finite)
        self._state = jax.tree_util.tree_map(
            lambda x: self._put(jnp.stack([jnp.asarray(x)] * self.W)),
            scenario.state,
        )
        #: compiled bodies by dispatch depth (all ride the grid's
        #: executable cache; this dict only skips the cache lookup)
        self._kernels: dict = {}
        self._verify_rr = 0
        #: EMA of wall seconds per interior step (dispatch-side), the
        #: service-time estimate deadline-slack k selection divides by
        self.step_s_ema: float | None = None
        #: highest occupied fraction this cohort ever reached — the
        #: monotone series the telemetry floor gate watches (live
        #: occupancy legitimately returns to 0 after retirement)
        self.peak_occupancy = 0.0
        self._sample_hbm()

    # ------------------------------------------------------------ device

    def _put(self, stacked):
        """Shard a ``[W, D, ...]`` stacked leaf on the device axis (axis
        1 — the member axis is replicated).  ``[W]``-only leaves stay
        replicated."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if stacked.ndim < 2:
            return stacked
        try:
            spec = P(None, SHARD_AXIS, *([None] * (stacked.ndim - 2)))
            return jax.device_put(stacked, NamedSharding(self.mesh, spec))
        except Exception:  # noqa: BLE001 — fall back to default placement
            return stacked

    def _put_member(self, leaf):
        """Shard ONE member's (unstacked) table on the device axis
        (axis 0 for the ``[D, ...]`` epoch tables); leaves without a
        device axis stay replicated — like :meth:`_put`, a layout hint
        the jit re-lands as its program requires."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if leaf.ndim < 1:
            return leaf
        try:
            spec = P(SHARD_AXIS, *([None] * (leaf.ndim - 1)))
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))
        except Exception:  # noqa: BLE001 — fall back to default placement
            return leaf

    def _combined_args(self, spec) -> object:
        """The runtime-table pytree one member contributes: the legacy
        tables alone, or the ``(legacy, wide)`` pair when this cohort
        runs wide-halo bodies — combining them lets stacking, admission
        content-checks, ``set_slot`` writes and promotion treat both
        table sets as one tree."""
        if self._wide is None:
            return spec.args
        return (spec.args, spec.wide.args)

    def _wide_g(self, k: int) -> int:
        """Exchange depth for a depth-``k`` dispatch: how many interior
        steps each exchange funds.  Clamped to the cohort's member-min
        budget and ``DCCRG_HALO_DEPTH``; below 2 the wide body IS the
        legacy body, so 0 (disengaged) is returned instead."""
        if self._wide is None:
            return 0
        g = min(int(k), self._wide_budget, halo_depth_cap())
        return g if g >= 2 else 0

    def _kernel_for(self, k: int):
        """The compiled depth-``k`` cohort body, via the grid's
        executable cache: one body per (kernel_key, W, k, shared,
        donate, wide_g) — occupancy churn at a held key re-dispatches,
        a new depth (or a new exchange depth g) compiles exactly one
        new body."""
        k = max(int(k), 1)
        g = self._wide_g(k)
        # a wide cohort's legacy-depth body (g clamped under 2) still
        # destructures the combined (legacy, wide) args pytree — it
        # must never share a cache entry with a plain cohort's body at
        # the same (kernel_key, W, k), so its key carries -1, not 0
        key_g = g if g else (-1 if self._wide is not None else 0)
        kern = self._kernels.get((k, g))
        if kern is None:
            kern = self.exec_cache.get(
                cohort_key(self.spec, self.W, k, self.shared_args,
                           self._donate, wide_g=key_g),
                lambda: self._build_kernel(k, g),
            )
            self._kernels[(k, g)] = kern
        return kern

    def _build_kernel(self, k: int, g: int = 0):
        """The compiled cohort body: vmap of the member program over the
        stacked leading axis (tables broadcast via ``in_axes=None`` in
        shared mode), inactive slots frozen by the runtime occupancy
        mask.  Depth k > 1 wraps the vmapped step in a ``lax.fori_loop``
        — k interior steps per host dispatch — with the per-member
        ``remaining`` budgets clamping each slot mid-loop the moment
        its budget is spent (``mask & (remaining > i)``): no member
        ever overshoots its requested steps.  The stacked state is
        donated (when enabled) so the dispatch aliases instead of
        copying it; ``remaining``/``dts``/``mask`` are runtime
        arguments, so neither budgets nor occupancy ever retrace.

        Exchange depth ``g >= 2`` (ISSUE 14) replaces the per-step body
        with ``ceil(k/g)`` unrolled blocks of [one wide exchange, then
        a ``fori_loop`` of up to g interior steps]: interior step j
        updates exactly the rows whose ``steps_ok`` exceeds j (every
        owned row, by the budget clamp) and freezes the stale ghost
        fringe at its exchanged values.  The split-phase DMA structure
        stays at PROGRAM level inside the wide exchange, exactly as in
        the member program."""
        import jax
        import jax.numpy as jnp

        spec = self.spec
        wide = self._wide if g >= 2 else None
        # with wide halos engaged the cohort args are the combined
        # (legacy, wide) pair even when a particular body runs legacy
        # (k=1, or g clamped under 2) — those bodies destructure
        call = (spec.call if self._wide is None
                else lambda a, s, d: spec.call(a[0], s, d))
        in_axes = (None, 0, 0) if self.shared_args else (0, 0, 0)
        donate = (1,) if self._donate else ()

        def freeze_tree(live, new, old):
            def freeze(n, o):
                m = live.reshape(live.shape + (1,) * (n.ndim - 1))
                return jnp.where(m, n, o)

            return jax.tree_util.tree_map(freeze, new, old)

        if wide is not None:
            wax = None if self.shared_args else 0
            vex = jax.vmap(wide.exchange, in_axes=(wax, wax, 0))
            vin = jax.vmap(wide.interior, in_axes=(wax, wax, 0, 0, None))

            def cohort_step(args, state, remaining, dts, mask):
                largs, wargs = args
                st = state
                for lo in range(0, k, g):
                    # one depth-g exchange funds this whole block; the
                    # per-member budgets freeze slots exactly as the
                    # legacy loop does, exchange included
                    st = freeze_tree(mask & (remaining > lo),
                                     vex(largs, wargs, st), st)

                    def one(i, s, lo=lo):
                        stepped = vin(largs, wargs, s, dts, i)
                        return freeze_tree(mask & (remaining > lo + i),
                                           stepped, s)

                    st = jax.lax.fori_loop(0, min(g, k - lo), one, st)
                return st
        elif k == 1:
            def cohort_step(args, state, remaining, dts, mask):
                stepped = jax.vmap(call, in_axes=in_axes)(args, state,
                                                          dts)
                return freeze_tree(mask, stepped, state)
        else:
            def cohort_step(args, state, remaining, dts, mask):
                def one(i, st):
                    stepped = jax.vmap(call, in_axes=in_axes)(args, st,
                                                              dts)
                    return freeze_tree(mask & (remaining > i), stepped,
                                       st)

                return jax.lax.fori_loop(0, k, one, state)

        return traced_jit(f"ensemble.step.{spec.kind}", cohort_step,
                          donate_argnums=donate)

    # ------------------------------------------------- runtime tables

    def _args_match(self, args) -> bool:
        """Whether a joiner's runtime tables are content-identical to
        the shared copy.  Object identity first (members of one model
        instance hand the SAME table arrays to every spec — free);
        byte compare once otherwise (one admission-time host pass, only
        for cross-instance joiners)."""
        import jax

        a = jax.tree_util.tree_leaves(self._args_src)
        b = jax.tree_util.tree_leaves(args)
        if len(a) != len(b):
            return False
        for x, y in zip(a, b):
            if x is y:
                continue
            xv, yv = np.asarray(x), np.asarray(y)
            if (xv.shape != yv.shape or xv.dtype != yv.dtype
                    or not np.array_equal(xv, yv)):
                return False
        return True

    def promote_to_stacked(self) -> None:
        """Re-land the broadcast-shared tables as a per-member ``[W,
        ...]`` stack so a joiner with genuinely different tables can
        occupy a slot.  Every current member shares the (verified
        content-identical) template tables, so stacking the template is
        loss-free; state rows are untouched.  Costs exactly one new
        cohort body per depth used afterwards (counted
        ``ensemble.cohort_promotions``), like width growth."""
        import jax
        import jax.numpy as jnp

        if not self.shared_args:
            return
        self._args = jax.tree_util.tree_map(
            lambda x: self._put(jnp.stack([jnp.asarray(x)] * self.W)),
            self._args_src,
        )
        self.shared_args = False
        self._kernels = {}
        metrics.inc("ensemble.cohort_promotions")
        self._member_bytes_cache = None
        self._sample_hbm()

    # --------------------------------------------------------- memory

    def member_hbm_bytes(self, in_flight: bool | None = None) -> int:
        """Measured device bytes per member: unique table buffers
        (shared tables count ONCE) plus the stacked state, divided by
        the width.  ``in_flight`` prices the dispatch-time state copy —
        2x state without effective donation, 1x with (measured, not
        assumed: the first donated dispatch checks whether the backend
        really invalidated the input buffers)."""
        cached = getattr(self, "_member_bytes_cache", None)
        if cached is None:
            import jax

            seen: set = set()
            args_b = 0
            for leaf in jax.tree_util.tree_leaves(self._args):
                if id(leaf) in seen:
                    continue
                seen.add(id(leaf))
                args_b += int(getattr(leaf, "nbytes", 0))
            state_b = sum(int(getattr(x, "nbytes", 0))
                          for x in jax.tree_util.tree_leaves(self._state))
            cached = self._member_bytes_cache = (args_b, state_b)
        args_b, state_b = cached
        factor = 1 if (in_flight is False or self._donate_effective) \
            else 2
        return int((args_b + state_b * factor) / max(self.W, 1))

    def member_hbm_bytes_stacked_tables(self) -> int:
        """What the pre-ISSUE-11 layout would hold per member: the
        template tables stacked W times (so per-member table cost is
        the FULL table set) plus the undonated double-buffered state —
        the baseline the shared-table + donation win is measured
        against."""
        import jax

        args_b = sum(int(np.asarray(x).nbytes)
                     for x in jax.tree_util.tree_leaves(self._args_src))
        cached = getattr(self, "_member_bytes_cache", None)
        if cached is None:
            self.member_hbm_bytes()
            cached = self._member_bytes_cache
        _args, state_b = cached
        return int(args_b + state_b * 2 / max(self.W, 1))

    def _sample_hbm(self) -> None:
        sample_ensemble_hbm(self.spec.kind, self.member_hbm_bytes())

    # -------------------------------------------------------- membership

    def compatible(self, scenario: Scenario) -> bool:
        return (scenario.spec is not None
                and scenario.spec.kind == self.spec.kind
                and scenario.spec.kernel_key == self.spec.kernel_key
                and _state_sig(scenario.state) == self.state_sig
                # wide-halo engagement must agree: the combined args
                # pytree (and so every compiled body) has a different
                # structure when the wide tables ride along
                and (_wide_of(scenario.spec) is None)
                == (self._wide is None))

    def free_slots(self) -> np.ndarray:
        return np.flatnonzero(~self._occupied)

    @property
    def occupancy(self) -> int:
        return int(self._occupied.sum())

    def admit(self, scenario: Scenario, slot: int) -> None:
        """Write one member into ``slot``: its state and dt land in the
        stacked arrays; its runtime tables land in the stack too
        (stacked mode) or are content-verified against the one
        broadcast copy (shared mode — a genuinely different joiner
        first promotes the cohort to the stack).  Shapes never change,
        so nothing retraces."""
        import jax

        slot = int(slot)
        if self._occupied[slot]:
            raise ValueError(f"slot {slot} already occupied")
        joiner_args = self._combined_args(scenario.spec)
        if self.shared_args and not self._args_match(joiner_args):
            self.promote_to_stacked()
        if self._wide is not None:
            # a heterogeneous joiner may fund fewer interior steps per
            # exchange than the template: the cohort's dispatch depth g
            # drops to the member minimum (one new body, like a depth
            # change — never a wrong row)
            self._wide_budget = min(self._wide_budget,
                                    int(scenario.spec.wide.budget))
        self.members[slot] = scenario
        self._occupied[slot] = True
        self._remaining[slot] = scenario.remaining
        self._dts[slot] = (self.dt_dtype.type(scenario.dt)
                           if scenario.dt is not None else 0)
        set_slot = lambda S, x: S.at[slot].set(x)
        if not self.shared_args:
            self._args = jax.tree_util.tree_map(
                set_slot, self._args, joiner_args
            )
        self._state = jax.tree_util.tree_map(
            set_slot, self._state, scenario.state
        )
        scenario.status = "active"
        if scenario.admitted_at is None:
            # growth re-lands members through admit(); their first
            # admission stamp is the one queue-wait accounting uses
            scenario.admitted_at = time.perf_counter()
        self.peak_occupancy = max(self.peak_occupancy,
                                  self.occupancy / max(self.W, 1))

    def member_state(self, slot: int):
        """The current state pytree of one slot (a device-array slice)."""
        import jax

        return jax.tree_util.tree_map(lambda S: S[int(slot)], self._state)

    def retire(self, slot: int) -> Scenario:
        """Free one slot: slice the member's final state out of the
        stack and hand the finished scenario back.  The other members'
        rows are untouched and the compiled body unchanged."""
        slot = int(slot)
        scn = self.members[slot]
        scn.result = self.member_state(slot)
        scn.status = "done"
        scn.retired_at = time.perf_counter()
        self.members[slot] = None
        self._occupied[slot] = False
        self._remaining[slot] = 0
        return scn

    def finished_slots(self) -> np.ndarray:
        return np.flatnonzero(self._occupied & (self._remaining <= 0))

    def min_deadline(self) -> float:
        dls = [m.deadline for m in self.members
               if m is not None and m.deadline is not None]
        return min(dls) if dls else float("inf")

    def min_deadline_tenant(self) -> str | None:
        """Tenant of the earliest-deadline member (None without one) —
        the identity whose predicted queue wait charges the slack
        clamp when the cost plane is armed (ROADMAP item 3 (b))."""
        best, tenant = float("inf"), None
        for m in self.members:
            if m is not None and m.deadline is not None \
                    and m.deadline < best:
                best, tenant = m.deadline, m.tenant
        return tenant

    # -------------------------------------------------------------- step

    def active_mask(self) -> np.ndarray:
        return self._occupied & (self._remaining > 0)

    def step(self, k: int | None = None) -> int:
        """One cohort dispatch advancing every occupied slot with
        remaining work by up to ``k`` interior steps (default: the
        cohort's configured depth) of its own dt, inside the single
        compiled program; inactive, exhausted and mid-k-exhausted slots
        are frozen by the mask + per-member remaining budgets.  Returns
        total member-steps served (``n_members`` at k=1, as before)."""
        import jax
        import jax.numpy as jnp

        mask = self.active_mask()
        n = int(mask.sum())
        if n == 0:
            return 0
        k = self.k if k is None else max(int(k), 1)
        g = self._wide_g(k)
        kernel = self._kernel_for(k)
        #: per-member steps this dispatch really advances (the in-loop
        #: clamp mirrors this on device)
        advanced = np.where(mask, np.minimum(self._remaining, k), 0)
        # the solo-replay oracle samples its member BEFORE the dispatch:
        # under donation the stacked input buffers alias into the output
        # and must never be read after the call
        verify_slot = pre_member = None
        if self._verify_active():
            slots = np.flatnonzero(mask)
            verify_slot = int(slots[self._verify_rr % len(slots)])
            self._verify_rr += 1
            pre_member = self.member_state(verify_slot)
        donated_probe = (
            jax.tree_util.tree_leaves(self._state)[0]
            if self._donate and self._donate_effective is None else None
        )
        dts = jnp.asarray(self._dts)
        mdev = jnp.asarray(mask)
        rdev = jnp.asarray(
            np.where(mask, self._remaining, 0).astype(np.int32))
        t0 = time.perf_counter()
        # the cohort context rides every span the dispatch completes, so
        # a trace attributes each ensemble.step to its cohort; the
        # request.step span names the member requests this dispatch
        # served (truncated — one span per DISPATCH, not per member)
        with timeline.context(cohort=self.sig_label, width=self.W):
            with metrics.phase("ensemble.step"):
                self._state = kernel(self._args, self._state, rdev,
                                     dts, mdev)
        dt_wall = time.perf_counter() - t0
        # exchange-amortization accounting (host-side: the in-trace
        # exchanges are invisible to the halo instrumentation) — a wide
        # body pays ceil(k/g) exchanges for its k interior steps, the
        # legacy body pays k; pure python ints, no device sync
        record_dispatch_exchanges(
            self.spec.kind, (k + g - 1) // g if g else k, k)
        if donated_probe is not None:
            # measured donation effectiveness: a really-donated input
            # buffer is invalidated at dispatch (CPU backends copy
            # instead); feeds the in-flight factor of the HBM gauge
            try:
                self._donate_effective = bool(donated_probe.is_deleted())
            except Exception:  # noqa: BLE001 — no such API: assume copy
                self._donate_effective = False
        if timeline.enabled or flightrec.enabled:
            args = {
                "cohort": self.sig_label, "members": n,
                # k-aware span accounting (ISSUE 11): one span still
                # covers one DISPATCH, but SLO service-time math needs
                # to know how many simulation steps it advanced
                "steps_per_dispatch": k,
                "member_steps": int(advanced.sum()),
                "requests": [self.members[s].id
                             for s in np.flatnonzero(mask)[:8]],
            }
            timeline.add("request.step", t0, dt_wall, args)
            flightrec.add_span("request.step", t0, dt_wall, args)
        self._remaining -= advanced
        # dispatch-side per-interior-step wall time EMA: the service
        # estimate deadline-slack k selection divides by
        per_step = dt_wall / k
        self.step_s_ema = (per_step if self.step_s_ema is None
                           else 0.5 * self.step_s_ema + 0.5 * per_step)
        cost_on = obs_cost.enabled()
        if cost_on:
            # online step-cost model (ISSUE 17): one per-interior-step
            # sample under the full compiled-body key — every dimension
            # that selects a distinct executable prices separately
            obs_cost.record_dispatch(self.spec.kind, self.sig_label,
                                     k, g, self.W, dt_wall)
        served: dict = {}
        for slot in np.flatnonzero(mask):
            scn = self.members[slot]
            adv = int(advanced[slot])
            scn.steps_done += adv
            served[scn.tenant] = served.get(scn.tenant, 0) + adv
        # per-tenant member-steps this dispatch advanced — the scheduler
        # reads this to feed the capacity tracker per scheduling TICK
        # (dispatch + admission + retirement overhead), because a queued
        # backlog drains at the tick rate, not the bare kernel rate
        self._served_last = served
        if metrics.enabled:
            metrics.inc_many([
                ("ensemble.steps_served", v, {"tenant": t})
                for t, v in served.items()
            ])
            # per-tenant device-seconds attribution: the dispatch held
            # every device in the cohort's mesh for dt_wall, so the
            # fleet bill is dt_wall * devices split by the member-steps
            # each tenant advanced this dispatch (pure host floats)
            total_adv = sum(served.values())
            if total_adv > 0:
                device_total = dt_wall * self.mesh.size
                metrics.inc_many([
                    ("ensemble.device_s", device_total * v / total_adv,
                     {"tenant": t, "model": self.spec.kind})
                    for t, v in served.items()
                ])
                # the chargeback conservation companion: the unlabeled
                # wall×mesh total the per-tenant splits must sum to
                metrics.inc("ensemble.device_s_total", device_total)
            metrics.gauge("ensemble.steps_per_dispatch", k,
                          model=self.spec.kind)
            self._sample_hbm()
        if verify_slot is not None:
            self._verify(pre_member, verify_slot,
                         int(advanced[verify_slot]))
        return int(advanced.sum())

    # ------------------------------------------------------------ oracle

    def _verify_active(self) -> bool:
        return self._verify_on if hasattr(self, "_verify_on") \
            else verify_enabled()

    def _verify(self, member_pre, slot: int, nsteps: int) -> int:
        """Replay the pre-sampled member ``nsteps`` solo steps through
        its own member program (the model's cached step kernel — the
        always-available oracle; ``nsteps`` is the member's real
        advance this dispatch, so a depth-k block is audited as k solo
        steps and a mid-k-retired member as its clamped count) and
        byte-compare every field of its cohort row.  Mismatches are
        counted, never raised; the sample rotates round-robin over
        active slots so every member is eventually audited.  Returns
        the mismatch count (tests read it).

        With wide halos engaged the replay IS the exchange-every-step
        oracle the amortized body must match — on OWNED rows.  Ghost
        replica rows legitimately hold block-stale values (that is the
        amortization), so state leaves carrying a per-row device axis
        (``leaf.shape[:2]`` matches the plan's ``local_mask``) are
        compared on local rows only; every other leaf stays a full
        byte-compare."""
        import jax

        t0 = time.perf_counter()
        take = lambda S: S[slot]
        member_args = (self._args if self.shared_args
                       else jax.tree_util.tree_map(take, self._args))
        local_mask = None
        if self._wide is not None:
            member_args = member_args[0]
            # the audited member's OWN local rows (a heterogeneous
            # joiner's row layout differs from the template's): ghost
            # and pad rows are the ones allowed to diverge
            member = self.members[slot]
            wide = (member.spec.wide if member is not None
                    else self._wide)
            local_mask = np.asarray(wide.local_mask)
        dt = self.dt_dtype.type(self._dts[slot])
        solo = member_pre
        for _ in range(max(nsteps, 1)):
            solo = self.spec.call(member_args, solo, dt)
        got = jax.tree_util.tree_map(take, self._state)
        names = sorted(solo) if isinstance(solo, dict) else None
        solo_l = jax.tree_util.tree_leaves(solo)
        got_l = jax.tree_util.tree_leaves(got)
        mismatches = 0
        for i, (a, b) in enumerate(zip(solo_l, got_l)):
            av, bv = np.asarray(a), np.asarray(b)
            if (local_mask is not None
                    and av.shape[:2] == local_mask.shape):
                av, bv = av[local_mask], bv[local_mask]
            if av.tobytes() != bv.tobytes():
                mismatches += 1
                labels = {"field": names[i]} if names else {}
                metrics.inc("ensemble.verify_mismatches", **labels)
        metrics.inc("ensemble.verify_checks", len(solo_l))
        metrics.phase_add("ensemble.verify", time.perf_counter() - t0)
        if mismatches and not getattr(self, "_fr_dumped", False):
            # a broken bit-identity anchor is black-box material: one
            # postmortem per cohort (not per step — mismatch storms
            # must not turn into dump storms), naming the audited
            # request and the in-flight cohort members
            self._fr_dumped = True
            flightrec.note("ensemble.verify_mismatch",
                           cohort=self.sig_label,
                           request=self.members[slot].id
                           if self.members[slot] is not None else None,
                           fields=mismatches)
            flightrec.dump(reason="ensemble.verify_mismatch")
        return mismatches


class Scheduler:
    """Admission/retirement loop over signature-keyed cohorts.

    ``submit`` enqueues; :meth:`admit` drains the queue into matching
    cohorts (creating or growing them along the width ladder);
    :meth:`step_once` steps every cohort with active members in policy
    order (``round_robin`` or ``deadline`` — earliest member deadline
    first) and retires finished members.  :meth:`queue_depth` is the
    backlog signal the elastic policy consumes
    (:func:`~dccrg_tpu.resilience.elastic.queue_depth_signal`)."""

    def __init__(self, policy: str = "round_robin",
                 max_width: int | None = None,
                 max_cohorts: int | None = None,
                 verify: bool | None = None,
                 steps_per_dispatch: int | None = None):
        if policy not in ("round_robin", "deadline"):
            raise ValueError(f"unknown scheduling policy {policy!r}")
        self.policy = policy
        self.max_width = (int(max_width) if max_width is not None
                          else _env_int("DCCRG_ENSEMBLE_MAX_COHORT", 1024))
        self.max_cohorts = max_cohorts
        self.verify = verify
        #: deep-dispatch depth override; None defers to each cohort's
        #: spec default (DCCRG_ENSEMBLE_K via the model providers)
        self.steps_per_dispatch = (
            max(int(steps_per_dispatch), 1)
            if steps_per_dispatch is not None else None)
        self._queue: deque = deque()
        self.cohorts: dict = {}
        self._rr = 0
        self.completed: list = []
        #: held width per cohort key (the hysteresis hints of the
        #: width ladder — survive cohort teardown like grid ring hints)
        self._width_hints: dict = {}
        #: tenants that ever had a gauged backlog: drained tenants get
        #: one more zero write so stale gauges never freeze into live
        #: windows (ISSUE 17)
        self._gauged_tenants: set = set()
        #: admission wall-seconds not yet charged to a scheduling tick —
        #: stacking joiners (and compiling their bodies) is drain work
        #: the queue-wait service rate must pay for (ISSUE 17)
        self._admit_busy_s: float = 0.0

    # ---------------------------------------------------------- requests

    def submit(self, scenario: Scenario) -> Scenario:
        """Enqueue one scenario, resolving its batch spec and signature.
        Invalid or unsupported requests are REJECTED (counted under
        ``ensemble.rejected{reason}``), never raised — the serving loop
        must survive any single bad request."""
        reason = None
        if scenario.steps <= 0:
            reason = "invalid"
        elif not hasattr(scenario.model, "batch_step_spec"):
            reason = "unsupported"
        else:
            try:
                scenario.spec = scenario.model.batch_step_spec()
                scenario.signature = scenario.model.grid.shape_signature()
            except Exception:  # noqa: BLE001 — unsupported path/model
                reason = "unsupported"
        if reason is not None:
            scenario.status = "rejected"
            scenario.reject_reason = reason
            metrics.inc("ensemble.rejected", reason=reason)
            flightrec.note("request.rejected", request=scenario.id,
                           tenant=scenario.tenant, reason=reason)
            return scenario
        self._queue.append(scenario)
        metrics.gauge("ensemble.queue_depth", self.queue_depth())
        if metrics.enabled and obs_cost.enabled():
            self._advise_admission(scenario)
        self._gauge_backlog()
        # the black box tracks the request from the moment it exists:
        # a postmortem names queued victims too, not just active ones
        flightrec.begin_request(scenario.id, tenant=scenario.tenant,
                                status="queued", steps=scenario.steps,
                                model=scenario.spec.kind,
                                deadline=scenario.deadline)
        flightrec.note("request.queued", request=scenario.id,
                       tenant=scenario.tenant)
        return scenario

    def queue_depth(self) -> int:
        """Backlog: submitted-but-not-admitted scenarios.  This is the
        load signal the PR 8 elastic policy was left waiting on."""
        return len(self._queue)

    def _queued_steps(self) -> dict:
        """Backlog member-steps per tenant (submitted, not admitted) —
        the numerator of the predicted queue-wait estimate."""
        out: dict = {}
        for scn in self._queue:
            out[scn.tenant] = out.get(scn.tenant, 0) + int(scn.steps)
        return out

    def _gauge_backlog(self) -> None:
        """Per-tenant backlog and predicted queue-wait gauges
        (ISSUE 17): ``ensemble.queue_depth_steps{tenant}`` is the
        member-step backlog, ``cost.predicted_queue_wait_s{tenant}``
        divides it by the measured service rate
        (:class:`~dccrg_tpu.obs.cost.ServiceRateTracker`).  Tenants
        whose backlog drained are written once more at zero, so a dead
        backlog never freezes a stale prediction into live windows."""
        if not metrics.enabled:
            return
        queued = self._queued_steps()
        tenants = self._gauged_tenants | set(queued)
        if not tenants:
            return
        waits = (obs_cost.predicted_wait(queued)
                 if obs_cost.enabled() else {})
        for t in sorted(tenants):
            metrics.gauge("ensemble.queue_depth_steps",
                          queued.get(t, 0), tenant=t)
            metrics.gauge("cost.predicted_queue_wait_s",
                          float(waits.get(t, 0.0)), tenant=t)
        # drained tenants just got their zero write — drop them so an
        # idle fleet stops paying per-tick gauge writes for every
        # tenant it ever served
        self._gauged_tenants = set(queued)

    def _advise_admission(self, scn: Scenario) -> None:
        """Counted-never-raised cost-based admission ADVICE (ISSUE 17):
        estimate the request's completion — predicted queue-wait for
        its tenant plus its steps at the model's per-step estimate —
        against its deadline, and count the verdict under
        ``ensemble.admission_estimates{verdict}``.  ``ok``: fits at the
        target quantile; ``at_risk``: fits at the mean but not the
        quantile; ``late``: predicted past the deadline even at the
        mean; ``unknown``: no deadline, or the model is still cold.
        This is the estimate plumbing a future reject-with-reason
        admission policy will gate on — today nothing is refused."""
        with metrics.phase("cost.estimate"):
            verdict = "unknown"
            est = obs_cost.model.predict(scn.spec.kind)
            if (scn.deadline is not None and est is not None
                    and est.n >= obs_cost.min_samples()):
                wait = obs_cost.predicted_wait(
                    self._queued_steps()).get(scn.tenant, 0.0)
                slack = scn.deadline - time.perf_counter() - wait
                steps = max(int(scn.steps), 0)
                if slack < steps * est.mean:
                    verdict = "late"
                elif slack < steps * est.q_value:
                    verdict = "at_risk"
                else:
                    verdict = "ok"
            metrics.inc("ensemble.admission_estimates", verdict=verdict)
            if verdict not in ("unknown", "ok"):
                flightrec.note("request.admission_estimate",
                               request=scn.id, tenant=scn.tenant,
                               verdict=verdict)

    def _cohort_id(self, scn: Scenario) -> tuple:
        return (scn.signature, scn.spec.kind, scn.spec.kernel_key,
                _state_sig(scn.state))

    # --------------------------------------------------------- admission

    def _grow(self, key, cohort: Cohort, need: int) -> Cohort:
        """Re-land a full cohort at the next ladder width: members keep
        their CURRENT stacked state (extracted per slot and re-admitted),
        so growth mid-flight is loss-free.  The wider body compiles once
        per (kernel_key, width) and is itself cached."""
        new_w = cohort_width(need, self._width_hints.get(key))
        if new_w <= cohort.W:
            new_w = cohort.W * 2
        if new_w > self.max_width:
            return cohort
        self._width_hints[key] = new_w
        members = [(s, cohort.members[s])
                   for s in np.flatnonzero(cohort._occupied)]
        template = members[0][1] if members else None
        if template is None:
            return cohort
        fresh = Cohort(template, width=new_w, shared=cohort.shared_args,
                       k=cohort.k)
        if self.verify is not None:
            fresh._verify_on = self.verify
        for new_slot, (old_slot, scn) in enumerate(members):
            scn.state = cohort.member_state(old_slot)
            fresh.admit(scn, new_slot)
        self.cohorts[key] = fresh
        metrics.inc("ensemble.cohort_grows")
        return fresh

    def admit(self) -> int:
        """Drain the queue into cohorts; returns how many scenarios were
        admitted this pass.  Scenarios whose cohort is full (and at the
        width cap) stay queued — that backlog IS the queue-depth signal."""
        admitted = 0
        if not self._queue:
            return 0
        _admit_t0 = time.perf_counter()
        with metrics.phase("ensemble.admit"):
            # size new (and grown) cohorts by the whole pending backlog
            # for their key, not one member at a time — a burst of 256
            # submissions lands in ONE width-256 cohort body instead of
            # walking the ladder through every intermediate width
            pending: dict = {}
            for scn in self._queue:
                key = self._cohort_id(scn)
                pending[key] = pending.get(key, 0) + 1
            still: deque = deque()
            while self._queue:
                scn = self._queue.popleft()
                key = self._cohort_id(scn)
                cohort = self.cohorts.get(key)
                if cohort is None:
                    if (self.max_cohorts is not None
                            and len(self.cohorts) >= self.max_cohorts):
                        scn.status = "rejected"
                        scn.reject_reason = "capacity"
                        metrics.inc("ensemble.rejected", reason="capacity")
                        pending[key] -= 1
                        continue
                    width = cohort_width(
                        min(pending.get(key, 1), self.max_width),
                        self._width_hints.get(key),
                    )
                    self._width_hints[key] = width
                    cohort = Cohort(scn, width=width,
                                    k=self.steps_per_dispatch)
                    if self.verify is not None:
                        cohort._verify_on = self.verify
                    self.cohorts[key] = cohort
                free = cohort.free_slots()
                if len(free) == 0:
                    cohort = self._grow(
                        key, cohort,
                        cohort.occupancy + pending.get(key, 1),
                    )
                    free = cohort.free_slots()
                if len(free) == 0:
                    still.append(scn)     # width cap: stays in backlog
                    continue
                t_admit = time.perf_counter()
                cohort.admit(scn, int(free[0]))
                pending[key] -= 1
                admitted += 1
                metrics.inc("ensemble.admitted")
                # queue wait from the already-stamped submit/admit pair
                # (ISSUE 10): the per-tenant histogram the SLO report
                # quantiles, plus the lifecycle spans — request.queued
                # covers the whole wait retroactively (both stamps are
                # perf_counter, the timeline's native timebase)
                wait = scn.admitted_at - scn.submitted_at
                metrics.observe("ensemble.queue_latency", wait)
                metrics.observe("ensemble.queue_wait_s", wait,
                                tenant=scn.tenant)
                target = _slo_target("DCCRG_SLO_QUEUE_S")
                if target is not None and wait > target:
                    metrics.inc("ensemble.slo_violations",
                                **{"class": "queue_wait"})
                if timeline.enabled or flightrec.enabled:
                    args = {"request": scn.id, "tenant": scn.tenant}
                    timeline.add("request.queued", scn.submitted_at,
                                 wait, args)
                    done = time.perf_counter()
                    timeline.add("request.admit", t_admit,
                                 done - t_admit, args)
                    flightrec.add_span("request.queued",
                                       scn.submitted_at, wait, args)
                flightrec.begin_request(scn.id, tenant=scn.tenant,
                                        status="active",
                                        model=scn.spec.kind,
                                        cohort=cohort.sig_label,
                                        deadline=scn.deadline)
                flightrec.note("request.admit", request=scn.id,
                               tenant=scn.tenant,
                               cohort=cohort.sig_label,
                               queue_wait_s=round(wait, 6))
            self._queue = still
        self._admit_busy_s += time.perf_counter() - _admit_t0
        self._update_gauges()
        return admitted

    def _update_gauges(self) -> None:
        if not metrics.enabled:
            return
        metrics.gauge("ensemble.queue_depth", self.queue_depth())
        for cohort in self.cohorts.values():
            metrics.gauge(
                "ensemble.cohort_occupancy",
                cohort.occupancy / max(cohort.W, 1),
                signature=cohort.sig_label,
            )
            metrics.gauge(
                "ensemble.cohort_peak_occupancy",
                cohort.peak_occupancy,
                signature=cohort.sig_label,
            )
        self._gauge_backlog()

    # ---------------------------------------------------------- stepping

    def _ordered_cohorts(self) -> list:
        live = [c for c in self.cohorts.values() if c.occupancy]
        if not live:
            return []
        if self.policy == "deadline":
            return sorted(live, key=Cohort.min_deadline)
        self._rr += 1
        k = self._rr % len(live)
        return live[k:] + live[:k]

    def select_k(self, cohort: Cohort, now: float | None = None) -> int:
        """Dispatch depth for this cohort's next step (ISSUE 11): the
        configured depth (scheduler override, else the cohort's spec
        default), clamped three ways —

        * to ``DCCRG_ENSEMBLE_K_MAX`` (compile-cache cardinality);
        * to the deepest step any active member can still USE
          (``max(remaining)`` — the in-kernel budgets already stop each
          member overshooting, this clamp stops the loop burning frozen
          iterations every member would discard);
        * to the earliest member deadline's slack over the per-step
          service-time estimate (a tight-deadline member must not sit
          out a deep block it only needed the first steps of — depth
          trades dispatch overhead against retirement latency, and
          slack is the budget for that trade).  The estimate is the
          fleet cost model's ``DCCRG_COST_QUANTILE`` (default p95 —
          a clamp sized to the mean overshoots half the time) for this
          cohort's compiled-body key once ``DCCRG_COST_MIN_SAMPLES``
          samples exist at the answering fallback level; below that, or
          with ``DCCRG_COST_MODEL=0``, the cohort-local EMA exactly as
          before (ISSUE 17);
        * to the cohort's exchange budget when wide halos engage
          (ISSUE 14) — a scheduled dispatch then pays exactly ONE
          exchange (``ceil(k/g) == 1``), which is the whole point of
          the amortization.  A direct ``cohort.step(k)`` past the
          budget still works (the body runs multiple exchange blocks);
          this clamp is the scheduler preferring more dispatches at
          full amortization over fewer at partial.
        """
        k = (self.steps_per_dispatch
             if self.steps_per_dispatch is not None else cohort.k)
        k = max(1, min(int(k), max_steps_per_dispatch()))
        if cohort._wide is not None:
            k = min(k, max(1, min(cohort._wide_budget,
                                  halo_depth_cap())))
        active = cohort.active_mask()
        if active.any():
            k = min(k, int(cohort._remaining[active].max()))
        deadline = cohort.min_deadline()
        per_step = cohort.step_s_ema
        queue_wait = 0.0
        if obs_cost.enabled():
            est = obs_cost.model.predict(
                cohort.spec.kind, sig=cohort.sig_label, k=k,
                g=cohort._wide_g(k), w=cohort.W)
            if est is not None and est.n >= obs_cost.min_samples():
                per_step = est.q_value
                # ROADMAP item 3 follow-on (b): an ARMED cost plane
                # spends the slack clamp from item 2's admission
                # estimates, not just the compiled-body cost — the
                # earliest-deadline member's usable slack is reduced by
                # its tenant's predicted queue wait (backlog it must
                # still drain behind).  Cold model or
                # DCCRG_COST_MODEL=0 keeps the EMA path untouched, and
                # either way k only changes dispatch granularity — the
                # oracle holds results byte-identical at every depth.
                tenant = cohort.min_deadline_tenant()
                if tenant is not None and deadline != float("inf"):
                    waits = obs_cost.predicted_wait(self._queued_steps())
                    queue_wait = float(waits.get(tenant, 0.0))
        if deadline != float("inf") and per_step and per_step > 0:
            now = time.perf_counter() if now is None else now
            slack = deadline - now - queue_wait
            k = 1 if slack <= 0 else min(k, max(1, int(slack / per_step)))
        return max(k, 1)

    def step_once(self) -> int:
        """One scheduling tick: step every cohort with active members
        (policy order) at its selected dispatch depth, then retire
        finished members.  Returns total member-steps served."""
        tick_t0 = time.perf_counter()
        served = 0
        tick_served: dict = {}
        for cohort in self._ordered_cohorts():
            served += cohort.step(self.select_k(cohort))
            for t, v in getattr(cohort, "_served_last", {}).items():
                tick_served[t] = tick_served.get(t, 0) + v
            for slot in cohort.finished_slots():
                scn = cohort.retire(int(slot))
                self.completed.append(scn)
                metrics.inc("ensemble.retired")
                self._account_retirement(scn, cohort)
        self._update_gauges()
        # step-boundary stream flush: live tailers see windows move
        # even between the periodic ticker's beats (no-op when no
        # stream is active or DCCRG_STREAM_FLUSH_S <= 0)
        obs_stream.maybe_flush()
        if tick_served and obs_cost.enabled():
            # capacity window (ISSUE 17): charge the FULL tick wall —
            # dispatches plus retirement/gauge overhead plus any
            # admission seconds carried since the last tick — because
            # that is the rate a queued backlog actually drains at;
            # the step-cost model above keeps the bare dispatch wall
            # (it prices the compiled body, not the scheduler)
            busy = (time.perf_counter() - tick_t0) + self._admit_busy_s
            self._admit_busy_s = 0.0
            obs_cost.tracker.note(tick_served, busy)
        return served

    def _account_retirement(self, scn: Scenario, cohort: Cohort) -> None:
        """Request-level SLO accounting at retirement (ISSUE 10):
        service/e2e latency histograms, deadline-miss counting (misses
        are counted, never raised — deadlines only affected scheduling
        order before), the closing lifecycle spans, and the flight
        recorder's in-flight table."""
        if not (metrics.enabled or flightrec.enabled):
            return
        service = scn.retired_at - scn.admitted_at
        e2e = scn.retired_at - scn.submitted_at
        missed = (scn.deadline is not None
                  and scn.retired_at > scn.deadline)
        metrics.observe("ensemble.service_s", service,
                        tenant=scn.tenant, model=cohort.spec.kind)
        metrics.observe("ensemble.e2e_s", e2e, tenant=scn.tenant)
        if missed:
            metrics.inc("ensemble.deadline_miss", tenant=scn.tenant)
            metrics.inc("ensemble.slo_violations",
                        **{"class": "deadline"})
        target = _slo_target("DCCRG_SLO_E2E_S")
        if target is not None and e2e > target:
            metrics.inc("ensemble.slo_violations", **{"class": "e2e"})
        if timeline.enabled or flightrec.enabled:
            args = {"request": scn.id, "tenant": scn.tenant,
                    "model": cohort.spec.kind, "steps": scn.steps_done,
                    "deadline_missed": bool(missed)}
            timeline.add("request.retire", scn.retired_at, 0.0, args)
            timeline.add("request.e2e", scn.submitted_at, e2e, args)
            flightrec.add_span("request.e2e", scn.submitted_at, e2e,
                               args)
        flightrec.end_request(scn.id, tenant=scn.tenant,
                              status="done", steps=scn.steps_done,
                              e2e_s=round(e2e, 6),
                              deadline_missed=bool(missed))

    def run(self, max_ticks: int | None = None) -> int:
        """Admit + step until every submitted scenario finishes (or
        ``max_ticks`` scheduling ticks elapse).  Returns total
        member-steps served."""
        total = 0
        ticks = 0
        while True:
            self.admit()
            served = self.step_once()
            total += served
            ticks += 1
            idle = (served == 0 and not self._queue)
            if idle or (max_ticks is not None and ticks >= max_ticks):
                return total


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class Ensemble:
    """User-facing serving front-end over :class:`Scheduler`.

    >>> ens = Ensemble()
    >>> t = ens.submit(model, state, steps=10, dt=dt, tenant="alice")
    >>> ens.run()
    >>> final = t.result          # bit-identical to solo stepping

    ``verify=True`` (or ``DCCRG_ENSEMBLE_VERIFY=1``) arms the
    solo-replay oracle; ``policy="deadline"`` steps cohorts by earliest
    member deadline instead of round-robin; ``steps_per_dispatch=k``
    makes every scheduling tick advance cohorts k simulation steps per
    host dispatch (deep dispatch — default is each model's
    ``DCCRG_ENSEMBLE_K`` spec depth)."""

    def __init__(self, policy: str = "round_robin",
                 max_width: int | None = None,
                 max_cohorts: int | None = None,
                 verify: bool | None = None,
                 steps_per_dispatch: int | None = None):
        self.scheduler = Scheduler(policy=policy, max_width=max_width,
                                   max_cohorts=max_cohorts, verify=verify,
                                   steps_per_dispatch=steps_per_dispatch)

    def submit(self, model, state, steps: int, dt=None,
               tenant: str = "default",
               deadline: float | None = None) -> Scenario:
        scn = Scenario(model, state, steps, dt=dt, tenant=tenant,
                       deadline=deadline)
        return self.scheduler.submit(scn)

    def admit_pending(self) -> int:
        return self.scheduler.admit()

    def step(self) -> int:
        return self.scheduler.step_once()

    def run(self, max_ticks: int | None = None) -> int:
        return self.scheduler.run(max_ticks=max_ticks)

    def queue_depth(self) -> int:
        return self.scheduler.queue_depth()

    @property
    def completed(self) -> list:
        return self.scheduler.completed

    @property
    def cohorts(self) -> dict:
        return self.scheduler.cohorts
