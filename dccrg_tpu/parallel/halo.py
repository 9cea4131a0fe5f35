"""Halo-exchange engine: ghost-cell updates as XLA collectives.

TPU-native replacement for the reference's per-rank-pair
``MPI_Type_create_struct`` + ``Isend/Irecv`` engine
(``dccrg.hpp:10564-11070``): the send/recv lists become device index arrays
(built in ``epoch.py`` from the same list computation as
``recalculate_neighbor_update_send_receive_lists``, ``dccrg.hpp:8590-8889``)
and the transfer lowers to a **per-peer ring schedule**: one
``lax.ppermute`` step per ring distance k (device d -> device (d+k) % D)
that any pair actually communicates over, each step's buffer sized by that
distance's true maximum pair count.  A slab-partitioned grid therefore
moves only its neighbor-distance traffic — wire bytes scale with the real
send/recv lists, the reference's neighbor-only messaging property — where
a padded ``[D, D, S]`` all_to_all would scale with worst-pair x D^2.
Everything runs inside one ``shard_map`` so XLA rides ICI and can overlap
the collectives with unrelated compute (the reference's split-phase
pattern, ``dccrg.hpp:4997-5367``).

Ghost copies are bit-identical to the source rows: the schedule moves raw
array values with no arithmetic.
"""
from __future__ import annotations

import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..obs.registry import metrics as _metrics
from . import halo_dma
from .exec_cache import ExecutableCache, mesh_key as _mesh_key, traced_jit
from .mesh import SHARD_AXIS, put_table
from .shapes import bucket_pairs

__all__ = ["HaloExchange", "HaloHandle", "interior_steps_per_exchange",
           "record_dispatch_exchanges"]


def interior_steps_per_exchange(ghost_depth: int,
                                stencil_radius: int = 1) -> int:
    """Deep-dispatch budget of one boundary sync (ISSUE 11): how many
    interior updates a ghost zone ``ghost_depth`` cells deep can serve
    before a stencil of ``stencil_radius`` has consumed it — the source
    paper's distance-k neighborhood premise (dccrg supports rings at
    any hood length precisely so a deeper exchange can amortize more
    local work).  Each update invalidates the outermost
    ``stencil_radius`` shells of the ghost zone, so the budget is
    ``ghost_depth // stencil_radius`` (floor 1: a zero-depth hood still
    supports its one face-coupled update, which is how the repo's
    nbh-length-0 workloads step today).

    The serving tier's fused k-step cohort bodies currently re-exchange
    inside the loop each interior step — correct at ANY k, since the
    in-kernel protocol equals k solo steps — so this budget is the
    PLANNING bound for the follow-on that hoists one depth-k exchange
    above the loop; the hoisted form keeps the DMA start/wait split at
    program level, exactly like the split-phase steps do."""
    depth = max(int(ghost_depth), 0)
    radius = max(int(stencil_radius), 1)
    return max(depth // radius, 1)


#: model kind -> [exchanges, steps]: cumulative dispatch-level exchange
#: amortization, fed by the serving tier (ISSUE 14)
_amortization: dict = {}


def record_dispatch_exchanges(kind: str, exchanges: int, steps: int) -> None:
    """Host-side exchange-amortization ledger for deep dispatch.

    In-trace exchanges are intentionally invisible to ``_record`` (it
    would count trace-time, not run-time), so the cohort front-end
    reports its OWN protocol here after each dispatch: a wide-halo body
    at depth g pays ``ceil(k / g)`` exchanges for k simulated steps, the
    legacy body pays k.  The cumulative ratio lands as the
    ``halo.exchanges_per_step`` gauge — the ISSUE 14 headline series
    (~1/k when the scheduler clamps k inside the exchange budget, 1.0 on
    the exchange-every-step path), CEILING-gated by ``telemetry_diff``.
    Pure python-int arithmetic: safe from the dispatch hot path."""
    steps = int(steps)
    if steps <= 0:
        return
    ent = _amortization.setdefault(kind, [0, 0])
    ent[0] += int(exchanges)
    ent[1] += steps
    _metrics.gauge("halo.exchanges_per_step", ent[0] / ent[1], model=kind)

#: process-wide fallback cache for exchanges constructed without a grid
#: (tests, ad-hoc schedules) — grid-owned exchanges share the grid's own
#: bounded cache instead
_default_cache = ExecutableCache()


def _flush_record_cache(cache: dict) -> None:
    """Materialize a schedule's buffered dispatch counts into the
    registry.  Shared by the registry-driven flush and the GC finalizer —
    an epoch rebuild drops its halo schedules, and the counts they
    buffered must land before the object goes away."""
    for entry in cache.values():
        pairs, n = entry
        entry[1] = 0
        if n:
            _metrics.inc_batch([(key, v * n) for key, v in pairs])


def _maybe_nan_storm(state):
    """Fault-injection seam: when the ``halo.nan`` site is armed and
    fires, poison a few random rows of every floating field with NaN
    *before* the exchange, so the storm propagates into ghost copies
    exactly the way a corrupted payload would (``resilience/inject``).
    Unarmed cost is one dict lookup; never runs under a jit trace (the
    poison must be real data, not a tracer op)."""
    from ..resilience.inject import plane

    if not plane.armed("halo.nan") or _tracing(state):
        return state
    if not plane.fires("halo.nan"):
        return state
    rng = plane.site_rng("halo.nan")
    n_rows = 0

    def poison(x):
        nonlocal n_rows
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.ndim < 2:
            return x
        k = min(4, x.shape[1])
        d = rng.integers(x.shape[0], size=k)
        r = rng.integers(x.shape[1], size=k)
        n_rows += k
        return x.at[jnp.asarray(d), jnp.asarray(r)].set(jnp.nan)

    out = jax.tree_util.tree_map(poison, state)
    if n_rows:
        _metrics.inc("resilience.nan_rows_poisoned", n_rows)
    return out


def _tracing(state) -> bool:
    """Whether any leaf of ``state`` is an abstract tracer — i.e. the
    exchange is being called inside someone else's jit trace, where
    host-side telemetry would record trace-time, not run-time."""
    try:
        tracer = jax.core.Tracer
    except AttributeError:  # jax moved/removed the alias
        return False
    return any(
        isinstance(x, tracer) for x in jax.tree_util.tree_leaves(state)
    )


class HaloHandle:
    """In-flight ghost payload returned by ``HaloExchange.start`` — a
    distinct type so passing it where a *state* belongs (the pre-rewrite
    split-phase calling convention) fails loudly instead of silently
    exchanging garbage."""

    __slots__ = ("payload",)

    def __init__(self, payload):
        self.payload = payload


class HaloExchange:
    """Compiled halo-exchange schedule for one (epoch, neighborhood).

    ``exchange(state)`` returns the state with ghost rows refreshed from
    their owners; ``state`` is a pytree of ``[D, R, ...]`` arrays sharded on
    the leading axis.
    """

    def __init__(self, epoch, hood, mesh, cell_datatype=None, hood_id=None,
                 exec_cache=None, ring_hints=None):
        self.mesh = mesh
        self.D = epoch.n_devices
        self.R = epoch.R
        self.hood_id = hood_id
        #: compiled-body cache (grid-owned when built via ``grid.halo``):
        #: the jitted exchange programs are keyed by ring structure, not
        #: by this schedule object, so an epoch rebuild that lands on the
        #:  same shape signature reuses every executable
        self._cache = exec_cache if exec_cache is not None else _default_cache
        #: grid-persistent ring-size hysteresis hints
        #: {(hood_id, field, k): held bucket} — pair counts wiggling
        #: with churn must not flap the per-distance table shapes, or
        #: every kernel taking the schedule as an argument retraces
        self._ring_hints = ring_hints if ring_hints is not None else {}
        #: wire transport the compiled bodies use (``DCCRG_HALO_BACKEND``):
        #: ``collective`` rides ``lax.ppermute``; ``pallas`` rides the
        #: async-DMA ring kernels (``parallel/halo_dma.py``), under the
        #: interpreter on non-TPU backends.  Part of ``structure_key``, so
        #: every cached body (and every model kernel keyed on it) is
        #: compiled per transport.
        self.backend = halo_dma.resolve_backend()
        self._interpret = halo_dma.interpret_mode()
        if _metrics.enabled:
            _metrics.inc("halo.backend_schedules", backend=self.backend)
        #: cells moved per exchange (useful payload, for bandwidth
        #: accounting)
        self.cells_moved = int(hood.pair_counts.sum())
        D = self.D
        # exact per-pair row lists, the substrate every ring schedule is
        # built from (the reference's send/recv lists,
        # ``dccrg.hpp:8590-8889``)
        pair_lists: dict = {}
        for i in range(D):
            for j in range(D):
                c = int(hood.pair_counts[i, j])
                if c:
                    pair_lists[(i, j)] = (
                        hood.send_rows[i, j, :c],
                        hood.recv_rows[j, i, :c],
                    )
        self._pair_lists = pair_lists
        #: per-cell dynamic payload policy (the reference's
        #: ``get_mpi_datatype(cell_id, sender, receiver, receiving,
        #: neighborhood_id)`` seam, ``dccrg_get_cell_datatype.hpp:48-125``):
        #: ``cell_datatype(field, cell_ids, sender, receiver, hood_id)``
        #: returns a bool mask — which of the pair's cells transfer this
        #: field on this exchange.  Evaluated ONCE per epoch at schedule
        #: build (the TPU trace-once analogue of the reference's per-call
        #: virtual dispatch); both sides of each pair derive from the one
        #: policy so send/recv schedules can never disagree the way a
        #: buggy asymmetric ``receiving=true/false`` pair could.
        self._cell_datatype = cell_datatype
        self._sender_cell_ids = (
            {key: epoch.cell_ids[key[0]][np.asarray(sr)]
             for key, (sr, _rr) in pair_lists.items()}
            if cell_datatype is not None else None
        )
        self._field_rings: dict = {}
        self._selective_fns: dict = {}
        (self.ring_ks, self.ring_perms, self.ring_send, self.ring_recv,
         self.wire_cells, _cells,
         self.ring_sizes) = self._ring_from_pairs(pair_lists, field=None)
        #: per-device cells shipped/received each exchange (telemetry;
        #: pairwise-symmetric by construction, so send and recv totals
        #: agree on every controller).  Static per schedule, so they are
        #: recorded ONCE here as gauges instead of per dispatch.
        self._send_per_dev = hood.pair_counts.sum(axis=1)
        self._recv_per_dev = hood.pair_counts.sum(axis=0)
        if _metrics.enabled:
            hood_label = "default" if hood_id is None else str(hood_id)
            for d in range(D):
                _metrics.gauge("halo.send_cells_per_exchange",
                               int(self._send_per_dev[d]),
                               device=d, hood=hood_label)
                _metrics.gauge("halo.recv_cells_per_exchange",
                               int(self._recv_per_dev[d]),
                               device=d, hood=hood_label)
        self._fn = self._build()

    def _ring_from_pairs(self, pair_lists, field=None):
        """Ring schedule from exact per-pair row lists: step k ships
        d -> (d+k) % D; only distances some pair actually uses appear,
        each sized by ITS max pair count.  Tables go through the
        ``put_table`` seam: sharded device arrays under one controller
        (no per-call transfer on the hot path), host numpy constants
        under many (jit closes over them transitively; closing over
        another process's device array is rejected)."""
        D, scratch = self.D, self.R - 1
        ks, perms, send_dev, recv_dev, sizes = [], [], [], [], []
        wire = 0
        cells = 0
        for k in range(1, D):
            S_k = max(
                (len(pair_lists[(d, (d + k) % D)][0])
                 for d in range(D) if (d, (d + k) % D) in pair_lists),
                default=0,
            )
            if S_k == 0:
                continue
            # ring step sizes ride the geometric bucket ladder (with
            # grid-persistent hysteresis) so pair counts wiggling with
            # AMR/LB churn keep the table (and payload) shapes sticky;
            # pad slots ship the scratch row and scatter back onto it —
            # bit-identical results, a margin of padded rows on the wire
            hint_key = (self.hood_id, field, k)
            S_k = bucket_pairs(S_k, self._ring_hints.get(hint_key))
            self._ring_hints[hint_key] = S_k
            st = np.full((D, S_k), scratch, np.int32)
            rt = np.full((D, S_k), scratch, np.int32)
            for d in range(D):
                sr = pair_lists.get((d, (d + k) % D))
                if sr is not None:
                    st[d, :len(sr[0])] = sr[0]
                    cells += len(sr[0])
                rr = pair_lists.get(((d - k) % D, d))
                if rr is not None:
                    rt[d, :len(rr[1])] = rr[1]
            ks.append(k)
            perms.append([(d, (d + k) % D) for d in range(D)])
            send_dev.append(put_table(st, self.mesh))
            recv_dev.append(put_table(rt, self.mesh))
            sizes.append(S_k)
            wire += D * S_k
        return ks, perms, send_dev, recv_dev, wire, cells, sizes

    def _rings_for_field(self, name: str):
        """The (ks, perms, send, recv) schedule moving ``name``: the
        shared full schedule without a policy, else the policy-filtered
        one (cached per field per epoch)."""
        if self._cell_datatype is None:
            return (self.ring_ks, self.ring_perms, self.ring_send,
                    self.ring_recv)
        if name not in self._field_rings:
            filtered = {}
            for (i, j), (sr, rr) in self._pair_lists.items():
                mask = np.asarray(self._cell_datatype(
                    name, self._sender_cell_ids[(i, j)], i, j, self.hood_id
                ), dtype=bool)
                if mask.shape != (len(sr),):
                    raise ValueError(
                        f"cell_datatype mask for field {name!r} pair "
                        f"({i}->{j}) has shape {mask.shape}, want "
                        f"({len(sr)},)"
                    )
                if mask.any():
                    filtered[(i, j)] = (np.asarray(sr)[mask],
                                        np.asarray(rr)[mask])
            ks, perms, send, recv, wire, cells, _sizes = (
                self._ring_from_pairs(filtered, field=name)
            )
            self._field_rings[name] = (ks, perms, send, recv, wire, cells)
        return self._field_rings[name][:4]

    # --------------------------------------------------- wire protocol

    @staticmethod
    def ring_start(blk, perms, send_tabs):
        """Inside a shard_map body: dispatch every ring step's payload
        for this device's ``[R, ...]`` block; returns the in-flight
        ``[S_k, ...]`` payloads (one per ring distance).  The single
        definition of the wire protocol — the blocking exchange, the
        split-phase pair, and workload overlap kernels all call this.

        Each step is wrapped in a ``named_scope`` keyed by its ring
        distance k (``perm[0]`` is ``(0, k)`` by construction), so the
        collective's HLO ops — and with them the ops of a profiler
        capture — carry a name that is STABLE across
        epoch rebuilds: ``halo.ring.k3.start`` attributes to ring
        distance 3 in every trace, regardless of how the schedule was
        rebuilt."""
        out = []
        for perm, sr in zip(perms, send_tabs):
            with jax.named_scope(f"halo.ring.k{perm[0][1]}.start"):
                out.append(jax.lax.ppermute(blk[sr], SHARD_AXIS, perm))
        return out

    @staticmethod
    def ring_finish(blk, recv_tabs, payloads):
        """Inside a shard_map body: scatter ``ring_start`` payloads into
        this device's ghost rows (padded slots land on the scratch
        row).  Scatter ops are scoped by ring-schedule position (the
        receive direction of step i), mirroring ``ring_start``'s
        per-distance scopes."""
        for i, (rr, p) in enumerate(zip(recv_tabs, payloads)):
            with jax.named_scope(f"halo.ring.r{i}.finish"):
                blk = blk.at[rr].set(p)
        return blk

    @property
    def ring_distances(self) -> tuple:
        """The ring distances this schedule actually ships (ascending)
        — the per-ring-distance schedule surface deep dispatch plans
        against (:func:`interior_steps_per_exchange`)."""
        return tuple(self.ring_ks)

    @property
    def structure_key(self) -> tuple:
        """Everything the compiled bodies' traces depend on besides
        argument shapes: the mesh, the active ring distances and the
        wire transport.  Model kernels mix this into their own cache
        keys — so a backend flip re-keys every composed program too."""
        return (_mesh_key(self.mesh), self.D, tuple(self.ring_ks),
                self.backend)

    def make_ring_start(self):
        """The backend-selected in-flight payload producer: a function
        ``(blk, send_tabs) -> [payload_k, ...]`` to call INSIDE a
        shard_map body.  Fused split-phase model kernels inline it
        between their halo dispatch and ghost-row scatter; it is a pure
        function of :attr:`structure_key`, so cached kernels closing
        over it stay valid across epoch rebuilds that keep the
        signature."""
        D, ks = self.D, tuple(self.ring_ks)
        if self.backend == "pallas":
            interpret = self._interpret
            return lambda blk, sends: halo_dma.ring_dma_start(
                blk, ks, D, sends, interpret=interpret
            )
        perms = [[(d, (d + k) % D) for d in range(D)] for k in ks]
        return lambda blk, sends: HaloExchange.ring_start(blk, perms, sends)

    @property
    def raw_body(self):
        """The cached jitted exchange body ``fn(*send_tabs, *recv_tabs,
        state)``.  Model kernels call this inside their own traces and
        pass the schedule tables along as arguments, so the composed
        program embeds no epoch-specific constants."""
        return self._fn

    def _build(self):
        return self._build_body(self.backend)

    def _build_body(self, backend: str):
        """The compiled blocking-exchange body for one transport.  The
        selected backend's body is the dispatch path; the collective
        body doubles as the always-available bit-identity oracle
        (``DCCRG_HALO_VERIFY=1`` builds it on demand even when the
        pallas body is live)."""
        mesh = self.mesh
        D = self.D
        ks = tuple(self.ring_ks)
        interpret = self._interpret

        def build():
            nk = len(ks)
            label = "halo.dma.body" if backend == "pallas" else "halo.body"
            if nk == 0:
                # no cross-device pairs (single device, or fully local
                # neighborhood): the exchange is the identity
                return traced_jit(label, lambda *args: args[-1])
            if backend == "pallas":
                ring = lambda blk, sends: halo_dma.ring_dma_start(
                    blk, ks, D, sends, interpret=interpret
                )
            else:
                perms = [[(d, (d + k) % D) for d in range(D)] for k in ks]
                ring = lambda blk, sends: HaloExchange.ring_start(
                    blk, perms, sends
                )
            data_spec = P(SHARD_AXIS)
            idx_spec = P(SHARD_AXIS, None)

            def body(*args):
                sends = [a[0] for a in args[:nk]]          # [S_k] each
                recvs = [a[0] for a in args[nk:2 * nk]]
                state = args[2 * nk]

                def exchange_leaf(x):
                    blk = x[0]                             # [R, ...]
                    payloads = ring(blk, sends)
                    return HaloExchange.ring_finish(
                        blk, recvs, payloads
                    )[None]

                return jax.tree_util.tree_map(exchange_leaf, state)

            fn = shard_map(
                body,
                mesh=mesh,
                in_specs=(idx_spec,) * (2 * nk) + (data_spec,),
                out_specs=data_spec,
                check_vma=False,
            )
            # schedule tables enter as jit ARGUMENTS, not closed-over
            # constants: closing over an array that spans other
            # controllers' devices is rejected under multi-process SPMD —
            # and argument tables are what lets the cached body outlive
            # the epoch that built this schedule
            return traced_jit(label, fn)

        return self._cache.get(
            ("halo.body", _mesh_key(mesh), D, ks, backend), build
        )

    def _selective(self, names: tuple):
        """Compiled per-field exchange for a cell_datatype policy: each
        field rides its own (possibly empty) ring schedule inside ONE
        shard_map, so a policy that strips a field from some cells costs
        exactly the surviving rows on the wire."""
        if names in self._selective_fns:
            return self._selective_fns[names]
        rings = [self._rings_for_field(n) for n in names]
        ks_all = tuple(tuple(r[0]) for r in rings)
        tab_args = []
        for r in rings:
            tab_args.extend(r[2])
            tab_args.extend(r[3])
        mesh = self.mesh
        D = self.D

        def build():
            nks = [len(ks) for ks in ks_all]
            perms_all = [
                [[(d, (d + k) % D) for d in range(D)] for k in ks]
                for ks in ks_all
            ]
            n_tabs = 2 * sum(nks)
            data_spec = P(SHARD_AXIS)
            idx_spec = P(SHARD_AXIS, None)

            def make_body(mode):
                def body(*args):
                    pos = 0
                    tabs = []
                    for nk in nks:
                        sends = [a[0] for a in args[pos:pos + nk]]
                        recvs = [a[0] for a in args[pos + nk:pos + 2 * nk]]
                        pos += 2 * nk
                        tabs.append((sends, recvs))
                    fields = args[pos:pos + len(names)]
                    payloads_in = args[pos + len(names):]
                    out = []
                    for fi, ((sends, recvs), perms, x) in enumerate(
                        zip(tabs, perms_all, fields)
                    ):
                        blk = x[0]
                        if mode == "start":
                            out.append(tuple(
                                p[None] for p in
                                HaloExchange.ring_start(blk, perms, sends)
                            ))
                            continue
                        if mode == "finish":
                            pay = [q[0] for q in payloads_in[fi]]
                        else:
                            pay = HaloExchange.ring_start(blk, perms, sends)
                        out.append(
                            HaloExchange.ring_finish(blk, recvs, pay)[None]
                        )
                    return tuple(out)

                return body

            def specs(extra):
                return ((idx_spec,) * n_tabs
                        + (data_spec,) * len(names) + extra)

            block = traced_jit("halo.selective", shard_map(
                make_body("block"), mesh=mesh,
                in_specs=specs(()), out_specs=data_spec, check_vma=False,
            ))
            start = traced_jit("halo.selective", shard_map(
                make_body("start"), mesh=mesh,
                in_specs=specs(()), out_specs=data_spec, check_vma=False,
            ))
            finish = traced_jit("halo.selective", shard_map(
                make_body("finish"), mesh=mesh,
                in_specs=specs((data_spec,) * len(names)),
                out_specs=data_spec, check_vma=False,
            ))
            return block, start, finish

        block, start, finish = self._cache.get(
            ("halo.selective", _mesh_key(mesh), D, names, ks_all), build
        )
        self._selective_fns[names] = (block, start, finish, tab_args)
        return self._selective_fns[names]

    @staticmethod
    def _names(state) -> tuple:
        if not isinstance(state, dict):
            raise TypeError(
                "a cell_datatype exchange needs a {field: array} state "
                "dict (fields are selected by name)"
            )
        return tuple(sorted(state))

    def __call__(self, state):
        if isinstance(state, HaloHandle):
            raise TypeError(
                "got a HaloHandle where a state pytree belongs — pass the "
                "handle as wait_remote_neighbor_copy_updates(state, handle)"
            )
        state = _maybe_nan_storm(state)
        if _metrics.enabled and not _tracing(state):
            self._record(state, "blocking")
            t0 = time.perf_counter()
            out = self._dispatch(state)
            _metrics.phase_add("halo.exchange", time.perf_counter() - t0)
        else:
            out = self._dispatch(state)
        if self._verify_active(state):
            self._verify_oracle(state, out)
        return out

    def _dispatch(self, state):
        if self._cell_datatype is None:
            return self._fn(*self.ring_send, *self.ring_recv, state)
        names = self._names(state)
        block, _start, _finish, tab_args = self._selective(names)
        outs = block(*tab_args, *(state[n] for n in names))
        return {**state, **dict(zip(names, outs))}

    # --------------------------------------------------- oracle verify

    def _verify_active(self, state) -> bool:
        """Whether this dispatch should replay on the collective oracle
        (``DCCRG_HALO_VERIFY=1``): only meaningful off the collective
        backend, only for the full-payload schedule (the policy-filtered
        path is collective-only), and never inside someone else's trace
        — the comparison is a host-side byte equality."""
        return (
            self.backend != "collective"
            and self._cell_datatype is None
            and halo_dma.verify_enabled()
            and not _tracing(state)
        )

    def _verify_oracle(self, state, out) -> int:
        """Cross-check one exchange against the collective oracle,
        bit-for-bit (byte compare — NaN payloads included, so a
        ``halo.nan`` storm verifies too).  Mismatching leaves are
        counted (``halo.verify_mismatches{field}``), never raised: the
        oracle is a detector the telemetry gates watch, not an
        assertion.  Returns the mismatch count (tests read it
        directly)."""
        t0 = time.perf_counter()
        oracle = self._build_body("collective")
        ref = oracle(*self.ring_send, *self.ring_recv, state)
        names = sorted(state) if isinstance(state, dict) else None
        out_l = jax.tree_util.tree_leaves(out)
        ref_l = jax.tree_util.tree_leaves(ref)
        mismatches = 0
        for i, (a, b) in enumerate(zip(out_l, ref_l)):
            if np.asarray(a).tobytes() != np.asarray(b).tobytes():
                mismatches += 1
                labels = {"field": names[i]} if names else {}
                _metrics.inc("halo.verify_mismatches", **labels)
        _metrics.inc("halo.verify_checks", len(out_l))
        _metrics.phase_add("halo.verify", time.perf_counter() - t0)
        return mismatches

    # ------------------------------------------------------- telemetry

    def _record(self, state, kind: str) -> None:
        """Host-side telemetry for one exchange dispatch: message/byte
        accounting per ring distance and field.  Callers gate on
        ``metrics.enabled and not _tracing(state)`` — recording inside a
        jit trace would count trace-time, not run-time, so exchanges
        embedded in fused device loops are intentionally not counted
        per step (the jitted program carries no telemetry ops at all).
        The phase timer around the dispatch measures host dispatch wall
        time; the collectives themselves complete asynchronously.

        Every recorded value is a pure function of the schedule and the
        state's field signature (shapes/dtypes), so the prepared batch is
        cached per signature and a dispatch only bumps its multiplicity —
        the batch materializes into the registry when a report/export
        flushes it (``metrics.register_flusher``).  A repeat dispatch
        therefore costs a signature hash and one integer add (the
        ≤2%-overhead budget of the bench acceptance).  The bare ``+= 1``
        is not atomic across threads; a lost bump under thread races is
        accepted — this is telemetry, not accounting."""
        if isinstance(state, dict):
            sig = (kind,) + tuple(
                (n, x.shape, x.dtype) for n, x in state.items()
            )
        else:
            sig = (kind, "tree") + tuple(
                (x.shape, x.dtype)
                for x in jax.tree_util.tree_leaves(state)
            )
        cache = getattr(self, "_record_cache", None)
        if cache is None:
            cache = self._record_cache = {}
            _metrics.register_flusher(self)
            # epoch rebuilds drop their schedules (grid._halo_cache is
            # cleared); pending buffered counts must not die with them
            weakref.finalize(self, _flush_record_cache, cache)
        entry = cache.get(sig)
        if entry is None:
            from ..obs.registry import _labels_key

            hood = "default" if self.hood_id is None else str(self.hood_id)
            items = [
                ("halo.exchanges", 1, {"kind": kind, "hood": hood}),
                ("halo.cells_moved", self.cells_moved),
                ("halo.bytes_moved", self.bytes_moved(state)),
                ("halo.wire_bytes", self.wire_bytes(state)),
                ("halo.permute_steps", len(self.ring_ks)),
            ]
            # per-device cells per dispatch (schedule rows; for a
            # cell_datatype policy this counts the full-payload schedule,
            # field-accurate bytes are in halo.field_bytes)
            items.extend(
                ("halo.send_cells", int(self._send_per_dev[d]),
                 {"device": d, "hood": hood}) for d in range(self.D)
            )
            items.extend(
                ("halo.recv_cells", int(self._recv_per_dev[d]),
                 {"device": d, "hood": hood}) for d in range(self.D)
            )
            per = self._per_cell_bytes(state)
            if self._cell_datatype is None:
                items.extend(
                    ("halo.ring_bytes", self.D * S * per, {"ring": k})
                    for k, S in zip(self.ring_ks, self.ring_sizes)
                )
                if isinstance(state, dict):
                    items.extend(
                        ("halo.field_bytes",
                         self.cells_moved * self._per_cell_bytes({n: arr}),
                         {"field": n})
                        for n, arr in state.items()
                    )
            else:
                for n in self._names(state):
                    self._rings_for_field(n)
                    _ks, _p, _s, _r, f_wire, f_cells = self._field_rings[n]
                    items.append(
                        ("halo.field_bytes",
                         f_cells * self._per_cell_bytes({n: state[n]}),
                         {"field": n})
                    )
            entry = cache[sig] = [
                [
                    ((it[0], _labels_key(it[2]) if len(it) > 2 else ()),
                     int(it[1])) for it in items
                ],
                0,
            ]
        entry[1] += 1

    def telemetry_flush(self, discard: bool = False) -> None:
        """Materialize buffered dispatch counts into the registry (or
        drop them on ``discard`` — a registry reset)."""
        cache = getattr(self, "_record_cache", None)
        if not cache:
            return
        if discard:
            for entry in cache.values():
                entry[1] = 0
            return
        _flush_record_cache(cache)

    # ------------------------------------------------------- split-phase

    def _build_split(self):
        """Split-phase pair (reference ``dccrg.hpp:5010-5367``): ``start``
        runs the ring collectives and returns the in-flight ghost payloads
        WITHOUT touching the state, so a jitted program can compute on
        inner cells with no data dependence on the collectives (XLA's
        latency-hiding scheduler overlaps them); ``finish`` scatters the
        payloads into the ghost rows — the data dependence IS the wait."""
        mesh = self.mesh
        D = self.D
        ks = tuple(self.ring_ks)
        backend = self.backend
        interpret = self._interpret

        def build():
            nk = len(ks)
            start_label = ("halo.dma.start" if backend == "pallas"
                           else "halo.start")
            if nk == 0:
                return (
                    traced_jit(
                        start_label,
                        lambda state: jax.tree_util.tree_map(
                            lambda x: (), state
                        ),
                    ),
                    traced_jit("halo.finish", lambda state, payload: state),
                )
            if backend == "pallas":
                # the DMA transfer completes inside the ring kernel; the
                # returned payloads are therefore already landed, and the
                # finish scatter below remains the program-level wait
                ring = lambda blk, sends: halo_dma.ring_dma_start(
                    blk, ks, D, sends, interpret=interpret
                )
            else:
                perms = [[(d, (d + k) % D) for d in range(D)] for k in ks]
                ring = lambda blk, sends: HaloExchange.ring_start(
                    blk, perms, sends
                )
            data_spec = P(SHARD_AXIS)
            idx_spec = P(SHARD_AXIS, None)

            def start_body(*args):
                sends = [a[0] for a in args[:nk]]
                state = args[nk]
                return jax.tree_util.tree_map(
                    lambda x: tuple(
                        p[None] for p in ring(x[0], sends)
                    ),
                    state,
                )

            def finish_body(*args):
                recvs = [a[0] for a in args[:nk]]
                state, payload = args[nk], args[nk + 1]
                return jax.tree_util.tree_map(
                    lambda x, p: HaloExchange.ring_finish(
                        x[0], recvs, [q[0] for q in p]
                    )[None],
                    state,
                    payload,
                    is_leaf=lambda v: isinstance(v, tuple),
                )

            start = shard_map(
                start_body,
                mesh=mesh,
                in_specs=(idx_spec,) * nk + (data_spec,),
                out_specs=data_spec,
                check_vma=False,
            )
            finish = shard_map(
                finish_body,
                mesh=mesh,
                in_specs=(idx_spec,) * nk + (data_spec, data_spec),
                out_specs=data_spec,
                check_vma=False,
            )
            return (traced_jit(start_label, start),
                    traced_jit("halo.finish", finish))

        self._start_fn, self._finish_fn = self._cache.get(
            ("halo.split",) + self.structure_key, build
        )

    def start(self, state) -> HaloHandle:
        """Dispatch the ghost-payload collectives; returns a
        ``HaloHandle`` wrapping the in-flight per-ring-step payload
        pytree."""
        if isinstance(state, HaloHandle):
            raise TypeError("start() takes the state, not a HaloHandle")
        if _metrics.enabled and not _tracing(state):
            # timed as its own phase (not halo.exchange): the span from
            # a halo.start begin to the next halo.exchange (finish) end
            # is the collective's in-flight window
            self._record(state, "split")
            t0 = time.perf_counter()
            out = self._start_dispatch(state)
            _metrics.phase_add("halo.start", time.perf_counter() - t0)
            return out
        return self._start_dispatch(state)

    def _start_dispatch(self, state) -> HaloHandle:
        if self._cell_datatype is not None:
            names = self._names(state)
            _block, start, _finish, tab_args = self._selective(names)
            payload = start(*tab_args, *(state[n] for n in names))
            return HaloHandle((names, payload))
        if not hasattr(self, "_start_fn"):
            self._build_split()
        return HaloHandle(self._start_fn(*self.ring_send, state))

    def finish(self, state, handle: HaloHandle):
        """Merge a ``start`` handle's payloads into the ghost rows."""
        if not isinstance(handle, HaloHandle):
            raise TypeError(
                "finish() expects the HaloHandle returned by start()"
            )
        if _metrics.enabled and not _tracing(state):
            t0 = time.perf_counter()
            out = self._finish_dispatch(state, handle)
            _metrics.phase_add("halo.exchange", time.perf_counter() - t0)
        else:
            out = self._finish_dispatch(state, handle)
        if self._verify_active(state):
            # the handle came from start(state) on this same state, so
            # the blocking oracle on `state` is the expected merge
            self._verify_oracle(state, out)
        return out

    def _finish_dispatch(self, state, handle: HaloHandle):
        if self._cell_datatype is not None:
            names, payload = handle.payload
            if names != self._names(state):
                raise ValueError("finish() got a different field set "
                                 "than start()")
            _block, _start, finish, tab_args = self._selective(names)
            outs = finish(*tab_args, *(state[n] for n in names), *payload)
            return {**state, **dict(zip(names, outs))}
        if not hasattr(self, "_finish_fn"):
            self._build_split()
        return self._finish_fn(*self.ring_recv, state, handle.payload)

    # ------------------------------------------------------- accounting

    @staticmethod
    def _per_cell_bytes(state) -> int:
        return sum(
            int(np.prod(x.shape[2:])) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(state)
        )

    def _per_field_totals(self, state) -> tuple[int, int]:
        """(useful bytes, wire bytes) under the cell_datatype policy."""
        useful = wire = 0
        for n in self._names(state):
            self._rings_for_field(n)
            _ks, _perms, _s, _r, f_wire, f_cells = self._field_rings[n]
            per = self._per_cell_bytes({n: state[n]})
            useful += f_cells * per
            wire += f_wire * per
        return useful, wire

    def bytes_moved(self, state) -> int:
        """Useful payload bytes (real send-list rows) per exchange."""
        if self._cell_datatype is not None:
            return self._per_field_totals(state)[0]
        return self.cells_moved * self._per_cell_bytes(state)

    def wire_bytes(self, state) -> int:
        """Bytes actually crossing the mesh per exchange: each ring step
        moves ``D * S_k`` rows (its own max pair count, padding
        included), so this scales with the real communication pattern —
        not with worst-pair x D^2 as a padded all_to_all would."""
        if self._cell_datatype is not None:
            return self._per_field_totals(state)[1]
        return self.wire_cells * self._per_cell_bytes(state)
