"""The Grid: dccrg's user model on a TPU mesh.

Mirrors the reference's ``Dccrg`` class surface (fluent builder ->
``initialize`` -> iterate local cells / exchange halos / refine / balance,
``dccrg.hpp:472-552, 8104-8230``) with a TPU-native execution model:

* cell payloads are SoA ``[n_devices, rows, ...]`` JAX arrays sharded over a
  1-D ``jax.sharding.Mesh`` (a cell is a row, not an object);
* the payload-type seam — the reference's ``get_mpi_datatype()``
  (``dccrg_get_cell_datatype.hpp:40-339``) — becomes a ``CellSpec`` dict of
  field name -> (shape, dtype);
* grid/refinement metadata stays host-side and replicated, like the
  reference's ``cell_process`` directory (``dccrg.hpp:7196``);
* halo exchanges are precompiled collective schedules (``parallel/halo.py``)
  regenerated per partition epoch.
"""
from __future__ import annotations

import itertools as _itertools
from contextlib import nullcontext as _nullcontext

import numpy as np
import jax
import jax.numpy as jnp

from .core.mapping import Mapping
from .core.topology import Topology
from .core.neighborhood import default_neighborhood, validate_neighborhood
from .core.neighbors import InconsistentGridError, LeafSet
from .geometry import CartesianGeometry, NoGeometry
from .obs.events import timeline as _timeline
from .parallel.epoch import build_epoch
from .parallel.exec_cache import ExecutableCache
from .parallel.halo import HaloExchange
from .parallel.mesh import SHARD_AXIS, make_mesh, shard_spec
from .parallel.shapes import epoch_shape_hints, signature_of
from .parallel.partition import block_partition, hilbert_partition, morton_partition
from .utils.collectives import fetch

__all__ = ["Grid", "CellSpec", "HAS_NO_NEIGHBOR", "HAS_LOCAL_NEIGHBOR_OF",
           "HAS_LOCAL_NEIGHBOR_TO", "HAS_REMOTE_NEIGHBOR_OF",
           "HAS_REMOTE_NEIGHBOR_TO"]

#: field name -> (per-cell shape tuple, dtype); the pytree/dtype analogue of
#: the reference's MPI datatype seam.
CellSpec = dict

#: neighbor-relation criteria bits for ``Grid.get_cells_by_criteria``
#: (reference ``dccrg.hpp:85-142``)
#: source of process-unique ``Grid.grid_id`` values (timeline span
#: separation for concurrent grids — see ``obs.events``)
_GRID_IDS = _itertools.count()

#: reusable no-op context (``nullcontext`` keeps no state, so one
#: instance serves every disabled-timeline dispatch)
_NULL_CTX = _nullcontext()

HAS_NO_NEIGHBOR = 0
HAS_LOCAL_NEIGHBOR_OF = 1 << 0
HAS_LOCAL_NEIGHBOR_TO = 1 << 1
HAS_REMOTE_NEIGHBOR_OF = 1 << 2
HAS_REMOTE_NEIGHBOR_TO = 1 << 3
HAS_LOCAL_NEIGHBOR_BOTH = HAS_LOCAL_NEIGHBOR_OF | HAS_LOCAL_NEIGHBOR_TO
HAS_REMOTE_NEIGHBOR_BOTH = HAS_REMOTE_NEIGHBOR_OF | HAS_REMOTE_NEIGHBOR_TO


class Grid:
    # ------------------------------------------------------------- builder

    def __init__(self):
        self._length = (1, 1, 1)
        self._max_ref_lvl = 0
        self._periodic = (False, False, False)
        self._hood_length = 1
        self._lb_method = "RCB"
        self._geometry_factory = None
        self.initialized = False

    def set_initial_length(self, length) -> "Grid":
        self._assert_uninitialized()
        self._length = tuple(int(v) for v in length)
        return self

    def set_maximum_refinement_level(self, lvl: int) -> "Grid":
        self._assert_uninitialized()
        self._max_ref_lvl = int(lvl)
        return self

    def set_periodic(self, x: bool, y: bool, z: bool) -> "Grid":
        self._assert_uninitialized()
        self._periodic = (bool(x), bool(y), bool(z))
        return self

    def set_neighborhood_length(self, n: int) -> "Grid":
        self._assert_uninitialized()
        if n < 0:
            raise ValueError("neighborhood length must be >= 0")
        self._hood_length = int(n)
        return self

    def set_load_balancing_method(self, method: str) -> "Grid":
        self._assert_uninitialized()
        # normalized once here: compute_partition upper-cases anyway, and
        # initialize's striping dispatch compares verbatim — a lowercase
        # method must not stripe differently from its uppercase spelling
        # (it would also defeat the multi-controller agreement digest)
        self._lb_method = str(method).upper()
        return self

    def set_geometry(self, factory=None, **params) -> "Grid":
        """``factory(mapping, topology) -> geometry``; or a geometry class
        plus keyword params (e.g. ``set_geometry(CartesianGeometry,
        start=..., level_0_cell_length=...)``)."""
        self._assert_uninitialized()
        if factory is None:
            factory = CartesianGeometry
        self._geometry_factory = lambda m, t: factory(mapping=m, topology=t, **params)
        return self

    def _assert_uninitialized(self):
        if self.initialized:
            raise RuntimeError("grid already initialized")

    # ---------------------------------------------------------- initialize

    def initialize(self, mesh=None, n_devices: int | None = None,
                   leaf_set=None) -> "Grid":
        """Create level-0 cells, stripe them over the mesh devices (the
        reference's ``create_level_0_cells``, ``dccrg.hpp:7967-8102``) and
        build all derived state.

        ``leaf_set``: start from an existing leaf-id array instead of the
        level-0 grid — the checkpoint loader's path (the saved set is a
        valid 2:1 forest already, so rebuilding derived state ONCE
        replaces the reference's level-by-level refinement replay,
        ``dccrg.hpp:3647-3716``).  The set is validated: exact domain
        tiling and the 2:1 balance invariant both raise on a corrupt
        file.

        Timed as the ``grid.initialize`` phase, holding
        ``grid.partition`` and the ``epoch.build`` of the first epoch."""
        from .obs import metrics

        with metrics.phase("grid.initialize"):
            return self._initialize(mesh, n_devices, leaf_set)

    def _initialize(self, mesh, n_devices, leaf_set) -> "Grid":
        from .obs import metrics

        self._assert_uninitialized()
        self.mesh = mesh if mesh is not None else make_mesh(n_devices=n_devices)
        self.n_devices = self.mesh.devices.size
        self.mapping = Mapping(length=self._length, max_refinement_level=self._max_ref_lvl)
        self.topology = Topology(periodic=self._periodic)
        factory = self._geometry_factory or (lambda m, t: NoGeometry(m, t))
        self.geometry = factory(self.mapping, self.topology)

        self.neighborhoods = {None: default_neighborhood(self._hood_length)}
        self.cell_weights = {}
        self.pin_requests = {}
        from .amr.refinement import AmrQueues

        self.amr = AmrQueues()
        self._last_new_cells = np.zeros(0, dtype=np.uint64)
        self._last_removed_cells = np.zeros(0, dtype=np.uint64)
        self._last_adaptation_delta = None
        self._prev_epoch = None
        #: process-unique id stamped (as ``grid_id``) onto every timeline
        #: span this grid's instrumented seams record, so traces from
        #: concurrent grids stay separable in one merged timeline
        self.grid_id = next(_GRID_IDS)
        self._tl_ctx = None   # cached reusable timeline context frame
        # compiled-schedule cache + recycled table buffers: both survive
        # every epoch rebuild (the whole point — see parallel/shapes.py)
        from .parallel.epoch_delta import TablePool

        self.exec_cache = ExecutableCache()
        self._table_pool = TablePool()
        # ring-size hysteresis hints (parallel/halo.py): shared by every
        # schedule this grid compiles, surviving rebuilds
        self._ring_hints = {}

        if leaf_set is not None:
            cells = np.unique(np.asarray(leaf_set, dtype=np.uint64))
            if len(cells) != len(np.asarray(leaf_set)):
                raise ValueError("leaf_set contains duplicate ids")
            self._validate_leaf_tiling(cells)
        else:
            n0 = int(np.prod(self._length))
            cells = np.arange(1, n0 + 1, dtype=np.uint64)
        # enforced multi-controller agreement on the builder inputs: a
        # controller whose settings diverge would build a different grid
        # and silently desynchronize every later collective; raise on all
        # controllers instead (no-op with one controller)
        from .utils.collectives import assert_agreement

        settings = repr((
            self._length, self._max_ref_lvl, self._periodic,
            self._hood_length, str(self._lb_method).upper(),
            type(self.geometry).__name__,
        )).encode()
        assert_agreement(
            "Grid.initialize settings",
            settings + self.geometry.params_to_file_bytes()
            + (cells.tobytes() if leaf_set is not None else b""),
        )
        with metrics.phase("grid.partition"):
            if self._lb_method in ("HSFC", "SFC", "HILBERT"):
                owner = hilbert_partition(self.mapping, cells, self.n_devices)
            elif self._lb_method == "MORTON":
                owner = morton_partition(self.mapping, cells, self.n_devices)
            else:
                owner = block_partition(cells, self.n_devices)
        self.leaves = LeafSet(cells=cells,
                              owner=owner.astype(np.int32, copy=False))
        self.initialized = True
        if leaf_set is not None:
            # the neighbor engine itself rejects many inconsistent sets
            # (no leaf found for a slot); surface those under the same
            # contract as the explicit checks
            try:
                self._rebuild()
            except InconsistentGridError as e:
                raise ValueError(
                    f"leaf_set is not a consistent 2:1 forest: {e}"
                ) from e
            self._validate_two_to_one()
        else:
            self._rebuild()
        return self

    def _validate_leaf_tiling(self, cells):
        """Exact-cover check for a candidate leaf set: the level-weighted
        volumes must tile the domain exactly, plus an explicit
        no-ancestor-overlap screen — the integer volume sum alone could
        be satisfied by a compensating overlap+hole pair, so each
        guarantee is checked on its own rather than delegated to the
        neighbor-engine/2:1 screens."""
        lvl = self.mapping.get_refinement_level(cells)
        if (lvl < 0).any():
            raise ValueError("leaf_set contains invalid cell ids")
        L = self.mapping.max_refinement_level
        counts = np.bincount(lvl.astype(np.int64), minlength=L + 1)
        total = sum(int(c) << (3 * (L - k)) for k, c in enumerate(counts))
        expect = int(np.prod(self._length)) << (3 * L)
        if total != expect:
            raise ValueError(
                "leaf_set does not tile the domain (corrupt checkpoint?)"
            )
        # walk every cell's ancestor chain and verify none is itself in
        # the set (disjointness); with the exact volume sum above this
        # makes the cover exact without relying on downstream checks
        anc = np.unique(cells[lvl > 0])
        while len(anc):
            anc = np.unique(self.mapping.get_parent(anc))
            if np.isin(anc, cells).any():
                raise ValueError(
                    "leaf_set contains both a cell and its ancestor "
                    "(corrupt checkpoint?)"
                )
            anc = anc[self.mapping.get_refinement_level(anc) > 0]

    def _validate_two_to_one(self):
        """Post-build 2:1 balance check from the epoch's neighbor tables:
        every neighbor pair's refinement levels differ by at most one
        (the invariant the neighbor engine assumes)."""
        hood = self.epoch.hoods[None]
        clen = self.epoch.cell_len.astype(np.int64)[..., None]
        nlen = hood.nbr_len.astype(np.int64)
        bad = hood.nbr_valid & (
            (nlen > 2 * clen) | (clen > 2 * nlen)
        )
        if bad.any():
            raise ValueError(
                "leaf_set violates 2:1 balance (corrupt checkpoint?)"
            )

    def _uniform_geometry(self) -> bool:
        """Whether every level-0 cell shares one physical size — the
        precondition for the dense fast path's metric factors (a
        stretched geometry's ``get_level_0_cell_length`` describes only
        its first cell)."""
        return bool(getattr(self.geometry, "uniform_level0", False))

    def _shape_hints(self) -> dict:
        """Bucket-hysteresis hints from the current epoch (empty before
        the first build) — see ``parallel/shapes.py``."""
        return epoch_shape_hints(getattr(self, "epoch", None))

    def shape_signature(self):
        """The current epoch's :class:`~dccrg_tpu.parallel.shapes.
        ShapeSignature` — the identity compiled schedules are keyed by,
        including this grid's held halo ring-size hints (so the
        signature alone predicts executable-cache behavior across a
        rescale or warm restart).  Two epochs with equal signatures
        share every cached executable (``grid.exec_cache``); a rebuild
        that keeps the signature costs zero retraces."""
        return signature_of(self.epoch, self._ring_hints)

    def _harvest_tables(self, old_epoch) -> None:
        """Park a retired epoch's gather-table buffers for reuse by the
        next delta patch — unless the epoch is shared with another grid
        (``copy_structure``), whose tables must stay intact."""
        if old_epoch is None or getattr(old_epoch, "_shared", False):
            return
        # multi-controller put_table hands jitted code the HOST arrays
        # themselves (no device copy) — recycling them would mutate live
        # schedule constants
        if jax.process_count() > 1:
            return
        for h in old_epoch.hoods.values():
            self._table_pool.put(
                (h.nbr_rows, h.nbr_valid, h.nbr_offset, h.nbr_len,
                 h.nbr_slot)
            )
        old_epoch.hoods = {}

    def _rebuild(self):
        """Recompute every derived structure for the current leaf set —
        the analogue of the reference's post-mutation rebuild tail
        (``dccrg.hpp:4063-4111, 10503-10551``).  Timed as the
        ``epoch.build`` phase inside ``build_epoch`` itself."""
        self.epoch = build_epoch(
            self.mapping, self.topology, self.leaves, self.n_devices,
            self.neighborhoods,
            uniform_geometry=self._uniform_geometry(),
            shape_hints=self._shape_hints(),
        )
        self._halo_cache = {}
        self._id_pos_cache = None
        self._unrefine_cache = None

    def _rebuild_incremental(self, old_epoch):
        """Derive the epoch for the current (already mutated) leaf set by
        delta-patching ``old_epoch`` (``parallel/epoch_delta.py``) —
        O(|touched| · K) instead of the full O(N · K) rebuild — falling
        back to ``build_epoch`` (the semantic oracle) whenever the delta
        path declines (closure too large, row-budget jump, dense-path
        flip; see ``epoch_delta.FALLBACK_REASONS``).  Shape hints keep
        the bucketed table shapes sticky, and the retired epoch's table
        buffers are recycled into ``_table_pool`` for the next patch."""
        from .parallel.epoch_delta import build_epoch_delta

        epoch = None
        if old_epoch is not None:
            epoch = build_epoch_delta(
                old_epoch, self.leaves, self.n_devices, self.neighborhoods,
                uniform_geometry=self._uniform_geometry(),
                shape_hints=epoch_shape_hints(old_epoch),
                table_pool=getattr(self, "_table_pool", None),
            )
        if epoch is None:
            self._rebuild()
            return
        self.epoch = epoch
        self._halo_cache = {}
        self._id_pos_cache = None
        self._unrefine_cache = None

    # --------------------------------------------------------- cell views

    def _assert_initialized(self):
        if not self.initialized:
            raise RuntimeError("grid not initialized")

    def _assert_no_staged_lb(self):
        """Structural mutators are forbidden while a staged balance_load
        is pending: the staged epoch reflects the current leaf set."""
        if getattr(self, "_staged_lb", None) is not None:
            raise RuntimeError("a staged balance_load is in progress")

    def get_cells(self) -> np.ndarray:
        """All existing (leaf) cells, ascending id — global view."""
        self._assert_initialized()
        return self.leaves.cells.copy()

    def local_cells(self, device: int | None = None) -> np.ndarray:
        """Cells owned by a device (all devices if None), ascending id."""
        self._assert_initialized()
        if device is None:
            return self.leaves.cells.copy()
        return self.leaves.cells[self.epoch.local_pos[device]]

    def inner_cells(self, device: int, hood_id=None) -> np.ndarray:
        h = self.epoch.hoods[hood_id]
        rows = np.flatnonzero(h.inner_mask[device])
        return self.epoch.cell_ids[device, rows]

    def outer_cells(self, device: int, hood_id=None) -> np.ndarray:
        h = self.epoch.hoods[hood_id]
        rows = np.flatnonzero(h.outer_mask[device])
        return self.epoch.cell_ids[device, rows]

    def remote_cells(self, device: int) -> np.ndarray:
        """Ghost cells held by a device."""
        return self.leaves.cells[self.epoch.ghost_pos[device]]

    def get_owner(self, ids) -> np.ndarray:
        """Owning device of given cells (-1 if not a leaf) — the cell
        directory query (reference ``cell_process``)."""
        pos = self.leaves.position(ids)
        return np.where(pos >= 0, self.leaves.owner[np.maximum(pos, 0)], -1)

    def is_local(self, ids, device: int) -> np.ndarray:
        return self.get_owner(ids) == device

    def get_neighbors_of(self, cell, hood_id=None):
        """(ids, offsets) of a cell's neighbors in reference order."""
        self._assert_initialized()
        pos = int(self.leaves.position(np.uint64(cell)))
        if pos < 0:
            raise ValueError(f"cell {cell} does not exist")
        return self.epoch.hoods[hood_id].lists.row(pos)

    def get_neighbors_to(self, cell, hood_id=None) -> np.ndarray:
        """Unique ids of cells having given cell as neighbor."""
        self._assert_initialized()
        pos = int(self.leaves.position(np.uint64(cell)))
        if pos < 0:
            raise ValueError(f"cell {cell} does not exist")
        h = self.epoch.hoods[hood_id]
        return self.leaves.cells[h.to_src[h.to_start[pos] : h.to_start[pos + 1]]]

    def get_face_neighbors_of(self, cell):
        """(neighbor id, direction) pairs with directions +-1/+-2/+-3 as in
        the reference (``dccrg.hpp:2806-2933``): neighbors sharing a face,
        direction is the axis (1=x, 2=y, 3=z) signed by side."""
        ids, offs = self.get_neighbors_of(cell)
        own_len = int(self.mapping.get_cell_length_in_indices(np.uint64(cell)))
        nbr_len = self.mapping.get_cell_length_in_indices(ids).astype(np.int64)
        out = []
        seen = set()
        for nid, off, nl in zip(ids, offs, nbr_len):
            d = _face_direction(off, own_len, int(nl))
            if d != 0 and (int(nid), d) not in seen:
                seen.add((int(nid), d))
                out.append((np.uint64(nid), d))
        return out

    def get_refinement_level(self, cell) -> int:
        return int(self.mapping.get_refinement_level(np.uint64(cell)))

    def neighbor_criteria(self, device: int, hood_id=None) -> np.ndarray:
        """Bitmask of neighbor-relation criteria per local cell of a device
        (reference bits, ``dccrg.hpp:85-142``)."""
        h = self.epoch.hoods[hood_id]
        lists = h.lists
        owner = self.leaves.owner.astype(np.int64)
        N = len(self.leaves)
        counts = np.diff(lists.start)
        src = np.repeat(np.arange(N), counts)
        bits = np.zeros(N, dtype=np.int32)
        local_nbr = owner[lists.nbr_pos] == owner[src]
        np.bitwise_or.at(bits, src[local_nbr], HAS_LOCAL_NEIGHBOR_OF)
        np.bitwise_or.at(bits, src[~local_nbr], HAS_REMOTE_NEIGHBOR_OF)
        src_to = np.repeat(np.arange(N), np.diff(h.to_start))
        local_to = owner[h.to_src] == owner[src_to]
        np.bitwise_or.at(bits, src_to[local_to], HAS_LOCAL_NEIGHBOR_TO)
        np.bitwise_or.at(bits, src_to[~local_to], HAS_REMOTE_NEIGHBOR_TO)
        return bits[self.epoch.local_pos[device]]

    def get_cells_by_criteria(
        self, device: int, criteria: int, exact_match: bool = False, hood_id=None
    ) -> np.ndarray:
        """Local cells of a device filtered by neighbor-relation criteria
        bits (reference ``get_cells``, ``dccrg.hpp:651-741, 2946-3053``):
        any-bit match by default, all-and-only with ``exact_match``."""
        bits = self.neighbor_criteria(device, hood_id)
        cells = self.local_cells(device)
        if criteria == HAS_NO_NEIGHBOR:
            return cells[bits == 0]
        if exact_match:
            return cells[bits == criteria]
        return cells[(bits & criteria) != 0]

    # ------------------------------------------------ structure sharing

    def copy_structure(self) -> "Grid":
        """A new Grid sharing this grid's decomposition (mapping, topology,
        geometry, leaf set, epoch) but no payload — the analogue of the
        reference's cross-instantiation copy constructor used to hold a
        second payload aligned with the same decomposition
        (``dccrg.hpp:338-438``).  Payloads are separate by construction
        here (states are user-held pytrees), so the copy can even share the
        derived epoch until either grid mutates."""
        g = Grid.__new__(Grid)
        g.__dict__.update(self.__dict__)
        g.cell_weights = dict(self.cell_weights)
        g.pin_requests = dict(self.pin_requests)
        if hasattr(self, "_hier_levels"):
            g._hier_levels = list(self._hier_levels)
            g._hier_options = [dict(o) for o in self._hier_options]
        if hasattr(self, "_partitioning_options"):
            g._partitioning_options = dict(self._partitioning_options)
        from .amr.refinement import AmrQueues

        g.amr = AmrQueues()
        g._halo_cache = dict(self._halo_cache)
        # the shared epoch's tables must never be recycled into either
        # grid's buffer pool while the other may still read them
        if hasattr(self, "epoch"):
            self.epoch._shared = True
        return g

    # -------------------------------------------------- options / getters

    def set_partitioning_option(self, name: str, value) -> "Grid":
        """Record a partitioner option (the reference forwards these as
        Zoltan strings, ``dccrg.hpp:5537-5564``).  The native partitioners
        act on ``LB_METHOD`` (overrides the method), ``IMBALANCE_TOL``
        (max part load as a multiple of the average) and
        ``PHG_CUT_OBJECTIVE``; known Zoltan tuning knobs are documented
        inert and anything unrecognized warns (``parallel/loadbalance.py``).
        Reserved names raise, as in the reference."""
        self._check_reserved_option(name)
        if not hasattr(self, "_partitioning_options"):
            self._partitioning_options = {}
        self._partitioning_options[str(name)] = value
        return self

    @staticmethod
    def _check_reserved_option(name):
        from .parallel.loadbalance import RESERVED_OPTIONS, warn_unknown_option

        if str(name).upper() in RESERVED_OPTIONS:
            raise ValueError(f"option {name!r} is reserved for dccrg")
        warn_unknown_option(name)

    def get_partitioning_options(self, level: int | None = None) -> dict:
        """The recorded global options, or — with ``level`` — the given
        hierarchical level's own options ({} for a nonexistent level)."""
        if level is None:
            return dict(getattr(self, "_partitioning_options", {}))
        opts = getattr(self, "_hier_options", [])
        if not 0 <= int(level) < len(opts):
            return {}
        return dict(opts[int(level)])

    def get_maximum_refinement_level(self) -> int:
        return self.mapping.max_refinement_level

    def get_neighborhood_length(self) -> int:
        return self._hood_length

    def get_load_balancing_method(self) -> str:
        return self._lb_method

    def get_periodicity(self) -> tuple:
        return self.topology.periodic

    def get_total_cells(self) -> int:
        return len(self.leaves)

    def get_local_cell_count(self, device: int) -> int:
        return int(self.epoch.n_local[device])

    def get_ghost_cell_count(self, device: int) -> int:
        return int(self.epoch.n_ghost[device])

    @property
    def length(self):
        return self.mapping.length

    # ------------------------------------------------------------ payloads

    def new_state(self, spec: CellSpec, fill=0):
        """Allocate sharded SoA payload arrays, one per field."""
        self._assert_initialized()
        D, R = self.n_devices, self.epoch.R
        state = {}
        for name, (shape, dtype) in spec.items():
            arr = jnp.full((D, R) + tuple(shape), fill, dtype=dtype)
            state[name] = jax.device_put(arr, shard_spec(self.mesh, arr.ndim))
        return state

    def set_cell_data(self, state, field: str, ids, values):
        """Host-side scatter of per-cell values into a field (init/IO path,
        not the compute path)."""
        ids = np.asarray(ids, dtype=np.uint64)
        pos = self.leaves.position(ids)
        if (pos < 0).any():
            raise ValueError("set_cell_data: non-existing cell")
        dev, row = self.epoch.global_rows(pos)
        host = fetch(state[field]).copy()
        host[dev, row] = values
        new = jax.device_put(
            jnp.asarray(host), shard_spec(self.mesh, host.ndim)
        )
        return {**state, field: new}

    def get_cell_data(self, state, field: str, ids):
        """Host-side gather of per-cell values (verification/IO path)."""
        ids = np.asarray(ids, dtype=np.uint64)
        pos = self.leaves.position(ids)
        if (pos < 0).any():
            raise ValueError("get_cell_data: non-existing cell")
        dev, row = self.epoch.global_rows(pos)
        return fetch(state[field])[dev, row]

    # ---------------------------------------------------------------- halo

    def set_cell_datatype(self, cell_datatype) -> "Grid":
        """Per-cell dynamic payload policy — the reference's
        ``get_mpi_datatype(cell_id, sender, receiver, receiving,
        neighborhood_id)`` seam (``dccrg_get_cell_datatype.hpp:48-125``),
        where a *cell* can vary its transferred content per exchange and
        neighborhood.  ``cell_datatype(field, cell_ids, sender, receiver,
        hood_id) -> bool mask`` selects which of a pair's cells transfer
        ``field``; unselected ghost copies simply keep their previous
        values (exactly the reference's not-included-in-the-datatype
        behavior).  Evaluated once per epoch at schedule compile — the
        trace-once analogue of the reference's per-call dispatch — and
        re-evaluated automatically after AMR/load-balance rebuilds.
        ``None`` clears the policy."""
        self._assert_initialized()
        self._cell_datatype = cell_datatype
        self._halo_cache = {}
        return self

    def halo(self, hood_id=None, cell_datatype=...) -> HaloExchange:
        """Compiled exchange schedule for a neighborhood (cached per
        epoch).  ``cell_datatype`` overrides the grid-level policy for
        this schedule (``...`` = inherit, None = full payloads)."""
        self._assert_initialized()
        installed = getattr(self, "_cell_datatype", None)
        policy = installed if cell_datatype is ... else cell_datatype
        # only the installed policy and the no-policy schedule are
        # cached: an ad-hoc override (often a fresh closure per call)
        # must not grow the cache without bound — it gets a fresh,
        # caller-owned schedule instead
        if policy is None or policy is installed:
            key = (hood_id, policy)
            if key not in self._halo_cache:
                self._halo_cache[key] = HaloExchange(
                    self.epoch, self.epoch.hoods[hood_id], self.mesh,
                    cell_datatype=policy, hood_id=hood_id,
                    exec_cache=self.exec_cache,
                    ring_hints=self._ring_hints,
                )
            return self._halo_cache[key]
        return HaloExchange(
            self.epoch, self.epoch.hoods[hood_id], self.mesh,
            cell_datatype=policy, hood_id=hood_id,
            exec_cache=self.exec_cache,
            ring_hints=self._ring_hints,
        )

    def _span_ctx(self):
        """Timeline context for this grid's instrumented entry points:
        every span recorded inside (halo dispatches, rebuild phases...)
        carries ``grid_id`` — workloads layer ``timeline.context(step=i)``
        on top — so merged traces from concurrent grids stay separable
        (see ``obs.events.EventTimeline.context``).  The frame object is
        cached: the per-dispatch cost is an enabled check plus a list
        push/pop."""
        if not _timeline.enabled:
            return _NULL_CTX
        ctx = self._tl_ctx
        if ctx is None:
            ctx = self._tl_ctx = _timeline.context(grid_id=self.grid_id)
        return ctx

    def update_copies_of_remote_neighbors(self, state, hood_id=None):
        """Blocking ghost refresh (reference ``dccrg.hpp:966-1000``)."""
        with self._span_ctx():
            return self.halo(hood_id)(state)

    def start_remote_neighbor_copy_updates(self, state, hood_id=None):
        """Split-phase start (reference ``dccrg.hpp:5010-5105``): launch
        the ghost-payload collective and return a handle.  The state is
        untouched, so inner-cell compute can proceed with no data
        dependence on the transfer — inside one jitted program XLA
        overlaps them (the reference's overlap pattern,
        ``examples/game_of_life.cpp:124-138``).  Merge with
        ``wait_remote_neighbor_copy_updates(state, handle)``."""
        with self._span_ctx():
            return self.halo(hood_id).start(state)

    def wait_remote_neighbor_copy_updates(self, state, handle=None, hood_id=None):
        """Split-phase wait: merge the ``start`` handle's payload into the
        ghost rows.  The merge is the synchronization — downstream reads of
        ghost rows now depend on the collective, nothing earlier does.
        Without a handle (legacy form) this degrades to a blocking ghost
        refresh."""
        with self._span_ctx():
            if handle is None:
                return self.halo(hood_id)(state)
            return self.halo(hood_id).finish(state, handle)

    # -------------------------------------------------- user neighborhoods

    def add_neighborhood(self, hood_id: int, offsets) -> bool:
        """Add a user-defined neighborhood with its own neighbor lists,
        send/recv schedule and iteration masks (reference
        ``dccrg.hpp:6383-6555``).  As in the reference, the offsets must fit
        inside the default neighborhood so ghost requirements (and hence
        payload layouts) are unchanged; existing states remain valid."""
        self._assert_no_staged_lb()
        self._assert_initialized()
        # enforced agreement BEFORE any early-out: every controller must
        # attempt the same registration or all of them fail loudly
        from .utils.collectives import assert_agreement

        assert_agreement(
            f"add_neighborhood({hood_id})",
            np.int64(-1 if hood_id is None else hood_id).tobytes()
            + np.asarray(offsets, dtype=np.int64).tobytes(),
        )
        if hood_id in self.neighborhoods or hood_id is None:
            return False
        offs = validate_neighborhood(offsets)
        n = self._hood_length
        if n == 0:
            default = {tuple(o) for o in self.neighborhoods[None].tolist()}
            if not all(tuple(o) in default for o in offs.tolist()):
                return False
        else:
            if np.abs(offs).max() > n:
                return False
        self.neighborhoods[hood_id] = offs
        self._rebuild()
        return True

    def remove_neighborhood(self, hood_id: int) -> bool:
        from .utils.collectives import assert_agreement

        assert_agreement(
            f"remove_neighborhood({hood_id})",
            np.int64(-1 if hood_id is None else hood_id).tobytes(),
        )
        if hood_id is None or hood_id not in self.neighborhoods:
            return False
        del self.neighborhoods[hood_id]
        self._rebuild()
        return True

    # ------------------------------------------------------- load balancing

    def set_cell_weight(self, cell, weight: float) -> bool:
        """Per-cell load-balance weight (reference ``dccrg.hpp:6210-6276``;
        default weight 1)."""
        self._assert_no_staged_lb()
        if not self.leaves.exists(np.uint64(cell)):
            return False
        self.cell_weights[int(cell)] = float(weight)
        return True

    def get_cell_weight(self, cell) -> float:
        return self.cell_weights.get(int(cell), 1.0)

    def pin(self, cell, device: int | None = None) -> bool:
        """Pin a cell to a device across load balances (its current owner if
        ``device`` is None) — reference ``dccrg.hpp:5832-6010``."""
        pos = int(self.leaves.position(np.uint64(cell)))
        if pos < 0:
            return False
        if device is None:
            device = int(self.leaves.owner[pos])
        if not 0 <= device < self.n_devices:
            return False
        self.pin_requests[int(cell)] = int(device)
        return True

    def unpin(self, cell) -> bool:
        if not self.leaves.exists(np.uint64(cell)):
            return False
        self.pin_requests.pop(int(cell), None)
        return True

    def unpin_all_cells(self) -> bool:
        self.pin_requests.clear()
        return True

    def add_partitioning_level(self, processes_per_part: int):
        """Hierarchical partitioning level (reference Zoltan HIER,
        ``dccrg.hpp:5566-5608``): devices are grouped in blocks of
        ``processes_per_part`` (e.g. chips per ICI-connected slice); cells
        are first balanced over groups, then within each group.  Multiple
        calls nest: each later level subdivides the previous level's
        groups (e.g. ``add_partitioning_level(4)`` then ``(2)`` on 8
        devices gives a 2x2x2 hierarchy: slices of 4, pairs of 2, then
        single devices).

        Each level starts with the reference's default per-level options
        (LB_METHOD=HYPERGRAPH, PHG_CUT_OBJECTIVE=CONNECTIVITY,
        ``dccrg.hpp:5600-5605``); override with
        ``add_partitioning_option(level, ...)``."""
        if int(processes_per_part) < 1:
            raise ValueError(
                "must assign at least 1 process to a hierarchical "
                "partitioning level"
            )
        if not hasattr(self, "_hier_levels"):
            self._hier_levels = []
            self._hier_options = []
        self._hier_levels.append(int(processes_per_part))
        self._hier_options.append({
            "LB_METHOD": "HYPERGRAPH",
            "PHG_CUT_OBJECTIVE": "CONNECTIVITY",
        })

    def remove_partitioning_level(self, level: int):
        """Remove the given hierarchical partitioning level (0-based);
        does nothing if it doesn't exist (``dccrg.hpp:5610-5648``)."""
        levels = getattr(self, "_hier_levels", [])
        if 0 <= int(level) < len(levels):
            del levels[int(level)]
            del self._hier_options[int(level)]

    def add_partitioning_option(self, level: int, name: str, value):
        """Add (or overwrite) a partitioning option for the given
        hierarchical level; does nothing if the level doesn't exist,
        raises on reserved names (``dccrg.hpp:5650-5706``)."""
        self._check_reserved_option(name)
        opts = getattr(self, "_hier_options", [])
        if 0 <= int(level) < len(opts):
            opts[int(level)][str(name)] = value

    def remove_partitioning_option(self, level: int, name: str):
        """Remove a partitioning option from the given hierarchical
        level; does nothing if the level or option doesn't exist
        (``dccrg.hpp:5708-5744``)."""
        opts = getattr(self, "_hier_options", [])
        if 0 <= int(level) < len(opts):
            opts[int(level)].pop(str(name), None)

    def balance_load(self, use_zoltan: bool = True):
        """Repartition cells (method from ``set_load_balancing_method``,
        pins override) and rebuild all derived state — the reference's
        3-phase ``balance_load`` (``dccrg.hpp:1024-1044, 3741-4147``)
        collapsed into one host-side step; carry payloads over with
        ``remap_state`` (pure ownership moves keep every cell's value).
        For chunked payload migration use ``initialize_balance_load`` /
        ``continue_balance_load`` / ``finish_balance_load``."""
        self._assert_initialized()
        if getattr(self, "_staged_lb", None) is not None:
            raise RuntimeError("a staged balance_load is in progress")
        from .obs import metrics

        with self._span_ctx(), metrics.phase("loadbalance.migrate"):
            owner = self._compute_new_owner(use_zoltan)
            self._lb_telemetry(self.leaves.owner, owner)
            self._last_new_cells = np.zeros(0, dtype=np.uint64)
            self._last_removed_cells = np.zeros(0, dtype=np.uint64)
            # load balancing cancels pending adaptation (reference:
            # requests are lost after balance_load, dccrg.hpp:2666-2668)
            self.amr.clear()
            if np.array_equal(owner, self.leaves.owner):
                # no cell moved: every derived table is still valid, skip
                # the (expensive) epoch rebuild; remap_state degenerates
                # to the identity (checkpoint reload hits this on its
                # post-replay balance when the partitioner reproduces the
                # current owners)
                self._prev_epoch = None
                return self
            old_epoch = self.epoch
            self.leaves = LeafSet(cells=self.leaves.cells, owner=owner)
            self._rebuild_incremental(old_epoch)
            self._prev_epoch = _EpochCarry(old_epoch)
            self._harvest_tables(old_epoch)
        return self

    def _lb_telemetry(self, old_owner, new_owner):
        """Record one repartition: cells whose owner changes and the load
        imbalance (max device load over the mean) before/after."""
        from .obs import metrics

        if not metrics.enabled:
            return
        metrics.inc("loadbalance.migrations")
        metrics.inc(
            "loadbalance.cells_migrated",
            int((np.asarray(old_owner) != np.asarray(new_owner)).sum()),
        )

        def imbalance(owner):
            counts = np.bincount(
                np.asarray(owner, dtype=np.int64), minlength=self.n_devices
            )
            avg = counts.mean()
            return float(counts.max() / avg) if avg > 0 else 1.0

        metrics.gauge("loadbalance.imbalance_before", imbalance(old_owner))
        metrics.gauge("loadbalance.imbalance_after", imbalance(new_owner))

    def _hierarchical_partition(self, method, weights, hier, options=None):
        """Multi-level partition over a device hierarchy (reference HIER,
        ``dccrg.hpp:5566-5798``): split cells over groups of ``hier[0]``
        devices (DCN level), then recurse into each group with the
        remaining levels, ending at single devices (ICI level).

        ``hier`` is a list of ``(processes_per_part, level_options)``
        pairs: each level's split runs under its own merged options
        (global ``set_partitioning_option`` values overlaid with the
        level's own, so a level-local IMBALANCE_TOL or LB_METHOD wins),
        mirroring the reference's per-level Zoltan option sets.  Levels
        exhausted with devices remaining fall through to the grid's
        global method."""
        from .parallel.loadbalance import compute_partition

        options = options or {}
        hier = [(int(per), dict(lv_opts or {})) for per, lv_opts in hier]

        def level_method(lv_opts):
            merged = {str(k).upper(): v for k, v in options.items()}
            merged.update({str(k).upper(): v for k, v in lv_opts.items()})
            return str(merged.get("LB_METHOD", method)).upper(), merged

        # one adjacency for the whole hierarchy, restricted per group —
        # built only if some level (or the fall-through method, which the
        # global LB_METHOD option can itself override) needs it
        methods_used = [level_method(lv_opts)[0] for _, lv_opts in hier]
        methods_used.append(level_method({})[0])
        adjacency = None
        if any(m in ("GRAPH", "HYPERGRAPH") for m in methods_used):
            from .parallel.graph import grid_adjacency

            adjacency = grid_adjacency(self)

        owner = np.zeros(len(self.leaves), dtype=np.int32)

        def recurse(sub, idx, w, levels, first, n_devices, adj):
            if n_devices <= 1 or len(idx) == 0:
                owner[idx] = first
                return
            if not levels:
                ft_method, ft_options = level_method({})
                owner[idx] = first + compute_partition(
                    ft_method, sub, n_devices, w, ft_options, adj
                )
                return
            lv_method, lv_options = level_method(levels[0][1])
            per = max(1, min(levels[0][0], n_devices))
            # groups of `per` devices plus a remainder group when per does
            # not divide the device count — no device may be left idle
            group_sizes = [per] * (n_devices // per)
            if n_devices % per:
                group_sizes.append(n_devices % per)
            if len(group_sizes) == 1:
                recurse(sub, idx, w, levels[1:], first, n_devices, adj)
                return
            # partition at device granularity, then merge consecutive parts
            # into groups proportional to each group's device count (equal
            # n_groups-way cuts would misweight a remainder group)
            fine = compute_partition(
                lv_method, sub, n_devices, w, lv_options, adj
            )
            bounds = np.cumsum([0] + group_sizes)
            group = np.searchsorted(bounds, fine, side="right") - 1
            for gi, n_dev_g in enumerate(group_sizes):
                sel = np.flatnonzero(group == gi)
                if not len(sel):
                    continue
                sub_adj = None
                if adj is not None:
                    from .parallel.graph import restrict_adjacency

                    sub_adj = restrict_adjacency(adj[0], adj[1], sel)
                recurse(
                    _SubGridView(sub, sel),
                    idx[sel],
                    w[sel] if w is not None else None,
                    levels[1:],
                    first + int(bounds[gi]),
                    n_dev_g,
                    sub_adj,
                )

        recurse(
            self,
            np.arange(len(self.leaves)),
            weights,
            list(hier),
            0,
            self.n_devices,
            adjacency,
        )
        return owner

    def _compute_new_owner(self, use_zoltan: bool) -> np.ndarray:
        """The new per-leaf owner array: multi-controller pin/weight
        agreement, partitioner, pin overrides."""
        from .parallel.loadbalance import compute_partition
        from .utils.collectives import sync_partition_inputs

        # multi-controller agreement on pins/weights before partitioning
        # (update_pin_requests All_Gather, dccrg.hpp:8297-8340) — a
        # transient merged view; this controller's own dicts stay local.
        # Identity under the single controller.
        all_pins, all_weights = sync_partition_inputs(
            self.pin_requests, self.cell_weights
        )

        weights = None
        if all_weights:
            weights = np.ones(len(self.leaves))
            for c, w in all_weights.items():
                p = int(self.leaves.position(np.uint64(c)))
                if p >= 0:
                    weights[p] = w

        method = self._lb_method if use_zoltan else "NONE"
        options = self.get_partitioning_options()
        hier = getattr(self, "_hier_levels", None)
        if hier and method.upper() != "NONE":
            hier_opts = getattr(self, "_hier_options", [{} for _ in hier])
            owner = self._hierarchical_partition(
                method, weights, list(zip(hier, hier_opts)), options
            )
        else:
            owner = compute_partition(
                method, self, self.n_devices, weights, options
            )

        # pins override the partitioner (make_new_partition,
        # dccrg.hpp:8417-8580)
        for c, d in all_pins.items():
            p = int(self.leaves.position(np.uint64(c)))
            if p >= 0:
                owner[p] = d
        return np.asarray(owner).astype(np.int32)

    def initialize_balance_load(self, use_zoltan: bool = True):
        """Phase 1 of the reference's split balance_load
        (``dccrg.hpp:3741-3884``): compute the new partition and build the
        new derived state WITHOUT touching the live grid — queries and
        stencils keep working on the old layout while payload chunks
        migrate through ``continue_balance_load``."""
        self._assert_initialized()
        if getattr(self, "_staged_lb", None) is not None:
            raise RuntimeError("a staged balance_load is in progress")
        from .obs import metrics

        with self._span_ctx(), metrics.phase("loadbalance.migrate"):
            owner = self._compute_new_owner(use_zoltan)
            self._lb_telemetry(self.leaves.owner, owner)
            # load balancing cancels pending adaptation
            # (dccrg.hpp:2666-2668)
            self.amr.clear()
            if np.array_equal(owner, self.leaves.owner):
                self._staged_lb = {"noop": True}
                return self
            new_leaves = LeafSet(cells=self.leaves.cells, owner=owner)
            # the staged epoch is a pure ownership migration off the live
            # one: the delta path reuses every neighbor relation and
            # re-derives only the owner-dependent tables
            from .parallel.epoch_delta import build_epoch_delta

            new_epoch = build_epoch_delta(
                self.epoch, new_leaves, self.n_devices, self.neighborhoods,
                uniform_geometry=self._uniform_geometry(),
                shape_hints=self._shape_hints(),
                table_pool=getattr(self, "_table_pool", None),
            )
            if new_epoch is None:
                new_epoch = build_epoch(
                    self.mapping, self.topology, new_leaves, self.n_devices,
                    self.neighborhoods,
                    uniform_geometry=self._uniform_geometry(),
                    shape_hints=self._shape_hints(),
                )
        self._staged_lb = {
            "noop": False,
            "leaves": new_leaves,
            "epoch": new_epoch,
            "staged": None,
            "host_old": None,
            "done": 0,
        }
        return self

    def continue_balance_load(self, state=None, max_cells=None) -> bool:
        """Phase 2, repeatable (``dccrg.hpp:3892-3934``): migrate the next
        ``max_cells`` leaves' payload rows into the staged new layout.
        Each call reads from the state PASSED TO IT (only the chunk's rows
        leave the device), so callers overlapping migration with compute
        must pass the state they want captured for that chunk — the same
        contract as the reference, which ships whatever is in cell_data at
        continue time.  Returns True while more cells remain; no ``state``
        means nothing to move (False)."""
        st = getattr(self, "_staged_lb", None)
        if st is None:
            raise RuntimeError("initialize_balance_load has not been called")
        if st.get("noop") or state is None:
            return False
        N = len(self.leaves)
        old, new = self.epoch, st["epoch"]
        if st["staged"] is None:
            st["staged"] = {
                k: np.zeros(
                    (new.n_devices, new.R) + tuple(v.shape[2:]),
                    np.dtype(v.dtype),
                )
                for k, v in state.items()
            }
        lo = st["done"]
        hi = N if max_cells is None else min(lo + int(max_cells), N)
        if lo < hi:
            from .obs import metrics

            metrics.inc("loadbalance.staged_rows", hi - lo)
            pos = np.arange(lo, hi)
            d_old, r_old = old.leaves.owner[pos], old.row_of[pos]
            d_new, r_new = new.leaves.owner[pos], new.row_of[pos]
            for k, arr in state.items():
                # per-chunk capture from the state passed to THIS call
                # (the split-phase contract); the eager gather runs SPMD
                # on every controller, fetch() brings the chunk home
                st["staged"][k][d_new, r_new] = fetch(arr[d_old, r_old])
            st["done"] = hi
        return hi < N

    def finish_balance_load(self, state=None):
        """Phase 3 (``dccrg.hpp:3942-4147``): commit the new directory and
        derived state.  Remaining chunks are drained from ``state`` first;
        returns the migrated state when payloads were staged, else the
        grid.  A partial migration with no ``state`` to finish from is an
        error (the staged copy would silently be incomplete)."""
        st = getattr(self, "_staged_lb", None)
        if st is None:
            raise RuntimeError("initialize_balance_load has not been called")
        if st.get("noop"):
            self._staged_lb = None
            self._prev_epoch = None
            self._last_new_cells = np.zeros(0, dtype=np.uint64)
            self._last_removed_cells = np.zeros(0, dtype=np.uint64)
            return state if state is not None else self
        if state is not None:
            while self.continue_balance_load(state):
                pass
        elif st["staged"] is not None and st["done"] < len(self.leaves):
            raise RuntimeError(
                "migration is partial; pass the state to finish_balance_load"
            )
        self._staged_lb = None
        old_epoch = self.epoch
        self._prev_epoch = _EpochCarry(old_epoch)
        self._last_new_cells = np.zeros(0, dtype=np.uint64)
        self._last_removed_cells = np.zeros(0, dtype=np.uint64)
        self.leaves = st["leaves"]
        self.epoch = st["epoch"]
        self._harvest_tables(old_epoch)
        self._halo_cache = {}
        self._id_pos_cache = None
        if st["staged"] is None:
            return self
        return {
            k: jax.device_put(jnp.asarray(v), shard_spec(self.mesh, v.ndim))
            for k, v in st["staged"].items()
        }

    # ------------------------------------------------------------------ AMR

    def _leaf_level(self, cell) -> int:
        pos = int(self.leaves.position(np.uint64(cell)))
        if pos < 0:
            return -1
        return self.mapping.refinement_level_of(int(cell))

    def refine_completely(self, cell) -> bool:
        """Queue a cell for refinement into 8 children at the next
        ``stop_refining`` (reference ``dccrg.hpp:2434-2532``)."""
        cell = int(cell)
        lvl = self._leaf_level(cell)
        if lvl < 0:
            return False
        if lvl == self.mapping.max_refinement_level:
            self.dont_unrefine(cell)
            return True
        if cell in self.amr.not_to_refine:
            return False
        ids = None
        if self.amr.not_to_refine:
            ids, _ = self.get_neighbors_of(cell)
            n_lvl = self.mapping.get_refinement_level(ids)
            if any(
                int(n) in self.amr.not_to_refine
                for n in ids[n_lvl < lvl]
            ):
                return False
        self.amr.to_refine.add(cell)
        # cancel conflicting unrefines: own siblings + same-or-coarser
        # neighbors' siblings (skipped when no unrefines are pending — the
        # mass-refinement fast path)
        if self.amr.to_unrefine:
            if ids is None:
                ids, _ = self.get_neighbors_of(cell)
            both = np.concatenate(
                [[np.uint64(cell)], ids, self.get_neighbors_to(cell)]
            ).astype(np.uint64)
            nl = self.mapping.get_refinement_level(both)
            cand = both[nl <= lvl]
            sibs = self.mapping.get_siblings(cand).reshape(-1)
            self.amr.to_unrefine.difference_update(sibs.tolist())
        return True

    def unrefine_completely(self, cell) -> bool:
        """Queue a cell's sibling family for replacement by its parent
        (reference ``dccrg.hpp:2560-2655``)."""
        cell = int(cell)
        lvl = self._leaf_level(cell)
        if lvl < 0:
            return False
        if lvl == 0:
            return True
        # per-sibling checks in the reference's order: has-children first
        # (False), then refine-queued/vetoed (True)
        siblings = self.mapping.siblings_of(cell)
        is_leaf = self.leaves.exists(np.asarray(siblings, dtype=np.uint64))
        for sib, leaf in zip(siblings, is_leaf):
            if not leaf:
                return False
            if sib in self.amr.to_refine or sib in self.amr.not_to_unrefine:
                return True
        # family already queued — hoisted above the expensive
        # parent-neighborhood search; a queued family always reaches a
        # True return below (queuing excludes child-bearing/refining/
        # vetoed siblings within an epoch), so the early exit preserves
        # the reference's return values
        if not self.amr.to_unrefine.isdisjoint(siblings):
            return True
        # parent's would-be neighborhood must not contain too-fine cells;
        # the neighbor structure is static per epoch, so it is computed
        # ONCE for every candidate parent in one vectorized search and
        # cached (only the to_refine membership check is per-call)
        too_fine, same_lvl_nbrs = self._unrefine_parent_info(
            self.mapping.parent_of(cell)
        )
        if too_fine:
            return True  # no-op: neighbor more than one level finer
        if not self.amr.to_refine.isdisjoint(same_lvl_nbrs):
            return True  # a would-be same-size neighbor is being refined
        self.amr.to_unrefine.add(cell)
        return True

    def _build_unrefine_cache(self):
        """Per-epoch vectorized answers for the unrefine parent-hood
        checks: ONE neighbor search over every candidate parent (the
        per-family scalar search used to dominate unrefinement request
        storms).  Returns ``(epoch, parents(sorted), too_fine_all,
        fcells, fstart)`` — per-parent answers resolve lazily by
        searchsorted; shared by the scalar and bulk request paths."""
        cache = getattr(self, "_unrefine_cache", None)
        if cache is not None and cache[0] is self.epoch:
            return cache
        from .amr.refinement import _find_for_nonleaves

        lvl = self.mapping.get_refinement_level(self.leaves.cells)
        finer = self.leaves.cells[lvl > 0]
        parents = np.unique(self.mapping.get_parent(finer))
        if len(parents):
            plists = _find_for_nonleaves(
                self.mapping, self.topology, self.leaves,
                parents, self.neighborhoods[None],
            )
            p_lvl = self.mapping.get_refinement_level(parents)
            counts = np.diff(plists.start)
            src = np.repeat(np.arange(len(parents)), counts)
            pos = plists.nbr_pos
            neg = (pos < 0).astype(np.int64)
            cum = np.concatenate(([0], np.cumsum(neg)))
            too_fine_all = (
                cum[plists.start[1:]] - cum[plists.start[:-1]]
            ) > 0
            n_lvl = np.where(
                pos >= 0,
                self.mapping.get_refinement_level(
                    self.leaves.cells[np.maximum(pos, 0)]
                ),
                -1,
            )
            fine_mask = n_lvl == p_lvl[src] + 1
            fsrc = src[fine_mask]
            fcells = self.leaves.cells[pos[fine_mask]]
            fcounts = np.bincount(fsrc, minlength=len(parents))
            fstart = np.concatenate(([0], np.cumsum(fcounts)))
        else:
            too_fine_all = np.zeros(0, dtype=bool)
            fcells = np.zeros(0, dtype=np.uint64)
            fstart = np.zeros(1, dtype=np.int64)
        cache = (self.epoch, parents, too_fine_all, fcells, fstart)
        self._unrefine_cache = cache
        return cache

    def _unrefine_parent_info(self, parent: int):
        """(too_fine, ids of the parent's would-be neighbors one level
        finer than it) for a candidate parent, from the per-epoch
        cache."""
        _, parents, too_fine_all, fcells, fstart = (
            self._build_unrefine_cache()
        )
        i = int(np.searchsorted(parents, np.uint64(parent)))
        if i >= len(parents) or parents[i] != np.uint64(parent):
            return True, frozenset()
        return (
            bool(too_fine_all[i]),
            set(fcells[fstart[i]:fstart[i + 1]].tolist()),
        )

    def dont_refine(self, cell) -> bool:
        cell = int(cell)
        lvl = self._leaf_level(cell)
        if lvl < 0:
            return False
        if lvl == self.mapping.max_refinement_level:
            return True
        self.amr.to_refine.discard(cell)
        self.amr.not_to_refine.add(cell)
        return True

    def dont_unrefine(self, cell) -> bool:
        cell = int(cell)
        lvl = self._leaf_level(cell)
        if lvl < 0:
            return False
        if lvl == 0:
            return True
        siblings = self.mapping.siblings_of(cell)
        if any(s in self.amr.not_to_unrefine for s in siblings):
            return True
        for s in siblings:
            self.amr.to_unrefine.discard(s)
        self.amr.not_to_unrefine.add(cell)
        return True

    # ------------------------------------------------- bulk request storms

    def _set_array(self, s):
        return np.fromiter(s, dtype=np.uint64, count=len(s))

    def refine_completely_many(self, cells) -> np.ndarray:
        """Vectorized ``refine_completely`` over an id array: identical
        final queue state and per-cell returns to calling the scalar API
        in order.  The vectorized form engages when no unrefines are
        pending and no refine vetoes exist (the mass-storm shape of
        adaptation drivers, where the scalar loop's per-request checks
        all degenerate); otherwise it falls back to the scalar loop."""
        ids = np.asarray(cells, dtype=np.uint64).reshape(-1)
        if len(ids) == 0:
            return np.zeros(0, dtype=bool)
        if self.amr.not_to_refine or self.amr.to_unrefine:
            return np.array(
                [self.refine_completely(int(c)) for c in ids], dtype=bool
            )
        pos = self.leaves.position(ids)
        exists = pos >= 0
        lvl = self.mapping.get_refinement_level(ids)
        at_max = exists & (lvl == self.mapping.max_refinement_level)
        if at_max.any():
            self.dont_unrefine_many(ids[at_max])
        mid = exists & ~at_max
        self.amr.to_refine.update(int(c) for c in ids[mid])
        return exists

    def unrefine_completely_many(self, cells) -> np.ndarray:
        """Vectorized ``unrefine_completely`` over an id array: identical
        final queue state and returns to the scalar loop (a pure
        unrefine storm's queue interactions are family-local, so every
        check vectorizes: sibling leaf-ness, refine-queued/vetoed
        siblings, already-queued families, the cached parent-hood
        answers, and first-requested-sibling-per-family dedupe)."""
        ids = np.asarray(cells, dtype=np.uint64).reshape(-1)
        out = np.zeros(len(ids), dtype=bool)
        if len(ids) == 0:
            return out
        pos = self.leaves.position(ids)
        exists = pos >= 0
        lvl = np.where(exists, self.mapping.get_refinement_level(ids), 0)
        out[exists & (lvl == 0)] = True
        idx = np.flatnonzero(exists & (lvl > 0))
        if not len(idx):
            return out
        sibs = self.mapping.get_siblings(ids[idx]).reshape(len(idx), 8)
        sib_leaf = self.leaves.exists(sibs.reshape(-1)).reshape(-1, 8)
        # one to_refine conversion per storm, shared with the parent-hood
        # check below
        tr_arr = (self._set_array(self.amr.to_refine)
                  if self.amr.to_refine else None)
        # the scalar loop walks siblings IN ORDER: the first non-leaf
        # sibling returns False, but a refine-queued/vetoed sibling
        # EARLIER in the family returns True first
        queued = np.zeros_like(sib_leaf)
        if tr_arr is not None:
            queued |= np.isin(sibs, tr_arr)
        if self.amr.not_to_unrefine:
            queued |= np.isin(
                sibs, self._set_array(self.amr.not_to_unrefine)
            )
        nonleaf = ~sib_leaf
        first_nonleaf = np.where(
            nonleaf.any(axis=1), np.argmax(nonleaf, axis=1), 8
        )
        first_queued = np.where(
            queued.any(axis=1), np.argmax(queued, axis=1), 8
        )
        # (a queued sibling strictly earlier than the first non-leaf one
        # wins the True return)
        ret_false = (first_nonleaf < 8) & ~(first_queued < first_nonleaf)
        out[idx] = ~ret_false
        proceed = (first_nonleaf == 8) & (first_queued == 8)
        idx = idx[proceed]
        if not len(idx):
            return out
        parents = self.mapping.get_parent(ids[idx])
        # family already queued before this storm
        if self.amr.to_unrefine:
            tu = self._set_array(self.amr.to_unrefine)
            queued_parents = np.unique(self.mapping.get_parent(tu))
            fresh = ~np.isin(parents, queued_parents)
            idx, parents = idx[fresh], parents[fresh]
            if not len(idx):
                return out
        # the parent's would-be neighborhood (per-epoch vectorized cache)
        too_fine, has_refining = self._unrefine_parent_info_many(
            parents, tr_arr
        )
        qual = ~too_fine & ~has_refining
        idx, parents = idx[qual], parents[qual]
        if len(idx):
            # first-requested sibling per family wins (np.unique's
            # return_index is the first occurrence in input order)
            _u, first = np.unique(parents, return_index=True)
            self.amr.to_unrefine.update(
                int(c) for c in ids[idx[np.sort(first)]]
            )
        return out

    def dont_unrefine_many(self, cells) -> np.ndarray:
        """Vectorized ``dont_unrefine``; engages when no unrefines are
        pending (nothing to discard), else scalar fallback."""
        ids = np.asarray(cells, dtype=np.uint64).reshape(-1)
        if len(ids) == 0:
            return np.zeros(0, dtype=bool)
        if self.amr.to_unrefine:
            return np.array(
                [self.dont_unrefine(int(c)) for c in ids], dtype=bool
            )
        pos = self.leaves.position(ids)
        exists = pos >= 0
        lvl = np.where(exists, self.mapping.get_refinement_level(ids), 0)
        idx = np.flatnonzero(exists & (lvl > 0))
        if len(idx):
            parents = self.mapping.get_parent(ids[idx])
            if self.amr.not_to_unrefine:
                ntu = self._set_array(self.amr.not_to_unrefine)
                vetoed_parents = np.unique(self.mapping.get_parent(ntu))
                fresh = ~np.isin(parents, vetoed_parents)
                idx, parents = idx[fresh], parents[fresh]
            if len(idx):
                _u, first = np.unique(parents, return_index=True)
                self.amr.not_to_unrefine.update(
                    int(c) for c in ids[idx[np.sort(first)]]
                )
        return exists

    def dont_refine_many(self, cells) -> np.ndarray:
        """Vectorized ``dont_refine`` (always exact: discard + add)."""
        ids = np.asarray(cells, dtype=np.uint64).reshape(-1)
        if len(ids) == 0:
            return np.zeros(0, dtype=bool)
        pos = self.leaves.position(ids)
        exists = pos >= 0
        lvl = self.mapping.get_refinement_level(ids)
        mid = exists & (lvl < self.mapping.max_refinement_level)
        mids = [int(c) for c in ids[mid]]
        self.amr.to_refine.difference_update(mids)
        self.amr.not_to_refine.update(mids)
        return exists

    def _unrefine_parent_info_many(self, parents, tr_arr=None):
        """Vectorized ``_unrefine_parent_info`` over a parent array:
        (too_fine, same-level-neighbor-being-refined) per parent from
        the per-epoch cache.  ``tr_arr``: the caller's to_refine array
        (one conversion per storm)."""
        _, cp, too_fine_all, fcells, fstart = self._build_unrefine_cache()
        i = np.searchsorted(cp, parents)
        ic = np.minimum(i, max(len(cp) - 1, 0))
        found = (i < len(cp)) & (len(cp) > 0)
        if len(cp):
            found &= cp[ic] == parents
        too_fine = np.where(found, too_fine_all[ic] if len(cp) else True,
                            True)
        if tr_arr is None and self.amr.to_refine:
            tr_arr = self._set_array(self.amr.to_refine)
        if tr_arr is not None and len(tr_arr) and len(fcells):
            hit = np.isin(fcells, tr_arr).astype(np.int64)
            csum = np.concatenate(([0], np.cumsum(hit)))
            seg = (csum[fstart[1:]] - csum[fstart[:-1]]) > 0
            has_ref = np.where(found, seg[ic] if len(cp) else False, False)
        else:
            has_ref = np.zeros(len(parents), dtype=bool)
        return too_fine, has_ref

    def refine_completely_at(self, coords) -> bool:
        c = self._cell_at(coords)
        return bool(c) and self.refine_completely(c)

    def unrefine_completely_at(self, coords) -> bool:
        c = self._cell_at(coords)
        return bool(c) and self.unrefine_completely(c)

    def dont_refine_at(self, coords) -> bool:
        c = self._cell_at(coords)
        return bool(c) and self.dont_refine(c)

    def dont_unrefine_at(self, coords) -> bool:
        c = self._cell_at(coords)
        return bool(c) and self.dont_unrefine(c)

    def _cell_at(self, coords) -> int:
        for lvl in range(self.mapping.max_refinement_level, -1, -1):
            c = self.geometry.get_cell(lvl, np.asarray(coords, dtype=np.float64))
            if int(c) and bool(self.leaves.exists(np.uint64(c))):
                return int(c)
        return 0

    def get_existing_cell(self, coords) -> np.ndarray:
        """Existing leaf containing each coordinate (vectorized; 0 for
        outside) — reference ``get_existing_cell`` (``dccrg.hpp:6316``)."""
        coords = np.atleast_2d(np.asarray(coords, dtype=np.float64))
        out = np.zeros(len(coords), dtype=np.uint64)
        unresolved = np.ones(len(coords), dtype=bool)
        for lvl in range(self.mapping.max_refinement_level, -1, -1):
            if not unresolved.any():
                break
            ids = self.geometry.get_cell(lvl, coords[unresolved])
            exists = self.leaves.exists(ids)
            idx = np.flatnonzero(unresolved)
            out[idx[exists]] = ids[exists]
            unresolved[idx[exists]] = False
        return out

    def stop_refining(self, sorted: bool = True, presynced: bool = False) -> np.ndarray:
        """Commit all queued refines/unrefines (veto -> induce -> override
        -> execute, reference ``dccrg.hpp:3461-3485``); returns the new
        cells.  Payload states allocated before this call must be carried
        over with ``remap_state``.  ``presynced`` skips the multi-controller
        queue union for callers that already ran ``sync_adaptation``."""
        self._assert_no_staged_lb()
        self._assert_initialized()
        from .amr.refinement import commit_adaptation
        from .utils.collectives import sync_adaptation

        # multi-controller agreement: every process commits the union of
        # all processes' queued requests (identity under one controller)
        from .obs import metrics

        with self._span_ctx(), metrics.phase("amr.refine"):
            if not presynced:
                sync_adaptation(self.amr)
            old_epoch = self.epoch
            new_cells, removed, delta = commit_adaptation(self)
            self._last_new_cells = new_cells
            self._last_removed_cells = removed
            self._last_adaptation_delta = delta
            if not len(new_cells) and not len(removed):
                # nothing changed (nothing queued, or everything vetoed):
                # the leaf set was left untouched, keep the current epoch
                # and every derived table instead of paying a full rebuild
                self._prev_epoch = None
                return new_cells.copy()
            self._rebuild_incremental(old_epoch)
            self._prev_epoch = _EpochCarry(old_epoch)
            self._harvest_tables(old_epoch)
        return new_cells.copy()

    def get_removed_cells(self) -> np.ndarray:
        """Cells removed by the last ``stop_refining`` (their parents are
        now leaves) — reference ``dccrg.hpp:3488-3520``."""
        return self._last_removed_cells.copy()

    def get_last_adaptation_delta(self):
        """The complete touched set of the last AMR commit
        (``amr.refinement.AdaptationDelta``: every id added to / removed
        from the leaf set, including refined parents and new unrefinement
        parents) — the seed the incremental epoch rebuild patches
        around.  None before the first commit."""
        return getattr(self, "_last_adaptation_delta", None)

    def release_prev_epoch(self) -> None:
        """Drop the retained pre-change carry without remapping any
        payload — for callers with no state to carry across the last
        structural change that want the host memory back immediately.
        ``remap_state`` becomes the identity until the next change."""
        self._prev_epoch = None

    def remap_state(self, state, policy=None):
        """Carry a payload state across the last structural change.

        Surviving cells keep their values.  Per-field ``policy`` entries
        control the rest: ``refine`` — how children get values from their
        refined parent ("inherit" default, or "zero"); ``unrefine`` — how a
        new parent reduces its removed children ("mean" default, "sum", or
        "zero").  This is the array-level form of the reference pattern of
        reading parent/child data after stop_refining
        (tests/advection/adapter.hpp:230-292).

        Memory note: only a slim carry of the old epoch (leaf directory +
        row assignment) is retained across a structural change — the old
        hood tables are freed eagerly at rebuild time.  The carry stays
        so further payloads can be remapped; call ``release_prev_epoch``
        once every payload is across to drop it too.
        """
        if self._prev_epoch is None or self._prev_epoch is self.epoch:
            # no structural change (e.g. a no-move balance_load): identity
            return state
        old, new = self._prev_epoch, self.epoch
        policy = policy or {}
        out = {}
        old_cells = old.leaves.cells
        new_cells = new.leaves.cells

        # classification of new leaves
        surv_pos_new = np.flatnonzero(old.leaves.exists(new_cells))
        fresh_pos_new = np.flatnonzero(~old.leaves.exists(new_cells))
        fresh = new_cells[fresh_pos_new]
        fresh_lvl = self.mapping.get_refinement_level(fresh)
        parents_of_fresh = self.mapping.get_parent(fresh)
        # children created by refinement: their parent was an old leaf
        is_child = old.leaves.exists(parents_of_fresh) & (fresh_lvl > 0)
        # new parents from unrefinement: their children were old leaves
        first_child = self.mapping.get_all_children(fresh)[:, 0]
        is_parent = np.where(
            fresh_lvl < self.mapping.max_refinement_level,
            old.leaves.exists(first_child),
            False,
        ) & ~is_child

        for name, arr in state.items():
            host_old = fetch(arr, dtype=arr.dtype)
            if host_old.ndim < 2 or host_old.shape[:2] != (
                old.n_devices, old.R
            ):
                # not a per-cell [D, R, ...] payload (e.g. a global
                # counter like the particles' overflow scalar) — carry
                # it through unchanged
                out[name] = arr
                continue
            field_shape = host_old.shape[2:]
            host_new = np.zeros((new.n_devices, new.R) + field_shape, host_old.dtype)
            pol = policy.get(name, {})

            def read(ids):
                pos = old.leaves.position(ids)
                dev = old.leaves.owner[pos]
                row = old.row_of[pos]
                return host_old[dev, row]

            def write(ids, values):
                pos = new.leaves.position(ids)
                dev = new.leaves.owner[pos]
                row = new.row_of[pos]
                host_new[dev, row] = values

            surv = new_cells[surv_pos_new]
            write(surv, read(surv))

            children = fresh[is_child]
            if len(children):
                if pol.get("refine", "inherit") == "inherit":
                    write(children, read(parents_of_fresh[is_child]))

            parents = fresh[is_parent]
            if len(parents):
                how = pol.get("unrefine", "mean")
                if how in ("mean", "sum"):
                    fam = self.mapping.get_all_children(parents)  # (M, 8)
                    vals = read(fam.reshape(-1)).reshape((len(parents), 8) + field_shape)
                    red = vals.sum(axis=1)
                    if how == "mean":
                        red = red / 8 if np.issubdtype(red.dtype, np.floating) else red // 8
                    write(parents, red.astype(host_old.dtype))

            out[name] = jax.device_put(
                jnp.asarray(host_new), shard_spec(self.mesh, host_new.ndim)
            )
        return out

    # ------------------------------------------------------------------- IO

    def save_grid_data(self, state, path: str, spec, user_header: bytes = b"",
                       ragged=None, version: int | None = None):
        """Checkpoint grid structure + payloads (reference
        ``save_grid_data``, ``dccrg.hpp:1089-1716``).  ``ragged`` maps a
        variable-size field to its count field: only ``count[i]`` rows are
        written per cell.  ``version=1`` writes the legacy CRC-less
        layout (default: the hardened v2 format)."""
        from .io.checkpoint import CHECKPOINT_VERSION
        from .io.checkpoint import save_grid_data as _save

        with self._span_ctx():
            _save(self, state, path, spec, user_header, ragged=ragged,
                  version=CHECKPOINT_VERSION if version is None else version)

    @staticmethod
    def load_grid_data(path: str, spec, mesh=None, n_devices=None, ragged=None,
                       on_error: str = "raise"):
        """Recreate a saved grid on the current devices; any device count
        works (reference ``load_grid_data``, ``dccrg.hpp:1742-2404``).
        Returns (grid, state, user_header); a torn or corrupt file raises
        :class:`~dccrg_tpu.io.checkpoint.CheckpointError` naming the
        failing section.  ``on_error="salvage"`` instead recovers every
        intact cell and returns ``(grid, state, user_header,
        lost_cells)``."""
        from .io.checkpoint import load_grid_data as _load

        return _load(path, spec, ragged=ragged, mesh=mesh,
                     n_devices=n_devices, on_error=on_error)

    @staticmethod
    def start_loading_grid_data(path: str, spec, mesh=None, n_devices=None,
                                ragged=None, on_error: str = "raise"):
        """Chunked load: returns a loader; call
        ``loader.continue_loading_grid_data(max_cells)`` until it returns
        False, then ``loader.finish_loading_grid_data()`` (reference
        ``dccrg.hpp:1742-2404``)."""
        from .io.checkpoint import start_loading_grid_data as _start

        return _start(path, spec, ragged=ragged, mesh=mesh,
                      n_devices=n_devices, on_error=on_error)

    def save_checkpoint(self, state, directory: str, spec, keep: int = 3,
                        user_header: bytes = b"", ragged=None) -> int:
        """Commit one generation into a crash-safe checkpoint lineage
        (``resilience/manager.py``): fsync'd atomic write, checksummed
        MANIFEST, oldest generations beyond ``keep`` rotated out.
        Returns the committed generation number."""
        from .resilience.manager import CheckpointLineage

        return CheckpointLineage(directory, keep=keep).commit(
            self, state, spec, user_header=user_header, ragged=ragged
        )

    @staticmethod
    def resume_latest(directory: str, spec, mesh=None, n_devices=None,
                      ragged=None, verify: bool = True):
        """Resume from the newest VALID generation in a lineage
        directory, scanning back past torn/corrupt ones and re-verifying
        the restored grid with ``utils.verify.verify_grid``.  Returns
        ``(grid, state, user_header, generation)``; raises
        :class:`~dccrg_tpu.io.checkpoint.CheckpointError` when nothing
        in the lineage is recoverable."""
        from .resilience.manager import CheckpointLineage

        return CheckpointLineage(directory).latest_valid(
            spec, mesh=mesh, n_devices=n_devices, ragged=ragged,
            verify=verify,
        )

    def write_vtk_file(self, path: str, scalars: dict | None = None,
                       binary: bool = True):
        """Dump leaf-cell geometry (+ optional scalars) as legacy VTK
        (reference ``dccrg.hpp:3298-3370``); BINARY encoding by default,
        ``binary=False`` for eyeball-readable ASCII."""
        from .io.vtk import write_vtk_file as _vtk

        _vtk(self, path, scalars, binary=binary)

    # -------------------------------------------------------- introspection

    @property
    def telemetry(self):
        """The process-wide metrics registry (``obs.metrics``) — the
        statistics accessor in dccrg's getter style.  Use
        ``grid.telemetry.report()`` for a raw snapshot, ``grid.report()``
        for the snapshot annotated with this grid's shape."""
        from .obs import metrics

        return metrics

    @property
    def events(self):
        """The process-wide event timeline (``obs.timeline``): the
        individual begin/end spans behind the aggregate phase timers.
        Export with ``obs.export_chrome_trace(path)`` for perfetto."""
        from .obs import timeline

        return timeline

    def report(self) -> dict:
        """Telemetry snapshot (phases, counters, gauges, histograms from
        every instrumented seam) plus this grid's current shape and the
        event-timeline fill state.  The same structure
        ``obs.export_json`` writes to ``telemetry.json``."""
        from .obs import metrics, timeline

        rep = metrics.report()
        rep["events"] = timeline.summary()
        if self.initialized:
            rep["grid"] = {
                "grid_id": int(self.grid_id),
                "n_cells": int(len(self.leaves)),
                "n_devices": int(self.n_devices),
                "rows_per_device": int(self.epoch.R),
                "ghost_cells": int(self.epoch.n_ghost.sum()),
                "neighborhoods": len(self.neighborhoods),
                "max_refinement_level": int(
                    self.mapping.max_refinement_level
                ),
            }
        return rep

    def get_number_of_update_send_cells(self, device: int, hood_id=None) -> int:
        return int(self.epoch.hoods[hood_id].pair_counts[device].sum())

    def get_number_of_update_receive_cells(self, device: int, hood_id=None) -> int:
        return int(self.epoch.hoods[hood_id].pair_counts[:, device].sum())


class _EpochCarry:
    """Slim view of a pre-change epoch: exactly what ``remap_state``
    needs to carry payloads across a structural change (the old leaf
    directory, row assignment and row budget).  Retaining this instead
    of the full ``Epoch`` frees the old hood tables — the ``[D, R,
    Kmax]`` gather tables and send/recv schedules, i.e. the bulk of a
    second epoch's host memory — eagerly at rebuild time instead of
    holding them until the next structural change."""

    __slots__ = ("leaves", "row_of", "n_devices", "R")

    def __init__(self, epoch):
        self.leaves = epoch.leaves
        self.row_of = epoch.row_of
        self.n_devices = epoch.n_devices
        self.R = epoch.R


class _SubGridView:
    """Minimal grid-shaped view over a subset of leaves, for hierarchical
    partitioning."""

    def __init__(self, grid, idx):
        from .core.neighbors import LeafSet

        self.mapping = grid.mapping
        self.geometry = grid.geometry
        self.leaves = LeafSet(
            cells=grid.leaves.cells[idx], owner=grid.leaves.owner[idx]
        )


def _face_direction(off, own_len: int, nbr_len: int) -> int:
    """Classify a neighbor-list offset as a face direction (0 = not a face
    neighbor), following the advection workload's offset logic
    (reference tests/advection/solve.hpp:71-123)."""
    ox, oy, oz = (int(v) for v in off)
    span = nbr_len
    for axis, o in ((1, ox), (2, oy), (3, oz)):
        others = [v for a, v in ((1, ox), (2, oy), (3, oz)) if a != axis]
        # face contact on the negative side: neighbor ends where cell begins
        if o == -nbr_len and all(-nbr_len < v < own_len for v in others):
            return -axis
        if o == own_len and all(-nbr_len < v < own_len for v in others):
            return axis
    return 0
