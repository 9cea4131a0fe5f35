"""Static-offset decomposition of the general stencil matvec.

The gather-path operator

    (A·x)[r] = scaling[r]·x[r] + Σ_k mult[r, k] · x[nbr_rows[r, k]]

has completely static structure: ``nbr_rows`` and ``mult`` are epoch
constants (the TPU analogue of the reference's cached neighbor pointer
lists + per-pair factors, ``poisson_solve.hpp:716-965``).  XLA's TPU
lowering of the ``[R, K]`` row gather is the one measured loss in the
benchmark suite (7.05e6 cell-iters/s on chip vs 52.7e6 on the CPU
denominator, round-3 chip run), so this module removes the gather:

Group the nonzero entries by their ROW OFFSET ``d = nbr_rows[r,k] - r``.
All entries sharing an offset collapse into one dense term

    W_d[r] · roll(x, -d)        with  W_d[r] = Σ_k mult[r, k]·[d_{rk} = d]

— a shifted multiply-add the TPU streams at HBM bandwidth.  This is the
flat voxel path's six-roll trick generalized to ANY static sparsity:
leaves sit in id order, so face neighbors concentrate on a handful of
offsets (±x/±y/±z strides per refinement region) and the offset
histogram is short.  Rare offsets (deep-AMR cross-level jumps,
periodic wraps) fall into a small static-COO exception term

    y[exc_r] += exc_w · x[exc_idx]

handled by one tiny gather + scatter-add.  When the histogram is too
flat for the decomposition to pay (``None`` return), callers keep the
general gather path.

Traffic per apply ≈ (2·T + 2)·R·itemsize for T dense terms, vs the
reference-shaped AoS walk's pointer-chasing — and vs the TPU gather
lowering's scalarized element loop this replaces.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["build_rolled_matvec", "make_rolled_apply",
           "build_rolled_matvec_multi", "make_rolled_apply_multi"]

#: build_rolled_matvec defaults; exposed for tests and calibration.
#: A dense term streams 2·R·itemsize per apply regardless of how many
#: entries it covers, while an exception costs per ENTRY — so a head of
#: ≤64 offsets plus a ≤15% exception tail (the shape measured on the
#: refined-ball bench config: 73% of entries on 16 offsets, 90% on 64)
#: still replaces ~90% of the scalarized gather work with streamed
#: shifted multiply-adds.
MAX_TERMS = 64
MIN_COUNT_FRAC = 0.004
MAX_EXC_FRAC = 0.15


def build_rolled_matvec(nbr_rows, mult, scaling, *, max_terms=MAX_TERMS,
                        min_count_frac=MIN_COUNT_FRAC,
                        max_exc_frac=MAX_EXC_FRAC):
    """Static tables for the rolled matvec, or None when the offset
    histogram is too flat to beat the gather.

    ``nbr_rows``: (R, K) int — neighbor row per (row, slot), any value
    for entries whose ``mult`` is zero (they are dropped).
    ``mult``: (R, K) float — per-entry multipliers, zeros for missing /
    inactive entries.  ``scaling``: (R,) float — the diagonal.

    Returns ``{"offsets", "weights" (T, R), "exc_r", "exc_idx",
    "exc_w", "scaling"}`` (all numpy; ``make_rolled_apply`` moves them
    to device).
    """
    nbr_rows = np.asarray(nbr_rows)
    mult = np.asarray(mult)
    scaling = np.asarray(scaling)
    R, K = nbr_rows.shape
    if R == 0:
        return None

    rr, kk = np.nonzero(mult)
    if rr.size == 0:
        return {  # pure-diagonal system: zero dense terms, no exceptions
            "offsets": [], "weights": np.zeros((0, R), mult.dtype),
            "exc_r": np.zeros(0, np.int32), "exc_idx": np.zeros(0, np.int32),
            "exc_w": np.zeros(0, mult.dtype), "scaling": scaling,
        }
    idx = nbr_rows[rr, kk].astype(np.int64)
    ww = mult[rr, kk]
    d = idx - rr

    offs, inv, counts = np.unique(d, return_inverse=True,
                                  return_counts=True)
    order = np.argsort(counts)[::-1]
    min_count = max(1, int(min_count_frac * R))
    dense_o = [o for o in order[:max_terms] if counts[o] >= min_count]
    dense_set = np.zeros(len(offs), dtype=bool)
    dense_set[dense_o] = True

    is_dense = dense_set[inv]
    n_exc = int((~is_dense).sum())
    if n_exc > max_exc_frac * rr.size:
        return None

    # rank dense terms by offset value: deterministic order -> the
    # unrolled roll chain (and therefore fp association) is stable
    # across builds of the same structure
    dense_sorted = sorted(dense_o, key=lambda o: int(offs[o]))
    T = len(dense_sorted)
    weights = np.zeros((T, R), dtype=mult.dtype)
    t_of = np.full(len(offs), -1)
    t_of[dense_sorted] = np.arange(T)
    t_of_entry = t_of[inv]
    m = is_dense
    np.add.at(weights, (t_of_entry[m], rr[m]), ww[m])

    e = ~is_dense
    # sort exceptions by source index: the residual gather walks x
    # monotonically (and the scatter-add association becomes a stable
    # function of the structure, not of np.nonzero's entry order)
    eo = np.lexsort((rr[e], idx[e]))
    return {
        "offsets": [int(offs[o]) for o in dense_sorted],
        "weights": weights,
        "exc_r": rr[e][eo].astype(np.int32),
        "exc_idx": idx[e][eo].astype(np.int32),
        "exc_w": ww[e][eo],
        "scaling": scaling,
    }


def make_rolled_apply(tables, dtype):
    """Jittable ``apply(x: [R]) -> [R]`` from ``build_rolled_matvec``
    tables.  The ≤ ``max_terms`` roll chain unrolls at trace time; the
    exception term is one small static-index gather + scatter-add."""
    offsets = tables["offsets"]
    weights = jnp.asarray(tables["weights"], dtype)
    scaling = jnp.asarray(tables["scaling"], dtype)
    has_exc = tables["exc_r"].size > 0
    if has_exc:
        exc_r = jnp.asarray(tables["exc_r"])
        exc_idx = jnp.asarray(tables["exc_idx"])
        exc_w = jnp.asarray(tables["exc_w"], dtype)

    def apply(x):
        y = scaling * x
        for t, o in enumerate(offsets):
            y = y + weights[t] * jnp.roll(x, -o)
        if has_exc:
            y = y.at[exc_r].add(exc_w * x[exc_idx])
        return y

    return apply


def build_rolled_matvec_multi(nbr_rows, mult, scaling, *,
                              max_terms=MAX_TERMS,
                              min_count_frac=MIN_COUNT_FRAC,
                              max_exc_frac=MAX_EXC_FRAC):
    """Sharded-mesh variant: per-device decompositions with a UNION
    offset set, or None when any device's histogram refuses.

    ``nbr_rows``/``mult``: (D, R, K); ``scaling``: (D, R).  Each
    device's row block is its own roll space (local + ghost + scratch
    rows, ghost values refreshed by the halo exchange before the
    apply, same as the gather path).  Roll amounts must be trace-time
    constants shared across devices, so the union of the per-device
    offset heads becomes the term list and a device missing an offset
    carries zero weights for it.  Exception lists are right-padded per
    device with zero-weight entries pointing at row 0.

    Returns ``{"offsets", "weights" (D, T, R), "exc_r"/"exc_idx"
    (D, E), "exc_w" (D, E), "scaling" (D, R)}``.
    """
    nbr_rows = np.asarray(nbr_rows)
    mult = np.asarray(mult)
    scaling = np.asarray(scaling)
    D, R, K = nbr_rows.shape
    per_dev = []
    for d in range(D):
        t = build_rolled_matvec(
            nbr_rows[d], mult[d], scaling[d], max_terms=max_terms,
            min_count_frac=min_count_frac, max_exc_frac=max_exc_frac)
        if t is None:
            return None
        per_dev.append(t)

    union = sorted({o for t in per_dev for o in t["offsets"]})
    if len(union) > 2 * max_terms:  # union blow-up across devices
        return None
    slot = {o: i for i, o in enumerate(union)}
    T = len(union)
    weights = np.zeros((D, T, R), dtype=mult.dtype)
    for d, t in enumerate(per_dev):
        for i, o in enumerate(t["offsets"]):
            weights[d, slot[o]] = t["weights"][i]

    E = max((t["exc_r"].size for t in per_dev), default=0)
    exc_r = np.zeros((D, E), np.int32)
    exc_idx = np.zeros((D, E), np.int32)
    exc_w = np.zeros((D, E), dtype=mult.dtype)
    for d, t in enumerate(per_dev):
        n = t["exc_r"].size
        exc_r[d, :n] = t["exc_r"]
        exc_idx[d, :n] = t["exc_idx"]
        exc_w[d, :n] = t["exc_w"]

    return {"offsets": union, "weights": weights, "exc_r": exc_r,
            "exc_idx": exc_idx, "exc_w": exc_w, "scaling": scaling}


def make_rolled_apply_multi(tables, dtype, mesh=None):
    """Jittable ``apply(x: [D, R]) -> [D, R]`` from
    ``build_rolled_matvec_multi`` tables.  Every op is device-local
    under the leading-axis sharding — per-device rolls along the row
    axis, elementwise weight multiplies, and a per-device batched
    exception gather/scatter-add — so XLA inserts no collectives
    (ghost refresh happens in the caller's halo exchange, exactly as
    on the gather path)."""
    if mesh is not None:
        from ..parallel.mesh import put_table

        put = lambda a, dt=None: put_table(a, mesh, dt)
    else:
        put = lambda a, dt=None: jnp.asarray(a, dt)
    offsets = tables["offsets"]
    weights = put(tables["weights"], dtype)
    scaling = put(tables["scaling"], dtype)
    has_exc = tables["exc_r"].shape[1] > 0
    if has_exc:
        exc_r = put(tables["exc_r"])
        exc_idx = put(tables["exc_idx"])
        exc_w = put(tables["exc_w"], dtype)
    D = tables["weights"].shape[0]
    didx = jnp.arange(D)[:, None]

    def apply(x):
        y = scaling * x
        for t, o in enumerate(offsets):
            y = y + weights[:, t] * jnp.roll(x, -o, axis=1)
        if has_exc:
            y = y.at[didx, exc_r].add(exc_w * x[didx, exc_idx])
        return y

    return apply
