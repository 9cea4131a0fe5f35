"""Pallas async-DMA halo transport: device-initiated ring copies.

The collective halo engine (``parallel/halo.py``) ships each ring step's
packed payload with one ``lax.ppermute`` — host-orchestrated collective
dispatch that XLA's latency-hiding scheduler *may* overlap with unrelated
compute.  This module provides the device-side alternative: per ring
distance ``k``, a Pallas kernel issues an asynchronous remote copy
(``pltpu.make_async_remote_copy``) of the packed ``[S_k, ...]`` payload
straight to logical device ``(d + k) % D`` over the interconnect, with
the send/receive DMA semaphores living in kernel scratch.  The kernel is
pure data movement — no arithmetic — so ghost copies remain bit-exact,
and the payload gather/scatter stays OUTSIDE the kernel on the existing
runtime-argument send/recv tables, which is what lets the compiled
bodies key cleanly on a :class:`~dccrg_tpu.parallel.shapes.ShapeSignature`
and survive epoch rebuilds in the executable cache.

Backend selection (``DCCRG_HALO_BACKEND``):

* ``collective`` — the ``ppermute`` ring schedule (always available, and
  the bit-identity oracle for everything else);
* ``pallas`` — the DMA ring bodies; on non-TPU backends the same kernels
  run under ``interpret=True`` (jax's interpreter emulates the remote
  DMA with collectives), so CI exercises the full integration path;
* ``auto`` (default) — ``pallas`` on TPU backends where Pallas is
  importable, ``collective`` everywhere else.

``DCCRG_HALO_VERIFY=1`` makes every pallas-backend exchange replay on the
collective oracle and compare bit-for-bit (see
``HaloExchange._verify_oracle``); mismatches are counted, never raised.

Split start/wait: each ring kernel starts *and* waits its copy; the
split-phase structure — interior compute issued with no data dependence
on the in-flight payload, the ghost-row scatter as the wait — lives at
the composed-program level exactly as it does for the collective
transport, which keeps the two backends drop-in interchangeable inside
the fused split-phase model steps.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mesh import SHARD_AXIS

__all__ = [
    "BACKENDS",
    "interpret_mode",
    "resolve_backend",
    "ring_dma_start",
    "verify_enabled",
]

#: legal DCCRG_HALO_BACKEND values
BACKENDS = ("collective", "pallas", "auto")


def interpret_mode() -> bool:
    """Whether DMA kernels must run under the Pallas interpreter: every
    backend except a real TPU (the interpreter emulates the remote copy
    with collectives, so CPU/CI runs the same kernel code)."""
    try:
        return jax.default_backend() != "tpu"
    except Exception:  # noqa: BLE001 — no backend at all
        return True


def _env_backend() -> str:
    v = os.environ.get("DCCRG_HALO_BACKEND", "auto").strip().lower()
    if not v:
        return "auto"
    if v not in BACKENDS:
        raise ValueError(
            f"DCCRG_HALO_BACKEND={v!r}: expected one of {BACKENDS}"
        )
    return v


def resolve_backend() -> str:
    """The transport a new halo schedule should compile: the env choice,
    with ``auto`` meaning pallas on TPU and collective everywhere else."""
    env = _env_backend()
    if env == "auto":
        return "collective" if interpret_mode() else "pallas"
    return env


def verify_enabled() -> bool:
    """Whether every non-collective exchange cross-checks against the
    collective oracle (``DCCRG_HALO_VERIFY=1``)."""
    return os.environ.get("DCCRG_HALO_VERIFY", "0").lower() not in (
        "", "0", "false", "no",
    )


# ----------------------------------------------------------- kernels


def _dma_kernel(in_ref, out_ref, send_sem, recv_sem, *, k: int, D: int):
    """One ring step's transfer: ship this device's packed payload to
    logical device ``(d + k) % D``.  By SPMD symmetry device
    ``(d - k) % D`` is simultaneously shipping ours; ``wait`` blocks on
    both semaphores (send drained, receive landed), so the kernel's
    output ref holds the incoming payload on return."""
    me = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int32)
    dst = jax.lax.rem(me + jnp.int32(k), jnp.int32(D))
    rdma = pltpu.make_async_remote_copy(
        src_ref=in_ref,
        dst_ref=out_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=dst,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    rdma.wait()


def ring_copy(payload, k: int, D: int, *, interpret: bool):
    """DMA-ship one ring step's packed ``[S_k, ...]`` payload to device
    ``(d + k) % D``; returns the payload received from ``(d - k) % D``
    (the exact ``ppermute`` contract).  Must run inside a ``shard_map``
    body over :data:`SHARD_AXIS`."""
    space = pl.ANY
    sem = pltpu.SemaphoreType.DMA
    return pl.pallas_call(
        functools.partial(_dma_kernel, k=k, D=D),
        out_shape=jax.ShapeDtypeStruct(payload.shape, payload.dtype),
        in_specs=[pl.BlockSpec(memory_space=space)],
        out_specs=pl.BlockSpec(memory_space=space),
        scratch_shapes=[sem, sem],
        interpret=interpret,
    )(payload)


def ring_dma_start(blk, ks, D: int, send_tabs, *, interpret: bool):
    """Inside a shard_map body: gather and DMA-dispatch every ring
    step's payload for this device's ``[R, ...]`` block; returns the
    per-ring-distance ``[S_k, ...]`` payloads.  The drop-in DMA form of
    ``HaloExchange.ring_start`` — same named-scope stamps
    (``halo.ring.k<k>.start``), so a profiler capture names both
    transports' ops alike."""
    out = []
    for k, sr in zip(ks, send_tabs):
        with jax.named_scope(f"halo.ring.k{k}.start"):
            out.append(ring_copy(blk[sr], int(k), D, interpret=interpret))
    return out
