"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone, into a small dict:

    {"devices": {plane: [[op, start_ns, end_ns, module], ...]},
     "host": [[span, start_ns, end_ns], ...]}

``devices`` holds, per device plane, the events of its ``XLA Ops`` line
(named by ``short_name``) with the HLO module each ran in, from the plane's
``XLA Modules`` line; ``host`` holds the harness's own
``TraceAnnotation`` spans (``loop``, ``dispatch``, ``readback``).  The rest
is interval arithmetic on that dict, kept here so that every PR computes
the same numbers the same way.
"""
from __future__ import annotations

import glob
import os
import re

#: host spans the harness writes around each call of the window
HOST_SPANS = ("loop", "dispatch", "readback")
#: modules of the harness's own jitted functions (density sum, snapshot):
#: device time that is not the program's step
HARNESS_MODULE = re.compile(r"bench_")
#: ops that move halo data between chips: XLA's collective permutes (the
#: ``ppermute`` of the dense and boxed paths) and the async-DMA ring's
#: remote copies (``parallel/halo_dma.py``)
HALO_OP = re.compile(r"collective-permute|ppermute|remote[-_]copy|ring_dma",
                     re.IGNORECASE)


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def find_xplane(directory: str) -> str | None:
    """The newest ``.xplane.pb`` under ``directory``."""
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def short_name(hlo: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``%fusion.2 fusion``; a
    custom call keeps its target."""
    m = re.match(r"(%?[\w.\-]+) = .*?\b([a-z][\w\-]*)\(", hlo)
    if not m:
        return hlo[:80]
    name = f"{m.group(1)} {m.group(2)}"
    t = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name} {t.group(1)}" if t else name


def _module_of(ops, modules):
    """Label each op with the module whose interval holds its start."""
    j = 0
    for op in ops:
        while j < len(modules) and modules[j][2] <= op[1]:
            j += 1
        if j < len(modules) and modules[j][1] <= op[1]:
            op[3] = modules[j][0]


def load(path: str) -> dict:
    """Reduce one ``.xplane.pb`` file (see the module's docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for e in line.events:
                    start = float(e.start_ns)
                    ev = [short_name(e.name), start,
                          start + float(e.duration_ns), ""]
                    (ops if line.name == "XLA Ops" else modules).append(ev)
            ops.sort(key=lambda o: o[1])
            modules.sort(key=lambda m: m[1])
            _module_of(ops, modules)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        start = float(e.start_ns)
                        host.append([e.name, start,
                                     start + float(e.duration_ns)])
    host.sort(key=lambda s: s[1])
    return {"devices": devices, "host": host}


# ----------------------------------------------------- interval arithmetic


def union(intervals):
    """Sorted, merged list of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo, hi):
    """Merged intervals cut to [lo, hi]."""
    return [[max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi]


def subtract(a, b):
    """Merged ``a`` minus merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# --------------------------------------------------------------- windows


def window(reduced: dict):
    """(start_ns, end_ns) of the traced stretch: from the first host
    ``loop`` span to the end of the last, else the device ops' extent."""
    loops = [s for s in reduced["host"] if s[0] == "loop"]
    if loops:
        return loops[0][1], max(s[2] for s in loops)
    ops = [o for v in reduced["devices"].values() for o in v]
    if not ops:
        return None
    return min(o[1] for o in ops), max(o[2] for o in ops)


def leaves(ops):
    """The ops that hold no other op: a loop's or a call's event spans
    the ops it runs, and is left out where ops are told apart by kind."""
    return [o for i, o in enumerate(ops)
            if not (i + 1 < len(ops) and ops[i + 1][1] < o[2])]


def is_step(op) -> bool:
    """An op of the program's step, not of the harness's own functions."""
    return not HARNESS_MODULE.search(op[3])


def is_halo(op) -> bool:
    return bool(HALO_OP.search(op[0]))


def per_device(reduced: dict, select=None, leaf=False):
    """{plane: merged intervals of its ops (of its leaf ops with ``leaf``)
    that ``select`` keeps, clipped to the traced window}."""
    w = window(reduced)
    if w is None:
        return {}
    out = {}
    for plane, ops in reduced["devices"].items():
        if leaf:
            ops = leaves(ops)
        keep = [(o[1], o[2]) for o in ops if select is None or select(o)]
        out[plane] = clip(union(keep), *w)
    return out


def busy_s(reduced: dict):
    """Seconds in which some op ran, averaged over the device planes."""
    busy = per_device(reduced)
    if not busy:
        return None
    return sum(measure(v) for v in busy.values()) / len(busy) / 1e9


def window_s(reduced: dict):
    w = window(reduced)
    return None if w is None else (w[1] - w[0]) / 1e9


def step_s(reduced: dict):
    """{plane: seconds the program's step ops ran}."""
    return {p: measure(v) / 1e9
            for p, v in per_device(reduced, is_step).items()}


def exposed_halo_s(reduced: dict):
    """{plane: seconds in which a halo op ran and no other leaf op did}."""
    halo = per_device(reduced, is_halo, leaf=True)
    other = per_device(reduced, lambda o: not is_halo(o), leaf=True)
    return {p: measure(subtract(halo[p], other[p])) / 1e9 for p in halo}


def top_ops(reduced: dict, n: int = 10):
    """[[op, seconds]] of the ``n`` ops that took most device time,
    averaged over the device planes.  An op that holds others (a loop)
    is left out, so no time counts twice."""
    w = window(reduced)
    if w is None or not reduced["devices"]:
        return []
    tot = {}
    for ops in reduced["devices"].values():
        for name, s, e, _ in leaves(ops):
            d = min(e, w[1]) - max(s, w[0])
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d
    nd = len(reduced["devices"])
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def idle_gaps(reduced: dict, n: int = 10):
    """[[what the host was doing, seconds]]: device idle time inside the
    traced window, attributed to the innermost harness span open at each
    gap's midpoint (``outside`` when none), summed per span name and
    averaged over the device planes, the ``n`` largest."""
    w = window(reduced)
    busy = per_device(reduced)
    if w is None or not busy:
        return []
    spans = reduced["host"]
    depth = {"loop": 0, "dispatch": 1, "readback": 1}
    tot, cnt = {}, {}
    for merged in busy.values():
        gaps = subtract([[w[0], w[1]]], merged)
        for s, e in gaps:
            mid = (s + e) / 2
            label, best = "outside", -1
            for name, hs, he in spans:
                if hs <= mid <= he and depth[name] > best:
                    label, best = name, depth[name]
            tot[label] = tot.get(label, 0.0) + (e - s)
            cnt[label] = cnt.get(label, 0) + 1
    nd = len(busy)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{k} ({cnt[k] // nd} gaps)", v / nd / 1e9] for k, v in best]
