#!/usr/bin/env python
"""Run one cell of ``BENCHMARK.json`` once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``benchmark/configs/<config>.json``), a
traffic mix (``benchmark/traffic/<traffic>.json``) and a chip count.  The
configuration's ``model`` names its driver (``benchmark/models/<model>.py``),
which builds the system under test through its normal entry points, makes the
data from the seed, and compares sampled calls with the plain reference
(``benchmark/references/``).  Each per-layer metric is read by
``benchmark/metrics/<metric>.py``.  Adding a configuration, a mix or a metric
adds files and entries only.

Order of a run: the persistent compile cache; the system under test and its
data (set-up); a warm-up of the cell's own shapes (set-up); a closed loop of
calls for ``--seconds``, each followed by a scalar read back to the host; the
peak device memory; with ``--trace 1`` the profiled stretch's reduction; the
comparison with the reference; the result line.

With no TPU, too few chips, or a device kind missing from
``benchmark/peaks.json``, it exits non-zero and prints no result.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT))

import xtrace  # noqa: E402


class Refused(SystemExit):
    """The run cannot measure this cell here: exit non-zero, no result."""

    def __init__(self, why: str):
        print(f"refused: {why}", file=sys.stderr, flush=True)
        super().__init__(3)


def _load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    """(bench, cell, config, traffic) of one workload, all from files."""
    bench = _load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load_json(ROOT / entry["file"])
    traffic = _load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    if traffic["kind"] != "closed_loop":
        raise Refused(f"traffic {cell['traffic']!r}: the harness drives "
                      f"closed loops only, not {traffic['kind']!r}")
    return bench, cell, cfg, traffic


class Clock:
    """Named host-clock parts of the set-up.  Each part ends when the
    device work it issued has ended (every live array is ready), so an
    asynchronous upload is counted in the part that issued it."""

    def __init__(self):
        self.parts = {}

    @contextlib.contextmanager
    def __call__(self, name):
        import jax

        t = time.perf_counter()
        try:
            yield
            jax.block_until_ready(jax.live_arrays())
        finally:
            self.parts[name] = self.parts.get(name, 0.0) + (
                time.perf_counter() - t)


class CompileCounter:
    """XLA compilations and persistent-cache hits and misses, from
    ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def executables(self) -> int:
        """Executables made so far: compiled, or loaded from the cache."""
        return self.compiles + self.hits


def devices(chips: int, require_tpu: bool, peaks: dict):
    """The chips this cell runs on, refused unless they can be measured."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    kind = devs[0].device_kind
    if require_tpu and platform != "tpu":
        raise Refused(f"no TPU: JAX found {platform} ({kind})")
    if len(devs) < chips:
        raise Refused(f"{len(devs)} {platform} device(s), the cell asks "
                      f"for {chips}")
    if require_tpu and kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in benchmark/peaks.json")
    return devs[:chips], platform, kind


class Reservoir:
    """Which calls of the window are kept for the comparison: ``k`` of
    them, uniformly over all calls, drawn from the seed before each call
    is issued."""

    def __init__(self, k: int, seed: int):
        import numpy as np

        self.k = k
        self.rng = np.random.default_rng([int(seed), 1])
        self.seen = 0

    def slot(self):
        i = self.seen
        self.seen += 1
        if i < self.k:
            return i
        j = int(self.rng.integers(0, i + 1))
        return j if j < self.k else None


def window(sim, seconds: float, traffic: dict, seed: int, trace_dir):
    """The closed loop.  Returns (calls, window seconds, samples, traced
    calls, host timings): the timings are the medians of each call's
    dispatch and readback and the calls in each half of the window, for
    the run's log."""
    import statistics

    import jax

    ann = jax.profiler.TraceAnnotation
    res = Reservoir(int(traffic["samples"]), seed)
    samples = [None] * res.k
    lead = max(0.0, (seconds - traffic["trace_seconds"]) / 2)
    tracing, traced, first = False, 0, 0
    calls = 0
    disp, wait, ends = [], [], []
    t0 = time.perf_counter()
    now = t0
    while True:
        if trace_dir is not None and not tracing and traced == 0 \
                and now - t0 >= lead:
            jax.profiler.start_trace(trace_dir)
            tracing, first = True, calls
        with ann("loop"):
            slot = res.slot()
            x_in = sim.snapshot() if slot is not None else None
            t = time.perf_counter()
            with ann("dispatch"):
                out = sim.call()
            t1 = time.perf_counter()
            if slot is not None:
                samples[slot] = (x_in, sim.snapshot())
            with ann("readback"):
                float(out)
        calls += 1
        now = time.perf_counter()
        disp.append(t1 - t)
        wait.append(now - t1)
        ends.append(now - t0)
        if tracing and now - t0 >= lead + traffic["trace_seconds"]:
            jax.profiler.stop_trace()
            tracing, traced = False, calls - first
        if now - t0 >= seconds:
            break
    if tracing:
        jax.profiler.stop_trace()
        traced = calls - first
    half = sum(1 for e in ends if e < (now - t0) / 2)
    timing = {"dispatch_ms_p50": 1e3 * statistics.median(disp),
              "readback_ms_p50": 1e3 * statistics.median(wait),
              "calls_by_half": [half, calls - half]}
    return (calls, now - t0, [s for s in samples if s is not None], traced,
            timing)


def peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Context:
    """What a per-layer metric's ``read(ctx)`` may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True):
    """One run of one cell; returns the result dict, whose last key is
    ``checks`` (each number compared beside its limit; ``failed`` counts
    those over it)."""
    import jax

    bench, cell, cfg, traffic = load_cell(workload)
    peaks = _load_json(HERE / "peaks.json")
    devs, platform, kind = devices(int(cell["chips"]), require_tpu, peaks)

    from dccrg_tpu.parallel.exec_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    counter = CompileCounter()
    clock = Clock()
    clock.parts["init"] = time.perf_counter() - _T0

    driver = _module("models", cfg["model"])
    sim = driver.Sim(cfg, traffic, seed, len(devs), clock)
    sim.check_setup()
    with clock("warmup"):
        for _ in range(2):
            out = sim.call()
        jax.block_until_ready(sim.snapshot())
        float(out)
    setup_s = time.perf_counter() - _T0
    info = {"workload": workload, "seed": seed, "platform": platform,
            "device_kind": kind, "device_count": len(devs),
            "setup_parts_s": clock.parts, "cache_dir": cache_dir,
            "setup_cache": {"hits": counter.hits, "misses": counter.misses,
                            "compiles": counter.compiles}, **sim.info()}
    print("setup " + json.dumps(info), file=sys.stderr, flush=True)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    made = counter.executables()
    calls, window_s, samples, traced, timing = window(
        sim, seconds, traffic, seed, trace_dir)
    compiles = counter.executables() - made
    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": peak_bytes(devs)}
    reduced = None
    if trace:
        path = xtrace.find_xplane(trace_dir)
        reduced = xtrace.load(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced and reduced["devices"]:
            device["busy_s"] = xtrace.busy_s(reduced)
            device["window_s"] = xtrace.window_s(reduced)

    sim.free()
    checks = sim.compare(samples)
    # a NaN reading is over its limit too
    failed = sum(1 for v, lim in checks.values() if not v <= lim)

    ctx = Context(cell=cell, config=cfg, traffic=traffic, sim=sim,
                  setup=clock.parts, setup_s=setup_s, calls=calls,
                  window_s=window_s, compiles=compiles, trace=reduced,
                  traced_calls=traced, peaks=peaks.get(kind), device=device)
    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = _module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"cell_updates_per_s": calls * sim.updates_per_call / window_s,
               "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": failed == 0, "attempted": calls, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduced:
        result["breakdown"] = {"device_ops": xtrace.top_ops(reduced),
                               "idle_gaps": xtrace.idle_gaps(reduced)}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print("window " + json.dumps({"calls": calls, "window_s": window_s,
                                  "samples": len(samples),
                                  "traced_calls": traced,
                                  "window_compiles": compiles, **timing}),
          file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    result = run(a.workload, a.seed, a.seconds, bool(a.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
