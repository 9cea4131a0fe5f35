#!/usr/bin/env python
"""Fleet trace: unify per-process timeline exports into one trace.

    python tools/trace_report.py --fleet T1 T2 .. [--merged-out FILE] [--json]

Every source is a Chrome trace-event export of one process's
``obs.timeline`` (``obs.export_chrome_trace``); each carries the
wall-clock anchor of its origin, and ``obs.merge_chrome_traces`` shifts
them onto the earliest one.  The merged trace is validated
(``obs.validate_merged_trace``); the exit code is 1 when it is not valid.

Device time is read from a ``jax.profiler`` capture instead, where the
program's phase spans sit on the profiler's host plane on the device
ops' clock (``benchmark/xtrace.py``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--fleet", nargs="+", required=True, metavar="TRACE",
                    help="per-process timeline exports to merge onto "
                         "their shared epoch-zero")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable record (CI mode)")
    ap.add_argument("--merged-out", default=None, metavar="FILE",
                    help="also write the fleet trace here")
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from dccrg_tpu.obs.events import (merge_chrome_traces,
                                      validate_merged_trace)

    fleet = merge_chrome_traces(args.fleet, out_path=args.merged_out)
    failures = validate_merged_trace(fleet)
    rec = {
        "sources": fleet["otherData"]["sources"],
        "events": len(fleet["traceEvents"]),
        "origin_unix_s": fleet["otherData"]["origin_unix_s"],
        "valid": not failures,
        "failures": failures,
    }
    if args.json:
        print(json.dumps(rec, indent=1))
    else:
        print(f"fleet trace: {rec['events']} events from "
              f"{len(rec['sources'])} processes on epoch-zero "
              f"{rec['origin_unix_s']:.6f}"
              + (f" -> {args.merged_out}" if args.merged_out else ""))
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
