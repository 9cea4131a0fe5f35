"""Share of the program's whole-run device time spent in its named step
kernels, per chip and averaged over the chips: the leaf ops that are
Pallas kernels named ``advection_<kernel>`` (trace ops
``%advection_<kernel>.<n> custom-call``), over all leaf ops of the
program's run modules (``jit_advection_<path>_run``).  What the rest
holds is the run's own overhead around its step: the per-step copies and
the per-call layout work.  Nothing to read where no run module holds a
named kernel: a path whose step is XLA fusions (boxed), or a program
whose runs carry other names."""
import re

import xtrace

#: module of a whole-run function built through ``traced_jit``
RUN_MODULE = re.compile(r"jit_advection_\w+_run(\(|$)")
#: a named advection Pallas kernel, as ``xtrace.short_name`` writes it
STEP_KERNEL = re.compile(r"%advection_[a-z_]+(\.\d+)? ")


def _run_op(op):
    return bool(RUN_MODULE.match(op[3]))


def read(ctx):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    run = xtrace.per_device(ctx.trace, _run_op, leaf=True)
    step = xtrace.per_device(
        ctx.trace, lambda o: _run_op(o) and bool(STEP_KERNEL.match(o[0])),
        leaf=True)
    shares = []
    for plane, ivs in run.items():
        total, kernel = xtrace.measure(ivs), xtrace.measure(step[plane])
        if total > 0 and kernel > 0:
            shares.append(kernel / total)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
