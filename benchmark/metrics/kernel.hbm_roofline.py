"""Useful HBM bytes of the traced calls over the device time of the
program's step ops, as a share of the chip's HBM peak, per chip and
averaged over the chips.

Useful bytes are the configuration's ``useful_bytes_per_update`` times the
cell updates of the traced calls (for advection 5 float32 arrays per cell
per step: density, vx, vy, vz read, density written), the same whatever
kernel the dispatch engaged.  Step ops are the device ops of every module
but the harness's own (``xtrace.is_step``)."""
import xtrace


def read(ctx):
    if not ctx.trace or not ctx.trace["devices"] or not ctx.traced_calls:
        return None
    step = xtrace.step_s(ctx.trace)
    useful = (ctx.sim.updates_per_call * ctx.traced_calls
              * ctx.sim.bytes_per_update / len(step))
    peak = ctx.peaks["hbm_bytes_per_s"]
    shares = [useful / s / peak for s in step.values() if s > 0]
    if len(shares) != len(step):
        return None
    return 100.0 * sum(shares) / len(shares)
