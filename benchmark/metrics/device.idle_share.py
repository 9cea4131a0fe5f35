"""Share of the traced stretch in which no op ran on the device: one
minus the union of op intervals over the stretch, averaged over the
chips (``xtrace.busy_s`` / ``xtrace.window_s``)."""
import xtrace


def read(ctx):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    busy, win = xtrace.busy_s(ctx.trace), xtrace.window_s(ctx.trace)
    if not busy or not win:
        return None
    return 100.0 * (1.0 - busy / win)
