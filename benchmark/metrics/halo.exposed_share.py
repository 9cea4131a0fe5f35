"""Share of the traced stretch in which a halo transfer op ran on a chip
and no other op did, averaged over the chips (``xtrace.exposed_halo_s``).
Nothing to read where the trace holds no halo op (one chip)."""
import xtrace


def read(ctx):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    if not any(xtrace.is_halo(o) for ops in ctx.trace["devices"].values()
               for o in ops):
        return None
    exposed = xtrace.exposed_halo_s(ctx.trace)
    win = xtrace.window_s(ctx.trace)
    return 100.0 * sum(exposed.values()) / len(exposed) / win
