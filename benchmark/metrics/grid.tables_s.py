"""Seconds of the program's ``epoch.tables`` phase, from its registry's
phase table (``dccrg_tpu.obs.metrics``): the per-cell tables of every
epoch built, at once or on first read (row layout, neighbour lists, gather
tables and halo schedules).  0 where every epoch was built without them
(the ``epoch.tables_deferred`` counter rose and the phase never ran), as on
a grid the dense path runs.  Read after the window, so a build that a run
forced counts too.  Nothing to read where the program has neither."""


def read(ctx):
    from dccrg_tpu.obs import metrics

    rec = metrics.report()["phases"].get("epoch.tables")
    if rec is not None:
        return rec["total_s"]
    if metrics.counter_value("epoch.tables_deferred") > 0:
        return 0.0
    return None
