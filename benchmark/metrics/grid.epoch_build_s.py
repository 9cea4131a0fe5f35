"""Seconds of the program's ``epoch.build`` phase, from its registry's
phase table (``dccrg_tpu.obs.metrics``): every full neighbour-list and
table build, the one inside ``Grid.initialize`` and the one after a
refinement.  Read after the window; the cells rebuild nothing there, so
it is the set-up's total.  ``grid.build_s`` is the harness's clock around
the whole grid build, this the part of it the epoch build takes."""


def read(ctx):
    from dccrg_tpu.obs import metrics

    rec = metrics.report()["phases"].get("epoch.build")
    return None if rec is None else rec["total_s"]
