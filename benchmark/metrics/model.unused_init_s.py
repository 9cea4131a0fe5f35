"""Seconds the model's set-up spent building run paths that no ``run()``
call took: the program's ``advection.init.<part>`` phases (``dense``;
the gather path's ``tables`` and ``step``; ``boxed``; ``flat``, with the
flat path's table probes inside its own) whose path did not engage, the
engaged paths read from the program's ``fused.runs{model=advection,path}``
counter.  Where a whole-run kernel (dense, fused, boxed, flat) engaged,
the gather path's tables and step count as unused: set-up still reads
them once, for the CFL time step and the first ghost exchange, but no
run does.  Nothing to read where the program has no such phases."""

#: set-up span of each candidate part
PARTS = ("dense", "tables", "step", "boxed", "flat")
#: ``fused.runs`` path label -> the set-up spans that built that path (a
#: uniform grid's model builds the dense path alone, and its ``general``
#: loop, without Pallas, steps the dense bundle's XLA step; any other
#: grid's ``general``/``split`` loop steps the gather path)
BUILT_BY = {"fused": ("dense",), "dense": ("dense",),
            "general": ("dense", "tables", "step"),
            "split": ("dense", "tables", "step"),
            "boxed": ("boxed",), "flat": ("flat",)}


def read(ctx):
    from dccrg_tpu.obs import metrics

    rep = metrics.report()
    built = {p: rep["phases"].get(f"advection.init.{p}") for p in PARTS}
    if not any(built.values()):
        return None
    used = set()
    for key in rep["counters"].get("fused.runs", {}):
        labels = dict(kv.split("=", 1) for kv in key.split(","))
        if labels.get("model") == "advection":
            used.update(BUILT_BY.get(labels.get("path"), ()))
    return sum(rec["total_s"] for p, rec in built.items()
               if rec is not None and p not in used)
