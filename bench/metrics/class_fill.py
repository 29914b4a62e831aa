"""The histogram's class fill, in %: the (slot, class) columns that held a
case, summed over supersteps, over the columns the histogram computed,
which are all K x C of every superstep: the program's
``frontier_present_classes`` over ``frontier_supersteps`` x
``frontier_slots`` x ``frontier_classes``.  Each grown node takes a slot
once, and its columns that held a case are the classes in its counts.  Low
fill means per-class passes over classes a slot does not hold; a program
without the gauges gives ``None`` and a note."""

from repro.obs import metrics

GAUGES = ("frontier_present_classes", "frontier_supersteps",
          "frontier_slots", "frontier_classes")


def read(ctx):
    got = {}
    for name in GAUGES:
        gauge = metrics.REGISTRY.get(name)
        if gauge is None or not gauge.labels_of():
            ctx["notes"].append(f"class_fill: the program's registry has no "
                                f"{name}")
            return None
        got[name] = gauge.value()
    grid = (got["frontier_supersteps"] * got["frontier_slots"]
            * got["frontier_classes"])
    if not grid:
        return None
    return 100.0 * got["frontier_present_classes"] / grid
