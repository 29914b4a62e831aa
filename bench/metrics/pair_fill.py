"""splitAtt's pair fill, in %: the (slot, attribute) pairs C4.5 scored,
summed over supersteps, over the pairs the histogram and the gain pass
computed, which are all K x A of every superstep: the program's
``frontier_tested_pairs`` over ``frontier_supersteps`` x
``frontier_slots`` x ``frontier_attrs``.  A pair is scored where its slot
holds an open node that is not stopped first (pure, small or at the depth
limit) and its attribute is still active there.  The splitAtt analogue of
``compact_fill``; a program without the gauges gives ``None`` and a
note."""

from repro.obs import metrics

GAUGES = ("frontier_tested_pairs", "frontier_supersteps", "frontier_slots",
          "frontier_attrs")


def read(ctx):
    got = {}
    for name in GAUGES:
        gauge = metrics.REGISTRY.get(name)
        if gauge is None or not gauge.labels_of():
            ctx["notes"].append(f"pair_fill: the program's registry has no "
                                f"{name}")
            return None
        got[name] = gauge.value()
    grid = (got["frontier_supersteps"] * got["frontier_slots"]
            * got["frontier_attrs"])
    if not grid:
        return None
    return 100.0 * got["frontier_tested_pairs"] / grid
