"""Frontier slot fill, in %: the open nodes a build processed over its
supersteps times the frontier's slots, from the program's
``frontier_open_nodes``, ``frontier_supersteps`` and ``frontier_slots``
gauges.  Low fill means supersteps whose fixed cost serves few nodes."""

from bench import scopes


def read(ctx):
    c = scopes.counts(ctx)
    if c is None or not c["frontier_supersteps"]:
        return None
    return 100.0 * c["frontier_open_nodes"] / (
        c["frontier_supersteps"] * c["frontier_slots"])
