"""Supersteps of one build, from the program's ``frontier_supersteps``
gauge (the last build of the window; every build grows the same tree)."""

from bench import scopes


def read(ctx):
    c = scopes.counts(ctx)
    return None if c is None else c["frontier_supersteps"]
