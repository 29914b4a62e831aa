"""Device ms per tree in the fused build's ``frontier.select`` scope, scopes
nested in it included: the selection of open nodes over the node-status
array and the loop's test for open nodes (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_tree(ctx, "frontier.select")
