"""Device ms per tree in the fused build's ``frontier.select`` scope, scopes
nested in it included: the frontier as the open id range ``[open_nodes,
n_nodes)`` and the loop's test ``open_nodes < n_nodes``
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_tree(ctx, "frontier.select")
